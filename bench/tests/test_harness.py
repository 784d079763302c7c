"""The harness end to end at a small size on the CPU, past its look for a
chip: sound runs are correct, and runs with the timed path broken
underneath -- a token altered where it is produced, a step that leaves
its KV state unchanged, half of the slots left out of a step -- are not.
Also: with no TPU the command exits non-zero and prints no result."""

import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

import control
import run

BENCH = pathlib.Path(__file__).resolve().parents[1]
SMALL = {"model": dict(num_layers=2, scan_repeats=2, d_model=256,
                       num_heads=4, num_kv_heads=2, head_dim=64, d_ff=512,
                       vocab_size=512),
         "engine": dict(prefill_chunk=16, prefill_budget=64, min_slot_len=0),
         "mix": dict(slots=4, round_requests=6,
                     prompt_len=dict(dist="exponential", mean=6, min=1,
                                     max=4096),
                     output_len=dict(dist="exponential", mean=5, min=1,
                                     max=4096))}
CELLS = [w["name"] for w in run.load_benchmark()["workloads"]]


def small_run(name, seed=11):
    return run.run_cell(run.load_cell(name), seed, 0.5, False,
                        overrides=SMALL)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = small_run(name)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"


def _altered_token(inner):
    """Every token produced after set-up is the next id after the one
    the model chose."""
    calls = []

    def step(self, params, kcache, table, toks, poss, q_lens, **kw):
        logits, cache = inner(self, params, kcache, table, toks, poss,
                              q_lens, **kw)
        calls.append(1)
        if len(calls) > 4:           # the warm-up round takes 3 steps
            rows = jnp.maximum(q_lens - 1, 0)
            lanes = jnp.arange(logits.shape[0])
            bad = (jnp.argmax(logits[lanes, rows], axis=-1) + 1) \
                % logits.shape[-1]
            logits = logits.at[lanes, rows, bad].set(1e9)
        return logits, cache
    return step


def _state_unchanged(inner):
    def step(self, params, kcache, table, toks, poss, q_lens, **kw):
        kept = jax_tree_copy(kcache)
        logits, _ = inner(self, params, kcache, table, toks, poss, q_lens,
                          **kw)
        return logits, kept
    return step


def _half_the_slots(inner):
    def step(self, params, kcache, table, toks, poss, q_lens, **kw):
        half = q_lens.at[1::2].set(0)
        return inner(self, params, kcache, table, toks, poss, half, **kw)
    return step


def jax_tree_copy(tree):
    import jax
    return jax.tree_util.tree_map(jnp.copy, tree)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_the_slots])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.runtime.scheduler import ServeEngine
    monkeypatch.setattr(ServeEngine, "mixed_step",
                        fault(ServeEngine.mixed_step))
    res = small_run(name)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = run.load_cell(name)
    cell["config"]["model"].update(SMALL["model"])
    cell["mix"].update(SMALL["mix"])
    for seed in (1, 2, 3):
        out = control.control_seed(cell, seed)
        assert out["program_weights_match"]
        ok, _ = run.correct.judge(out["float8"],
                                  cell["config"]["correct"]["limits"])
        assert not ok, out["float8"]


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "phi3m-2L.decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
