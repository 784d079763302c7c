"""The plain reference against the program at a small size on the CPU:
it draws the program's weights bit for bit from the seed, binarises the
MLP as the weight store serves it, and agrees with a served run."""

import functools

import jax
import numpy as np
import pytest

import correct
import run

SMALL = dict(num_layers=2, scan_repeats=2, d_model=64, num_heads=4,
             num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256)
CELLS = [w["name"] for w in run.load_benchmark()["workloads"]]


def small_config(cell_name):
    cell = run.load_cell(cell_name)
    model = {**cell["config"]["model"], **SMALL}
    return cell, model, run.program_config(cell["config"], model)


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("seed", [0, 2**31 + 17, 2**40 + 3])
def test_weights_match_the_programs_bit_for_bit(cell_name, seed):
    from repro.models.api import get_model
    cell, model, cfg = small_config(cell_name)
    key = run.weight_key(seed)
    ref = correct.load_reference(cell["config"]["reference"])
    mine = ref.init_params(model, key)
    prog = jax.jit(functools.partial(get_model(cfg).init_params, cfg))(key)
    blk = prog["scan"]["b0"]
    pairs = [(prog["embed"], mine["embed"]),
             (prog["lm_head"], mine["lm_head"])]
    pairs += [(blk["attn"][k], mine["layers"][k])
              for k in ("wq", "wk", "wv", "wo")]
    pairs += [(blk["mlp"][k], mine["layers"][k])
              for k in ("gate", "up", "down")]
    for a, b in pairs:
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool((a == b).all())


def test_binarised_mlp_matches_the_weight_store():
    from repro.runtime.weight_store import WeightStore
    cell, model, _ = small_config("phi3m-2L.decode")
    ref = correct.load_reference(cell["config"]["reference"])
    w = ref.init_params(model, run.weight_key(3))["layers"]["gate"]
    store = WeightStore()
    store.register_model("m", {"mlp": {"gate": np.asarray(w)}})
    served = store.materialize("m")["mlp"]["gate"]
    for r in range(w.shape[0]):
        assert bool((served[r] == ref.binarize(w[r])).all())
