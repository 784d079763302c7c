"""The comparison that decides ``correct``.

After the window has closed, a sample of the requests it finished --
drawn from the seed, the longest always among them -- is run through the
configuration's plain reference (``bench/refs/<reference>.py``),
teacher-forced on each prompt followed by the tokens the program served.
At every position that produced a served token, the gap is how far the
reference's logit of that token lies below the reference's best logit
there.  Served greedily and correctly, a token's gap is 0 or a rounding
step; a wrong weight, page, mask or token makes it as large as the
spread of the logits.

The number compared, against the limit the configuration file gives
under "correct":

* ``wide_gap_share`` -- the share of the sampled served tokens whose gap
  exceeds the configuration's ``wide_gap`` times the standard deviation
  of the reference's logits (their spread, so one threshold serves every
  width).

The widest gap (``gap_max``) is printed beside it and not compared.  The
MLP applies sign() to its inputs, so a rounding difference near zero --
in the order of a sum inside attention, say -- flips a sign and moves
that position's logits: a correct bfloat16 program, and a float32 one,
both differ from the reference by a gap of a few tenths at a few
positions in a hundred, and the widest of them is as large as what the
control (float8) gives.  The share of wide gaps separates the two
(``PERF.md``, "How correct is decided").
"""

from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

REFS = pathlib.Path(__file__).resolve().parent / "refs"
SAMPLE_TOKENS = 400          # served tokens compared per run, at least
SAMPLE_MAX_REQUESTS = 24


def load_reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{name}", REFS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(served: list[tuple[np.ndarray, list[int]]], seed: int
           ) -> list[int]:
    """Indices of the requests to compare: the longest (prompt plus served
    tokens), then others in an order drawn from the seed, until
    ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX_REQUESTS`` requests."""
    if not served:
        return []
    sizes = [len(p) + len(g) for p, g in served]
    longest = int(np.argmax(sizes))
    rest = [i for i in np.random.default_rng([seed, 7]).permutation(
        len(served)) if i != longest]
    picked, tokens = [longest], len(served[longest][1])
    for i in rest:
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        picked.append(int(i))
        tokens += len(served[i][1])
    return picked


def teacher_forced(served: list[tuple[np.ndarray, list[int]]]):
    """(token sequences, rows): each prompt with its served tokens but the
    last, and the positions whose logits chose the served tokens."""
    seqs, rows = [], []
    for prompt, gen in served:
        seqs.append(np.concatenate([prompt, np.asarray(gen[:-1], np.int32)]
                                   ).astype(np.int32))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(gen)))
    return seqs, rows


def gaps(ref_logits: list[np.ndarray], chosen: list[np.ndarray]
         ) -> np.ndarray:
    """Reference best minus reference logit of each chosen token."""
    out = [lg.max(axis=-1) - lg[np.arange(len(c)), c]
           for lg, c in zip(ref_logits, chosen)]
    return np.concatenate(out) if out else np.zeros(0)


def spread(ref_logits: list[np.ndarray]) -> float:
    """Mean over the compared positions of the reference logits' standard
    deviation."""
    return float(np.mean(np.concatenate([lg.std(axis=-1)
                                         for lg in ref_logits])))


def summary(g: np.ndarray, wide_gap: float, scale: float) -> dict:
    """The number compared, and a few printed beside it, of the gaps
    ``g`` of the compared tokens; no token compared reads as all wide."""
    if not len(g):
        return {"wide_gap_share": 1.0, "gap_max": float("inf"), "tokens": 0}
    return {"wide_gap_share": float((g > wide_gap * scale).mean()),
            "gap_max": float(g.max()),
            "gap_mean": float(g.mean()),
            "logit_std": scale,
            "tokens": int(len(g))}


def compare(ref, m: dict, params, served, length: int, n_rows: int,
            wide_gap: float) -> dict:
    """The numbers for the served requests ``served`` [(prompt, served
    tokens)], against the reference in the configuration's own dtype;
    ``length`` and ``n_rows`` bound every sequence and its served tokens,
    so the reference compiles one shape."""
    seqs, rows = teacher_forced(served)
    logits = ref.logits_at(m, params, seqs, rows, "bfloat16", length,
                           n_rows)
    g = gaps(logits, [np.asarray(gen, np.int64) for _, gen in served])
    return summary(g, wide_gap, spread(logits))


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number within its
    limit; a missing limit or a missing number is not correct."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        out[name] = {"value": v, "limit": limit}
        ok &= v is not None and limit is not None and v <= limit
    return bool(ok), out
