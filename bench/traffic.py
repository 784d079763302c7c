"""The one traffic generator: a mix file under ``bench/mixes`` in, seeded
requests out.

A mix is an offline batch job served in rounds.  Each round submits
``round_requests`` requests at once and is served to completion.  Every
round serves the same lengths -- the quantiles of the mix's length
distributions at ``(i + 0.5) / round_requests`` -- in an order fixed by
the round's index alone, so every seed does the same work; the seed draws
the token ids.  (The order of one round decides how its requests pack
into the slots and how long its tail runs, so an order drawn from the
seed would change the work from seed to seed.)  Nothing here imports the
program.
"""

from __future__ import annotations

import json
import math
import pathlib

import numpy as np

MIXES = pathlib.Path(__file__).resolve().parent / "mixes"
ORDER_SEED = 0x5EED      # the rounds' length orders, the same for every seed


def load_mix(name: str) -> dict:
    with open(MIXES / f"{name}.json") as f:
        mix = json.load(f)
    if mix["name"] != name:
        raise ValueError(f"mix file {name}.json names itself {mix['name']!r}")
    return mix


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 0.5) / n, clipped
    to [min, max].  The one family is "exponential" (mean)."""
    if dist["dist"] != "exponential":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    raw = [-dist["mean"] * math.log1p(-(i + 0.5) / n) for i in range(n)]
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


def max_output_len(mix: dict) -> int:
    """Longest output a round of the mix asks for."""
    return int(quantile_lengths(mix["output_len"], mix["round_requests"]).max())


def max_cache_len(mix: dict) -> int:
    """Longest prompt plus longest output a round of the mix sends."""
    n = mix["round_requests"]
    return int(quantile_lengths(mix["prompt_len"], n).max()) \
        + max_output_len(mix)


def make_round(mix: dict, vocab: int, seed: int, index: int
               ) -> list[tuple[np.ndarray, int]]:
    """Timed round ``index`` (from 0) of the mix under ``seed`` ->
    [(prompt ids, output length)]."""
    n = mix["round_requests"]
    order = np.random.default_rng([ORDER_SEED, index])
    prompts = order.permutation(quantile_lengths(mix["prompt_len"], n))
    outputs = order.permutation(quantile_lengths(mix["output_len"], n))
    rng = np.random.default_rng([seed, index + 1])
    shared = rng.integers(0, vocab, mix["shared_prefix_len"])
    return [(np.concatenate([shared, rng.integers(0, vocab, p - len(shared))]
                            ).astype(np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def warmup_round(mix: dict, vocab: int, seed: int, chunk: int
                 ) -> list[tuple[np.ndarray, int]]:
    """The smallest round that compiles every step shape of the timed
    rounds: one prompt longer than a chunk (chunk ticks, then a partial
    chunk) and two outputs (a decode tick)."""
    rng = np.random.default_rng([seed, 0])
    return [(rng.integers(0, vocab, chunk + 1).astype(np.int32), 2)]


def slot_length(mix: dict, chunk: int, page: int) -> int:
    """Cache rows per slot: room for the longest request of a timed round
    and for the warm-up round's, in whole pages."""
    need = max(max_cache_len(mix), chunk + 1 + 2)
    return -(-need // page) * page
