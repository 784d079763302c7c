"""The one traffic generator: lengths from a mix's distributions, the same
work for every seed, token ids from the seed."""

import pytest

import run
import traffic

EXP = {"dist": "exponential", "mean": 58.45, "min": 1, "max": 4096}
MIX = {"round_requests": 128, "shared_prefix_len": 0,
       "prompt_len": {"dist": "exponential", "mean": 19.31, "min": 1,
                      "max": 4096},
       "output_len": EXP}


@pytest.mark.parametrize("dist", [EXP, {**EXP, "mean": 19.31},
                                  {**EXP, "min": 16, "max": 160}])
def test_quantile_lengths_keep_the_mean_and_the_clips(dist):
    n = traffic.quantile_lengths(dist, 512)
    assert n.min() >= dist["min"] and n.max() <= dist["max"]
    if dist["max"] > 1000:
        assert n.mean() == pytest.approx(dist["mean"], rel=0.02)


def test_unknown_family_is_refused():
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "zipf", "min": 1, "max": 9}, 4)


def test_cache_bounds_are_the_rounds_longest():
    assert traffic.max_output_len(MIX) == 324
    assert traffic.max_cache_len(MIX) == 107 + 324
    assert traffic.slot_length(MIX, 64, 16) == 432
    assert traffic.slot_length({**MIX, "round_requests": 2}, 16, 16) == 112
    assert traffic.slot_length({**MIX, "round_requests": 2}, 128, 16) == 144


@pytest.mark.parametrize("index", [0, 1, 5])
def test_every_seed_serves_the_same_lengths(index):
    a = traffic.make_round(MIX, 32064, 2147483921, index)
    b = traffic.make_round(MIX, 32064, 7, index)
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert any((p != q).any() for (p, _), (q, _) in zip(a, b) if len(p) > 3)
    assert all(0 <= p.min() and p.max() < 32064 for p, _ in a)


def test_engine_minimum_slot_length():
    eng = {"prefill_chunk": 64, "kv_page_size": 16}
    assert run.slot_length(eng, MIX) == 432
    assert run.slot_length({**eng, "min_slot_len": 512}, MIX) == 512
    assert run.slot_length({**eng, "min_slot_len": 256}, MIX) == 432
