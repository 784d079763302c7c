"""Hardware-shaped kernel launch: tiled pools, multi-page grid steps,
in-kernel codec decode, and the launch-shape autotuner.

The tiling-equivalence contract: a pool padded toward the TPU's
(8, 128) sublane/lane register tiles, walked ``pages_per_step`` pages
per grid step, must stay *token-identical* to the identity layout —
padding is masked inside the online softmax, zero feature columns drop
out of every dot product, and regrouped page DMAs only reassociate the
online-softmax accumulation (the same tolerance regime as the
kernel-vs-dense-oracle tests).  At ``pages_per_step=1`` the padded
kernel output is **bit-identical** to the unpadded one; the serve-level
suites assert token identity across the full launch-shape grid.

Also here: the in-kernel half-codebook gather vs the full table
(bit-identity regression), the ``kernel_qblock_rounded`` telemetry
for gcd-rounded q_blocks, and ``tune_kernel`` unit tests.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import repro.runtime.scheduler as sched_mod
from repro.kernels import kv_codec
from repro.kernels.paged_attention import (effective_q_block,
                                           paged_mixed_attention)
from repro.models.api import (TILE_LANE, TILE_SUBLANE, padded_page_dims,
                              round_up)
from repro.runtime import Scheduler, tune_kernel
from repro.runtime.autotune import _KERNEL_TUNE_CACHE
from tests.harness import (MIXED, assert_tokens_identical, make_engine,
                           mixed_requests, run_trace)
from tests.test_paged_attention import random_paged_cache

pytestmark = pytest.mark.pallas


def pad_pool(pool, rows, feat_last, fill=0):
    """Zero-pad a (n_pages, page, KH, D) pool to (n_pages, rows, KH,
    feat_last) — the SlotPool hardware-tiled layout."""
    p = np.full((pool.shape[0], rows, *pool.shape[2:-1], feat_last),
                fill, pool.dtype)
    p[:, :pool.shape[1], ..., :pool.shape[-1]] = pool
    return p


class TestPaddedPageDims:
    def test_identity_when_off(self):
        assert padded_page_dims((1, 4, 2, 16), 1, 4, False) == (4, (2, 16))

    def test_pads_sublane_and_lane(self):
        rows, feat = padded_page_dims((1, 4, 2, 16), 1, 4, True)
        assert rows == TILE_SUBLANE and feat == (2, TILE_LANE)

    def test_aligned_dims_untouched(self):
        rows, feat = padded_page_dims((1, 16, 2, 256), 1, 16, True)
        assert rows == 16 and feat == (2, 256)

    def test_featureless_leaf(self):
        assert padded_page_dims((1, 3), 1, 3, True) == (TILE_SUBLANE, ())


class TestTilingEquivalenceKernel:
    """Padded pools vs the identity layout at the kernel level."""

    @pytest.mark.parametrize("page,pages", [(1, 8), (4, 5), (5, 3)])
    @pytest.mark.parametrize("pps", [1, 2, 4])
    def test_padded_matches_unpadded(self, page, pages, pps):
        rng = np.random.default_rng(page * 10 + pps)
        s, kh, d, dv = 3, 2, 16, 16
        q_lens = np.array([2, 4, 1], np.int32)
        k, v, table, lengths = random_paged_cache(rng, s, kh, d, dv, page,
                                                  pages)
        # the kernel contract: q_lens[s] new tokens are part of
        # lengths[s]; rows past it are finite garbage the caller ignores
        # (and garbage legitimately depends on the page grouping)
        lengths = np.maximum(lengths, q_lens)
        q = rng.normal(size=(s, 4, 4, d)).astype(np.float32)
        base = np.asarray(paged_mixed_attention(
            q, k, v, table, lengths, q_lens, interpret=True))
        rows, feat = round_up(page, TILE_SUBLANE), round_up(d, TILE_LANE)
        out = np.asarray(paged_mixed_attention(
            q, pad_pool(k, rows, feat), pad_pool(v, rows, feat),
            table, lengths, q_lens, page_size=page, pages_per_step=pps,
            interpret=True))[..., :dv]
        for i in range(s):
            got, want = out[i, :q_lens[i]], base[i, :q_lens[i]]
            if pps == 1:
                # row/lane padding alone is bit-exact: padded rows score
                # NEG_INF (exp underflows to 0.0) and zero columns add
                # nothing to any f32 dot
                np.testing.assert_array_equal(got, want)
            else:
                # multi-page steps regroup the online softmax — same
                # tolerance regime as the kernel-vs-dense oracle
                np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("pps", [2, 4])
    def test_non_divisor_page_count(self, pps):
        """Logical page counts the group width does not divide pad the
        table with dummy-page entries — all masked, tokens unchanged."""
        rng = np.random.default_rng(3)
        s, kh, d, dv, page, pages = 2, 2, 8, 8, 4, 3   # 3 % pps != 0
        q_lens = np.array([3, 1], np.int32)
        k, v, table, lengths = random_paged_cache(rng, s, kh, d, dv, page,
                                                  pages)
        lengths = np.maximum(lengths, q_lens)
        q = rng.normal(size=(s, 3, 4, d)).astype(np.float32)
        base = np.asarray(paged_mixed_attention(
            q, k, v, table, lengths, q_lens, interpret=True))
        out = np.asarray(paged_mixed_attention(
            q, k, v, table, lengths, q_lens, pages_per_step=pps,
            interpret=True))
        for i in range(s):
            np.testing.assert_allclose(out[i, :q_lens[i]],
                                       base[i, :q_lens[i]],
                                       rtol=2e-6, atol=2e-6)

    def test_poisoned_dummy_sink_under_padding(self):
        """Garbage in page 0 — including its padded rows — must never
        reach any output: every reference to it is masked."""
        rng = np.random.default_rng(4)
        s, kh, d, dv, page, pages = 2, 2, 8, 8, 4, 4
        q_lens = np.array([2, 3], np.int32)
        k, v, table, lengths = random_paged_cache(rng, s, kh, d, dv, page,
                                                  pages)
        lengths = np.maximum(lengths, q_lens)
        rows, feat = TILE_SUBLANE, round_up(d, TILE_LANE)
        kp, vp = pad_pool(k, rows, feat), pad_pool(v, rows, feat)
        q = rng.normal(size=(s, 3, 4, d)).astype(np.float32)
        clean = np.asarray(paged_mixed_attention(
            q, kp, vp, table, lengths, q_lens, page_size=page,
            pages_per_step=2, interpret=True))
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[0], vp2[0] = 1e9, 1e9
        poisoned = np.asarray(paged_mixed_attention(
            q, kp2, vp2, table, lengths, q_lens, page_size=page,
            pages_per_step=2, interpret=True))
        for i in range(s):
            np.testing.assert_array_equal(poisoned[i, :q_lens[i]],
                                          clean[i, :q_lens[i]])


class TestDequantGather:
    """The in-kernel half-codebook lane gather vs the full table."""

    def test_gather_bitwise_matches_onehot(self):
        """The codec kernel equals the fp kernel over the pool decoded
        through the full 256-entry codebook, bit for bit: the kernel's
        magnitude lookup in the non-negative half, negated for negative
        codes, is the same table entry."""
        rng = np.random.default_rng(5)
        s, kh, d, dv, page, pages = 3, 2, 16, 16, 4, 4
        q_lens = np.array([2, 4, 1], np.int32)
        k, v, table, lengths = random_paged_cache(rng, s, kh, d, dv, page,
                                                  pages)
        lengths = np.maximum(lengths, q_lens)
        ck, ks = kv_codec.encode(jnp.asarray(k), axes=(-2, -1))
        cv, vs = kv_codec.encode(jnp.asarray(v), axes=(-2, -1))
        q = rng.normal(size=(s, 4, 4, d)).astype(np.float32)
        cb = np.asarray(kv_codec.codebook())

        def table_decode(c, sc):
            return cb[np.asarray(c, np.int32) + kv_codec.ZERO_CODE] \
                * np.asarray(sc)[:, :, None, None]

        a = np.asarray(paged_mixed_attention(
            q, ck, cv, table, lengths, q_lens, k_scales=ks, v_scales=vs,
            interpret=True))
        b = np.asarray(paged_mixed_attention(
            q, table_decode(ck, ks), table_decode(cv, vs), table, lengths,
            q_lens, interpret=True))
        np.testing.assert_array_equal(a, b)

    def test_codec_padded_pool_matches_unpadded(self):
        """int8 code pools padded with zero codes decode the padding to
        exactly 0.0 (zero-centred codebook), so the padded codec kernel
        is bit-identical at pps=1."""
        rng = np.random.default_rng(6)
        s, kh, d, dv, page, pages = 2, 2, 16, 16, 4, 4
        q_lens = np.array([3, 2], np.int32)
        k, v, table, lengths = random_paged_cache(rng, s, kh, d, dv, page,
                                                  pages)
        lengths = np.maximum(lengths, q_lens)
        ck, ks = kv_codec.encode(jnp.asarray(k), axes=(-2, -1))
        cv, vs = kv_codec.encode(jnp.asarray(v), axes=(-2, -1))
        q = rng.normal(size=(s, 3, 4, d)).astype(np.float32)
        base = np.asarray(paged_mixed_attention(
            q, ck, cv, table, lengths, q_lens, k_scales=ks, v_scales=vs,
            interpret=True))
        rows, feat = TILE_SUBLANE, round_up(d, TILE_LANE)
        pad_s = np.zeros((ks.shape[0], rows), np.float32)
        pad_s[:, :page] = np.asarray(ks)
        pad_vs = np.zeros((vs.shape[0], rows), np.float32)
        pad_vs[:, :page] = np.asarray(vs)
        out = np.asarray(paged_mixed_attention(
            q, pad_pool(np.asarray(ck), rows, feat),
            pad_pool(np.asarray(cv), rows, feat),
            table, lengths, q_lens, k_scales=pad_s, v_scales=pad_vs,
            page_size=page, interpret=True))[..., :dv]
        for i in range(s):
            np.testing.assert_array_equal(out[i, :q_lens[i]],
                                          base[i, :q_lens[i]])


@pytest.fixture(scope="module")
def engine():
    return make_engine("minitron-8b")


@pytest.fixture(scope="module")
def baseline(engine):
    reqs = mixed_requests(engine, MIXED[:4])
    return reqs, run_trace(engine, reqs, prefill_chunk=4,
                           attn_backend="gathered", kv_page_size=4)


class TestTilingEquivalenceServe:
    """Padded + multi-page serving vs the gathered oracle, token level."""

    @pytest.mark.parametrize("page", [1, 4, 5])
    @pytest.mark.parametrize("pps", [1, 2, 4])
    def test_tokens_identical_across_launch_shapes(self, engine, baseline,
                                                   page, pps):
        reqs, want = baseline
        got = run_trace(engine, reqs, prefill_chunk=4,
                        attn_backend="pallas_paged", kv_page_size=page,
                        kernel_tune=f"0,{pps}")
        assert_tokens_identical(got, want,
                                f"tiled page={page} pps={pps}")

    @pytest.mark.parametrize("arch,page,pps", [
        ("gemma2-2b", 4, 2),          # windowed + softcap layers
        ("deepseek-v2-236b", 3, 4),   # MLA absorbed two-operand path
    ])
    def test_other_archs(self, arch, page, pps):
        eng = make_engine(arch)
        reqs = mixed_requests(eng, MIXED[:3])
        want = run_trace(eng, reqs, prefill_chunk=4,
                         attn_backend="gathered", kv_page_size=page)
        got = run_trace(eng, reqs, prefill_chunk=4,
                        attn_backend="pallas_paged", kv_page_size=page,
                        kernel_tune=f"0,{pps}")
        assert_tokens_identical(got, want, f"tiled {arch}")

    def test_codec_tokens_identical(self, engine):
        reqs = mixed_requests(engine, MIXED[:3])
        want = run_trace(engine, reqs, prefill_chunk=4,
                         attn_backend="pallas_paged", kv_page_size=4,
                         kv_codec="cluster")
        got = run_trace(engine, reqs, prefill_chunk=4,
                        attn_backend="pallas_paged", kv_page_size=4,
                        kv_codec="cluster", kernel_tune="0,2")
        assert_tokens_identical(got, want, "tiled codec")

    def test_explicit_qblock(self, engine, baseline):
        reqs, want = baseline
        got = run_trace(engine, reqs, prefill_chunk=4,
                        attn_backend="pallas_paged", kv_page_size=4,
                        kernel_tune="2,2")
        assert_tokens_identical(got, want, "tiled qb=2")


class TestQblockRounding:
    def test_effective_q_block(self):
        assert effective_q_block(8, 0) == 8
        assert effective_q_block(8, 4) == 4
        assert effective_q_block(6, 4) == 2
        assert effective_q_block(5, 4) == 1

    def test_rounding_counted_and_warned(self, engine):
        """A tuned q_block that does not divide the mixed step's Q must
        bump kernel_qblock_rounded and warn once."""
        engine.metrics.kernel_qblock_rounded = 0
        sched_mod._QBLOCK_WARNED.clear()
        reqs = mixed_requests(engine, MIXED[:2])
        with pytest.warns(RuntimeWarning, match="does not divide"):
            # chunk width 3 with q_block 2: gcd(3, 2) = 1 rounds every
            # chunked step
            run_trace(engine, reqs, prefill_chunk=3,
                      attn_backend="pallas_paged", kv_page_size=4,
                      kernel_tune="2,1")
        assert engine.metrics.kernel_qblock_rounded > 0

    def test_dividing_qblock_not_counted(self, engine):
        engine.metrics.kernel_qblock_rounded = 0
        reqs = mixed_requests(engine, MIXED[:2])
        run_trace(engine, reqs, prefill_chunk=4,
                  attn_backend="pallas_paged", kv_page_size=4,
                  kernel_tune="2,1")
        assert engine.metrics.kernel_qblock_rounded == 0


class TestTuneKernel:
    def test_returns_candidate_winner(self, engine):
        _KERNEL_TUNE_CACHE.clear()
        res = tune_kernel(engine.cfg, 4, 4, interpret=True, repeats=1,
                          pages_per_step=(1, 2))
        assert res["q_block"] in (1, 2, 4)
        assert res["pages_per_step"] in (1, 2)
        assert not res["cached"]
        assert res["best_ms"] == min(t[2] for t in res["timings"])
        assert len(res["timings"]) == 6      # divisors(4) x pps(2)

    def test_memoised_per_key(self, engine):
        res1 = tune_kernel(engine.cfg, 4, 4, interpret=True, repeats=1,
                           pages_per_step=(1, 2))
        res2 = tune_kernel(engine.cfg, 4, 4, interpret=True, repeats=1,
                           pages_per_step=(1, 2))
        assert res2["cached"] and res2["q_block"] == res1["q_block"]
        # a different Q is a different launch point
        res3 = tune_kernel(engine.cfg, 4, 2, interpret=True, repeats=1,
                           pages_per_step=(1,), q_blocks=(2,))
        assert not res3["cached"] and res3["key"] != res1["key"]

    def test_serve_auto_matches_off(self, engine, baseline):
        """The full wiring: --kernel-tune auto serves token-identically
        to the identity layout."""
        reqs, want = baseline
        got = run_trace(engine, reqs, prefill_chunk=4,
                        attn_backend="pallas_paged", kv_page_size=4,
                        kernel_tune="auto")
        assert_tokens_identical(got, want, "kernel_tune=auto")

    def test_rejects_bad_spec(self, engine):
        with pytest.raises(ValueError, match="kernel_tune"):
            Scheduler(engine, attn_backend="pallas_paged", kv_page_size=4,
                      kernel_tune="fastest")
        with pytest.raises(ValueError, match="pallas_paged"):
            Scheduler(engine, kernel_tune="auto")
