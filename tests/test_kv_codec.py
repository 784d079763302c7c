"""Property + kernel tests for the KV page codec (``kernels.kv_codec``).

Two layers, in the style of tests/test_property_core.py:

* deterministic seed grid (always runs) + hypothesis drivers (CI) over
  the codec invariants the serving stack relies on:
  - roundtrip error is elementwise-bounded by ``error_bound(scale)``
    (= scale / 254, half a quantization step);
  - encode∘decode is idempotent — re-encoding a decoded page recovers
    the exact codes and scales (the gathered backend re-encodes whole
    views every scatter, so drift would compound);
  - the compressed page (int8 codes + one f32 scale per token) is never
    larger than the fp32 page it replaces;
  - all-zero pages (the page-0 dummy sink) encode to code 0 / scale 0
    and decode back to exactly zero;
  - the at-rest Huffman archive (``archive_pages``/``restore_pages``)
    is lossless and its report ratios are sane.

* pallas-marked kernel tests (the CI kernels-interpret job runs these):
  the in-kernel codebook dequant path of ``kernels.paged_attention``
  must be bit-identical to running the fp kernel on an up-front-decoded
  pool — for plain GQA and for the MLA second score operand — and the
  poison-resistant dummy-sink guarantee must survive the codec.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import kv_codec
from repro.kernels.paged_attention import paged_decode_attention
from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from tests.harness import (MIXED, assert_tokens_identical, make_engine,
                           mixed_requests)
from tests.harness import run_trace as serve
from tests.test_paged_attention import random_paged_cache

SEED_GRID = [0, 1, 2, 3, 17, 255]

# (shape, feature axes) grid covering the layouts the SlotPool encodes:
# attention K/V pages (page, KH, HD), MLA latent rows (page, r), and
# scan-stacked pools with leading repeat dims
SHAPES = [
    ((6, 4, 2, 8), (-2, -1)),     # (pages, page, KH, HD)
    ((3, 5, 16), (-1,)),          # (pages, page, r) MLA latent
    ((2, 4, 3, 2, 8), (-2, -1)),  # scan-stacked (R, pages, page, KH, HD)
    ((7, 1), (-1,)),              # degenerate single-feature tokens
]


def random_values(seed: int, shape, magnitude: float = 1.0) -> np.ndarray:
    """Normal values with a few exact zeros and one huge outlier mixed
    in, scaled by ``magnitude`` (exercises tiny and huge dynamic
    ranges)."""
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(shape).astype(np.float32) * magnitude
    flat = v.reshape(-1)
    if flat.size > 3:
        flat[:: max(flat.size // 3, 1)] = 0.0
        flat[1] = 100.0 * magnitude
    return v


def expand_scale(scale, axes, ndim):
    """Re-insert the squeezed feature axes for broadcasting."""
    for ax in sorted(tuple(a % ndim for a in axes)):
        scale = np.expand_dims(scale, ax)
    return scale


# ---------------------------------------------------------------------------
# check functions (shared by deterministic grid + hypothesis drivers)
# ---------------------------------------------------------------------------

def check_roundtrip_bound_and_idempotence(values, axes) -> None:
    codes, scale = kv_codec.encode(values, axes)
    assert codes.dtype == jnp.int8 and codes.shape == values.shape
    sc = expand_scale(np.asarray(scale), axes, values.ndim)
    recon = np.asarray(kv_codec.decode(codes, sc))
    bound = np.asarray(kv_codec.error_bound(sc))
    err = np.abs(recon - np.asarray(values, np.float32))
    assert (err <= bound + 1e-7 * np.abs(sc)).all(), \
        f"max err {err.max()} exceeds bound {bound.max()}"
    # idempotence: the amax element maps to ±MAX_CODE exactly, so
    # re-encoding the reconstruction recovers identical codes and scales
    codes2, scale2 = kv_codec.encode(recon, axes)
    np.testing.assert_array_equal(np.asarray(codes2), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(scale2), np.asarray(scale))


def check_compressed_not_larger(values, axes) -> None:
    """int8 codes + one f32 scale per token never exceed the fp32 page
    whenever the token's feature block has >= 2 elements (every real KV
    layout; a single-feature token would pay 5 bytes for 4 — the byte
    accounting in SlotPool counts that case honestly too)."""
    codes, scale = kv_codec.encode(values, axes)
    if values.size // max(scale.size, 1) < 2:
        return
    fp_bytes = values.size * 4                      # fp32 page at rest
    packed = codes.size * codes.dtype.itemsize + scale.size * 4
    assert packed <= fp_bytes, (packed, fp_bytes)


def check_zero_page_stays_zero(shape, axes) -> None:
    zero = np.zeros(shape, np.float32)
    codes, scale = kv_codec.encode(zero, axes)
    assert not np.asarray(codes).any()
    assert not np.asarray(scale).any()
    sc = expand_scale(np.asarray(scale), axes, zero.ndim)
    assert not np.asarray(kv_codec.decode(codes, sc)).any()
    assert not np.asarray(kv_codec.error_bound(sc)).any()


def check_archive_roundtrip(codes: np.ndarray) -> None:
    words, nbits, assign = kv_codec.archive_pages(codes)
    assert words.dtype == np.uint32 and words.size == -(-nbits // 32)
    out = kv_codec.restore_pages(words, nbits, assign, codes.shape)
    np.testing.assert_array_equal(out, codes)


# ---------------------------------------------------------------------------
# deterministic grid (runs with or without hypothesis)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEED_GRID)
@pytest.mark.parametrize("shape,axes", SHAPES)
def test_roundtrip_grid(seed, shape, axes):
    rng = np.random.default_rng(seed + 4000)
    mag = float(10.0 ** rng.integers(-6, 7))
    v = random_values(seed, shape, mag)
    check_roundtrip_bound_and_idempotence(v, axes)
    check_compressed_not_larger(v, axes)


@pytest.mark.parametrize("shape,axes", SHAPES)
def test_zero_page_grid(shape, axes):
    check_zero_page_stays_zero(shape, axes)


@pytest.mark.parametrize("seed", SEED_GRID)
def test_archive_roundtrip_grid(seed):
    rng = np.random.default_rng(seed + 5000)
    shape = (int(rng.integers(1, 5)), int(rng.integers(1, 33)), 8)
    codes = rng.integers(-127, 128, shape).astype(np.int8)
    check_archive_roundtrip(codes)


def test_huffman_report_skewed_codes_compress():
    """KV codes concentrated around zero (the serving distribution) get
    an at-rest Huffman ratio > 1 vs the 8-bit resident pool; clustering
    reports at least as short an average code."""
    rng = np.random.default_rng(0)
    codes = np.clip(rng.normal(0.0, 6.0, 4096).round(), -127, 127) \
        .astype(np.int8)
    rep = kv_codec.huffman_report(codes)
    assert rep["symbols"] == 4096
    assert rep["ratio"] > 1.0
    assert rep["clustered_avg_bits"] <= rep["avg_bits"] + 1e-9
    check_archive_roundtrip(codes.reshape(64, 64))


# ---------------------------------------------------------------------------
# hypothesis drivers (skipped cleanly when hypothesis is absent)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    seed_st = st.integers(min_value=0, max_value=2 ** 32 - 1)

    @settings(max_examples=60, deadline=None)
    @given(seed=seed_st, pages=st.integers(1, 6), page=st.integers(1, 8),
           kh=st.integers(1, 3), hd=st.integers(1, 16),
           mag_exp=st.integers(-6, 6))
    def test_roundtrip_property(seed, pages, page, kh, hd, mag_exp):
        v = random_values(seed, (pages, page, kh, hd), 10.0 ** mag_exp)
        check_roundtrip_bound_and_idempotence(v, (-2, -1))
        check_compressed_not_larger(v, (-2, -1))

    @settings(max_examples=40, deadline=None)
    @given(seed=seed_st, n=st.integers(1, 256))
    def test_archive_property(seed, n):
        rng = np.random.default_rng(seed)
        check_archive_roundtrip(
            rng.integers(-127, 128, (n,)).astype(np.int8))


# ---------------------------------------------------------------------------
# in-kernel dequant path (CI kernels-interpret job runs these)
# ---------------------------------------------------------------------------

def encode_pool(pages: np.ndarray):
    """Pool (n_pages, page, *feat) -> (int8 codes, (n_pages, page)
    scales, decoded fp pool) with one scale per page token."""
    axes = tuple(range(2, pages.ndim))
    codes, scale = kv_codec.encode(pages, axes)
    sc = expand_scale(np.asarray(scale), axes, pages.ndim)
    return codes, jnp.asarray(scale), jnp.asarray(kv_codec.decode(codes, sc))


@pytest.mark.pallas
class TestKernelCodecPath:
    @pytest.mark.parametrize("page,pages_per_slot", [(3, 4), (4, 3)])
    def test_codec_kernel_bit_matches_decoded_pool(self, page,
                                                   pages_per_slot):
        """The in-kernel codebook dequant must equal decoding the pool
        up front and running the fp kernel — bit-identical, so the codec
        adds exactly the quantization error and nothing else."""
        rng = np.random.default_rng(page)
        s, h, kh, d = 4, 4, 2, 16
        k_pages, v_pages, table, lengths = random_paged_cache(
            rng, s, kh, d, d, page, pages_per_slot)
        q = jnp.asarray(
            rng.standard_normal((s, h, d)).astype(np.float32)) * d ** -0.5
        kc, ks, kd = encode_pool(k_pages)
        vc, vs, vd = encode_pool(v_pages)
        out = paged_decode_attention(
            q, jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table),
            jnp.asarray(lengths), k_scales=ks, v_scales=vs, interpret=True)
        want = paged_decode_attention(
            q, kd, vd, jnp.asarray(table), jnp.asarray(lengths),
            interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_codec_kernel_mla_second_operand(self):
        """MLA absorbed decode: latent pool (shared K/V codes + scales)
        plus rope-part second operand, all dequantized in-kernel."""
        rng = np.random.default_rng(5)
        s, h, r, dr, page, pps = 3, 4, 8, 4, 3, 4
        c_pages, _, table, lengths = random_paged_cache(
            rng, s, 1, r, r, page, pps)
        c_pages = c_pages[:, :, 0]                       # (n, page, r)
        pe_pages = rng.standard_normal(
            (c_pages.shape[0], page, dr)).astype(np.float32)
        q1 = jnp.asarray(rng.standard_normal((s, h, r)).astype(np.float32))
        q2 = jnp.asarray(rng.standard_normal((s, h, dr)).astype(np.float32))
        scale = (r + dr) ** -0.5
        cc, cs, cd = encode_pool(c_pages)
        pc, ps, pd = encode_pool(pe_pages)
        args = dict(scale=scale, interpret=True)
        out = paged_decode_attention(
            q1, jnp.asarray(cc)[:, :, None], jnp.asarray(cc)[:, :, None],
            jnp.asarray(table), jnp.asarray(lengths), q2,
            jnp.asarray(pc)[:, :, None], k_scales=cs, v_scales=cs,
            k2_scales=ps, **args)
        want = paged_decode_attention(
            q1, cd[:, :, None], cd[:, :, None], jnp.asarray(table),
            jnp.asarray(lengths), q2, pd[:, :, None], **args)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_codec_dummy_sink_stays_inert(self):
        """Page 0 stays all-zero codes / zero scale under the codec;
        poisoning its *codes* must not change any output (the mask never
        admits it) and zero scale keeps its decode exactly zero."""
        rng = np.random.default_rng(9)
        s, h, kh, d = 3, 4, 2, 8
        k_pages, v_pages, table, lengths = random_paged_cache(
            rng, s, kh, d, d, 4, 4)
        k_pages[0] = 0.0
        v_pages[0] = 0.0
        q = jnp.asarray(
            rng.standard_normal((s, h, d)).astype(np.float32)) * d ** -0.5
        kc, ks, _ = encode_pool(k_pages)
        vc, vs, _ = encode_pool(v_pages)
        assert not np.asarray(kc[0]).any() and not np.asarray(ks[0]).any()

        def run(kcodes, vcodes):
            return np.asarray(paged_decode_attention(
                q, jnp.asarray(kcodes), jnp.asarray(vcodes),
                jnp.asarray(table), jnp.asarray(lengths), k_scales=ks,
                v_scales=vs, interpret=True))

        clean = run(kc, vc)
        kc2, vc2 = np.asarray(kc).copy(), np.asarray(vc).copy()
        kc2[0] = 127
        vc2[0] = -127
        poisoned = run(kc2, vc2)
        assert np.isfinite(poisoned).all()
        np.testing.assert_array_equal(clean, poisoned)


# ---------------------------------------------------------------------------
# codec through the serving stack (tests.harness)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    return make_engine()


class TestCodecServing:
    def test_codec_none_is_identity(self, engine):
        """kv_codec="none" over paged lanes is byte-for-byte the plain
        paged path — every generated token identical to the monolithic
        baseline."""
        reqs = mixed_requests(engine, MIXED[:4])
        base = serve(engine, reqs)
        got = serve(engine, reqs, kv_page_size=4, kv_codec="none")
        assert_tokens_identical(got, base, "kv_codec=none")

    @pytest.mark.parametrize("backend", [
        "gathered",
        pytest.param("pallas_paged", marks=pytest.mark.pallas)])
    def test_cluster_first_tokens_exact(self, engine, backend):
        """kv_codec="cluster" is lossy at rest, but the first generated
        token of every request comes out of the (uncompressed) prefill
        forward pass before any page is encoded — it must be exact under
        both attention backends, and every request must still finish."""
        reqs = mixed_requests(engine, MIXED[:4])
        kw = dict(kv_page_size=4, attn_backend=backend)
        base = serve(engine, reqs, **kw)
        got = serve(engine, reqs, kv_codec="cluster", **kw)
        assert set(got) == set(base)
        for i in sorted(base):
            assert got[i][0] == base[i][0], \
                f"first token diverged for request {i}"
            assert len(got[i]) == len(base[i])
