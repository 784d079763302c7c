"""The attention-backend seam: ``pallas_paged`` in-kernel decode attention
must be token-identical to the ``gathered`` reference across archs
(plain GQA / rolling-window gemma2 / MLA deepseek), page sizes
(1, 4, odd), chunked prefill, wave mode, and mid-decode pool growth —
and the kernel itself must match ``attention.decode_attention`` on random
page tables including the page-0 dummy sink.  The kernel backend's hot
loop must also move zero gather/scatter bytes (the acceptance metric for
killing the per-step page copies)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import kv_codec
from repro.kernels.paged_attention import (VMEM_BUDGET, Launch,
                                           effective_q_block, gqa_q_block,
                                           kernel_launches, latent_q_block,
                                           live_grid_steps,
                                           paged_decode_attention,
                                           paged_mixed_attention,
                                           q_row_bytes)
from repro.models.api import (cache_layout, get_model,
                              supports_paged_attention)
from repro.models.attention import decode_attention
from repro.runtime import Scheduler
from tests.harness import MIXED, make_engine, mixed_requests
from tests.harness import run_trace as serve

pytestmark = pytest.mark.pallas   # CI kernels-interpret job runs these


# ---------------------------------------------------------------------------
# kernel unit tests vs the decode_attention oracle
# ---------------------------------------------------------------------------

def random_paged_cache(rng, s, kh, d, dv, page, pages_per_slot,
                       n_pages=None):
    """Random pools + a shuffled page table whose tail rows point at the
    page-0 dummy sink (exactly the scheduler's layout contract)."""
    lengths = rng.integers(1, pages_per_slot * page + 1, s).astype(np.int32)
    need = int(sum(-(-int(ln) // page) for ln in lengths))
    n_pages = n_pages or need + 3                    # spare pages + dummy
    assert n_pages > need
    k_pages = rng.standard_normal((n_pages, page, kh, d)).astype(np.float32)
    v_pages = rng.standard_normal((n_pages, page, kh, dv)).astype(np.float32)
    ids = list(range(1, n_pages))
    rng.shuffle(ids)
    it = iter(ids)
    table = np.zeros((s, pages_per_slot), np.int32)  # 0 = dummy sink
    for i in range(s):
        for j in range(-(-int(lengths[i]) // page)):
            table[i, j] = next(it)
    return k_pages, v_pages, table, lengths


def gather_reference(q, k_pages, v_pages, table, lengths, **kw):
    """The gathered oracle: contiguous per-slot views + decode_attention.

    ``q`` is raw (decode_attention applies the 1/sqrt(d) scale itself; the
    kernel takes pre-scaled queries — callers scale only the kernel's)."""
    s, h, d = q.shape
    page = k_pages.shape[1]
    kh, dv = k_pages.shape[2], v_pages.shape[-1]
    smax = table.shape[1] * page
    k_view = k_pages[table].reshape(s, smax, kh, d)
    v_view = v_pages[table].reshape(s, smax, kh, dv)
    return decode_attention(jnp.asarray(q[:, None]), jnp.asarray(k_view),
                            jnp.asarray(v_view),
                            jnp.asarray(lengths - 1), **kw)[:, 0]


class TestKernelVsOracle:
    @pytest.mark.parametrize("page,pages_per_slot", [(1, 8), (3, 4), (4, 3),
                                                     (8, 2)])
    def test_random_tables_incl_dummy_sink(self, page, pages_per_slot):
        rng = np.random.default_rng(page)
        s, h, kh, d, dv = 4, 4, 2, 16, 16
        k_pages, v_pages, table, lengths = random_paged_cache(
            rng, s, kh, d, dv, page, pages_per_slot)
        q = rng.standard_normal((s, h, d)).astype(np.float32)
        out = paged_decode_attention(
            jnp.asarray(q) * d ** -0.5, jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(table), jnp.asarray(lengths),
            interpret=True)
        want = gather_reference(q, k_pages, v_pages, table, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window,softcap", [(5, 0.0), (0, 4.0),
                                                (7, 3.0)])
    def test_window_and_softcap(self, window, softcap):
        rng = np.random.default_rng(11)
        s, h, kh, d = 3, 4, 1, 8
        k_pages, v_pages, table, lengths = random_paged_cache(
            rng, s, kh, d, d, 4, 4)
        q = rng.standard_normal((s, h, d)).astype(np.float32)
        out = paged_decode_attention(
            jnp.asarray(q) * d ** -0.5, jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(table), jnp.asarray(lengths),
            window=window, softcap_val=softcap, interpret=True)
        want = gather_reference(q, k_pages, v_pages, table, lengths,
                                window=window, attn_softcap=softcap)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_mla_second_operand(self):
        """(q, k) + (q2, k2) scoring with a shared post-sum scale — the MLA
        absorbed-decode form (latent pool doubles as the value pool)."""
        rng = np.random.default_rng(5)
        s, h, r, dr, page, pps = 3, 4, 8, 4, 3, 4
        c_pages, _, table, lengths = random_paged_cache(
            rng, s, 1, r, r, page, pps)
        pe_pages = rng.standard_normal(
            (c_pages.shape[0], page, 1, dr)).astype(np.float32)
        q1 = rng.standard_normal((s, h, r)).astype(np.float32)
        q2 = rng.standard_normal((s, h, dr)).astype(np.float32)
        scale = (r + dr) ** -0.5
        out = paged_decode_attention(
            jnp.asarray(q1), jnp.asarray(c_pages), None,
            jnp.asarray(table), jnp.asarray(lengths), jnp.asarray(q2),
            jnp.asarray(pe_pages), scale=scale, interpret=True)
        smax = pps * page
        for i in range(s):
            c = c_pages[table[i], :, 0].reshape(smax, r)
            pe = pe_pages[table[i], :, 0].reshape(smax, dr)
            sc = (q1[i] @ c.T + q2[i] @ pe.T) * scale
            sc = np.where(np.arange(smax)[None] < lengths[i], sc, -1e30)
            p = np.asarray(jax.nn.softmax(jnp.asarray(sc), axis=-1))
            np.testing.assert_allclose(np.asarray(out[i]), p @ c,
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("g,qn,window,softcap,codec", [
        (1, 1, 0, 0.0, False),        # decode, one query head a KV head
        (2, 6, 0, 0.0, False),        # chunk program, whole-Q block
        (4, 40, 0, 0.0, False),       # 160 rows: blocks of 32, Q % 32
        (8, 20, 0, 0.0, False),       # 160 rows: blocks of 16
        (2, 6, 5, 3.0, False),        # gemma2-like window and softcap
        (4, 40, 9, 0.0, False),       # window over two q blocks
        (4, 6, 0, 0.0, True),         # KV codec
        (8, 20, 7, 2.0, True),        # everything at once
    ])
    def test_gqa_blocks_vs_gathered_reference(self, g, qn, window, softcap,
                                              codec):
        """Every KV head's query heads in one product per page, against
        the gathered reference token by token: a full chunk beside decode
        slots (q_lens 1), a free slot (q_lens 0), a partial chunk, and
        lengths ending mid-page and on a page boundary."""
        rng = np.random.default_rng(g * 100 + qn)
        kh, d, page, pages_per_slot = 2, 8, 4, 20
        h = g * kh
        q_lens = np.array([qn, 1, 0, 1, max(qn - 1, 1)], np.int32)
        on_boundary = -(-(qn + 13) // page) * page
        lengths = np.array([on_boundary, 24, 0, 9, qn + 31], np.int32)
        s_n = len(q_lens)
        n_pages = s_n * pages_per_slot + 1
        k_pages = rng.standard_normal((n_pages, page, kh, d)).astype(
            np.float32)
        v_pages = rng.standard_normal((n_pages, page, kh, d)).astype(
            np.float32)
        ids = iter(rng.permutation(np.arange(1, n_pages)))
        table = np.zeros((s_n, pages_per_slot), np.int32)
        for s in range(s_n):
            for j in range(-(-int(lengths[s]) // page)):
                table[s, j] = next(ids)
        q = rng.standard_normal((s_n, qn, h, d)).astype(np.float32)
        kw = {}
        if codec:
            k_in, ks = kv_codec.encode(jnp.asarray(k_pages), axes=(-2, -1))
            v_in, vs = kv_codec.encode(jnp.asarray(v_pages), axes=(-2, -1))
            k_pages = np.asarray(kv_codec.decode(k_in, ks[..., None, None]))
            v_pages = np.asarray(kv_codec.decode(v_in, vs[..., None, None]))
            kw = dict(k_scales=ks, v_scales=vs)
        else:
            k_in, v_in = jnp.asarray(k_pages), jnp.asarray(v_pages)
        out = np.asarray(paged_mixed_attention(
            jnp.asarray(q) * d ** -0.5, k_in, v_in, jnp.asarray(table),
            jnp.asarray(lengths), jnp.asarray(q_lens), window=window,
            softcap_val=softcap, interpret=True, **kw))
        assert np.isfinite(out).all()
        smax = pages_per_slot * page
        k_view = jnp.asarray(k_pages[table].reshape(s_n, smax, kh, d))
        v_view = jnp.asarray(v_pages[table].reshape(s_n, smax, kh, d))
        for i in range(qn):
            want = np.asarray(decode_attention(
                jnp.asarray(q[:, i:i + 1]), k_view, v_view,
                jnp.asarray(lengths - q_lens + i), window=window,
                attn_softcap=softcap))[:, 0]
            for s in np.flatnonzero(q_lens > i):
                np.testing.assert_allclose(out[s, i], want[s],
                                           rtol=2e-5, atol=2e-5)
        # a q block with no real query does no arithmetic: its rows keep
        # the zero accumulator (the free slot's, and past a decode token)
        qb = gqa_q_block(qn, 0, g, kh, d, d, page)
        for s in range(s_n):
            dead = -(-int(q_lens[s]) // qb) * qb
            assert not out[s, dead:].any()

    def test_dummy_sink_never_contaminates(self):
        """Poisoning the page-0 dummy sink with huge values must not
        change any output: every position the mask admits has a real
        page, so the sink is never read as a valid key."""
        rng = np.random.default_rng(9)
        s, h, kh, d = 3, 4, 2, 8
        k_pages, v_pages, table, lengths = random_paged_cache(
            rng, s, kh, d, d, 4, 4)
        q = rng.standard_normal((s, h, d)).astype(np.float32)

        def run(kp, vp):
            return np.asarray(paged_decode_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(table), jnp.asarray(lengths), interpret=True))

        clean = run(k_pages, v_pages)
        k_pages[0] = 1e6
        v_pages[0] = -1e6
        poisoned = run(k_pages, v_pages)
        assert np.isfinite(poisoned).all()
        np.testing.assert_array_equal(clean, poisoned)


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


def _brute_live_steps(lengths, q_lens, *, qn, qb, n_pages, logical,
                      window):
    """Grid steps that compute, cell by cell: a q block holding a real
    query, and a page holding a key at a position below the slot's
    length that the block's first query's window (if any) reaches."""
    live = 0
    for length, qlen in zip(lengths, q_lens):
        for qi in range(-(-qn // qb)):
            if qi * qb >= qlen:
                continue
            first = length - qlen + qi * qb
            for j in range(n_pages):
                keys = [p for p in range(j * logical, (j + 1) * logical)
                        if p < length and (not window or p > first - window)]
                live += bool(keys)
    return live


class TestLiveGridSteps:
    @pytest.mark.parametrize("window,qb", [(0, 1), (0, 4), (5, 2), (9, 3),
                                           (4, 8)])
    def test_matches_brute_force(self, window, qb):
        rng = np.random.default_rng(window * 10 + qb)
        qn, logical, n_pages = 8, 4, 10
        q_lens = rng.integers(0, qn + 1, 64)
        q_lens[:4] = [0, 1, qn, 1]
        lengths = q_lens + rng.integers(0, n_pages * logical - qn + 1, 64)
        lengths[:4] = [0, 12, 40, 1]
        kw = dict(qn=qn, qb=qb, n_pages=n_pages, logical=logical,
                  window=window)
        walked, live = live_grid_steps(lengths, q_lens, **kw)
        assert walked == 64 * -(-qn // qb) * n_pages
        assert live == _brute_live_steps(lengths, q_lens, **kw)
        assert 0 < live < walked

    @pytest.mark.parametrize("window", [0, 6, 3])
    def test_kernel_folds_exactly_the_live_pages(self, window):
        """The kernel computes on the pages the rule calls live and on no
        other.  Two readings show it.  A padding row of a live q block
        masks every key, so each page folded into it adds weight one to
        every key row: its output is the mean value row over exactly the
        pages computed.  And NaN values in every page the rule leaves
        dead for all of a slot's q blocks (the dummy sink, pages a window
        has passed) reach no output: neither read nor computed on."""
        rng = np.random.default_rng(window + 1)
        kh, g, d, page, pages_per_slot, qn = 2, 2, 8, 4, 8, 3
        q_lens = np.array([1, 2, 0, 1, 3], np.int32)
        lengths = np.array([30, 8, 0, 17, 21], np.int32)
        s_n = len(q_lens)
        n_pages = s_n * pages_per_slot + 1
        k_pages = rng.standard_normal((n_pages, page, kh, d)).astype(
            np.float32)
        v_pages = rng.standard_normal((n_pages, page, kh, d)).astype(
            np.float32)
        table = np.zeros((s_n, pages_per_slot), np.int32)
        ids = iter(range(1, n_pages))
        for s in range(s_n):
            for j in range(-(-int(lengths[s]) // page)):
                table[s, j] = next(ids)
        q = jnp.asarray(rng.standard_normal((s_n, qn, kh * g, d)),
                        jnp.float32)

        def run(vp):
            return np.asarray(paged_mixed_attention(
                q, jnp.asarray(k_pages), jnp.asarray(vp),
                jnp.asarray(table), jnp.asarray(lengths),
                jnp.asarray(q_lens), window=window, interpret=True))

        clean = run(v_pages)
        poisoned = v_pages.copy()
        poisoned[0] = np.nan                          # the dummy sink
        for s in range(s_n):
            opens = lengths[s] - q_lens[s] - window + 1
            for j in range(pages_per_slot):
                if table[s, j] and window and (j + 1) * page <= opens:
                    poisoned[table[s, j]] = np.nan
        assert np.isnan(poisoned).any(axis=(1, 2, 3)).sum() == \
            {0: 1, 6: 12, 3: 15}[window]
        np.testing.assert_array_equal(run(poisoned), clean)
        checked = 0
        for s in np.flatnonzero((q_lens > 0) & (q_lens < qn)):
            first = lengths[s] - q_lens[s]
            lo = max(first - window + 1, 0) // page if window else 0
            hi = (lengths[s] - 1) // page
            live = v_pages[table[s, lo:hi + 1]].reshape(-1, kh, d)
            for t in range(q_lens[s], qn):
                for hh in range(kh * g):
                    np.testing.assert_allclose(
                        clean[s, t, hh], live[:, hh // g].mean(0),
                        rtol=1e-5, atol=1e-5)
                    checked += 1
        assert checked == 5 * kh * g         # 2 + 1 + 2 padding rows

    def test_q_block_from_shapes(self):
        """About one MXU pass of rows, a multiple of the sublane tile or
        all of Q; an explicit block keeps its gcd meaning."""
        phi3 = dict(g=4, kh=10, d=128, wv=128, page=16)
        assert gqa_q_block(1, 0, **phi3) == 1
        assert gqa_q_block(64, 0, **phi3) == 32
        assert gqa_q_block(24, 0, **phi3) == 24
        assert gqa_q_block(64, 16, **phi3) == 16
        assert gqa_q_block(64, 24, **phi3) == 8
        assert gqa_q_block(128, 0, g=2, kh=4, d=256, wv=256, page=16) == 64
        assert gqa_q_block(100, 0, g=3, kh=1, d=64, wv=64, page=16) == 40

    def test_refused_explicit_block_falls_back(self):
        """An explicit block whose rows the chip refuses (neither a
        multiple of 8 nor all of ``Q * g``) runs the block sized from the
        shapes: a chunk's block at a 1 + 4 verify, or 2 tokens at g 2."""
        phi3 = dict(g=4, kh=10, d=128, wv=128, page=16)
        gemma2 = dict(g=2, kh=4, d=256, wv=256, page=16)
        for q_block in (4, 32):
            assert gqa_q_block(5, q_block, **phi3) == 5
            assert gqa_q_block(5, q_block, **gemma2) == 5
        assert gqa_q_block(64, 2, **gemma2) == 64
        assert gqa_q_block(64, 4, **gemma2) == 4
        assert gqa_q_block(1, 32, **phi3) == 1

    @pytest.mark.parametrize("q_block,want_qb", [
        (0, 5),       # sized from the shapes
        (5, 5),       # asked and run
        (4, 5),       # gcd 1: 2 rows a KV head, refused
        (10, 5)])     # gcd 5 is all of Q
    def test_kernel_launches_read_from_the_trace(self, q_block, want_qb):
        """Every kernel call records its launch; a call under a scan
        counts once per iteration, and each window is its own launch."""
        rng = np.random.default_rng(3)
        s, qn, kh, g, d, page, pages = 2, 5, 2, 2, 16, 4, 3
        k, v, table, lengths = random_paged_cache(rng, s, kh, d, d, page,
                                                  pages)
        q_lens = np.array([qn, 1], np.int32)
        lengths = np.maximum(lengths, q_lens)
        q = jnp.asarray(rng.normal(size=(s, qn, kh * g, d)), jnp.float32)

        def attend(x, window):
            return paged_mixed_attention(
                x, k, v, table, lengths, q_lens, window=window,
                q_block=q_block, interpret=True)

        def step(x):
            x = attend(x, 0)
            return jax.lax.scan(lambda c, _: (attend(c, 4), None), x,
                                None, length=3)[0]

        launches = kernel_launches(jax.jit(step).trace(q).jaxpr)
        base = Launch(qn=qn, qb=want_qb, n_pages=pages, logical=page,
                      window=0)
        assert launches == {base: 1, base._replace(window=4): 3}
        assert base.grid_steps(lengths, q_lens) == live_grid_steps(
            lengths, q_lens, qn=qn, qb=want_qb, n_pages=pages,
            logical=page)


# every LM config with a layer the kernel pages (h2o-danube and mixtral
# are windowed on every layer, so their caches stay lanes)
PAGED_ARCHS = ("gemma2-2b", "phi3-medium-14b", "minitron-8b",
               "paligemma-3b", "deepseek-v2-236b")


class TestQBlockFromShapes:
    def test_effective_q_block(self):
        assert effective_q_block(8, 0) == 8
        assert effective_q_block(8, 4) == 4
        assert effective_q_block(6, 4) == 2
        assert effective_q_block(5, 4) == 1

    @pytest.mark.parametrize("page", [16, 64])
    @pytest.mark.parametrize("qn", [1, 5, 64])
    @pytest.mark.parametrize("arch", PAGED_ARCHS)
    def test_block_fits_published_widths(self, arch, qn, page):
        """The q block the kernel sizes from the shapes at a config's
        published widths, for decode, a 1 + 4 verify and a 64-token
        chunk at both cells' page sizes: its rows are whole sublane
        tiles or all of ``Q * g``, its scoped VMEM (the block's rows and
        one page of each pool) fits the budget, and its q blocks cover Q
        with none wholly past it."""
        cfg = get_config(arch)
        api = get_model(cfg)
        slot_len = 2 * max(cfg.window, 4096)     # windowed leaves: lanes
        leaves = jax.tree_util.tree_leaves(
            api.init_cache_specs(cfg, 1, slot_len))
        _, len_axes = cache_layout(api, cfg, slot_len)
        feats = {tuple(leaf.shape[ax + 1:])
                 for leaf, ax in zip(leaves, len_axes) if ax is not None}
        h = cfg.num_heads
        if cfg.kv_lora_rank:
            kh, d, w2 = 1, cfg.kv_lora_rank, cfg.rope_head_dim
            assert feats == {(d,), (w2,)}
            g, wv, pools = h, d, (d, w2)
            qb = latent_q_block(qn, 0, h, d, w2, page)
        else:
            kh, d, w2 = cfg.num_kv_heads, cfg.head_dim, 0
            assert feats == {(kh, d)}
            g, wv, pools = h // kh, d, (kh * d, kh * d)
            qb = gqa_q_block(qn, 0, g, kh, d, wv, page)
        rows = qb * g
        assert rows % 8 == 0 or rows == qn * g, (qb, rows)
        itemsize = jnp.dtype(cfg.jnp_dtype).itemsize
        scoped = q_row_bytes(kh, d, wv, w2, page) * rows \
            + sum(page * w * itemsize for w in pools)
        assert scoped <= VMEM_BUDGET, (qb, scoped)
        n_blocks = -(-qn // qb)
        assert (n_blocks - 1) * qb < qn <= n_blocks * qb, (qb, n_blocks)

# ---------------------------------------------------------------------------
# backend seam: token-identical serving across archs / page sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def baseline(engine):
    reqs = mixed_requests(engine, MIXED[:4])
    return reqs, serve(engine, reqs)


class TestBackendTokenEquivalence:
    @pytest.mark.parametrize("page", [1, 4, 5])
    def test_kernel_backend_any_page_size(self, engine, baseline, page):
        """pallas_paged == gathered for page sizes 1, 4, and odd."""
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=page,
                     attn_backend="pallas_paged") == base

    def test_kernel_backend_matches_gathered_paged(self, engine, baseline):
        """Three-way: monolithic lanes == gathered pages == in-kernel."""
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=4) == base
        assert serve(engine, reqs, kv_page_size=4,
                     attn_backend="pallas_paged") == base

    def test_kernel_backend_with_chunked_prefill(self, engine, baseline):
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=4, prefill_chunk=3,
                     attn_backend="pallas_paged") == base

    def test_kernel_backend_wave_mode(self, engine, baseline):
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=8, mode="wave",
                     attn_backend="pallas_paged") == base

    @pytest.mark.parametrize("arch", ["gemma2-2b", "deepseek-v2-236b"])
    def test_rolling_window_and_mla_archs(self, arch):
        """gemma2: rolling-window lanes run the reference path next to
        paged global layers in the same step; deepseek: MLA absorbed
        decode through the kernel's second score operand."""
        engine = make_engine(arch)
        rng = np.random.default_rng(3)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, L), g)
                for L, g in [(20, 6), (4, 3), (11, 8)]]
        base = serve(engine, reqs)
        assert serve(engine, reqs, kv_page_size=4,
                     attn_backend="pallas_paged") == base
        assert serve(engine, reqs, kv_page_size=3,
                     attn_backend="pallas_paged") == base

    def test_requires_page_size(self, engine):
        with pytest.raises(ValueError, match="kv_page_size"):
            Scheduler(engine, attn_backend="pallas_paged")

    def test_unknown_backend_rejected(self, engine):
        with pytest.raises(ValueError, match="backend"):
            Scheduler(engine, kv_page_size=4, attn_backend="flash3")

    def test_recurrent_arch_falls_back_with_note(self):
        """The backend downgrade warns (warn-once per family); the
        trigger rides inside ``pytest.warns`` so the escaped-warning
        escalation in pyproject.toml stays clean."""
        from repro.runtime import scheduler as sched_mod

        engine = make_engine("recurrentgemma-2b")
        assert not supports_paged_attention(engine.cfg)
        notes = []
        sched_mod._FALLBACK_WARNED.clear()     # deterministic first hit
        with pytest.warns(RuntimeWarning,
                          match="supports_paged_attention=False"):
            sched = Scheduler(engine, kv_page_size=4,
                              attn_backend="pallas_paged",
                              emit=notes.append)
        assert sched.attn_backend == "gathered"
        assert any("gathered" in n for n in notes)


class TestKernelBackendHotPath:
    def test_zero_gather_bytes_on_decode_path(self, engine, baseline):
        """The acceptance metric: under pallas_paged the decode hot loop
        performs no per-step page gather/scatter copies at all, while the
        gathered backend moves two full view copies per step."""
        reqs, base = baseline
        engine.metrics = type(engine.metrics)()
        assert serve(engine, reqs, kv_page_size=4,
                     attn_backend="pallas_paged") == base
        m = engine.metrics
        assert m.kv_gather_bytes == 0
        assert m.kv_gather_bytes_avoided > 0
        engine.metrics = type(engine.metrics)()
        serve(engine, reqs, kv_page_size=4)
        m = engine.metrics
        assert m.kv_gather_bytes > 0
        assert m.kv_gather_bytes_avoided == 0

    def test_grow_pages_mid_decode_no_recompile(self, engine):
        """Growing the logical pool within page_capacity mid-serving must
        not touch the compiled paged decode step and must keep tokens
        correct."""
        rng = np.random.default_rng(2)
        sched = Scheduler(engine, batch_size=2, buckets=(16,),
                          kv_page_size=4, kv_pages=5, kv_page_capacity=16,
                          attn_backend="pallas_paged")
        prompts = [rng.integers(0, engine.cfg.vocab_size, 8)
                   for _ in range(3)]
        sched.submit(prompts[0], 6)
        out1 = sched.run()
        assert len(out1) == 1
        key = (sched._pool.paged_flags, sched._pool.page_size, 1, False)
        c0 = engine._mixed_jits[key]._cache_size()
        sched._pool.grow_pages(9)
        sched.submit(prompts[1], 6)
        sched.submit(prompts[2], 6)
        out2 = sched.run()
        assert len(out2) == 2
        assert engine._mixed_jits[key]._cache_size() == c0
        assert sched._pool.allocator.n_allocated == 0
        # identical prompts generate identical tokens before/after growth
        ref = serve(engine, [(prompts[0], 6)], buckets=(16,))
        assert tuple(out1[0].generated) == ref[0]

    def test_no_pages_leaked_after_retire(self, engine, baseline):
        reqs, _ = baseline
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=4, attn_backend="pallas_paged")
        for r in reqs:
            sched.submit(*r)
        sched.run()
        pool = sched._pool
        assert pool.allocator.n_allocated == 0
        assert pool.allocator.reserved == 0
        assert (pool.table == 0).all()
