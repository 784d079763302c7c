"""Unified mixed-step execution: prefill chunks and decode tokens through
one paged-attention invocation.

The ``pallas_paged`` + ``prefill_chunk`` combination must be
token-identical to the gathered oracle (the plain monolithic-prefill
serving path) across archs (plain GQA / rolling-window gemma2 / MLA
deepseek), chunk sizes {1, 3, page_size, > page_size}, and page sizes
{1, 4, odd} — and its hot loop must move **zero** KV gather/scatter
bytes on the prefill *and* decode paths (no standalone prefill cache, no
install copy: chunks write straight into the page pools).  The kernel's
ragged multi-token form is additionally checked against a pure-numpy
oracle on random page tables.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import paged_mixed_attention
from repro.runtime import Scheduler
from tests.harness import MIXED, make_engine, mixed_requests
from tests.harness import run_trace as serve

pytestmark = pytest.mark.pallas   # CI kernels-interpret job runs these


# ---------------------------------------------------------------------------
# ragged kernel vs numpy oracle
# ---------------------------------------------------------------------------

class TestRaggedKernel:
    @pytest.mark.parametrize("window,q_block", [(0, 0), (4, 0), (0, 2)])
    def test_mixed_block_vs_dense_oracle(self, window, q_block):
        """Chunk rows, a decode row, and an empty (free-lane) row in one
        block; every real token must match dense masked attention at its
        absolute position, padding must stay finite."""
        rng = np.random.default_rng(0)
        s_n, qn, h, kh, d, page, pps = 3, 4, 4, 2, 8, 3, 4
        n_pages = s_n * pps + 2
        k_pages = rng.standard_normal(
            (n_pages, page, kh, d)).astype(np.float32)
        v_pages = rng.standard_normal(
            (n_pages, page, kh, d)).astype(np.float32)
        ids = list(range(1, n_pages))
        rng.shuffle(ids)
        it = iter(ids)
        lengths = np.array([7, 1, 10], np.int32)   # incl. this block
        q_lens = np.array([3, 1, 0], np.int32)     # chunk, decode, free
        table = np.zeros((s_n, pps), np.int32)
        for i in range(s_n):
            for j in range(-(-int(lengths[i]) // page)):
                table[i, j] = next(it)
        q = rng.standard_normal((s_n, qn, h, d)).astype(np.float32)

        out = np.asarray(paged_mixed_attention(
            jnp.asarray(q) * d ** -0.5, jnp.asarray(k_pages),
            jnp.asarray(v_pages), jnp.asarray(table),
            jnp.asarray(lengths), jnp.asarray(q_lens),
            window=window, q_block=q_block, interpret=True))
        assert np.isfinite(out).all()

        smax = pps * page
        for s in range(s_n):
            kv = k_pages[table[s]].reshape(smax, kh, d)
            vv = v_pages[table[s]].reshape(smax, kh, d)
            for i in range(int(q_lens[s])):
                qpos = int(lengths[s]) - int(q_lens[s]) + i
                for hh in range(h):
                    khh = hh // (h // kh)
                    sc = (q[s, i, hh] * d ** -0.5) @ kv[:, khh].T
                    mask = np.arange(smax) <= qpos
                    if window:
                        mask &= np.arange(smax) > qpos - window
                    sc = np.where(mask, sc, -1e30)
                    p = np.exp(sc - sc.max())
                    p /= p.sum()
                    np.testing.assert_allclose(
                        out[s, i, hh], p @ vv[:, khh],
                        rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# mixed-step serving vs the gathered oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def baseline(engine):
    """The gathered oracle: monolithic prefill, monolithic lanes."""
    reqs = mixed_requests(engine, MIXED[:4])
    return reqs, serve(engine, reqs)


class TestMixedStepTokenEquivalence:
    @pytest.mark.parametrize("chunk", [1, 3, 4, 7])
    def test_chunk_sizes_incl_page_and_beyond(self, engine, baseline,
                                              chunk):
        """chunk < page, == page (4), and > page, incl. single-token."""
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=4, prefill_chunk=chunk,
                     attn_backend="pallas_paged") == base

    @pytest.mark.parametrize("page", [1, 5])
    def test_page_sizes_one_and_odd(self, engine, baseline, page):
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=page, prefill_chunk=3,
                     attn_backend="pallas_paged") == base

    def test_wave_mode_and_budget(self, engine, baseline):
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=4, prefill_chunk=3,
                     mode="wave", attn_backend="pallas_paged") == base
        assert serve(engine, reqs, kv_page_size=4, prefill_chunk=2,
                     prefill_budget=16,
                     attn_backend="pallas_paged") == base

    @pytest.mark.parametrize("arch,chunk,page", [
        ("gemma2-2b", 1, 5), ("gemma2-2b", 7, 4),
        ("deepseek-v2-236b", 3, 1), ("deepseek-v2-236b", 5, 4)])
    def test_rolling_window_and_mla_archs(self, arch, chunk, page):
        """gemma2: rolling-window lanes run the ragged reference path
        beside paged global layers inside the same mixed trace; deepseek:
        MLA absorbed chunks through the kernel's second score operand."""
        engine = make_engine(arch)
        rng = np.random.default_rng(3)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, L), g)
                for L, g in [(20, 6), (4, 3), (11, 8)]]
        base = serve(engine, reqs)
        assert serve(engine, reqs, kv_page_size=page, prefill_chunk=chunk,
                     attn_backend="pallas_paged") == base


class TestMixedStepHotPath:
    def test_zero_gather_bytes_prefill_and_decode(self, engine, baseline):
        """The acceptance metric: under the mixed-step path neither the
        decode loop nor the prefill path copies any KV — no per-step page
        gather/scatter AND no install of a standalone prefill cache —
        while the gathered oracle moves both."""
        reqs, base = baseline
        engine.metrics = type(engine.metrics)()
        assert serve(engine, reqs, kv_page_size=4, prefill_chunk=3,
                     attn_backend="pallas_paged") == base
        m = engine.metrics
        assert m.kv_gather_bytes == 0
        assert m.kv_prefill_gather_bytes == 0
        assert m.kv_gather_bytes_avoided > 0
        assert m.kv_prefill_gather_bytes_avoided > 0
        assert "prefill gather" in m.stats_line()
        engine.metrics = type(engine.metrics)()
        serve(engine, reqs, kv_page_size=4, prefill_chunk=3)
        m = engine.metrics
        assert m.kv_gather_bytes > 0             # per-step page copies
        assert m.kv_prefill_gather_bytes > 0     # install copies
        assert m.kv_gather_bytes_avoided == 0
        assert m.kv_prefill_gather_bytes_avoided == 0

    @pytest.mark.parametrize("arch,paged_layers", [("minitron-8b", 2),
                                                   ("gemma2-2b", 2)])
    def test_attn_grid_step_counters(self, arch, paged_layers):
        """Every mixed step counts the paged-attention grid steps of each
        paged layer's kernel call (gemma2: the two global layers; its
        local layers are lanes): all those walked, and those live by the
        kernel's rule, brute-forced here from the blocks the scheduler
        sent.  The live count rides the ``mixed_step`` span and both
        reach Prometheus."""
        from repro.runtime.telemetry import Telemetry
        from tests.test_paged_attention import _brute_live_steps
        tel = Telemetry(trace=True)
        engine = make_engine(arch, telemetry=tel)
        page = 4
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=page, prefill_chunk=3,
                          attn_backend="pallas_paged")
        for r in mixed_requests(engine, MIXED[:4]):
            sched.submit(*r)
        pool = sched._ensure_pool()
        blocks = []
        orig = pool.mixed_step

        def recorded(params, toks, poss, q_lens):
            blocks.append((toks.shape[1], poss.copy(), q_lens.copy()))
            return orig(params, toks, poss, q_lens)

        pool.mixed_step = recorded
        sched.run()
        assert sched._pool is pool
        assert {qn for qn, _, _ in blocks} == {1, 3}
        walked = live = 0
        for qn, poss, q_lens in blocks:
            walked += paged_layers * 2 * pool.pages_per_slot
            live += paged_layers * _brute_live_steps(
                poss + q_lens, q_lens, qn=qn, qb=qn,
                n_pages=pool.pages_per_slot, logical=page, window=0)
        m = engine.metrics
        assert (m.attn_grid_steps, m.attn_grid_steps_live) == (walked, live)
        assert 0 < live < walked
        spans = [e for e in tel.tracer.chrome()["traceEvents"]
                 if e.get("name") == "mixed_step"]
        assert len(spans) == len(blocks)
        assert sum(e["args"]["attn_grid_steps_live"] for e in spans) == live
        prom = engine.render_prom()
        assert f"repro_attn_grid_steps_total {walked}" in prom
        assert f"repro_attn_grid_steps_live_total {live}" in prom

    def test_attn_grid_steps_counted_only_with_telemetry(self, engine):
        """The grid-step count is host work that only feeds a metric: an
        engine without telemetry does not make it."""
        m = engine.metrics
        m.attn_grid_steps = m.attn_grid_steps_live = 0
        steps = m.decode_steps
        serve(engine, mixed_requests(engine, MIXED[:2]), kv_page_size=4,
              prefill_chunk=3, attn_backend="pallas_paged")
        assert m.decode_steps > steps
        assert (m.attn_grid_steps, m.attn_grid_steps_live) == (0, 0)

    def test_no_standalone_prefill_cache(self, engine, baseline):
        """Mixed-step admissions never allocate the batch-1 prefill cache
        — the slot's pcache stays None through its whole lifecycle."""
        reqs, _ = baseline
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=4, prefill_chunk=3,
                          attn_backend="pallas_paged")
        seen = []
        orig = sched._mixed_tick

        def checked(pool, completed):
            seen.extend(s.pcache for s in pool.prefilling())
            orig(pool, completed)

        sched._mixed_tick = checked
        for r in reqs:
            sched.submit(*r)
        done = sched.run()
        assert len(done) == len(reqs) and seen
        assert all(c is None for c in seen)

    def test_mixed_compiles_two_widths(self, engine, baseline):
        """Bounded compile count: chunked ticks trace at Q=prefill_chunk,
        pure-decode ticks at Q=1 — remainder chunks ride padded instead
        of compiling their own width."""
        reqs, base = baseline
        engine._mixed_jits.clear()
        assert serve(engine, reqs, kv_page_size=4, prefill_chunk=3,
                     attn_backend="pallas_paged") == base
        widths = sorted(k[2] for k in engine._mixed_jits)
        assert widths == [1, 3]

    def test_grow_pages_mid_serving_no_recompile(self, engine):
        """Growing the logical pool within page_capacity mid-serving must
        not touch the compiled mixed step and must keep tokens correct."""
        rng = np.random.default_rng(2)
        sched = Scheduler(engine, batch_size=2, buckets=(16,),
                          kv_page_size=4, kv_pages=5, kv_page_capacity=16,
                          prefill_chunk=3, attn_backend="pallas_paged")
        prompts = [rng.integers(0, engine.cfg.vocab_size, 8)
                   for _ in range(3)]
        sched.submit(prompts[0], 6)
        out1 = sched.run()
        assert len(out1) == 1
        keys = [k for k in engine._mixed_jits
                if k[:2] == (sched._pool.paged_flags, sched._pool.page_size)]
        c0 = {k: engine._mixed_jits[k]._cache_size() for k in keys}
        sched._pool.grow_pages(9)
        sched.submit(prompts[1], 6)
        sched.submit(prompts[2], 6)
        out2 = sched.run()
        assert len(out2) == 2
        assert {k: engine._mixed_jits[k]._cache_size()
                for k in keys} == c0
        assert sched._pool.allocator.n_allocated == 0
        ref = serve(engine, [(prompts[0], 6)], buckets=(16,))
        assert tuple(out1[0].generated) == ref[0]

    def test_no_pages_leaked_after_retire(self, engine, baseline):
        reqs, _ = baseline
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=4, prefill_chunk=3,
                          attn_backend="pallas_paged")
        for r in reqs:
            sched.submit(*r)
        sched.run()
        pool = sched._pool
        assert pool.allocator.n_allocated == 0
        assert pool.allocator.reserved == 0
        assert (pool.table == 0).all()
