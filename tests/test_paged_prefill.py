"""Chunked prefill + paged KV lanes: token equivalence against the
monolithic PR-2 paths, page-allocator invariants (no leak, no double
allocation, cross-slot isolation), and pool growth without decode
recompiles.

Chunked prefill sums attention and the recurrent scans in another order
than monolithic prefill.  The compressed MLPs' ``sign()`` turns that
last-bit difference into another token as early as a request's first
(0.2-0.7 apart in its logits on the float32 reduced minitron-8b and
recurrentgemma-2b), so the tests where the order differs serve
uncompressed weights and compare the logits row behind every generated
token within :data:`LOGITS_ATOL`, besides the tokens."""

import numpy as np
import pytest

from repro.models.api import supports_chunked_prefill
from repro.runtime import PageAllocator, Scheduler
from tests.harness import make_engine, mixed_requests
from tests.harness import run_trace as serve

# float32 logits of two summation orders
LOGITS_ATOL = 1e-4


def serve_recording(engine, reqs, **kw):
    """Serve ``reqs`` like ``run_trace`` -> ({request index: generated
    tokens}, {(request index, k): the logits row that chose generated
    token k}), read from the monolithic prefill, the last prompt chunk
    and each decode step of the gathered paths."""
    kw.setdefault("batch_size", 2)
    kw.setdefault("buckets", (32,))
    sched = Scheduler(engine, **kw)
    index = {sched.submit(*r).rid: i for i, r in enumerate(reqs)}
    prompts = [np.asarray(p) for p, _ in reqs]
    rows = {}
    prefill, chunk_step, slot_decode = (
        engine.prefill, engine.prefill_chunk_step, engine.slot_decode)

    def whole(params, tokens, cache, *extra):
        logits, cache = prefill(params, tokens, cache, *extra)
        (i,) = [i for i, p in enumerate(prompts)
                if np.array_equal(p, np.asarray(tokens)[0])]
        rows[(i, 0)] = np.asarray(logits[0, -1])
        return logits, cache

    def chunked(params, cache, chunk, pos, **k):
        logits, cache = chunk_step(params, cache, chunk, pos, **k)
        last = [i for i, p in enumerate(prompts)
                if len(p) == pos + len(chunk)
                and np.array_equal(p[pos:], chunk)]
        if last:
            (i,) = last
            rows[(i, 0)] = np.asarray(logits[0, -1])
        return logits, cache

    def decode(params, cache, toks, poss, **k):
        logits, cache = slot_decode(params, cache, toks, poss, **k)
        for s in sched._pool.active():
            key = (index[s.req.rid], s.pos - s.req.prompt_len + 1)
            rows[key] = np.asarray(logits[s.index, 0, -1])
        return logits, cache

    engine.prefill, engine.prefill_chunk_step, engine.slot_decode = (
        whole, chunked, decode)
    try:
        done = sched.run()
    finally:
        del engine.prefill, engine.prefill_chunk_step, engine.slot_decode
    tokens = {index[r.rid]: tuple(r.generated) for r in done}
    assert len(tokens) == len(reqs)
    assert len(rows) == sum(len(t) for t in tokens.values())
    return tokens, rows


def assert_same_logits(got, want):
    """The same tokens, and every logits row behind them within
    :data:`LOGITS_ATOL`."""
    (got_toks, got_rows), (want_toks, want_rows) = got, want
    assert got_toks == want_toks
    assert got_rows.keys() == want_rows.keys()
    worst = max(float(np.abs(got_rows[k] - want_rows[k]).max())
                for k in want_rows)
    assert worst < LOGITS_ATOL, worst


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def baseline(engine):
    """Monolithic-prefill, monolithic-lane tokens (the PR-2 path)."""
    reqs = mixed_requests(engine)
    return reqs, serve(engine, reqs)


@pytest.fixture(scope="module")
def plain_engine():
    """The engine with uncompressed weights."""
    return make_engine(compress=False)


@pytest.fixture(scope="module")
def recorded(plain_engine):
    """The baseline's tokens and the logits rows behind them, served
    uncompressed."""
    reqs = mixed_requests(plain_engine)
    return reqs, serve_recording(plain_engine, reqs)


class TestTokenEquivalence:
    @pytest.mark.parametrize("chunk", [1, 3, 5, 64])
    def test_chunked_prefill_any_chunk_size(self, plain_engine, recorded,
                                            chunk):
        reqs, base = recorded
        assert_same_logits(
            serve_recording(plain_engine, reqs, prefill_chunk=chunk), base)

    @pytest.mark.parametrize("page", [4, 8, 16, 32])
    def test_paged_kv_any_page_size(self, engine, baseline, page):
        """Any page size dividing slot_len — including one page == whole
        lane (page=32: slots are 32 long for the MIXED trace)."""
        reqs, base = baseline
        assert serve(engine, reqs, kv_page_size=page) == base

    def test_chunked_and_paged_combined(self, plain_engine, recorded):
        reqs, base = recorded
        assert_same_logits(serve_recording(
            plain_engine, reqs, prefill_chunk=3, kv_page_size=4), base)
        assert_same_logits(serve_recording(
            plain_engine, reqs, prefill_chunk=5, kv_page_size=8,
            mode="wave"), base)

    def test_prefill_budget_does_not_change_tokens(self, plain_engine,
                                                   recorded):
        reqs, base = recorded
        assert_same_logits(serve_recording(
            plain_engine, reqs, prefill_chunk=2, prefill_budget=16), base)

    def test_overcommitted_pool_defers_but_matches(self, engine, baseline):
        """A pool too small to back every slot admits fewer requests at a
        time (reservation gating) but generates identical tokens."""
        reqs, base = baseline
        # slots need up to 8 pages of 4; 9 usable pages < 2 slots x 8
        assert serve(engine, reqs, kv_page_size=4, kv_pages=10) == base

    @pytest.mark.parametrize("arch", ["gemma2-2b", "deepseek-v2-236b"])
    def test_windowed_and_mla_archs(self, arch):
        """Rolling-window (gemma2 local/global) and MLA latent caches:
        windowed leaves stay per-slot lanes, latent leaves page."""
        engine = make_engine(arch)
        rng = np.random.default_rng(3)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, L), g)
                for L, g in [(20, 6), (4, 3), (11, 8)]]
        base = serve(engine, reqs)
        assert serve(engine, reqs, prefill_chunk=6, kv_page_size=8) == base

    @pytest.mark.parametrize("arch", ["recurrentgemma-2b", "mamba2-780m"])
    def test_recurrent_arch_resumes_chunked_prefill(self, arch):
        """Recurrent blocks (RG-LRU / SSM) resume a prompt mid-cache by
        seeding their scan from the cached recurrent state: chunked
        prefill is supported and matches monolithic within
        :data:`LOGITS_ATOL`, with no downgrade warning (the suite
        escalates stray RuntimeWarnings to errors, so silence is asserted
        by construction)."""
        engine = make_engine(arch, compress=False)
        assert supports_chunked_prefill(engine.cfg)
        rng = np.random.default_rng(5)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, L), g)
                for L, g in [(9, 4), (4, 6), (13, 3)]]
        base = serve_recording(engine, reqs)
        for chunk in (1, 4, 64):
            assert_same_logits(
                serve_recording(engine, reqs, prefill_chunk=chunk), base)

    def test_multimodal_fallback_warns_once_with_reason(self):
        """The monolithic-prefill downgrade is never silent: the first
        Scheduler that hits it raises a RuntimeWarning naming the reason
        (supports_chunked_prefill=False — a multimodal prefix cannot
        resume a prompt mid-cache); later Schedulers of the same family
        stay quiet (warn-once) but still emit the note."""
        import warnings

        from repro.runtime import scheduler as sched_mod

        engine = make_engine("paligemma-3b")
        assert not supports_chunked_prefill(engine.cfg)
        sched_mod._FALLBACK_WARNED.clear()
        with pytest.warns(RuntimeWarning,
                          match="supports_chunked_prefill=False"):
            Scheduler(engine, prefill_chunk=4, emit=lambda s: None)
        with warnings.catch_warnings():
            warnings.simplefilter("error")     # a second warning -> fail
            notes = []
            sched = Scheduler(engine, prefill_chunk=4, emit=notes.append)
        assert sched.prefill_chunk is None
        assert any("monolithic" in n for n in notes)


class TestPageAllocator:
    def test_free_xor_allocated(self):
        a = PageAllocator(range(1, 9))
        assert a.reserve(5)
        got = [a.alloc() for _ in range(5)]
        assert len(set(got)) == 5                      # no double allocation
        assert a.n_free + a.n_allocated == a.total
        a.release(got[:2])
        assert a.n_free + a.n_allocated == a.total
        # released pages can be handed out again, still unique vs live ones
        assert a.reserve(2)
        again = [a.alloc() for _ in range(2)]
        assert not set(again) & set(got[2:])

    def test_reservation_gates_allocation(self):
        a = PageAllocator(range(4))
        assert a.reserve(3)
        assert not a.reserve(2)                        # only 1 unreserved
        assert a.reserve(1)
        assert a.available() == 0
        with pytest.raises(AssertionError):
            PageAllocator(range(2)).alloc()            # alloc w/o reserve

    def test_double_free_caught(self):
        """Releasing an id already on the free list must raise — a silent
        double free would put the page on the free list twice and hand it
        to two slots at once."""
        a = PageAllocator(range(4))
        a.reserve(1)
        pid = a.alloc()
        a.release([pid])
        with pytest.raises(ValueError, match="double free"):
            a.release([pid])

    def test_fragmented_free_list_keeps_reservations_infallible(self):
        """Interleaved admit/retire until the free list is riddled with
        holes: reservations must still make every subsequent alloc
        infallible (the mid-decode no-OOM guarantee does not depend on
        contiguity), and free xor allocated must hold throughout."""
        rng = np.random.default_rng(0)
        a = PageAllocator(range(1, 65))
        # deterministic fragmentation: admit 16 four-page requests (ids
        # hand out in order), then retire every other one — the free
        # list is now 8 disjoint runs with allocated pages between them
        assert a.reserve(64)
        groups = [[a.alloc() for _ in range(4)] for _ in range(16)]
        held: list[list[int]] = []
        for i, grp in enumerate(groups):
            if i % 2 == 0:
                a.release(grp)
            else:
                held.append(grp)
        free = sorted(a._free)
        assert any(b - c > 1 for c, b in zip(free, free[1:])), \
            "free list unexpectedly contiguous"
        # random admit/retire churn on top, invariants at every step
        for _ in range(300):
            if held and rng.random() < 0.5:
                a.release(held.pop(int(rng.integers(0, len(held)))))
            else:
                n = int(rng.integers(1, 6))
                if a.reserve(n):
                    held.append([a.alloc() for _ in range(n)])
            assert a.n_free + a.n_allocated == a.total
        # reserve every remaining page against the fragmented list, then
        # draw them all down: none may fail, none may be handed out twice
        n = a.available()
        assert n > 0 and a.reserve(n)
        got = [a.alloc() for _ in range(n)]
        live = set(got)
        for grp in held:
            live |= set(grp)
        assert len(got) == n and len(live) == a.n_allocated
        assert a.n_free == 0 and a.reserved == 0


class TestPoolInvariants:
    def test_no_page_leaked_after_retire(self, engine, baseline):
        reqs, _ = baseline
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=4, prefill_chunk=3)
        for r in reqs:
            sched.submit(*r)
        sched.run()
        pool = sched._pool
        assert pool.allocator.n_allocated == 0         # every page returned
        assert pool.allocator.reserved == 0            # every earmark undone
        assert pool.allocator.n_free == pool.allocator.total
        assert (pool.table == 0).all()                 # rows reset to dummy

    def test_tables_disjoint_during_serving(self, engine):
        """A physical page is owned by at most one slot at every decode
        step (cache reads can never cross into another slot's pages)."""
        rng = np.random.default_rng(11)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, L), g)
                for L, g in [(9, 6), (4, 8), (13, 3), (6, 5)]]
        sched = Scheduler(engine, batch_size=2, buckets=(16,),
                          kv_page_size=4)
        for r in reqs:
            sched.submit(*r)
        seen = []

        orig_step = sched._step

        def checked_step(pool, completed):
            live = pool.table[pool.table != 0]
            assert len(live) == len(set(live.tolist())), \
                f"page owned by two slots: {pool.table}"
            assert pool.allocator.n_allocated == len(live)
            seen.append(len(live))
            orig_step(pool, completed)

        sched._step = checked_step
        done = sched.run()
        assert len(done) == len(reqs) and seen and max(seen) > 0

    def test_short_requests_use_fewer_pages(self, engine):
        """Paged memory is per-request need, not per-pool worst case: a
        short request's slot allocates only the pages its positions
        reach."""
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=4, slot_len=32)
        sched.submit(np.arange(3) % engine.cfg.vocab_size, 2)    # short
        sched.submit(np.arange(20) % engine.cfg.vocab_size, 8)   # long
        sched.run()
        m = engine.metrics
        assert m.pages_total > 0
        # worst case would be 2 slots x 8 pages; the mixed pair peaks lower
        assert m.pages_in_use <= 8 + 2

    def test_grow_pages_keeps_decode_compile(self, engine):
        """Growing the physical pool re-traces only the page gather /
        scatter; the compiled vmapped decode step is untouched."""
        rng = np.random.default_rng(2)
        sched = Scheduler(engine, batch_size=2, buckets=(16,),
                          kv_page_size=4, kv_pages=5)
        sched.submit(rng.integers(0, engine.cfg.vocab_size, 8), 6)
        out1 = sched.run()
        assert len(out1) == 1
        n0 = engine._slot_decode_jit._cache_size()
        sched._pool.grow_pages(9)
        sched.submit(rng.integers(0, engine.cfg.vocab_size, 8), 6)
        sched.submit(rng.integers(0, engine.cfg.vocab_size, 8), 6)
        out2 = sched.run()
        assert len(out2) == 2
        assert engine._slot_decode_jit._cache_size() == n0
        assert sched._pool.allocator.n_allocated == 0

    def test_grow_after_fragmentation_keeps_decode_compile(self, engine):
        """Mixed-length requests retire at different times, scrambling
        the free list; growing the pool within ``kv_page_capacity``
        afterwards is pure free-list bookkeeping — no decode recompile —
        and serving through the fragmented, grown pool still completes
        with the reservation guarantee intact."""
        rng = np.random.default_rng(4)
        sched = Scheduler(engine, batch_size=2, buckets=(16,),
                          kv_page_size=4, kv_pages=9, kv_page_capacity=24)
        for length, gen in [(9, 2), (4, 7), (13, 3), (6, 5), (3, 8)]:
            sched.submit(rng.integers(0, engine.cfg.vocab_size, length),
                         gen)
        assert len(sched.run()) == 5
        pool = sched._pool
        n0 = engine._slot_decode_jit._cache_size()
        pool.grow_pages(20)            # within capacity: headroom only
        assert pool.page_capacity == 24
        assert engine._slot_decode_jit._cache_size() == n0
        for length, gen in [(8, 4), (5, 6), (12, 3)]:
            sched.submit(rng.integers(0, engine.cfg.vocab_size, length),
                         gen)
        assert len(sched.run()) == 3
        assert engine._slot_decode_jit._cache_size() == n0
        assert pool.allocator.n_allocated == 0
        assert pool.allocator.n_free == pool.allocator.total == 19

    def test_undersized_pool_raises_instead_of_spinning(self, engine):
        """A pool that cannot back even one full slot is rejected up
        front (otherwise admission would defer forever)."""
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          kv_page_size=4, kv_pages=3, slot_len=32)
        sched.submit(np.arange(20) % engine.cfg.vocab_size, 8)
        with pytest.raises(ValueError, match="cannot back"):
            sched.run()


class TestMetrics:
    def test_chunk_and_page_counters(self, engine):
        engine.metrics = type(engine.metrics)()
        rng = np.random.default_rng(13)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, 10), 4)
                for _ in range(3)]
        serve(engine, reqs, prefill_chunk=4, kv_page_size=4)
        m = engine.metrics
        # 10-token prompts in 4-token chunks -> 3 chunks each
        assert m.prefill_chunks == 9
        assert m.prefill_chunk_tokens == 30
        assert m.pages_total > 0
        assert 0.0 < m.page_occupancy() <= 1.0
        assert "chunks" in m.stats_line() and "pages" in m.stats_line()
