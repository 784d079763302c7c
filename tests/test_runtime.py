"""Runtime subsystem tests: decode-cache policies + accounting invariants,
weight-store round-trips (cached tiles == direct fused kernel), slot-level
scheduler batching + mode equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression
from repro.kernels import ops
from repro.runtime import (DecodeTileCache, Scheduler, ServeEngine,
                           Telemetry, WeightStore)
from repro.runtime.decode_cache import POLICIES, LRUPolicy
from tests.test_models import reduced


def make_store(rng, d=72, f=256, layers=1, cache=None, cluster=False):
    params = {f"l{i}": {"mlp": {"up": rng.standard_normal(
        (d, f)).astype(np.float32)}} for i in range(layers)}
    store = WeightStore(cache if cache is not None else DecodeTileCache())
    store.register_model("m", params, cluster=cluster,
                         select=lambda p, nd: p.endswith("mlp/up"))
    return store, params


class TestDecodeTileCache:
    def test_hit_miss_accounting(self):
        c = DecodeTileCache()
        assert c.get("a") is None and c.misses == 1 and c.hits == 0
        c.put("a", np.zeros(4), streamed_bytes=100)
        assert c.bytes_streamed == 100
        assert c.get("a") is not None
        assert c.hits == 1 and c.bytes_avoided == 100
        assert c.hit_rate() == 0.5

    def test_lru_eviction_order(self):
        v = np.zeros(2, np.uint8)                      # 2 bytes each
        c = DecodeTileCache(capacity_bytes=4)          # holds two entries
        c.put("a", v)
        c.put("b", v)
        c.get("a")                                     # refresh a -> b is LRU
        c.put("c", v)                                  # evicts b, not a
        assert c.evictions == 1
        assert "a" in c and "c" in c and "b" not in c
        assert c.keys()[0] == "a"                      # c most recent

    def test_capacity_bound_and_oversize(self):
        v = np.zeros(8, np.uint8)
        c = DecodeTileCache(capacity_bytes=20)
        for k in range(4):
            c.put(k, v)
        assert c.resident_bytes <= 20 and len(c) == 2
        c.put("big", np.zeros(64, np.uint8))           # larger than capacity
        assert "big" not in c                          # never cached
        assert c.resident_bytes <= 20

    def test_zero_capacity_disables(self):
        c = DecodeTileCache(capacity_bytes=0)
        c.put("a", np.zeros(4))
        assert c.get("a") is None and c.misses == 1

    def test_get_or_decode(self):
        c = DecodeTileCache()
        calls = {"n": 0}

        def decode():
            calls["n"] += 1
            return np.ones(4)

        v1, hit1 = c.get_or_decode("k", decode, streamed_bytes=7)
        v2, hit2 = c.get_or_decode("k", decode, streamed_bytes=7)
        assert not hit1 and hit2 and calls["n"] == 1
        np.testing.assert_array_equal(v1, v2)
        assert c.bytes_streamed == 7 and c.bytes_avoided == 7


class TestEvictionPolicies:
    """Invariants every policy must hold, plus per-policy behaviour."""

    @pytest.mark.parametrize("policy", sorted(POLICIES))
    def test_invariants_under_random_stream(self, policy, rng):
        """resident <= capacity, resident == sum of live entry sizes,
        hits + misses == accesses, bytes_avoided monotone — after every
        single operation of a random access stream."""
        capacity = 64
        c = DecodeTileCache(capacity, policy=policy)
        last_avoided = 0
        universe = [f"k{i}" for i in range(24)]
        sizes = {k: int(rng.integers(1, 33)) for k in universe}
        for _ in range(600):
            key = universe[int(rng.integers(len(universe)))]
            if rng.random() < 0.5:
                c.get(key)
            else:
                c.put(key, np.zeros(sizes[key], np.uint8),
                      streamed_bytes=sizes[key])
            assert c.resident_bytes <= capacity
            assert c.resident_bytes == sum(
                sizes[k] for k in universe if k in c)
            assert c.hits + c.misses == c.accesses
            assert c.bytes_avoided >= last_avoided
            last_avoided = c.bytes_avoided
            assert sorted(c.keys()) == sorted(k for k in universe if k in c)

    @pytest.mark.parametrize("policy", sorted(POLICIES))
    def test_zero_capacity_zero_hit_rate(self, policy):
        c = DecodeTileCache(0, policy=policy)
        for i in range(20):
            c.put(i % 5, np.zeros(4, np.uint8))
            assert c.get(i % 5) is None
        assert c.hit_rate() == 0.0 and len(c) == 0

    @pytest.mark.parametrize("policy", sorted(POLICIES))
    def test_update_existing_key_exact_accounting(self, policy):
        """Regression: re-inserting a key must replace it exactly — the old
        nbytes released, never double-counted against capacity."""
        c = DecodeTileCache(100, policy=policy)
        c.put("a", np.zeros(40, np.uint8))
        c.put("b", np.zeros(30, np.uint8))
        assert c.resident_bytes == 70
        c.put("a", np.zeros(40, np.uint8))       # same size re-insert
        assert c.resident_bytes == 70 and len(c) == 2
        assert c.evictions == 0                  # a 2nd 40 would have evicted
        c.put("a", np.zeros(10, np.uint8))       # shrink in place
        assert c.resident_bytes == 40
        c.put("a", np.zeros(60, np.uint8))       # grow in place, still fits
        assert c.resident_bytes == 90 and c.evictions == 0
        c.put("a", np.zeros(200, np.uint8))      # grow past capacity:
        assert "a" not in c                      # dropped, bytes released
        assert c.resident_bytes == 30 and len(c) == 1

    def test_lfu_keeps_frequent_over_recent(self):
        v = np.zeros(2, np.uint8)
        c = DecodeTileCache(4, policy="lfu")
        c.put("hot", v)
        for _ in range(5):
            c.get("hot")
        c.put("cold1", v)
        c.put("cold2", v)                        # evicts cold1, not hot
        assert "hot" in c and "cold2" in c and "cold1" not in c

    def test_freq_prior_pins_hot_through_cold_scan(self):
        """The paper-skew policy: seeded-hot tiles survive a one-off cold
        scan that flushes LRU completely."""
        v = np.zeros(2, np.uint8)
        hot = [("hot", i) for i in range(4)]
        for policy, expect_hot in (("freq", True), ("lru", False)):
            c = DecodeTileCache(10, policy=policy)
            for k in hot:
                c.seed_frequency(k, 100.0)
            for k in hot:
                c.put(k, v)
            for i in range(40):                  # cold scan, each key once
                c.put(("cold", i), v)
            resident = [k in c for k in hot]
            assert all(resident) == expect_hot
            if expect_hot:                       # hot re-access hits
                hits_before = c.hits
                for k in hot:
                    assert c.get(k) is not None
                assert c.hits == hits_before + len(hot)


class TestWeightStore:
    def test_lazy_tiling(self, rng):
        store, _ = make_store(rng)
        (layer,) = [l for ls in store.layers("m").values() for l in ls]
        assert layer.tiled is None                     # stream-only storage
        store.materialize("m")
        assert layer.tiled is not None                 # tiled on first use

    @pytest.mark.parametrize("cluster", [False, True])
    def test_reconstruction_matches_offline_decompress(self, rng, cluster):
        store, params = make_store(rng, cluster=cluster)
        w = params["l0"]["mlp"]["up"]
        rec = np.asarray(store.materialize("m")["l0"]["mlp"]["up"])
        (layer,) = [l for ls in store.layers("m").values() for l in ls]
        bits = compression.decompress(layer.ct)        # stream-path oracle
        expect = ((bits * 2.0 - 1.0) * layer.scale[:, None]).T
        np.testing.assert_array_equal(rec, expect.astype(np.float32))
        if not cluster:                                # lossless: exact signs
            np.testing.assert_array_equal(rec == 0, np.zeros_like(w, bool))
            np.testing.assert_array_equal(np.signbit(rec), np.signbit(
                np.where(w >= 0, 1.0, -1.0)))

    def test_cached_tiles_match_direct_fused_kernel(self, rng):
        """Round trip: cache-served reconstruction == fused Pallas decode+GEMM
        bit-for-bit (same store, same bits)."""
        store, _ = make_store(rng, d=72, f=128)
        words, tables, meta = store.fused_operands("m", "l0/mlp/up")
        x = rng.standard_normal((5, 72)).astype(np.float32)
        y_fused = np.asarray(ops.compressed_binary_matmul(
            jnp.asarray(x), words, tables, k_true=meta["k_true"],
            n_true=meta["n_true"], codes=meta["codes"]))
        w_rec = np.asarray(store.materialize("m")["l0"]["mlp"]["up"])
        signs = w_rec / np.asarray(meta["scale"])[None, :]   # +-1 matrix
        y_cached = np.where(x >= 0, 1.0, -1.0) @ signs
        np.testing.assert_array_equal(y_fused.astype(np.float32), y_cached)

    def test_tile_reuse_across_steps(self, rng):
        cache = DecodeTileCache()
        store, _ = make_store(rng, layers=2, cache=cache)
        store.materialize("m")
        misses_first = cache.misses
        assert cache.hits == 0 and misses_first == store.n_tiles("m")
        first = store.materialize("m")
        second = store.materialize("m")
        assert cache.misses == misses_first            # no re-decode
        assert cache.hits == 2 * misses_first
        # memoised device arrays are reused, not rebuilt
        for a, b in zip(jax.tree_util.tree_leaves(first),
                        jax.tree_util.tree_leaves(second)):
            assert a is b

    def test_multi_model_keys_dont_collide(self, rng):
        cache = DecodeTileCache()
        store = WeightStore(cache)
        for mid in ("a", "b"):
            store.register_model(
                mid, {"mlp": {"up": rng.standard_normal(
                    (36, 64)).astype(np.float32)}})
        store.materialize("a")
        store.materialize("b")
        assert cache.misses == store.n_tiles("a") + store.n_tiles("b")
        assert cache.hits == 0


class _CountingLRU(LRUPolicy):
    """LRU that counts the hits it is told of (the per-tile touches that
    order a bounded cache's evictions)."""

    def __init__(self):
        super().__init__()
        self.touches = 0

    def on_hit(self, key):
        self.touches += 1
        super().on_hit(key)


class TestMemoisedWalk:
    """``materialize`` on an unbounded cache whose ``version`` has not
    moved since the model's last full walk returns that walk's tree and
    credits its hits in bulk; every other walk looks every tile up."""

    def test_unchanged_unbounded_cache_serves_the_recorded_tree(self, rng):
        tel = Telemetry(trace=True)
        cache = DecodeTileCache()
        store, _ = make_store(rng, layers=2, cache=cache)
        store.telemetry = tel
        store.materialize("m")                 # every tile misses once
        n, version = store.n_tiles("m"), cache.version
        first = store.materialize("m")
        second = store.materialize("m")
        assert second is first and cache.version == version
        assert (store.walks, store.walk_tiles, store.memo_walks) == \
            (3, n, 2)
        assert (cache.hits, cache.misses) == (2 * n, n)
        assert cache.bytes_avoided == 2 * cache.bytes_streamed > 0
        assert [e["args"]["memo"] for e in tel.tracer.events
                if e["name"] == "weights.materialize"] == [False, True, True]

    @pytest.mark.parametrize("mutation", ["other_model_miss", "clear",
                                          "put"])
    def test_any_mutation_forces_a_full_walk(self, rng, mutation):
        cache = DecodeTileCache()
        store, _ = make_store(rng, layers=2, cache=cache)
        store.register_model("other", {"mlp": {"up": rng.standard_normal(
            (36, 64)).astype(np.float32)}})
        n = store.n_tiles("m")
        store.materialize("m")
        memo = store.materialize("m")
        assert store.memo_walks == 1
        if mutation == "other_model_miss":
            store.materialize("other")
        elif mutation == "clear":
            cache.clear()
        else:
            cache.put(("m", "stray", 0), np.zeros(4, np.int32))
        tiles = store.walk_tiles
        full = store.materialize("m")
        assert store.walk_tiles - tiles == n and store.memo_walks == 1
        assert full is not memo                # the tree is built anew
        for a, b in zip(jax.tree_util.tree_leaves(memo),
                        jax.tree_util.tree_leaves(full)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            # after clear() every tile missed and every array is rebuilt
            assert (a is b) == (mutation != "clear")
        assert store.materialize("m") is full  # memoised again
        assert store.memo_walks == 2

    @pytest.mark.parametrize("fraction", [0.0, 0.5, 2.0])
    def test_bounded_cache_walks_every_tile(self, rng, fraction):
        """Capacity 0, a thrashing bound and one above the working set:
        every walk looks every tile up and tells the policy of each hit,
        so eviction order is what the per-tile walk makes."""
        policy = _CountingLRU()
        cache = DecodeTileCache(policy=policy)
        store, _ = make_store(rng, layers=2, cache=cache)
        cache.capacity_bytes = int(store.decoded_bytes("m") * fraction)
        n = store.n_tiles("m")
        for walks in (1, 2, 3):
            store.materialize("m")
            assert cache.accesses == walks * n
            assert store.walk_tiles == walks * n
        assert store.memo_walks == 0
        assert policy.touches == cache.hits
        if fraction > 1:
            assert cache.hits == 2 * n and cache.evictions == 0

    def test_served_tokens_and_cache_stats_match_a_bounded_cache(self):
        """The same requests through an unbounded cache (memoised walks)
        and a bounded one larger than the working set (per-tile walks):
        identical tokens and identical hit, miss and byte accounting."""
        cfg = reduced("minitron-8b")
        params = jax.tree_util.tree_map(
            np.asarray,
            __import__("repro.models.api", fromlist=["get_model"])
            .get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)))
        rng = np.random.default_rng(7)
        reqs = [(rng.integers(0, cfg.vocab_size, L), g)
                for L, g in [(5, 6), (9, 3), (4, 8)]]
        served = {}
        cap = None
        for bound in (False, True):
            engine = ServeEngine(cfg, params, compress=True,
                                 cache_bytes=cap)
            cap = 2 * engine.store.decoded_bytes(engine.model_id)
            sched = Scheduler(engine, batch_size=2, buckets=(16,))
            rids = [sched.submit(p, g).rid for p, g in reqs]
            done = {r.rid: tuple(r.generated) for r in sched.run()}
            stats = engine.cache.stats()
            served[bound] = ([done[r] for r in rids],
                             {k: stats[k] for k in ("hits", "misses",
                                                    "bytes_streamed",
                                                    "bytes_avoided")},
                             engine.store.memo_walks)
        (toks, stats, memo), (btoks, bstats, bmemo) = \
            served[False], served[True]
        assert toks == btoks and stats == bstats
        assert memo > 0 and bmemo == 0


class TestScheduler:
    @pytest.fixture(scope="class")
    def engine(self):
        cfg = reduced("minitron-8b")
        params = jax.tree_util.tree_map(
            np.asarray,
            __import__("repro.models.api", fromlist=["get_model"])
            .get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)))
        return ServeEngine(cfg, params, compress=True)

    def test_engine_compresses_scan_mlps(self, engine):
        assert engine.compressed
        assert engine.report["layers"] >= 2            # stacked repeats split

    def test_wave_serving_and_cache_hit_rate(self, engine):
        engine.cache.reset_counters()
        sched = Scheduler(engine, batch_size=2, log_every=0)
        rng = np.random.default_rng(1)
        for _ in range(2):
            sched.submit(rng.integers(0, engine.cfg.vocab_size, 8), 12)
        done = sched.run()
        assert len(done) == 2
        assert all(len(r.generated) == 12 and r.done for r in done)
        # decoded tiles are reused, not re-decoded per token
        assert engine.cache.hit_rate() >= 0.9
        assert engine.metrics.tokens_generated == 24

    def test_bucketing_splits_waves(self, engine):
        sched = Scheduler(engine, batch_size=4, buckets=(8, 16),
                          mode="wave")
        rng = np.random.default_rng(2)
        sched.submit(rng.integers(0, engine.cfg.vocab_size, 6), 2)
        sched.submit(rng.integers(0, engine.cfg.vocab_size, 12), 2)
        sched.submit(rng.integers(0, engine.cfg.vocab_size, 7), 2)
        waves_before = engine.metrics.waves
        done = sched.run()
        assert len(done) == 3
        # lengths 6 and 7 share the 8-bucket; 12 goes to the 16-bucket
        assert engine.metrics.waves - waves_before == 2

    def test_mode_and_order_equivalence(self, engine):
        """Same request set -> identical tokens under wave mode,
        continuous mode, and shuffled admission order: per-slot exact
        positions make generation independent of batch neighbours."""
        rng = np.random.default_rng(5)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, L), g)
                for L, g in [(5, 7), (8, 2), (11, 5), (6, 9)]]

        def serve(mode, order):
            sched = Scheduler(engine, batch_size=2, mode=mode,
                              buckets=(16,))
            rids = {}
            for i in order:
                rids[sched.submit(*reqs[i]).rid] = i
            done = sched.run()
            return {rids[r.rid]: tuple(r.generated) for r in done}

        wave = serve("wave", [0, 1, 2, 3])
        cont = serve("continuous", [0, 1, 2, 3])
        shuf = serve("continuous", [2, 0, 3, 1])
        assert wave == cont == shuf
        assert sorted(len(v) for v in wave.values()) == [2, 5, 7, 9]

    def test_admit_on_retire_raises_occupancy(self, engine):
        """Heterogeneous budgets: continuous batching refills retired
        slots mid-decode, finishing in fewer decode steps than wave mode
        while producing the same tokens."""
        rng = np.random.default_rng(6)
        reqs = [(rng.integers(0, engine.cfg.vocab_size, 6), g)
                for g in (2, 8, 3, 7)]
        stats = {}
        for mode in ("wave", "continuous"):
            sched = Scheduler(engine, batch_size=2, mode=mode)
            steps0 = engine.metrics.decode_steps
            slot0 = engine.metrics.slot_steps
            cap0 = engine.metrics.capacity_steps
            for r in reqs:
                sched.submit(*r)
            done = sched.run()
            assert len(done) == 4
            stats[mode] = (engine.metrics.decode_steps - steps0,
                           engine.metrics.slot_steps - slot0,
                           engine.metrics.capacity_steps - cap0)
        # same generated-token total, fewer decode steps, higher occupancy
        assert stats["continuous"][1] == stats["wave"][1]
        assert stats["continuous"][0] < stats["wave"][0]
        occ = {m: s[1] / s[2] for m, s in stats.items()}
        assert occ["continuous"] > occ["wave"]

    def test_serving_logits_match_direct_eval(self):
        """Bit-identical round trip at the logits level: scheduler serving
        on cache-reconstructed weights == a direct decode loop on offline
        stream-decompressed weights."""
        cfg = reduced("minitron-8b")
        from repro.models.api import get_model
        params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(1))
        engine = ServeEngine(cfg, params, compress=True)
        prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, 8)
        sched = Scheduler(engine, batch_size=1, buckets=(8,))
        req = sched.submit(prompt, 6)
        sched.run()

        # direct eval: same BNN cfg, weights rebuilt without the cache
        cfg_b = engine.cfg
        api = get_model(cfg_b)
        direct = {}
        for path, stack in engine.store.layers("lm").items():
            recs = []
            for layer in stack:
                bits = compression.decompress(layer.ct)
                recs.append((((bits * 2.0 - 1.0) * layer.scale[:, None]).T
                             ).astype(np.float32))
            direct[path] = np.stack(recs) if len(recs) > 1 else recs[0]

        def sub(p, leaf):
            name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in p)
            return jnp.asarray(direct[name]) if name in direct else leaf

        params_direct = jax.tree_util.tree_map_with_path(sub, params)
        cache = api.init_cache(cfg_b, 1, 8 + 6)
        toks = jnp.asarray(prompt[None].astype(np.int32))
        logits, kv = api.prefill(cfg_b, params_direct, toks, cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out = []
        for i in range(6):
            out.append(int(tok[0, 0]))
            logits, kv = api.decode_step(cfg_b, params_direct, kv, tok,
                                         jnp.int32(8 + i))
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        assert req.generated == out
