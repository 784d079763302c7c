"""Observability layer: histogram math vs numpy oracles, the
Prometheus registry round-trip, Chrome-trace well-formedness, serving
span trees (every admitted request retires exactly once, spans nest,
timestamps monotone), windowed stats-line semantics, token-identity
with telemetry on vs off, and the capacity-autotune knee.

Serving tests run the gathered backend (the pure-jnp oracle), except
``TestProfilerSpans``, which serves the ``pallas_paged`` mixed step
(interpret mode) under the JAX profiler and reads the phase spans back
from its host plane.
"""

import glob
import json
import math

import jax
import numpy as np
import pytest

from repro.models.api import get_model
from repro.runtime import (NULL_TELEMETRY, DecodeTileCache, Histogram,
                           MetricsRegistry, Scheduler, ServeEngine,
                           ServeMetrics, Telemetry, Tracer, WeightStore,
                           find_knee, parse_prom, recommend_store_capacity,
                           sweep_store)
from repro.runtime.telemetry import (NULL_TRACER, PID_ENGINE, PID_REQUEST,
                                     NullTelemetry)
from tests.test_models import reduced

# ---------------------------------------------------------------------------
# histogram math vs numpy oracles
# ---------------------------------------------------------------------------

BUCKET_RATIO = 10 ** (1 / 5)      # default per_decade=5 -> one-bucket error


class TestHistogram:
    def test_counts_sum_and_moments(self):
        h = Histogram()
        vals = [1e-4, 3e-3, 3e-3, 0.5, 2.0]
        for v in vals:
            h.record(v)
        assert h.n == len(vals) == sum(h.counts)
        assert h.total == pytest.approx(sum(vals))
        assert h.mean() == pytest.approx(np.mean(vals))
        assert h.min == min(vals) and h.max == max(vals)

    def test_empty(self):
        h = Histogram()
        assert h.n == 0
        assert h.mean() == 0.0
        assert h.percentile(50) == 0.0

    def test_single_value_clamps_to_it(self):
        h = Histogram()
        h.record(0.0371)
        for p in (1, 50, 99, 100):
            assert h.percentile(p) == 0.0371

    def test_overflow_bucket_reports_max(self):
        h = Histogram(lo=1e-6, hi=120.0)
        h.record(500.0)           # above the largest edge
        h.record(900.0)
        assert h.counts[-1] == 2
        assert h.percentile(99) == 900.0

    def test_underflow_lands_in_bucket_zero(self):
        h = Histogram(lo=1e-6)
        h.record(1e-9)
        assert h.counts[0] == 1
        assert h.percentile(50) == pytest.approx(1e-9)   # clamped to min

    @pytest.mark.parametrize("p", [50, 90, 99])
    def test_percentile_vs_numpy_exact_rank(self, p):
        """The estimate must land within one bucket ratio of the exact
        rank-based percentile — the constant relative error the
        geometric bucket edges guarantee."""
        rng = np.random.default_rng(0)
        vals = np.exp(rng.normal(-4.0, 1.2, size=5000))   # ~ms-scale
        h = Histogram()
        for v in vals:
            h.record(float(v))
        exact = float(np.sort(vals)[max(1, math.ceil(p / 100 * len(vals)))
                                    - 1])
        est = h.percentile(p)
        assert exact / BUCKET_RATIO <= est <= exact * BUCKET_RATIO

    def test_estimate_always_inside_value_range(self):
        rng = np.random.default_rng(1)
        h = Histogram()
        vals = rng.uniform(1e-5, 10.0, 200)
        for v in vals:
            h.record(float(v))
        for p in (0.1, 25, 50, 75, 99.9):
            assert vals.min() <= h.percentile(p) <= vals.max()


# ---------------------------------------------------------------------------
# metrics registry -> Prometheus text -> parse_prom round-trip
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_round_trip(self):
        reg = MetricsRegistry()
        state = {"c": 7, "g": 0.25}
        reg.counter("things_total", lambda: state["c"], "things done")
        reg.gauge("fullness", lambda: state["g"])
        out = parse_prom(reg.render())
        assert out[("repro_things_total", "")] == 7
        assert out[("repro_fullness", "")] == 0.25
        state["c"] = 9                      # pull-based: re-render sees it
        assert parse_prom(reg.render())[("repro_things_total", "")] == 9

    def test_histogram_render_cumulative(self):
        reg = MetricsRegistry()
        h = Histogram()
        for v in (1e-4, 1e-4, 0.01, 5.0):
            h.record(v)
        reg.histogram("lat_seconds", h, "latency")
        out = parse_prom(reg.render())
        buckets = [(k, v) for k, v in out.items()
                   if k[0] == "repro_lat_seconds_bucket"]
        # cumulative and capped by the +Inf bucket == count
        vals = [v for _, v in buckets]
        assert vals == sorted(vals)
        assert out[("repro_lat_seconds_bucket", 'le="+Inf"')] == 4
        assert out[("repro_lat_seconds_count", "")] == 4
        assert out[("repro_lat_seconds_sum", "")] == pytest.approx(
            h.total)

    def test_rejects_bad_and_duplicate_names(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad name", lambda: 0)
        reg.counter("ok_total", lambda: 0)
        with pytest.raises(ValueError):
            reg.counter("ok_total", lambda: 0)

    def test_parse_prom_rejects_malformed(self):
        with pytest.raises(ValueError):
            parse_prom("this is not prometheus\n")
        with pytest.raises(ValueError):
            parse_prom("metric_name not_a_number\n")
        assert parse_prom("# just a comment\n\n") == {}

    def test_sample_scalars_only(self):
        reg = MetricsRegistry()
        reg.counter("a_total", lambda: 3)
        reg.histogram("h_seconds", Histogram())
        assert reg.sample() == {"repro_a_total": 3.0}


# ---------------------------------------------------------------------------
# tracer: chrome JSON round-trip
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_and_instant_round_trip(self, tmp_path):
        tr = Tracer()
        tr.name_track(PID_REQUEST, 3, "request 3")
        with tr.span(PID_ENGINE, 0, "phase", k=1):
            tr.instant(PID_REQUEST, 3, "mark")
        obj = json.loads(json.dumps(tr.chrome()))     # JSON round-trip
        evs = obj["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        inst = [e for e in evs if e["ph"] == "i"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert len(spans) == 1 and spans[0]["name"] == "phase"
        assert spans[0]["dur"] >= 0 and spans[0]["ts"] >= 0
        assert spans[0]["args"] == {"k": 1}
        assert len(inst) == 1 and inst[0]["s"] == "t"
        # metadata names both the processes and the request track
        assert {(m["name"], m["pid"]) for m in meta} >= {
            ("process_name", PID_REQUEST), ("process_name", PID_ENGINE),
            ("thread_name", PID_REQUEST)}
        # file export self-loads
        p = tmp_path / "trace.json"
        tr.write_chrome(p)
        assert json.loads(p.read_text())["traceEvents"]
        pl = tmp_path / "trace.jsonl"
        tr.write_jsonl(pl)
        assert all(json.loads(line)
                   for line in pl.read_text().splitlines())

    def test_instant_inside_span_window(self):
        tr = Tracer()
        with tr.span(PID_ENGINE, 0, "outer"):
            tr.instant(PID_ENGINE, 0, "inside")
        span = next(e for e in tr.events if e["ph"] == "X")
        mark = next(e for e in tr.events if e["ph"] == "i")
        assert span["ts"] <= mark["ts"] <= span["ts"] + span["dur"]


class TestNullPaths:
    def test_null_telemetry_is_free_and_silent(self):
        tel = NULL_TELEMETRY
        assert isinstance(tel, NullTelemetry)
        assert tel.tracing is False and tel.tracer is NULL_TRACER
        ctx = tel.timed("anything", slot=1)
        assert tel.timed("other") is ctx       # one shared null context
        with ctx:
            pass
        assert tel.phases == {}

    def test_untraced_telemetry_keeps_histograms_only(self):
        tel = Telemetry(trace=False)
        with tel.timed("work"):
            pass
        assert tel.tracing is False
        assert tel.phases["work"].n == 1

    def test_traced_telemetry_emits_engine_span(self):
        tel = Telemetry(trace=True)
        with tel.timed("work", detail=2):
            pass
        (ev,) = tel.tracer.events
        assert ev["name"] == "work" and ev["pid"] == PID_ENGINE
        assert ev["args"] == {"detail": 2}
        assert tel.phases["work"].n == 1

    def test_annotate_adds_args_once_under_way(self):
        tel = Telemetry(trace=True)
        with tel.timed("work", detail=2) as span:
            span.annotate(width=64)
        (ev,) = tel.tracer.events
        assert ev["args"] == {"detail": 2, "width": 64}
        with NULL_TELEMETRY.timed("work") as span:
            span.annotate(width=64)            # the null context ignores it


# ---------------------------------------------------------------------------
# windowed stats-line semantics
# ---------------------------------------------------------------------------

class TestWindows:
    def test_first_window_is_lifetime_then_deltas(self):
        m = ServeMetrics()
        m.record_decode_step(4, 0.5, n_slots=4)
        w1 = m.window()
        assert w1["slot_steps"] == 4 and w1["decode_s"] == 0.5
        m.record_decode_step(2, 0.25, n_slots=4)
        w2 = m.window()
        assert w2["slot_steps"] == 2 and w2["decode_s"] == 0.25
        assert m.slot_steps == 6               # lifetime counters intact
        assert m.window()["slot_steps"] == 0   # empty window

    def test_stats_line_reports_window_rate(self):
        m = ServeMetrics()
        m.record_decode_step(10, 1.0, n_slots=10)
        m.window()                              # close the first window
        m.record_decode_step(1, 1.0, n_slots=10)
        line = m.stats_line()
        assert "1.0 tok/s" in line              # window rate, not (11/2)
        assert "tokens 11" in line              # lifetime total stays

    def test_stats_line_has_latency_percentiles(self):
        m = ServeMetrics()
        m.record_ttft(0.01)
        m.tpot_hist.record(0.002)
        line = m.stats_line()
        assert "ttft p50" in line and "tpot p50" in line

    def test_cache_hit_rate_windowed(self):
        m = ServeMetrics()
        cache = DecodeTileCache()
        cache.get_or_decode(("k",), lambda: 1, nbytes=8)    # miss
        m.window(cache)
        cache.get_or_decode(("k",), lambda: 1, nbytes=8)    # hit
        assert "hit-rate 100.0%" in m.stats_line(cache)


# ---------------------------------------------------------------------------
# serving span trees + prometheus (gathered backend -> tier-1)
# ---------------------------------------------------------------------------

REQS = [(5, 4), (11, 2), (3, 5)]


def make_engine(telemetry=None):
    cfg = reduced("minitron-8b")
    params = jax.tree_util.tree_map(
        np.asarray, get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    return ServeEngine(cfg, params, compress=True, telemetry=telemetry)


def serve(engine, reqs, **kw):
    kw.setdefault("batch_size", 2)
    kw.setdefault("buckets", (16,))
    sched = Scheduler(engine, **kw)
    rids = [sched.submit(np.asarray(p), g).rid for p, g in reqs]
    done = {r.rid: r for r in sched.run()}
    assert len(done) == len(reqs)
    return rids, [tuple(done[rid].generated) for rid in rids]


@pytest.fixture(scope="module")
def reqs():
    rng = np.random.default_rng(5)
    return [(rng.integers(0, 128, L), g) for L, g in REQS]


@pytest.fixture(scope="module")
def baseline(reqs):
    _, toks = serve(make_engine(), reqs,
                    prefill_chunk=4, kv_page_size=8)
    return toks


@pytest.fixture(scope="module")
def traced(reqs):
    tel = Telemetry(trace=True)
    engine = make_engine(telemetry=tel)
    rids, toks = serve(engine, reqs, prefill_chunk=4, kv_page_size=8)
    return engine, tel, rids, toks


class TestServingSpans:
    def test_tokens_identical_with_telemetry(self, baseline, traced):
        """The acceptance invariant: telemetry observes, never steers."""
        assert traced[3] == baseline

    def test_every_request_retires_exactly_once(self, traced, reqs):
        _, tel, rids, _ = traced
        evs = tel.tracer.chrome()["traceEvents"]
        req_evs = [e for e in evs
                   if e.get("pid") == PID_REQUEST and e["ph"] != "M"]
        by_name: dict = {}
        for e in req_evs:
            by_name.setdefault(e["name"], []).append(e)
        n = len(reqs)
        assert len(by_name["queued"]) == n
        assert len(by_name["request"]) == n
        assert len(by_name["admitted"]) == n
        assert len(by_name["retired"]) == n
        # one lifecycle per rid, on that rid's own track
        for name in ("queued", "request", "admitted", "retired"):
            assert sorted(e["tid"] for e in by_name[name]) == sorted(rids)

    def test_spans_nest_and_timestamps_monotone(self, traced):
        _, tel, rids, _ = traced
        evs = tel.tracer.chrome()["traceEvents"]
        eps = 1.0                                         # 1 us slack
        for rid in rids:
            track = [e for e in evs
                     if e.get("pid") == PID_REQUEST and e.get("tid") == rid
                     and e["ph"] != "M"]
            get = {e["name"]: e for e in track if e["ph"] == "X"}
            req, queued = get["request"], get["queued"]
            assert req["ts"] >= 0 and req["dur"] >= 0
            # queued starts the request span and ends inside it
            assert abs(queued["ts"] - req["ts"]) <= eps
            end = req["ts"] + req["dur"] + eps
            assert queued["ts"] + queued["dur"] <= end
            # every span/instant on the track lies inside [start, end]
            for e in track:
                assert req["ts"] - eps <= e["ts"] <= end
                if e["ph"] == "X":
                    assert e["ts"] + e["dur"] <= end
            # decode follows admission: first_token after queued ends
            if "decode" in get:
                assert get["decode"]["ts"] >= queued["ts"] + queued["dur"] \
                    - eps

    def test_chunk_spans_cover_each_prompt(self, traced, reqs):
        _, tel, rids, _ = traced
        evs = tel.tracer.events
        for rid, (prompt, _) in zip(rids, reqs):
            chunks = [e for e in evs
                      if e.get("tid") == rid and e["ph"] == "X"
                      and e["name"] == "prefill_chunk"]
            assert sum(e["args"]["tokens"] for e in chunks) == len(prompt)
            cursors = [e["args"]["cursor"] for e in chunks]
            assert cursors == sorted(cursors)     # chunks advance in order

    def test_engine_phase_spans_present(self, traced):
        _, tel, _, _ = traced
        names = {e["name"] for e in tel.tracer.events
                 if e["pid"] == PID_ENGINE and e["ph"] == "X"}
        assert {"decode", "prefill"} <= names
        assert {"admit", "decode", "prefill"} <= set(tel.phases)

    def test_latency_histograms_filled(self, traced, reqs):
        engine, _, _, _ = traced
        m = engine.metrics
        assert m.ttft_hist.n == len(reqs)
        assert m.e2e_hist.n == len(reqs)
        assert m.tpot_hist.n == sum(1 for _, g in REQS if g > 1)
        assert m.chunk_hist.n == m.prefill_chunks
        assert m.step_hist.n == m.decode_steps

    def test_prometheus_parses_and_counters_monotone(self, traced, reqs):
        engine, _, _, _ = traced
        first = parse_prom(engine.render_prom())
        serve(engine, reqs, prefill_chunk=4, kv_page_size=8)
        second = parse_prom(engine.render_prom())
        monotone = [k for k in first
                    if k[0].endswith(("_total", "_count", "_bucket"))
                    or k[1].startswith("le=")]
        assert monotone
        for k in monotone:
            assert second[k] >= first[k], k
        # the scrape covers serving + cache + store + phase families
        fams = {k[0] for k in second}
        assert "repro_tokens_generated_total" in fams
        assert "repro_cache_hits_total" in fams
        assert "repro_store_prefetch_dispatched_total" in fams
        assert any(f.startswith("repro_phase_") for f in fams)


# ---------------------------------------------------------------------------
# phase spans in a JAX profiler trace (pallas_paged mixed step)
# ---------------------------------------------------------------------------

STEP_CHILDREN = ("mixed_step.prepare", "mixed_step.dispatch",
                 "mixed_step.wait", "mixed_step.commit")
PROGRAM_SPANS = ("admit", "admit.walk", "mixed_step",
                 "weights.materialize") \
    + STEP_CHILDREN


@pytest.fixture(scope="module")
def profiled(reqs, tmp_path_factory):
    """The mixed-step path served twice -- telemetry off, then telemetry
    on under the JAX profiler -> (off tokens, on tokens, engine, tel,
    the program's host spans [(name, start, end)] from the profile)."""
    from jax.profiler import ProfileData
    kw = dict(prefill_chunk=4, kv_page_size=8, attn_backend="pallas_paged")
    _, off = serve(make_engine(), reqs, **kw)
    tel = Telemetry()
    engine = make_engine(telemetry=tel)
    log_dir = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(log_dir)):
        _, on = serve(engine, reqs, **kw)
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    spans = [(ev.name, ev.start_ns, ev.end_ns)
             for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name in PROGRAM_SPANS]
    return off, on, engine, tel, spans


def _inside(span, outers):
    _, a, b = span
    return any(oa <= a and b <= ob for _, oa, ob in outers)


def test_store_walk_counters_count_every_tile():
    """One add per walk and per layer, yet every tile the first walk
    looks up is counted (tensors of many tiles each); on the unchanged
    unbounded cache the later walks are memoised, look nothing up and
    count in ``memo_walks``.  Prometheus reads the same counters."""
    rng = np.random.default_rng(0)
    params = {"mlp": {"up": rng.standard_normal((96, 256), np.float32),
                      "down": rng.standard_normal((256, 96), np.float32)}}
    store = WeightStore()
    store.register_model("m", params)
    n_tiles = store.n_tiles("m")
    assert n_tiles > 2 * len(store.layers("m"))
    for walks in (1, 2, 3):
        store.materialize("m")
        assert (store.walks, store.walk_tiles, store.memo_walks) == \
            (walks, n_tiles, walks - 1)
    prom = parse_prom(ServeMetrics().render_prom(store=store))
    assert prom[("repro_store_walks_total", "")] == 3
    assert prom[("repro_store_walk_tiles_total", "")] == n_tiles
    assert prom[("repro_store_memo_walks_total", "")] == 2


@pytest.mark.pallas
class TestProfilerSpans:
    def test_tokens_identical_under_profiler(self, profiled):
        off, on, _, _, _ = profiled
        assert on == off

    def test_step_children_nest_in_mixed_step(self, profiled):
        _, _, _, tel, spans = profiled
        steps = [s for s in spans if s[0] == "mixed_step"]
        assert len(steps) == tel.phases["mixed_step"].n > 0
        for name in STEP_CHILDREN:
            mine = [s for s in spans if s[0] == name]
            assert len(mine) == len(steps), name
            assert all(_inside(s, steps) for s in mine), name
        # each step's children run in order: prepare, dispatch, wait,
        # commit
        for _, a, b in steps:
            kids = sorted((s for s in spans if s[0] in STEP_CHILDREN
                           and a <= s[1] and s[2] <= b),
                          key=lambda s: s[1])
            assert tuple(k[0] for k in kids) == STEP_CHILDREN

    def test_weight_walks_under_admit_and_mixed_step(self, profiled, reqs):
        _, _, _, _, spans = profiled
        walks = [s for s in spans if s[0] == "weights.materialize"]
        admits = [s for s in spans if s[0] == "admit"]
        steps = [s for s in spans if s[0] == "mixed_step"]
        under_admit = [w for w in walks if _inside(w, admits)]
        # each admission's walk also sits in its own admit.walk phase
        admit_walks = [s for s in spans if s[0] == "admit.walk"]
        assert len(admit_walks) == len(reqs)
        assert all(_inside(w, admit_walks) for w in under_admit)
        under_step = [w for w in walks if _inside(w, steps)]
        assert len(under_admit) == len(reqs)      # one per admission
        assert len(under_step) == len(steps)      # one per step
        assert len(under_admit) + len(under_step) == len(walks)

    def test_store_counts_walks_and_tiles(self, profiled, reqs):
        _, _, engine, tel, _ = profiled
        store = engine.store
        assert store.walks == len(reqs) + tel.phases["mixed_step"].n
        # the unbounded cache changes only in the first walk (all
        # misses): that walk looks every tile up, every later one is
        # memoised and looks up none
        assert store.walk_tiles == store.n_tiles(engine.model_id)
        assert store.memo_walks == store.walks - 1
        # the engine's metrics window saw every walk (the store was
        # built with this engine, so no walk predates the window)
        assert (engine.metrics.weight_walks,
                engine.metrics.weight_walk_tiles) == (store.walks,
                                                      store.walk_tiles)
        prom = parse_prom(engine.render_prom())
        assert prom[("repro_store_walks_total", "")] == store.walks
        assert prom[("repro_store_walk_tiles_total", "")] == \
            store.walk_tiles
        assert prom[("repro_store_memo_walks_total", "")] == \
            store.memo_walks


# ---------------------------------------------------------------------------
# capacity autotune
# ---------------------------------------------------------------------------

class TestAutotune:
    def test_find_knee_picks_cliff_not_max_capacity(self):
        caps = [10, 20, 30, 40, 50]
        rates = [0.05, 0.10, 0.80, 0.81, 0.82]
        assert find_knee(caps, rates) == 2     # knee at the cliff

    def test_find_knee_respects_tolerance(self):
        caps = [10, 20, 30]
        rates = [0.10, 0.70, 0.80]             # cliff at 1, but 0.70 is
        assert find_knee(caps, rates, tolerance=0.02) == 2   # too far off
        assert find_knee(caps, rates, tolerance=0.15) == 1

    def test_find_knee_guarantee(self):
        rng = np.random.default_rng(2)
        for _ in range(20):
            rates = list(rng.uniform(0, 1, 6))
            i = find_knee(list(range(6)), rates, tolerance=0.02)
            assert rates[i] >= max(rates) - 0.02

    def test_find_knee_staircase_prefers_latest_jump(self):
        """Equal-size jumps tie-break toward the *latest* riser: on a
        staircase curve Python's ``max()`` alone would return the first
        maximal jump — a capacity still inside the thrashing region."""
        caps = [10, 20, 30, 40]
        rates = [0.10, 0.40, 0.70, 1.00]       # three equal 0.30 jumps
        assert find_knee(caps, rates) == 3
        # a genuinely larger early jump still wins over later small ones
        assert find_knee([10, 20, 30], [0.0, 0.8, 0.81]) == 1

    def test_sweep_store_clamps_tiny_models(self):
        """A model whose working set rounds ``int(ws * frac)`` below one
        decoded tile must still sweep non-degenerate caches: every
        capacity is clamped up to the largest decoded tile, so the
        full-capacity point hits (steps-1)/steps instead of 0."""
        w = np.ones((4, 16), np.float32)       # tiny: one tile per layer
        store = WeightStore(DecodeTileCache())
        store.register_model("tiny", {"up": w}, select=lambda p, nd: True)
        caps, rates = sweep_store(store, "tiny", steps=8)
        tile = max(ts.c * ts.s * 4
                   for _, stack in store.layers("tiny").items()
                   for ts in [stack[0].ensure_tiled()])
        assert all(c >= tile for c in caps)
        assert rates[-1] == pytest.approx(7 / 8)
        rec = recommend_store_capacity(store, "tiny", steps=8)
        assert rec["capacity"] >= tile and rec["hit_rate"] > 0

    def test_find_knee_rejects_bad_input(self):
        with pytest.raises(ValueError):
            find_knee([1, 2], [0.5])
        with pytest.raises(ValueError):
            find_knee([], [])

    def test_recommend_store_capacity(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((64, 256)).astype(np.float32)
        store = WeightStore(DecodeTileCache())
        store.register_model("m", {"up": w}, select=lambda p, nd: True)
        rec = recommend_store_capacity(store, "m", steps=8)
        ws = store.decoded_bytes("m")
        assert rec["working_set"] == ws
        assert 0 < rec["capacity"] <= ws
        assert rec["capacity"] == ws * rec["fraction"] // 1 or \
            rec["capacity"] == int(ws * rec["fraction"])
        assert 0.0 <= rec["hit_rate"] <= rec["best_rate"] <= 1.0
        assert len(rec["capacities"]) == len(rec["rates"])
        # the cyclic scan at full capacity hits (steps-1)/steps
        assert rec["rates"][-1] == pytest.approx(7 / 8)
