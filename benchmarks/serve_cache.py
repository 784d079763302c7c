"""Decode-tile cache benchmarks: capacity sweep + trace replay + slot batching.

Ten sections:

1. **Capacity sweep** (default): the paper's §IV cache cliff on a real
   WeightStore — during batched decoding every step touches every tile of
   every compressed layer (a cyclic scan), so an LRU cache smaller than the
   decoded working set thrashes to ~0% hit rate while one that covers it
   converges to (steps-1)/steps.

2. **Trace replay** (``--trace bursty``): a synthetic multi-tenant serving
   trace with bursty arrivals and Zipf-skewed tenant popularity (the
   serving-time analogue of the paper's §III-A sequence skew), replayed
   through :class:`DecodeTileCache` under every eviction policy at several
   capacities.  One hot tenant dominates accesses while cold tenants burst
   in and out; their full-model tile scans flush recency-based caches but
   not the FrequencyWeighted policy, whose victims are ranked by a prior
   seeded from the tenants' occurrence weights (the role
   ``core.frequency`` histograms play in the real store).

3. **Slot batching** (``--trace``/``--smoke``): the same bursty request
   mix served by the real scheduler on a reduced model in ``wave`` vs
   ``continuous`` mode — identical tokens, different occupancy, so
   slot-level admit-on-retire wins tokens/s.

4. **Chunked vs monolithic prefill** (``--trace``/``--smoke``): a mixed
   long/short prompt trace through the real scheduler — monolithic
   admission prefills a whole long prompt while every other lane waits;
   chunked prefill (+ paged KV) interleaves, so short requests' time to
   first token stops scaling with their neighbours' prompt lengths.
   Token-identical by assertion.

5. **Attention backends** (``--trace``/``--smoke``): the same request mix
   decoded under ``attn_backend="gathered"`` (copy each slot's pages into
   a contiguous view per step, two full cache copies) vs
   ``"pallas_paged"`` (the in-kernel paged-attention backend reads the
   page pool in place).  Token-identical by assertion; the table reports
   decode-step latency and the per-step KV bytes each backend moved /
   avoided.

6. **Capacity autotune** (``--autotune``): sweep a fine capacity grid
   over the replayed trace (synthetic, or ``--trace-file``), locate the
   hit-rate cliff, and print a recommended ``decode_cache`` capacity —
   the knee: the smallest capacity past the cliff within a small
   tolerance of the best measured hit rate (shared logic with the
   launcher's ``--cache-mb auto``: ``runtime.autotune.find_knee``).

7. **Telemetry** (``--trace``/``--smoke``): serve a small mix with
   request-lifecycle tracing on and validate the observability surface
   end to end — Chrome-trace JSON loads with admitted == retired spans,
   the Prometheus text parses with monotone counters across scrapes,
   and tokens are identical to a telemetry-off run.  ``--trace-out`` /
   ``--metrics-out`` additionally write (and re-validate) the files,
   which is what the CI smoke job does.

8. **KV page codec** (``--trace``/``--smoke``): the same request mix
   served with ``kv_codec="cluster"`` vs the fp pools under both
   attention backends.  Cluster stores paged K/V leaves as int8
   codebook codes plus a per-(page, token) f32 scale — >= 1.3x fewer
   resident pool bytes at equal page count by assertion — and the
   table reports the effective-capacity multiplier plus how many
   fully-backed slots one fixed HBM budget holds under each codec.

9. **Prefix sharing** (``--trace``/``--smoke``): the checked-in
   multi-tenant shared-prefix trace replayed with ``prefix_share`` off
   vs on — token-identical by assertion, with the accounting identity
   ``chunk_tokens(on) + tokens_reused == chunk_tokens(off)`` pinning
   that every reused token is prefill work the off run actually paid.

10. **Speculative decoding** (``--trace``/``--smoke``): the checked-in
    repetition-heavy trace (``benchmarks/traces/repetition.jsonl``)
    served with ``speculate="ngram"`` vs ``"off"`` across backend/codec
    cells — token-identical by assertion (greedy verification), drafts
    accepted and decode steps strictly reduced everywhere, and >= 1.2x
    tokens/s on the single-phase ``pallas_paged`` cell (asserted on the
    full run at the default seed).

``--out report.json`` dumps every section's headline numbers (tokens/s,
TTFT, hit/acceptance rates, compression multipliers) as one JSON report;
the checked-in ``BENCH_serve.json`` is generated this way and refreshed
by CI as a build artifact.

Real traffic traces: ``--trace-file path.jsonl`` replays a recorded
trace (one JSON object per line: ``arrival_time`` seconds, ``prompt_len``,
``decode_len``, ``tenant``) through the same policy sweep the synthetic
generator uses; tenant popularity for the FrequencyWeighted prior is
estimated from the trace itself.  A tiny sample lives at
``benchmarks/traces/sample.jsonl`` and is replayed by ``--smoke``.

``--seed`` seeds the synthetic trace generators (bursty arrivals and the
request mixes of the scheduler sections), so replays are reproducible
run-to-run and distinct seeds give distinct-but-reproducible traffic.

Run:  PYTHONPATH=src python benchmarks/serve_cache.py [--steps 24]
      PYTHONPATH=src python benchmarks/serve_cache.py --trace bursty
      PYTHONPATH=src python benchmarks/serve_cache.py \
          --trace-file benchmarks/traces/sample.jsonl
      PYTHONPATH=src python benchmarks/serve_cache.py --autotune
      PYTHONPATH=src python benchmarks/serve_cache.py --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time

import numpy as np

from repro.runtime import DecodeTileCache, WeightStore
from repro.runtime.autotune import DEFAULT_FRACTIONS, find_knee

SAMPLE_TRACE = pathlib.Path(__file__).parent / "traces" / "sample.jsonl"
SHARED_PREFIX_TRACE = (pathlib.Path(__file__).parent / "traces"
                       / "shared_prefix.jsonl")
REPETITION_TRACE = (pathlib.Path(__file__).parent / "traces"
                    / "repetition.jsonl")

# per-section headline numbers, dumped by --out as BENCH_serve.json
REPORT: dict = {}

LAYERS = 4
D, F = 288, 512
FRACTIONS = (0.1, 0.25, 0.5, 0.75, 1.0, 1.2)

TRACE_FRACTIONS = (0.15, 0.25, 0.4, 0.6, 1.0)
SMOKE_FRACTIONS = (0.25, 0.6, 1.0)
POLICY_NAMES = ("lru", "lfu", "freq")


def build_store(cache: DecodeTileCache, rng) -> WeightStore:
    """A stack of motif-structured binary MLP layers (C1-style skew)."""
    params = {}
    for i in range(LAYERS):
        motifs = rng.standard_normal((4, D)).astype(np.float32)
        base = motifs[rng.integers(0, 4, F)] * \
            rng.choice([-1.0, 1.0], F)[:, None]
        base += 0.08 * np.abs(base).mean() * rng.standard_normal((F, D))
        params[f"layer{i}"] = {"mlp": {"up": base.T.astype(np.float32)}}
    store = WeightStore(cache)
    store.register_model("bench", params,
                         select=lambda p, nd: p.endswith("mlp/up"))
    return store


def capacity_sweep(steps: int) -> None:
    rng = np.random.default_rng(0)
    probe = build_store(DecodeTileCache(), rng)
    working_set = probe.decoded_bytes("bench")
    n_tiles = probe.n_tiles("bench")
    print(f"{LAYERS} layers x ({F}x{D}), {n_tiles} decode tiles, "
          f"decoded working set {working_set / 1024:.0f} KiB, "
          f"{steps} decode steps\n")
    print(f"{'capacity':>10} {'frac':>5} | {'hit rate':>8} | "
          f"{'recon/s':>8} | {'streamed':>10} | {'evict':>6}")

    for frac in FRACTIONS:
        rng = np.random.default_rng(0)          # identical weights per run
        cache = DecodeTileCache(int(working_set * frac))
        store = build_store(cache, rng)
        t0 = time.monotonic()
        for _ in range(steps):                  # one materialise per step
            store.materialize("bench")
        dt = time.monotonic() - t0
        st = cache.stats()
        recon_s = steps * LAYERS / dt
        print(f"{cache.capacity_bytes:>10} {frac:>5.2f} | "
              f"{st['hit_rate'] * 100:>7.1f}% | {recon_s:>8.1f} | "
              f"{st['bytes_streamed']:>10} | {st['evictions']:>6}")


# ---------------------------------------------------------------------------
# trace replay: bursty multi-tenant arrivals over a tile universe
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceRequest:
    arrival: int        # earliest admission step
    tenant: int
    gen: int            # decode steps (tokens) the request runs for
    prompt_len: int = 8  # prompt tokens (trace-file ingestion records it)


@dataclasses.dataclass
class Trace:
    """Synthetic bursty serving trace over ``n_tenants`` tenant models."""

    requests: list
    n_tenants: int
    tiles_per_tenant: int
    tile_bytes: int
    popularity: np.ndarray      # per-tenant occurrence weight (Zipf)

    @property
    def total_bytes(self) -> int:
        return self.n_tenants * self.tiles_per_tenant * self.tile_bytes


def bursty_trace(rng, *, n_tenants: int = 8, tiles_per_tenant: int = 32,
                 tile_bytes: int = 4096, n_requests: int = 64,
                 burst: int = 4, gen_lo: int = 4, gen_hi: int = 24) -> Trace:
    """Bursty arrivals, Zipf tenant popularity (tenant 0 dominates).

    Requests arrive in bursts of ~``burst``; each picks a tenant from a
    Zipf(1.6) marginal, so one hot tenant carries most decode steps while
    cold tenants scan their whole tile set through the cache in short
    bursts — the access shape that separates frequency-aware eviction from
    recency-based eviction.
    """
    popularity = 1.0 / np.arange(1, n_tenants + 1) ** 1.6
    popularity /= popularity.sum()
    requests = []
    step = 0
    while len(requests) < n_requests:
        for _ in range(1 + rng.integers(0, burst)):
            if len(requests) >= n_requests:
                break
            tenant = int(rng.choice(n_tenants, p=popularity))
            gen = int(rng.integers(gen_lo, gen_hi + 1))
            requests.append(TraceRequest(step, tenant, gen))
        step += int(rng.integers(1, 7))         # gap until the next burst
    return Trace(requests, n_tenants, tiles_per_tenant, tile_bytes,
                 popularity)


def load_trace_file(path, *, time_step: float = 0.05,
                    tiles_per_tenant: int = 32,
                    tile_bytes: int = 4096) -> Trace:
    """Ingest a recorded serving trace (JSONL) into a :class:`Trace`.

    One JSON object per line with keys ``arrival_time`` (seconds from
    trace start), ``prompt_len``, ``decode_len``, and ``tenant`` (any
    hashable label; mapped to dense indices in order of first
    appearance).  ``time_step`` converts wall-clock arrivals into
    scheduler admission steps.  The FrequencyWeighted prior that the
    synthetic generator takes from its Zipf marginal is estimated here
    from the trace's own tenant frequencies — the serving-time stand-in
    for the paper's §III-A occurrence histogram.
    """
    tenants: dict = {}
    reqs = []
    for line in pathlib.Path(path).read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        row = json.loads(line)
        tenant = tenants.setdefault(row["tenant"], len(tenants))
        reqs.append(TraceRequest(
            arrival=int(float(row["arrival_time"]) / time_step),
            tenant=tenant,
            gen=int(row["decode_len"]),
            prompt_len=int(row["prompt_len"])))
    if not reqs:
        raise ValueError(f"empty trace file: {path}")
    counts = np.bincount([r.tenant for r in reqs],
                         minlength=len(tenants)).astype(np.float64)
    return Trace(reqs, len(tenants), tiles_per_tenant, tile_bytes,
                 counts / counts.sum())


def replay(trace: Trace, cache: DecodeTileCache, n_slots: int = 6) -> dict:
    """Serve the trace with continuous slots, touching every tile of a
    request's tenant each decode step (the materialize scan) -> stats."""
    if cache.policy.name == "freq":
        # the occurrence-count prior: tenant popularity is what the
        # compression-time core.frequency histograms encode in the store
        for m in range(trace.n_tenants):
            for t in range(trace.tiles_per_tenant):
                cache.seed_frequency((m, t), float(trace.popularity[m]))
    queue = sorted(trace.requests, key=lambda r: r.arrival)
    pending = list(queue)
    slots: list = [None] * n_slots   # (tenant, steps_left) per busy lane
    step = 0
    while pending or any(slots):
        for i in range(n_slots):     # admit-on-retire
            if slots[i] is None and pending and pending[0].arrival <= step:
                r = pending.pop(0)
                slots[i] = [r.tenant, r.gen]
        for i in range(n_slots):
            if slots[i] is None:
                continue
            tenant, _ = slots[i]
            for t in range(trace.tiles_per_tenant):
                cache.get_or_decode((tenant, t), lambda: True,
                                    nbytes=trace.tile_bytes,
                                    streamed_bytes=trace.tile_bytes)
            slots[i][1] -= 1
            if slots[i][1] <= 0:
                slots[i] = None
        step += 1
    return cache.stats()


def autotune_capacity(trace: Trace, policy: str = "freq",
                      tolerance: float = 0.02) -> int:
    """Sweep a fine capacity grid over ``trace`` and recommend the
    hit-rate-cliff knee.

    The cliff/knee logic is shared with the launcher's ``--cache-mb
    auto`` path (``runtime.autotune.find_knee``): the cliff is the
    largest hit-rate jump between consecutive capacities (the paper's
    §IV working-set threshold appearing at serving time); the knee is
    the smallest capacity at/after it within ``tolerance`` of the best
    measured rate — everything past it buys memory, not hits.  Returns
    the recommended capacity in bytes.
    """
    fractions = DEFAULT_FRACTIONS
    total = trace.total_bytes
    caps, rates = [], []
    print(f"capacity autotune ({policy} policy, "
          f"{len(trace.requests)} requests, "
          f"{total // 1024} KiB tile universe):\n")
    print(f"{'capacity':>10} {'frac':>5} | {'hit rate':>8}")
    for frac in fractions:
        cache = DecodeTileCache(int(total * frac), policy=policy)
        st = replay(trace, cache)
        caps.append(int(total * frac))
        rates.append(st["hit_rate"])
        print(f"{caps[-1]:>10} {frac:>5.2f} | {rates[-1] * 100:>7.1f}%")
    best = max(rates)
    jumps = [rates[i] - rates[i - 1] for i in range(1, len(rates))]
    cliff = int(np.argmax(jumps)) + 1 if jumps else 0
    knee = find_knee(caps, rates, tolerance=tolerance)
    print(f"\ncliff: {caps[cliff]} bytes "
          f"(+{jumps[cliff - 1] * 100:.1f} pts over the previous "
          f"capacity)" if jumps else "\nno cliff detected")
    print(f"recommended decode_cache capacity: {caps[knee]} bytes "
          f"({fractions[knee]:.2f}x of the decoded universe, "
          f"hit rate {rates[knee] * 100:.1f}%, within "
          f"{tolerance * 100:.0f} pts of best {best * 100:.1f}%)")
    return caps[knee]


def trace_replay(smoke: bool, trace: Trace | None = None,
                 label: str = "bursty", seed: int = 0) -> None:
    if trace is None:
        rng = np.random.default_rng(seed)
        trace = bursty_trace(rng, n_requests=24 if smoke else 64)
    fractions = SMOKE_FRACTIONS if smoke else TRACE_FRACTIONS
    total = trace.total_bytes
    hot_share = float(trace.popularity.max())
    print(f"{label} trace: {len(trace.requests)} requests over "
          f"{trace.n_tenants} tenants x {trace.tiles_per_tenant} tiles "
          f"({total // 1024} KiB universe), hot tenant carries "
          f"~{hot_share * 100:.0f}% of arrivals\n")
    print(f"{'capacity':>10} {'frac':>5} | " +
          " | ".join(f"{p:>6}" for p in POLICY_NAMES) + "   hit rate")
    worst = None
    for frac in fractions:
        rates = {}
        for policy in POLICY_NAMES:
            cache = DecodeTileCache(int(total * frac), policy=policy)
            st = replay(trace, cache)
            rates[policy] = st["hit_rate"]
        print(f"{int(total * frac):>10} {frac:>5.2f} | " +
              " | ".join(f"{rates[p] * 100:5.1f}%" for p in POLICY_NAMES))
        margin = rates["freq"] - rates["lru"]
        worst = margin if worst is None else min(worst, margin)
    print(f"\nFrequencyWeighted - LRU hit-rate margin, worst capacity: "
          f"{worst * 100:+.1f} pts")
    REPORT.setdefault("trace_replay", {})[label] = dict(
        requests=len(trace.requests),
        freq_minus_lru_worst_pts=round(worst * 100, 2))
    # the synthetic replay is fully deterministic (seeded trace, no
    # timing), so the paper-skew claim is a hard invariant CI can
    # enforce on the default seed; recorded traces and alternate seeds
    # carry no such guarantee and just report
    if label == "bursty" and seed == 0:
        assert worst >= 0, \
            f"FrequencyWeighted lost to LRU by {-worst * 100:.1f} pts"


# ---------------------------------------------------------------------------
# chunked vs monolithic prefill on a mixed long/short prompt trace
# ---------------------------------------------------------------------------

def _reduced_lm(vocab_size: int = 128):
    import jax
    from repro.configs.base import get_config
    from repro.models.api import get_model

    cfg = get_config("minitron-8b").scaled(
        dtype="float32", vocab_size=vocab_size, num_layers=2,
        scan_repeats=2, d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=128)
    params = jax.tree_util.tree_map(
        np.asarray, get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, params


def prefill_compare(smoke: bool, seed: int = 0) -> None:
    """Mixed long/short prompts: monolithic batch-1 prefill stalls every
    lane for a whole long prompt, chunked prefill interleaves the chunks
    with decode steps (round-robin across prefilling slots), so short
    requests reach their first token after their own chunks instead of
    queueing behind a long neighbour's full prompt.  Tokens are identical
    by construction; the table shows what changes: time-to-first-token of
    the short class, and decode throughput while prefills are in flight.
    """
    from repro.runtime import Scheduler, ServeEngine

    cfg, params = _reduced_lm()
    long_len, short_len = (48, 6) if smoke else (96, 8)
    gen_s, gen_l = (6, 4) if smoke else (16, 8)
    n_pairs = 3 if smoke else 6
    rng = np.random.default_rng(seed)
    # long, short, short, long, ... — shorts always queue behind a long
    reqs = []
    for _ in range(n_pairs):
        reqs.append((rng.integers(0, cfg.vocab_size, long_len), gen_l))
        reqs.append((rng.integers(0, cfg.vocab_size, short_len), gen_s))
        reqs.append((rng.integers(0, cfg.vocab_size, short_len), gen_s))
    slot_len = max(len(p) + g for p, g in reqs)
    chunk = 8
    print(f"\nchunked vs monolithic prefill: {len(reqs)} requests "
          f"(prompts {short_len}/{long_len} tokens, chunk {chunk}), "
          f"batch 2, reduced minitron-8b")
    print(f"{'prefill':>12} | {'ttft short':>10} | {'ttft long':>10} | "
          f"{'tok/s':>7} | {'stall':>7}")

    results = {}
    for label, kw in (
            ("monolithic", {}),
            ("chunked", dict(prefill_chunk=chunk, prefill_budget=chunk,
                             kv_page_size=16))):
        engine = ServeEngine(cfg, params, compress=True)
        sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                          buckets=(128,), **kw)
        sched.submit(reqs[0][0], 2)              # warmup: compile prefill,
        sched.submit(reqs[1][0], 2)              # chunks, and decode shapes
        sched.run()
        engine.metrics = type(engine.metrics)()
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        done = sched.run()
        assert len(done) == len(reqs)
        by_rid = sorted(done, key=lambda r: r.rid)[-len(reqs):]
        ttft = {True: [], False: []}
        for r in by_rid:
            ttft[r.prompt_len <= short_len].append(r.first_token_latency())
        m = engine.metrics
        results[label] = (
            np.mean(ttft[True]), np.mean(ttft[False]), m.tokens_per_s(),
            m.decode_stall_s,
            tuple(tuple(r.generated) for r in by_rid))
        t_s, t_l, tps, stall, _ = results[label]
        print(f"{label:>12} | {t_s * 1000:>8.0f}ms | {t_l * 1000:>8.0f}ms | "
              f"{tps:>7.1f} | {stall:>6.2f}s")
    assert results["monolithic"][4] == results["chunked"][4], \
        "chunked prefill changed generated tokens"
    speedup = results["monolithic"][0] / max(results["chunked"][0], 1e-9)
    print(f"  short-request time-to-first-token: {speedup:.1f}x faster "
          f"chunked (token-identical outputs)")
    REPORT["prefill_compare"] = {
        label: dict(ttft_short_ms=round(results[label][0] * 1000, 1),
                    ttft_long_ms=round(results[label][1] * 1000, 1),
                    tok_s=round(results[label][2], 2))
        for label in ("monolithic", "chunked")}
    # deterministic in structure, robust in time: a short prompt's first
    # token needs 1 chunk + its own prefill, not a neighbour's whole
    # long-prompt prefill
    assert results["chunked"][0] < results["monolithic"][0], \
        "chunked prefill did not improve short-request TTFT"


# ---------------------------------------------------------------------------
# attention backends: paged-gather vs in-kernel decode on the real scheduler
# ---------------------------------------------------------------------------

def backend_compare(smoke: bool, seed: int = 0) -> None:
    """Decode-step latency under the attention backends + mixed step.

    ``gathered`` copies every slot's pages into a contiguous lane view and
    scatters them back *each step* — two full cache copies on the decode
    hot path.  ``pallas_paged`` hands the donated page pool + page tables
    to the paged-attention kernel, which walks the table in-kernel: the
    per-step copies disappear (the kv-gather metric must read exactly 0,
    asserted here).  ``mixed`` adds chunked prefill on top of
    ``pallas_paged``: prompt chunks and decode tokens ride one ragged
    batched trace whose K/V writes land straight in the pools, so the
    *prefill*-side gather (the gathered oracle's install copy of every
    freshly prefilled cache) reads exactly 0 too — also asserted.  Tokens
    are identical by assertion; on CPU the kernel runs interpreted, so
    the latency column shows the copy-free data path, not TPU-compiled
    kernel speed.
    """
    from repro.runtime import Scheduler, ServeEngine

    cfg, params = _reduced_lm()
    rng = np.random.default_rng(seed)
    n = 6 if smoke else 12
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 20))),
             int(rng.integers(4, 12))) for _ in range(n)]
    slot_len = max(len(p) + g for p, g in reqs)
    print(f"\nattention backends: {n} requests, batch 2, page size 8, "
          f"reduced minitron-8b")
    print(f"{'backend':>14} | {'ms/step':>8} | {'kv moved/step':>13} | "
          f"{'kv avoided/step':>15} | {'prefill moved':>13}")

    configs = {
        "gathered": dict(attn_backend="gathered"),
        "pallas_paged": dict(attn_backend="pallas_paged"),
        "mixed": dict(attn_backend="pallas_paged", prefill_chunk=8),
    }
    results = {}
    for label, kw in configs.items():
        engine = ServeEngine(cfg, params, compress=True)
        sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                          buckets=(32,), kv_page_size=8, **kw)
        sched.submit(reqs[0][0], 2)              # warmup compile
        sched.run()
        engine.metrics = type(engine.metrics)()
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        done = sched.run()
        assert len(done) == n
        m = engine.metrics
        steps = max(m.decode_steps, 1)
        results[label] = (
            m.ms_per_token(), m.kv_gather_bytes, m.kv_gather_bytes_avoided,
            tuple(tuple(r.generated) for r in
                  sorted(done, key=lambda r: r.rid)[-n:]),
            m.kv_prefill_gather_bytes)
        print(f"{label:>14} | {m.ms_per_token():>8.1f} | "
              f"{m.kv_gather_bytes // steps:>13} | "
              f"{m.kv_gather_bytes_avoided // steps:>15} | "
              f"{m.kv_prefill_gather_bytes:>13}")
    assert results["gathered"][3] == results["pallas_paged"][3], \
        "attention backend changed generated tokens"
    assert results["gathered"][3] == results["mixed"][3], \
        "mixed-step execution changed generated tokens"
    assert results["pallas_paged"][1] == 0, \
        "pallas_paged backend copied KV on the decode hot path"
    assert results["pallas_paged"][2] > 0 and results["gathered"][1] > 0
    assert results["mixed"][1] == 0 and results["mixed"][4] == 0, \
        "mixed-step path copied KV on the prefill or decode hot path"
    assert results["gathered"][4] > 0 and results["pallas_paged"][4] > 0, \
        "install-path prefill copies were not accounted"
    print("  pallas_paged moved 0 gather/scatter bytes; mixed-step also "
          "moved 0 prefill install bytes (token-identical outputs)")
    REPORT["backend_compare"] = {
        label: dict(ms_per_step=round(results[label][0], 2),
                    kv_gather_bytes=results[label][1],
                    kv_prefill_gather_bytes=results[label][4])
        for label in configs}


# ---------------------------------------------------------------------------
# kv page codec: compressed pools vs fp pools at equal HBM budget
# ---------------------------------------------------------------------------

def kv_codec_compare(smoke: bool, seed: int = 0) -> None:
    """Resident-KV compression of ``kv_codec="cluster"`` vs the fp pools.

    The cluster codec stores every paged K/V leaf as int8 codebook codes
    plus one f32 scale per (page, token) — decoded in-kernel under
    ``pallas_paged`` (codebook lookup in VMEM after the per-page DMA,
    before the online-softmax score) and at gather under ``gathered``.
    The table reports tokens/s, resident bytes per page, the effective-
    capacity multiplier, and how many slots one fixed HBM budget backs
    under each codec — the serving win: more resident requests per byte.
    Closeness is reported as the mean per-token agreement with the
    bit-exact ``none`` oracle (greedy argmax on a random-weight reduced
    model amplifies the bounded KV reconstruction error into occasional
    token flips; the documented elementwise bound is max scale / 254,
    printed from the metric).
    """
    from repro.runtime import Scheduler, ServeEngine

    cfg, params = _reduced_lm()
    rng = np.random.default_rng(seed)
    n = 6 if smoke else 12
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 20))),
             int(rng.integers(4, 12))) for _ in range(n)]
    slot_len = max(len(p) + g for p, g in reqs)
    print(f"\nkv page codec: {n} requests, batch 2, page size 8, "
          f"reduced minitron-8b")
    print(f"{'backend/codec':>22} | {'tok/s':>7} | {'B/page':>7} | "
          f"{'capacity':>8} | {'agree':>6}")

    configs = {
        "gathered/none": dict(attn_backend="gathered", kv_codec="none"),
        "gathered/cluster": dict(attn_backend="gathered",
                                 kv_codec="cluster"),
        "pallas_paged/none": dict(attn_backend="pallas_paged",
                                  kv_codec="none"),
        "pallas_paged/cluster": dict(attn_backend="pallas_paged",
                                     kv_codec="cluster"),
    }
    results = {}
    for label, kw in configs.items():
        engine = ServeEngine(cfg, params, compress=True)
        sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                          buckets=(32,), kv_page_size=8, **kw)
        sched.submit(reqs[0][0], 2)              # warmup compile
        sched.run()
        engine.metrics = type(engine.metrics)()
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        done = sched.run()
        assert len(done) == n
        m = engine.metrics
        pool = sched._pool
        results[label] = dict(
            toks=tuple(tuple(r.generated) for r in
                       sorted(done, key=lambda r: r.rid)[-n:]),
            tok_s=m.tokens_per_s(),
            page_fp=pool.page_bytes_fp,
            page_res=pool.page_bytes_resident,
            pages_per_slot=pool.pages_per_slot,
            avoided=m.kv_bytes_avoided,
            mult=m.kv_capacity_multiplier(),
            err=m.kv_codec_error_bound)
    base = results["gathered/none"]

    def agreement(toks):
        hits = sum(a == b for t, bt in zip(toks, base["toks"])
                   for a, b in zip(t, bt))
        return hits / sum(len(t) for t in base["toks"])

    for label, r in results.items():
        mult = r["page_fp"] / r["page_res"]
        print(f"{label:>22} | {r['tok_s']:>7.1f} | {r['page_res']:>7} | "
              f"{mult:>7.2f}x | {agreement(r['toks']) * 100:>5.0f}%")

    # "none" is the bit-exact oracle under both backends (PR-5 seam)
    assert results["pallas_paged/none"]["toks"] == base["toks"], \
        "kv_codec='none' is not bit-identical across backends"
    for label in ("gathered/cluster", "pallas_paged/cluster"):
        r = results[label]
        # the at-rest claim: >= 1.3x fewer resident pool bytes at equal
        # page count (int8 + one f32 scale per token vs f32 pages)
        assert r["page_fp"] / r["page_res"] >= 1.3, \
            f"{label}: page compression below 1.3x"
        assert r["avoided"] > 0 and r["mult"] >= 1.3
        assert 0.0 < r["err"] < 0.1, f"{label}: error bound {r['err']}"
        # token closeness vs the oracle: monolithic prefill is exact, so
        # every request's *first* decoded token matches; later tokens
        # drift only within the bounded reconstruction error
        firsts = [t[0] for t in r["toks"]]
        assert firsts == [t[0] for t in base["toks"]], \
            f"{label}: first decoded tokens diverged from kv_codec='none'"
        assert agreement(r["toks"]) >= 0.4, \
            f"{label}: token agreement collapsed"
    # equal-HBM-budget capacity: how many fully-backed slots one fixed
    # pool budget holds under each codec
    r = results["pallas_paged/cluster"]
    budget = 64 * r["pages_per_slot"] * r["page_fp"]   # 64 fp slots
    slots_fp = budget // (r["pages_per_slot"] * r["page_fp"])
    slots_cl = budget // (r["pages_per_slot"] * r["page_res"])
    assert slots_cl >= slots_fp * 1.3
    print(f"  equal HBM budget ({budget // 1024} KiB): {slots_fp} fp slots "
          f"-> {slots_cl} cluster slots "
          f"({r['page_fp'] / r['page_res']:.2f}x resident compression, "
          f"error bound {r['err']:.2e})")
    REPORT["kv_codec_compare"] = {
        label.replace("/", "_"): dict(
            tok_s=round(rr["tok_s"], 2),
            page_compression=round(rr["page_fp"] / rr["page_res"], 3),
            agreement=round(agreement(rr["toks"]), 4))
        for label, rr in results.items()}


# ---------------------------------------------------------------------------
# prefix sharing: shared-prefix trace replay, sharing on vs off
# ---------------------------------------------------------------------------

def prefix_share_compare(smoke: bool, seed: int = 0) -> None:
    """Replay the checked-in multi-tenant shared-prefix trace
    (benchmarks/traces/shared_prefix.jsonl: each tenant's prompts extend
    one deterministic 16-token system prefix) with ``prefix_share`` off
    vs on.  Sharing must be token-identical, and the accounting identity
    ``chunk_tokens(on) + tokens_reused == chunk_tokens(off)`` pins that
    every reused token is prefill work the off run actually paid for —
    the table reports the reuse, chunks avoided, copy-on-write copies,
    and mean time-to-first-token."""
    from repro.runtime import Scheduler, ServeEngine

    cfg, params = _reduced_lm()
    rows = [json.loads(line) for line in
            SHARED_PREFIX_TRACE.read_text().splitlines() if line.strip()]
    if smoke:
        rows = rows[:8]
    tenants = sorted({r["tenant"] for r in rows})
    prefixes = {t: np.random.default_rng(seed + 100 + i).integers(
        0, cfg.vocab_size, 16) for i, t in enumerate(tenants)}
    rng = np.random.default_rng(seed)
    reqs = []
    for r in rows:
        pre = prefixes[r["tenant"]]
        tail = rng.integers(0, cfg.vocab_size, r["prompt_len"] - len(pre))
        reqs.append((np.concatenate([pre, tail]), r["decode_len"]))
    slot_len = max(len(p) + g for p, g in reqs)
    chunk = 4
    print(f"\nprefix sharing: {len(reqs)} requests, {len(tenants)} tenants "
          f"(16-token shared prefixes), chunk {chunk}, page 8, batch 2, "
          f"reduced minitron-8b  [shared_prefix.jsonl]")
    print(f"{'sharing':>8} | {'tok/s':>7} | {'ttft':>7} | {'reused':>6} | "
          f"{'avoided':>7} | {'cow':>4}")

    results = {}
    for label, on in (("off", False), ("on", True)):
        engine = ServeEngine(cfg, params, compress=True)
        # kv_pages: headroom beyond the 2-slot worst case — a pool sized
        # exactly to the slots would evict every cached prefix at each
        # admission's reservation (the index lives in the spare pages)
        sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                          buckets=(64,), kv_page_size=8, kv_pages=20,
                          prefill_chunk=chunk, prefix_share=on)
        sched.submit(reqs[0][0], 2)              # warmup compile
        sched.run()
        if on:
            sched._pool.prefix.clear()           # cold index for the run
        engine.metrics = type(engine.metrics)()
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        done = sched.run()
        assert len(done) == len(reqs)
        m = engine.metrics
        ttfts = [r.first_token_latency() for r in
                 sorted(done, key=lambda r: r.rid)[-len(reqs):]]
        results[label] = dict(
            toks=tuple(tuple(r.generated) for r in
                       sorted(done, key=lambda r: r.rid)[-len(reqs):]),
            tok_s=m.tokens_per_s(),
            ttft=float(np.mean([t for t in ttfts if t is not None])),
            chunk_tokens=m.prefill_chunk_tokens,
            reused=m.prefix_tokens_reused,
            avoided=m.prefill_chunks_avoided,
            cow=m.prefix_cow_copies)
        r = results[label]
        print(f"{label:>8} | {r['tok_s']:>7.1f} | "
              f"{r['ttft'] * 1000:>5.0f}ms | {r['reused']:>6} | "
              f"{r['avoided']:>7} | {r['cow']:>4}")

    off, on = results["off"], results["on"]
    assert on["toks"] == off["toks"], \
        "prefix sharing changed generated tokens"
    assert on["reused"] > 0, "shared-prefix trace produced no reuse"
    assert [t[0] for t in on["toks"]] == [t[0] for t in off["toks"]]
    assert on["chunk_tokens"] + on["reused"] == off["chunk_tokens"], \
        "reused tokens do not account for the skipped prefill work"
    print(f"  {on['reused']} prompt tokens served from cached pages "
          f"({on['avoided']} chunks avoided, {on['cow']} copy-on-write "
          f"copies); token-identical outputs")
    REPORT["prefix_share_compare"] = {
        label: dict(tok_s=round(results[label]["tok_s"], 2),
                    ttft_ms=round(results[label]["ttft"] * 1000, 1),
                    tokens_reused=results[label]["reused"],
                    cow_copies=results[label]["cow"])
        for label in ("off", "on")}


# ---------------------------------------------------------------------------
# speculative decoding: ngram drafter vs plain decode on a repetitive trace
# ---------------------------------------------------------------------------

def speculative_compare(smoke: bool, seed: int = 0) -> None:
    """Speculative decoding (``speculate="ngram"``) vs plain decode on the
    checked-in repetition-heavy trace (benchmarks/traces/repetition.jsonl:
    short tiled prompts, long decode budgets).  Greedy verification makes
    speculation token-identical by construction — asserted per cell — so
    the whole comparison is about decode steps: every accepted draft token
    is one verify row instead of one full scheduler iteration.  The drafter
    pays off exactly when the token stream is predictable (here: tiled
    prompts steer the reduced model into its argmax attractor cycles,
    which the n-gram matcher then predicts), which is the workload the
    trace encodes; on incompressible streams acceptance drops and ``off``
    wins, hence the dedicated trace rather than the random mixes the other
    sections use.  The deterministic invariant (fewer decode steps, drafts
    accepted) is asserted everywhere; the wall-clock >= 1.2x tokens/s
    claim only on the full run at the default seed, on the single-phase
    ``pallas_paged`` cell where verification rides the same ragged
    mixed-step invocation as plain decode.  The model is the reduced
    minitron at ``vocab_size=8`` — narrow enough that greedy decode
    settles into its argmax attractor cycles (the predictable-stream
    regime speculation targets) instead of the near-random wander of the
    128-token vocabulary the other sections use."""
    from repro.runtime import Scheduler, ServeEngine

    cfg, params = _reduced_lm(vocab_size=8)
    rng = np.random.default_rng(seed)
    trace = load_trace_file(REPETITION_TRACE)
    rows = trace.requests[:4] if smoke else trace.requests
    reqs = []
    for r in rows:
        pat = rng.integers(0, cfg.vocab_size, 3)
        reps = -(-r.prompt_len // len(pat))          # ceil division
        prompt = np.tile(pat, reps)[:r.prompt_len]
        reqs.append((prompt, max(6, r.gen // 8) if smoke else r.gen))
    slot_len = max(len(p) + g for p, g in reqs)
    print(f"\nspeculative decoding: {len(reqs)} requests "
          f"(decode {min(g for _, g in reqs)}..{max(g for _, g in reqs)}), "
          f"batch 2, draft k=4, reduced minitron-8b  [repetition.jsonl]")
    print(f"{'backend/codec':>20} | {'spec':>5} | {'tok/s':>7} | "
          f"{'steps':>5} | {'accept':>6} | {'steps/tok':>9}")

    cells = {
        "gathered/none": dict(attn_backend="gathered", kv_page_size=4),
        "pallas_paged/none": dict(attn_backend="pallas_paged",
                                  kv_page_size=4, prefill_chunk=4),
        "pallas_paged/cluster": dict(attn_backend="pallas_paged",
                                     kv_page_size=4, prefill_chunk=4,
                                     kv_codec="cluster"),
    }
    reps_n = 1 if smoke else 3
    results = {}
    for label, kw in cells.items():
        for spec in ("off", "ngram"):
            engine = ServeEngine(cfg, params, compress=True)
            sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                              buckets=(128,), speculate=spec, draft_k=4,
                              **kw)
            sched.submit(reqs[0][0], 2)              # warmup compile
            sched.run()
            best = None
            for _ in range(reps_n):                  # best-of-N de-noises
                engine.metrics = type(engine.metrics)()
                for prompt, gen in reqs:
                    sched.submit(prompt, gen)
                done = sched.run()
                assert len(done) == len(reqs)
                m = engine.metrics
                total = sum(len(r.generated) for r in done)
                rep = dict(
                    toks=tuple(tuple(r.generated) for r in
                               sorted(done, key=lambda r: r.rid)
                               [-len(reqs):]),
                    tok_s=m.tokens_per_s(), steps=m.decode_steps,
                    accept=m.spec_acceptance_rate(),
                    spt=m.decode_steps / max(total, 1))
                if best is None or rep["tok_s"] > best["tok_s"]:
                    best = rep
            results[label, spec] = best
            print(f"{label:>20} | {spec:>5} | {best['tok_s']:>7.1f} | "
                  f"{best['steps']:>5} | {best['accept'] * 100:>5.0f}% | "
                  f"{best['spt']:>9.2f}")

    for label in cells:
        off, ngram = results[label, "off"], results[label, "ngram"]
        # greedy verification is the oracle: every emitted token is the
        # model's own argmax, so outputs must match token for token
        assert ngram["toks"] == off["toks"], \
            f"{label}: speculation changed generated tokens"
        assert ngram["accept"] > 0, f"{label}: no draft tokens accepted"
        # deterministic (no timing): accepted drafts collapse scheduler
        # iterations, and amortise to < 1 verify step per emitted token
        assert ngram["steps"] < off["steps"], \
            f"{label}: speculation did not reduce decode steps"
        assert ngram["spt"] < 1.0, \
            f"{label}: {ngram['spt']:.2f} verify steps per token"
    off = results["pallas_paged/none", "off"]
    ngram = results["pallas_paged/none", "ngram"]
    speedup = ngram["tok_s"] / max(off["tok_s"], 1e-9)
    print(f"  pallas_paged/none ngram/off tokens/s: {speedup:.2f}x at "
          f"{ngram['accept'] * 100:.0f}% acceptance "
          f"({ngram['spt']:.2f} steps/token; token-identical outputs)")
    REPORT["speculative"] = {
        label.replace("/", "_"): dict(
            tok_s_off=round(results[label, "off"]["tok_s"], 2),
            tok_s_ngram=round(results[label, "ngram"]["tok_s"], 2),
            acceptance=round(results[label, "ngram"]["accept"], 4),
            steps_per_token=round(results[label, "ngram"]["spt"], 4))
        for label in cells}
    REPORT["speculative"]["speedup_pallas_none"] = round(speedup, 3)
    # wall-clock claim, gated like trace_replay's skew invariant: full
    # run, default seed (smoke decode budgets are too small to amortise
    # the drafter's host work)
    if not smoke and seed == 0:
        assert speedup >= 1.2, \
            f"ngram speculation {speedup:.2f}x < 1.2x on repetition trace"


# ---------------------------------------------------------------------------
# telemetry: lifecycle trace + Prometheus export on the real scheduler
# ---------------------------------------------------------------------------

def telemetry_smoke(smoke: bool, seed: int = 0, trace_out=None,
                    metrics_out=None) -> None:
    """Serve a small mix with tracing on and validate the whole
    observability surface: the Chrome-trace JSON loads and carries
    exactly one admitted/retired pair per completed request, the
    Prometheus text parses, counters are monotone across scrapes, and
    tokens are identical to a telemetry-off run (telemetry must
    observe, never steer).  With ``trace_out`` / ``metrics_out`` set
    the artifacts are also written to disk (the CI smoke job does, and
    re-validates the files)."""
    from repro.runtime import Scheduler, ServeEngine, Telemetry, parse_prom

    cfg, params = _reduced_lm()
    rng = np.random.default_rng(seed)
    n = 5 if smoke else 10
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 16))),
             int(rng.integers(3, 9))) for _ in range(n)]
    print(f"\ntelemetry: {n} requests, batch 2, chunked prefill, "
          f"reduced minitron-8b")

    outs = {}
    for label, tel in (("off", None), ("on", Telemetry(trace=True))):
        engine = ServeEngine(cfg, params, compress=True, telemetry=tel)
        sched = Scheduler(engine, batch_size=2, buckets=(32,),
                          prefill_chunk=4, kv_page_size=8)
        for prompt, gen in reqs:
            sched.submit(prompt, gen)
        done = sched.run()
        assert len(done) == n
        outs[label] = tuple(tuple(r.generated)
                            for r in sorted(done, key=lambda r: r.rid))
        if tel is None:
            continue
        # scrape twice around extra work: every counter must be monotone
        prom1 = parse_prom(engine.render_prom())
        engine.cache.get(("nope",))          # one more miss
        prom2 = parse_prom(engine.render_prom())
        for key, v1 in prom1.items():
            name = key[0]
            if name.endswith(("_total", "_count", "_bucket", "_sum")):
                assert prom2[key] >= v1, f"counter {key} went backwards"
        chrome = tel.tracer.chrome()
        counts: dict = {}
        for e in chrome["traceEvents"]:
            counts[e["name"]] = counts.get(e["name"], 0) + 1
        assert counts.get("admitted") == counts.get("retired") == n, \
            f"admitted/retired spans != {n}: {counts}"
        assert counts.get("request") == n
        if trace_out:
            tel.tracer.write_chrome(trace_out)
            with open(trace_out) as f:
                loaded = json.load(f)
            assert len(loaded["traceEvents"]) == len(chrome["traceEvents"])
            print(f"  trace -> {trace_out} "
                  f"({len(loaded['traceEvents'])} events)")
        if metrics_out:
            text = engine.render_prom()
            parse_prom(text)
            with open(metrics_out, "w") as f:
                f.write(text)
            print(f"  metrics -> {metrics_out} "
                  f"({len(text.splitlines())} lines)")
        print(f"  {counts['request']} request span trees, "
              f"{len(prom2)} prometheus samples, counters monotone")
    assert outs["on"] == outs["off"], "telemetry changed generated tokens"
    print("  telemetry on/off token-identical")


# ---------------------------------------------------------------------------
# slot-level continuous batching vs wave mode on the real scheduler
# ---------------------------------------------------------------------------

def slot_vs_wave(smoke: bool, seed: int = 0) -> None:
    from repro.runtime import Scheduler, ServeEngine

    cfg, params = _reduced_lm()
    batch = 4
    prompt_len = 8                           # fixed: one prefill compile,
    rng = np.random.default_rng(seed)        # hit by every admission
    trace = bursty_trace(rng, n_requests=10 if smoke else 24,
                         gen_lo=2 if smoke else 8,
                         gen_hi=12 if smoke else 48)
    reqs = [(rng.integers(0, cfg.vocab_size, prompt_len), r.gen)
            for r in trace.requests]
    slot_len = prompt_len + max(g for _, g in reqs)  # shared decode shape
    print(f"\nslot batching vs wave mode: {len(reqs)} requests "
          f"(gen {min(g for _, g in reqs)}..{max(g for _, g in reqs)}), "
          f"batch {batch}, reduced minitron-8b")

    # continuous runs FIRST so one-time process warmup (XLA autotuning
    # etc.) can only help wave-mode; best-of-3 reps de-noises the tiny
    # decode totals of the reduced model
    results = {}
    for mode in ("continuous", "wave"):
        engine = ServeEngine(cfg, params, compress=True)
        sched = Scheduler(engine, batch_size=batch, mode=mode,
                          slot_len=slot_len)
        sched.submit(reqs[0][0], 2)          # warmup: compile prefill at
        sched.run()                          # prompt_len + decode at (S, L)
        best = None
        for _ in range(3):
            engine.metrics = type(engine.metrics)()
            for prompt, gen in reqs:
                sched.submit(prompt, gen)
            done = sched.run()
            m = engine.metrics
            assert len(done) == len(reqs)
            rep = (m.tokens_per_s(), m.occupancy(), m.decode_steps,
                   tuple(tuple(r.generated) for r in
                         sorted(done, key=lambda r: r.rid)[-len(reqs):]))
            if best is None or rep[0] > best[0]:
                best = rep
        results[mode] = best
        print(f"  {mode:>10}: {best[0]:7.1f} tok/s | "
              f"occupancy {best[1] * 100:3.0f}% | "
              f"{best[2]} decode steps")
    assert results["wave"][3] == results["continuous"][3], \
        "scheduling mode changed generated tokens"
    # deterministic invariants (step counts and occupancy don't depend on
    # machine timing): admit-on-retire must strictly reduce decode steps
    assert results["continuous"][2] < results["wave"][2], \
        "continuous batching did not reduce decode steps"
    assert results["continuous"][1] > results["wave"][1], \
        "continuous batching did not raise occupancy"
    speedup = results["continuous"][0] / max(results["wave"][0], 1e-9)
    print(f"  continuous/wave tokens/s: {speedup:.2f}x "
          f"(token-identical outputs)")
    REPORT["slot_vs_wave"] = {
        mode: dict(tok_s=round(results[mode][0], 2),
                   occupancy=round(results[mode][1], 4),
                   decode_steps=results[mode][2])
        for mode in ("continuous", "wave")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--trace", choices=["bursty"], default=None,
                    help="replay a synthetic arrival trace through every "
                         "eviction policy + compare scheduler modes")
    ap.add_argument("--trace-file", type=str, default=None,
                    help="replay a recorded JSONL trace (arrival_time, "
                         "prompt_len, decode_len, tenant per line) through "
                         "every eviction policy; see benchmarks/traces/"
                         "sample.jsonl")
    ap.add_argument("--trace-time-step", type=float, default=0.05,
                    help="seconds of recorded arrival time per scheduler "
                         "admission step (trace-file replay)")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run: synthetic + sample-file trace "
                         "replay (all policies), slot-vs-wave, chunked "
                         "prefill, and the attention-backend comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the synthetic trace generators "
                         "(bursty arrivals + scheduler request mixes), so "
                         "replays are reproducible run-to-run; the "
                         "freq-vs-LRU CI invariant is only asserted on "
                         "the default seed")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep a fine capacity grid over the replayed "
                         "trace (synthetic bursty, or --trace-file) and "
                         "print the recommended decode_cache capacity at "
                         "the hit-rate-cliff knee")
    ap.add_argument("--autotune-policy", choices=list(POLICY_NAMES),
                    default="freq",
                    help="eviction policy the autotune sweep measures")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write the telemetry section's Chrome-trace JSON "
                         "here (CI validates it re-loads)")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the telemetry section's Prometheus text "
                         "exposition here (CI validates it re-parses)")
    ap.add_argument("--out", type=str, default=None,
                    help="write each section's headline numbers (tokens/s, "
                         "TTFT, hit/acceptance rates, compression "
                         "multipliers) as one JSON report — the checked-in "
                         "BENCH_serve.json is generated this way")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.autotune:
        if args.trace_file:
            trace = load_trace_file(args.trace_file,
                                    time_step=args.trace_time_step)
        else:
            trace = bursty_trace(np.random.default_rng(args.seed),
                                 n_requests=24 if args.smoke else 64)
        autotune_capacity(trace, policy=args.autotune_policy)
        return
    if args.trace_file:
        trace = load_trace_file(args.trace_file,
                                time_step=args.trace_time_step)
        trace_replay(smoke=args.smoke, trace=trace,
                     label=pathlib.Path(args.trace_file).name)
        if not (args.trace or args.smoke):
            return
    if args.trace or args.smoke:
        trace_replay(smoke=args.smoke, seed=args.seed)
        if args.smoke:
            print()
            trace_replay(smoke=True,
                         trace=load_trace_file(SAMPLE_TRACE),
                         label="sample.jsonl")
        slot_vs_wave(smoke=args.smoke, seed=args.seed)
        prefill_compare(smoke=args.smoke, seed=args.seed)
        backend_compare(smoke=args.smoke, seed=args.seed)
        kv_codec_compare(smoke=args.smoke, seed=args.seed)
        prefix_share_compare(smoke=args.smoke, seed=args.seed)
        speculative_compare(smoke=args.smoke, seed=args.seed)
        telemetry_smoke(smoke=args.smoke, seed=args.seed,
                        trace_out=args.trace_out,
                        metrics_out=args.metrics_out)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(REPORT, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"\nheadline numbers ({len(REPORT)} sections) -> "
                  f"{args.out}")
        return
    capacity_sweep(args.steps)


if __name__ == "__main__":
    main()
