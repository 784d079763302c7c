"""Serving driver on the compressed-weight runtime.

Batched requests flow through the slot-level continuous-batching scheduler
(per-slot prefill -> vmapped per-slot decode -> admit-on-retire); the
model's MLP projections are binarised, Huffman-compressed into the
WeightStore, and reconstructed each step from the decode-tile cache —
after the first step every tile is a cache hit, so weights are *reused*,
not re-decoded per token.  ``--mode wave`` reproduces the old
wave-granular scheduling (token-identical, lower slot occupancy);
``--policy`` picks the decode-cache eviction policy;
``--prefill-chunk`` interleaves prompt chunks with decode steps and
``--kv-page-size`` backs the KV lanes with demand-allocated pages —
both token-identical to the monolithic defaults.  ``--attn-backend
pallas_paged`` decodes straight over the page pool with the in-kernel
paged-attention kernel (zero per-step KV gather/scatter copies; also
token-identical).  Combining ``--attn-backend pallas_paged`` with
``--prefill-chunk`` engages the unified **mixed-step** path: prefill
chunks and decode tokens of every slot ride one ragged batched trace
per iteration, chunks write straight into the page pools, and the
serve summary's KV gather counters read zero for prefill *and* decode.
``--kv-codec cluster`` stores the page pools as int8 codebook codes plus
per-token scales (decoded in-kernel under ``pallas_paged``, at gather
under ``gathered``) — ~4x resident-KV compression at a reported
reconstruction-error bound, with the at-rest Huffman ratio of the
resident codes printed in the summary.  ``--prefix-share`` caches
completed prefills' KV pages in a refcounted prefix index so requests
extending a cached prefix (generate them with ``--shared-prefix-len``)
map the shared pages and skip that prefill work — token-identical, with
copy-on-write guarding every shared page.  The paged kernel's launch
shape is worked out inside the kernel from the model's and the step's
shapes.

Observability: ``--trace-out trace.json`` records every request's
lifecycle span tree (queued -> admitted -> prefill chunks -> decode ->
retired) plus engine phase spans as Chrome-trace JSON — open it in
``chrome://tracing`` or https://ui.perfetto.dev (``--trace-jsonl``
additionally dumps the raw events one-per-line).  ``--metrics-out
metrics.prom`` dumps every serving counter/gauge/histogram in
Prometheus text-exposition format.  Both are validated before exit
(span count == completed requests; the .prom text re-parses) and
neither changes generated tokens.  ``--cache-mb auto`` sweeps the
materialize access pattern over a capacity grid and serves with the
recommended hit-rate-cliff knee capacity.

One process serves on one device (``launch.mesh.make_serving_mesh``):
on a TPU host that is the first chip, and nothing here starts a child
process that would need another.  ``main(argv)`` takes the argv list
in-process; ``build_engine`` + ``serve`` are the same two steps for a
caller that serves several runs from one compressed engine
(``chip_smoke.py``).

  PYTHONPATH=src python -m repro.launch.serve --scale tiny
  PYTHONPATH=src python -m repro.launch.serve --scale tiny \
      --trace-out /tmp/trace.json --metrics-out /tmp/metrics.prom \
      --cache-mb auto
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b \
      --batch 4 --prompt-len 64 --gen 32 --requests 8 --policy freq \
      --prefill-chunk 16 --kv-page-size 16 --attn-backend pallas_paged
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import base as cfgs
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.launch.train import tiny_config
from repro.models.api import get_model
from repro.runtime import (Scheduler, ServeEngine, Telemetry, parse_prom,
                           recommend_store_capacity)
from repro.runtime.decode_cache import POLICIES
from repro.runtime.metrics import ServeMetrics


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=cfgs.ARCH_NAMES)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests to serve (default: one full batch)")
    ap.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--cache-mb", type=str, default=None,
                    help="decode-tile cache capacity in MiB (omit = "
                         "unbounded; 0 = caching disabled, the no-cache "
                         "baseline; 'auto' = sweep the materialize access "
                         "pattern over a capacity grid and serve with the "
                         "hit-rate-cliff knee capacity)")
    ap.add_argument("--policy", choices=sorted(POLICIES), default="lru",
                    help="decode-cache eviction policy")
    ap.add_argument("--mode", choices=["continuous", "wave"],
                    default="continuous",
                    help="slot scheduling: continuous (admit-on-retire) or "
                         "wave (drain before admitting, the old behavior)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split prompts into chunks of this many tokens, "
                         "interleaved with decode steps (omit = monolithic "
                         "batch-1 prefill at admission); with --attn-"
                         "backend pallas_paged this engages the unified "
                         "mixed-step path (chunks + decode tokens in one "
                         "batched trace, zero prefill/decode KV copies)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prefill tokens per scheduler iteration "
                         "(default: one chunk); bounds decode-latency "
                         "impact of long prompts.  On the mixed-step "
                         "path each prefilling slot advances at most one "
                         "chunk per iteration, so budget beyond "
                         "batch * chunk has no additional effect")
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="back KV lanes with pages of this many tokens, "
                         "allocated on demand (omit = monolithic "
                         "slot_len lanes)")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="logical page-pool size (default: fully backs "
                         "every slot; smaller = overcommit, admission "
                         "defers when reservations fail)")
    ap.add_argument("--attn-backend", choices=["gathered", "pallas_paged"],
                    default="gathered",
                    help="how decode reads paged KV: gathered (copy pages "
                         "into contiguous views each step, the reference) "
                         "or pallas_paged (in-kernel paged attention, "
                         "zero per-step cache copies; needs "
                         "--kv-page-size)")
    ap.add_argument("--kv-codec", choices=["none", "cluster"],
                    default="none",
                    help="KV page-pool codec: none (fp pages, bit-exact) "
                         "or cluster (pages stored as int8 codebook codes "
                         "+ per-token scales, decoded in-kernel / at "
                         "gather; ~4x resident-KV compression at a "
                         "bounded reconstruction error; needs "
                         "--kv-page-size)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="cache completed prefills' KV pages in a prefix "
                         "index; requests extending a cached prefix map "
                         "the shared (refcounted) pages into their page "
                         "table and skip that prefill work entirely, "
                         "with copy-on-write protecting shared pages — "
                         "token-identical to serving each request "
                         "privately (needs --kv-page-size and "
                         "--prefill-chunk)")
    ap.add_argument("--prompt-pattern", type=int, default=0,
                    help="tile each request's prompt from its own "
                         "repeating pattern of this many tokens (0 = "
                         "fully random prompts); repetitive prompts are "
                         "the regime where --speculate ngram pays, since "
                         "the drafter continues patterns the history "
                         "already contains")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="generate request prompts sharing a common "
                         "prefix of this many tokens (0 = fully random "
                         "prompts); pair with --prefix-share to see "
                         "reuse, or without it for the baseline")
    ap.add_argument("--speculate", default="off",
                    help="speculative decoding drafter: 'off' (default), "
                         "'ngram' (prompt/history n-gram matcher, no "
                         "extra weights), or 'draft'/'draft:<arch>' (a "
                         "tiny draft model sharing the engine's weight "
                         "store).  Each slot proposes up to --draft-k "
                         "tokens per step, verified in the same ragged "
                         "batched invocation; greedy verification is "
                         "token-identical to --speculate off")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="max draft tokens proposed per slot per step "
                         "(bounds the verify width at 1 + k)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable async next-layer tile prefetch")
    ap.add_argument("--no-compress", action="store_true",
                    help="uncompressed baseline on the same scheduler")
    ap.add_argument("--log-every", type=int, default=16)
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write per-request lifecycle spans + engine phase "
                         "spans as Chrome-trace JSON to this path (open in "
                         "chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--trace-jsonl", type=str, default=None,
                    help="additionally dump the raw trace events as JSONL "
                         "(one event per line) to this path")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write every serving counter/gauge/histogram in "
                         "Prometheus text-exposition format to this path")
    return ap.parse_args(argv)


def build_engine(args: argparse.Namespace) -> ServeEngine:
    """Random weights from seed 0 at ``args.scale``, compressed into the
    weight store (unless ``--no-compress``) -> the serving engine."""
    cfg = tiny_config(args.arch) if args.scale == "tiny" \
        else cfgs.get_config(args.arch)
    cache_auto = args.cache_mb == "auto"
    cache_bytes = None if args.cache_mb is None or cache_auto \
        else int(float(args.cache_mb) * 2 ** 20)
    # trace spans only when a trace sink was asked for; phase histograms
    # ride along whenever any telemetry output is requested.  The default
    # (no flags) serves with the zero-cost null recorder.
    telemetry = Telemetry(trace=bool(args.trace_out or args.trace_jsonl)) \
        if (args.trace_out or args.trace_jsonl or args.metrics_out) \
        else None

    with shd.use_mesh(make_serving_mesh()):
        # the engine keeps what it serves from; the originals die here
        engine = ServeEngine(
            cfg, get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)),
            compress=not args.no_compress, cache_bytes=cache_bytes,
            cache_policy=args.policy, prefetch=not args.no_prefetch,
            telemetry=telemetry)
        if cache_auto:
            if not engine.compressed:
                raise SystemExit("--cache-mb auto needs the compressed "
                                 "path; drop --no-compress")
            rec = recommend_store_capacity(engine.store, engine.model_id,
                                           policy=args.policy)
            engine.cache.capacity_bytes = rec["capacity"]
            print(f"cache autotune: working set "
                  f"{rec['working_set'] / 2 ** 20:.2f} MiB -> recommended "
                  f"capacity {rec['capacity'] / 2 ** 20:.2f} MiB "
                  f"({rec['fraction']:.2f}x, projected hit rate "
                  f"{rec['hit_rate'] * 100:.1f}%, best "
                  f"{rec['best_rate'] * 100:.1f}%)")
        if engine.compressed:
            rep = engine.report
            print(f"weight store: {rep['layers']} compressed MLP tensors, "
                  f"{rep['packed_bytes']} packed bytes -> "
                  f"{rep['stream_bytes']} stream bytes "
                  f"({rep['ratio_stream']:.3f}x)")
        else:
            print(f"weight store: no compressible MLPs in {args.arch}; "
                  "serving uncompressed")
    return engine


def serve(engine: ServeEngine, args: argparse.Namespace) -> dict:
    """Serve ``args``'s synthetic request mix on ``engine`` and print the
    summary -> {"scheduler", "completed", "wall_s"}.  Each call reports
    its own run: the engine's serving metrics start from zero."""
    cfg = engine.cfg
    n_requests = args.requests or args.batch
    telemetry = engine.telemetry
    engine.metrics = ServeMetrics()
    with shd.use_mesh(make_serving_mesh()):
        sched = Scheduler(engine, batch_size=args.batch, mode=args.mode,
                          prefill_chunk=args.prefill_chunk,
                          prefill_budget=args.prefill_budget,
                          kv_page_size=args.kv_page_size,
                          kv_pages=args.kv_pages,
                          attn_backend=args.attn_backend,
                          kv_codec=args.kv_codec,
                          prefix_share=args.prefix_share,
                          speculate=args.speculate,
                          draft_k=args.draft_k,
                          log_every=args.log_every)
        rng = np.random.default_rng(0)
        shared_len = min(args.shared_prefix_len, args.prompt_len - 1)
        common = rng.integers(0, cfg.vocab_size, max(shared_len, 0))
        for _ in range(n_requests):
            tail_len = args.prompt_len - len(common)
            if args.prompt_pattern:
                pat = rng.integers(0, cfg.vocab_size, args.prompt_pattern)
                tail = np.tile(pat, -(-tail_len // len(pat)))[:tail_len]
            else:
                tail = rng.integers(0, cfg.vocab_size, tail_len)
            sched.submit(np.concatenate([common, tail]), args.gen)

        t0 = time.monotonic()
        completed = sched.run()
        wall = time.monotonic() - t0

    m = engine.metrics
    assert len(completed) == n_requests
    assert all(len(r.generated) == r.max_new_tokens for r in completed)
    print(f"served {len(completed)} requests in {wall:.2f}s "
          f"({args.mode} slots, batch {args.batch}, "
          f"{m.prefills} prefills)")
    ttfts = [r.first_token_latency() for r in completed]
    ttft = sum(t for t in ttfts if t is not None) / max(len(ttfts), 1)
    print(f"prefill: {m.prefill_s:.2f}s total "
          f"(mean time-to-first-token {ttft * 1000:.0f} ms)")
    for label, hist, unit in (("ttft", m.ttft_hist, 1000.0),
                              ("tpot", m.tpot_hist, 1000.0),
                              ("e2e ", m.e2e_hist, 1000.0)):
        if hist.n:
            p50, p90, p99 = hist.percentiles(50, 90, 99)
            print(f"{label}   : p50 {p50 * unit:.1f} ms | "
                  f"p90 {p90 * unit:.1f} ms | p99 {p99 * unit:.1f} ms "
                  f"(n={hist.n})")
    if m.prefill_chunks:
        print(f"chunked prefill: {m.prefill_chunks} chunks of "
              f"<= {args.prefill_chunk} tokens, "
              f"{m.prefill_chunk_ms():.1f} ms/chunk, decode stalled "
              f"{m.decode_stall_s:.2f}s behind chunks")
    print(f"decode : {m.ms_per_token():.1f} ms/step "
          f"({m.tokens_per_s():.1f} tok/s, "
          f"occupancy {m.occupancy() * 100:.0f}%)")
    if m.pages_total:
        print(f"kv pages: {args.kv_page_size}-token pages, pool "
              f"{m.pages_total}, mean occupancy "
              f"{m.page_occupancy() * 100:.0f}%")
        print(f"kv gather ({sched.attn_backend} backend): "
              f"{m.kv_gather_bytes} bytes copied on the decode hot path, "
              f"{m.kv_gather_bytes_avoided} avoided in-kernel")
        print(f"prefill gather: {m.kv_prefill_gather_bytes} bytes copied "
              f"installing prefilled caches, "
              f"{m.kv_prefill_gather_bytes_avoided} avoided by "
              f"mixed-step in-pool prefill")
    if sched.prefix_share:
        pool = sched._pool
        print(f"prefix share: {m.prefix_hits} hits, "
              f"{m.prefix_tokens_reused} prompt tokens served from "
              f"cached pages ({m.prefill_chunks_avoided} prefill chunks "
              f"avoided), {m.prefix_cow_copies} copy-on-write page "
              f"copies, {m.prefix_evictions} index evictions")
        print(f"prefix index: {pool.prefix.n_nodes} cached pages "
              f"covering {pool.prefix.tokens_cached} tokens")
    if args.kv_codec == "cluster":
        pool = sched._pool
        print(f"kv codec (cluster): page {pool.page_bytes_fp} fp bytes -> "
              f"{pool.page_bytes_resident} resident bytes "
              f"({m.kv_capacity_multiplier():.2f}x effective capacity, "
              f"{m.kv_bytes_avoided} resident bytes avoided)")
        print(f"kv codec error bound: {m.kv_codec_error_bound:.3e} "
              f"(max per-token scale / 254)")
        # at-rest Huffman layer over the resident int8 codes (report
        # only — the pool itself stays raw int8 for in-kernel decode)
        codes = (jax.tree_util.tree_leaves(pool.kcache)
                 if pool.backend == "pallas_paged" else pool.pages)
        codes = [np.asarray(c) for c in codes if c.dtype == np.int8]
        if codes:
            from repro.kernels import kv_codec as kvc
            rep = kvc.huffman_report(
                np.concatenate([c.ravel() for c in codes]))
            print(f"kv codec at-rest huffman: {rep['avg_bits']:.2f} "
                  f"bits/code ({rep['ratio']:.2f}x vs int8), clustered "
                  f"{rep['clustered_avg_bits']:.2f} bits "
                  f"({rep['clustered_ratio']:.2f}x)")
    if engine.compressed:
        st = engine.cache.stats()
        print(f"decode-tile cache ({st['policy']}): {st['hits']} hits / "
              f"{st['misses']} misses / {st['evictions']} evictions")
        print(f"cache hit-rate: {st['hit_rate'] * 100:.1f}%")
        print(f"compressed bytes streamed: {st['bytes_streamed']}; "
              f"bytes avoided by cache: {st['bytes_avoided']}")
        if engine.store.prefetch_dispatched:
            print(f"tile prefetch: {engine.store.prefetch_dispatched} "
                  f"dispatched, {engine.store.prefetch_used} consumed")
    if m.spec_rounds:
        total = sum(len(r.generated) for r in completed)
        print(f"speculative ({sched.speculate}, k={sched.draft_k}): "
              f"{m.spec_accepted_tokens}/{m.spec_draft_tokens} draft "
              f"tokens accepted ({m.spec_acceptance_rate() * 100:.0f}%), "
              f"{m.decode_steps / max(total, 1):.2f} verify steps/token")
    print("sample token ids:", completed[0].generated[:16])

    if telemetry.tracing:
        tr = telemetry.tracer
        n_spans = sum(1 for e in tr.events
                      if e["ph"] == "X" and e["name"] == "request")
        assert n_spans == len(completed), \
            f"trace has {n_spans} request spans, served {len(completed)}"
        if args.trace_out:
            tr.write_chrome(args.trace_out)
            with open(args.trace_out) as f:
                loaded = json.load(f)          # self-check: valid JSON
            print(f"trace: {len(loaded['traceEvents'])} events "
                  f"({n_spans} request spans) -> {args.trace_out} "
                  f"(open in chrome://tracing or ui.perfetto.dev)")
        if args.trace_jsonl:
            tr.write_jsonl(args.trace_jsonl)
            print(f"trace events (JSONL) -> {args.trace_jsonl}")
    if args.metrics_out:
        text = engine.render_prom()
        parse_prom(text)                       # self-check: parseable
        with open(args.metrics_out, "w") as f:
            f.write(text)
        print(f"metrics: {len(text.splitlines())} lines of Prometheus "
              f"text exposition -> {args.metrics_out}")
    return {"scheduler": sched, "completed": completed, "wall_s": wall}


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    serve(build_engine(args), args)


if __name__ == "__main__":
    main()
