"""Compressed-weight serving runtime.

Software analogue of the paper's evaluation hardware ("Exploiting Kernel
Compression on BNNs"), module by module:

  ===================  ====================================================
  module               paper structure it mirrors
  ===================  ====================================================
  weight_store         DRAM weight storage: compressed varlen Huffman
                       streams (§III layout); the fetch unit's re-blocking
                       into substream-parallel decode tiles happens lazily
                       on first use (stream -> tiled layout).  Async tile
                       prefetch dispatches the next layer's decodes while
                       the current layer reconstructs — the fetch unit
                       running ahead of the compute pipeline.
  decode_cache         §IV caching unit: a small capacity-bounded store of
                       *decoded* tiles beside the decoder.  Eviction is
                       pluggable (EvictionPolicy): LRU, LFU, and the
                       paper-motivated FrequencyWeighted policy whose
                       victims are ranked by observed accesses plus a
                       static prior seeded from core.frequency occurrence
                       counts — the paper's C1 observation (a few
                       sequences dominate a trained BNN's kernels) turned
                       into an eviction rule, so a one-off cold scan
                       cannot flush the hot set the way it flushes LRU.
  scheduler            the evaluation pipeline driver as slot-level
                       continuous batching: a SlotPool of fixed decode
                       slots, per-slot positions/KV lanes, exact-position
                       prefill on admission (monolithic batch-1 or
                       fixed-size chunks interleaved with decode under a
                       token budget), one decode step for all slots,
                       admit-on-retire.  KV lanes are optionally backed
                       by demand-allocated fixed-size pages
                       (PageAllocator + per-slot page tables) so short
                       requests stop paying long-request memory and the
                       pool grows without recompiling decode.  How decode
                       *reads* those pages is the attention-backend seam
                       (attn_backend): "gathered" copies each slot's
                       pages into a contiguous view per step (reference
                       oracle), "pallas_paged" hands the donated pools +
                       page tables to kernels.paged_attention, which
                       walks the table in-kernel — the §IV consume-in-
                       place principle applied to KV, zero per-step cache
                       copies.  With chunked prefill it runs mixed-step
                       execution: prefill chunks and decode tokens of
                       every slot ride one ragged batched invocation per
                       iteration, chunks write straight into the pools,
                       and the prefill path's install copy disappears
                       too.  mode="wave" reproduces the old
                       wave-granular scheduling as a slot config; every
                       scheduling config and both backends are
                       token-identical, only latency, occupancy, and
                       copy traffic differ.
  prefix_index         the paper's C1 skew applied to *requests*: a
                       page-granular token trie caching completed
                       prefills' KV pages, so a prompt extending a cached
                       prefix maps those refcounted pages into its page
                       table with zero prefill work; writes into shared
                       pages copy-on-write, and eviction ranks entries
                       with the same FrequencyWeighted prior (prefix
                       hits as occurrence mass) the decode cache uses.
  metrics              the paper's measured quantities as counters:
                       throughput, slot occupancy, decode-cache hit rate,
                       HBM bytes streamed vs avoided, prefill-chunk
                       latency / decode stall, KV-page occupancy, and
                       KV gather/scatter bytes moved vs avoided on both
                       the decode and prefill paths (the acceptance
                       signal for the in-kernel backend and the
                       mixed-step path: both must read 0 moved) — plus
                       latency *distributions*: log-bucket histograms
                       (TTFT / time-per-output-token / end-to-end /
                       chunk / step) with p50/p99, windowed stats-line
                       rates, and Prometheus text export (render_prom).
  telemetry            the observability layer: per-request lifecycle
                       span trees (queued -> admitted -> prefill_chunk[i]
                       -> decode -> retired) exportable as Chrome-trace
                       JSON / JSONL, phase-timing hooks (timed(phase)),
                       and the pull-based metrics registry behind
                       render_prom.  Default is a zero-cost null
                       recorder; telemetry never changes tokens.
  autotune             capacity recommendation: replay the materialize
                       access pattern over a capacity grid, find the
                       hit-rate-cliff knee (the launcher's
                       ``--cache-mb auto``).
  ===================  ====================================================

The module <-> paper-structure mapping, with the request lifecycle
diagram, is documented in docs/ARCHITECTURE.md.

The fused Pallas path (``kernels.fused_decode_contraction``) remains the
in-kernel decoder (decode-on-the-fly, nothing cached); the runtime adds the
complementary cached mode and serves both from one WeightStore so they stay
bit-identical (tests/test_runtime.py round-trip).
"""

from repro.runtime.autotune import (find_knee, recommend_store_capacity,
                                    sweep_store)
from repro.runtime.decode_cache import (DecodeTileCache, EvictionPolicy,
                                        FrequencyWeightedPolicy, LFUPolicy,
                                        LRUPolicy, make_policy)
from repro.runtime.metrics import ServeMetrics
from repro.runtime.prefix_index import PrefixIndex, PrefixNode
from repro.runtime.scheduler import (PageAllocator, Request, Scheduler,
                                     ServeEngine, Slot, SlotPool)
from repro.runtime.telemetry import (NULL_TELEMETRY, Histogram,
                                     MetricsRegistry, NullTelemetry,
                                     Telemetry, Tracer, parse_prom)
from repro.runtime.weight_store import StoredLayer, WeightStore

__all__ = [
    "DecodeTileCache",
    "EvictionPolicy",
    "FrequencyWeightedPolicy",
    "Histogram",
    "LFUPolicy",
    "LRUPolicy",
    "MetricsRegistry",
    "NULL_TELEMETRY",
    "NullTelemetry",
    "PageAllocator",
    "PrefixIndex",
    "PrefixNode",
    "Request",
    "Scheduler",
    "ServeEngine",
    "ServeMetrics",
    "Slot",
    "SlotPool",
    "StoredLayer",
    "Telemetry",
    "Tracer",
    "WeightStore",
    "find_knee",
    "make_policy",
    "parse_prom",
    "recommend_store_capacity",
    "sweep_store",
]
