"""Slot-level continuous batching: SlotPool + paged KV + chunked prefill.

The serving core is a **SlotPool** — a fixed set of decode slots, each one
batch lane of a pooled per-slot KV cache.  Every per-request quantity the
old wave loop shared across a batch is per-slot state here:

  admit    — a queued request takes any free slot: its prompt is prefilled
             alone (batch-1, exact length, exact positions — no pad tokens
             visible to attention, no RoPE shift) and the filled cache is
             scattered into the slot's lane.  With ``prefill_chunk`` set,
             the prompt is split into fixed-size chunks interleaved with
             decode steps of the other slots (a token budget per scheduler
             iteration bounds the decode-latency impact) — token-identical
             to monolithic prefill because each chunk attends to the
             already-prefilled cache under the same absolute-position
             masks;
  decode   — ONE jit(vmap(decode_step)) advances every slot with its own
             position; slots at different depths of different requests
             share each step's weight-tile fetch, so decoded-tile reuse is
             continuous across request boundaries instead of resetting at
             wave boundaries;
  retire   — a slot whose request exhausted its budget frees immediately
             and is refilled from the queue *before the next decode step*
             (admit-on-retire), so finished requests never idle a lane.

With ``kv_page_size`` set, the length-scaling KV lanes are backed by a
pool of fixed-size pages handed out by a :class:`PageAllocator` instead of
one monolithic ``(n_slots, 1, slot_len, ...)`` buffer: a slot owns only
the pages its positions have reached, short requests stop paying for
long-request memory, and the page pool can grow (``SlotPool.grow_pages``)
without recompiling the vmapped decode step — only the cheap page
gather/scatter re-traces.  ``kv_page_size=None`` keeps the PR-2 monolithic
lanes (donated in-place decode, zero gather traffic); one page = whole
lane reproduces the same tokens through the paged machinery (equivalence
locked down in tests/test_paged_prefill.py).

Scheduler-state invariants (enforced by construction, asserted in tests):

  * slot lifecycle   — FREE (req is None) -> PREFILLING (req set,
    ``prefilling``; under the gathered backend the chunk cursor advances
    on a standalone batch-1 cache outside the pool, under the
    ``pallas_paged`` **mixed-step** path chunks write straight into the
    slot's pages/lane and no standalone cache exists) -> ACTIVE (cache
    in the lane/pages, decode advances ``pos``) -> FREE (retire releases
    pages + reservations).  Admission overwrites the whole lane — and
    mixed-step prefill rewrites every position before the masks can
    expose it — so a free lane's stale state can never leak into a new
    request.
  * page ownership   — a physical page is referenced by at most one slot's
    table row; page 0 is the shared dummy sink that absorbs writes from
    free lanes (which keep decoding for fixed shapes, output discarded)
    and is never read as a valid position (attention masks by absolute
    position, and every position < a slot's cursor has a real page).
  * no mid-flight OOM — admission reserves every page the request can ever
    need (ceil(cache_len / page_size)); on-demand allocation during decode
    draws from that reservation, so it cannot fail; retire returns unused
    reservations.
  * ``mode="wave"``   — reproduces the old wave-granular scheduling as a
    slot configuration: admission only happens when the pool has fully
    drained, so slots retire in place and freed lanes idle until the wave
    ends.  Both modes run the same per-slot decode, which is what makes
    them token-identical (the scheduler equivalence test) — scheduling
    policy changes throughput, never content.

Every decode step asks the WeightStore to materialise the serving params:
on step 1 the tiles stream+decode (cache misses); from step 2 on they are
served from the decode cache and the memoised device arrays are reused.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import kv_codec as kv_codec_mod
from repro.kernels.kv_codec import KV_CODECS
from repro.kernels.paged_attention import effective_q_block
from repro.kernels.platform import interpret_mode
from repro.models.api import (ATTN_BACKENDS, cache_layout, get_model,
                              padded_page_dims, supports_chunked_prefill,
                              supports_paged_attention,
                              supports_prefix_share, supports_speculation)
from repro.runtime import weight_store as ws_mod
from repro.runtime.decode_cache import DecodeTileCache, EvictionPolicy
from repro.runtime.metrics import ServeMetrics
from repro.runtime.prefix_index import PrefixIndex
from repro.runtime.telemetry import (NULL_TELEMETRY, PID_REQUEST,
                                     Telemetry)
from repro.runtime.weight_store import WeightStore

DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)
SLOT_LEN_QUANTUM = 16      # slot cache lengths round up to this many tokens
DUMMY_PAGE = 0             # physical page that absorbs idle-lane writes

# capability downgrades warn once per (arch family, capability) so a
# fleet of Scheduler instances does not spam, but the first silent
# downgrade is impossible (satellite of the mixed-step refactor)
_FALLBACK_WARNED: set = set()


def _warn_fallback(family: str, capability: str, message: str) -> None:
    key = (family, capability)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(message, RuntimeWarning, stacklevel=3)


# the kernel rounds a q_block that does not divide this step's Q down to
# gcd(Q, q_block); every rounded step bumps kernel_qblock_rounded, the
# first one per (Q, q_block) also warns so the degraded launch shape is
# impossible to miss
_QBLOCK_WARNED: set = set()


def _warn_qblock_rounded(qn: int, q_block: int) -> None:
    key = (qn, q_block)
    if key in _QBLOCK_WARNED:
        return
    _QBLOCK_WARNED.add(key)
    warnings.warn(
        f"kernel q_block={q_block} does not divide this step's Q={qn}; "
        f"rounding down to gcd={effective_q_block(qn, q_block)} "
        "(counted in kernel_qblock_rounded)", RuntimeWarning,
        stacklevel=3)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                    # (L,) int32 token ids
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0                 # monotonic submission time
    t_admit: float | None = None          # monotonic admission time
    t_first: float | None = None          # monotonic first-token time
    t_done: float | None = None           # monotonic retire time

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    def first_token_latency(self) -> float | None:
        """Seconds from submission to the first generated token."""
        return None if self.t_first is None else self.t_first - self.t_submit


class PageAllocator:
    """Free-list allocator over a fixed set of physical KV page ids, with
    admission-time reservations.

    ``reserve(n)`` earmarks capacity without picking pages (called once per
    admitted request with its worst-case page count); ``alloc`` hands out a
    concrete page against an existing reservation, so on-demand allocation
    during decode can never fail mid-request.

    Pages are **refcounted** so prefix sharing can map one physical page
    into several owners: ``alloc`` starts a page at refcount 1, ``share``
    takes another reference (no free-list traffic, no reservation), and
    ``release`` drops one reference per call — the page returns to the
    free list only when the last reference goes.  Invariants (see
    tests/test_paged_prefill.py and tests/test_prefix_share.py): every id
    is free xor allocated-with-refcount >= 1, a page is never handed out
    twice without fully releasing it, releasing a page that is not
    allocated raises ``ValueError`` (double frees must never silently
    corrupt the free list), and ``reserved <= len(free)`` at all times.
    """

    def __init__(self, page_ids):
        ids = list(page_ids)
        self.total = len(ids)
        self._free = sorted(ids, reverse=True)    # pop() -> ascending ids
        self._allocated: set[int] = set()
        self._refs: dict[int, int] = {}
        self.reserved = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_allocated(self) -> int:
        return len(self._allocated)

    def available(self) -> int:
        """Pages free and not spoken for by a reservation."""
        return len(self._free) - self.reserved

    def reserve(self, n: int) -> bool:
        """Earmark ``n`` future allocations; False if they could not all be
        satisfied (the caller should defer admission, not retry-loop)."""
        if n > self.available():
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        assert 0 <= n <= self.reserved, (n, self.reserved)
        self.reserved -= n

    def alloc(self) -> int:
        """One page against an existing reservation (refcount 1)."""
        assert self.reserved > 0, "alloc without reservation"
        assert self._free, "reservation invariant broken: no free pages"
        self.reserved -= 1
        pid = self._free.pop()
        self._allocated.add(pid)
        self._refs[pid] = 1
        return pid

    def share(self, pid: int) -> int:
        """Take one more reference on an allocated page (prefix sharing).
        Consumes no free pages and no reservation."""
        if pid not in self._allocated:
            raise ValueError(f"share of unallocated page {pid}")
        self._refs[pid] += 1
        return pid

    def refcount(self, pid: int) -> int:
        return self._refs.get(pid, 0)

    def shared_pages(self) -> int:
        """Physical pages currently referenced by more than one owner."""
        return sum(1 for r in self._refs.values() if r >= 2)

    def release(self, page_ids) -> None:
        """Drop one reference per page; a page returns to the free list
        only when its last reference goes."""
        for pid in page_ids:
            if pid not in self._allocated:
                raise ValueError(f"double free of page {pid}")
            self._refs[pid] -= 1
            if self._refs[pid] == 0:
                del self._refs[pid]
                self._allocated.remove(pid)
                self._free.append(pid)

    def add_pages(self, page_ids) -> None:
        """Grow the pool (``SlotPool.grow_pages``)."""
        ids = list(page_ids)
        assert not (set(ids) & self._allocated) and \
            not (set(ids) & set(self._free))
        self.total += len(ids)
        self._free.extend(sorted(ids, reverse=True))


class ServeEngine:
    """Model + compressed weight store + decode cache + metrics.

    ``compress=True`` binarises and Huffman-compresses the model's MLP
    projections into the store and serves in BNN-MLP mode
    (``cfg.binarize_mlp``); ``compress=False`` is the uncompressed baseline
    on the same scheduler.  ``cache_policy`` picks the decode-cache
    eviction policy (``lru`` | ``lfu`` | ``freq`` or an EvictionPolicy
    instance); ``prefetch`` toggles async next-layer tile prefetch.
    ``telemetry`` accepts a ``runtime.telemetry.Telemetry`` recorder
    (request-lifecycle spans + phase histograms); the default is the
    zero-cost null recorder, and telemetry never changes generated
    tokens (tested).
    """

    def __init__(self, cfg, params, *, compress: bool = True,
                 cache_bytes: int | None = None, model_id: str = "lm",
                 cluster: bool = False,
                 cache_policy: str | EvictionPolicy | None = None,
                 prefetch: bool = True,
                 telemetry: Telemetry | None = None,
                 select: Callable[[str, int], bool] = ws_mod.default_select):
        self.cache = DecodeTileCache(cache_bytes, policy=cache_policy)
        self.telemetry = telemetry if telemetry is not None \
            else NULL_TELEMETRY
        self.store = WeightStore(self.cache, prefetch=prefetch,
                                 telemetry=self.telemetry)
        self.metrics = ServeMetrics()
        self.model_id = model_id
        self.compressed = False
        if compress:
            try:
                self.report = self.store.register_model(
                    model_id, params, cluster=cluster, select=select)
                self.compressed = True
                cfg = cfg.scaled(binarize_mlp=True)
            except ValueError:
                # arch without compressible MLPs (pure SSM etc.): serve raw
                self.report = None
        self.cfg = cfg
        self.api = get_model(cfg)
        # compressed serving keeps only the store's compressed streams +
        # memoised reconstructions; the originals are released
        self._raw_params = None if self.compressed else params
        # per-slot decode: vmap gives every batch lane its own position and
        # cache lane (leaves (S, 1, ...)); one compile per (S, slot_len).
        # The pooled cache is donated — the KV update happens in place
        # instead of copying every lane's cache each step.
        def _mk_slot_decode(kvq: bool):
            if kvq:
                step = lambda p, c, t, q: self.api.decode_step(
                    self.cfg, p, c, t, q, kv_quant=True)
            else:   # families without kv_quant (encdec) share this path
                step = lambda p, c, t, q: self.api.decode_step(
                    self.cfg, p, c, t, q)
            return jax.jit(jax.vmap(step, in_axes=(None, 0, 0, 0)),
                           donate_argnums=(1,))

        # keyed by kv_quant: under kv_codec="cluster" the gathered decode
        # quantises the new row before write *and* attention, matching
        # the paged kernel's in-VMEM decode numerics
        self._slot_decode_jits = {kvq: _mk_slot_decode(kvq)
                                  for kvq in (False, True)}
        self._slot_decode_jit = self._slot_decode_jits[False]
        self._decode_jit = jax.jit(
            lambda p, c, t, q: self.api.decode_step(self.cfg, p, c, t, q))
        # chunked prefill: batch-1, one compile per distinct chunk length
        # (fixed-size chunks + one remainder size keep that bounded);
        # keyed by kv_quant (the codec round-trip is baked into the trace)
        self._chunk_jit = None
        self._chunk_jits: dict = {}
        if self.api.prefill_chunk is not None:
            for kvq in (False, True):
                self._chunk_jits[kvq] = jax.jit(
                    functools.partial(
                        lambda kvq, p, c, t, q: self.api.prefill_chunk(
                            self.cfg, p, c, t, q, kv_quant=kvq), kvq),
                    donate_argnums=(1,))
            self._chunk_jit = self._chunk_jits[False]
        # speculative verification: vmapped over slot lanes (leaves
        # (S, 1, ...), toks (S, 1, Q), poss/q_lens (S,)), keyed by
        # (commit, kv_quant) — the non-committing scoring pass keeps the
        # input cache alive for the rollback-free commit pass, which
        # donates it
        self._verify_jits: dict = {}
        # pallas_paged backend: one compiled mixed step per (cache layout,
        # padded block width) — decode-only ticks compile at Q=1, chunked
        # ticks at Q=prefill_chunk (the pools are donated; the Pallas
        # kernel runs interpreted on CPU, compiled on TPU)
        self.kernel_interpret = interpret_mode()
        self._mixed_jits: dict = {}

    @property
    def supports_chunked_prefill(self) -> bool:
        return self._chunk_jit is not None and \
            supports_chunked_prefill(self.cfg)

    @property
    def supports_paged_attention(self) -> bool:
        return self.api.mixed_step is not None and \
            supports_paged_attention(self.cfg)

    def mixed_step(self, params, kcache, table, toks, poss, q_lens, *,
                   paged_flags: tuple, page_size: int, q_block: int = 0,
                   pages_per_step: int = 1, kv_scales=None):
        """One ragged mixed step for every slot straight over the paged
        pools: toks (S, Q) int32, poss (S,) int32 start positions, q_lens
        (S,) int32 real token counts (0 = free lane) -> (logits (S, Q, V),
        new cache tree).  ``kcache`` is donated — the page-pool update
        happens in place, with no gather/scatter anywhere on the prefill
        or decode path.

        ``q_block`` / ``pages_per_step`` are the tuned kernel launch
        parameters (``runtime.autotune.tune_kernel``); a ``q_block``
        that does not divide this step's ``Q`` silently rounds down to
        ``gcd(Q, q_block)`` inside the kernel, so the rounding is
        counted (``kernel_qblock_rounded``) and warned once here.

        ``kv_scales`` (``kv_codec="cluster"``): the scale-pool tree
        riding alongside int8 code pools; it is donated too and the
        return grows to ``(logits, new cache, new scales)``."""
        codec = kv_scales is not None
        qn = int(toks.shape[1])
        eff = effective_q_block(qn, q_block)
        if q_block and eff not in (q_block, qn):
            # eff == qn (e.g. decode's Q=1) still runs one whole-Q block
            # — nothing degraded; only a genuinely fragmented launch
            # counts
            self.metrics.record_kernel_qblock_rounded()
            _warn_qblock_rounded(qn, q_block)
        fn = self.mixed_step_fn(paged_flags, page_size, qn, codec, q_block,
                                pages_per_step)
        if codec:
            return fn(params, kcache, table, toks, poss, q_lens, kv_scales)
        return fn(params, kcache, table, toks, poss, q_lens)

    def mixed_step_fn(self, paged_flags: tuple, page_size: int, qn: int,
                      codec: bool, q_block: int = 0,
                      pages_per_step: int = 1):
        """The jitted mixed step for one (layout, block width, codec,
        launch shape) key, built on first use.  Its arguments are
        ``mixed_step``'s, with the scale tree last under the codec."""
        key = (paged_flags, page_size, qn, codec, q_block, pages_per_step)
        fn = self._mixed_jits.get(key)
        if fn is None:
            step = functools.partial(
                self.api.mixed_step, self.cfg,
                paged_flags=paged_flags, page_size=page_size,
                q_block=q_block, pages_per_step=pages_per_step,
                interpret=self.kernel_interpret)
            if codec:
                fn = jax.jit(
                    lambda p, c, t, tok, pos, ql, sc:
                        step(p, c, t, tok, pos, ql, scales=sc),
                    donate_argnums=(1, 6))
            else:
                fn = jax.jit(
                    lambda p, c, t, tok, pos, ql:
                        step(p, c, t, tok, pos, ql),
                    donate_argnums=(1,))
            self._mixed_jits[key] = fn
        return fn

    def step_params(self):
        """Per-step serving params (tile-cache-served when compressed)."""
        if self.compressed:
            return self.store.materialize(self.model_id)
        return self._raw_params

    # stubbed multimodal frontends, matching the launcher conventions
    def extra_inputs(self, batch: int) -> tuple:
        cfg = self.cfg
        if cfg.family == "vlm":
            return (jnp.zeros((batch, cfg.num_vision_tokens, cfg.d_model),
                              cfg.jnp_dtype),)
        if cfg.family == "audio":
            return (jnp.zeros((batch, cfg.encoder_seq, cfg.d_model),
                              cfg.jnp_dtype),)
        return ()

    def pos_offset(self, prompt_len: int) -> int:
        """Absolute position of the first generated token."""
        if self.cfg.family == "vlm":
            return prompt_len + self.cfg.num_vision_tokens
        return prompt_len

    def cache_len(self, prompt_len: int, gen: int) -> int:
        return self.pos_offset(prompt_len) + gen

    def prefill(self, params, tokens, cache, *extra):
        if self.cfg.family == "vlm":
            return self.api.prefill(self.cfg, params, tokens, cache,
                                    vision_embeds=extra[0])
        return self.api.prefill(self.cfg, params, tokens, cache, *extra)

    def prefill_request(self, params, prompt: np.ndarray, slot_len: int):
        """Batch-1 exact-position prefill -> (first generated token, filled
        slot cache with leaves (1, ...))."""
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
        cache = self.api.init_cache(self.cfg, 1, slot_len)
        logits, cache = self.prefill(params, toks, cache,
                                     *self.extra_inputs(1))
        if not bool(jnp.isfinite(logits[0, -1]).all()):
            raise RuntimeError(
                "non-finite prefill logits (compressed reconstruction or "
                "model numerics are broken)")
        return int(jnp.argmax(logits[0, -1])), cache

    def fresh_slot_cache(self, slot_len: int):
        """Zeroed batch-1 cache for an in-flight chunked prefill."""
        return self.api.init_cache(self.cfg, 1, slot_len)

    def prefill_chunk_step(self, params, cache, chunk: np.ndarray,
                           pos: int, *, kv_quant: bool = False):
        """One prompt chunk at absolute positions pos..pos+len-1 ->
        (last-position logits, updated cache).  The cache argument is
        donated.  ``kv_quant`` round-trips the chunk's K/V through the
        cluster codec (gathered backend under ``kv_codec="cluster"``)."""
        toks = jnp.asarray(np.asarray(chunk, np.int32)[None])
        return self._chunk_jits[bool(kv_quant)](params, cache, toks,
                                                jnp.int32(pos))

    def verify_slots(self, params, pooled_cache, toks, poss, q_lens, *,
                     commit: bool, kv_quant: bool = False):
        """Speculative verification over slot lanes: toks (S, 1, Q) int32,
        poss (S,) int32 start positions, q_lens (S,) int32 real token
        counts (0 = idle lane, an exact cache no-op) -> (full logits
        (S, 1, Q, V), new pooled cache).

        ``commit=False`` scores drafts without donating the cache (the
        new cache is discarded, the input stays alive); ``commit=True``
        re-runs with the accepted lengths and donates, writing exactly
        the accepted tokens' KV in place — speculative rollback by
        construction, with no pool rewind."""
        key = (bool(commit), bool(kv_quant))
        fn = self._verify_jits.get(key)
        if fn is None:
            fn = jax.jit(
                functools.partial(
                    lambda kvq, p, c, t, pos, ql: jax.vmap(
                        lambda c1, t1, pos1, ql1: self.api.verify_step(
                            self.cfg, p, c1, t1, pos1, ql1, kv_quant=kvq),
                        in_axes=(0, 0, 0, 0))(c, t, pos, ql),
                    bool(kv_quant)),
                donate_argnums=(1,) if commit else ())
            self._verify_jits[key] = fn
        # q_lens rides as (S, 1) so each vmapped lane sees a (1,) array
        # (the ragged masks index it per-lane)
        return fn(params, pooled_cache, toks, poss,
                  jnp.asarray(q_lens, jnp.int32).reshape(-1, 1))

    def slot_decode(self, params, pooled_cache, toks, poss, *,
                    kv_quant: bool = False):
        """One decode step for every slot: toks (S, 1, 1) int32, poss (S,)
        int32 -> (logits (S, 1, 1, V), new pooled cache)."""
        return self._slot_decode_jits[bool(kv_quant)](
            params, pooled_cache, toks, poss)

    def decode_step(self, params, cache, tok, pos: int):
        """Single shared-position decode (legacy path; slot serving goes
        through :meth:`slot_decode`)."""
        return self._decode_jit(params, cache, tok, jnp.int32(pos))

    def stats_line(self) -> str:
        return self.metrics.stats_line(self.cache if self.compressed
                                       else None)

    def render_prom(self) -> str:
        """Prometheus text exposition of every serving metric: the
        ServeMetrics counters + histograms, the decode-cache and
        weight-store counters, and any telemetry phase histograms."""
        return self.metrics.render_prom(cache=self.cache, store=self.store,
                                        telemetry=self.telemetry)


@dataclasses.dataclass
class Slot:
    """One decode lane: its request and per-slot decode state.

    ``tok`` is the most recently generated token (already appended to the
    request) and the next decode input; ``pos`` is its absolute position.
    While ``prefilling``, the slot owns the request but not yet a lane:
    ``prefill_cursor`` counts prompt tokens already pushed through
    ``prefill_chunk`` into ``pcache`` (a standalone batch-1 cache that is
    installed into the pool when the last chunk lands).  ``reserved_left``
    is the slot's outstanding page reservation (paged pools only).
    ``prefix_matched`` counts prompt tokens served from the prefix index
    at admission — the chunk loop starts its cursor there, so those
    tokens cost zero prefill work; ``_prefix_nodes`` holds the mapped
    index nodes until the slot activates (gathered-backend pcache
    seeding).
    """

    index: int
    req: Request | None = None
    pos: int = 0
    tok: int = 0
    prefilling: bool = False
    prefill_cursor: int = 0
    pcache: object = None
    reserved_left: int = 0
    prefix_matched: int = 0
    _prefix_nodes: list | None = None


class SlotPool:
    """Fixed decode slots over one pooled per-slot KV cache.

    ``page_size=None`` (default): the PR-2 monolithic layout — each slot's
    cache is batch lane ``index`` of one pooled buffer (leaves
    ``(n_slots, 1, slot_len, ...)``), donated into the vmapped decode so
    the KV update happens in place.

    ``page_size=N``: length-scaling cache leaves are re-backed by a pool
    of fixed-size pages plus a per-slot page table.  How decode consumes
    that pool is the **attention-backend seam** (``backend``):

      * ``"gathered"`` — decode gathers each lane's pages into the same
        contiguous view the monolithic path uses (so the compiled decode
        step is identical) and scatters the updated pages back: two full
        cache copies per step, kept as the reference oracle;
      * ``"pallas_paged"`` — the pools are stored in the kernel-consumable
        layout (each pageable leaf's length axis becomes ``(n_pages,
        page)`` in place, the batch axis is dropped; lane leaves batch the
        slot axis in place of batch) and the donated tree is handed to
        ``mixed_step`` together with the page table: the Pallas
        kernel walks the table in-kernel and the per-step
        ``_gather``/``_scatter_pages`` copies disappear entirely.  The
        gather/scatter machinery survives only for admission (installing a
        prefilled batch-1 cache into the pool) and the fallback backend.

    Pages are allocated on demand as a slot's position crosses page
    boundaries and released at retire; leaves whose length does not scale
    with ``slot_len`` (rolling-window KV, recurrent states,
    cross-attention) stay per-slot lanes under both backends.  Page 0 is a
    shared dummy sink: unallocated table entries point at it, free lanes
    write into it, and attention's absolute-position masks guarantee it is
    never read as a valid key.

    ``page_capacity`` (default ``n_pages``) sizes the *physical buffers*;
    ``grow_pages`` up to the capacity is pure free-list bookkeeping — no
    buffer realloc, no re-trace, and (crucially, under ``pallas_paged``,
    whose compiled decode is keyed on the pool shape) no decode recompile.
    Growth beyond capacity reallocates with geometric headroom.

    Free lanes keep decoding (fixed shapes — same cost as the old
    full-wave step) but their output is discarded and their state never
    leaks: admission overwrites the whole lane.
    """

    def __init__(self, engine: ServeEngine, n_slots: int, slot_len: int,
                 *, page_size: int | None = None,
                 n_pages: int | None = None,
                 backend: str = "gathered",
                 page_capacity: int | None = None,
                 kv_codec: str = "none",
                 prefix_share: bool = False,
                 q_block: int = 0,
                 pages_per_step: int = 1,
                 hw_tiles: bool = False):
        if backend not in ATTN_BACKENDS:
            raise ValueError(f"unknown attention backend {backend!r}")
        if kv_codec not in KV_CODECS:
            raise ValueError(f"unknown kv codec {kv_codec!r}; "
                             f"choose from {KV_CODECS}")
        if (hw_tiles or pages_per_step != 1 or q_block) and \
                backend != "pallas_paged":
            raise ValueError("hw_tiles / pages_per_step / q_block shape "
                             "the pallas_paged kernel launch; the "
                             f"{backend!r} backend does not consume them")
        self.engine = engine
        self.n_slots = n_slots
        self.page_size = page_size
        self.paged = page_size is not None
        self.backend = backend
        self.kv_codec = kv_codec
        self.codec = kv_codec == "cluster"
        self.q_block = q_block
        self.pages_per_step = max(int(pages_per_step), 1)
        self.hw_tiles = hw_tiles
        self.prefix_share = prefix_share
        self.prefix: PrefixIndex | None = None
        if backend == "pallas_paged" and not self.paged:
            raise ValueError("the pallas_paged backend needs paged KV "
                             "lanes; set a page_size")
        if self.codec and not self.paged:
            raise ValueError("kv_codec='cluster' compresses the page "
                             "pools; set a kv page_size")
        if prefix_share and not self.paged:
            raise ValueError("prefix_share maps shared KV pages; set a "
                             "page_size")
        if self.paged:
            if page_size <= 0:
                raise ValueError(f"page_size must be positive: {page_size}")
            slot_len = -(-slot_len // page_size) * page_size
        self.slot_len = slot_len
        self.pages_per_slot = (slot_len // page_size) if self.paged else 0
        self.slots = [Slot(i) for i in range(n_slots)]
        self.kscales = None          # pallas_paged codec scale-pool tree
        self.page_scales = []        # gathered codec scale pools
        self.page_bytes_fp = 0
        self.page_bytes_resident = 0
        specs = engine.api.init_cache_specs(engine.cfg, 1, slot_len)
        # install() copies one freshly prefilled batch-1 cache into the
        # slot's pages + lane — the prefill-path gather traffic the
        # mixed-step path eliminates (its chunks write straight into the
        # pools, so a chunked pallas_paged admission never installs)
        self.install_bytes = sum(
            int(np.prod(s.shape)) * s.dtype.itemsize
            for s in jax.tree_util.tree_leaves(specs))
        if not self.paged:
            self.cache = jax.tree_util.tree_map(
                lambda s: jnp.zeros((n_slots, *s.shape), s.dtype), specs)
            self._scatter = jax.jit(
                lambda pool, new, i: jax.tree_util.tree_map(
                    lambda p, n: p.at[i].set(n.astype(p.dtype)), pool, new),
                donate_argnums=(0,))
            self.gather_bytes_per_step = 0
            self.gather_bytes_avoided_per_step = 0
            return
        # -- paged layout ---------------------------------------------------
        # A leaf is paged iff its shape scales 1:1 with slot_len (full-length
        # KV); rolling-window, recurrent-state, and encoder-length leaves
        # keep per-slot lanes.  ``models.api.cache_layout`` probes the spec
        # factory instead of guessing from shapes (scan-stacked leaves carry
        # a leading repeats dim, e.g. (R, 1, L, KH, HD)) — the same probe
        # the paged decode step interprets the tree with, so the scheduler
        # and the model cannot disagree about which leaves page.
        leaves_a, self._treedef = jax.tree_util.tree_flatten(specs)
        self._batch_axis, self._paged_axis = cache_layout(
            engine.api, engine.cfg, slot_len)
        self.paged_flags = tuple(ax is not None for ax in self._paged_axis)
        if n_pages is None:
            n_pages = n_slots * self.pages_per_slot + 1   # +1: dummy sink
        if n_pages < self.pages_per_slot + 1:
            raise ValueError(
                f"n_pages {n_pages} cannot back even one full slot "
                f"({self.pages_per_slot} pages + dummy)")
        self.n_pages = n_pages
        self.page_capacity = max(page_capacity or 0, n_pages)
        self.allocator = PageAllocator(range(1, n_pages))   # 0 = dummy
        self.table = np.zeros((n_slots, self.pages_per_slot), np.int32)
        # per-step copy accounting: the gathered backend moves every paged
        # leaf's per-slot view twice per step (pool -> view, view -> pool);
        # the kernel backend moves none of it
        view_bytes = 2 * n_slots * sum(
            int(np.prod(sa.shape)) * sa.dtype.itemsize
            for sa, ax in zip(leaves_a, self._paged_axis) if ax is not None)
        cap = self.page_capacity
        # per-physical-page resident bytes across all paged leaves: fp at
        # rest vs kv_codec="cluster"'s int8 codes + one f32 scale per
        # (page, token) — the at-rest compression the codec-ratio metric
        # and benchmark section report
        fp_page, codec_page = 0, 0
        for sa, ax in zip(leaves_a, self._paged_axis):
            if ax is None:
                continue
            elems = int(np.prod(sa.shape)) // sa.shape[ax] * page_size
            feat = int(np.prod(sa.shape[ax + 1:])) or 1
            fp_page += elems * sa.dtype.itemsize
            codec_page += elems + (elems // feat) * 4
        self.page_bytes_fp = fp_page
        self.page_bytes_resident = codec_page if self.codec else fp_page
        if prefix_share:
            # every cache leaf must page for a mapped prefix to carry the
            # request's whole state (Scheduler gates on the
            # supports_prefix_share probe before building the pool)
            if not all(self.paged_flags):
                raise ValueError(
                    "prefix_share needs every cache leaf paged; this "
                    "arch keeps per-slot lanes a shared page cannot "
                    "carry")
            self.prefix = PrefixIndex(self.allocator, page_size,
                                      page_bytes=self.page_bytes_resident)
        if backend == "pallas_paged":
            self.gather_bytes_per_step = 0
            self.gather_bytes_avoided_per_step = view_bytes
            # kernel-consumable layout: length axis -> (n_pages, page) in
            # place with the batch-1 axis dropped; lane leaves carry the
            # slot axis where batch sat, so the paged decode runs all
            # slots in one batched trace
            # hardware tiling pads each pool's page (sublane) dim and
            # trailing feature (lane) dim toward the (8, 128) register
            # tiles; the padding is layout-only — write() zero-fills it,
            # the kernel masks the extra rows, and zero feature columns
            # drop out of every dot product exactly
            self.page_rows = padded_page_dims(
                (page_size,), 0, page_size, hw_tiles)[0] \
                if self.paged else page_size
            kleaves, sleaves = [], []
            # lane leaves under this backend are rolling-window KV: the
            # slot axis sits where batch sat (bax) and the W rolling rows
            # right behind it.  Speculative verification snapshots the
            # draft-covered rows before a mixed step and restores the
            # rejected ones after — a stale rejected row at position p
            # would otherwise be reinterpreted as position p - W inside
            # a future window.  ``lane_min_rows`` bounds the draft depth
            # (distinct modular rows per leaf).
            self._lane_info: list[tuple[int, int, int]] = []
            for li, (sa, ax, bax) in enumerate(zip(leaves_a,
                                                   self._paged_axis,
                                                   self._batch_axis)):
                if ax is not None:
                    assert bax == ax - 1 and sa.shape[bax] == 1, \
                        (sa.shape, ax, bax)
                    rows, feat = padded_page_dims(sa.shape, ax, page_size,
                                                  hw_tiles)
                    kleaves.append(jnp.zeros(
                        (*sa.shape[:ax - 1], cap, rows, *feat),
                        jnp.int8 if self.codec else sa.dtype))
                    sleaves.append(jnp.zeros(
                        (*sa.shape[:ax - 1], cap, rows), jnp.float32)
                        if self.codec else None)
                else:
                    kleaves.append(jnp.zeros(
                        (*sa.shape[:bax], n_slots, *sa.shape[bax + 1:]),
                        sa.dtype))
                    sleaves.append(None)
                    self._lane_info.append((li, bax, sa.shape[bax + 1]))
            self.lane_min_rows = min(
                (w for _, _, w in self._lane_info), default=None)
            self.kcache = jax.tree_util.tree_unflatten(self._treedef,
                                                       kleaves)
            # scale-pool tree: same treedef position-for-position, f32
            # (n_pages, page) pools at pageable leaves, None elsewhere —
            # the canonical per-leaf form mixed_step round-trips
            self.kscales = jax.tree_util.tree_unflatten(
                self._treedef, sleaves) if self.codec else None
            self._build_kernel_jits()
            return
        self.gather_bytes_per_step = view_bytes
        self.gather_bytes_avoided_per_step = 0
        self.pages = [
            jnp.zeros((cap, *sa.shape[:ax], page_size,
                       *sa.shape[ax + 1:]),
                      jnp.int8 if self.codec else sa.dtype)
            for sa, ax in zip(leaves_a, self._paged_axis) if ax is not None]
        # one f32 scale per (page, token) rides each code pool; gather
        # decodes pages back to fp views (the compiled decode step is
        # untouched), scatter re-encodes them — idempotently, so
        # untouched pages round-trip bit-identically
        self.page_scales = [
            jnp.zeros((cap, *sa.shape[:ax], page_size), jnp.float32)
            for sa, ax in zip(leaves_a, self._paged_axis)
            if ax is not None] if self.codec else []
        self.unpaged = [
            jnp.zeros((n_slots, *sa.shape), sa.dtype)
            for sa, ax in zip(leaves_a, self._paged_axis) if ax is None]
        self._build_page_jits()

    def _build_page_jits(self) -> None:
        axes = self._paged_axis
        pps, page, view = self.pages_per_slot, self.page_size, self.slot_len
        codec = self.codec
        dtypes = [sa.dtype for sa in
                  jax.tree_util.tree_flatten(
                      self.engine.api.init_cache_specs(
                          self.engine.cfg, 1, self.slot_len))[0]]

        def feat_axes(v_ndim, rest_ndim):
            # the trailing ``rest`` dims are the token's feature block,
            # reduced into one codec scale per (page, token)
            return tuple(range(v_ndim - rest_ndim, v_ndim))

        # A paged pool leaf is (n_pages, *lead, page, *rest) where the lane
        # leaf is (*lead, view, *rest) with view at axis ``ax``
        # (lead = leaf.shape[:ax]).  Gather pulls P pages per slot and
        # splices the page axis back into position ax; scatter inverts it.
        # Under kv_codec="cluster" the pools hold int8 codes + f32 scales:
        # gather decodes pages into the original-dtype views (so the
        # compiled decode step never changes), scatter re-encodes them.
        def gather(pages, scales, unpaged, table):
            views, pi, ui = [], 0, 0
            for ax, dt in zip(axes, dtypes):
                if ax is not None:
                    v = pages[pi][table]        # (S, P, *lead, page, *rest)
                    if codec:
                        sc = scales[pi][table]  # (S, P, *lead, page)
                        rest = v.ndim - sc.ndim
                        v = kv_codec_mod.decode(
                            v, sc.reshape(*sc.shape, *(1,) * rest)) \
                            .astype(dt)
                    pi += 1
                    v = jnp.moveaxis(v, 1, 1 + ax)   # (S, *lead, P, page, ..)
                    views.append(v.reshape(*v.shape[:1 + ax], view,
                                           *v.shape[3 + ax:]))
                else:
                    views.append(unpaged[ui])
                    ui += 1
            return jax.tree_util.tree_unflatten(self._treedef, views)

        def scatter(pages, scales, new_tree, table):
            leaves = jax.tree_util.tree_flatten(new_tree)[0]
            out_pages, out_scales, out_unpaged, pi = [], [], [], 0
            for leaf, ax in zip(leaves, axes):
                if ax is not None:
                    pool = pages[pi]
                    v = leaf.reshape(*leaf.shape[:1 + ax], pps, page,
                                     *leaf.shape[2 + ax:])
                    v = jnp.moveaxis(v, 1 + ax, 1)  # (S, P, *lead, page, ..)
                    if codec:
                        v, sc = kv_codec_mod.encode(
                            v, feat_axes(v.ndim, leaf.ndim - ax - 2))
                        out_scales.append(
                            scales[pi].at[table].set(sc))
                    pi += 1
                    out_pages.append(pool.at[table].set(v.astype(pool.dtype)))
                else:
                    out_unpaged.append(leaf)
            return out_pages, out_scales, out_unpaged

        def lane_scatter(pages, scales, unpaged, lane, row, i):
            leaves = jax.tree_util.tree_flatten(lane)[0]
            out_pages, out_scales, out_unpaged, pi, ui = [], [], [], 0, 0
            for leaf, ax in zip(leaves, axes):
                if ax is not None:
                    pool = pages[pi]
                    v = leaf.reshape(*leaf.shape[:ax], pps, page,
                                     *leaf.shape[1 + ax:])
                    v = jnp.moveaxis(v, ax, 0)  # (P, *lead, page, *rest)
                    if codec:
                        v, sc = kv_codec_mod.encode(
                            v, feat_axes(v.ndim, leaf.ndim - ax - 1))
                        out_scales.append(scales[pi].at[row].set(sc))
                    pi += 1
                    out_pages.append(pool.at[row].set(v.astype(pool.dtype)))
                else:
                    pool = unpaged[ui]
                    ui += 1
                    out_unpaged.append(pool.at[i].set(leaf.astype(pool.dtype)))
            return out_pages, out_scales, out_unpaged

        def page_copy(pages, scales, src, dst):
            # copy-on-write: duplicate physical page src into dst across
            # every paged pool (and scale pool) leaf
            return ([p.at[dst].set(p[src]) for p in pages],
                    [s.at[dst].set(s[src]) for s in scales])

        # growing past page_capacity re-traces only these (decode compiles
        # are keyed on the gathered view, whose shape is pool-independent)
        self._gather = jax.jit(gather)
        self._scatter_pages = jax.jit(scatter, donate_argnums=(0, 1))
        self._lane_scatter = jax.jit(lane_scatter, donate_argnums=(0, 1, 2))
        self._page_copy = jax.jit(page_copy, donate_argnums=(0, 1))

    def _build_kernel_jits(self) -> None:
        """Admission-path scatter for the ``pallas_paged`` layout: write a
        freshly prefilled batch-1 cache into the slot's pages and lane.
        This is the only gather/scatter that survives under the kernel
        backend — the decode hot path touches the pools in place."""
        len_axes, batch_axes = self._paged_axis, self._batch_axis
        pps, page, treedef = self.pages_per_slot, self.page_size, \
            self._treedef
        codec = self.codec

        def install(kcache, kscales, cache1, row, i):
            leaves = jax.tree_util.tree_flatten(kcache)[0]
            fresh = jax.tree_util.tree_flatten(cache1)[0]
            sleaves = jax.tree_util.tree_flatten(
                kscales, is_leaf=lambda x: x is None)[0] if codec \
                else [None] * len(leaves)
            out, sout = [], []
            for leaf, src, sleaf, ax, bax in zip(leaves, fresh, sleaves,
                                                 len_axes, batch_axes):
                if ax is not None:
                    # (*lead, 1, L, *rest) -> (*lead, P, page, *rest),
                    # scattered to this slot's physical pages
                    v = src.reshape(*src.shape[:ax - 1], pps, page,
                                    *src.shape[ax + 1:])
                    idx = (slice(None),) * (ax - 1) + (row,)
                    if codec:
                        # page axis sits at ax, features trail it; encode
                        # before padding so zero-padded codes decode to
                        # exactly 0 under the zero-centred codebook
                        v, sc = kv_codec_mod.encode(
                            v, tuple(range(ax + 1, v.ndim)))
                        if sc.shape[-1] != sleaf.shape[-1]:
                            sc = jnp.pad(sc, [(0, 0)] * (sc.ndim - 1)
                                         + [(0, sleaf.shape[-1]
                                             - sc.shape[-1])])
                        sleaf = sleaf.at[idx].set(sc)
                    if v.shape[ax:] != leaf.shape[ax:]:
                        # hardware-tiled pool: zero-fill the sublane (page
                        # row) and lane (trailing feature) padding
                        target = (*v.shape[:ax], *leaf.shape[ax:])
                        v = jnp.pad(v, [(0, dp - dv) for dp, dv
                                        in zip(target, v.shape)])
                else:
                    v = jnp.squeeze(src, axis=bax)
                    idx = (slice(None),) * bax + (i,)
                out.append(leaf.at[idx].set(v.astype(leaf.dtype)))
                sout.append(sleaf)
            new_kcache = jax.tree_util.tree_unflatten(treedef, out)
            if not codec:
                return new_kcache, kscales
            return new_kcache, jax.tree_util.tree_unflatten(treedef, sout)

        def kernel_copy(kcache, kscales, src, dst):
            # copy-on-write in the kernel layout: pool leaves are
            # (*lead, cap, page, *rest) with the physical-page axis at
            # ax - 1; scale leaves are (*lead, cap, page)
            leaves = jax.tree_util.tree_flatten(kcache)[0]
            sleaves = jax.tree_util.tree_flatten(
                kscales, is_leaf=lambda x: x is None)[0] if codec \
                else [None] * len(leaves)
            out, sout = [], []
            for leaf, sleaf, ax in zip(leaves, sleaves, len_axes):
                if ax is not None:
                    s_idx = (slice(None),) * (ax - 1) + (src,)
                    d_idx = (slice(None),) * (ax - 1) + (dst,)
                    leaf = leaf.at[d_idx].set(leaf[s_idx])
                    if codec:
                        sleaf = sleaf.at[d_idx].set(sleaf[s_idx])
                out.append(leaf)
                sout.append(sleaf)
            new_kcache = jax.tree_util.tree_unflatten(treedef, out)
            if not codec:
                return new_kcache, kscales
            return new_kcache, jax.tree_util.tree_unflatten(treedef, sout)

        self._kernel_install = jax.jit(install, donate_argnums=(0, 1))
        self._kernel_copy = jax.jit(kernel_copy, donate_argnums=(0, 1))

        lane_info, n_slots = self._lane_info, self.n_slots

        def lane_snapshot(kcache, poss, k):
            # rows (pos+1+i) % W per lane leaf: the rolling rows draft
            # tokens 0..k-1 will overwrite this step
            leaves = jax.tree_util.tree_flatten(kcache)[0]
            snaps = []
            for li, bax, w in lane_info:
                l2 = jnp.moveaxis(leaves[li], (bax, bax + 1), (0, 1))
                rows = (poss[:, None] + 1 + jnp.arange(k)) % w
                snaps.append(l2[jnp.arange(n_slots)[:, None], rows])
            return snaps

        def lane_restore(kcache, snaps, poss, keep):
            # keep (S, k) bool: restore the snapshotted row (a rejected
            # draft's write must be undone); False leaves the new write
            leaves, treedef = jax.tree_util.tree_flatten(kcache)
            for (li, bax, w), snap in zip(lane_info, snaps):
                l2 = jnp.moveaxis(leaves[li], (bax, bax + 1), (0, 1))
                rows = (poss[:, None] + 1 + jnp.arange(keep.shape[1])) % w
                idx = (jnp.arange(n_slots)[:, None], rows)
                m = keep.reshape(*keep.shape,
                                 *(1,) * (snap.ndim - keep.ndim))
                l2 = l2.at[idx].set(jnp.where(m, snap, l2[idx]))
                leaves[li] = jnp.moveaxis(l2, (0, 1), (bax, bax + 1))
            return jax.tree_util.tree_unflatten(treedef, leaves)

        self._lane_snapshot = jax.jit(lane_snapshot, static_argnums=(2,))
        self._lane_restore = jax.jit(lane_restore, donate_argnums=(0,))

    # -- speculative decoding -----------------------------------------------
    def spec_snapshot(self, poss, k: int):
        """Snapshot the rolling-lane rows draft tokens will overwrite
        (``pallas_paged`` only; no-op without lane leaves)."""
        if not self._lane_info:
            return None
        return self._lane_snapshot(self.kcache, jnp.asarray(poss), k)

    def spec_restore(self, snaps, poss, keep) -> None:
        """Undo rejected drafts' rolling-lane writes: ``keep`` (S, k)
        marks rows to roll back.  Paged leaves self-heal (every position
        is rewritten by the round that covers it before it is attended),
        so only the modular lane rows need this."""
        if snaps is None or not np.asarray(keep).any():
            return
        self.kcache = self._lane_restore(self.kcache, snaps,
                                         jnp.asarray(poss),
                                         jnp.asarray(keep))

    def spec_score(self, params, toks, poss, q_lens):
        """Speculative phase 1 (gathered / monolithic backends): score
        the ragged draft blocks without touching the resident cache ->
        (logits (S, 1, Q, V), opaque commit context).  The scoring pass
        is not donated — its cache output is discarded, which is what
        makes rejection free."""
        assert self.backend != "pallas_paged"
        if self.paged:
            tel = self.engine.telemetry
            table = jnp.asarray(self.table)
            with tel.timed("kv_decode" if self.codec else "kv_gather"):
                views = self._gather(self.pages, self.page_scales,
                                     self.unpaged, table)
            logits, _ = self.engine.verify_slots(
                params, views, toks, poss, q_lens, commit=False,
                kv_quant=self.codec)
            return logits, (views, table)
        logits, _ = self.engine.verify_slots(
            params, self.cache, toks, poss, q_lens, commit=False)
        return logits, None

    def spec_commit(self, params, toks, poss, commit_lens, ctx) -> None:
        """Speculative phase 2: re-run the block at the *accepted*
        lengths with the cache donated — exactly the accepted tokens'
        KV (and recurrent state advance) lands in place, so rollback
        never has to rewind anything."""
        assert self.backend != "pallas_paged"
        if self.paged:
            views, table = ctx
            tel = self.engine.telemetry
            _, new_tree = self.engine.verify_slots(
                params, views, toks, poss, commit_lens, commit=True,
                kv_quant=self.codec)
            with tel.timed("kv_encode" if self.codec else "kv_scatter"):
                self.pages, self.page_scales, self.unpaged = \
                    self._scatter_pages(self.pages, self.page_scales,
                                        new_tree, table)
        else:
            _, self.cache = self.engine.verify_slots(
                params, self.cache, toks, poss, commit_lens, commit=True)

    # -- page bookkeeping ---------------------------------------------------
    def pages_needed(self, cache_len: int) -> int:
        return -(-cache_len // self.page_size) if self.paged else 0

    def pages_in_use(self) -> int:
        return self.allocator.n_allocated if self.paged else 0

    def codec_error_bound(self) -> float:
        """Worst-case elementwise KV reconstruction error of the resident
        pool (max per-token scale / 254); 0.0 when the codec is off."""
        if not self.codec:
            return 0.0
        scales = (jax.tree_util.tree_leaves(self.kscales)
                  if self.backend == "pallas_paged" else self.page_scales)
        top = max((float(jnp.max(s)) for s in scales), default=0.0)
        return float(kv_codec_mod.error_bound(top))

    def _ensure_pages(self, slot: Slot, upto_pos: int) -> None:
        """Allocate table entries so positions [0, upto_pos] are backed."""
        need = upto_pos // self.page_size + 1
        assert need <= self.pages_per_slot, (need, self.pages_per_slot)
        for j in range(need):
            if self.table[slot.index, j] == DUMMY_PAGE:
                self.table[slot.index, j] = self.allocator.alloc()
                slot.reserved_left -= 1
                assert slot.reserved_left >= 0

    def grow_pages(self, n_pages: int) -> None:
        """Grow the logical page pool to ``n_pages`` without touching the
        compiled decode step.

        Growth within ``page_capacity`` is pure free-list bookkeeping — no
        buffer realloc and no re-trace under either backend (the kernel
        backend's compiled decode is keyed on the physical pool shape, so
        capacity headroom is what keeps it stable).  Growth beyond
        capacity reallocates the buffers with geometric headroom; the
        gathered backend then re-traces only its gather/scatter jits,
        while the kernel backend recompiles its decode once per
        capacity doubling."""
        assert self.paged, "grow_pages on a monolithic pool"
        if n_pages <= self.n_pages:
            return
        if n_pages > self.page_capacity:
            new_cap = max(n_pages, 2 * self.page_capacity)
            extra = new_cap - self.page_capacity
            if self.backend == "pallas_paged":
                kleaves = jax.tree_util.tree_flatten(self.kcache)[0]
                out = []
                for leaf, ax in zip(kleaves, self._paged_axis):
                    if ax is not None:
                        pad = jnp.zeros((*leaf.shape[:ax - 1], extra,
                                         *leaf.shape[ax:]), leaf.dtype)
                        leaf = jnp.concatenate([leaf, pad], axis=ax - 1)
                    out.append(leaf)
                self.kcache = jax.tree_util.tree_unflatten(self._treedef,
                                                           out)
                if self.codec:
                    # scale pools are (*lead, cap, page): pad the cap axis
                    self.kscales = jax.tree_util.tree_map(
                        lambda s: jnp.concatenate(
                            [s, jnp.zeros((*s.shape[:-2], extra,
                                           s.shape[-1]), s.dtype)],
                            axis=-2),
                        self.kscales)
            else:
                self.pages = [
                    jnp.concatenate(
                        [p, jnp.zeros((extra, *p.shape[1:]), p.dtype)])
                    for p in self.pages]
                self.page_scales = [
                    jnp.concatenate(
                        [s, jnp.zeros((extra, *s.shape[1:]), s.dtype)])
                    for s in self.page_scales]
            self.page_capacity = new_cap
            if self.backend != "pallas_paged":
                self._build_page_jits()
        self.allocator.add_pages(range(self.n_pages, n_pages))
        self.n_pages = n_pages

    # -- slot queries ---------------------------------------------------
    def free(self) -> list[Slot]:
        return [s for s in self.slots if s.req is None]

    def active(self) -> list[Slot]:
        return [s for s in self.slots if s.req is not None
                and not s.prefilling]

    def prefilling(self) -> list[Slot]:
        return [s for s in self.slots if s.prefilling]

    def busy(self) -> bool:
        return any(s.req is not None for s in self.slots)

    # -- lane install / retire ---------------------------------------------
    def reserve_for(self, slot: Slot, req: Request) -> bool:
        """Reserve every page ``req`` can need; False -> defer admission.

        A mapped prefix discounts the worst case by its fully-covered
        pages only: positions >= ``prefix_matched`` span ``need`` pages
        (a partially-matched boundary page is written and therefore
        copy-on-write'd, costing one fresh allocation like any other).
        Under reservation pressure the prefix index evicts cold entries
        before admission is deferred — mapped pages stay alive through
        the slot's own references."""
        if not self.paged:
            return True
        need = self.pages_needed(
            self.engine.cache_len(req.prompt_len, req.max_new_tokens)) \
            - slot.prefix_matched // self.page_size
        if not self.allocator.reserve(need):
            if self.prefix is None:
                return False
            evicted = self.prefix.evict_until(need)
            if evicted:
                self.engine.metrics.record_prefix_evictions(evicted)
            if not self.allocator.reserve(need):
                return False
        slot.reserved_left = need
        return True

    # -- prefix sharing -----------------------------------------------------
    def map_prefix(self, slot: Slot, req: Request, align: int) -> int:
        """Map the longest cached prefix of ``req``'s prompt into the
        slot's page table (one shared reference per page, owned by the
        slot and released by the normal retire path) -> matched tokens.
        ``align`` is the prefill chunk size: the match is floored to a
        chunk boundary so the computed suffix is bit-identical to the
        sharing-off oracle's."""
        if self.prefix is None:
            return 0
        nodes, matched = self.prefix.lookup(req.prompt,
                                            req.prompt_len - 1, align)
        if not matched:
            return 0
        row = self.table[slot.index]
        for j, node in enumerate(nodes):
            row[j] = self.allocator.share(node.page)
        self.prefix.hit(nodes)
        slot.prefix_matched = matched
        slot._prefix_nodes = nodes
        return matched

    def unmap_prefix(self, slot: Slot) -> None:
        """Roll back :meth:`map_prefix` (reservation failure path)."""
        if not slot.prefix_matched:
            return
        row = self.table[slot.index]
        n = -(-slot.prefix_matched // self.page_size)
        self.allocator.release(int(row[j]) for j in range(n))
        row[:n] = DUMMY_PAGE
        slot.prefix_matched = 0
        slot._prefix_nodes = None

    def seed_pcache(self, slot: Slot) -> None:
        """Write the mapped prefix's raw-fp fragments into the slot's
        fresh standalone prefill cache at positions [0, matched) exactly
        — bit-identical to what the sharing-off chunk loop would have
        computed there (gathered backend only; the mixed-step path reads
        the shared pool pages in place)."""
        matched = slot.prefix_matched
        if not matched or slot.pcache is None:
            return
        leaves, treedef = jax.tree_util.tree_flatten(slot.pcache)
        P = self.page_size
        for k, node in enumerate(slot._prefix_nodes):
            lo, hi = k * P, min((k + 1) * P, matched)
            if hi <= lo:
                break
            pi = 0
            for li, ax in enumerate(self._paged_axis):
                if ax is None:
                    continue
                frag = node.frag[pi]
                pi += 1
                sub = frag[(slice(None),) * ax + (slice(0, hi - lo),)]
                leaves[li] = leaves[li].at[
                    (slice(None),) * ax + (slice(lo, hi),)].set(
                    jnp.asarray(sub))
        slot.pcache = jax.tree_util.tree_unflatten(treedef, leaves)

    def register_prefix(self, slot: Slot, cache1=None) -> None:
        """Insert a just-prefilled slot's pages into the prefix index:
        full prompt pages plus the partial boundary page (its tail holds
        positions the mapping masks never expose; the first write by the
        owning slot copy-on-writes away from it, funded by one extra
        reservation taken here).  ``cache1`` is the gathered backend's
        completed standalone cache, snapshotted into raw-fp fragments
        before install quantised it into the pool."""
        if self.prefix is None:
            return
        req = slot.req
        L, P = req.prompt_len, self.page_size
        row = self.table[slot.index]
        frags = self._extract_frags(cache1, -(-L // P)) \
            if cache1 is not None else None
        if L % P and self.allocator.reserve(1):
            if self.prefix.register(req.prompt, row, frags=frags,
                                    allow_partial=True):
                slot.reserved_left += 1
            else:
                self.allocator.unreserve(1)
        else:
            self.prefix.register(req.prompt, row, frags=frags,
                                 allow_partial=False)

    def _extract_frags(self, cache1, n_pages: int) -> list:
        """Host copies of each paged leaf's per-page slices of a
        standalone batch-1 cache -> frags[page][leaf]."""
        leaves = jax.tree_util.tree_flatten(cache1)[0]
        P = self.page_size
        frags = []
        for j in range(n_pages):
            per_leaf = []
            for leaf, ax in zip(leaves, self._paged_axis):
                if ax is None:
                    continue
                per_leaf.append(np.asarray(
                    leaf[(slice(None),) * ax
                         + (slice(j * P, (j + 1) * P),)]))
            frags.append(per_leaf)
        return frags

    def _prepare_write(self, slot: Slot, lo_pos: int, hi_pos: int) -> None:
        """Copy-on-write barrier: before positions [lo_pos, hi_pos] are
        written, any shared page backing them (refcount >= 2: the prefix
        index and/or another slot also reference it) is duplicated into a
        fresh private page and swapped into this slot's table row.  Draws
        on the slot's reservation like any other allocation, so it cannot
        fail mid-request."""
        if self.prefix is None:
            return
        row = self.table[slot.index]
        P = self.page_size
        for j in range(lo_pos // P, hi_pos // P + 1):
            pid = int(row[j])
            if pid == DUMMY_PAGE or self.allocator.refcount(pid) < 2:
                continue
            new = self.allocator.alloc()
            slot.reserved_left -= 1
            assert slot.reserved_left >= 0
            self._copy_page(pid, new)
            row[j] = new
            self.allocator.release([pid])
            self.engine.metrics.record_prefix_cow()

    def _copy_page(self, src: int, dst: int) -> None:
        with self.engine.telemetry.timed("kv_cow"):
            if self.backend == "pallas_paged":
                self.kcache, self.kscales = self._kernel_copy(
                    self.kcache, self.kscales, jnp.int32(src),
                    jnp.int32(dst))
            else:
                self.pages, self.page_scales = self._page_copy(
                    self.pages, self.page_scales, jnp.int32(src),
                    jnp.int32(dst))

    def install(self, slot: Slot, cache1, tok: int) -> None:
        """Write a freshly prefilled batch-1 cache into the slot's lane and
        flip it to ACTIVE with first token ``tok``."""
        req = slot.req
        end = self.engine.pos_offset(req.prompt_len)   # positions < end used
        if self.paged:
            # install rewrites the whole row: positions < prefix_matched
            # carry bit-identical bytes (the pcache was seeded from the
            # cached prefix's raw-fp fragments, and the codec encodes
            # per-token), so fully-matched shared pages are safe to
            # rewrite in place — only the partially-matched boundary
            # page (written with this request's own suffix) needs the
            # copy-on-write barrier
            self._prepare_write(slot, slot.prefix_matched,
                                max(end - 1, slot.prefix_matched))
            self._ensure_pages(slot, max(end - 1, 0))
            row = jnp.asarray(self.table[slot.index])
            if self.backend == "pallas_paged":
                self.kcache, self.kscales = self._kernel_install(
                    self.kcache, self.kscales, cache1, row,
                    jnp.int32(slot.index))
            else:
                self.pages, self.page_scales, self.unpaged = \
                    self._lane_scatter(
                        self.pages, self.page_scales, self.unpaged, cache1,
                        row, jnp.int32(slot.index))
        else:
            self.cache = self._scatter(self.cache, cache1,
                                       jnp.int32(slot.index))
        slot.prefilling = False
        slot.pcache = None
        slot.tok = tok
        slot.pos = end
        # install is the prefill path's cache copy (pool/lane scatter of
        # the standalone prefill cache) — counted so the mixed-step path
        # can assert it moved nothing
        self.engine.metrics.record_prefill_gather(self.install_bytes, 0)

    def retire(self, slot: Slot) -> None:
        """Release the slot's lane, pages, and outstanding reservations."""
        if self.paged:
            row = self.table[slot.index]
            self.allocator.release(int(p) for p in row if p != DUMMY_PAGE)
            row[:] = DUMMY_PAGE
            if slot.reserved_left:
                self.allocator.unreserve(slot.reserved_left)
        slot.reserved_left = 0
        slot.prefilling = False
        slot.pcache = None
        slot.prefix_matched = 0
        slot._prefix_nodes = None
        slot.req = None

    # -- mixed step (pallas_paged): prefill chunks + decode, one trace ------
    def mixed_step(self, params, toks, poss, q_lens):
        """One ragged mixed step over the donated pools: toks (S, Q),
        poss (S,) start positions, q_lens (S,) real token counts (0 =
        free lane) -> logits (S, Q, V).  Pages backing every written
        position must already be ensured by the caller."""
        assert self.backend == "pallas_paged"
        if self.codec:
            logits, self.kcache, self.kscales = self.engine.mixed_step(
                params, self.kcache, jnp.asarray(self.table),
                jnp.asarray(toks, dtype=jnp.int32), jnp.asarray(poss),
                jnp.asarray(q_lens), paged_flags=self.paged_flags,
                page_size=self.page_size, q_block=self.q_block,
                pages_per_step=self.pages_per_step,
                kv_scales=self.kscales)
        else:
            logits, self.kcache = self.engine.mixed_step(
                params, self.kcache, jnp.asarray(self.table),
                jnp.asarray(toks, dtype=jnp.int32), jnp.asarray(poss),
                jnp.asarray(q_lens), paged_flags=self.paged_flags,
                page_size=self.page_size, q_block=self.q_block,
                pages_per_step=self.pages_per_step)
        return logits

    def lowered_mixed_step(self, params, width: int = 1):
        """The engine's mixed step for this pool at block width ``width``,
        lowered against the live pools without running it -- what a
        caller inspects to see which kernels the step holds."""
        assert self.backend == "pallas_paged"
        fn = self.engine.mixed_step_fn(self.paged_flags, self.page_size,
                                       width, self.codec, self.q_block,
                                       self.pages_per_step)
        blk = jnp.zeros((self.n_slots, width), jnp.int32)
        lens = jnp.zeros((self.n_slots,), jnp.int32)
        args = (params, self.kcache, jnp.asarray(self.table), blk, lens,
                lens) + ((self.kscales,) if self.codec else ())
        return fn.lower(*args)

    # -- decode -------------------------------------------------------------
    def decode(self, params) -> list[tuple[Slot, int, bool]]:
        """One decode step for every slot -> per active slot (slot, next
        token, logits_finite); advances each active slot's (tok, pos).

        Backend seam: ``gathered`` gathers pages into contiguous views,
        runs the vmapped per-slot decode, and scatters the pages back;
        ``pallas_paged`` hands the donated pools + page table straight to
        the paged decode step — zero per-step cache copies."""
        active = self.active()
        toks = np.zeros((self.n_slots, 1, 1), np.int32)
        poss = np.zeros(self.n_slots, np.int32)
        q_lens = np.zeros(self.n_slots, np.int32)
        for s in active:
            toks[s.index, 0, 0] = s.tok
            poss[s.index] = s.pos
            q_lens[s.index] = 1
            if self.paged:
                # a registered request's partial boundary page is shared
                # with the prefix index: the decode append must land on a
                # private copy
                self._prepare_write(s, s.pos, s.pos)
                self._ensure_pages(s, s.pos)   # page for this step's write
        if self.backend == "pallas_paged":
            logits = self.mixed_step(params, toks[:, :, 0], poss, q_lens)
            last = logits[:, -1]                          # (S, V)
        elif self.paged:
            tel = self.engine.telemetry
            table = jnp.asarray(self.table)
            with tel.timed("kv_decode" if self.codec else "kv_gather"):
                views = self._gather(self.pages, self.page_scales,
                                     self.unpaged, table)
            logits, new_tree = self.engine.slot_decode(
                params, views, jnp.asarray(toks), jnp.asarray(poss),
                kv_quant=bool(self.codec))
            with tel.timed("kv_encode" if self.codec else "kv_scatter"):
                self.pages, self.page_scales, self.unpaged = \
                    self._scatter_pages(self.pages, self.page_scales,
                                        new_tree, table)
            last = logits[:, 0, -1]                       # (S, V)
        else:
            logits, self.cache = self.engine.slot_decode(
                params, self.cache, jnp.asarray(toks), jnp.asarray(poss))
            last = logits[:, 0, -1]                       # (S, V)
        nxt = np.asarray(jnp.argmax(last, axis=-1)).astype(np.int32)
        finite = np.asarray(jnp.isfinite(last).all(axis=-1))
        out = []
        for s in active:
            s.pos += 1
            s.tok = int(nxt[s.index])
            out.append((s, s.tok, bool(finite[s.index])))
        return out


class Scheduler:
    """Admit -> (chunked or monolithic) per-slot prefill -> vmapped
    continuous decode.

    ``mode="continuous"`` (default): admit-on-retire — any freed slot is
    refilled from the queue before the next decode step.
    ``mode="wave"``: the old wave-granular scheduling as a slot config —
    admission waits until every slot has drained, and each admission round
    takes up to ``batch_size`` queued requests sharing the head request's
    length bucket (the old grouping).

    ``prefill_chunk=N`` splits each admitted prompt into N-token chunks
    interleaved with decode steps; ``prefill_budget`` caps prefill tokens
    per scheduler iteration (default: one chunk).  ``kv_page_size=N``
    backs the KV lanes with N-token pages (``kv_pages`` overrides the
    logical pool size; default fully backs every slot;
    ``kv_page_capacity`` pre-sizes the physical buffers so ``grow_pages``
    up to it never recompiles decode).

    ``attn_backend`` picks how decode reads the paged KV: ``"gathered"``
    (default — copy pages into contiguous per-slot views each step, the
    reference oracle) or ``"pallas_paged"`` (the in-kernel paged-attention
    backend: requires ``kv_page_size``; archs without attention-style
    caches fall back to ``gathered`` with a RuntimeWarning naming the
    capability probe that failed — warned once per family — plus the
    emitted note, like the chunked-prefill fallback).  Both backends are
    token-identical by test.

    ``attn_backend="pallas_paged"`` together with ``prefill_chunk``
    engages the unified **mixed-step** path: every scheduler iteration,
    active slots contribute their decode token and prefilling slots up to
    one prompt chunk to a *single* ragged ``mixed_step`` trace over the
    donated page pools.  There is no standalone prefill cache and no
    install copy — per-iteration KV gather bytes are zero on the prefill
    and decode paths alike, and the gathered chunk loop below survives as
    the token-identical oracle.
    """

    def __init__(self, engine: ServeEngine, *, batch_size: int = 4,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 mode: str = "continuous", slot_len: int | None = None,
                 prefill_chunk: int | None = None,
                 prefill_budget: int | None = None,
                 kv_page_size: int | None = None,
                 kv_pages: int | None = None,
                 kv_page_capacity: int | None = None,
                 attn_backend: str = "gathered",
                 kv_codec: str = "none",
                 prefix_share: bool = False,
                 kernel_tune: str | None = None,
                 speculate: str = "off", draft_k: int = 4,
                 log_every: int = 0, emit: Callable[[str], None] = print):
        if mode not in ("continuous", "wave"):
            raise ValueError(f"unknown scheduling mode {mode!r}")
        if draft_k < 1:
            raise ValueError(f"draft_k must be >= 1: {draft_k}")
        if prefill_chunk is not None and prefill_chunk <= 0:
            raise ValueError(f"prefill_chunk must be positive: "
                             f"{prefill_chunk}")
        if attn_backend not in ATTN_BACKENDS:
            raise ValueError(f"unknown attention backend {attn_backend!r}; "
                             f"choose from {ATTN_BACKENDS}")
        if attn_backend == "pallas_paged" and kv_page_size is None:
            raise ValueError("attn_backend='pallas_paged' needs paged KV "
                             "lanes; set kv_page_size")
        if kv_codec not in KV_CODECS:
            raise ValueError(f"unknown kv codec {kv_codec!r}; "
                             f"choose from {KV_CODECS}")
        if kv_codec == "cluster" and kv_page_size is None:
            raise ValueError("kv_codec='cluster' compresses the page "
                             "pools; set kv_page_size")
        if prefix_share and kv_page_size is None:
            raise ValueError("prefix_share maps shared KV pages; set "
                             "kv_page_size")
        if prefix_share and prefill_chunk is None:
            raise ValueError("prefix_share skips prefill chunk by chunk; "
                             "set prefill_chunk")
        kernel_tune = kernel_tune or "off"
        if kernel_tune != "off" and attn_backend != "pallas_paged":
            raise ValueError("kernel_tune shapes the pallas_paged kernel "
                             "launch; set attn_backend='pallas_paged' or "
                             "leave it 'off'")
        if kernel_tune not in ("auto", "off"):
            try:
                parts = [int(p) for p in kernel_tune.split(",")]
                assert 1 <= len(parts) <= 2 and min(parts) >= 0
            except (ValueError, AssertionError):
                raise ValueError(
                    f"unknown kernel_tune {kernel_tune!r}; choose 'auto', "
                    "'off', or explicit 'Q_BLOCK[,PAGES_PER_STEP]'")
        self.engine = engine
        self.batch_size = batch_size
        self.buckets = tuple(sorted(buckets))
        self.mode = mode
        self.slot_len = slot_len
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = prefill_budget or prefill_chunk
        self.kv_page_size = kv_page_size
        self.kv_pages = kv_pages
        self.kv_page_capacity = kv_page_capacity
        self.attn_backend = attn_backend
        self.kv_codec = kv_codec
        self.prefix_share = prefix_share
        self.kernel_tune = kernel_tune
        self.speculate = speculate or "off"
        self.draft_k = int(draft_k)
        self.drafter = None
        self.log_every = log_every
        self.emit = emit
        self._queue: list[Request] = []
        self._pool: SlotPool | None = None
        self._next_rid = 0
        if prefill_chunk is not None and \
                not engine.supports_chunked_prefill:
            self.prefill_chunk = None
            _warn_fallback(
                engine.cfg.family, "chunked_prefill",
                f"{engine.cfg.family} arch downgraded to monolithic "
                f"prefill: supports_chunked_prefill=False (a multimodal "
                f"prefix cannot resume a prompt mid-cache)")
            emit(f"note: {engine.cfg.family} arch cannot resume a prompt "
                 "mid-cache; falling back to monolithic prefill")
        if self.speculate != "off" and (
                not supports_speculation(engine.cfg) or
                engine.api.verify_step is None):
            self.speculate = "off"
            _warn_fallback(
                engine.cfg.family, "speculation",
                f"{engine.cfg.family} arch downgraded to plain decoding: "
                f"supports_speculation=False (draft verification rides "
                f"the resume-from-cache machinery this arch lacks)")
            emit(f"note: {engine.cfg.family} arch cannot verify draft "
                 "tokens mid-cache; speculative decoding off")
        if self.speculate != "off":
            from repro.runtime.drafter import make_drafter
            self.drafter = make_drafter(self.speculate, engine)
        if attn_backend == "pallas_paged" and \
                not engine.supports_paged_attention:
            self.attn_backend = "gathered"
            self.kernel_tune = "off"
            _warn_fallback(
                engine.cfg.family, "paged_attention",
                f"{engine.cfg.family} arch downgraded to the gathered "
                f"attention backend: supports_paged_attention=False (no "
                f"attention-style cache to page)")
            emit(f"note: {engine.cfg.family} arch has no paged decode "
                 "attention; falling back to the gathered backend")
        if self.prefix_share and (self.prefill_chunk is None or
                                  not supports_prefix_share(engine.cfg)):
            self.prefix_share = False
            _warn_fallback(
                engine.cfg.family, "prefix_share",
                f"{engine.cfg.family} arch downgraded to unshared KV "
                f"pages: supports_prefix_share=False (prefix sharing "
                f"needs chunked prefill and every cache leaf paged)")
            emit(f"note: {engine.cfg.family} arch cannot map shared "
                 "prefix pages; serving each request's KV privately")

    # -- admission ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int) -> Request:
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.shape[0] > self.buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.shape[0]} exceeds the largest "
                f"length bucket ({self.buckets[-1]}); truncate the prompt "
                f"or configure larger buckets")
        req = Request(self._next_rid, prompt, int(max_new_tokens),
                      t_submit=time.monotonic())
        self._next_rid += 1
        self._queue.append(req)
        return req

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _wave_group(self) -> list[Request]:
        """Up to batch_size queued requests sharing the head's bucket."""
        head_bucket = self._bucket(self._queue[0].prompt_len)
        group, rest = [], []
        for req in self._queue:
            if len(group) < self.batch_size and \
                    self._bucket(req.prompt_len) == head_bucket:
                group.append(req)
            else:
                rest.append(req)
        self._queue = rest
        return group

    def _ensure_pool(self) -> SlotPool:
        """(Re)build the pool when the queue needs longer slot caches;
        reuse it otherwise so compiled decode shapes carry across runs."""
        eng = self.engine
        needed = max(eng.cache_len(r.prompt_len, r.max_new_tokens)
                     for r in self._queue)
        slot_len = self.slot_len or \
            -(-needed // SLOT_LEN_QUANTUM) * SLOT_LEN_QUANTUM
        if self._pool is None or self._pool.slot_len < slot_len or \
                self._pool.n_slots != self.batch_size:
            slot_len = max(slot_len, self._pool.slot_len if self._pool
                           else 0)
            q_block, pages_per_step, hw_tiles = \
                self._resolve_kernel_tune(slot_len)
            self._pool = SlotPool(eng, self.batch_size, slot_len,
                                  page_size=self.kv_page_size,
                                  n_pages=self.kv_pages,
                                  backend=self.attn_backend,
                                  page_capacity=self.kv_page_capacity,
                                  kv_codec=self.kv_codec,
                                  prefix_share=self.prefix_share,
                                  q_block=q_block,
                                  pages_per_step=pages_per_step,
                                  hw_tiles=hw_tiles)
        return self._pool

    def _resolve_kernel_tune(self, slot_len: int) -> tuple[int, int, bool]:
        """``kernel_tune`` -> (q_block, pages_per_step, hw_tiles) for the
        pool about to be built.

        ``"off"`` keeps the identity layout (no padding, one page per
        grid step, whole-Q blocks); any other value turns hardware
        tiling on.  ``"auto"`` sweeps the live ``(arch, page, Q)`` point
        through :func:`runtime.autotune.tune_kernel` (memoised per key);
        ``"QB[,PPS]"`` pins the launch shape explicitly."""
        if self.kernel_tune == "off" or self.attn_backend != "pallas_paged":
            return 0, 1, False
        if self.kernel_tune != "auto":
            parts = [int(p) for p in self.kernel_tune.split(",")]
            return parts[0], parts[1] if len(parts) > 1 else 1, True
        from repro.runtime.autotune import tune_kernel
        width = min(self.prefill_chunk, slot_len) \
            if self.prefill_chunk else 1
        res = tune_kernel(self.engine.cfg, self.kv_page_size, width,
                          codec=self.kv_codec == "cluster",
                          interpret=self.engine.kernel_interpret)
        self.emit(f"kernel autotune {res['key']}: q_block={res['q_block']} "
                  f"pages_per_step={res['pages_per_step']} "
                  f"({res['best_ms']:.3f} ms/step"
                  f"{', cached' if res['cached'] else ''})")
        return res["q_block"], res["pages_per_step"], True

    # -- serving -----------------------------------------------------------
    def run(self) -> list[Request]:
        """Serve the queue to completion -> completed requests."""
        if not self._queue:
            return []
        tel = self.engine.telemetry
        completed: list[Request] = []
        pool = self._ensure_pool()
        while self._queue or pool.busy():
            if self._queue:
                with tel.timed("admit"):
                    self._admit(pool, completed)
            if self._mixed_path(pool):
                with tel.timed("mixed_step"):
                    self._mixed_tick(pool, completed)
            else:
                if pool.prefilling():
                    with tel.timed("prefill"):
                        self._prefill_tick(pool, completed)
                if pool.active():
                    if self.drafter is not None:
                        if pool.backend == "pallas_paged":
                            # single-phase in-kernel speculation: the
                            # mixed tick verifies drafts even with no
                            # chunks in flight
                            with tel.timed("mixed_step"):
                                self._mixed_tick(pool, completed)
                        else:
                            self._spec_step(pool, completed)
                    else:
                        with tel.timed("decode"):
                            self._step(pool, completed)
        if pool.codec:
            self.engine.metrics.record_kv_codec_error(
                pool.codec_error_bound())
        return completed

    def _mixed_path(self, pool: SlotPool) -> bool:
        """True when serving runs the unified mixed-step path: prefill
        chunks and decode tokens of every slot ride one batched
        ``mixed_step`` trace per iteration, writing straight into the
        page pools (``pallas_paged`` + chunked prefill; the gathered
        backend keeps the standalone-cache chunk loop as the
        token-identical oracle)."""
        return pool.backend == "pallas_paged" and \
            self.prefill_chunk is not None

    def _trace_admitted(self, req: Request, slot: Slot) -> None:
        """Close the request's queued span and mark its admission."""
        req.t_admit = time.monotonic()
        tr = self.engine.telemetry.tracer
        if tr.enabled:
            tr.name_track(PID_REQUEST, req.rid, f"request {req.rid}")
            tr.complete(PID_REQUEST, req.rid, "queued", req.t_submit,
                        req.t_admit, prompt_len=req.prompt_len)
            tr.instant(PID_REQUEST, req.rid, "admitted", req.t_admit,
                       slot=slot.index, backend=self.attn_backend)

    def _record_first_token(self, req: Request, tok: int) -> None:
        req.generated.append(tok)
        req.t_first = time.monotonic()
        self.engine.metrics.record_ttft(req.t_first - req.t_submit)
        tr = self.engine.telemetry.tracer
        if tr.enabled:
            tr.instant(PID_REQUEST, req.rid, "first_token", req.t_first,
                       token=tok)

    def _start_or_admit(self, pool: SlotPool, req: Request, params,
                        completed: list[Request]) -> None:
        """Place ``req`` in a free slot: chunked -> PREFILLING state,
        monolithic -> full prefill now (the PR-2 admission path)."""
        m = self.engine.metrics
        slot = pool.free()[0]
        if self.engine.cache_len(req.prompt_len, req.max_new_tokens) \
                > pool.slot_len:
            raise ValueError(
                f"request {req.rid} needs "
                f"{self.engine.cache_len(req.prompt_len, req.max_new_tokens)}"
                f" cache positions > slot_len {pool.slot_len}")
        if self.prefill_chunk is not None:
            slot.req = req
            slot.prefilling = True
            # a mapped prefix starts the chunk cursor past the cached
            # span — those prompt tokens cost zero prefill work
            slot.prefill_cursor = slot.prefix_matched
            # mixed-step prefill writes chunks straight into the slot's
            # pages/lane — no standalone batch-1 cache exists at all
            slot.pcache = None if self._mixed_path(pool) else \
                self.engine.fresh_slot_cache(pool.slot_len)
            if slot.prefix_matched:
                pool.seed_pcache(slot)
                m.record_prefix_hit(
                    slot.prefix_matched,
                    slot.prefix_matched // self.prefill_chunk)
            self._trace_admitted(req, slot)
            if slot.prefix_matched:
                tr = self.engine.telemetry.tracer
                if tr.enabled:
                    tr.instant(PID_REQUEST, req.rid, "prefix_hit",
                               req.t_admit, tokens=slot.prefix_matched)
            return
        t0 = time.monotonic()
        slot.req = req
        self._trace_admitted(req, slot)
        tok, cache1 = self.engine.prefill_request(params, req.prompt,
                                                  pool.slot_len)
        pool.install(slot, cache1, tok)
        t1 = time.monotonic()
        tr = self.engine.telemetry.tracer
        if tr.enabled:
            tr.complete(PID_REQUEST, req.rid, "prefill", t0, t1,
                        slot=slot.index, tokens=req.prompt_len)
        self._record_first_token(req, tok)
        m.record_admit(1, t1 - t0, tokens=1)
        self._maybe_finish(pool, slot, completed)

    def _maybe_finish(self, pool: SlotPool, slot: Slot,
                      completed: list[Request]) -> None:
        req = slot.req
        if len(req.generated) >= req.max_new_tokens:
            req.done = True
            req.t_done = time.monotonic()
            tr = self.engine.telemetry.tracer
            if tr.enabled:
                pages = int((pool.table[slot.index] != DUMMY_PAGE).sum()) \
                    if pool.paged else 0
                if req.t_first is not None:
                    tr.complete(PID_REQUEST, req.rid, "decode",
                                req.t_first, req.t_done, slot=slot.index,
                                tokens=len(req.generated),
                                pages_held=pages)
                tr.complete(PID_REQUEST, req.rid, "request", req.t_submit,
                            req.t_done, prompt_len=req.prompt_len,
                            tokens=len(req.generated),
                            backend=self.attn_backend)
                tr.instant(PID_REQUEST, req.rid, "retired", req.t_done,
                           slot=slot.index)
            pool.retire(slot)
            completed.append(req)
            self.engine.metrics.record_completed(1)
            self.engine.metrics.record_request_done(req)

    def _admit(self, pool: SlotPool, completed: list[Request]) -> None:
        m = self.engine.metrics
        if self.mode == "wave":
            if pool.busy() or not self._queue:
                return                    # wave mode: drain before admitting
            group = self._wave_group()[: pool.n_slots]
            m.record_wave()
        else:
            group = None                  # continuous: straight FIFO
        while self._queue or group:
            if group is not None:
                if not group:
                    return
                req = group[0]
            else:
                if not pool.free():
                    return
                req = self._queue[0]
            slot = pool.free()[0] if pool.free() else None
            ok = False
            if slot is not None:
                matched = pool.map_prefix(slot, req,
                                          self.prefill_chunk or 1)
                ok = pool.reserve_for(slot, req)
                if not ok and matched:
                    # a hit whose *remaining* pages cannot be reserved is
                    # rolled back — the request may still fit unshared
                    # (mapped pages themselves occupy free-list capacity)
                    pool.unmap_prefix(slot)
                    ok = pool.reserve_for(slot, req)
            if not ok:
                if slot is not None and not pool.busy():
                    # idle pool that still can't reserve: no retire will
                    # ever free pages, so deferring would spin forever
                    need = pool.pages_needed(self.engine.cache_len(
                        req.prompt_len, req.max_new_tokens))
                    raise ValueError(
                        f"request {req.rid} needs {need} KV pages but "
                        f"the pool only has {pool.allocator.total}; "
                        f"raise kv_pages")
                # paged pool under pressure: keep FIFO order, admit when
                # a retire returns pages (reservation makes this safe)
                if group is not None:
                    self._queue = group + self._queue
                return
            (group or self._queue).pop(0)
            params = self.engine.step_params()
            self._start_or_admit(pool, req, params, completed)

    def _prefill_tick(self, pool: SlotPool, completed: list[Request]) -> None:
        """Advance chunked prefills by up to ``prefill_budget`` prompt
        tokens (whole chunks; at least one per tick for progress) — the
        gathered oracle's chunk loop, each prefilling slot on its
        standalone batch-1 cache (the ``pallas_paged`` backend runs
        chunks through :meth:`_mixed_tick` instead).

        Chunks round-robin across prefilling slots so a short prompt
        admitted next to a long one reaches its first token after its own
        few chunks instead of queueing behind the long prompt's."""
        if self.prefill_chunk is None:
            return
        m = self.engine.metrics
        budget = self.prefill_budget
        spent = 0
        pending = pool.prefilling()
        while pending and spent < budget:
            for slot in pending:
                if spent >= budget:
                    break
                req = slot.req
                c = min(self.prefill_chunk,
                        req.prompt_len - slot.prefill_cursor)
                chunk = req.prompt[slot.prefill_cursor:
                                   slot.prefill_cursor + c]
                t0 = time.monotonic()
                params = self.engine.step_params()
                # under a KV codec the chunk's K/V is codec-roundtripped
                # in the standalone cache so install's re-encode lands on
                # the codec's own fixed point — bit-identical to the
                # monolithic prefill's single encode
                logits, slot.pcache = self.engine.prefill_chunk_step(
                    params, slot.pcache, chunk, slot.prefill_cursor,
                    kv_quant=bool(pool.codec))
                dt = time.monotonic() - t0
                m.record_prefill_chunk(c, dt, stalled=bool(pool.active()))
                tr = self.engine.telemetry.tracer
                if tr.enabled:
                    tr.complete(PID_REQUEST, req.rid, "prefill_chunk",
                                t0, t0 + dt, slot=slot.index, tokens=c,
                                cursor=slot.prefill_cursor)
                slot.prefill_cursor += c
                spent += c
                if slot.prefill_cursor >= req.prompt_len:
                    if not bool(jnp.isfinite(logits[0, -1]).all()):
                        raise RuntimeError(
                            "non-finite prefill logits (compressed "
                            "reconstruction or model numerics are broken)")
                    tok = int(jnp.argmax(logits[0, -1]))
                    # install clears pcache; the prefix index snapshots
                    # its raw-fp pages (install is not donated cache1)
                    cache1 = slot.pcache
                    pool.install(slot, cache1, tok)
                    pool.register_prefix(slot, cache1)
                    self._record_first_token(req, tok)
                    m.record_admit(1, 0.0, tokens=1)
                    self._maybe_finish(pool, slot, completed)
            pending = [s for s in pending if s.prefilling]

    def _mixed_tick(self, pool: SlotPool,
                    completed: list[Request]) -> None:
        """One iteration of the unified mixed-step path: every active
        slot contributes its decode token and every prefilling slot up to
        one prompt chunk, all through a single ragged ``mixed_step``
        trace over the donated page pools.  ``prefill_budget`` caps the
        *total* chunk tokens admitted to the trace (always at least one
        chunk for progress); unlike the gathered chunk loop, a slot can
        never advance more than ``prefill_chunk`` tokens per iteration —
        the trace width Q is bounded, so budget beyond
        ``n_prefilling * prefill_chunk`` has no additional effect.

        There is no standalone prefill cache and no install copy — chunk
        K/V lands straight in the slot's pages (lane leaves are written
        in the same trace with ragged masks) — so per-iteration KV gather
        bytes are zero on the prefill and decode paths alike, which the
        metrics record and tests assert."""
        m = self.engine.metrics
        active = pool.active()
        chunks: list[tuple[Slot, int]] = []
        spent = 0
        for slot in pool.prefilling():
            if spent >= self.prefill_budget and chunks:
                break
            c = min(self.prefill_chunk,
                    slot.req.prompt_len - slot.prefill_cursor)
            chunks.append((slot, c))
            spent += c
        if not active and not chunks:
            return
        drafts: dict[int, np.ndarray] = {}
        if self.drafter is not None and active:
            # rolling-window lanes are snapshot/restored around the
            # trace; the snapshot depth caps how deep a draft may write
            cap = None if pool.lane_min_rows is None \
                else pool.lane_min_rows - 1
            with self.engine.telemetry.timed("spec_draft"):
                drafts = self._propose_drafts(pool, active, cap=cap)
        # pad every chunk-carrying tick to one block width so compiled
        # mixed-step shapes stay bounded: Q = prefill_chunk while chunks
        # are in flight (remainders ride padded; drafts fold into the
        # same padding), Q = 1 + draft_k on speculative decode ticks,
        # Q = 1 for plain decode
        width = min(self.prefill_chunk, pool.slot_len) if chunks else 1
        if chunks:
            drafts = {i: d[:width - 1] for i, d in drafts.items()}
        drafts = {i: d for i, d in drafts.items() if len(d)}
        if drafts and not chunks:
            width = 1 + self.draft_k
        toks = np.zeros((pool.n_slots, width), np.int32)
        poss = np.zeros(pool.n_slots, np.int32)
        q_lens = np.zeros(pool.n_slots, np.int32)
        for slot in active:
            d = drafts.get(slot.index)
            nd = 0 if d is None else len(d)
            toks[slot.index, 0] = slot.tok
            if nd:
                toks[slot.index, 1:1 + nd] = d
            poss[slot.index] = slot.pos
            q_lens[slot.index] = 1 + nd
            pool._prepare_write(slot, slot.pos, slot.pos + nd)
            pool._ensure_pages(slot, slot.pos + nd)
        for slot, c in chunks:
            cur = slot.prefill_cursor
            toks[slot.index, :c] = slot.req.prompt[cur:cur + c]
            poss[slot.index] = cur
            q_lens[slot.index] = c
            # chunk K/V lands in the pool in place: shared pages under
            # the write range must be copy-on-write'd first
            pool._prepare_write(slot, cur, cur + c - 1)
            pool._ensure_pages(slot, cur + c - 1)
        t0 = time.monotonic()
        params = self.engine.step_params()
        snaps = kk = None
        if drafts and pool.lane_min_rows is not None:
            # rolling-window lanes have no rewind: snapshot the rows the
            # drafts will overwrite so rejected writes can be undone
            kk = max(len(d) for d in drafts.values())
            snaps = pool.spec_snapshot(poss, kk)
        logits = pool.mixed_step(params, toks, poss, q_lens)
        g = np.asarray(jnp.argmax(logits, axis=-1))              # (S, Q)
        ok_rows = np.asarray(jnp.isfinite(logits).all(axis=-1))  # (S, Q)
        lanes = np.arange(pool.n_slots)
        nxt = g[lanes, np.maximum(q_lens - 1, 0)].astype(np.int32)
        finite = ok_rows[lanes, np.maximum(q_lens - 1, 0)]
        dt = time.monotonic() - t0
        # wall time attributed to decode vs prefill by token share
        n_chunk_toks = sum(c for _, c in chunks)
        n_dec_toks = int(sum(q_lens[s.index] for s in active))
        total = n_dec_toks + n_chunk_toks
        dt_decode = dt * n_dec_toks / total if total else 0.0
        emitted = 0
        acc: dict[int, int] = {}
        for slot in active:
            d = drafts.get(slot.index)
            nd = 0 if d is None else len(d)
            a = 0
            while a < nd and int(d[a]) == int(g[slot.index, a]):
                a += 1
            acc[slot.index] = a
            if not ok_rows[slot.index, :a + 1].all():
                raise RuntimeError(
                    f"non-finite logits in mixed step for request "
                    f"{slot.req.rid} (compressed reconstruction or model "
                    f"numerics are broken)")
            for t in g[slot.index, :a + 1]:
                slot.req.generated.append(int(t))
            emitted += a + 1
            slot.pos += a + 1
            slot.tok = int(g[slot.index, a])
            if nd:
                m.record_spec(nd, a)
            self._maybe_finish(pool, slot, completed)
        if snaps is not None:
            with self.engine.telemetry.timed("spec_rollback"):
                keep = np.zeros((pool.n_slots, kk), bool)
                for slot in active:
                    d = drafts.get(slot.index)
                    if d is not None:
                        keep[slot.index, acc[slot.index]:len(d)] = True
                pool.spec_restore(snaps, poss, keep)
        tr = self.engine.telemetry.tracer
        for slot, c in chunks:
            m.record_prefill_chunk(c, (dt - dt_decode) / len(chunks),
                                   stalled=bool(active))
            if tr.enabled:
                # chunks share one ragged trace; each request's span
                # covers the tick's prefill share
                tr.complete(PID_REQUEST, slot.req.rid, "prefill_chunk",
                            t0, t0 + (dt - dt_decode), slot=slot.index,
                            tokens=c, cursor=slot.prefill_cursor)
            slot.prefill_cursor += c
            if slot.prefill_cursor >= slot.req.prompt_len:
                if not finite[slot.index]:
                    raise RuntimeError(
                        "non-finite prefill logits (compressed "
                        "reconstruction or model numerics are broken)")
                req = slot.req
                slot.prefilling = False
                slot.pcache = None
                slot.tok = int(nxt[slot.index])
                slot.pos = self.engine.pos_offset(req.prompt_len)
                # mixed-step pages hold the kernel-written (possibly
                # codec-encoded) K/V; the index shares them in place —
                # per-(page, token) encoding keeps a future hit
                # bit-identical to the sharing-off run
                pool.register_prefix(slot)
                self._record_first_token(req, slot.tok)
                m.record_admit(1, 0.0, tokens=1)
                # the install copy the gathered oracle performs at the
                # end of every prefill never happened here
                m.record_prefill_gather(0, pool.install_bytes)
                self._maybe_finish(pool, slot, completed)
        if active:
            m.record_decode_step(emitted, dt_decode,
                                 n_slots=pool.n_slots)
            m.record_pages(pool.pages_in_use(), pool.allocator.total)
            if pool.prefix is not None:
                m.record_shared_pages(pool.allocator.shared_pages())
            m.record_kv_gather(0, pool.gather_bytes_avoided_per_step)
            if pool.codec:
                m.record_kv_codec(pool.pages_in_use() * pool.page_bytes_fp,
                                  pool.pages_in_use() *
                                  pool.page_bytes_resident)
            if self.log_every and m.decode_steps % self.log_every == 0:
                self.emit(self.engine.stats_line())

    def _propose_drafts(self, pool: SlotPool, active: list[Slot],
                        cap: int | None = None) -> dict[int, np.ndarray]:
        """Ask the drafter for up to ``draft_k`` guesses per active slot
        -> {slot.index: draft tokens}.  Per-slot limits keep every
        accepted run inside the request's token budget (``remaining - 1``
        — the verified bonus token always fits) and the slot's cache
        (writes stop at ``slot_len - 1``); ``cap`` adds a backend bound
        (rolling-lane snapshot depth on the mixed path)."""
        hists = [np.concatenate([np.asarray(s.req.prompt, np.int64),
                                 np.asarray(s.req.generated, np.int64)])
                 for s in active]
        limits = []
        for s in active:
            lim = s.req.max_new_tokens - len(s.req.generated) - 1
            lim = min(lim, pool.slot_len - 1 - s.pos)
            if cap is not None:
                lim = min(lim, cap)
            limits.append(max(lim, 0))
        drafts = self.drafter.propose(hists, self.draft_k, limits=limits)
        return {s.index: np.asarray(d, np.int64)
                for s, d in zip(active, drafts)}

    def _spec_step(self, pool: SlotPool, completed: list[Request]) -> None:
        """One speculative round on the gathered / monolithic backends:
        draft -> one ragged scoring pass over every slot lane (phase 1,
        cache discarded) -> greedy accept on the host -> one committing
        pass at the accepted lengths (phase 2, cache donated).  Rejected
        drafts never touch the resident cache, so rollback is free by
        construction; greedy acceptance emits exactly the argmax chain
        plain decoding would, so the output is token-identical."""
        m = self.engine.metrics
        tel = self.engine.telemetry
        active = pool.active()
        t0 = time.monotonic()
        with tel.timed("spec_draft"):
            drafts = self._propose_drafts(pool, active)
        if not any(len(d) for d in drafts.values()):
            # nothing proposed anywhere: a plain decode step is cheaper
            # than a two-phase verify round at Q = 1
            with tel.timed("decode"):
                self._step(pool, completed)
            return
        qn = 1 + self.draft_k
        toks = np.zeros((pool.n_slots, 1, qn), np.int32)
        poss = np.zeros(pool.n_slots, np.int32)
        q_lens = np.zeros(pool.n_slots, np.int32)
        for s in active:
            d = drafts[s.index]
            toks[s.index, 0, 0] = s.tok
            if len(d):
                toks[s.index, 0, 1:1 + len(d)] = d
            poss[s.index] = s.pos
            q_lens[s.index] = 1 + len(d)
            if pool.paged:
                # the real token and every draft write [pos, pos + d]:
                # shared pages under the range go copy-on-write first
                pool._prepare_write(s, s.pos, s.pos + len(d))
                pool._ensure_pages(s, s.pos + len(d))
        params = self.engine.step_params()
        jtoks, jposs = jnp.asarray(toks), jnp.asarray(poss)
        with tel.timed("spec_verify"):
            logits, ctx = pool.spec_score(params, jtoks, jposs, q_lens)
            g = np.asarray(jnp.argmax(logits[:, 0], axis=-1))     # (S, Q)
            finite = np.asarray(jnp.isfinite(logits[:, 0]).all(axis=-1))
        accepted: dict[int, int] = {}
        commit_lens = np.zeros(pool.n_slots, np.int32)
        for s in active:
            d = drafts[s.index]
            a = 0
            while a < len(d) and int(d[a]) == int(g[s.index, a]):
                a += 1
            accepted[s.index] = a
            commit_lens[s.index] = 1 + a
        with tel.timed("spec_rollback"):
            pool.spec_commit(params, jtoks, jposs, commit_lens, ctx)
        dt = time.monotonic() - t0
        emitted = 0
        for s in active:
            a = accepted[s.index]
            if not finite[s.index, :a + 1].all():
                raise RuntimeError(
                    f"non-finite logits in speculative step for request "
                    f"{s.req.rid} (compressed reconstruction or model "
                    f"numerics are broken)")
            for t in g[s.index, :a + 1]:
                s.req.generated.append(int(t))
            emitted += a + 1
            s.pos += a + 1
            s.tok = int(g[s.index, a])
            m.record_spec(len(drafts[s.index]), a)
            self._maybe_finish(pool, s, completed)
        m.record_decode_step(emitted, dt, n_slots=pool.n_slots)
        m.record_pages(pool.pages_in_use(),
                       pool.allocator.total if pool.paged else 0)
        if pool.prefix is not None:
            m.record_shared_pages(pool.allocator.shared_pages())
        m.record_kv_gather(pool.gather_bytes_per_step,
                           pool.gather_bytes_avoided_per_step)
        if pool.codec:
            m.record_kv_codec(pool.pages_in_use() * pool.page_bytes_fp,
                              pool.pages_in_use() *
                              pool.page_bytes_resident)
        if self.log_every and m.decode_steps % self.log_every == 0:
            self.emit(self.engine.stats_line())

    def _step(self, pool: SlotPool, completed: list[Request]) -> None:
        m = self.engine.metrics
        t0 = time.monotonic()
        params = self.engine.step_params()
        results = pool.decode(params)
        n_active = len(results)
        for slot, tok, finite in results:
            if not finite:
                raise RuntimeError(
                    f"non-finite logits in decode step for request "
                    f"{slot.req.rid} (compressed reconstruction or model "
                    f"numerics are broken)")
            slot.req.generated.append(tok)
            self._maybe_finish(pool, slot, completed)
        m.record_decode_step(n_active, time.monotonic() - t0,
                             n_slots=pool.n_slots)
        m.record_pages(pool.pages_in_use(),
                       pool.allocator.total if pool.paged else 0)
        if pool.prefix is not None:
            m.record_shared_pages(pool.allocator.shared_pages())
        m.record_kv_gather(pool.gather_bytes_per_step,
                          pool.gather_bytes_avoided_per_step)
        if pool.codec:
            m.record_kv_codec(pool.pages_in_use() * pool.page_bytes_fp,
                              pool.pages_in_use() *
                              pool.page_bytes_resident)
        if self.log_every and m.decode_steps % self.log_every == 0:
            self.emit(self.engine.stats_line())
