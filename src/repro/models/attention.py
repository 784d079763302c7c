"""Attention substrate: flash-style chunked attention, GQA, sliding windows,
prefix-LM masks, logit softcaps, KV caches (full + rolling-window), and
DeepSeek-style MLA with latent-space decode.

Memory discipline: training/prefill attention never materialises an (Sq, Sk)
score matrix — it runs an online-softmax scan over (q_chunk, kv_chunk) tiles,
so activation memory is linear in sequence length (required for the 32k
prefill cells and scan-over-layers remat).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain, seq_shard_attention
from repro.models.layers import apply_rope, dense_init, rms_norm, softcap

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class PagedContext:
    """Per-step state of the ``pallas_paged`` attention backend.

    Present (non-None) only on blocks whose cache leaves are page pools:
    ``table`` maps each slot's logical pages to physical pages of the
    shared pool, ``page_size`` is the positions-per-page layout constant,
    and ``interpret`` routes the Pallas kernel through the interpreter on
    hosts without a TPU.  Blocks whose leaves stay per-slot lanes
    (rolling-window KV, recurrent state) receive ``paged=None`` and run
    the gathered reference path on their lanes.

    ``page_size`` is the *logical* positions-per-page constant; the pool
    leaves themselves may carry hardware-tiled padding (page rows padded
    to the sublane tile, trailing feature dim to the lane tile — see
    ``api.padded_page_dims``), which :meth:`write` fills with zeros and
    the kernel masks out.  ``q_block`` / ``pages_per_step`` are the
    tuned kernel launch parameters (``runtime.autotune.tune_kernel``).
    """

    table: jax.Array         # (S, pages_per_slot) int32
    page_size: int
    interpret: bool = False
    q_block: int = 0         # kernel query-block width (0 = whole Q)
    pages_per_step: int = 1  # physical pages per kernel grid step

    def write(self, pool: jax.Array, values: jax.Array, pos,
              q_lens=None) -> jax.Array:
        """Scatter this step's per-slot token block ``values`` (S, Q, ...)
        into each slot's pages of ``pool`` (n_pages, page, ...): token
        ``i`` of slot ``s`` lands at absolute position ``pos[s] + i`` for
        ``i < q_lens[s]``; padded tokens of the ragged mixed-step block
        (``i >= q_lens[s]``, or everything when ``q_lens[s] == 0``) are
        routed to the page-0 dummy sink instead.  ``q_lens=None`` means
        every token is real.  This is the layout contract the paged
        kernel depends on: the chunk's K/V is in the pool *before* the
        kernel walks the table (per-token causal masks keep
        write-after-attend semantics)."""
        s_n, qn = values.shape[:2]
        if values.shape[2:] != pool.shape[2:]:
            # hardware-tiled pool: zero-fill the lane padding so padded
            # feature columns decode/score to exactly 0
            values = jnp.pad(values, [(0, 0), (0, 0)] + [
                (0, dp - dv) for dp, dv in
                zip(pool.shape[2:], values.shape[2:])])
        p = jnp.asarray(pos, jnp.int32)[:, None] \
            + jnp.arange(qn, dtype=jnp.int32)[None]           # (S, Q)
        lidx = jnp.clip(p // self.page_size, 0, self.table.shape[1] - 1)
        pids = jnp.take_along_axis(self.table, lidx, axis=1)
        if q_lens is not None:
            valid = jnp.arange(qn)[None] < \
                jnp.asarray(q_lens, jnp.int32)[:, None]
            pids = jnp.where(valid, pids, 0)
            p = jnp.where(valid, p, 0)
        return pool.at[pids, p % self.page_size].set(
            values.astype(pool.dtype))


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def _allowed(q_pos, k_pos, *, causal: bool, window: int, prefix_len: int):
    """Boolean mask (..., Sq, Sk) of attendable pairs."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    ok = (k <= q) if causal else jnp.ones(jnp.broadcast_shapes(
        q.shape, k.shape), bool)
    if window:
        ok &= k > q - window
    if prefix_len:
        ok |= k < prefix_len
    return ok


# ---------------------------------------------------------------------------
# flash attention (train / prefill)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix_len", "attn_softcap",
                     "q_chunk", "kv_chunk"))
def flash_attention(
    q: jax.Array,            # (B, Sq, H, D)
    k: jax.Array,            # (B, Sk, KH, D)
    v: jax.Array,            # (B, Sk, KH, Dv)
    *,
    causal: bool = True,
    window: int = 0,
    prefix_len: int = 0,
    attn_softcap: float = 0.0,
    q_offset: int = 0,
    q_chunk: int = 4096,
    kv_chunk: int = 0,       # kept for API compat; kv is processed densely
) -> jax.Array:
    """Memory-chunked attention: lax.scan over q chunks, dense over kv.

    Design note (EXPERIMENTS.md §Perf iter 2): an inner kv-chunk scan makes
    the backward emit a dK/dV all-reduce *per kv chunk per q chunk* when q
    is sequence-sharded and k/v replicated (measured 112 GB/step on gemma2
    train_4k).  With kv dense inside the q-scan, dK/dV accumulate in the
    scan carry locally and are reduced once per layer (~1.7 GB/step).  The
    (cq, Sk) score block is transient and recomputed under remat.
    """
    b, sq, h, d = q.shape
    sk, kh, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kh
    q_chunk = math.gcd(sq, q_chunk)        # largest common divisor <= chunk

    nq = sq // q_chunk
    # scores/PV run on the MXU in the model dtype with f32 accumulation;
    # only the softmax statistics stay f32 (halves attention bytes & flops
    # vs an all-f32 flash — §Perf iter 5)
    qs = (q * jnp.asarray(d ** -0.5, q.dtype)).reshape(
        b, nq, q_chunk, kh, g, d)
    qs = jnp.moveaxis(qs, 1, 0)                       # (nq, B, cq, KH, G, D)
    # Attention sharding over the "model" axis (DESIGN.md §4): shard KV
    # heads when they divide the axis (MLA's 128 heads), else shard the q
    # rows (GQA archs with 1-10 kv heads).  Without an explicit constraint
    # GSPMD replicates the whole score block on every model rank (measured:
    # 16x redundant attention FLOPs on the 16x16 mesh).
    from repro.dist.sharding import current_mesh
    mesh = current_mesh()
    head_tp = mesh is not None and "model" in mesh.axis_names and \
        kh % mesh.shape.get("model", 1) == 0
    if head_tp:
        qs = constrain(qs, None, "batch", None, "model", None, None)
    else:
        qs = constrain(qs, None, "batch", "model", None, None, None)
    k_pos = jnp.arange(sk)

    def q_step(_, qx):
        qc, qi = qx
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        if head_tp:
            qc = constrain(qc, "batch", None, "model", None, None)
        else:
            qc = constrain(qc, "batch", "model", None, None, None)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, k,
                       preferred_element_type=jnp.float32)
        if attn_softcap:
            s = softcap(s, attn_softcap)
        mask = _allowed(q_pos, k_pos, causal=causal, window=window,
                        prefix_len=prefix_len)            # (cq, Sk)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        if head_tp:
            s = constrain(s, "batch", "model", None, None, None)
        else:
            s = constrain(s, "batch", None, None, "model", None)
        m = s.max(-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(-1, keepdims=True)
        out = jnp.einsum("bhgqk,bkhd->bhgqd",
                         (p / jnp.maximum(l, 1e-20)).astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        if head_tp:
            out = constrain(out, "batch", "model", None, None, None)
        else:
            out = constrain(out, "batch", None, None, "model", None)
        return None, out                                  # (B, KH, G, cq, Dv)

    if nq == 1:
        # dense path: one score block per layer -> dK/dV reduce ONCE per
        # layer instead of once per scan step (the scan form psums the
        # replicated-K cotangent on every iteration; measured 223 GB/step)
        _, out1 = q_step(None, (qs[0], jnp.zeros((), jnp.int32)))
        outs = out1[None]
    else:
        # remat each q chunk: the (cq, Sk) score block would otherwise be
        # saved per scan step for the backward (nq x 0.5 GB of residuals)
        _, outs = jax.lax.scan(jax.checkpoint(q_step), None,
                               (qs, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 1)                        # (B, nq, KH, G, cq, Dv)
    out = jnp.moveaxis(out, -2, 2)                        # (B, nq, cq, KH, G, Dv)
    return out.reshape(b, sq, h, dv)


# ---------------------------------------------------------------------------
# decode attention over a cache
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,            # (B, 1, H, D)
    k_cache: jax.Array,      # (B, Smax, KH, D)
    v_cache: jax.Array,      # (B, Smax, KH, Dv)
    cur_pos: jax.Array,      # () shared or (B,) per-lane current position
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    rolling: bool = False,
) -> jax.Array:
    """Reference decode attention over a contiguous per-lane cache.

    ``cur_pos`` may be a scalar (every lane at the same depth — the wave
    path) or a ``(B,)`` vector (slot serving: each lane has its own
    position).  This is the oracle the ``pallas_paged`` kernel backend is
    tested against.
    """
    b, smax, kh, d = k_cache.shape
    h = q.shape[2]
    g = h // kh
    qs = (q.astype(jnp.float32) * d ** -0.5).reshape(b, kh, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qs, k_cache.astype(jnp.float32))
    if attn_softcap:
        s = softcap(s, attn_softcap)
    slot = jnp.arange(smax)
    cur = jnp.asarray(cur_pos)[..., None]        # (1,) or (B, 1)
    if rolling:
        # rolling window cache: slots hold the last min(cur_pos+1, Smax) keys
        valid = slot < jnp.minimum(cur + 1, smax)
    else:
        valid = slot <= cur
        if window:
            valid &= slot > cur - window
    valid = valid if valid.ndim == 2 else valid[None]      # (B|1, Smax)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, v_cache.shape[-1])


# ---------------------------------------------------------------------------
# chunked-prefill attention over a partially filled cache
# ---------------------------------------------------------------------------

def chunk_attention(
    q: jax.Array,            # (B, S, H, D)   chunk queries
    k: jax.Array,            # (B, S, KH, D)  chunk keys
    v: jax.Array,            # (B, S, KH, Dv) chunk values
    k_past: jax.Array,       # (B, P, KH, D)  resident cache (physical order)
    v_past: jax.Array,       # (B, P, KH, Dv)
    q_pos: jax.Array,        # (S,) | (B, S) absolute chunk-token positions
    k_pos: jax.Array,        # (P,) | (B, P) absolute past-key pos (<0: hole)
    *,
    window: int = 0,
    attn_softcap: float = 0.0,
    q_lens: jax.Array | None = None,   # (B,) real tokens per lane (ragged)
) -> jax.Array:
    """Attention of a prefill chunk against (resident cache ++ chunk) keys.

    The cache may be physically reordered (rolling-window slots) or contain
    never-written holes; ``k_pos`` carries each physical slot's absolute
    position (negative = not a real key), so causality and windowing are
    enforced on absolute positions, exactly as monolithic prefill's mask
    would.  The chunk's own keys are appended *after* the resident ones so
    rolling caches whose chunk writes would overwrite still-needed old keys
    stay attendable (write-back happens after this call).

    ``q_pos``/``k_pos`` may carry a leading lane axis (mixed-step serving:
    every lane at its own depth) and ``q_lens`` marks the ragged padding —
    tokens at ``i >= q_lens[b]`` neither act as keys nor produce
    meaningful output (the caller discards their rows).
    """
    kk = jnp.concatenate([k_past.astype(jnp.float32),
                          k.astype(jnp.float32)], axis=1)
    vv = jnp.concatenate([v_past.astype(jnp.float32),
                          v.astype(jnp.float32)], axis=1)
    b, s, h, d = q.shape
    q_pos2 = jnp.asarray(q_pos)
    q_pos2 = q_pos2[None] if q_pos2.ndim == 1 else q_pos2      # (B|1, S)
    k_pos2 = jnp.asarray(k_pos)
    k_pos2 = k_pos2[None] if k_pos2.ndim == 1 else k_pos2      # (B|1, P)
    chunk_pos = q_pos2
    if q_lens is not None:
        chunk_pos = jnp.where(
            jnp.arange(s)[None] < jnp.asarray(q_lens)[:, None], q_pos2, -1)
    bb = max(q_pos2.shape[0], k_pos2.shape[0], chunk_pos.shape[0])
    pos_all = jnp.concatenate(
        [jnp.broadcast_to(k_pos2, (bb, k_pos2.shape[1])),
         jnp.broadcast_to(chunk_pos, (bb, s))], axis=1)        # (B|1, P+S)
    kh = kk.shape[2]
    g = h // kh
    qs = (q.astype(jnp.float32) * d ** -0.5).reshape(b, s, kh, g, d)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qs, kk)
    if attn_softcap:
        sc = softcap(sc, attn_softcap)
    ok = (pos_all[:, None, :] <= q_pos2[..., None]) & \
        (pos_all[:, None, :] >= 0)
    if window:
        ok &= pos_all[:, None, :] > q_pos2[..., None] - window
    sc = jnp.where(ok[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vv)
    return out.reshape(b, s, h, vv.shape[-1])


def _codec_roundtrip(x: jax.Array, axes: tuple) -> jax.Array:
    """Quantise ``x`` onto the ``kv_codec="cluster"`` codebook and decode
    it straight back (one scale per the block trailing ``axes``).

    The gathered backend's chunked prefill uses this to reproduce the
    ``pallas_paged`` mixed step's numerics exactly: the kernel path
    encodes each chunk's K/V into the code pools and attends to the
    *decoded* codes, so later chunks see quantised keys.  Round-tripping
    here makes the standalone-chunk oracle see the same values — and
    because the codec encode is idempotent (``encode(decode(encode(x)))
    == encode(x)``), the install-time re-encode then lands bit-identical
    codes in the pool."""
    from repro.kernels import kv_codec
    codes, sc = kv_codec.encode(x, axes)
    rest = codes.ndim - sc.ndim
    return kv_codec.decode(
        codes, sc.reshape(*sc.shape, *(1,) * rest)).astype(x.dtype)


def _rolling_slot_positions(pos, smax: int) -> jax.Array:
    """Absolute position held by each physical slot of a rolling cache
    *before* positions >= ``pos`` are written (negative = never written).

    Position p lands at slot p % smax, so slot j holds the largest
    p < pos with p === j (mod smax).  ``pos`` may be a scalar (one lane /
    shared depth) or a ``(B,)`` vector (per-lane depths -> (B, smax))."""
    slot = jnp.arange(smax)
    last = jnp.asarray(pos)[..., None] - 1
    return (last - (last - slot) % smax).reshape(
        (-1, smax) if jnp.ndim(pos) else (smax,))


def _lane_chunk_write(cache: jax.Array, new: jax.Array, pos,
                      q_lens=None, *, rolling: bool) -> jax.Array:
    """Scatter chunk K/V ``new`` (B, S, ...) into per-lane caches at
    per-lane positions ``pos`` (scalar or (B,)).  Rolling caches wrap at
    slot ``p % smax`` and only the last ``smax`` real tokens survive when
    a lane's chunk exceeds the window; ``q_lens`` marks ragged padding
    (those writes are dropped, never clobbering live positions)."""
    b, s = new.shape[:2]
    smax = cache.shape[1]
    i = jnp.arange(s)[None]                                   # (1, S)
    pos = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1, 1), (b, 1))
    ql = (jnp.full((b, 1), s, jnp.int32) if q_lens is None
          else jnp.asarray(q_lens, jnp.int32)[:, None])
    keep = i < ql
    if rolling:
        keep &= i >= ql - smax
        idx = jnp.where(keep, (pos + i) % smax, smax)
    else:
        idx = jnp.where(keep, pos + i, smax)
    lane = jnp.arange(b)[:, None]
    return cache.at[lane, idx].set(new.astype(cache.dtype), mode="drop")


# ---------------------------------------------------------------------------
# standard GQA attention layer (init / train / prefill+cache / decode)
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype) -> dict:
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, h * hd, dtype),
        "wk": dense_init(kk, d, kh * hd, dtype),
        "wv": dense_init(kv, d, kh * hd, dtype),
        "wo": dense_init(ko, h * hd, d, dtype),
    }


def _qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, kh, hd)
    v = (x @ p["wv"]).reshape(b, s, kh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(
    p: dict, x: jax.Array, cfg, *,
    kind: str,                       # "attn" | "swa" | "local" | "global" | "bidir"
    cache: dict | None = None,       # None = train; dict = prefill/decode
    pos=None,                        # decode: () shared or (B,) per-lane pos
    prefix_len: int = 0,
    paged: PagedContext | None = None,
    q_lens: jax.Array | None = None,  # (B,) real tokens per lane (ragged
    #                                    mixed step; None = all real)
    scales: dict | None = None,       # kv_codec="cluster": {"k","v"} scale
    #                                    pools (n_pages, page) f32; implies
    #                                    paged + int8 code pools
    kv_quant: bool = False,           # kv_codec="cluster" on a *lane* cache:
    #                                    round-trip chunk K/V through the
    #                                    codec so install re-encodes losslessly
) -> tuple[jax.Array, dict | None]:
    """-> (y, new_cache); with ``scales`` -> (y, new_cache, new_scales)."""
    b, s, _ = x.shape
    window = cfg.window if kind in ("swa", "local") else 0
    causal = kind != "bidir"
    decode = cache is not None and s == 1 and q_lens is None
    chunked = cache is not None and pos is not None and paged is None and \
        (s > 1 or q_lens is not None)

    if paged is not None:
        # ``pallas_paged`` backend: the cache leaves are the physical page
        # pools (n_pages, page, KH, HD) shared by every slot; this step's
        # token block — 1..s tokens per slot, a prefill chunk or a single
        # decode token — is scattered into each slot's pages and attention
        # walks the page table inside the kernel, with per-token causal
        # masks standing in for write-after-attend.  No contiguous
        # per-slot view is ever gathered.
        from repro.kernels.paged_attention import paged_mixed_attention
        pos = jnp.asarray(pos, jnp.int32)
        ql = (jnp.full((b,), s, jnp.int32) if q_lens is None
              else jnp.asarray(q_lens, jnp.int32))
        positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        q, k, v = _qkv(p, x, cfg, positions)
        hd = cfg.head_dim
        kw = {}
        if scales is not None:
            # kv_codec="cluster": quantize this step's K/V onto the
            # codebook (one scale per (slot, token)), scatter the int8
            # codes + scale rows, and let the kernel decode each page in
            # VMEM — the fp cache never exists.
            from repro.kernels import kv_codec
            k, k_sc = kv_codec.encode(k, axes=(-2, -1))
            v, v_sc = kv_codec.encode(v, axes=(-2, -1))
            new_scales = {"k": paged.write(scales["k"], k_sc, pos, q_lens),
                          "v": paged.write(scales["v"], v_sc, pos, q_lens)}
            kw = dict(k_scales=new_scales["k"], v_scales=new_scales["v"])
        k_pool = paged.write(cache["k"], k, pos, q_lens)
        v_pool = paged.write(cache["v"], v, pos, q_lens)
        out = paged_mixed_attention(
            (q.astype(jnp.float32) * hd ** -0.5), k_pool, v_pool,
            paged.table, pos + ql, ql, window=window,
            softcap_val=cfg.attn_logit_softcap,
            page_size=paged.page_size, q_block=paged.q_block,
            pages_per_step=paged.pages_per_step,
            interpret=paged.interpret, **kw)[..., :hd]
        y = out.reshape(b, s, -1).astype(x.dtype) @ p["wo"]
        new_cache = {"k": k_pool, "v": v_pool}
        if scales is not None:
            return y, new_cache, new_scales
        return y, new_cache

    if chunked:
        # chunked prefill / mixed lane step: 1..s tokens per lane at
        # absolute positions pos..pos+len-1 against a partially filled
        # cache.  Attention runs over (resident cache ++ chunk) with
        # absolute-position masks; the chunk's K/V is written back
        # afterwards so rolling windows never read their own overwrites.
        q_pos = jnp.asarray(pos)[..., None] + jnp.arange(s)  # (S,) | (B,S)
        positions = q_pos if q_pos.ndim == 2 else q_pos[None, :]
        q, k, v = _qkv(p, x, cfg, positions)
        smax = cache["k"].shape[1]
        rolling = bool(window)
        if kv_quant and not rolling:
            # rolling-window lanes stay raw under the kernel backend too
            # (their pages never enter the code pools), so only full-history
            # lanes quantise here.
            k = _codec_roundtrip(k, (-2, -1))
            v = _codec_roundtrip(v, (-2, -1))
        if rolling:
            k_pos = _rolling_slot_positions(pos, smax)
        else:
            slot = jnp.arange(smax)
            k_pos = jnp.where(slot < jnp.asarray(pos)[..., None], slot, -1)
        out = chunk_attention(q, k, v, cache["k"], cache["v"], q_pos, k_pos,
                              window=window,
                              attn_softcap=cfg.attn_logit_softcap,
                              q_lens=q_lens)
        new_cache = {
            "k": _lane_chunk_write(cache["k"], k, pos, q_lens,
                                   rolling=rolling),
            "v": _lane_chunk_write(cache["v"], v, pos, q_lens,
                                   rolling=rolling),
        }
    elif decode:
        rolling = bool(window)
        if jnp.ndim(pos) == 0:           # shared position (wave decode)
            positions = jnp.full((b, 1), pos, jnp.int32)
            q, k, v = _qkv(p, x, cfg, positions)
            if kv_quant and not rolling:
                # quantise-then-attend, matching the kernel backend: the
                # new row's key/value enter this step's softmax already
                # on the codebook, exactly as every later step sees them
                k = _codec_roundtrip(k, (-2, -1))
                v = _codec_roundtrip(v, (-2, -1))
            slot = pos % cache["k"].shape[1] if rolling else pos
            k_cache = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        else:                            # (B,) per-lane positions
            positions = jnp.asarray(pos, jnp.int32)[:, None]
            q, k, v = _qkv(p, x, cfg, positions)
            if kv_quant and not rolling:
                k = _codec_roundtrip(k, (-2, -1))
                v = _codec_roundtrip(v, (-2, -1))
            slot = positions[:, 0] % cache["k"].shape[1] if rolling \
                else positions[:, 0]
            lane = jnp.arange(b)
            k_cache = cache["k"].at[lane, slot].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[lane, slot].set(
                v[:, 0].astype(cache["v"].dtype))
        out = decode_attention(q, k_cache, v_cache, pos, window=window,
                               attn_softcap=cfg.attn_logit_softcap,
                               rolling=rolling)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        positions = jnp.arange(s)[None, :]
        q, k, v = _qkv(p, x, cfg, positions)
        q, k, v = seq_shard_attention(q, k, v)   # SP layout (dist.sharding)
        out = flash_attention(
            q, k, v, causal=causal, window=window, prefix_len=prefix_len,
            attn_softcap=cfg.attn_logit_softcap)
        out = constrain(out, "batch", "model", None, None)
        new_cache = None
        if cache is not None:                      # prefill: fill the cache
            smax = cache["k"].shape[1]
            if window and smax < s:                # rolling window cache:
                # position p must land at slot p % smax for decode to append
                shift = s % smax
                k_keep = jnp.roll(k[:, -smax:], shift, axis=1)
                v_keep = jnp.roll(v[:, -smax:], shift, axis=1)
            else:
                k_keep = jnp.pad(k, ((0, 0), (0, smax - min(s, smax)),
                                     (0, 0), (0, 0)))[:, :smax]
                v_keep = jnp.pad(v, ((0, 0), (0, smax - min(s, smax)),
                                     (0, 0), (0, 0)))[:, :smax]
            new_cache = {"k": k_keep.astype(cache["k"].dtype),
                         "v": v_keep.astype(cache["v"].dtype)}
    y = out.reshape(b, s, -1).astype(x.dtype) @ p["wo"]
    return y, new_cache


def attn_cache_spec(cfg, kind: str, batch: int, max_len: int):
    """ShapeDtypeStructs of this layer kind's cache."""
    window = cfg.window if kind in ("swa", "local") else 0
    length = min(window, max_len) if window else max_len
    shp = (batch, length, cfg.num_kv_heads, cfg.head_dim)
    dt = cfg.jnp_dtype
    return {"k": jax.ShapeDtypeStruct(shp, dt),
            "v": jax.ShapeDtypeStruct(shp, dt)}


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg, dtype) -> dict:
    return attn_init(key, cfg, dtype)


def cross_attn_apply(p, x, cfg, *, enc_kv=None, enc_out=None):
    """enc_kv: precomputed {"k","v"} (prefill caches them); else compute from
    enc_out."""
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    if enc_kv is None:
        se = enc_out.shape[1]
        k = (enc_out @ p["wk"]).reshape(b, se, kh, hd)
        v = (enc_out @ p["wv"]).reshape(b, se, kh, hd)
    else:
        k, v = enc_kv["k"], enc_kv["v"]
    out = flash_attention(q, k, v, causal=False)
    return out.reshape(b, s, -1).astype(x.dtype) @ p["wo"], {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): latent KV compression, absorbed decode
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    h = cfg.num_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    return {
        "w_dq": dense_init(ks[0], d, r_q, dtype),
        "q_norm": jnp.zeros((r_q,), dtype),
        "w_uq": dense_init(ks[1], r_q, h * (dn + dr), dtype),
        "w_dkv": dense_init(ks[2], d, r_kv + dr, dtype),
        "kv_norm": jnp.zeros((r_kv,), dtype),
        "w_uk": dense_init(ks[3], r_kv, h * dn, dtype),
        "w_uv": dense_init(ks[4], r_kv, h * dv, dtype),
        "wo": dense_init(ks[5], h * dv, d, dtype),
    }


def mla_apply(p, x, cfg, *, cache=None, pos=None, paged=None, q_lens=None,
              scales=None, kv_quant=False):
    """-> (y, new_cache); with ``scales`` -> (y, new_cache, new_scales)."""
    b, s, d = x.shape
    h = cfg.num_heads
    r_kv = cfg.kv_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    # the absorbed-latent branch serves both single-token decode (s == 1)
    # and chunked prefill (s > 1): every einsum already carries the s axis,
    # only the causal mask needs per-query positions
    decode = cache is not None and pos is not None
    if paged is not None:
        positions = jnp.asarray(pos, jnp.int32)[:, None] \
            + jnp.arange(s, dtype=jnp.int32)[None]            # (B, S)
    else:
        positions = (pos + jnp.arange(s)[None, :] if decode
                     else jnp.arange(s)[None, :])

    cq = rms_norm(p["q_norm"], x @ p["w_dq"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    dkv = x @ p["w_dkv"]                                  # (B, S, r_kv + dr)
    c_kv = rms_norm(p["kv_norm"], dkv[..., :r_kv], cfg.norm_eps)
    k_pe = apply_rope(dkv[..., None, r_kv:], positions, cfg.rope_theta)[:, :, 0]

    if paged is not None:
        # absorbed attention straight over the paged latent pools — one
        # ragged mixed-step block of 1..s tokens per slot: the MLA latent
        # is one shared KV "head" whose key has a latent part (c_kv,
        # scored against q absorbed through w_uk) and a rope part (k_pe)
        # — exactly the kernel's (q, k) + (q2, k2) split, with the latent
        # pool doubling as the value pool.
        from repro.kernels.paged_attention import paged_mixed_attention
        pos = jnp.asarray(pos, jnp.int32)
        ql = (jnp.full((b,), s, jnp.int32) if q_lens is None
              else jnp.asarray(q_lens, jnp.int32))
        kw = {}
        if scales is not None:
            # kv_codec="cluster" over the latent pools: the latent (c_kv)
            # doubles as key and value so its scale pool rides both
            # operands; the rope part (k_pe) is the second-score operand.
            from repro.kernels import kv_codec
            c_kv, c_sc = kv_codec.encode(c_kv, axes=(-1,))
            k_pe, pe_sc = kv_codec.encode(k_pe, axes=(-1,))
            new_scales = {
                "c_kv": paged.write(scales["c_kv"], c_sc, pos, q_lens),
                "k_pe": paged.write(scales["k_pe"], pe_sc, pos, q_lens)}
            kw = dict(k_scales=new_scales["c_kv"],
                      v_scales=new_scales["c_kv"],
                      k2_scales=new_scales["k_pe"])
        c_pool = paged.write(cache["c_kv"], c_kv, pos, q_lens)
        pe_pool = paged.write(cache["k_pe"], k_pe, pos, q_lens)
        w_uk = p["w_uk"].reshape(r_kv, h, dn)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))      # (B, S, H, r_kv)
        ctx = paged_mixed_attention(
            q_lat, c_pool[:, :, None], c_pool[:, :, None],
            paged.table, pos + ql, ql,
            q_pe.astype(jnp.float32), pe_pool[:, :, None],
            scale=(dn + dr) ** -0.5, page_size=paged.page_size,
            q_block=paged.q_block, pages_per_step=paged.pages_per_step,
            interpret=paged.interpret, **kw)[..., :r_kv]
        w_uv = p["w_uv"].reshape(r_kv, h, dv)
        out = jnp.einsum("bshr,rhv->bshv", ctx,
                         w_uv.astype(jnp.float32))        # (B, S, H, dv)
        y = out.reshape(b, s, h * dv).astype(x.dtype) @ p["wo"]
        new_cache = {"c_kv": c_pool, "k_pe": pe_pool}
        if scales is not None:
            return y, new_cache, new_scales
        return y, new_cache

    if decode:
        if kv_quant:
            c_kv = _codec_roundtrip(c_kv, (-1,))
            k_pe = _codec_roundtrip(k_pe, (-1,))
        if q_lens is None:
            c_cache = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, pos, 0))
            pe_cache = jax.lax.dynamic_update_slice(
                cache["k_pe"], k_pe.astype(cache["k_pe"].dtype), (0, pos, 0))
        else:
            # ragged speculative verification: only rows < q_lens are real
            # — rejected-draft and padding rows are routed out of bounds
            # and dropped, so the cache never sees them (a q_lens == 0
            # lane is an exact no-op)
            ql = jnp.asarray(q_lens, jnp.int32)
            rows = pos + jnp.arange(s)[None, :]               # (1, S)
            rows = jnp.where(jnp.arange(s)[None, :] < ql[:, None],
                             rows, cache["c_kv"].shape[1])
            lane = jnp.arange(b)[:, None]
            c_cache = cache["c_kv"].at[lane, rows].set(
                c_kv.astype(cache["c_kv"].dtype), mode="drop")
            pe_cache = cache["k_pe"].at[lane, rows].set(
                k_pe.astype(cache["k_pe"].dtype), mode="drop")
        # absorbed attention in latent space
        w_uk = p["w_uk"].reshape(r_kv, h, dn)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))      # (B, 1, H, r_kv)
        scale = (dn + dr) ** -0.5
        s_lat = jnp.einsum("bshr,bkr->bhsk", q_lat,
                           c_cache.astype(jnp.float32))
        s_pe = jnp.einsum("bshd,bkd->bhsk", q_pe.astype(jnp.float32),
                          pe_cache.astype(jnp.float32))
        scores = (s_lat + s_pe) * scale                # (B, H, s, K)
        q_pos = pos + jnp.arange(s)
        valid = jnp.arange(c_cache.shape[1])[None, :] <= q_pos[:, None]
        scores = jnp.where(valid[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhsk,bkr->bshr", probs,
                         c_cache.astype(jnp.float32))     # (B, 1, H, r_kv)
        w_uv = p["w_uv"].reshape(r_kv, h, dv)
        out = jnp.einsum("bshr,rhv->bshv", ctx, w_uv.astype(jnp.float32))
        new_cache = {"c_kv": c_cache, "k_pe": pe_cache}
    else:
        k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, dn)
        v = (c_kv @ p["w_uv"]).reshape(b, s, h, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], (b, s, h, dr))], -1)
        q_full = jnp.concatenate([q_nope, q_pe], -1)
        # MLA has 128 heads: head-TP divides the 16-wide model axis cleanly
        q_full = constrain(q_full, "batch", None, "model", None)
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)
        out = flash_attention(q_full, k, v, causal=True)
        out = constrain(out, "batch", None, "model", None)
        new_cache = None
        if cache is not None:
            smax = cache["c_kv"].shape[1]
            ck = jnp.pad(c_kv, ((0, 0), (0, smax - s), (0, 0)))
            pk = jnp.pad(k_pe, ((0, 0), (0, smax - s), (0, 0)))
            new_cache = {"c_kv": ck.astype(cache["c_kv"].dtype),
                         "k_pe": pk.astype(cache["k_pe"].dtype)}
    y = out.reshape(b, s, h * dv).astype(x.dtype) @ p["wo"]
    return y, new_cache


def mla_cache_spec(cfg, batch: int, max_len: int):
    dt = cfg.jnp_dtype
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_len, cfg.kv_lora_rank), dt),
        "k_pe": jax.ShapeDtypeStruct((batch, max_len, cfg.rope_head_dim), dt),
    }
