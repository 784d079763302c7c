"""Pallas TPU kernel: in-kernel paged attention over slot page tables.

Attention that consumes the scheduler's paged KV layout *directly*: the
physical page pool ``(n_pages, page, KH, D)`` plus a per-slot page table
and per-slot lengths.  Each ``(slot, q_block, page group)`` grid step
pulls ``pages_per_step`` physical pages into VMEM — the BlockSpec index
maps read the page table through scalar prefetch, so the DMA engine
walks the table and never touches pages the slot does not own — applies
the per-token causal/position mask, and folds the pages into an
online-softmax accumulator held in VMEM scratch.  No contiguous
per-slot view of the cache is ever materialised, in HBM or anywhere
else: this is the serving analogue of the paper's in-pipeline decoding
unit (§IV), which consumes operands in their at-rest layout instead of
expanding them into memory first.

Since the mixed-step generalisation the kernel serves *ragged
multi-token* queries: slot ``s`` contributes ``q_lens[s]`` consecutive
tokens (a prefill chunk, or a single decode token) out of a padded
``(S, Q)`` block, and causality is enforced per query token inside the
online-softmax loop — token ``i`` of slot ``s`` sits at absolute position
``lengths[s] - q_lens[s] + i`` and may only attend keys at positions
``<= `` its own.  Decode is the ``Q == 1`` special case
(:func:`paged_decode_attention`); prefill chunks and decode tokens of
different slots ride in the same invocation.

Layout contract (shared with ``runtime.scheduler.SlotPool``):

  * physical page 0 is the dummy sink — table entries past a slot's length
    point at it and it is never read as a valid position (every position
    ``< lengths[s]`` has a real page, and everything else is masked);
  * a slot's logical page ``j`` covers absolute positions
    ``[j * page_size, (j + 1) * page_size)`` where ``page_size`` is the
    *logical* page length — the pool's physical page dimension may be
    padded up to a sublane tile (``page_size=0`` means they coincide),
    and padded rows are masked out of the softmax like any other
    out-of-range position;
  * ``lengths[s]`` = number of valid positions *including* this step's
    tokens (the whole chunk's K/V is written into the pool *before* the
    call; the per-token causal masks preserve write-after-attend
    semantics — a query never sees a later chunk token's key);
  * padded rows/tokens (``i >= q_lens[s]``, including ``q_lens[s] == 0``
    free lanes) attend nothing and produce finite garbage the caller
    discards.

Hardware shaping (``pages_per_step``, tiled pools): with
``pages_per_step = c > 1`` each grid step carries ``c`` physical pages,
one BlockSpec per page, indexed ``table[s, j * c + i]``.  Pallas
double-buffers every input BlockSpec across grid steps, so the ``c``
page DMAs of step ``j + 1`` overlap the score/softmax compute of step
``j`` — the same async-copy overlap ``pltpu.make_async_copy`` expresses
by hand, but driven by the pipeline.  The pages of a group are folded
into the online softmax one after another, so ``c`` changes the DMA
shape and not the arithmetic.  Feature dims padded toward the (8, 128)
sublane/lane tiles by ``SlotPool`` are never read: the kernel slices
each page to its logical rows and the query's width.

Mosaic shape rules: the body uses only 2-D operations.  Each query head
and each KV head is read out of its block by a strided load, scored as
one (qb, D) x (D, page) product, and accumulated into per-head VMEM
scratch; no value is reshaped across the sublane axis, which the TPU
compiler refuses (``infer-vector-layout: unsupported shape cast``).

The optional second score operand ``(q2, k2_pages)`` serves MLA absorbed
decode: scores are ``q . k + q2 . k2`` (latent + rope parts) over a
single shared KV head, and ``v_pages`` is the latent pool itself.
``scale`` is applied to the summed scores (MLA) — GQA callers pre-scale
``q`` and leave it at 1.0, matching ``attention.decode_attention``'s
operation order exactly.

Compressed pages (``kv_codec="cluster"``): when ``k_scales``/``v_scales``
are passed the pools hold int8 codebook indices and each page is decoded
*in VMEM* right after its DMA — a codebook lookup, done as one
128-lane register gather per lane tile over the codebook's non-negative
half (Mosaic lowers no wider gather), times the per-(slot, token) scale
column that rides its own page-table-walked BlockSpec — before the
online-softmax score ever sees it.  The fp page
never exists in HBM.

``interpret=True`` runs the identical kernel through the Pallas
interpreter on CPU — how CI exercises it (same convention as
``fused_decode_matmul``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_codec

NEG_INF = -1e30


def effective_q_block(qn: int, q_block: int) -> int:
    """The query-block width the kernel will actually run.

    ``q_block=0`` means the whole ``Q`` per grid step; non-divisor
    requests round down to ``gcd(Q, q_block)`` (the same convention as
    flash_attention's ``q_chunk``).  Exposed so the scheduler can count
    the silent roundings (``kernel_qblock_rounded``)."""
    return math.gcd(qn, q_block) if q_block else qn


def _page_operand(ref, scale_ref, half, kv_head: int, rows: int,
                  width: int):
    """One KV head of one page as an f32 (rows, width) operand.

    ``ref`` is a page block (1, page, KH, D); indexing the KV head is a
    strided sublane load, so no in-kernel reshape or transpose is needed.
    Only the first ``rows`` rows and ``width`` feature columns are read:
    the rest of a tile-padded pool is layout padding.
    Under the codec the int8 codes are looked up in ``half`` -- the
    codebook's non-negative half broadcast to (page, 128), one lane per
    magnitude, so each 128-lane slice of the page is one in-register
    lane gather (the only gather Mosaic lowers) -- negated for negative
    codes, which is exact because the codebook is symmetric, and scaled
    by the per-token scale column ``scale_ref`` (1, page, 1).  That is
    ``kv_codec.decode`` bit for bit."""
    x = ref[0, :rows, kv_head, :width]
    if scale_ref is None:
        return x.astype(jnp.float32)
    codes = x.astype(jnp.int32)
    mag = jnp.abs(codes)
    lanes = half.shape[-1]
    vals = jnp.concatenate([
        jnp.take_along_axis(half, mag[:, lo:lo + lanes], axis=1,
                            mode="promise_in_bounds")
        for lo in range(0, mag.shape[-1], lanes)], axis=1)
    return jnp.where(codes < 0, -vals, vals) * scale_ref[0, :rows]


def _dot(a, b, contract_b: int):
    """a (m, k) contracted with axis ``contract_b`` of the 2-D ``b``, in
    f32 at full precision.  It is written as a batch-1 batched product:
    on the CPU the interpreter then sums each output in the same order
    whether an operand was loaded or computed (a decoded codec page), so
    the codec kernel stays bit-identical to the fp kernel over the
    decoded pool; Mosaic lowers it as the same single MXU product."""
    return jax.lax.dot_general(
        a[None], b[None], (((2,), (contract_b + 1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0]


def _kernel(table_ref, len_ref, qlen_ref, q_ref, *rest,
            logical: int, c: int, kh: int, g: int, qb: int, widths: tuple,
            window: int, softcap_val: float, scale: float,
            has_q2: bool, has_codec: bool):
    wk, wv, w2 = widths
    i = 0
    k_refs = rest[i:i + c]
    i += c
    v_refs = rest[i:i + c]
    i += c
    q2_ref, k2_refs = None, (None,) * c
    if has_q2:
        q2_ref = rest[i]
        i += 1
        k2_refs = rest[i:i + c]
        i += c
    ks_refs = vs_refs = k2s_refs = (None,) * c
    if has_codec:
        ks_refs = rest[i:i + c]
        i += c
        vs_refs = rest[i:i + c]
        i += c
        if has_q2:
            k2s_refs = rest[i:i + c]
            i += c
        half = jnp.broadcast_to(rest[i][...], (logical, rest[i].shape[-1]))
        i += 1
    else:
        half = None
    o_ref, m_ref, l_ref, acc_ref = rest[i:]
    s_idx = pl.program_id(0)
    qb_idx = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # query token i of this block sits at absolute position
    # lengths[s] - q_lens[s] + (qb_idx * qb + i); tokens past q_lens[s]
    # are ragged padding and attend nothing.  Key row r of page i of this
    # group is logical position (j * c + i) * logical + r; rows at or
    # past the logical page length are layout padding and never read.
    length = len_ref[s_idx]
    qlen = qlen_ref[s_idx]
    qi = qb_idx * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, logical), 0)
    qpos = (length - qlen) + qi
    row = jax.lax.broadcasted_iota(jnp.int32, (qb, logical), 1)

    # Every query head is its own 2-D (qb, D) x (D, page) product against
    # its KV head's page, and each page of the group is folded into the
    # online softmax in turn -- so the result does not depend on how many
    # pages one grid step carries.
    for pi in range(c):
        gpos = (j * c + pi) * logical + row
        valid = (gpos <= qpos) & (qi < qlen)
        if window:
            valid &= gpos > qpos - window
        for kv in range(kh):
            k = _page_operand(k_refs[pi], ks_refs[pi], half, kv, logical, wk)
            v = _page_operand(v_refs[pi], vs_refs[pi], half, kv, logical, wv)
            k2 = _page_operand(k2_refs[pi], k2s_refs[pi], half, kv, logical,
                               w2) if has_q2 else None
            for h in range(kv * g, (kv + 1) * g):
                s = _dot(q_ref[0, :, h, :].astype(jnp.float32), k, 1)
                if has_q2:
                    s = s + _dot(q2_ref[0, :, h, :].astype(jnp.float32),
                                 k2, 1)
                if scale != 1.0:
                    s = s * scale
                if softcap_val:
                    s = jnp.tanh(s / softcap_val) * softcap_val
                s = jnp.where(valid, s, NEG_INF)
                m_prev = m_ref[h]                              # (qb, 1)
                m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * alpha + _dot(p, v, 0)
                m_ref[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        for h in range(kh * g):
            o_ref[0, :, h, :] = acc_ref[h] / jnp.maximum(l_ref[h], 1e-20)


@functools.partial(jax.jit, static_argnames=("window", "softcap_val",
                                             "scale", "q_block",
                                             "page_size", "pages_per_step",
                                             "interpret"))
def paged_mixed_attention(
    q: jax.Array,            # (S, Q, H, D)  padded per-slot query blocks
    k_pages: jax.Array,      # (n_pages, page, KH, D)   physical key pool
    v_pages: jax.Array,      # (n_pages, page, KH, Dv)  physical value pool
    table: jax.Array,        # (S, P) int32 physical page per logical page
    lengths: jax.Array,      # (S,) int32 valid positions incl. this block
    q_lens: jax.Array,       # (S,) int32 real query tokens per slot (<= Q)
    q2: jax.Array | None = None,        # (S, Q, H, D2) MLA rope-part queries
    k2_pages: jax.Array | None = None,  # (n_pages, page, KH, D2)
    k_scales: jax.Array | None = None,   # (n_pages, page) f32 codec scales
    v_scales: jax.Array | None = None,   # (n_pages, page) f32 codec scales
    k2_scales: jax.Array | None = None,  # (n_pages, page) f32 codec scales
    *,
    window: int = 0,
    softcap_val: float = 0.0,
    scale: float = 1.0,
    q_block: int = 0,        # 0 = whole Q per grid step; non-divisors
    #                          round down to gcd(Q, q_block), same
    #                          convention as flash_attention's q_chunk
    page_size: int = 0,      # logical tokens per page; 0 = the pools'
    #                          physical page dim (i.e. no row padding)
    pages_per_step: int = 1,  # physical pages DMA'd per grid step
    interpret: bool = False,
) -> jax.Array:
    """out (S, Q, H, Dv) float32 — ragged mixed-step paged attention.

    Numerically equivalent to gathering each slot's pages into a
    contiguous cache and running the gathered reference attention
    (``attention.decode_attention`` / ``attention.chunk_attention`` — the
    oracles in tests); the cache copy just never happens.  Rows beyond
    ``q_lens[s]`` are padding: their output is finite garbage the caller
    must ignore.

    When ``k_scales`` is given (``kv_codec="cluster"``) the K/V pools —
    and ``k2_pages`` if present — hold int8 ``kv_codec`` codes; each
    page is decoded in VMEM (a codebook lookup times its per-token
    scale) before scoring.  Equivalent to decoding the whole pool
    up front, without ever materialising the fp pool.

    Tiled pools: the kernel reads only the first ``D`` (``q``'s width)
    feature columns of the key pool and ``D2`` of ``k2_pages``; a value
    pool as wide as the key pool is read at ``D`` too (pools padded
    together), any other at its own width ``Dv``, which is the output's
    width.  ``page_size < k_pages.shape[1]`` declares the trailing
    physical rows of every page to be layout padding.  Padding is thus
    never computed on, and a padded pool gives the unpadded result.
    """
    s_n, qn, h, d = q.shape
    n_pages, page, kh, dk = k_pages.shape
    dv = v_pages.shape[-1]
    logical = page_size or page
    assert 0 < logical <= page, (logical, page)
    assert dk >= d, (dk, d)
    assert h % kh == 0, (h, kh)
    g = h // kh
    wv = d if dv == dk else dv        # value columns read and returned
    c = max(int(pages_per_step), 1)
    n_groups = -(-table.shape[1] // c)
    if n_groups * c != table.shape[1]:
        # pad the table with dummy-page entries so every grid step walks
        # exactly c pages; the extra logical pages sit past the slot
        # capacity, so every row of them is masked.
        table = jnp.pad(table, ((0, 0), (0, n_groups * c - table.shape[1])))
    qb = effective_q_block(qn, q_block)
    nqb = qn // qb

    def walk(i, block):
        # one BlockSpec per page of the group: page i of grid step j is
        # physical page table[s, j * c + i]; Pallas pipelines the next
        # step's c DMAs behind this step's compute.
        return pl.BlockSpec(
            block, lambda s, qi, j, t, ln, ql, i=i: (t[s, j * c + i],)
            + (0,) * (len(block) - 1))

    in_specs = [
        pl.BlockSpec((1, qb, h, d),
                     lambda s, qi, j, t, ln, ql: (s, qi, 0, 0)),
        *[walk(i, (1, page, kh, dk)) for i in range(c)],
        *[walk(i, (1, page, kh, dv)) for i in range(c)],
    ]
    args = [q, *[k_pages] * c, *[v_pages] * c]
    w2 = 0
    if q2 is not None:
        d2, w2 = k2_pages.shape[-1], q2.shape[-1]
        assert d2 >= w2, (d2, w2)
        in_specs += [
            pl.BlockSpec((1, qb, h, w2),
                         lambda s, qi, j, t, ln, ql: (s, qi, 0, 0)),
            *[walk(i, (1, page, kh, d2)) for i in range(c)],
        ]
        args += [q2, *[k2_pages] * c]
    scratch = [
        pltpu.VMEM((h, qb, 1), jnp.float32),      # running max
        pltpu.VMEM((h, qb, 1), jnp.float32),      # running normaliser
        pltpu.VMEM((h, qb, wv), jnp.float32),     # output accumulator
    ]
    if k_scales is not None:
        # one scale column (page, 1) per physical page, walked through the
        # page table exactly like the pools themselves; a column scales
        # the decoded page's rows without an in-kernel transpose
        for sc in (k_scales, v_scales) + ((k2_scales,) if q2 is not None
                                           else ()):
            in_specs += [walk(i, (1, page, 1)) for i in range(c)]
            args += [sc.astype(jnp.float32).reshape(n_pages, page, 1)] * c
        in_specs += [pl.BlockSpec((1, kv_codec.ZERO_CODE),
                                  lambda s, qi, j, t, ln, ql: (0, 0))]
        args += [kv_codec.codebook()[None, kv_codec.ZERO_CODE:]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n, nqb, n_groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, qb, h, wv),
                               lambda s, qi, j, t, ln, ql: (s, qi, 0, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_kernel, logical=logical, c=c, kh=kh,
                          g=g, qb=qb, widths=(d, wv, w2), window=window,
                          softcap_val=softcap_val, scale=scale,
                          has_q2=q2 is not None,
                          has_codec=k_scales is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, qn, h, wv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(q_lens, jnp.int32), *args)


def paged_decode_attention(
    q: jax.Array,            # (S, H, D)   this step's queries, one per slot
    k_pages: jax.Array,
    v_pages: jax.Array,
    table: jax.Array,
    lengths: jax.Array,      # (S,) int32   valid positions per slot
    q2: jax.Array | None = None,
    k2_pages: jax.Array | None = None,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    k2_scales: jax.Array | None = None,
    *,
    window: int = 0,
    softcap_val: float = 0.0,
    scale: float = 1.0,
    page_size: int = 0,
    pages_per_step: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """out (S, H, Dv) float32 — single-token decode, the ``Q == 1``
    special case of :func:`paged_mixed_attention` (each slot's one query
    sits at position ``lengths[s] - 1``)."""
    out = paged_mixed_attention(
        q[:, None], k_pages, v_pages, table, lengths,
        jnp.ones((q.shape[0],), jnp.int32),
        None if q2 is None else q2[:, None], k2_pages,
        k_scales, v_scales, k2_scales,
        window=window, softcap_val=softcap_val, scale=scale,
        page_size=page_size, pages_per_step=pages_per_step,
        interpret=interpret)
    return out[:, 0]
