"""Pallas TPU kernel: in-kernel paged attention over slot page tables.

Attention that consumes the scheduler's paged KV layout *directly*: the
physical page pool ``(n_pages, page, KH, D)`` plus a per-slot page table
and per-slot lengths.  Each ``(slot, q_block, page)`` grid step pulls
one physical page into VMEM — the BlockSpec index maps read the page
table through scalar prefetch, so the DMA engine walks the table and
never touches pages the slot does not own; Pallas double-buffers every
input BlockSpec, so page ``j + 1``'s DMA overlaps page ``j``'s compute —
applies the per-token causal/position mask, and folds the page into an
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
    ``[j * page, (j + 1) * page)``, ``page`` being the pools' page axis;
  * ``lengths[s]`` = number of valid positions *including* this step's
    tokens (the whole chunk's K/V is written into the pool *before* the
    call; the per-token causal masks preserve write-after-attend
    semantics — a query never sees a later chunk token's key);
  * padded rows/tokens (``i >= q_lens[s]``, including ``q_lens[s] == 0``
    free lanes) attend nothing and produce finite garbage the caller
    discards.

Query heads as rows: the ``g = H / KH`` query heads of one KV head share
one product per page.  The queries are laid out before the call as a 2-D
``(Q * g, D)`` operand per slot and KV head -- row ``t * g + i`` is head
``kv * g + i`` of token ``t`` -- so a q block is ``qb * g`` rows, scored
in one ``(qb * g, D) x (D, page)`` product and folded into the online
softmax with one ``(qb * g, page) x (page, Dv)`` product, ``KH`` of each
per page.  For GQA ``qb`` is sized from the shapes (:func:`gqa_q_block`):
all of Q while ``Q * g`` rows fit about one MXU pass, else the most
tokens whose rows do and are a multiple of the 8-row sublane tile; the
last q block may then run past Q, and its rows are padding.

Shared latent head (MLA): the optional second score operand ``(q2,
k2_pages)`` serves absorbed attention over one KV head that all ``H``
query heads share: scores are ``q . k + q2 . k2`` (latent + rope parts)
and the latent key pool is the value pool too (``v_pages=None``, one DMA
per page).  ``qb`` is the largest divisor of Q whose ``qb * H`` rows fit
:data:`VMEM_BUDGET` (:func:`latent_q_block`; 128 heads x 576 wide: 4 at
a 16-token chunk, 1 at decode).  ``scale`` is applied to the summed
scores (MLA) -- GQA callers pre-scale ``q`` and leave it at 1.0, matching
``attention.decode_attention``'s operation order exactly.

Mosaic shape rules: the body uses only 2-D operations.  Each KV head is
read out of its page block by a strided load and each KV head's query
rows out of the q block by a leading-axis index; softmax state is kept
per KV head in VMEM scratch.  No value is reshaped across the sublane
axis, which the TPU compiler refuses (``infer-vector-layout:
unsupported shape cast``).

Dead grid steps: a grid step whose q block holds no real query
(``qb_idx * qb >= q_lens[s]``), or whose pages hold no key a real query
of the block may see (a page starting at or past ``lengths[s]``, or,
under a window, one that ends before the block's first query's window)
does no arithmetic; :func:`live_grid_steps` counts the others on the
host, for the launch each call records on its kernel
(:func:`kernel_launches` reads them back from a traced program).  The
grid step still runs, and its page index maps read the table as it
stands: past a slot's length every entry names the dummy sink, one
block for all of the slot's dead pages, so Pallas starts no DMA for
them; a dead q block, and under a window a page before it, still fetch
their pages, which costs less than the scalar work of clamping the
maps to the live pages.  Skipping is exact: a page every row of the
block masks adds exactly zero to a real row's online softmax once a page
holding one of its keys has been folded in, and a real row's own
position always lies in a live page.

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

import collections
import functools
import math
from typing import NamedTuple

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import kv_codec

NEG_INF = -1e30
# scoped VMEM a q block's per-row blocks and scratch may take: v5e's
# default scoped limit is 16 MiB, and the page blocks and the compiler's
# own scratch need the rest
VMEM_BUDGET = 8 * 2**20
# query rows a GQA q block aims at: one pass of v5e's 128 x 128 MXU
GQA_BLOCK_ROWS = 128


def effective_q_block(qn: int, q_block: int) -> int:
    """The query-block width the kernel will actually run.

    ``q_block=0`` means the whole ``Q`` per grid step; non-divisor
    requests round down to ``gcd(Q, q_block)`` (the same convention as
    flash_attention's ``q_chunk``)."""
    return math.gcd(qn, q_block) if q_block else qn


def q_row_bytes(kh: int, d: int, wv: int, w2: int, page: int) -> int:
    """VMEM bytes one query row of a q block takes: per KV head its f32
    query row (and, under MLA, rope-query row ``w2`` wide) and output
    row, double-buffered, its accumulator row and its running max and
    normaliser (a lane tile each); and a score and a probability row of
    the page."""
    lanes = -(-page // 128) * 128
    return 4 * (kh * (2 * (d + w2) + 2 * wv + wv + 2 * 128) + 2 * lanes)


def latent_q_block(qn: int, q_block: int, h: int, wq: int, w2: int,
                   page: int) -> int:
    """Query tokens per grid step over the shared latent head (MLA): the
    largest divisor of ``Q`` at or below the requested block (0 = all of
    Q) whose ``qb * h`` rows fit :data:`VMEM_BUDGET`; the latent is the
    value, so the output rows are ``wq`` wide."""
    qb = effective_q_block(qn, q_block)
    per_token = h * q_row_bytes(1, wq, wq, w2, page)
    while qb > 1 and qb * per_token > VMEM_BUDGET:
        qb = max(d for d in range(1, qb) if qn % d == 0)
    return qb


def gqa_q_block(qn: int, q_block: int, g: int, kh: int, d: int, wv: int,
                page: int) -> int:
    """Query tokens per grid step of the GQA kernel, whose q block is
    ``qb * g`` rows per KV head.  An explicit ``q_block`` keeps
    :func:`effective_q_block`'s meaning where the chip takes its rows (a
    multiple of the 8-row sublane tile, or all of ``Q * g``); a block it
    would refuse falls back to the one sized from the shapes.  That is
    all of Q while ``Q * g`` rows fit :data:`GQA_BLOCK_ROWS` and
    :data:`VMEM_BUDGET`; else the most tokens whose rows fit both and
    are a multiple of the sublane tile, which need not divide Q."""
    if q_block:
        qb = effective_q_block(qn, q_block)
        if qb == qn or qb * g % 8 == 0:
            return qb
    rows = min(GQA_BLOCK_ROWS,
               VMEM_BUDGET // q_row_bytes(kh, d, wv, 0, page))
    if qn * g <= rows:
        return qn
    step = 8 // math.gcd(8, g)
    return max(step, rows // g // step * step)


def _live_pages(length, qlen, first, *, page: int, window: int,
                maximum=jnp.maximum):
    """The logical pages ``[lo, hi]`` that hold a key some real query of
    a q block may see, the block's first token being token ``first`` of
    the slot's ``qlen``: none at or past ``length``, and under a window
    none that ends before the first query's window opens.  The one rule
    of the kernel's skips and :func:`live_grid_steps`; ``maximum`` is
    ``np.maximum`` on the host."""
    hi = (length + page - 1) // page - 1
    if not window:
        return 0, hi
    opens = length - qlen + first - window + 1      # earliest key it sees
    return maximum(opens, 0) // page, hi


def live_grid_steps(lengths, q_lens, *, qn: int, qb: int, n_pages: int,
                    logical: int, window: int = 0) -> tuple[int, int]:
    """(walked, live) grid steps of one :func:`paged_mixed_attention`
    call over slots of ``lengths`` and ``q_lens`` (host arrays), ``Q =
    qn`` tokens in q blocks of ``qb``, page tables of ``n_pages`` pages
    of ``logical`` tokens: all the steps its grid runs, and those that
    compute -- a q block holding a real query and a page that is live by
    :func:`_live_pages`."""
    lengths = np.asarray(lengths, np.int64)[:, None]
    q_lens = np.asarray(q_lens, np.int64)[:, None]
    nqb = -(-qn // qb)
    first = np.arange(nqb)[None] * qb
    lo, hi = _live_pages(lengths, q_lens, first, page=logical,
                         window=window, maximum=np.maximum)
    pages = np.maximum(hi - np.asarray(lo) + 1, 0)
    live = int(np.where(first < q_lens, pages, 0).sum())
    return lengths.shape[0] * nqb * n_pages, live


class Launch(NamedTuple):
    """One :func:`paged_mixed_attention` call as the kernel resolved it:
    :func:`live_grid_steps`'s keywords (``logical`` is the page
    length)."""
    qn: int
    qb: int
    n_pages: int
    logical: int
    window: int

    def grid_steps(self, lengths, q_lens) -> tuple[int, int]:
        """(walked, live) grid steps of this call over slots of
        ``lengths`` and ``q_lens`` (:func:`live_grid_steps`)."""
        return live_grid_steps(lengths, q_lens, **self._asdict())


def kernel_launches(jaxpr) -> collections.Counter:
    """The :func:`paged_mixed_attention` calls a traced program makes: a
    Counter of :class:`Launch` over the times each runs per execution,
    read from the launch each call records on its kernel.  A call under
    a ``scan`` counts once per iteration; one under a ``cond`` once per
    branch."""
    out: collections.Counter = collections.Counter()

    def walk(jx, times):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                if eqn.params["name"] == "paged_mixed_attention":
                    out[Launch(**{k: int(v) for k, v in
                                  eqn.params["metadata"].items()})] += times
                continue
            n = times * eqn.params["length"] \
                if eqn.primitive.name == "scan" else times
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr, n)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub, n)

    walk(getattr(jaxpr, "jaxpr", jaxpr), 1)
    return out


def _page_operand(ref, scale_ref, half, kv_head: int):
    """One KV head of one page as an f32 (page, D) operand.

    ``ref`` is a page block (1, page, KH, D); indexing the KV head is a
    strided sublane load, so no in-kernel reshape or transpose is needed.
    Under the codec the int8 codes are looked up in ``half`` -- the
    codebook's non-negative half broadcast to (page, 128), one lane per
    magnitude, so each 128-lane slice of the page is one in-register
    lane gather (the only gather Mosaic lowers) -- negated for negative
    codes, which is exact because the codebook is symmetric, and scaled
    by the per-token scale column ``scale_ref`` (1, page, 1).  That is
    ``kv_codec.decode`` bit for bit."""
    x = ref[0, :, kv_head, :]
    if scale_ref is None:
        return x.astype(jnp.float32)
    codes = x.astype(jnp.int32)
    mag = jnp.abs(codes)
    lanes = half.shape[-1]
    vals = jnp.concatenate([
        jnp.take_along_axis(half, mag[:, lo:lo + lanes], axis=1,
                            mode="promise_in_bounds")
        for lo in range(0, mag.shape[-1], lanes)], axis=1)
    return jnp.where(codes < 0, -vals, vals) * scale_ref[0]


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


def _row_token(r, g: int, n: int):
    """``r // g`` for the row indices ``0 <= r < n * g``, without a vector
    division: a shift when ``g`` is a power of two, else ``n - 1``
    compares."""
    if g & (g - 1) == 0:
        return r >> (g.bit_length() - 1)
    tok = jnp.zeros_like(r)
    for t in range(1, n):
        tok = tok + (r >= t * g).astype(jnp.int32)
    return tok


def _kernel(table_ref, len_ref, qlen_ref, q_ref, *rest,
            page: int, kh: int, g: int, qb: int, window: int,
            softcap_val: float, scale: float, has_codec: bool,
            latent: bool):
    """One grid step: per KV head, its ``qb * g`` query rows (token-major,
    row ``t * g + i`` is head ``kv * g + i``) against one page, one score
    product and one value product per KV head; no arithmetic on a dead q
    block or page (:func:`_live_pages`).  Under ``latent`` (MLA) the
    second operand adds ``q2 . k2`` to the scores and the key page is the
    value page too."""
    refs = iter(rest)
    k_ref = next(refs)
    q2_ref = next(refs) if latent else None
    # the k2 page under ``latent``, else the value page
    x_ref = next(refs)
    ks_ref = xs_ref = half_ref = None
    if has_codec:
        ks_ref, xs_ref, half_ref = next(refs), next(refs), next(refs)
    o_ref, m_ref, l_ref, acc_ref = refs
    s_idx = pl.program_id(0)
    qb_idx = pl.program_id(1)
    j = pl.program_id(2)
    rows = qb * g

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[s_idx]
    qlen = qlen_ref[s_idx]
    first = qb_idx * qb
    lo, hi = _live_pages(length, qlen, first, page=page, window=window)

    @pl.when((first < qlen) & (j >= lo) & (j <= hi))
    def _page():
        # row r holds token first + r // g of the block, at absolute
        # position lengths[s] - q_lens[s] + that; tokens past q_lens[s]
        # are ragged padding and attend nothing.  Key row r of this page
        # is position j * page + r.
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0)
        qi = first + _row_token(r, g, qb)
        qpos = (length - qlen) + qi
        gpos = j * page + jax.lax.broadcasted_iota(jnp.int32, (rows, page), 1)
        valid = (gpos <= qpos) & (qi < qlen)
        if window:
            valid &= gpos > qpos - window
        half = None if half_ref is None else jnp.broadcast_to(
            half_ref[...], (page, half_ref.shape[-1]))
        for kv in range(kh):
            k = _page_operand(k_ref, ks_ref, half, kv)
            s = _dot(q_ref[0, kv].astype(jnp.float32), k, 1)
            if latent:
                k2 = _page_operand(x_ref, xs_ref, half, 0)
                s = s + _dot(q2_ref[0, 0].astype(jnp.float32), k2, 1)
                v = k
            else:
                v = _page_operand(x_ref, xs_ref, half, kv)
            if scale != 1.0:
                s = s * scale
            if softcap_val:
                s = jnp.tanh(s / softcap_val) * softcap_val
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[kv]                       # (rows, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[kv] = l_ref[kv] * alpha + p.sum(-1, keepdims=True)
            acc_ref[kv] = acc_ref[kv] * alpha + _dot(p, v, 0)
            m_ref[kv] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("window", "softcap_val",
                                             "scale", "q_block",
                                             "interpret"))
def paged_mixed_attention(
    q: jax.Array,            # (S, Q, H, D)  padded per-slot query blocks
    k_pages: jax.Array,      # (n_pages, page, KH, D)   physical key pool
    v_pages: jax.Array | None,  # (n_pages, page, KH, Dv) value pool;
    #                             None (MLA): the key pool is the value pool
    table: jax.Array,        # (S, P) int32 physical page per logical page
    lengths: jax.Array,      # (S,) int32 valid positions incl. this block
    q_lens: jax.Array,       # (S,) int32 real query tokens per slot (<= Q)
    q2: jax.Array | None = None,        # (S, Q, H, D2) MLA rope-part queries
    k2_pages: jax.Array | None = None,  # (n_pages, page, 1, D2)
    k_scales: jax.Array | None = None,   # (n_pages, page) f32 codec scales
    v_scales: jax.Array | None = None,   # (n_pages, page) f32 codec scales
    k2_scales: jax.Array | None = None,  # (n_pages, page) f32 codec scales
    *,
    window: int = 0,
    softcap_val: float = 0.0,
    scale: float = 1.0,
    q_block: int = 0,        # 0 = sized from the shapes (gqa_q_block /
    #                          latent_q_block); else gcd(Q, q_block), the
    #                          convention of flash_attention's q_chunk,
    #                          where the chip takes its rows
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
    """
    s_n, qn, h, d = q.shape
    n_pages, page, kh, dk = k_pages.shape
    latent = q2 is not None
    if latent:
        # MLA: one shared latent head, which is the value pool too
        assert kh == 1 and v_pages is None and v_scales is None, (kh,)
        assert not window and not softcap_val, (window, softcap_val)
        w2, d2 = q2.shape[-1], k2_pages.shape[-1]
        assert d2 == w2, (d2, w2)
        wv = d
    else:
        wv = v_pages.shape[-1]
        w2 = 0
    assert dk == d, (dk, d)
    assert h % kh == 0, (h, kh)
    g = h // kh
    if latent:
        qb = latent_q_block(qn, q_block, h, d, w2, page)
    else:
        qb = gqa_q_block(qn, q_block, g, kh, d, wv, page)
    rows = qb * g

    def walk(block):
        # grid step j reads physical page table[s, j]; Pallas pipelines
        # the next step's DMA behind this step's compute.  The table is
        # read as it stands: a clamp to the q block's live pages cost
        # 6-10 % of a call in scalar work on a v5e, more than the DMAs it
        # saved.
        return pl.BlockSpec(
            block, lambda s, qi, j, t, ln, ql: (t[s, j],)
            + (0,) * (len(block) - 1))

    def rows_spec(width):
        return pl.BlockSpec((1, kh, rows, width),
                            lambda s, qi, j, t, ln, ql: (s, 0, qi, 0))

    def by_kv_head(x):
        # (S, Q, H, w) -> (S, KH, Q * g, w): the g query heads of a KV
        # head become rows of one operand, token-major
        w = x.shape[-1]
        return x.reshape(s_n, qn, kh, g, w).transpose(0, 2, 1, 3, 4) \
            .reshape(s_n, kh, qn * g, w)

    in_specs = [rows_spec(d), walk((1, page, kh, d))]
    args = [by_kv_head(q), k_pages]
    if latent:
        in_specs += [rows_spec(w2), walk((1, page, 1, w2))]
        args += [by_kv_head(q2), k2_pages]
        scales = (k_scales, k2_scales)
    else:
        in_specs += [walk((1, page, kh, wv))]
        args += [v_pages]
        scales = (k_scales, v_scales)
    if k_scales is not None:
        # one scale column (page, 1) per physical page, walked through the
        # page table exactly like the pools themselves; a column scales
        # the decoded page's rows without an in-kernel transpose
        for sc in scales:
            in_specs += [walk((1, page, 1))]
            args += [sc.astype(jnp.float32).reshape(n_pages, page, 1)]
        in_specs += [pl.BlockSpec((1, kv_codec.ZERO_CODE),
                                  lambda s, qi, j, t, ln, ql: (0, 0))]
        args += [kv_codec.codebook()[None, kv_codec.ZERO_CODE:]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n, -(-qn // qb), table.shape[1]),
        in_specs=in_specs,
        out_specs=rows_spec(wv),
        scratch_shapes=[
            pltpu.VMEM((kh, rows, 1), jnp.float32),      # running max
            pltpu.VMEM((kh, rows, 1), jnp.float32),      # normaliser
            pltpu.VMEM((kh, rows, wv), jnp.float32),     # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, kh=kh, g=g, qb=qb,
                          window=window, softcap_val=softcap_val,
                          scale=scale, has_codec=k_scales is not None,
                          latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, kh, qn * g, wv), jnp.float32),
        name="paged_mixed_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        # what kernel_launches reads back from a traced program
        metadata={k: str(v) for k, v in Launch(
            qn=qn, qb=qb, n_pages=table.shape[1], logical=page,
            window=window)._asdict().items()},
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(q_lens, jnp.int32), *args)
    return out.reshape(s_n, kh, qn, g, wv).transpose(0, 2, 1, 3, 4).reshape(
        s_n, qn, h, wv)


def paged_decode_attention(
    q: jax.Array,            # (S, H, D)   this step's queries, one per slot
    k_pages: jax.Array,
    v_pages: jax.Array | None,
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
        interpret=interpret)
    return out[:, 0]
