"""Pallas TPU kernel: substream-parallel simplified-Huffman decode.

The TPU adaptation of the paper's *decoding unit* (DESIGN.md §2):

  * paper's input buffer  -> the (W, S) compressed tile streamed HBM->VMEM by
    the pallas grid pipeline (double-buffered DMA = the paper's "fetch while
    decoding" overlap);
  * paper's stream parser -> vectorised prefix classification on 128 lanes;
  * paper's banked 1 KB scratchpad -> the 160-entry decode table in VMEM;
  * serial bitstream -> S=128 independent substreams decoded in lockstep,
    the per-lane bit cursor being the only sequential state.

Per grid step we decode one tile: C codes x S substreams -> (C, S) int32
sequence values.  The variable-length chain is a ``fori_loop`` over C; all
work inside an iteration is lane-parallel.

Two table-gather strategies (perf-iteration subject, EXPERIMENTS.md §Perf):
  * ``gather="onehot"``   — 160-row one-hot select (paper-faithful indirection
                            table, baseline);
  * ``gather="bitplane"`` — bit-sliced LUT: the 160 entries are packed into a
                            (5, 9) uint32 bit-plane array; a 5-row one-hot +
                            9 shifts replaces the 160-row reduce (~3x fewer
                            VPU ops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

TABLE_SIZE = 160


def pack_bitplane_tables(tables_flat: np.ndarray) -> np.ndarray:
    """(160,) int32 -> (5, 9) uint32 bit-plane LUT.

    entry (g, j) packs bit (8-j) of table values for flat indices
    [32g, 32g+32): bit c of word (g, j) = tap j of table[32g + c].
    """
    t = np.asarray(tables_flat, dtype=np.uint32).reshape(5, 32)
    taps = np.arange(9)
    bits = (t[:, :, None] >> (8 - taps)[None, None, :]) & 1   # (5, 32, 9)
    shifts = np.arange(32, dtype=np.uint32)
    return (bits.transpose(0, 2, 1).astype(np.uint32)
            << shifts).sum(-1, dtype=np.uint32)               # (5, 9)


def decode_step(words, bitpos, tables, gather: str):
    """One lane-parallel decode step: (W, S) words + (S,) cursors ->
    (values (S,), new cursors (S,)).  Shared by this kernel and the fused
    decode+GEMM kernel."""
    w_rows = words.shape[0]
    word_idx = bitpos >> 5
    bit_off = bitpos & 31
    rows = jax.lax.broadcasted_iota(jnp.int32, (w_rows, words.shape[1]), 0)
    w0 = jnp.sum(jnp.where(rows == word_idx[None, :], words, 0),
                 axis=0, dtype=jnp.uint32)
    nidx = jnp.minimum(word_idx + 1, w_rows - 1)
    w1 = jnp.sum(jnp.where(rows == nidx[None, :], words, 0),
                 axis=0, dtype=jnp.uint32)
    off = bit_off.astype(jnp.uint32)
    lo = jnp.where(off > 0, w1 >> (32 - jnp.maximum(off, 1)), 0)
    window = ((w0 << off) | lo) >> 20                 # 12-bit peek
    top3 = window >> 9
    is0 = top3 < 4
    is1 = (top3 >> 1) == 2
    is2 = top3 == 6
    is3 = top3 == 7
    flat_idx = jnp.where(
        is0, (window >> 6) & 31,
        jnp.where(is1, 32 + ((window >> 4) & 63), 96 + ((window >> 3) & 63)),
    ).astype(jnp.int32)
    if gather == "onehot":
        tidx = jax.lax.broadcasted_iota(jnp.int32, (TABLE_SIZE, len(bitpos)), 0)
        tval = jnp.sum(
            jnp.where(tidx == flat_idx[None, :], tables[:, None], 0), axis=0)
    elif gather == "bitplane":
        g = flat_idx >> 5                              # (S,) in [0, 5)
        c = (flat_idx & 31).astype(jnp.uint32)
        grows = jax.lax.broadcasted_iota(jnp.int32, (5, len(bitpos)), 0)
        tval = jnp.zeros(len(bitpos), jnp.int32)
        for j in range(9):
            plane = jnp.sum(
                jnp.where(grows == g[None, :], tables[:, j][:, None], 0),
                axis=0, dtype=jnp.uint32)
            tval |= (((plane >> c) & 1) << (8 - j)).astype(jnp.int32)
    else:  # pragma: no cover
        raise ValueError(gather)
    val = jnp.where(is3, (window & 511).astype(jnp.int32), tval)
    length = jnp.where(is0, 6, jnp.where(is1, 8, jnp.where(is2, 9, 12)))
    return val, bitpos + length.astype(jnp.int32)


def _kernel(words_ref, tables_ref, out_ref, *, c: int, gather: str):
    words = words_ref[0]                               # (W, S)
    tables = tables_ref[...] if gather == "bitplane" else tables_ref[0]

    def body(ci, bitpos):
        val, bitpos = decode_step(words, bitpos, tables, gather)
        out_ref[pl.ds(0, 1), pl.ds(ci, 1), :] = val[None, None, :]
        return bitpos

    jax.lax.fori_loop(0, c, body, jnp.zeros(words.shape[1], jnp.int32))


@functools.partial(jax.jit, static_argnames=("c", "gather", "interpret"))
def huffman_decode(
    words: jax.Array,        # (T, W, S) uint32 tiled compressed stream
    tables: jax.Array,       # (160,) int32  |  (5, 9) uint32 bit-plane LUT
    *,
    c: int,                  # codes per substream per tile
    gather: str = "onehot",
    interpret: bool = False,
) -> jax.Array:
    """Decode the tiled stream -> (T, C, S) int32 sequence values."""
    t, w, s = words.shape
    if gather == "bitplane":
        tables = tables.astype(jnp.uint32).reshape(5, 9)
        tspec = pl.BlockSpec((5, 9), lambda ti: (0, 0))
    else:
        tables = tables.astype(jnp.int32).reshape(1, TABLE_SIZE)
        tspec = pl.BlockSpec((1, TABLE_SIZE), lambda ti: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, c=c, gather=gather),
        grid=(t,),
        in_specs=[pl.BlockSpec((1, w, s), lambda ti: (ti, 0, 0)), tspec],
        out_specs=pl.BlockSpec((1, c, s), lambda ti: (ti, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, c, s), jnp.int32),
        interpret=interpret,
    )(words, tables)
