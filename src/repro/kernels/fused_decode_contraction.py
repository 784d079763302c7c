"""Pallas TPU kernel: fused Huffman-decode + xnor/popcount GEMM.

The end-to-end analogue of the paper's hardware pipeline: compressed weights
stream HBM->VMEM, are decoded and channel-packed on the fly (the *decoding
unit*), and feed the binary contraction (the xnor/popcount datapath) without
ever materialising uncompressed weights in HBM.  The HBM weight traffic is
therefore ``1/ratio_tiled`` of the baseline kernel's — this is the paper's
1.35x speedup mechanism expressed as a roofline memory-term reduction.

Compressed layout (``repro.core.compression.compress_gemm_fused``):
  * weight sequences (N, G) are re-ordered into (NB, GB, 32, 32) blocks —
    32 N-rows x 32 sequences (= one 288-bit K block);
  * each (nb, gb) block is one decode tile: 1024 sequences over S=128
    substreams x C=8 codes;
  * words: (NB, GB, W, S) uint32.

Grid = (NB, MB, GB) with GB innermost: the (bm, 32) accumulator lives in
VMEM scratch across the K sweep; weights are decoded once per grid step and
consumed immediately from VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.huffman_decode import TABLE_SIZE, decode_step

SUB = 128         # substreams
DEFAULT_CODES = 8  # codes per substream per tile; N rows per tile = 4*codes


def _kernel(words_ref, x_ref, tables_ref, out_ref, acc_ref, w_scratch,
            *, ngb: int, k_true: int, total_bits: int, gather: str,
            codes: int):
    bn = 4 * codes
    gb = pl.program_id(2)

    @pl.when(gb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- decode unit: one tile -> bn rows x one K-block of packed words ---
    words = words_ref[0, 0]                         # (W, S)
    tables = tables_ref[...] if gather == "bitplane" else tables_ref[0]

    def body(ci, bitpos):
        val, bitpos = decode_step(words, bitpos, tables, gather)
        w_scratch[pl.ds(ci, 1), :] = val[None, :]
        return bitpos

    jax.lax.fori_loop(0, codes, body, jnp.zeros(SUB, jnp.int32))

    # ---- packing unit: (C, S) sequences -> (bn rows, 9 taps) uint32 -------
    seqs = w_scratch[...].reshape(bn, 32).astype(jnp.uint32)  # row-major tile
    lane = jnp.arange(32, dtype=jnp.uint32)[None, :]
    w_words = []
    for j in range(9):                              # tap j, MSB-first
        bit_j = (seqs >> (8 - j)) & 1
        w_words.append((bit_j << lane).sum(-1, dtype=jnp.uint32))
    w_packed = jnp.stack(w_words, axis=-1)          # (32, 9)

    # ---- xnor/popcount contraction ----------------------------------------
    x = x_ref[:, 0, :]                              # (bm, 9) uint32
    xnor = ~(x[:, None, :] ^ w_packed[None, :, :])  # (bm, bn, 9)
    acc_ref[...] += jax.lax.population_count(xnor).sum(-1).astype(jnp.int32)

    @pl.when(gb == ngb - 1)
    def _done():
        n_pad = total_bits - k_true
        out_ref[...] = 2 * (acc_ref[...] - n_pad) - k_true


@functools.partial(jax.jit, static_argnames=("k_true", "n_true", "bm",
                                             "gather", "codes", "interpret"))
def fused_decode_matmul(
    words: jax.Array,       # (NB, GB, W, S) uint32 compressed weights
    x_words: jax.Array,     # (M, G, 9) uint32 packed activations
    tables: jax.Array,      # (160,) int32 | (5, 9) uint32 bit-plane LUT
    *,
    k_true: int,
    n_true: int,
    bm: int = 256,
    gather: str = "onehot",
    codes: int = DEFAULT_CODES,
    interpret: bool = False,
) -> jax.Array:
    """out (M, n_true) int32 = packed x  .  decoded(words) with +-1 semantics.

    ``codes`` must match the layout's codes_per_sub (tile = 4*codes N-rows).
    """
    bn = 4 * codes
    nb, ngb, w, s = words.shape
    m, g, nine = x_words.shape
    assert s == SUB and nine == 9, (s, nine)
    assert g == ngb, f"activation K blocks {g} != weight tiles {ngb}"
    bm = min(bm, m)
    mp = -(-m // bm) * bm
    x_words = jnp.pad(x_words, ((0, mp - m), (0, 0), (0, 0)))
    if gather == "bitplane":
        tables = tables.astype(jnp.uint32).reshape(5, 9)
        tspec = pl.BlockSpec((5, 9), lambda ni, mi, gi: (0, 0))
    else:
        tables = tables.astype(jnp.int32).reshape(1, TABLE_SIZE)
        tspec = pl.BlockSpec((1, TABLE_SIZE), lambda ni, mi, gi: (0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, ngb=ngb, k_true=k_true,
                          total_bits=ngb * 288, gather=gather, codes=codes),
        grid=(nb, mp // bm, ngb),
        in_specs=[
            pl.BlockSpec((1, 1, w, s), lambda ni, mi, gi: (ni, gi, 0, 0)),
            pl.BlockSpec((bm, 1, 9), lambda ni, mi, gi: (mi, gi, 0)),
            tspec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda ni, mi, gi: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, nb * bn), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),
            pltpu.VMEM((codes, SUB), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(words, x_words, tables)
    return out[:m, :n_true]
