"""Pallas TPU kernel: xnor + popcount binary GEMM over packed words.

This is the uncompressed-weights baseline path (paper's daBnn analogue):
both operands are channel-packed uint32 words; the contraction is

    out[m, n] = 2 * (popcount(xnor(x[m, :], w[n, :])) - pad_bits) - k_true

Grid is (M/bm, N/bn, K/ck) with a VMEM int32 accumulator carried across the
innermost (arbitrary) K dimension; the +-1 correction is applied on the last
K step.  All VPU work — the MXU never sees the 1-bit operands, which is the
point: 32x fewer HBM/VMEM bytes per MAC than bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_CK = 128   # uint32 words per K step (= 4096 binary MACs / output)


def _kernel(x_ref, w_ref, out_ref, acc_ref, *, nk: int, k_true: int,
            total_bits: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # (bm, ck) uint32
    w = w_ref[...]                                  # (bn, ck) uint32
    xnor = ~(x[:, None, :] ^ w[None, :, :])         # (bm, bn, ck)
    acc_ref[...] += jax.lax.population_count(xnor).sum(-1).astype(jnp.int32)

    @pl.when(kb == nk - 1)
    def _done():
        n_pad = total_bits - k_true
        out_ref[...] = 2 * (acc_ref[...] - n_pad) - k_true


@functools.partial(jax.jit, static_argnames=("k_true", "bm", "bn", "ck",
                                             "interpret"))
def binary_contraction(
    x_words: jax.Array,          # (M, KW) uint32  (flattened (G, 9))
    w_words: jax.Array,          # (N, KW) uint32
    *,
    k_true: int,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    ck: int = DEFAULT_CK,
    interpret: bool = False,
) -> jax.Array:
    m, kw = x_words.shape
    n, kw2 = w_words.shape
    assert kw == kw2, (kw, kw2)
    bm, bn, ck = min(bm, m), min(bn, n), min(ck, kw)
    # pad every dim to a block multiple (zero words are corrected as pad bits)
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, -(-kw // ck) * ck
    x_words = jnp.pad(x_words, ((0, mp - m), (0, kp - kw)))
    w_words = jnp.pad(w_words, ((0, np_ - n), (0, kp - kw)))
    nk = kp // ck
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, k_true=k_true, total_bits=kp * 32),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, ck), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bn, ck), lambda mi, ni, ki: (ni, ki)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_words, w_words)
    return out[:m, :n]
