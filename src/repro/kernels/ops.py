"""Public jit'd wrappers around the Pallas kernels.

Every op has a pure-jnp oracle in :mod:`repro.kernels.ref`; the wrappers
take interpret mode from :func:`repro.kernels.platform.interpret_mode`
(interpreted on CPU, compiled on TPU) so the same call sites run on both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression
from repro.kernels import ref
from repro.kernels.binarize_pack import binarize_pack as binarize_pack_kernel
from repro.kernels.binary_contraction import binary_contraction
from repro.kernels.fused_decode_contraction import fused_decode_matmul
from repro.kernels.huffman_decode import huffman_decode, pack_bitplane_tables
from repro.kernels.platform import interpret_mode


def _interpret(flag: bool | None) -> bool:
    return interpret_mode() if flag is None else flag


def binarize_pack(x: jax.Array, *, use_kernel: bool = False,
                  interpret: bool | None = None) -> jax.Array:
    """(M, K) real -> (M, G, 9) packed sign bits; Pallas kernel on TPU."""
    if use_kernel:
        return binarize_pack_kernel(x, interpret=_interpret(interpret))
    return ref.binarize_pack(x)


# ---------------------------------------------------------------------------
# binary matmul (uncompressed baseline path)
# ---------------------------------------------------------------------------

def binary_matmul_packed(
    x_words: jax.Array,       # (M, G, 9) uint32
    w_words: jax.Array,       # (N, G, 9) uint32
    k_true: int,
    *,
    interpret: bool | None = None,
    **block_kw,
) -> jax.Array:
    """(M, N) int32 +-1 dot of packed operands."""
    xw = x_words.reshape(x_words.shape[0], -1)
    ww = w_words.reshape(w_words.shape[0], -1)
    return binary_contraction(
        xw, ww, k_true=k_true, interpret=_interpret(interpret), **block_kw)


def binary_matmul(
    x: jax.Array,             # (M, K) real
    w: jax.Array,             # (N, K) real latent weights
    *,
    interpret: bool | None = None,
    **block_kw,
) -> jax.Array:
    """sign(x) @ sign(w).T via the packed xnor/popcount kernel -> (M, N) f32."""
    k = x.shape[-1]
    xw = ref.binarize_pack(x)
    ww = ref.binarize_pack(w)
    return binary_matmul_packed(
        xw, ww, k, interpret=interpret, **block_kw).astype(jnp.float32)


# ---------------------------------------------------------------------------
# compressed path (paper's contribution)
# ---------------------------------------------------------------------------

def compressed_binary_matmul(
    x: jax.Array,                       # (M, K) real
    words: jax.Array,                   # (NB, GB, W, S) uint32
    tables: jax.Array,                  # (160,) | (5, 9) bit-plane
    *,
    k_true: int,
    n_true: int,
    gather: str = "onehot",
    interpret: bool | None = None,
    **block_kw,
) -> jax.Array:
    """sign(x) @ decoded-weights.T, decoding fused into the GEMM."""
    xw = ref.binarize_pack(x)
    return fused_decode_matmul(
        words, xw, tables, k_true=k_true, n_true=n_true, gather=gather,
        interpret=_interpret(interpret), **block_kw).astype(jnp.float32)


def decode_sequences(
    words: jax.Array, tables: jax.Array, *, c: int, n_seqs: int,
    gather: str = "onehot", interpret: bool | None = None,
) -> jax.Array:
    """Standalone decode: tiled stream -> flat (n_seqs,) int32 sequences."""
    out = huffman_decode(words, tables, c=c, gather=gather,
                         interpret=_interpret(interpret))
    return ref.tiled_to_sequences(out, n_seqs)


# ---------------------------------------------------------------------------
# 3x3 BNN convolution (im2col + contraction)
# ---------------------------------------------------------------------------

def _im2col_bits(x: jax.Array, stride: int) -> tuple[jax.Array, tuple[int, ...]]:
    """NHWC real -> ((N*Ho*Wo, Cin*9) {0,1} bits, out spatial shape).

    Zero bits encode -1, so SAME zero-padding of the *bit* tensor implements
    the BNN's -1 padding exactly (ref.binary_conv3x3 semantics).
    """
    n, h, w, cin = x.shape
    bits = (x >= 0).astype(jnp.float32)
    patches = jax.lax.conv_general_dilated_patches(
        bits, (3, 3), (stride, stride), padding=((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    ho, wo = patches.shape[1], patches.shape[2]
    return patches.reshape(n * ho * wo, cin * 9), (n, ho, wo)


def binary_conv3x3(
    x: jax.Array,             # (N, H, W, Cin) real
    w: jax.Array,             # (Cout, Cin, 3, 3) real latent weights
    *,
    stride: int = 1,
    interpret: bool | None = None,
    **block_kw,
) -> jax.Array:
    """BNN 3x3 conv via im2col + packed contraction -> (N, Ho, Wo, Cout) f32."""
    cout, cin = w.shape[:2]
    cols, (n, ho, wo) = _im2col_bits(x, stride)
    xw = ref.pack_bits_runtime(cols.astype(jnp.uint32))
    w_bits = (w >= 0).astype(jnp.uint32).reshape(cout, cin * 9)
    ww = ref.pack_bits_runtime(w_bits)
    out = binary_matmul_packed(xw, ww, cin * 9, interpret=interpret, **block_kw)
    return out.reshape(n, ho, wo, cout).astype(jnp.float32)


def compressed_binary_conv3x3(
    x: jax.Array,                       # (N, H, W, Cin) real
    words: jax.Array,                   # fused layout of (Cout, Cin*9) bits
    tables: jax.Array,
    *,
    cin: int,
    cout: int,
    stride: int = 1,
    gather: str = "onehot",
    interpret: bool | None = None,
    **block_kw,
) -> jax.Array:
    """BNN 3x3 conv with weights Huffman-decoded inside the GEMM kernel."""
    cols, (n, ho, wo) = _im2col_bits(x, stride)
    xw = ref.pack_bits_runtime(cols.astype(jnp.uint32))
    out = fused_decode_matmul(
        words, xw, tables, k_true=cin * 9, n_true=cout, gather=gather,
        interpret=_interpret(interpret), **block_kw)
    return out.reshape(n, ho, wo, cout).astype(jnp.float32)


# ---------------------------------------------------------------------------
# offline helpers: numpy weights -> device arrays for the compressed path
# ---------------------------------------------------------------------------

def prepare_compressed_gemm(w_bits: np.ndarray, cluster: bool = True,
                            gather: str = "onehot", codes: int = 8):
    """(N, K) {0,1} -> (words, tables, meta dict) ready for the fused kernel."""
    fc = compression.compress_gemm_fused(w_bits, cluster=cluster,
                                         codes_per_sub=codes)
    tables = fc.ct.decode_tables()
    if gather == "bitplane":
        tables = pack_bitplane_tables(tables)
    return (jnp.asarray(fc.words), jnp.asarray(tables),
            dict(k_true=fc.k_true, n_true=fc.n_true, codes=codes,
                 ratio_stream=fc.ct.ratio_stream(),
                 ratio_tiled=fc.ratio_tiled()))


def prepare_compressed_conv(w_bits: np.ndarray, cluster: bool = True,
                            gather: str = "onehot", codes: int = 8):
    """(Cout, Cin, 3, 3) {0,1} -> fused-kernel operands (GEMM view)."""
    cout, cin = w_bits.shape[:2]
    return prepare_compressed_gemm(
        w_bits.reshape(cout, cin * 9), cluster=cluster, gather=gather,
        codes=codes)
