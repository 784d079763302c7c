"""KV-page codec: codebook quantization + Huffman archive for paged KV.

The paper compresses binary-weight kernels by exploiting a skewed
bit-sequence distribution: frequent sequences get short Huffman codes
and are decoded through a tiny cache (PAPER SectionIII-IV).  At serving
time the paged KV pool is the activation-side analogue — every slot's
K/V pages pay full fp bytes per token even though per-token value
distributions are heavily concentrated around zero.

This module is the single source of truth for the ``kv_codec`` seam:

* ``"none"``   — pages stay in the model dtype; bit-exact oracle.
* ``"cluster"``— page contents are clustered onto a 256-entry codebook
  (symmetric int8 levels) with one f32 scale per (slot, token); pages
  are stored as int8 codes at rest and decoded *in-kernel* by
  ``kernels.paged_attention`` (codebook lookup in VMEM after the
  per-page DMA, before the online-softmax score) — the same shape as
  ``kernels.fused_decode_contraction``'s weight-tile decode.

On top of the resident int8 pool, :func:`huffman_report` /
:func:`archive_pages` reuse ``core.huffman`` + ``core.clustering`` to
measure and build the at-rest Huffman stream for cold pages (codes live
in the same <512-symbol space the paper's coder was built for).

Design constraints the codec satisfies:

* codebook[ZERO] == 0 exactly, so all-zero pages (the page-0 dummy
  sink) encode to code 0 / scale 0 and decode back to exactly zero.
* encode∘decode is idempotent: the amax element maps to ±MAX_CODE, so
  re-encoding a decoded page recovers the same scale and codes.  The
  gathered backend relies on this — it re-encodes whole views on every
  scatter.
* reconstruction error is elementwise-bounded by ``scale / 254``
  (half a quantization step of the per-token scale).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

KV_CODECS = ("none", "cluster")

LEVELS = 256            # codebook entries == int8 code space
ZERO_CODE = LEVELS // 2  # codebook index of code 0 (decodes to exactly 0.0)
MAX_CODE = LEVELS // 2 - 1  # 127: symmetric clip range for codes


def codebook() -> jnp.ndarray:
    """``(LEVELS,)`` f32 centroids in units of the per-token scale.

    ``codebook()[code + ZERO_CODE] == code / MAX_CODE`` for int8
    ``code`` in ``[-MAX_CODE, MAX_CODE]``; entry ``ZERO_CODE`` is 0.0,
    so zero codes decode to zero regardless of scale.
    """
    return jnp.asarray(_CODEBOOK)


# computed once in numpy: under jit, XLA rewrites a division by the
# constant MAX_CODE into a multiplication by its rounded reciprocal, which
# moves 16 of the 256 entries by one ulp -- the table must not depend on
# where it is built
_CODEBOOK = (np.arange(LEVELS, dtype=np.float32) - ZERO_CODE) \
    / np.float32(MAX_CODE)


def encode(values, axes):
    """Quantize ``values`` onto the codebook.

    ``axes`` are the feature axes reduced into one amax scale per
    remaining (slot, token) index.  Returns ``(codes, scale)`` where
    ``codes`` is int8 with ``values.shape`` and ``scale`` is f32 with
    ``axes`` squeezed out.  All-zero tokens get scale 0 and code 0.
    """
    v = jnp.asarray(values, jnp.float32)
    axes = tuple(ax % v.ndim for ax in axes)
    scale = jnp.max(jnp.abs(v), axis=axes, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round(v / safe * MAX_CODE), -MAX_CODE, MAX_CODE)
    return codes.astype(jnp.int8), jnp.squeeze(scale, axis=axes)


def decode(codes, scale):
    """Inverse of :func:`encode`: ``codebook[codes + ZERO_CODE] * scale``.

    ``scale`` must already broadcast against ``codes`` (callers expand
    the squeezed feature axes back).
    """
    vals = codebook()[jnp.asarray(codes, jnp.int32) + ZERO_CODE]
    return vals * jnp.asarray(scale, jnp.float32)


def error_bound(scale):
    """Elementwise bound: ``|decode(encode(v)) - v| <= scale / (2*MAX_CODE)``."""
    return jnp.asarray(scale, jnp.float32) / (2 * MAX_CODE)


# ---------------------------------------------------------------------------
# At-rest Huffman layer (host-side, exact) — reuses the paper's coder.
# ---------------------------------------------------------------------------

def huffman_report(codes) -> dict:
    """Entropy report of an int8 code pool through the paper's coder.

    Histograms ``codes + ZERO_CODE`` (all < 512, i.e. inside the
    ``core.bitpack`` sequence space), assigns node-limited Huffman
    codes, and also measures what Hamming-1 clustering
    (``core.clustering.apply_clustering``) would add.  The clustered
    ratio is a *report only* — the resident pool keeps raw int8 codes;
    only the exact (non-clustered) stream is used by
    :func:`archive_pages`.
    """
    from repro.core.bitpack import NUM_SEQUENCES
    from repro.core.clustering import apply_clustering
    from repro.core.huffman import assign_nodes

    flat = np.asarray(codes).ravel().astype(np.int64) + ZERO_CODE
    hist = np.bincount(flat, minlength=NUM_SEQUENCES).astype(np.int64)
    assign = assign_nodes(hist)
    avg = assign.avg_bits(hist)
    clustered, _ = apply_clustering(flat, hist=hist)
    chist = np.bincount(np.asarray(clustered, np.int64),
                        minlength=NUM_SEQUENCES).astype(np.int64)
    cavg = assign_nodes(chist).avg_bits(chist)
    return {
        "symbols": int(flat.size),
        "avg_bits": float(avg),
        "ratio": (8.0 / avg) if avg else float("inf"),
        "clustered_avg_bits": float(cavg),
        "clustered_ratio": (8.0 / cavg) if cavg else float("inf"),
    }


def archive_pages(codes):
    """Huffman-encode int8 codes into an exact uint32 bit stream.

    Returns ``(words, nbits, assign)`` suitable for
    :func:`restore_pages`; the stream is lossless (no clustering).
    """
    from repro.core.bitpack import NUM_SEQUENCES
    from repro.core.huffman import assign_nodes, encode_stream

    flat = np.asarray(codes).ravel().astype(np.int64) + ZERO_CODE
    hist = np.bincount(flat, minlength=NUM_SEQUENCES).astype(np.int64)
    assign = assign_nodes(hist)
    words, nbits = encode_stream(flat, assign)
    return words, nbits, assign


def restore_pages(words, nbits, assign, shape):
    """Exact inverse of :func:`archive_pages` back to int8 codes."""
    from repro.core.huffman import decode_stream

    seqs = decode_stream(words, nbits, assign,
                         count=int(np.prod(shape)) if shape else 1)
    return (np.asarray(seqs, np.int64) - ZERO_CODE).astype(np.int8) \
        .reshape(shape)
