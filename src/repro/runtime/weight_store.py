"""Multi-model registry of compressed binary weights.

Storage follows the paper's DRAM layout: each registered tensor is held as
one contiguous varlen Huffman *stream* (``core.compression`` stream layout —
the layout the compression-ratio tables measure).  The TPU-native *tiled*
layout (substream-parallel (W, S) blocks) is materialised lazily, per
layer, on first use — the runtime analogue of the paper's fetch unit
re-blocking DRAM words for the decoder.

Serving paths offered per registered layer:

  * :meth:`materialize` — rebuild the model's parameter pytree with every
    compressed tensor reconstructed as sign * per-channel-scale.  Tiles are
    fetched through the DecodeTileCache, so consecutive decode steps reuse
    decoded tiles instead of re-decoding (the acceptance metric of PR 1).
    The assembled device array is memoised and only rebuilt when at least
    one of its tiles missed the cache.
  * :meth:`fused_operands` — device operands (words, tables, meta) for the
    fused decode+GEMM Pallas path (``kernels.ops.compressed_binary_matmul``),
    built from the *same* cached tiles so both paths are bit-identical.

Two frequency-path features ride on the tile fetch:

  * **prior seeding** — at first tiling, each tile's share of the layer's
    sequence-occurrence mass (``core.frequency`` histogram, the paper's
    §III-A skew) is pushed into the decode cache via ``seed_frequency`` so
    the FrequencyWeighted eviction policy can rank tiles before any access
    history exists;
  * **async prefetch** — while one layer's tiles are being reconstructed on
    the host, the *next* layer's missing tiles are already dispatched to the
    device decoder (jax async dispatch), so the device decode of layer i+1
    overlaps the host bit-unpack of layer i (the runtime analogue of the
    paper's fetch unit running ahead of the compute pipeline).  Prefetch
    changes latency only — hit/miss accounting and the decoded bits are
    identical with it on or off.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack, compression, frequency, huffman
from repro.dist.sharding import path_name
from repro.kernels import ref
from repro.kernels.huffman_decode import pack_bitplane_tables
from repro.runtime.decode_cache import DecodeTileCache
from repro.runtime.telemetry import NULL_TELEMETRY

# serving tiles reuse the offline layout default (C=8 -> 1024 sequences/
# tile); the tile is also the cache's eviction granularity
DEFAULT_CODES_PER_SUB = compression.DEFAULT_CODES_PER_SUB
# tiles decoded per device call: a miss decodes the fixed-size batch of
# consecutive tiles around it, so a full-width layer (thousands of tiles)
# costs a handful of dispatches instead of one per tile
DECODE_BATCH = 256


def default_select(path: str, ndim: int) -> bool:
    """Default compression predicate: MLP projection matrices."""
    parts = path.split("/")
    return ndim >= 2 and parts[-1] in ("up", "gate", "down") \
        and "mlp" in parts[:-1]


@dataclasses.dataclass
class StoredLayer:
    """One compressed (N, K) binary tensor + its dequantisation scale."""

    name: str
    ct: compression.CompressedTensor      # stream layout (tiled=None)
    scale: np.ndarray                     # (N,) per-output-channel alpha
    n: int                                # output channels (rows of bits)
    k: int                                # true contraction length
    dtype: np.dtype
    # sequences the stream encodes, kept until first use re-tiles them
    # (the scalar stream decoder is the tests' oracle, far too slow for a
    # full-width layer)
    seqs: np.ndarray | None = None
    # lazily materialised state
    tiled: compression.TiledStream | None = None
    tables: np.ndarray | None = None
    tile_freq: np.ndarray | None = None   # per-tile occurrence mass
    freq_seeded: bool = False

    def ensure_tiled(self) -> compression.TiledStream:
        """First-use re-tiling: stream sequences -> substream-parallel
        layout."""
        if self.tiled is None:
            seqs, self.seqs = self.seqs, None
            self.tiled = compression.tile_stream(seqs, self.ct.assign)
            self.tables = self.ct.decode_tables()
            # per-tile frequency mass: how much of the layer's skewed
            # sequence-occurrence histogram (paper §III-A) each decode tile
            # carries -> static prior for FrequencyWeighted eviction.  Tail
            # padding indexes a zero sentinel bin so pad slots add no mass
            # (index 0 is the all-(-1) sequence, typically the hottest bin).
            hist = np.append(frequency.sequence_histogram(seqs), 0)
            per_tile = self.tiled.c * self.tiled.s
            padded = np.full(self.tiled.n_tiles * per_tile,
                             hist.size - 1, np.int64)
            padded[: seqs.size] = seqs
            self.tile_freq = hist[padded.reshape(
                self.tiled.n_tiles, per_tile)].sum(axis=1)
        return self.tiled

    def tile_compressed_bytes(self) -> int:
        ts = self.ensure_tiled()
        return ts.w * ts.s * 4            # uint32 words per tile

    def stream_bytes(self) -> int:
        return int(self.ct.stream_words.size * 4)

    def packed_bytes(self) -> int:
        """9-bit channel-packed baseline footprint (paper's reference)."""
        return self.ct.n_seqs * huffman.SEQ_BITS // 8


@dataclasses.dataclass
class _ModelEntry:
    params: dict
    layers: dict[str, list[StoredLayer]]  # tree path -> per-repeat layers
    stacked: dict[str, bool]              # tree path -> 3-d scan-stacked leaf
    memo: dict = dataclasses.field(default_factory=dict)
    fused_memo: dict = dataclasses.field(default_factory=dict)


@functools.partial(jax.jit, static_argnames=("c",))
def _decode_tiles_jit(words, tables, c):
    return ref.decode_tiled(words, tables, c)


def _decode_batch(ts: compression.TiledStream, tables, t: int):
    """Dispatch the device decode of the ``DECODE_BATCH`` tiles starting at
    tile ``t`` (indices past the end repeat the last tile) -> (T, C, S)
    int32 future."""
    idx = np.minimum(np.arange(t, t + DECODE_BATCH), ts.n_tiles - 1)
    return _decode_tiles_jit(jnp.asarray(ts.words[idx]), tables, ts.c)


class WeightStore:
    """Registry: model id -> compressed layers, served through one cache.

    ``prefetch=True`` dispatches the next layer's missing tile decodes to
    the device while the current layer's tiles are reconstructed on the
    host (async tile prefetch; bit-identical results either way).
    """

    def __init__(self, cache: DecodeTileCache | None = None, *,
                 prefetch: bool = False, telemetry=None):
        self.cache = cache if cache is not None else DecodeTileCache()
        self.prefetch = prefetch
        self.prefetch_dispatched = 0
        self.prefetch_used = 0
        self.telemetry = telemetry if telemetry is not None \
            else NULL_TELEMETRY
        self._models: dict[str, _ModelEntry] = {}

    # -- registration ------------------------------------------------------
    def register_model(self, model_id: str, params, *,
                       select: Callable[[str, int], bool] = default_select,
                       cluster: bool = False) -> dict:
        """Compress every selected weight of ``params`` into the store.

        Selected 2-d leaves (d_in, d_out) are binarised in the BNN layer
        convention (``layers.binary_linear``): bits of w.T with per-output
        -channel scale mean|w|.  3-d leaves are treated as scan-stacked
        (R, d_in, d_out) and registered per repeat so each repeat owns its
        tiles.  Returns a summary dict (layer count, byte footprints).
        """
        if model_id in self._models:
            raise ValueError(f"model {model_id!r} already registered")
        layers: dict[str, list[StoredLayer]] = {}
        stacked: dict[str, bool] = {}

        def visit(path, leaf):
            name = path_name(path)
            if not select(name, getattr(leaf, "ndim", 0)):
                return leaf
            w = np.asarray(leaf)
            if w.ndim == 2:
                stack = w[None]
            elif w.ndim == 3:
                stack = w
            else:
                return leaf
            layers[name] = [
                self._compress_tensor(f"{name}[{r}]", stack[r],
                                      cluster=cluster)
                for r in range(stack.shape[0])]
            stacked[name] = w.ndim == 3
            # the uncompressed original is NOT retained: only its
            # shape/dtype stub stays in the serving tree skeleton
            return jax.ShapeDtypeStruct(w.shape, w.dtype)

        skeleton = jax.tree_util.tree_map_with_path(visit, params)
        if not layers:
            raise ValueError("no weights matched the compression predicate")
        self._models[model_id] = _ModelEntry(params=skeleton, layers=layers,
                                             stacked=stacked)
        return self.report(model_id)

    def _compress_tensor(self, name: str, w2: np.ndarray, *,
                         cluster: bool) -> StoredLayer:
        wt = np.ascontiguousarray(w2.T)                # (N=d_out, K=d_in)
        scale = np.abs(wt).mean(axis=1)                # binarize_weights alpha
        bits = (wt >= 0).astype(np.uint8)
        seqs = bitpack.gemm_to_sequences(bits)
        ct = compression.compress_sequences(seqs, bits.shape, "gemm",
                                            cluster=cluster, tiled=False)
        if ct.replacement is not None:       # the clustered sequences
            seqs = ct.replacement[seqs]
        return StoredLayer(name=name, ct=ct, scale=scale,
                           n=wt.shape[0], k=wt.shape[1], dtype=w2.dtype,
                           seqs=seqs.ravel())

    # -- tile-level serving ------------------------------------------------
    def _seed_layer(self, model_id: str, layer: StoredLayer) -> None:
        """Push the layer's per-tile occurrence mass into the cache policy
        (once) so FrequencyWeighted eviction can rank its tiles."""
        if layer.freq_seeded:
            return
        for t in range(layer.tiled.n_tiles):
            self.cache.seed_frequency((model_id, layer.name, t),
                                      float(layer.tile_freq[t]))
        layer.freq_seeded = True

    def _prefetch_layer(self, model_id: str, layer: StoredLayer,
                        pending: dict) -> None:
        """Dispatch device decodes for the layer's missing tiles without
        blocking (jax async dispatch); results land in ``pending``."""
        ts = layer.ensure_tiled()
        missing = [t for t in range(ts.n_tiles)
                   if (model_id, layer.name, t) not in self.cache
                   and (model_id, layer.name, t) not in pending]
        if not missing:
            return                      # steady state: stay off the device
        with self.telemetry.timed("weights.prefetch", layer=layer.name,
                                  tiles=len(missing)):
            tables = jnp.asarray(layer.tables)
            batches: dict = {}
            for t in missing:
                b = t - t % DECODE_BATCH
                if b not in batches:
                    batches[b] = _decode_batch(ts, tables, b)
                pending[(model_id, layer.name, t)] = (batches[b], t - b)
                self.prefetch_dispatched += 1

    def _fetch_tiles(self, model_id: str, layer: StoredLayer,
                     pending: dict | None = None) -> tuple[list, bool]:
        """All decode tiles of one layer via the cache ->
        (tiles [(C, S) int32], any_tile_missed).

        A miss consumes the prefetched in-flight decode when one exists
        (same accounting as a direct decode: the stream bytes were spent)."""
        ts = layer.ensure_tiled()
        self._seed_layer(model_id, layer)
        comp_bytes = layer.tile_compressed_bytes()
        tiles = []
        any_miss = False
        decoded: dict = {}          # batch start -> host (T, C, S) tiles
        for t in range(ts.n_tiles):
            key = (model_id, layer.name, t)
            tile = self.cache.get(key)
            if tile is None:
                fut = pending.pop(key, None) if pending else None
                if fut is not None:
                    self.prefetch_used += 1
                    tile = np.asarray(fut[0])[fut[1]]
                else:
                    b = t - t % DECODE_BATCH
                    if b not in decoded:
                        with self.telemetry.timed("weights.decode_tile"):
                            decoded[b] = np.asarray(_decode_batch(
                                ts, jnp.asarray(layer.tables), b))
                    tile = decoded[b][t - b]
                self.cache.put(key, tile, streamed_bytes=comp_bytes)
                any_miss = True
            tiles.append(tile)
        return tiles, any_miss

    def _fetch_sequences(self, model_id: str, layer: StoredLayer
                         ) -> tuple[np.ndarray, bool]:
        """(flat (n_seqs,) int32 in original order, any_tile_missed)."""
        tiles, any_miss = self._fetch_tiles(model_id, layer)
        flat = np.stack(tiles).reshape(-1)[: layer.ct.n_seqs]
        return flat, any_miss

    def _to_weights(self, layer: StoredLayer, tiles: list) -> np.ndarray:
        """Cached tiles -> (d_in, d_out) real tensor sign * alpha."""
        seqs = np.stack(tiles).reshape(-1)[: layer.ct.n_seqs]
        bits = bitpack.sequences_to_gemm(
            seqs.astype(np.uint16).reshape(layer.ct.seq_shape), layer.k)
        w = (bits.astype(np.float32) * 2.0 - 1.0) * layer.scale[:, None]
        return w.T.astype(layer.dtype)

    # -- model-level serving ----------------------------------------------
    def materialize(self, model_id: str):
        """Serving params: compressed leaves rebuilt from cached tiles.

        Call once per decode step; after the first step every tile is a
        cache hit and the memoised device arrays are returned as-is (the
        hit path only touches the cache for accounting — no bit unpack,
        reconstruction, or host->device transfer is repeated).

        Layers are processed in registration order; with ``prefetch`` on,
        layer i+1's missing tile decodes are dispatched right after layer
        i's tiles are fetched, so they run on-device while layer i's
        weights are reconstructed host-side.
        """
        entry = self._models[model_id]
        names = list(entry.layers)
        pending: dict = {}
        rebuilt: dict = {}
        with self.telemetry.timed("weights.materialize", model=model_id):
            for i, name in enumerate(names):
                stack = entry.layers[name]
                fetched = [self._fetch_tiles(model_id, l, pending)
                           for l in stack]
                if self.prefetch and i + 1 < len(names):
                    for nxt in entry.layers[names[i + 1]]:
                        self._prefetch_layer(model_id, nxt, pending)
                if all(not miss for _, miss in fetched) \
                        and name in entry.memo:
                    rebuilt[name] = entry.memo[name]
                    continue
                arrs = [self._to_weights(l, tiles)
                        for l, (tiles, _) in zip(stack, fetched)]
                out = jnp.asarray(np.stack(arrs) if entry.stacked[name]
                                  else arrs[0])
                entry.memo[name] = out
                rebuilt[name] = out

        def sub(path, leaf):
            return rebuilt.get(path_name(path), leaf)

        return jax.tree_util.tree_map_with_path(sub, entry.params)

    def fused_operands(self, model_id: str, path: str, repeat: int = 0,
                       *, gather: str = "onehot", codes: int | None = None):
        """(words, tables, meta) for the fused decode+GEMM kernel, built
        from the same cache-served bits as :meth:`materialize`."""
        entry = self._models[model_id]
        layer = entry.layers[path][repeat]
        mkey = (path, repeat, gather, codes)
        seqs, miss = self._fetch_sequences(model_id, layer)
        if not miss and mkey in entry.fused_memo:
            return entry.fused_memo[mkey]
        bits = bitpack.sequences_to_gemm(
            seqs.astype(np.uint16).reshape(layer.ct.seq_shape), layer.k)
        fc = compression.compress_gemm_fused(
            bits, cluster=False,
            codes_per_sub=codes or DEFAULT_CODES_PER_SUB)
        tables = fc.ct.decode_tables()
        if gather == "bitplane":
            tables = pack_bitplane_tables(tables)
        ops = (jnp.asarray(fc.words), jnp.asarray(tables),
               dict(k_true=fc.k_true, n_true=fc.n_true,
                    codes=codes or DEFAULT_CODES_PER_SUB,
                    scale=jnp.asarray(layer.scale.astype(np.float32)),
                    ratio_stream=fc.ct.ratio_stream(),
                    ratio_tiled=fc.ratio_tiled()))
        entry.fused_memo[mkey] = ops
        return ops

    # -- introspection -----------------------------------------------------
    def models(self) -> list[str]:
        return list(self._models)

    def layers(self, model_id: str) -> dict[str, list[StoredLayer]]:
        return self._models[model_id].layers

    def n_tiles(self, model_id: str) -> int:
        return sum(l.ensure_tiled().n_tiles
                   for ls in self._models[model_id].layers.values()
                   for l in ls)

    def decoded_bytes(self, model_id: str) -> int:
        """Total decoded-tile bytes of the model (cache working set)."""
        total = 0
        for ls in self._models[model_id].layers.values():
            for l in ls:
                ts = l.ensure_tiled()
                total += ts.n_tiles * ts.c * ts.s * 4       # int32 tiles
        return total

    def prom_metrics(self) -> list:
        """(name, kind, getter, help) rows for a pull-based metrics
        registry (``ServeMetrics.registry`` prefixes them ``store_``)."""
        return [
            ("prefetch_dispatched_total", "counter",
             lambda: self.prefetch_dispatched,
             "tile decodes dispatched ahead of use"),
            ("prefetch_used_total", "counter",
             lambda: self.prefetch_used,
             "prefetched tile decodes consumed by a miss"),
        ]

    def report(self, model_id: str) -> dict:
        entry = self._models[model_id]
        ls = [l for stack in entry.layers.values() for l in stack]
        packed = sum(l.packed_bytes() for l in ls)
        stream = sum(l.stream_bytes() for l in ls)
        return {
            "layers": len(ls),
            "packed_bytes": packed,
            "stream_bytes": stream,
            "ratio_stream": packed / max(stream, 1),
        }
