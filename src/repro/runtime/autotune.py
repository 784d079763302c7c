"""Serving-time autotuning: decode-cache capacity and kernel launch shapes.

The paper's §IV working-set threshold reappears at serving time as a
cliff in the decode-cache hit-rate-vs-capacity curve: below the decoded
working set the cyclic materialize scan thrashes, at it the rate jumps
to ~(steps-1)/steps.  :func:`find_knee` locates that cliff on any
measured (capacity, hit-rate) curve and returns the knee — the smallest
capacity past the cliff within a tolerance of the best measured rate,
past which more memory buys no hits.  The benchmark's ``--autotune``
sweep and the launcher's ``--cache-mb auto`` both resolve through it.

:func:`recommend_store_capacity` runs the sweep against a *real*
registered model: it replays the materialize access pattern (every step
touches every tile of every compressed layer) through fresh
:class:`DecodeTileCache` instances at a grid of fractions of the
decoded working set — pure cache accounting, no tensor decodes, so the
sweep costs microseconds even for models whose real materialize takes
seconds.

:func:`tune_kernel` does the same for the paged attention kernel's
launch shape: it times real :func:`paged_mixed_attention` calls on a
synthetic hardware-tiled pool matching the live model's head layout and
page size, sweeping ``(q_block, pages_per_step)``, and memoises the
winner per ``(arch, page, Q)`` key so a fleet of pools resolves the
sweep once.
"""

from __future__ import annotations

import math
import time

from repro.runtime.decode_cache import DecodeTileCache

# the sweep grid: fine below 0.5 where the cliff usually sits
DEFAULT_FRACTIONS = (0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.4,
                     0.5, 0.6, 0.75, 0.9, 1.0)


def find_knee(capacities, rates, tolerance: float = 0.02) -> int:
    """Index of the knee of a measured hit-rate-vs-capacity curve.

    The cliff is the largest hit-rate jump between consecutive
    capacities; the knee is the smallest capacity at/after the cliff
    whose hit rate is within ``tolerance`` of the best measured rate.
    Non-monotone curves where nothing past the cliff qualifies fall
    back to the best capacity itself, so the returned index always
    satisfies ``rates[i] >= max(rates) - tolerance``.

    Ties between equal-size jumps break toward the *latest* one: on a
    staircase curve (several equal jumps), the working-set cliff is the
    last riser — picking the first would return a capacity still inside
    the thrashing region.
    """
    if len(capacities) != len(rates) or not rates:
        raise ValueError("need equal-length, non-empty capacity/rate lists")
    best = max(rates)
    best_i = max(range(len(rates)), key=lambda i: rates[i])
    jumps = [rates[i] - rates[i - 1] for i in range(1, len(rates))]
    cliff = max(range(len(jumps)), key=lambda i: (jumps[i], i)) + 1 \
        if jumps else 0
    return next((i for i in range(cliff, len(rates))
                 if rates[i] >= best - tolerance), best_i)


def sweep_store(store, model_id: str, *, steps: int = 8,
                policy: str | None = None,
                fractions=DEFAULT_FRACTIONS) -> tuple:
    """Replay ``steps`` materialize scans of ``model_id`` at each cache
    capacity fraction -> (capacities, hit_rates).

    The scan is simulated through the cache's own accounting (every
    step touches every tile of every layer, in registration order, with
    the layer's real decoded/compressed byte sizes and frequency
    priors) — the access pattern is exact, only the tile *values* are
    stand-ins, so the hit rates match a real materialize sweep.
    """
    working_set = store.decoded_bytes(model_id)
    layers = [(name, layer, layer.ensure_tiled())
              for name, stack in store.layers(model_id).items()
              for layer in stack]
    # tiny models round int(working_set * frac) below a single decoded
    # tile (even to 0), making the low-fraction sweep points degenerate
    # caches that can never hold anything — clamp every capacity to the
    # largest decoded tile so each point can at least cache one tile
    min_cap = max((ts.c * ts.s * 4 for _, _, ts in layers), default=1)
    caps, rates = [], []
    for frac in fractions:
        cap = max(int(working_set * frac), min_cap)
        cache = DecodeTileCache(cap, policy=policy)
        for name, layer, ts in layers:
            if layer.tile_freq is not None:
                for t in range(ts.n_tiles):
                    cache.seed_frequency((model_id, layer.name, t),
                                         float(layer.tile_freq[t]))
        for _ in range(steps):
            for name, layer, ts in layers:
                nbytes = ts.c * ts.s * 4            # decoded int32 tile
                streamed = layer.tile_compressed_bytes()
                for t in range(ts.n_tiles):
                    cache.get_or_decode((model_id, layer.name, t),
                                        lambda: True, nbytes=nbytes,
                                        streamed_bytes=streamed)
        caps.append(cap)
        rates.append(cache.hit_rate())
    return caps, rates


def recommend_store_capacity(store, model_id: str, *, steps: int = 8,
                             policy: str | None = None,
                             fractions=DEFAULT_FRACTIONS,
                             tolerance: float = 0.02) -> dict:
    """Recommended decode-cache capacity for serving ``model_id``.

    Returns a dict: ``capacity`` (bytes, the knee), ``fraction`` (of
    the decoded working set), ``hit_rate`` (measured at the knee),
    ``best_rate``, ``working_set`` (decoded bytes), and the full
    ``capacities`` / ``rates`` sweep for reporting.
    """
    caps, rates = sweep_store(store, model_id, steps=steps, policy=policy,
                              fractions=fractions)
    knee = find_knee(caps, rates, tolerance=tolerance)
    return {
        "capacity": caps[knee],
        "fraction": fractions[knee],
        "hit_rate": rates[knee],
        "best_rate": max(rates),
        "working_set": store.decoded_bytes(model_id),
        "capacities": caps,
        "rates": rates,
    }


# memoised tune_kernel winners per (arch, page, Q, codec): a fleet of
# SlotPools (or repeated pool rebuilds on slot_len growth) resolves the
# sweep once per launch-shape point
_KERNEL_TUNE_CACHE: dict = {}

DEFAULT_PAGES_PER_STEP = (1, 2, 4)


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def tune_kernel(cfg, page_size: int, q: int, *, codec: bool = False,
                interpret: bool = False, n_slots: int = 4,
                pages_per_slot: int = 4,
                q_blocks=None, pages_per_step=DEFAULT_PAGES_PER_STEP,
                repeats: int = 3, seed: int = 0) -> dict:
    """Pick ``(q_block, pages_per_step)`` for the paged attention kernel
    on the live ``(arch, page, Q)`` point -> result dict.

    Builds a synthetic hardware-tiled page pool matching ``cfg``'s head
    layout (GQA: ``(KH, head_dim)`` pools; MLA: the shared latent /
    rope-part pools) at ``page_size``, then times one compiled
    ``paged_mixed_attention`` mixed step per candidate — real kernel,
    real shapes, stand-in values — and returns the fastest launch
    shape.  ``q_blocks`` defaults to the divisors of ``q`` (the kernel
    rounds non-divisors down to a gcd, so sweeping them would double
    count) whose ``qb * H / KH`` query rows the chip's block rule takes:
    a multiple of the 8-row sublane tile, or all of Q.  Candidates are
    timed best-of-``repeats`` after a warmup call that eats the
    compile.

    Returns ``q_block`` / ``pages_per_step`` (the winner), ``best_ms``,
    the full ``timings`` list of ``(q_block, pages_per_step, ms)``,
    ``key`` — the ``(arch, page, Q, codec)`` memoisation key — and
    ``cached`` (True when a previous call already resolved this key).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import kv_codec
    from repro.kernels.paged_attention import paged_mixed_attention
    from repro.models.api import padded_page_dims

    key = (getattr(cfg, "name", cfg.family), int(page_size), int(q),
           bool(codec))
    hit = _KERNEL_TUNE_CACHE.get(key)
    if hit is not None:
        return {**hit, "cached": True}

    mla = bool(getattr(cfg, "kv_lora_rank", 0))
    h = cfg.num_heads
    kh, d = (1, cfg.kv_lora_rank) if mla else \
        (max(cfg.num_kv_heads, 1), cfg.head_dim)
    rows, (kh_p, d_p) = padded_page_dims((1, page_size, kh, d), 1,
                                         page_size, True)
    n_pages = n_slots * pages_per_slot + 1
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(n_pages, rows, kh_p, d_p)).astype(np.float32)
    table = rng.permutation(np.arange(1, n_pages))[
        :n_slots * pages_per_slot].reshape(n_slots, pages_per_slot)
    table = table.astype(np.int32)
    lengths = np.full((n_slots,), pages_per_slot * page_size, np.int32)
    q_lens = np.full((n_slots,), q, np.int32)
    qs = rng.normal(size=(n_slots, q, h, d)).astype(np.float32)
    kw = {}
    if codec:
        codes, scales = kv_codec.encode(jnp.asarray(pool), axes=(-2, -1))
        kw = dict(k_scales=scales, v_scales=scales)
        pool = codes
    pool = jnp.asarray(pool)

    def run(qb, pps):
        out = paged_mixed_attention(
            qs, pool, pool, jnp.asarray(table), jnp.asarray(lengths),
            jnp.asarray(q_lens), page_size=page_size, q_block=qb,
            pages_per_step=pps, interpret=interpret, **kw)
        out.block_until_ready()

    timings = []
    if q_blocks is None:
        g = h // kh
        q_blocks = [qb for qb in _divisors(q) if qb == q or qb * g % 8 == 0]
    for qb in q_blocks:
        for pps in pages_per_step:
            run(qb, pps)                       # warmup: compile
            best = math.inf
            for _ in range(repeats):
                t0 = time.perf_counter()
                run(qb, pps)
                best = min(best, time.perf_counter() - t0)
            timings.append((qb, pps, best * 1e3))
    qb, pps, ms = min(timings, key=lambda t: t[2])
    res = {"q_block": qb, "pages_per_step": pps, "best_ms": ms,
           "timings": timings, "key": key, "cached": False}
    _KERNEL_TUNE_CACHE[key] = res
    return res
