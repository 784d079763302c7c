"""Decoder-only LM composition: block dispatch + scan-over-layers stacking.

A config's layer stack is ``prefix_kinds + scan_pattern * scan_repeats +
suffix_kinds``.  The repeated pattern is stacked parameter-wise and executed
with ``lax.scan`` over super-blocks (one super-block = one pass through the
pattern) so HLO size and compile time are independent of depth; prefix and
suffix layers are unrolled.  Heterogeneous patterns (gemma2's local/global
alternation, recurrentgemma's rec/rec/attn triple) are naturally supported
because the super-block pytree is uniform across repeats.

Block kinds: attn | swa | local | global | attn_local | mla_dense | mla_moe |
swa_moe | moe | ssm | rglru | bidir (encoder) | dec (decoder w/ cross-attn).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (chunked_cross_entropy, cross_entropy,
                                 embed_init, mlp_apply, mlp_init, rms_norm,
                                 rms_norm_init, softcap)

ATTN_KINDS = ("attn", "swa", "local", "global", "attn_local", "bidir")
MOE_KINDS = ("swa_moe", "mla_moe", "moe")
MLA_KINDS = ("mla_dense", "mla_moe")
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def remat_wrap(cfg, fn):
    """Wrap a scan body in jax.checkpoint per the config's remat policy."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _attn_kind(kind: str) -> str:
    """Map block kind -> attention variant."""
    return {"swa": "swa", "swa_moe": "swa", "local": "local",
            "attn_local": "local", "bidir": "bidir"}.get(kind, "attn")


# ---------------------------------------------------------------------------
# block init / apply
# ---------------------------------------------------------------------------

def block_init(kind: str, cfg, key, dtype) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p: dict = {"ln1": rms_norm_init(d, dtype)}
    if kind == "ssm":
        p["mixer"] = ssm_mod.ssm_init(ks[0], cfg, dtype)
        return p
    if kind == "rglru":
        p["mixer"] = rglru_mod.rglru_init(ks[0], cfg, dtype)
    elif kind in MLA_KINDS:
        p["attn"] = attn.mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
    if kind == "dec":
        p["ln_cross"] = rms_norm_init(d, dtype)
        p["cross"] = attn.cross_attn_init(ks[2], cfg, dtype)
    p["ln2"] = rms_norm_init(d, dtype)
    if kind in MOE_KINDS:
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    elif cfg.d_ff:
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_act, dtype)
    if cfg.post_norms:
        p["post_ln1"] = rms_norm_init(d, dtype)
        p["post_ln2"] = rms_norm_init(d, dtype)
    return p


def block_apply(kind: str, cfg, p: dict, x: jax.Array, *,
                cache=None, pos=None, prefix_len: int = 0, enc_out=None,
                paged=None, q_lens=None, scales=None, kv_quant=False,
                dropless: bool = False):
    """-> (x, new_cache, aux_loss); with ``scales`` ->
    (x, new_cache, new_scales, aux_loss).  ``dropless`` (every inference
    step, :func:`_run_stack`) routes a MoE block's real tokens with no
    capacity (``moe.moe_serve``), and its ``aux`` is then its held
    experts' pair counts (n_held,) int32 instead of a loss; training
    dispatches by capacity (``moe.moe_apply``).

    ``paged`` (an ``attention.PagedContext``) is only passed on mixed /
    decode steps of the ``pallas_paged`` backend, and only for blocks
    whose cache leaves are page pools; lane-backed blocks receive
    ``paged=None`` and run the gathered reference path.  ``q_lens``
    carries the ragged per-slot token counts of a mixed step (None =
    every token is real).  ``scales`` carries this block's
    ``kv_codec="cluster"`` scale pools (same keys as the cache leaves,
    ``(n_pages, page)`` f32 each) and implies the cache leaves hold int8
    codes; only attention blocks can receive it.
    """
    aux = jnp.zeros((), jnp.float32)
    new_scales = None
    h = rms_norm(p["ln1"], x, cfg.norm_eps)

    if kind == "ssm":
        y, new_cache = ssm_mod.ssm_apply(p["mixer"], h, cfg,
                                         cache=cache, pos=pos,
                                         q_lens=q_lens)
        return x + y, new_cache, aux

    if kind == "rglru":
        y, new_cache = rglru_mod.rglru_apply(p["mixer"], h, cfg,
                                             cache=cache, pos=pos,
                                             q_lens=q_lens)
    elif kind in MLA_KINDS:
        with jax.named_scope("mla"):
            res = attn.mla_apply(p["attn"], h, cfg, cache=cache, pos=pos,
                                 paged=paged, q_lens=q_lens, scales=scales,
                                 kv_quant=kv_quant)
        if scales is not None:
            y, new_cache, new_scales = res
        else:
            y, new_cache = res
    else:
        self_cache = cache.get("self") if isinstance(cache, dict) and \
            "self" in (cache or {}) else cache
        res = attn.attn_apply(
            p["attn"], h, cfg, kind=_attn_kind(kind), cache=self_cache,
            pos=pos, prefix_len=prefix_len, paged=paged, q_lens=q_lens,
            scales=scales, kv_quant=kv_quant)
        if scales is not None:
            y, new_cache, new_scales = res
        else:
            y, new_cache = res
    if cfg.post_norms:
        y = rms_norm(p["post_ln1"], y, cfg.norm_eps)
    x = x + y

    if kind == "dec":                     # cross-attention sub-layer
        hc = rms_norm(p["ln_cross"], x, cfg.norm_eps)
        # cached cross-K/V is only valid for decode; prefill recomputes it
        # from enc_out (the initial cache is zeros)
        decode_mode = x.shape[1] == 1 and enc_out is None
        enc_kv = cache.get("cross") if (decode_mode and
                                        isinstance(cache, dict)) else None
        yc, cross_kv = attn.cross_attn_apply(p["cross"], hc, cfg,
                                             enc_kv=enc_kv, enc_out=enc_out)
        x = x + yc
        if cache is not None:
            new_cache = {"self": new_cache, "cross": cross_kv}

    if "moe" in p or "mlp" in p:
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        if kind in MOE_KINDS and dropless:
            real = None if q_lens is None else \
                jnp.arange(h2.shape[1])[None] < \
                jnp.asarray(q_lens, jnp.int32)[:, None]
            with jax.named_scope("moe"):
                y2, aux = moe_mod.moe_serve(p["moe"], h2, cfg, real)
        elif kind in MOE_KINDS:
            with jax.named_scope("moe"):
                y2, aux = moe_mod.moe_apply(p["moe"], h2, cfg)
        else:
            y2 = mlp_apply(p["mlp"], h2, cfg.mlp_act,
                           binarized=cfg.binarize_mlp)
        if cfg.post_norms:
            y2 = rms_norm(p["post_ln2"], y2, cfg.norm_eps)
        x = x + y2
    if scales is not None:
        return x, new_cache, new_scales, aux
    return x, new_cache, aux


def block_cache_spec(kind: str, cfg, batch: int, max_len: int):
    if kind == "ssm":
        return ssm_mod.ssm_cache_spec(cfg, batch)
    if kind == "rglru":
        return rglru_mod.rglru_cache_spec(cfg, batch)
    if kind in MLA_KINDS:
        return attn.mla_cache_spec(cfg, batch, max_len)
    if kind == "dec":
        return {"self": attn.attn_cache_spec(cfg, "attn", batch, max_len),
                "cross": {"k": jax.ShapeDtypeStruct(
                              (batch, cfg.encoder_seq, cfg.num_kv_heads,
                               cfg.head_dim), cfg.jnp_dtype),
                          "v": jax.ShapeDtypeStruct(
                              (batch, cfg.encoder_seq, cfg.num_kv_heads,
                               cfg.head_dim), cfg.jnp_dtype)}}
    return attn.attn_cache_spec(cfg, _attn_kind(kind), batch, max_len)


# ---------------------------------------------------------------------------
# parameter / cache trees
# ---------------------------------------------------------------------------

def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg, key) -> dict:
    dtype = cfg.jnp_dtype
    keys = jax.random.split(key, 4 + len(cfg.prefix_kinds)
                            + cfg.scan_repeats + len(cfg.suffix_kinds))
    ki = iter(range(len(keys)))
    params: dict = {
        "embed": embed_init(keys[next(ki)], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rms_norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(
            keys[next(ki)], cfg.vocab_size, cfg.d_model, dtype).T
    params["prefix"] = [
        block_init(k, cfg, keys[next(ki)], dtype) for k in cfg.prefix_kinds]
    reps = []
    for _ in range(cfg.scan_repeats):
        kk = jax.random.split(keys[next(ki)], len(cfg.scan_pattern))
        reps.append({f"b{i}": block_init(k, cfg, kk[i], dtype)
                     for i, k in enumerate(cfg.scan_pattern)})
    params["scan"] = _stack(reps) if reps else {}
    params["suffix"] = [
        block_init(k, cfg, keys[next(ki)], dtype) for k in cfg.suffix_kinds]
    return params


def init_cache_specs(cfg, batch: int, max_len: int):
    cache: dict = {
        "prefix": [block_cache_spec(k, cfg, batch, max_len)
                   for k in cfg.prefix_kinds],
        "suffix": [block_cache_spec(k, cfg, batch, max_len)
                   for k in cfg.suffix_kinds],
    }
    one = {f"b{i}": block_cache_spec(k, cfg, batch, max_len)
           for i, k in enumerate(cfg.scan_pattern)}
    cache["scan"] = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((cfg.scan_repeats, *s.shape), s.dtype),
        one) if cfg.scan_repeats else {}
    return cache


def init_cache(cfg, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        init_cache_specs(cfg, batch, max_len))


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens, vision_embeds=None):
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    # sequence-parallel residual stream: tokens sharded over "model"
    return constrain(x, "batch", "model", None)


def _unembed(cfg, params, x):
    """x: final-norm'd hidden -> softcapped f32 logits."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    logits = softcap(logits, cfg.final_logit_softcap)
    # logits stay sequence-sharded: (B, S/model, V) — 16x less live memory
    # than vocab-full-per-device, and CE is fully local in S
    return constrain(logits.astype(jnp.float32), "batch", "model", None)


def backbone(cfg, params, tokens, *, vision_embeds=None):
    """Embed + layer stack + final norm -> (hidden (B, S*, D), aux loss)."""
    prefix_len = vision_embeds.shape[1] if vision_embeds is not None else 0
    x = _embed(cfg, params, tokens, vision_embeds)
    aux_total = jnp.zeros((), jnp.float32)

    for kind, p in zip(cfg.prefix_kinds, params["prefix"]):
        x, _, aux = block_apply(kind, cfg, p, x, prefix_len=prefix_len)
        aux_total += aux

    if cfg.scan_repeats:
        def body(carry, layer_params):
            x, aux_sum = carry
            for i, kind in enumerate(cfg.scan_pattern):
                x, _, aux = block_apply(kind, cfg, layer_params[f"b{i}"], x,
                                        prefix_len=prefix_len)
                aux_sum += aux
            x = constrain(x, "batch", "model", None)
            return (x, aux_sum), None

        (x, aux_total), _ = jax.lax.scan(remat_wrap(cfg, body),
                                         (x, aux_total), params["scan"])

    for kind, p in zip(cfg.suffix_kinds, params["suffix"]):
        x, _, aux = block_apply(kind, cfg, p, x, prefix_len=prefix_len)
        aux_total += aux
    return rms_norm(params["final_norm"], x, cfg.norm_eps), aux_total


def forward(cfg, params, tokens, *, vision_embeds=None):
    """Training/scoring forward -> (logits (B, S*, V) f32, aux loss)."""
    x, aux_total = backbone(cfg, params, tokens,
                            vision_embeds=vision_embeds)
    return _unembed(cfg, params, x), aux_total


def loss_fn(cfg, params, batch) -> jax.Array:
    hidden, aux = backbone(cfg, params, batch["tokens"],
                           vision_embeds=batch.get("vision_embeds"))
    if batch.get("vision_embeds") is not None:
        hidden = hidden[:, batch["vision_embeds"].shape[1]:]
    hidden = constrain(hidden, "batch", "model", None)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ce = chunked_cross_entropy(hidden, head, batch["labels"],
                               softcap_val=cfg.final_logit_softcap)
    return ce + 0.01 * aux


def _run_stack(cfg, params, cache, x, *, pos=None, prefix_len: int = 0,
               flags=None, ctx=None, q_lens=None, scales=None,
               kv_quant=False):
    """One pass through prefix + scan + suffix blocks, threading the cache.

    The single block walker behind :func:`prefill`,
    :func:`prefill_chunk`, :func:`decode_step`, and :func:`mixed_step` —
    they differ only in how ``x`` is embedded, which positions are
    attached, and which logits are kept.  ``flags``/``ctx`` carry the
    per-leaf pageability mask + ``attention.PagedContext`` of a paged
    mixed step (None = gathered/lane serving); ``q_lens`` the ragged
    per-slot token counts.  ``scales`` is the ``kv_codec="cluster"``
    scale-pool tree mirroring ``cache``'s block structure (None at
    non-pageable blocks).

    Every block runs as an inference step: MoE blocks route dropless
    over the real tokens (``q_lens``), where :func:`backbone` (training)
    dispatches by capacity.

    Returns ``(x, new_cache, new_scales, load)``; ``new_scales`` is None
    unless ``scales`` was passed, and ``load`` is None for a model
    without MoE blocks, else it stacks each MoE block's pairs per held
    expert, (n_moe_blocks, n_held) int32, in layer order.
    """
    def block_ctx(f):
        if f is None:
            return None
        leaves = jax.tree_util.tree_leaves(f)
        assert all(leaves) or not any(leaves), \
            "mixed paged/lane cache leaves within one block"
        return ctx if leaves and all(leaves) else None

    def norm_sc(b):
        # a block's scales subtree is "real" iff any leaf is an array;
        # lane-backed blocks carry per-leaf Nones (the canonical scale
        # tree mirrors the cache treedef position-for-position) and run
        # without scales
        if b is None:
            return None
        flat = jax.tree_util.tree_flatten(
            b, is_leaf=lambda v: v is None)[0]
        return b if any(v is not None for v in flat) else None

    loads = []

    def apply(x, kind, p, c, pg, sc):
        # normalise block_apply's with/without-scales return arity
        if sc is None:
            x, nc, aux = block_apply(kind, cfg, p, x, cache=c, pos=pos,
                                     prefix_len=prefix_len, paged=pg,
                                     q_lens=q_lens, kv_quant=kv_quant,
                                     dropless=True)
            return x, nc, None, aux
        x, nc, nsc, aux = block_apply(kind, cfg, p, x, cache=c, pos=pos,
                                      prefix_len=prefix_len, paged=pg,
                                      q_lens=q_lens, scales=sc,
                                      kv_quant=kv_quant, dropless=True)
        return x, nc, nsc, aux

    new_cache = {"prefix": [], "suffix": []}
    new_scales = None if scales is None else {"prefix": [], "suffix": []}
    for i, (kind, p, c) in enumerate(zip(cfg.prefix_kinds,
                                         params["prefix"],
                                         cache["prefix"])):
        sc_blk = scales["prefix"][i] if scales is not None else None
        x, nc, nsc, aux = apply(
            x, kind, p, c,
            block_ctx(flags["prefix"][i] if flags else None),
            norm_sc(sc_blk))
        if kind in MOE_KINDS:
            loads.append(aux[None])
        new_cache["prefix"].append(nc)
        if new_scales is not None:
            new_scales["prefix"].append(nsc if nsc is not None else sc_blk)

    if cfg.scan_repeats:
        pgs = [block_ctx(flags["scan"][f"b{i}"] if flags else None)
               for i in range(len(cfg.scan_pattern))]
        # each MoE block's expert stacks (R, n_held, ., .) stay out of the
        # scanned inputs: a grouped product cannot fuse a slice of its
        # weights, so a scanned slice would copy every layer's experts each
        # step.  The body gets them whole, as (R * n_held, ., .) (a
        # bitcast), with the layer's index to find its own groups.
        xs_params, held = dict(params["scan"]), {}
        for i, kind in enumerate(cfg.scan_pattern):
            if kind in MOE_KINDS:
                blk = xs_params[f"b{i}"]
                held[f"b{i}"] = {w: blk["moe"][w].reshape(
                    -1, *blk["moe"][w].shape[2:]) for w in EXPERT_WEIGHTS}
                xs_params[f"b{i}"] = {**blk, "moe": {
                    k: v for k, v in blk["moe"].items()
                    if k not in EXPERT_WEIGHTS}}

        def body(x, xs):
            layer, layer_params, layer_cache, layer_scales = xs
            ncs, nscs, lds = {}, {}, []
            for i, kind in enumerate(cfg.scan_pattern):
                sc_blk = (layer_scales[f"b{i}"]
                          if layer_scales is not None else None)
                p = layer_params[f"b{i}"]
                if f"b{i}" in held:
                    p = {**p, "moe": {**p["moe"], **held[f"b{i}"],
                                      "layer": layer}}
                x, nc, nsc, aux = apply(
                    x, kind, p, layer_cache[f"b{i}"],
                    pgs[i], norm_sc(sc_blk))
                ncs[f"b{i}"] = nc
                nscs[f"b{i}"] = nsc if nsc is not None else sc_blk
                if kind in MOE_KINDS:
                    lds.append(aux)
            return x, (ncs, nscs, jnp.stack(lds) if lds else None)

        x, (scan_cache, scan_scales, scan_loads) = jax.lax.scan(
            body, x, (jnp.arange(cfg.scan_repeats) if held else None,
                      xs_params, cache["scan"],
                      scales["scan"] if scales is not None else None))
        if scan_loads is not None:
            # (repeats, blocks of the pattern, n_held) -> layer order
            loads.append(scan_loads.reshape(-1, scan_loads.shape[-1]))
        new_cache["scan"] = scan_cache
        if new_scales is not None:
            new_scales["scan"] = scan_scales
    else:
        new_cache["scan"] = {}
        if new_scales is not None:
            new_scales["scan"] = {}

    for i, (kind, p, c) in enumerate(zip(cfg.suffix_kinds,
                                         params["suffix"],
                                         cache["suffix"])):
        sc_blk = scales["suffix"][i] if scales is not None else None
        x, nc, nsc, aux = apply(
            x, kind, p, c,
            block_ctx(flags["suffix"][i] if flags else None),
            norm_sc(sc_blk))
        if kind in MOE_KINDS:
            loads.append(aux[None])
        new_cache["suffix"].append(nc)
        if new_scales is not None:
            new_scales["suffix"].append(nsc if nsc is not None else sc_blk)
    load = jnp.concatenate(loads) if loads else None
    return x, new_cache, new_scales, load


def prefill(cfg, params, tokens, cache, *, vision_embeds=None):
    """Run the full prompt, returning (last-token logits, filled cache)."""
    prefix_len = vision_embeds.shape[1] if vision_embeds is not None else 0
    x = _embed(cfg, params, tokens, vision_embeds)
    x, new_cache, _, _ = _run_stack(cfg, params, cache, x,
                                    prefix_len=prefix_len)
    x = rms_norm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = _unembed(cfg, params, x)
    return logits, new_cache


def _embed_step(cfg, params, tokens):
    """Embed serving-step tokens (no vision splice, lane-sharded)."""
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return constrain(x, "batch", None, None)


def prefill_chunk(cfg, params, cache, tokens, pos, *, kv_quant=False):
    """One prefill chunk: ``tokens`` (B, S) at absolute positions
    pos..pos+S-1 against a partially filled cache -> (last-position logits
    (B, 1, V), new cache).

    Splitting a prompt into chunks and feeding them here in order is
    mathematically identical to one monolithic :func:`prefill` call — the
    chunk attends to everything already in the cache plus itself, under the
    same absolute-position causal/window masks — which is what lets the
    scheduler interleave prompt chunks with decode steps of other slots
    (token-equivalence locked down in tests/test_paged_prefill.py).
    This is the *gathered oracle's* chunk step (standalone batch-1 cache);
    the ``pallas_paged`` backend runs chunks through :func:`mixed_step`
    instead.  Recurrent blocks (ssm / rglru) resume their scan from the
    cached recurrent state.  ``kv_quant`` (``kv_codec="cluster"`` on the
    gathered backend) round-trips the chunk's K/V through the codec so
    later chunks attend to the same quantised keys the kernel backend
    sees, and install's page re-encode is lossless.
    """
    x = _embed_step(cfg, params, tokens)
    x, new_cache, _, _ = _run_stack(cfg, params, cache, x, pos=pos,
                                    kv_quant=kv_quant)
    x = rms_norm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    return _unembed(cfg, params, x), new_cache


def verify_step(cfg, params, cache, tokens, pos, q_lens, *, kv_quant=False):
    """Speculative verification: score ``tokens`` (B, S) at absolute
    positions pos..pos+S-1 against a partially filled cache -> (*full*
    logits (B, S, V), new cache).

    Identical to :func:`prefill_chunk` except every position's logits are
    returned (the scheduler needs row ``i`` to check draft token ``i+1``)
    and ``q_lens`` makes the block ragged: lane ``b`` contributes
    ``q_lens[b]`` real tokens, rows past that are padding whose cache
    writes are dropped and whose logits are garbage.  A ``q_lens[b] == 0``
    lane is an exact no-op on its cache.
    """
    x = _embed_step(cfg, params, tokens)
    x, new_cache, _, _ = _run_stack(cfg, params, cache, x, pos=pos,
                                    q_lens=q_lens, kv_quant=kv_quant)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _unembed(cfg, params, x), new_cache


def decode_step(cfg, params, cache, tokens, pos, *, kv_quant=False):
    """One token with a filled cache -> (logits (B,1,V), new cache).

    ``pos`` is the absolute position of ``tokens`` (vision prefix included
    for VLM archs).  ``kv_quant`` round-trips the new row's K/V through
    the cluster codec before write *and* attention (gathered backend
    under ``kv_codec="cluster"``) — quantise-then-attend, the same
    numerics the paged kernel's in-VMEM decode applies.
    """
    x = _embed_step(cfg, params, tokens)
    x, new_cache, _, _ = _run_stack(cfg, params, cache, x, pos=pos,
                                    kv_quant=kv_quant)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _unembed(cfg, params, x), new_cache


def mixed_step(cfg, params, cache, table, tokens, poss, q_lens, *,
               paged_flags: tuple, page_size: int,
               interpret: bool = False, scales=None):
    """One mixed serving step for *every* slot straight over the paged KV
    pools: slot ``s`` contributes ``q_lens[s]`` consecutive tokens — a
    prefill chunk, a single decode token, or nothing (``0``, a free lane)
    — out of the padded block ``tokens`` ``(S, Q)``, starting at absolute
    position ``poss[s]``.

    This is the ``pallas_paged`` backend's only step function (decode is
    the ``Q == 1``, all-``q_lens``-1 special case; the former
    ``decode_step_paged`` and the paged half of chunked prefill merged
    here): ``cache`` has the same tree structure as
    :func:`init_cache_specs` but each pageable leaf is the *physical page
    pool* shared by all slots (``(n_pages, page, ...)``; scan-stacked
    leaves keep their leading repeats axis) and each non-pageable leaf is
    a batched per-slot lane (``(n_slots, ...)``).  ``table`` ``(S, P)``
    maps logical to physical pages per slot.

    ``paged_flags`` is the flat per-leaf pageability mask from
    ``models.api.cache_layout`` (static — it picks the kernel vs lane path
    per block at trace time).  Pageable leaves take the in-kernel path:
    the chunk's K/V is scattered into the slot's pages *before* the
    kernel walks the page table (per-token causal masks preserve
    write-after-attend semantics; ragged padding is routed to the page-0
    dummy sink).  Lane leaves (rolling-window KV) run the gathered
    reference chunk attention on their lanes in the same trace, with
    write-after-attend and ragged writes dropped past ``q_lens``.  There
    is no per-step gather/scatter of the cache anywhere — for decode
    tokens *or* prefill chunks.

    Returns ``(logits (S, Q, V), new cache tree)`` with the pool leaves
    updated in place (donation-friendly: every output leaf has its input
    leaf's shape and dtype).  Logits of padded rows (``i >= q_lens[s]``)
    are garbage the caller ignores; a slot's next token comes from row
    ``q_lens[s] - 1``.

    ``scales`` (``kv_codec="cluster"``): the per-block scale-pool tree —
    pageable leaves hold int8 codebook codes, decoded in-kernel — and
    the return grows to ``(logits, new_cache, new_scales)``.

    A model with MoE blocks (``ModelAPI.expert_load``) appends the
    step's pairs per held expert of every MoE block, (n_moe_blocks,
    n_held) int32, as the last element of the return.
    """
    specs = init_cache_specs(cfg, 1, page_size)
    flags = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(specs), list(paged_flags))
    ctx = attn.PagedContext(table=table, page_size=page_size,
                            interpret=interpret)
    x = _embed_step(cfg, params, tokens)
    x, new_cache, new_scales, load = _run_stack(
        cfg, params, cache, x, pos=poss, flags=flags, ctx=ctx,
        q_lens=q_lens, scales=scales)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    out = (_unembed(cfg, params, x), new_cache)
    if scales is not None:
        out += (new_scales,)
    if load is not None:
        out += (load,)
    return out
