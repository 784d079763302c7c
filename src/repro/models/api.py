"""Family-dispatched model API: one entry point per step kind.

``get_model(cfg)`` returns a small namespace with uniform signatures so the
launcher / dry-run never branches on families.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

from repro.models import encdec, transformer


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    init_params: Callable[..., Any]
    loss_fn: Callable[..., Any]              # (cfg, params, batch) -> loss
    forward: Callable[..., Any]
    prefill: Callable[..., Any]
    decode_step: Callable[..., Any]          # (cfg, params, cache, tok, pos)
    init_cache_specs: Callable[..., Any]
    init_cache: Callable[..., Any]
    prefill_chunk: Callable[..., Any] | None = None
    # (cfg, params, cache, tokens (B, S), pos) -> (last logits, new cache);
    # the gathered oracle's chunk step (standalone batch-1 cache); None
    # when the family cannot resume a prompt mid-cache (encoder-decoder)
    mixed_step: Callable[..., Any] | None = None
    # (cfg, params, paged cache, table, tokens (S, Q), poss (S,),
    #  q_lens (S,), *, paged_flags, page_size, interpret)
    #   -> (logits (S, Q, V), new cache[, expert pairs if expert_load]);
    # the in-kernel half of the attention-backend seam: one ragged batched
    # trace where every slot contributes q_lens[s] tokens — a prefill
    # chunk, one decode token, or nothing — against the shared page pools
    # (decode is the Q == 1 special case).  None when the family cannot
    # consume a paged cache (encoder-decoder)
    verify_step: Callable[..., Any] | None = None
    # (cfg, params, cache, tokens (B, S), pos, q_lens, *, kv_quant)
    #   -> (full logits (B, S, V), new cache);
    # speculative verification on a lane cache: prefill_chunk's ragged
    # sibling that keeps every position's logits so the scheduler can
    # accept/reject draft tokens.  None when the family cannot resume a
    # prompt mid-cache (encoder-decoder)
    expert_load: bool = False
    # mixed_step appends each MoE block's pairs per held expert,
    # (n_moe_blocks, n_held) int32: the model has MoE blocks


# the attention backends the serving stack can decode with: "gathered"
# copies each slot's pages into a contiguous lane view per step (the
# reference oracle), "pallas_paged" hands the page pool + page tables to
# mixed_step, whose Pallas kernel walks the table in-kernel
ATTN_BACKENDS = ("gathered", "pallas_paged")

# block kinds whose caches can resume a prompt mid-prefill: attention-style
# KV caches resume by construction, and the recurrent kinds (ssm / rglru)
# resume by seeding their scan from the cached recurrent state; only
# cross-attention decoders (encoder-decoder) fall back to monolithic prefill
CHUNKABLE_KINDS = frozenset(
    ("attn", "swa", "local", "global", "attn_local",
     "mla_dense", "mla_moe", "swa_moe", "moe", "ssm", "rglru"))

# block kinds the paged decode-attention backend can serve: attention-style
# caches (full-length leaves page; rolling-window leaves stay lanes and run
# the reference path in the same step); recurrent state and cross-attention
# decoders have no paged equivalent and fall back to "gathered"
PAGEABLE_KINDS = frozenset(
    ("attn", "swa", "local", "global", "attn_local",
     "mla_dense", "mla_moe", "swa_moe", "moe"))


def supports_chunked_prefill(cfg) -> bool:
    """True if ``cfg`` can run :func:`transformer.prefill_chunk`: every
    block kind keeps an attention-style cache and there is no multimodal
    prefix spliced into the prompt (vlm / audio)."""
    if cfg.family in ("vlm", "audio"):
        return False
    kinds = (tuple(cfg.prefix_kinds) + tuple(cfg.scan_pattern)
             + tuple(cfg.suffix_kinds))
    return all(k in CHUNKABLE_KINDS for k in kinds)


def supports_speculation(cfg) -> bool:
    """True if ``cfg`` can decode speculatively: draft tokens are verified
    by the same resume-from-cache machinery chunked prefill uses (the
    ragged :func:`transformer.verify_step` / ``mixed_step`` paths), so the
    gate is identical — every block resumes mid-cache and there is no
    multimodal prefix."""
    return supports_chunked_prefill(cfg)


def supports_paged_attention(cfg) -> bool:
    """True if ``cfg`` can serve with the ``pallas_paged`` attention
    backend: every block keeps an attention-style cache (pageable or
    lane-backed) and the family exposes :func:`transformer.mixed_step`."""
    if cfg.family == "audio":
        return False
    kinds = (tuple(cfg.prefix_kinds) + tuple(cfg.scan_pattern)
             + tuple(cfg.suffix_kinds))
    return all(k in PAGEABLE_KINDS for k in kinds)


def supports_prefix_share(cfg) -> bool:
    """True if ``cfg`` can map shared prefix KV pages into a new
    request's page table: chunked prefill must be resumable (the suffix
    is computed chunk by chunk from the cached span), no multimodal
    prefix may shift absolute positions, and **every** cache leaf must
    page — a rolling-window or recurrent lane would leave prefix state a
    shared page cannot carry.  Rolling-window kinds (swa / local) are
    chunkable and pageable but keep lane-backed leaves, so they are
    excluded here."""
    if not supports_chunked_prefill(cfg) or \
            not supports_paged_attention(cfg):
        return False
    kinds = (tuple(cfg.prefix_kinds) + tuple(cfg.scan_pattern)
             + tuple(cfg.suffix_kinds))
    # probing cache_layout needs an api instance; kind names are the
    # cheaper single source of truth for "has a non-length-scaling leaf"
    windowed = ("swa", "local", "attn_local", "swa_moe")
    return all(k in PAGEABLE_KINDS and k not in windowed for k in kinds)


def cache_layout(api: "ModelAPI", cfg, slot_len: int):
    """Probe the cache-spec factory for each leaf's memory role.

    Returns ``(batch_axes, len_axes)``, two tuples aligned with the flat
    leaves of ``api.init_cache_specs(cfg, 1, slot_len)``:

      * ``batch_axes[i]`` — the axis that scales with the batch argument
        (where the scheduler threads the slot dimension);
      * ``len_axes[i]``  — the axis that scales with cache length, or
        ``None`` for leaves that do not (rolling-window KV, recurrent
        state, cross-attention): these are *not pageable* and stay
        per-slot lanes under every backend.

    This probe is the single source of truth for "which leaves are
    pageable, kernel-consumable": the SlotPool uses it to build the page
    pools and ``mixed_step`` receives the pageability mask derived
    from it, so the two can never disagree about the layout.
    """
    leaves_a = jax.tree_util.tree_leaves(
        api.init_cache_specs(cfg, 1, slot_len))
    leaves_l = jax.tree_util.tree_leaves(
        api.init_cache_specs(cfg, 1, 2 * slot_len))
    leaves_b = jax.tree_util.tree_leaves(
        api.init_cache_specs(cfg, 2, slot_len))
    batch_axes, len_axes = [], []
    for sa, sl, sb in zip(leaves_a, leaves_l, leaves_b):
        bdiff = [i for i, (a, b) in enumerate(zip(sa.shape, sb.shape))
                 if a != b]
        assert bdiff == [bdiff[0]] and sa.shape[bdiff[0]] == 1 and \
            sb.shape[bdiff[0]] == 2, (sa.shape, sb.shape)
        batch_axes.append(bdiff[0])
        if sa.shape == sl.shape:
            len_axes.append(None)
            continue
        ldiff = [i for i, (a, b) in enumerate(zip(sa.shape, sl.shape))
                 if a != b]
        assert len(sa.shape) == len(sl.shape) and ldiff == [ldiff[0]] and \
            sa.shape[ldiff[0]] == slot_len and \
            sl.shape[ldiff[0]] == 2 * slot_len, (sa.shape, sl.shape)
        len_axes.append(ldiff[0])
    return tuple(batch_axes), tuple(len_axes)


def get_model(cfg) -> ModelAPI:
    if cfg.family == "audio":
        return ModelAPI(
            init_params=encdec.init_params,
            loss_fn=encdec.loss_fn,
            forward=encdec.forward,
            prefill=encdec.prefill,
            decode_step=encdec.decode_step,
            init_cache_specs=encdec.init_cache_specs,
            init_cache=encdec.init_cache,
            prefill_chunk=None,
            mixed_step=None,
            verify_step=None,
        )
    return ModelAPI(
        init_params=transformer.init_params,
        loss_fn=transformer.loss_fn,
        forward=transformer.forward,
        prefill=transformer.prefill,
        decode_step=transformer.decode_step,
        init_cache_specs=transformer.init_cache_specs,
        init_cache=transformer.init_cache,
        prefill_chunk=transformer.prefill_chunk,
        mixed_step=transformer.mixed_step,
        verify_step=transformer.verify_step,
        expert_load=any(k in transformer.MOE_KINDS for k in cfg.layer_kinds),
    )
