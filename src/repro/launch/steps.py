"""jit-compiled step builders: train_step / prefill / serve_step.

These are what the dry-run lowers and what the real launchers execute.  All
sharding is decided here (params/batch/cache shardings from dist.sharding)
so the model code stays mesh-agnostic.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd
from repro.dist.compression_comm import compress_grads, init_error_feedback
from repro.models.api import get_model
from repro.train import optimizer as opt


def train_state_specs(cfg, mesh, *, fsdp: bool = True):
    """ShapeDtypeStructs + shardings of (params, opt_state) without
    allocating anything (dry-run path)."""
    api = get_model(cfg)
    params_sds = jax.eval_shape(
        functools.partial(api.init_params, cfg), jax.random.PRNGKey(0))
    p_shard = shd.params_shardings(params_sds, mesh, fsdp=fsdp)
    opt_sds = jax.eval_shape(opt.init_state, params_sds)
    o_shard = {
        "step": NamedSharding(mesh, P()),
        "mu": shd.params_shardings(params_sds, mesh, fsdp=fsdp),
        "nu": shd.params_shardings(params_sds, mesh, fsdp=fsdp),
    }
    return (params_sds, p_shard), (opt_sds, o_shard)


def build_train_step(cfg, mesh, oc: opt.OptConfig | None = None,
                     *, fsdp: bool = True, grad_compression: str = "none",
                     donate: bool = True, batch_sds=None):
    """Returns (jitted step, in_shardings pytree builder).

    step(state, batch) -> (state, loss); state = {"params", "opt"}.
    ``batch_sds``: optional pytree of ShapeDtypeStructs — when given, the
    batch in_shardings are fixed (DP over the leading axis) so the dry-run
    lowers with correctly-sharded inputs instead of replicated defaults.
    """
    api = get_model(cfg)
    oc = oc or opt.OptConfig()
    (p_sds, p_shard), (o_sds, o_shard) = train_state_specs(cfg, mesh,
                                                           fsdp=fsdp)

    if grad_compression != "none":
        raise ValueError(
            "grad compression needs local (unreduced) gradients; use "
            "build_compressed_dp_train_step (pure-DP shard_map path)")

    def step(state, batch):
        params = state["params"]

        def loss_of(p):
            return api.loss_fn(cfg, p, batch)

        loss, grads = jax.value_and_grad(loss_of)(params)
        new_params, new_opt, metrics = opt.apply_updates(
            params, grads, state["opt"], oc)
        return {"params": new_params, "opt": new_opt}, loss

    state_shardings = {"params": p_shard, "opt": o_shard}
    batch_spec = (shd.batch_shardings(batch_sds, mesh)
                  if batch_sds is not None else None)
    jit_step = jax.jit(
        step,
        in_shardings=(state_shardings, batch_spec),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )
    return jit_step, state_shardings


def build_compressed_dp_train_step(loss_fn, mesh, oc: opt.OptConfig,
                                   *, mode: str = "onebit"):
    """Data-parallel train step with compressed gradient exchange.

    This is the honest 1-bit/int8 path: the whole step runs under
    ``shard_map`` over the DP axes, so ``value_and_grad`` yields *local*
    gradients and the only cross-replica traffic is the packed sign words
    (+ scales) of compression_comm — the collective bytes the roofline sees.

    Params are replicated across DP (suits the ~100M-scale BNN/example
    models this path serves); TP meshes should use build_train_step.

    loss_fn(params, batch) -> scalar local loss.
    state = {"params", "opt", "ef"}; returns (step_fn, state_shardings).
    """
    from jax import shard_map

    axes = shd.batch_axes(mesh)
    repl = NamedSharding(mesh, P())

    def step(state, batch):
        def local(params, opt_state, ef, batch):
            with shd.no_mesh():   # shard_map body is already per-shard
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads, new_ef = compress_grads(grads, ef, axes, mode=mode)
            new_params, new_opt, _ = opt.apply_updates(
                params, grads, opt_state, oc)
            return new_params, new_opt, new_ef, jax.lax.pmean(loss, axes)

        p_specs = jax.tree_util.tree_map(lambda _: P(), state["params"])
        o_specs = jax.tree_util.tree_map(lambda _: P(), state["opt"])
        e_specs = jax.tree_util.tree_map(lambda _: P(), state["ef"])
        b_specs = jax.tree_util.tree_map(
            lambda _: P(axes), batch)
        new_p, new_o, new_e, loss = shard_map(
            local, mesh=mesh,
            in_specs=(p_specs, o_specs, e_specs, b_specs),
            out_specs=(p_specs, o_specs, e_specs, P()),
            check_vma=False,
        )(state["params"], state["opt"], state["ef"], batch)
        return {"params": new_p, "opt": new_o, "ef": new_e}, loss

    state_shardings = jax.tree_util.tree_map(lambda _: repl, {"_": 0})
    return jax.jit(step, donate_argnums=(0,)), state_shardings


def init_train_state(cfg, mesh, key, *, fsdp: bool = True,
                     grad_compression: str = "none"):
    """Materialise sharded params + optimizer state on the mesh."""
    api = get_model(cfg)
    (p_sds, p_shard), (_, o_shard) = train_state_specs(cfg, mesh, fsdp=fsdp)
    init = jax.jit(functools.partial(api.init_params, cfg),
                   out_shardings=p_shard)
    params = init(key)
    opt_state = jax.jit(opt.init_state, out_shardings=o_shard)(params)
    state = {"params": params, "opt": opt_state}
    if grad_compression != "none":
        ef_shard = p_shard
        state["ef"] = jax.jit(init_error_feedback,
                              out_shardings=ef_shard)(params)
    return state


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def build_serve_steps(cfg, mesh, batch: int, max_len: int,
                      *, fsdp: bool = False):
    """(prefill_fn, decode_fn, cache_specs, cache_shardings)."""
    api = get_model(cfg)
    params_sds = jax.eval_shape(
        functools.partial(api.init_params, cfg), jax.random.PRNGKey(0))
    p_shard = shd.params_shardings(params_sds, mesh, fsdp=fsdp)
    cache_sds = api.init_cache_specs(cfg, batch, max_len)
    c_shard = shd.cache_shardings(cache_sds, mesh)

    def prefill_fn(params, tokens, cache, *extra):
        if cfg.family == "vlm":
            return api.prefill(cfg, params, tokens, cache,
                               vision_embeds=extra[0])
        return api.prefill(cfg, params, tokens, cache, *extra)

    def decode_fn(params, cache, tokens, pos):
        return api.decode_step(cfg, params, cache, tokens, pos)

    logits_shard = NamedSharding(mesh, shd.safe_spec(
        mesh, (batch, 1, cfg.vocab_size), "batch", None, "model"))
    tok_shard = NamedSharding(mesh, shd.safe_spec(
        mesh, (batch, 1), "batch", None))
    extra_shards = ()
    if cfg.family in ("vlm", "audio"):   # stubbed-frontend embeddings
        extra_shards = (NamedSharding(mesh, shd.safe_spec(
            mesh, (batch, 1, cfg.d_model), "batch", None, None)),)
    prefill_jit = jax.jit(
        prefill_fn,
        in_shardings=(p_shard, tok_shard, c_shard) + extra_shards,
        out_shardings=(logits_shard, c_shard))
    decode_jit = jax.jit(
        decode_fn,
        in_shardings=(p_shard, c_shard, tok_shard, NamedSharding(mesh, P())),
        out_shardings=(logits_shard, c_shard),
        donate_argnums=(1,))
    return prefill_jit, decode_jit, (params_sds, p_shard), (cache_sds, c_shard)
