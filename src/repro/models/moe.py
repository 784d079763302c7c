"""Mixture-of-Experts substrate (Mixtral top-2, DeepSeek-V2 shared+routed).

Routing (:func:`route`): softmax scores over every expert; with
``n_group`` the experts form that many equal groups, a group scores its
best expert, and a token picks its top-k experts inside its best
``topk_group`` groups (DeepSeek-V2's ``group_limited_greedy``).  The
top-k gates are renormalised to sum 1 (``norm_topk_prob``), or left as
softmax scores and multiplied by ``routed_scaling_factor``.

An expert layer may hold only some of the routed experts
(``experts_held`` from ``expert_start``): the chip's share of an
expert-parallel deployment.  It routes over all of them and computes
its own experts' part of the result; what the others would add is left
out, and the shared experts apply to every token.

Inference (:func:`moe_serve`, every step of the model's block walker
``transformer._run_stack``: prefill, chunk, verify, decode and mixed
steps) is dropless: the (token, expert) pairs that land on held experts
are sorted by expert and run as grouped products
(``jax.lax.ragged_dot``), with no capacity; rows the caller marks as
padding are routed nowhere, so a real row's output never depends on its
batch mates.  The products of a scanned MoE layer read its experts in
place from the stack of every scanned layer's, at the layer's group
offset.

Training (:func:`moe_apply`, every expert held) uses sort-based
capacity-bounded dispatch (GShard/Switch style): tokens are sorted by
expert id, packed into an (E, C, D) buffer (C = capacity), processed
with one grouped einsum per projection, and combined back weighted by
the router probability.  Compute is proportional to *active* parameters
(top_k / E of the expert pool), which keeps HLO_FLOPs ~ 6·N_active·D
— the roofline "useful compute" check in EXPERIMENTS.md depends on this.

Expert sharding (DESIGN.md §5): the leading E axis of the expert weights is
sharded over "model" when E divides the axis (DeepSeek: 160/16 = 10 experts
per device, true EP); otherwise the d_ff axis is TP-sharded (Mixtral: 8
experts < 16 shards).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.dist.sharding import constrain
from repro.models.layers import dense_init


def moe_init(key, cfg, dtype) -> dict:
    """Router over all ``num_experts``; routed expert weights for the held
    experts only.  With ``experts_held`` set, expert ``e`` draws from its
    own key (``fold_in`` of its global id), so a share holds exactly the
    uncut layer's weights of its experts."""
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    scale = d ** -0.5
    if cfg.experts_held:
        ids = jnp.arange(cfg.expert_start,
                         cfg.expert_start + cfg.experts_held)

        def per_expert(k, shape, std):
            draw = jax.vmap(lambda i: jax.random.normal(
                jax.random.fold_in(k, i), shape))
            return (draw(ids) * std).astype(dtype)
        w_gate = per_expert(ks[1], (d, f), scale)
        w_up = per_expert(ks[2], (d, f), scale)
        w_down = per_expert(ks[3], (f, d), f ** -0.5)
    else:
        w_gate = (jax.random.normal(ks[1], (e, d, f)) * scale).astype(dtype)
        w_up = (jax.random.normal(ks[2], (e, d, f)) * scale).astype(dtype)
        w_down = (jax.random.normal(ks[3], (e, f, d))
                  * f ** -0.5).astype(dtype)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": w_gate,
        "w_up": w_up,
        "w_down": w_down,
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "gate": dense_init(k1, d, fs, dtype),
            "up": dense_init(k2, d, fs, dtype),
            "down": dense_init(k3, fs, d, dtype),
        }
    return p


def route(p: dict, x: jax.Array, cfg):
    """x (..., D) -> (gates (..., k) f32, global expert ids (..., k),
    softmax scores (..., E))."""
    e, k = cfg.num_experts, cfg.top_k
    # float32 scores as published: on a TPU a float32 product runs at
    # bfloat16 unless asked for HIGHEST, which would round the router
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    if cfg.n_group:
        g = cfg.n_group
        best = probs.reshape(*probs.shape[:-1], g, e // g).max(-1)
        _, top = jax.lax.top_k(best, cfg.topk_group)
        keep = (top[..., None] == jnp.arange(g)).any(-2)     # (..., G)
        scores = jnp.where(jnp.repeat(keep, e // g, axis=-1), probs, 0.0)
    gate, eid = jax.lax.top_k(scores, k)
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    else:
        gate = gate * cfg.routed_scaling_factor
    return gate, eid, probs


def _swiglu(x, gate, up, down, dot=jnp.dot):
    """SwiGLU over rows of ``x`` with float32 accumulation and the
    activation in float32, each product rounded once to ``x``'s dtype."""
    f32 = jnp.float32

    def mm(a, w):
        return dot(a, w, preferred_element_type=f32).astype(x.dtype)
    h = jax.nn.silu(mm(x, gate).astype(f32)) * mm(x, up).astype(f32)
    return mm(h.astype(x.dtype), down)


def _shared(p: dict, x: jax.Array) -> jax.Array:
    sp = p["shared"]
    return _swiglu(x, sp["gate"], sp["up"], sp["down"])


@jax.custom_batching.custom_vmap
def _held_experts(x, gate, grp, w_gate, w_up, w_down):
    """out (T, D): each row's gate-weighted sum over its pairs' expert
    outputs.  ``grp`` (T, k) is a pair's group, the index of its expert
    in the weights' leading axis, or that axis' length for a pair not
    computed; every pair is one row of a grouped product over the pairs
    sorted by group (``ragged_dot``), and pairs not computed sort past
    the last group."""
    t, k = grp.shape
    n = w_gate.shape[0]
    take = grp < n
    flat = grp.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    ys = _swiglu(x[order // k], w_gate, w_up, w_down,
                 functools.partial(jax.lax.ragged_dot, group_sizes=sizes))
    y = jnp.zeros_like(ys).at[order].set(ys).reshape(t, k, -1)
    # a select, not a product: rows past the last group are left unwritten
    # by the TPU's grouped product, and 0 * NaN is NaN; elementwise in f32,
    # since a dot would run the gates at bf16 on the MXU
    wy = jnp.where(take[..., None],
                   gate[..., None] * y.astype(jnp.float32), 0.0)
    return wy.sum(1).astype(x.dtype)


@_held_experts.def_vmap
def _held_experts_vmap(axis_size, in_batched, x, gate, grp, w_gate, w_up,
                       w_down):
    """Mapped lanes (the gathered backend's per-slot decode) are more
    rows of one grouped product: rows never interact."""
    assert not any(in_batched[3:]), "held experts' weights are not mapped"
    lead = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, b in zip((x, gate, grp), in_batched[:3])]
    t = lead[0].shape[1]
    flat = [a.reshape(axis_size * t, *a.shape[2:]) for a in lead]
    out = _held_experts(*flat, w_gate, w_up, w_down)
    return out.reshape(axis_size, t, -1), True


def moe_serve(p: dict, x: jax.Array, cfg, real=None):
    """Serving MoE: x (B, S, D), ``real`` (B, S) bool (None = every row)
    -> (out (B, S, D), pairs per held expert (n_held,) int32).

    Dropless: every pair a held expert receives is computed, whatever
    the batch, and padding rows are routed nowhere.

    The expert weights may stack the held experts of R layers,
    ``(R * n_held, ...)``, with ``p["layer"]`` this layer's index among
    them (the scanned MoE blocks of ``transformer._run_stack``): a pair
    then groups at ``layer * n_held`` past its held expert, so the
    grouped products read the layer's experts in place and the other
    layers' groups stay empty."""
    b, s, d = x.shape
    n = cfg.n_experts_held
    x2 = x.reshape(b * s, d)
    real = (jnp.ones((b * s,), bool) if real is None
            else jnp.asarray(real, bool).reshape(b * s))
    gate, eid, _ = route(p, x2, cfg)
    local = eid - cfg.expert_start
    pairs = jnp.sum((local[..., None] == jnp.arange(n))
                    & real[:, None, None], axis=(0, 1), dtype=jnp.int32)
    take = (local >= 0) & (local < n) & real[:, None]
    grp = jnp.where(take, p.get("layer", 0) * n + local,
                    p["w_gate"].shape[0])
    out = _held_experts(x2, gate, grp, p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        out = out + _shared(p, x2)
    return out.reshape(b, s, d), pairs


def _capacity(tokens: int, cfg) -> int:
    c = -(-int(tokens * cfg.top_k * cfg.capacity_factor)
          // cfg.num_experts)
    # floor at top_k (a group must fit one token's own experts), round to 4
    return max(cfg.top_k, -(-c // 4) * 4)


def moe_apply(p: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) -> (out (B, S, D), aux load-balance loss scalar).

    Hierarchical (per-sequence) dispatch — EXPERIMENTS.md §Perf deepseek
    iteration D1.  The previous global argsort-based dispatch is
    unsharddable (data-dependent global permutation): GSPMD replicated the
    (T*k, D) gather/scatter buffers on every device and all-reduced the
    full (E, C, D) expert output per layer (measured 28.7 TB collective
    bytes/step on deepseek-v2 train_4k).  Here every data-dependent index
    stays *within one sequence* (cumsum-of-one-hot positions, vmapped
    row-local scatter/gather) and the combine scatter uses static indices,
    so the batch axis stays DP-sharded end-to-end and the only model-axis
    traffic is the (B, E, C, D) buffer resharding to expert-parallel
    layout — the canonical MoE all-to-all, activation-sized.
    """
    b0, s0, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    assert cfg.n_experts_held == e, "capacity dispatch holds every expert"
    # decode (s=1): per-row dispatch would pay the top_k capacity floor per
    # token (measured 22x useful-FLOPs loss on mixtral decode_32k, §Perf
    # iter D3) — regroup tokens across the batch so capacity is shared
    if s0 == 1 and b0 > 1:
        from repro.dist.sharding import _dp_size, current_mesh
        mesh = current_mesh()
        dp = _dp_size(mesh) if mesh is not None else 16
        g = next((c for c in (dp, 16, 8, 4, 2) if b0 % c == 0), 1)
        b, s = g, b0 // g
        x = x.reshape(b, s, d)
    else:
        b, s = b0, s0
    cap = _capacity(s, cfg)

    gate, eid, probs = route(p, x, cfg)                      # (B, S, k)

    # Switch aux loss: E * sum_e (fraction routed to e) * (mean prob of e)
    onehot = (eid[..., None] == jnp.arange(e)).astype(jnp.int32)
    frac = onehot.any(2).astype(jnp.float32).mean((0, 1))
    aux = e * jnp.sum(frac * probs.mean((0, 1)))

    # ---- per-sequence positions: cumsum of one-hot along (S*k) -----------
    oh = onehot.reshape(b, s * k, e)
    cum = jnp.cumsum(oh, axis=1)                             # (B, S*k, E)
    flat_eid = eid.reshape(b, s * k)
    pos = jnp.take_along_axis(cum, flat_eid[..., None], -1)[..., 0] - 1
    keep = pos < cap
    dest = jnp.where(keep, flat_eid * cap + pos, e * cap)    # (B, S*k)
    src = jnp.arange(s * k) // k                             # static!

    # ---- row-local scatter into the expert buffer -------------------------
    def scat(xr, destr):
        return jnp.zeros((e * cap + 1, d), x.dtype).at[destr].set(
            xr[src], mode="drop", unique_indices=True)

    buf = jax.vmap(scat)(x, dest)                            # (B, E*cap+1, D)
    hidden = buf[:, :-1].reshape(b, e, cap, d)
    # expert-parallel layout: E over "model" (no-op when E % model != 0,
    # e.g. mixtral's 8 experts -> per-expert d_ff TP via the weight specs)
    hidden = constrain(hidden, "batch", "model", None, None)

    # ---- expert compute (grouped einsums, batched over B) ----------------
    act = jax.nn.silu(jnp.einsum("becd,edf->becf", hidden, p["w_gate"]))
    up = jnp.einsum("becd,edf->becf", hidden, p["w_up"])
    out_e = jnp.einsum("becf,efd->becd", act * up, p["w_down"])
    out_e = constrain(out_e, "batch", "model", None, None)
    out_rows = out_e.reshape(b, e * cap, d)

    # ---- row-local gather + static-index combine --------------------------
    def gath(bufr, destr):
        return bufr[jnp.minimum(destr, e * cap - 1)]

    slot_out = jax.vmap(gath)(out_rows, dest)                # (B, S*k, D)
    w = (gate.reshape(b, s * k) * keep).astype(x.dtype)
    weighted = slot_out * w[..., None]
    combined = weighted.reshape(b, s, k, d).sum(2)           # static combine

    if "shared" in p:
        combined = combined + _shared(p, x)
    return combined.reshape(b0, s0, d), aux
