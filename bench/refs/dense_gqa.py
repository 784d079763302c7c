"""Plain reference of a dense decoder with grouped-query attention and a
binarised SwiGLU MLP, as the benchmark's configurations serve it.

It imports nothing of the program.  Weights come from the seed through
this file's own initialiser, which draws the same numbers as the model's
published-shape initialiser (one key per leaf, split in the same order);
``bench/tests/test_reference.py`` checks that at a small size.  The MLP
weights are binarised here, directly from those weights: sign(W) times
the mean |W| of each output channel, the mean taken over the input axis
in the weights' own dtype.

The forward is teacher-forced over whole sequences in float32 under
``jax.default_matmul_precision("highest")``, with no cache, no paging and
no kernel.  Every tensor the configuration stores in its dtype (bfloat16:
weights, layer outputs, the residual stream, keys and values) is rounded
to it at the same points; arithmetic between those points is float32.
The MLP applies sign() to its inputs, so any difference in a stored value
near zero flips a sign and moves the output by 2 * alpha: an all-float32
forward (``STORAGE["float32"]``, kept to show it) picks another token than
this one at some positions in ten.  ``STORAGE["float8"]`` is
the control: the same forward storing float8_e4m3 with a per-tensor
power-of-two scale, the precision below bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(x):
    top = jnp.max(jnp.abs(x))
    scale = jnp.where(top > 0, 2.0 ** jnp.floor(jnp.log2(448.0 / top)), 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


STORAGE = {"bfloat16": _bf16, "float8": _fp8, "float32": lambda x: x}


# -- weights ----------------------------------------------------------------

def _dense(key, d_in, d_out):
    return (jax.random.normal(key, (d_in, d_out)) * d_in ** -0.5
            ).astype(jnp.bfloat16)


def _embed(key, vocab, d):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(jnp.bfloat16)


def _layer_init(m, key):
    d, hd = m["d_model"], m["head_dim"]
    ks = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(ks[0], 4)
    k1, k2, k3 = jax.random.split(ks[1], 3)
    zeros = jnp.zeros((d,), jnp.bfloat16)
    return {
        "ln1": zeros, "ln2": zeros,
        "wq": _dense(kq, d, m["num_heads"] * hd),
        "wk": _dense(kk, d, m["num_kv_heads"] * hd),
        "wv": _dense(kv, d, m["num_kv_heads"] * hd),
        "wo": _dense(ko, m["num_heads"] * hd, d),
        "gate": _dense(k1, d, m["d_ff"]),
        "down": _dense(k2, m["d_ff"], d),
        "up": _dense(k3, d, m["d_ff"]),
    }


@functools.partial(jax.jit, static_argnums=0)
def _init(frozen, key):
    m = dict(frozen)
    n = m["num_layers"]
    keys = jax.random.split(key, 4 + n)
    layers = [_layer_init(m, jax.random.split(keys[2 + i], 1)[0])
              for i in range(n)]
    return {
        "embed": _embed(keys[0], m["vocab_size"], m["d_model"]),
        "lm_head": _embed(keys[1], m["vocab_size"], m["d_model"]).T,
        "final_norm": jnp.zeros((m["d_model"],), jnp.bfloat16),
        "layers": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
    }


def freeze(m: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def init_params(m: dict, key) -> dict:
    """bfloat16 weights from ``key``: {"embed", "lm_head", "final_norm",
    "layers": stacked per-layer leaves}."""
    if m["scan_pattern"] not in (["attn"], ["swa"]) or \
            m["mlp_act"] != "swiglu" or m["tie_embeddings"]:
        raise ValueError("dense_gqa covers one-kind attention stacks with "
                         "an untied head and a SwiGLU MLP")
    return _init(freeze(m), key)


def binarize(w) -> jnp.ndarray:
    """(d_in, d_out) weights -> sign(W) * alpha, alpha the mean |W| of each
    output channel over the input axis, in the weights' dtype."""
    wt = np.ascontiguousarray(np.asarray(w).T)          # (d_out, d_in)
    alpha = np.abs(wt).mean(axis=1)
    sign = jnp.where(w >= 0, 1.0, -1.0).astype(w.dtype)
    return sign * jnp.asarray(alpha)[None, :]


# -- forward ----------------------------------------------------------------

def _dot(a, b):
    return jnp.dot(a, b.astype(jnp.float32), precision=HIGHEST)


def _rms(x, g, eps, rnd):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return rnd(x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32)))


def _rope(x, positions, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (positions[:, None].astype(jnp.float32) * freqs)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(frozen, storage, lp, x):
    m = dict(frozen)
    rnd = STORAGE[storage]
    t = x.shape[0]
    h_n, kh, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(t)
    w = {k: rnd(v.astype(jnp.float32)) for k, v in lp.items()}
    h = _rms(x, w["ln1"], m["norm_eps"], rnd)
    q = rnd(_rope(rnd(_dot(h, w["wq"])).reshape(t, h_n, hd), pos,
                  m["rope_theta"]))
    k = rnd(_rope(rnd(_dot(h, w["wk"])).reshape(t, kh, hd), pos,
                  m["rope_theta"]))
    v = rnd(_dot(h, w["wv"])).reshape(t, kh, hd)
    k = jnp.repeat(k, h_n // kh, axis=1)
    v = jnp.repeat(v, h_n // kh, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q * hd ** -0.5, k, precision=HIGHEST)
    ok = pos[None, :] <= pos[:, None]
    if m["window"]:
        ok &= pos[None, :] > pos[:, None] - m["window"]
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    o = rnd(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    x = rnd(x + rnd(_dot(o.reshape(t, h_n * hd), w["wo"])))
    sx = jnp.where(_rms(x, w["ln2"], m["norm_eps"], rnd) >= 0, 1.0, -1.0)
    g = rnd(_dot(sx, w["gate"]))
    u = rnd(_dot(sx, w["up"]))
    sa = jnp.where(rnd(jax.nn.silu(g) * u) >= 0, 1.0, -1.0)
    return rnd(x + rnd(_dot(sa, w["down"])))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(frozen, storage, final_norm, lm_head, x):
    m = dict(frozen)
    rnd = STORAGE[storage]
    h = _rms(x, rnd(final_norm.astype(jnp.float32)), m["norm_eps"], rnd)
    return _dot(h, rnd(lm_head.astype(jnp.float32)))


def logits_at(m: dict, params: dict, seqs: list[np.ndarray],
              rows: list[np.ndarray], storage: str = "bfloat16",
              length: int | None = None, n_rows: int | None = None
              ) -> list[np.ndarray]:
    """Teacher-forced logits of each token sequence at its ``rows``
    (positions) -> one (len(rows), vocab) float32 array per sequence.

    Sequences are padded at the end to ``length`` (causal attention keeps
    padding out of every earlier position) and rows to ``n_rows`` so one
    compiled program of each kind serves them all; layers run one at a
    time, with the MLP binarised as it is reached, so the reference holds
    one layer's float32 copy at a time."""
    frozen = freeze(m)
    length = length or max(len(s) for s in seqs)
    n_rows = n_rows or max(len(r) for r in rows)
    rnd = STORAGE[storage]
    xs = []
    for s in seqs:
        ids = np.zeros(length, np.int32)
        ids[:len(s)] = s
        xs.append(rnd(params["embed"][jnp.asarray(ids)].astype(jnp.float32)))
    stacked = params["layers"]
    with jax.default_matmul_precision("highest"):
        for i in range(m["num_layers"]):
            lp = {k: v[i] for k, v in stacked.items()}
            for name in ("gate", "up", "down"):
                lp[name] = binarize(lp[name])
            xs = [_layer(frozen, storage, lp, x) for x in xs]
        out = []
        for x, r in zip(xs, rows):
            pick = np.full(n_rows, r[-1])
            pick[:len(r)] = r
            lg = _head(frozen, storage, params["final_norm"],
                       params["lm_head"], _rows(x, jnp.asarray(pick)))
            out.append(np.asarray(lg)[:len(r)])
    return out


@jax.jit
def _rows(x, pick):
    return x[pick]
