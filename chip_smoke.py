"""Bring-up smoke run on one TPU chip: gemma2-2b at its published widths.

Three phases, each through the repository's own code:

  (a) kernel check -- the compiled ``paged_mixed_attention`` against a
      plain ``jax.numpy`` attention over gathered pages, at gemma2-2b's
      global-layer widths (8 query heads, 4 KV heads, head_dim 256,
      16-token bf16 pages), for Q = 1 (decode), Q = 128 (a prefill
      chunk) and Q = 5 (a 1 + 4 speculative verify), with the KV codec
      off and on;
  (b) main path -- ``repro.launch.serve`` at ``--scale full`` (all 26
      layers, d_model 2304, d_ff 9216, vocab 256000, bf16, random
      weights from seed 0, MLPs compressed into the weight store)
      serving 8 requests of 512 prompt tokens and 32 generated tokens
      over 4 slots on the mixed-step path (``pallas_paged`` attention,
      128-token prefill chunks, 16-token KV pages);
  (c) codec and speculation -- phase (b) again on the same engine with
      ``--kv-codec cluster --speculate ngram --draft-k 4`` and
      repetitive prompts, so draft tokens are proposed and verified.

Any failed check exits non-zero.  On success the last line of standard
output is one JSON object: ``{"ok": true, "device": {...}}``.  It needs
a TPU: on any other platform it exits non-zero before phase (a).  It
uses the first chip only and starts no other process.

    python chip_smoke.py

Timings printed on the way are from one unrepeated run.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time
import warnings

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# serve-phase arguments shared by (b) and (c)
SERVE_ARGS = ["--arch", "gemma2-2b", "--scale", "full", "--batch", "4",
              "--requests", "8", "--prompt-len", "512", "--gen", "32",
              "--attn-backend", "pallas_paged", "--prefill-chunk", "128",
              "--kv-page-size", "16", "--log-every", "0"]
CODEC_SPEC_ARGS = ["--kv-codec", "cluster", "--speculate", "ngram",
                   "--draft-k", "4", "--prompt-pattern", "64"]

# Phase (a) tolerance.  Kernel and reference both compute in f32 with
# HIGHEST matmul precision; the kernel folds pages into an online softmax
# one at a time (a different summation order) and Mosaic's exp/tanh are
# not XLA's, so outputs (weighted means of unit-normal values) differ by
# about 1e-6.  1e-4 is two orders above that and three below what a wrong
# mask, page or scale does to them (O(0.1)).
KERNEL_TOL = 1e-4


def reference_attention(q, k_pages, v_pages, table, lengths, q_lens, *,
                        softcap):
    """Plain jnp attention over each slot's pages gathered into one
    contiguous cache: query i of slot s sits at lengths[s] - q_lens[s] + i
    and sees keys at positions <= its own."""
    import jax
    import jax.numpy as jnp

    s_n, qn, h, d = q.shape
    kh = k_pages.shape[2]
    k = k_pages[table].reshape(s_n, -1, kh, d).astype(jnp.float32)
    v = v_pages[table].reshape(s_n, -1, kh, d).astype(jnp.float32)
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    sc = jnp.einsum("sqhd,skhd->shqk", q, k,
                    precision=jax.lax.Precision.HIGHEST)
    if softcap:
        sc = jnp.tanh(sc / softcap) * softcap
    qpos = (lengths - q_lens)[:, None] + jnp.arange(qn)[None]   # (S, Q)
    kpos = jnp.arange(k.shape[1])
    ok = kpos[None, None, :] <= qpos[:, :, None]                # (S, Q, K)
    sc = jnp.where(ok[:, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("shqk,skhd->sqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def kernel_check(cfg) -> list[str]:
    """Phase (a): compiled kernel vs the plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import kv_codec
    from repro.kernels.paged_attention import paged_mixed_attention

    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    page, slots, pages_per_slot = 16, 4, 34          # 544-token slots
    n_pages = slots * pages_per_slot + 1
    rng = np.random.default_rng(0)
    table = rng.permutation(np.arange(1, n_pages)).reshape(
        slots, pages_per_slot).astype(np.int32)
    lines = []
    for qn in (1, 128, 5):
        for codec in ("none", "cluster"):
            q_lens = np.array([qn, max(qn - 1, 1), qn, 1], np.int32)
            lengths = np.array([544, 300, qn, 17], np.int32)
            k = rng.standard_normal((n_pages, page, kh, d)).astype(np.float32)
            v = rng.standard_normal((n_pages, page, kh, d)).astype(np.float32)
            q = rng.standard_normal((slots, qn, h, d)).astype(np.float32) \
                * d ** -0.5
            kw = {}
            if codec == "cluster":
                kc, ks = kv_codec.encode(k, axes=(-2, -1))
                vc, vs = kv_codec.encode(v, axes=(-2, -1))
                k_ref = kv_codec.decode(kc, ks[..., None, None])
                v_ref = kv_codec.decode(vc, vs[..., None, None])
                k_in, v_in = kc, vc
                kw = dict(k_scales=ks, v_scales=vs)
            else:
                k_in = jnp.asarray(k, jnp.bfloat16)
                v_in = jnp.asarray(v, jnp.bfloat16)
                k_ref, v_ref = k_in, v_in
            t0 = time.monotonic()
            out = paged_mixed_attention(
                jnp.asarray(q), k_in, v_in, jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(q_lens),
                softcap_val=cfg.attn_logit_softcap, interpret=False, **kw)
            out = np.asarray(jax.block_until_ready(out))
            dt = time.monotonic() - t0
            want = np.asarray(reference_attention(
                jnp.asarray(q), jnp.asarray(k_ref), jnp.asarray(v_ref),
                jnp.asarray(table), jnp.asarray(lengths),
                jnp.asarray(q_lens), softcap=cfg.attn_logit_softcap))
            err = max(float(np.abs(out[i, :q_lens[i]]
                                   - want[i, :q_lens[i]]).max())
                      for i in range(slots))
            assert np.isfinite(out).all(), f"Q={qn} {codec}: non-finite"
            assert err <= KERNEL_TOL, \
                f"Q={qn} codec={codec}: max |kernel - ref| {err:.3e}"
            lines.append(f"kernel Q={qn:3d} codec={codec:7s}: max abs err "
                         f"{err:.2e} (tol {KERNEL_TOL:.0e}), first call "
                         f"{dt:.2f}s incl. compile")
    return lines


def serve_phase(serve, engine, argv, *, label):
    """Serve one phase through the launcher; fail on any fallback."""
    args = serve.parse_args(argv)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.monotonic()
        res = serve.serve(engine, args)
        dt = time.monotonic() - t0
    fallbacks = [str(w.message) for w in caught
                 if "downgraded" in str(w.message)]
    assert not fallbacks, f"{label}: capability fallback fired: {fallbacks}"
    done = res["completed"]
    assert len(done) == args.requests, (label, len(done))
    assert all(len(r.generated) == args.gen for r in done), label
    sched = res["scheduler"]
    assert sched.attn_backend == "pallas_paged", sched.attn_backend
    assert sched.prefill_chunk == args.prefill_chunk, sched.prefill_chunk
    assert engine.kernel_interpret is False
    hlo = sched._pool.lowered_mixed_step(engine.step_params()).as_text()
    assert "tpu_custom_call" in hlo, f"{label}: no Pallas kernel in step"
    print(f"[{label}] {len(done)} requests x {args.gen} tokens in "
          f"{dt:.1f}s (incl. compiles; one unrepeated run)", flush=True)
    return sched


def main() -> int:
    import jax

    from repro.configs.base import get_config
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} ({len(jax.devices())} visible, "
          f"using 1); compile cache {cache_dir}", flush=True)

    cfg = get_config("gemma2-2b")
    t0 = time.monotonic()
    for line in kernel_check(cfg):
        print(line, flush=True)
    print(f"[a] kernel check {time.monotonic() - t0:.1f}s", flush=True)

    t0 = time.monotonic()
    engine = serve.build_engine(serve.parse_args(SERVE_ARGS))
    print(f"[b] engine built in {time.monotonic() - t0:.1f}s (random "
          f"weights + weight-store compression)", flush=True)
    serve_phase(serve, engine, SERVE_ARGS, label="b")

    sched = serve_phase(serve, engine, SERVE_ARGS + CODEC_SPEC_ARGS,
                        label="c")
    assert sched.kv_codec == "cluster", sched.kv_codec
    assert sched._pool.codec
    assert sched.speculate == "ngram", sched.speculate
    m = engine.metrics
    assert m.spec_draft_tokens > 0, "no draft token was ever verified"
    print(f"[c] {m.spec_accepted_tokens}/{m.spec_draft_tokens} draft tokens "
          f"accepted", flush=True)

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory {stats['peak_bytes_in_use'] / 2**30:.2f} "
              f"GiB (one unrepeated run)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
