"""Training driver: data pipeline -> supervised jit step -> checkpoints.

Runs on whatever devices exist (CPU in this container; the production mesh
on a real pod).  End-to-end example driver for deliverable (b):

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b \
      --steps 200 --batch 8 --seq 256 --scale tiny --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import base as cfgs
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.dist import sharding as shd
from repro.dist.fault import FaultConfig, Supervisor
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.train.optimizer import OptConfig

TINY_OVERRIDES = dict(
    num_layers=2, scan_repeats=2, prefix_kinds=(), suffix_kinds=(),
    d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
    vocab_size=512, dtype="float32", window=64,
)


def tiny_config(arch: str):
    cfg = cfgs.get_config(arch)
    over = dict(TINY_OVERRIDES)
    if cfg.family == "ssm":
        over.update(num_heads=0, num_kv_heads=0, head_dim=0, d_ff=0,
                    ssm_heads=4, ssm_state=16, ssm_chunk=32, expand=2)
    if cfg.family == "moe":
        over.update(num_experts=4, top_k=2, moe_d_ff=128,
                    num_shared_experts=min(1, cfg.num_shared_experts))
        if cfg.prefix_kinds:
            over.update(prefix_kinds=cfg.prefix_kinds[:1], scan_repeats=1,
                        num_layers=2)
        if cfg.kv_lora_rank:
            over.update(num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48,
                        rope_head_dim=16, nope_head_dim=32, v_head_dim=32)
    if cfg.family == "hybrid":
        over.update(scan_repeats=1, suffix_kinds=("rglru",), num_layers=4,
                    lru_width=128, num_kv_heads=1)
    if cfg.family == "vlm":
        over.update(num_vision_tokens=8, num_kv_heads=1)
    if cfg.family == "audio":
        over.update(encoder_layers=2, encoder_seq=32, num_kv_heads=4)
    if cfg.scan_pattern and len(cfg.scan_pattern) > 1:
        over.update(scan_repeats=max(1, over["num_layers"]
                                     // len(cfg.scan_pattern)))
        over["num_layers"] = over["scan_repeats"] * len(cfg.scan_pattern) \
            + len(over.get("suffix_kinds", ()))
    return cfg.scaled(**over)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=cfgs.ARCH_NAMES)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = tiny_config(args.arch) if args.scale == "tiny" \
        else cfgs.get_config(args.arch)
    mesh = make_host_mesh()
    oc = OptConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps)

    with shd.use_mesh(mesh):
        step_fn, state_shardings = steps_mod.build_train_step(cfg, mesh, oc)
        state = steps_mod.init_train_state(cfg, mesh, jax.random.PRNGKey(0))

        sup = Supervisor(FaultConfig(ckpt_dir=args.ckpt_dir,
                                     ckpt_every=args.ckpt_every))
        state, start = sup.maybe_restore(state)

        data = SyntheticLM(cfg.vocab_size, args.batch, args.seq)
        pf = Prefetcher(data, start_step=start)
        losses = []
        t0 = time.monotonic()
        for step in range(start, args.steps):
            batch = {k: jax.numpy.asarray(v) for k, v in next(pf).items()}
            if cfg.family == "vlm":
                batch["vision_embeds"] = jax.numpy.zeros(
                    (args.batch, cfg.num_vision_tokens, cfg.d_model),
                    cfg.jnp_dtype)
            if cfg.family == "audio":
                batch["frame_embeds"] = jax.numpy.zeros(
                    (args.batch, cfg.encoder_seq, cfg.d_model),
                    cfg.jnp_dtype)
            state, report = sup.run_step(step_fn, state, batch, step)
            losses.append(report.loss)
            sup.maybe_save(state, step)
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.monotonic() - t0
                print(f"step {step:5d} loss {report.loss:8.4f} "
                      f"({dt / max(step - start + 1, 1):.2f}s/step)",
                      flush=True)
        pf.close()
        sup.finalize(state, args.steps)
        head = float(np.mean(losses[:10]))
        tail = float(np.mean(losses[-10:]))
        print(json.dumps({"first10_loss": head, "last10_loss": tail,
                          "events": sup.events[-5:]}))
        if args.steps >= 100:
            assert tail < head, "training did not reduce loss"


if __name__ == "__main__":
    main()
