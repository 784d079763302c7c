"""Paged-attention kernel serve identity: the kernel against the oracle.

The same request mix served end to end under the gathered oracle and the
``pallas_paged`` kernel backend, under both KV codecs; tokens must be
identical within each codec.  The weights are served uncompressed: the
two backends sum attention in different orders, and the binarised MLP's
``sign()`` would turn that last-bit difference into other tokens.  The
report goes to one JSON file (``--out``).  The kernel's launch shape is
worked out from the shapes inside the kernel, so there is nothing to
sweep.

On the CPU the kernel runs through the Pallas interpreter
(``repro.kernels.platform.interpret_mode``).

Run:  PYTHONPATH=src python benchmarks/kernel_bench.py
      PYTHONPATH=src python benchmarks/kernel_bench.py --smoke \
          --out BENCH_kernels.json
"""

from __future__ import annotations

import argparse
import json

import numpy as np


def serve_identity(smoke: bool, seed: int) -> dict:
    import jax

    from repro.configs.base import get_config
    from repro.models.api import get_model
    from repro.runtime import Scheduler, ServeEngine

    cfg = get_config("minitron-8b").scaled(
        dtype="float32", vocab_size=128, num_layers=2, scan_repeats=2,
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128)
    params = jax.tree_util.tree_map(
        np.asarray, get_model(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(seed)
    n = 4 if smoke else 8
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(4, 16))),
             int(rng.integers(3, 9))) for _ in range(n)]
    slot_len = max(len(p) + g for p, g in reqs)
    backends = ("gathered", "pallas_paged")
    print(f"serve identity: {n} requests, page size 4, both codecs, "
          f"backends {list(backends)}")
    report = {}
    for kv_codec in ("none", "cluster"):
        # chunked prefill exercises the mixed-step path; the gathered
        # backend's chunked install quantises rows through the codec
        # before attention (same fixed point the in-pool mixed-step
        # write reaches), so the cross-backend oracle runs chunked under
        # both codecs
        toks = {}
        for backend in backends:
            engine = ServeEngine(cfg, params, compress=False)
            sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                              buckets=(32,), kv_page_size=4,
                              kv_codec=kv_codec, prefill_chunk=4,
                              attn_backend=backend)
            for prompt, gen in reqs:
                sched.submit(prompt, gen)
            done = sched.run()
            assert len(done) == n
            toks[backend] = [list(map(int, r.generated)) for r in
                             sorted(done, key=lambda r: r.rid)]
        assert toks["pallas_paged"] == toks["gathered"], (
            f"kv_codec={kv_codec}: the kernel backend changed tokens vs "
            f"the gathered oracle")
        print(f"  kv_codec={kv_codec}: pallas_paged == gathered oracle "
              f"({sum(len(t) for t in toks['gathered'])} tokens)")
        report[kv_codec] = dict(identical=True, tokens=toks["gathered"])
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="fewer requests, for CI")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default=None,
                    help="write the JSON report (e.g. BENCH_kernels.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    identity = serve_identity(args.smoke, args.seed)
    if args.out:
        report = dict(
            generated_by="benchmarks/kernel_bench.py",
            smoke=args.smoke, seed=args.seed,
            serve_identity={k: v["identical"] for k, v in identity.items()})
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
