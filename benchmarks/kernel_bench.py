"""Paged-attention kernel microbenchmarks: PR-8 launch shape vs tuned.

Three sections, all emitted into one JSON report (``--out``):

1. **Kernel sweep**: the paged mixed-attention kernel timed directly on
   synthetic pools, ``pr8`` launch shape (unpadded pool, one page DMA
   per grid step) vs ``tuned`` (pool rows padded to the 8-sublane tile,
   ``pages_per_step >= 2`` so the next grid step's page DMAs overlap
   this step's compute).  Swept over page size x decode/mixed Q x codec.
   Outputs are asserted numerically equivalent between variants.

2. **Autotune**: ``runtime.autotune.tune_kernel`` sweeping
   ``(q_block, pages_per_step)`` on a reduced minitron-8b geometry —
   the winner the serve path picks up under ``--kernel-tune auto`` —
   plus its memoisation key.

3. **Serve identity**: the same request mix served end-to-end under
   the gathered oracle, the PR-8 kernel launch (``kernel_tune="off"``)
   and the tuned launch (``kernel_tune="0,2"``), under both KV codecs.
   Tokens must be identical within each codec — the tiling padding and
   multi-page DMAs are layout changes, not numerics changes.

On the CPU the kernel runs through the Pallas interpreter
(``repro.kernels.platform.interpret_mode``): timings are then CPU
interpreter figures, not TPU-compiled speed.

Run:  PYTHONPATH=src python benchmarks/kernel_bench.py
      PYTHONPATH=src python benchmarks/kernel_bench.py --smoke \
          --out BENCH_kernels.json
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

TILE_SUBLANE = 8   # f32 sublane tile: pool page rows pad to this


def _round_up(n: int, tile: int) -> int:
    return -(-n // tile) * tile


# ---------------------------------------------------------------------------
# synthetic pools (same layout the SlotPool builds)
# ---------------------------------------------------------------------------

def make_case(rng, *, n_slots, pages_per_slot, page, kh, d, dv, q, h,
              codec, padded):
    """Pools + table + queries for one kernel launch.

    ``padded`` pads the page row dim to the sublane tile (feature dims
    here are chosen lane-aligned already, as real head dims are); codec
    pools hold int8 codes + per-(page, token) f32 scales, zero-padded
    rows decoding to exactly 0 by the codebook's ZERO_CODE convention.
    """
    from repro.kernels.kv_codec import MAX_CODE

    rows = _round_up(page, TILE_SUBLANE) if padded else page
    n_pages = n_slots * pages_per_slot + 1          # page 0 = dummy
    table = np.zeros((n_slots, pages_per_slot), np.int32)
    table.flat[:] = rng.permutation(n_pages - 1)[:table.size] + 1
    lengths = np.full((n_slots,), pages_per_slot * page, np.int32)
    q_arr = rng.standard_normal((n_slots, q, h, d)).astype(np.float32)
    q_lens = np.full((n_slots,), q, np.int32)

    def pool(feat):
        live = rng.standard_normal(
            (n_pages, page, kh, feat)).astype(np.float32)
        out = np.zeros((n_pages, rows, kh, feat), np.float32)
        out[:, :page] = live
        return out

    case = dict(q=q_arr, table=table, lengths=lengths, q_lens=q_lens,
                page_size=page if padded else 0)
    if not codec:
        case.update(k_pages=pool(d), v_pages=pool(dv))
        return case

    def codes():
        out = np.zeros((n_pages, rows, kh, d), np.int8)
        out[:, :page] = rng.integers(
            -MAX_CODE, MAX_CODE + 1, (n_pages, page, kh, d), dtype=np.int64)
        return out

    def scales():
        out = np.zeros((n_pages, rows), np.float32)
        out[:, :page] = rng.uniform(0.5, 2.0, (n_pages, page))
        return out

    case.update(k_pages=codes(), v_pages=codes(), k_scales=scales(),
                v_scales=scales())
    return case


def run_case(case, *, pps, q_block, interpret):
    import jax

    from repro.kernels.paged_attention import paged_mixed_attention

    return jax.block_until_ready(paged_mixed_attention(
        case["q"], case["k_pages"], case["v_pages"], case["table"],
        case["lengths"], case["q_lens"],
        k_scales=case.get("k_scales"), v_scales=case.get("v_scales"),
        page_size=case["page_size"], pages_per_step=pps, q_block=q_block,
        interpret=interpret))


def bench(fn, repeats):
    fn()                                            # warmup + compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1000.0


# ---------------------------------------------------------------------------
# section 1: kernel sweep, pr8 launch shape vs tuned
# ---------------------------------------------------------------------------

VARIANTS = {
    # PR-8 launch: physical page per grid step, no layout padding
    "pr8": dict(padded=False, pps=1),
    # hardware-shaped launch: sublane-padded pool rows, two page DMAs
    # per grid step (double buffering)
    "tuned": dict(padded=True, pps=2),
}


def kernel_sweep(smoke: bool, seed: int, repeats: int) -> list:
    from repro.kernels.platform import interpret_mode

    interpret = interpret_mode()
    pages = (8,) if smoke else (4, 8, 16)
    qs = (1,) if smoke else (1, 8)
    pages_per_slot = 4 if smoke else 8
    kh, h, d, dv = 2, 4, 128, 128                   # lane-aligned dims
    print(f"kernel sweep: {len(pages)} page sizes x Q {qs} x codec "
          f"{{fp,cluster}}, 4 slots x {pages_per_slot} pages, "
          f"kh={kh} h={h} d={d} "
          f"({'interpreted' if interpret else 'TPU-compiled'})")
    print(f"{'codec':>8} {'page':>5} {'Q':>3} | {'pr8 ms':>8} | "
          f"{'tuned ms':>8} | {'speedup':>7}")
    rows = []
    for codec in (False, True):
        for page in pages:
            for q in qs:
                outs = {}
                row = dict(codec="cluster" if codec else "none",
                           page=page, q=q)
                for label, v in VARIANTS.items():
                    # identical draws per variant: only the layout differs
                    rng = np.random.default_rng(seed)
                    case = make_case(
                        rng, n_slots=4, pages_per_slot=pages_per_slot,
                        page=page, kh=kh, d=d, dv=dv, q=q, h=h,
                        codec=codec, padded=v["padded"])
                    kw = dict(pps=v["pps"], q_block=0, interpret=interpret)
                    outs[label] = run_case(case, **kw)
                    row[f"{label}_ms"] = bench(
                        lambda case=case, kw=kw: run_case(case, **kw),
                        repeats)
                # layout changes must not change the math
                np.testing.assert_allclose(
                    outs["tuned"], outs["pr8"], rtol=2e-6, atol=2e-6)
                row["speedup"] = row["pr8_ms"] / row["tuned_ms"]
                rows.append(row)
                print(f"{row['codec']:>8} {page:>5} {q:>3} | "
                      f"{row['pr8_ms']:>8.2f} | {row['tuned_ms']:>8.2f} | "
                      f"{row['speedup']:>6.2f}x")
    return rows


# ---------------------------------------------------------------------------
# section 2: the autotuner's pick on a reduced serving geometry
# ---------------------------------------------------------------------------

def autotune_report(smoke: bool) -> dict:
    from repro.configs.base import get_config
    from repro.kernels.platform import interpret_mode
    from repro.runtime.autotune import tune_kernel

    cfg = get_config("minitron-8b").scaled(
        dtype="float32", vocab_size=128, num_layers=2, scan_repeats=2,
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128)
    interpret = interpret_mode()
    picks = {}
    for q in (1,) if smoke else (1, 8):
        r = tune_kernel(cfg, 8, q, codec=True, interpret=interpret,
                        repeats=1 if smoke else 3)
        picks[f"Q={q}"] = {k: r[k] for k in
                           ("q_block", "pages_per_step", "best_ms")}
        print(f"autotune minitron-8b page=8 Q={q}: q_block={r['q_block']} "
              f"pages_per_step={r['pages_per_step']} "
              f"({r['best_ms']:.2f} ms best of {len(r['timings'])})")
    return picks


# ---------------------------------------------------------------------------
# section 3: end-to-end token identity, oracle vs pr8 vs tuned launches
# ---------------------------------------------------------------------------

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
    launches = {
        "oracle": dict(attn_backend="gathered"),
        "pr8": dict(attn_backend="pallas_paged", kernel_tune="off"),
        "tuned": dict(attn_backend="pallas_paged", kernel_tune="0,2"),
    }
    print(f"\nserve identity: {n} requests, page size 4, both codecs, "
          f"launches {list(launches)}")
    report = {}
    for kv_codec in ("none", "cluster"):
        # chunked prefill exercises the mixed-step path; the gathered
        # backend's chunked install now quantises rows through the codec
        # before attention (same fixed point the in-pool mixed-step
        # write reaches), so the cross-backend oracle runs chunked under
        # both codecs
        chunk = dict(prefill_chunk=4)
        toks = {}
        for label, kw in launches.items():
            engine = ServeEngine(cfg, params, compress=True)
            sched = Scheduler(engine, batch_size=2, slot_len=slot_len,
                              buckets=(32,), kv_page_size=4,
                              kv_codec=kv_codec, **chunk, **kw)
            for prompt, gen in reqs:
                sched.submit(prompt, gen)
            done = sched.run()
            assert len(done) == n
            toks[label] = [list(map(int, r.generated)) for r in
                           sorted(done, key=lambda r: r.rid)]
        for label in ("pr8", "tuned"):
            assert toks[label] == toks["oracle"], (
                f"kv_codec={kv_codec}: {label} launch changed tokens "
                f"vs the gathered oracle")
        print(f"  kv_codec={kv_codec}: pr8 == tuned == gathered oracle "
              f"({sum(len(t) for t in toks['oracle'])} tokens)")
        report[kv_codec] = dict(identical=True, tokens=toks["oracle"])
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid + repeats for CI")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=None,
                    help="timing repeats per variant (default 5, smoke 2)")
    ap.add_argument("--out", type=str, default=None,
                    help="write the JSON report (e.g. BENCH_kernels.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    repeats = args.repeats or (2 if args.smoke else 5)

    rows = kernel_sweep(args.smoke, args.seed, repeats)
    picks = autotune_report(args.smoke)
    identity = serve_identity(args.smoke, args.seed)

    best = max((r for r in rows if r["page"] >= 8),
               key=lambda r: r["speedup"])
    print(f"\nbest speedup at page >= 8: {best['speedup']:.2f}x "
          f"(codec={best['codec']}, page={best['page']}, Q={best['q']})")
    if not args.smoke:
        # the PR's acceptance bar; skipped in --smoke where the tiny
        # grid + CI-runner jitter make timing ratios unreliable
        assert best["speedup"] >= 1.15, \
            f"tuned kernel speedup {best['speedup']:.2f}x < 1.15x"

    if args.out:
        report = dict(
            generated_by="benchmarks/kernel_bench.py",
            smoke=args.smoke, seed=args.seed, repeats=repeats,
            variants={k: dict(v) for k, v in VARIANTS.items()},
            kernel_sweep=rows, autotune=picks,
            serve_identity={k: v["identical"] for k, v in identity.items()},
            best_speedup_page_ge8=best["speedup"])
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
