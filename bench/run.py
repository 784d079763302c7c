"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/mixes/<mix>.json``).  The run drives the program's public
serving API only -- ``ServeEngine(cfg, params, compress=True)``,
``Scheduler(...)``, ``submit`` and ``run`` -- on the mixed-step path with
``attn_backend="pallas_paged"``; codec, speculation, prefix sharing and
kernel tuning stay at their defaults (off).

Set-up is everything before the first timed round: imports, weights from
the seed (one jitted call on the chip), the engine's host compression of
the MLPs, the first ``step_params()`` (every weight tile decoded), and a
warm-up round that compiles every step shape the timed rounds use, from
JAX's persistent compilation cache in ``.jax_cache/`` at the root of the
checkout.  The window then serves rounds of the mix until ``--seconds``
have passed; the round in flight finishes and counts, and every rate is
all the work of all rounds over their whole wall time.  ``--trace 1``
runs the same window under the JAX profiler and reports the per-layer
metrics instead of the end-to-end ones.

After the window, ``memory_peak_bytes`` is read, the program's state is
freed, and a sample of the finished requests is checked against the
configuration's plain reference (``bench/correct.py``).

It needs the chips the cell asks for: with no TPU, or too few, it exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

# ruff: noqa: E402
import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import correct
import trace_reduce
import traffic

COMPILE_CACHE = ROOT / ".jax_cache"
WINDOW_SPAN = "bench.window"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell ``name`` of BENCHMARK.json with its configuration file,
    its mix and the metrics it reports."""
    bm = load_benchmark()
    wl = {w["name"]: w for w in bm["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    cell = wl[name]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as f:
        config = json.load(f)

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {"workload": cell, "config": config,
            "mix": traffic.load_mix(cell["traffic"]),
            "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
            "per_layer": [m for m in bm["per_layer"] if mine(m)]}


def load_metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def program_config(config: dict, model: dict):
    """The program's config named by the file, with every size the file
    states; the file is the truth, so any key the program lacks fails."""
    from repro.configs.base import get_config
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in model.items()}
    cfg = get_config(config["program_config"]).scaled(**fields)
    assert all(getattr(cfg, k) == v for k, v in fields.items())
    return cfg


def slot_length(engine: dict, mix: dict) -> int:
    """Cache rows per slot: what the mix needs (``traffic.slot_length``),
    and at least the engine's ``min_slot_len`` where it sets one."""
    return max(traffic.slot_length(mix, engine["prefill_chunk"],
                                   engine["kv_page_size"]),
               engine.get("min_slot_len", 0))


def weight_key(seed: int):
    """The PRNG key the weights are drawn from: a seed of up to 64 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass
class RunRecord:
    """What a run measured; ``bench/metrics/<name>.py`` readers take
    their numbers from it."""
    model: dict
    page_size: int
    peaks: dict | None
    setup_s: float
    build_s: float
    first_materialize_s: float
    window_s: float
    requests: list            # (prompt_len, n_generated, t_first, t_done)
    serve_metrics: object     # the program's ServeMetrics of the window
    phases: dict              # the program's telemetry phase histograms
    ticks: list               # (poss, q_lens) of every mixed step
    trace: dict | None        # trace_reduce.reduce() of the window
    trace_events: object      # trace_reduce.Trace, or None


class _Counter:
    """Compiles seen by JAX's monitoring hooks, from one point on."""

    def __init__(self):
        self.compiles = 0
        self.cache_misses = 0

    def on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def _wrap(obj, attr, name, record=None):
    """Put a profiler span (and an argument recorder) around obj.attr."""
    import jax
    inner = getattr(obj, attr)

    @functools.wraps(inner)
    def wrapped(*a, **kw):
        if record is not None:
            record(a)
        with jax.profiler.TraceAnnotation(name):
            return inner(*a, **kw)
    setattr(obj, attr, wrapped)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             overrides: dict | None = None) -> dict:
    """Set up, serve the window, check the outputs -> the result dict
    (without the device check, which ``main`` makes first).

    ``overrides`` ({"model": ..., "engine": ..., "mix": ...}) shrinks a
    cell for the tests, which drive it on the CPU."""
    import jax
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_serving_mesh
    from repro.models.api import get_model
    from repro.runtime import Scheduler, ServeEngine, ServeMetrics, Telemetry

    over = overrides or {}
    config = cell["config"]
    model = {**config["model"], **over.get("model", {})}
    mix = {**cell["mix"], **over.get("mix", {})}
    eng = {**config["engine"], **over.get("engine", {})}
    page, chunk = eng["kv_page_size"], eng["prefill_chunk"]
    slot_len = slot_length(eng, mix)
    cfg = program_config(config, model)
    key = weight_key(seed)
    counter = _Counter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)

    with shd.use_mesh(make_serving_mesh()):
        t0 = time.monotonic()
        params = jax.jit(functools.partial(get_model(cfg).init_params,
                                           cfg))(key)
        engine = ServeEngine(cfg, params, compress=True,
                             telemetry=Telemetry() if trace else None)
        del params
        build_s = time.monotonic() - t0
        t0 = time.monotonic()
        jax.block_until_ready(engine.step_params())
        first_materialize_s = time.monotonic() - t0
        sched = Scheduler(engine, batch_size=mix["slots"], slot_len=slot_len,
                          prefill_chunk=chunk,
                          prefill_budget=eng["prefill_budget"],
                          kv_page_size=page,
                          attn_backend="pallas_paged",
                          buckets=tuple(eng["buckets"]))
        for prompt, n in traffic.warmup_round(mix, cfg.vocab_size, seed,
                                              chunk):
            sched.submit(prompt, n)
        sched.run()

        ticks: list = []
        if trace:
            _wrap(engine, "mixed_step", "bench.mixed_step",
                  lambda a: ticks.append((a[4], a[5])))
            _wrap(engine.store, "materialize", "bench.materialize")
            _wrap(sched, "submit", "bench.submit")
            _wrap(sched, "run", "bench.run")
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
        engine.metrics = ServeMetrics()
        engine.telemetry.phases.clear()
        setup_s = time.monotonic() - T_START
        compiles0 = counter.compiles
        if trace:
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        reqs, rounds = [], 0
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            while True:
                reqs += [sched.submit(p, n) for p, n in traffic.make_round(
                    mix, cfg.vocab_size, seed, rounds)]
                sched.run()
                rounds += 1
                if time.monotonic() - t0 >= seconds:
                    break
        window_s = time.monotonic() - t0
        if trace:
            jax.profiler.stop_trace()
        compiles = counter.compiles - compiles0

        devices = jax.devices()
        stats = [d.memory_stats() or {} for d in devices]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        served = [(r.prompt, list(r.generated)) for r in reqs if r.done]
        record = RunRecord(
            model=model, page_size=page,
            peaks=_peaks(devices[0].device_kind, required=False),
            setup_s=setup_s, build_s=build_s,
            first_materialize_s=first_materialize_s, window_s=window_s,
            requests=[(r.prompt_len, len(r.generated), r.t_first, r.t_done)
                      for r in reqs if r.done],
            serve_metrics=engine.metrics,
            phases=dict(engine.telemetry.phases),
            ticks=[(np.asarray(p), np.asarray(q)) for p, q in ticks],
            trace=None, trace_events=None)
        del sched, engine
        gc.collect()

    if trace:
        record.trace_events = trace_reduce.load(trace_reduce.find_xplane(
            log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        record.trace = trace_reduce.reduce(record.trace_events,
                                           window_span=WINDOW_SPAN)

    ref = correct.load_reference(config["reference"])
    picked = correct.sample(served, seed)
    values = correct.compare(ref, model, ref.init_params(model, key),
                             [served[i] for i in picked], slot_len,
                             traffic.max_output_len(mix),
                             config["correct"]["wide_gap"])
    ok, compared = correct.judge(values, config["correct"]["limits"])

    print(f"rounds {rounds}, requests {len(reqs)}, window {window_s:.3f} s, "
          f"compiles in window {compiles}, compile-cache misses "
          f"{counter.cache_misses}; compared {len(picked)} requests: "
          + ", ".join(f"{k} {v}" for k, v in values.items()),
          file=sys.stderr)
    metrics = {}
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    for spec in wanted:
        v = (load_metric_reader(spec["name"])(record) if trace
             else _end_to_end(spec["name"], record))
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": ok, "attempted": len(reqs),
              "failed": len(reqs) - len(served), "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        result["breakdown"] = {"device_ops": record.trace["device_ops"],
                               "idle_gaps": record.trace["idle_gaps"]}
    result["compared"] = compared
    return result


def _end_to_end(name: str, r: RunRecord) -> float:
    if name == "setup_s":
        return r.setup_s
    if name == "out_tok_s":
        return sum(n for _, n, _, _ in r.requests) / r.window_s
    if name == "prompt_tok_s":
        return sum(p for p, _, _, _ in r.requests) / r.window_s
    if name == "tpot_p90_ms":
        tpot = [(done - first) / (n - 1) for _, n, first, done in r.requests
                if n > 1]
        return float(np.percentile(tpot, 90)) * 1e3
    raise KeyError(f"no end-to-end metric {name!r}")


def _peaks(kind: str, required: bool = True) -> dict | None:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table and required:
        raise SystemExit(f"device {kind!r} is not in bench/peaks.json")
    return table.get(kind)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    want = cell["workload"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: the cell needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    _peaks(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
