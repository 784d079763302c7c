"""The readers of the program's own telemetry (``walk_tiles_per_step``,
``sched_host_ms``): on hand-built records with known answers, on a
record of a program that keeps neither (they read nothing), and on the
record of a small traced run on the CPU."""

import importlib.util
import pathlib

import pytest

import run
from repro.runtime.metrics import ServeMetrics
from repro.runtime.telemetry import Histogram
from test_harness import SMALL

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
READERS = ("walk_tiles_per_step", "sched_host_ms")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _hist(*values):
    h = Histogram()
    for v in values:
        h.record(v)
    return h


def _record(**kw):
    base = dict(model={}, page_size=4, peaks=None, setup_s=0, build_s=0,
                first_materialize_s=0, window_s=1.0, requests=[],
                serve_metrics=ServeMetrics(), phases={}, ticks=[],
                trace=None, trace_events=None)
    return run.RunRecord(**{**base, **kw})


def test_walk_tiles_per_step_reads_the_window_counter():
    m = ServeMetrics()
    for _ in range(6):                   # 2 admissions + 4 steps
        m.record_weight_walk(100)
    rec = _record(serve_metrics=m,
                  phases={"mixed_step": _hist(*[0.01] * 4)})
    assert _reader("walk_tiles_per_step")(rec) == 150


def test_sched_host_ms_reads_prepare_commit_and_admit_less_its_walks():
    # two steps: prepare 1 + 2 ms, commit 2 + 1; admit 43 ms, 40 of
    # them in its walks; the steps' own walks, dispatch and wait are not
    # the scheduler's
    phases = {
        "mixed_step": _hist(0.050, 0.050),
        "mixed_step.prepare": _hist(0.001, 0.002),
        "mixed_step.dispatch": _hist(0.003, 0.003),
        "mixed_step.wait": _hist(0.030, 0.030),
        "mixed_step.commit": _hist(0.002, 0.001),
        "weights.materialize": _hist(0.014, 0.014, 0.020, 0.020),
        "admit": _hist(0.043),
        "admit.walk": _hist(0.020, 0.020),
    }
    # (3 prepare + 3 commit + 3 admit) ms over 2 steps
    assert _reader("sched_host_ms")(_record(phases=phases)) == \
        pytest.approx(4.5)


class _OlderMetrics:
    """A program's ServeMetrics from before the weight-walk counters."""


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_its_inputs(name):
    """The program before these readers: no walk counter, and only the
    ``mixed_step``, ``admit`` and ``weights.materialize`` phases."""
    rec = _record(serve_metrics=_OlderMetrics(),
                  phases={"mixed_step": _hist(0.05), "admit": _hist(0.04),
                          "weights.materialize": _hist(0.03, 0.03)})
    assert _reader(name)(rec) is None


def test_traced_small_run_feeds_both_readers(monkeypatch):
    """A small traced run on the CPU (which has no device trace to
    reduce): every walk of the window is counted, one per admission and
    one per step, each of the same tiles, and the scheduler's own host
    work is a part of the step."""
    records = []
    monkeypatch.setattr(run.trace_reduce, "reduce", lambda *a, **kw: {
        "busy_s": 0.0, "window_s": 1.0, "idle_share": 1.0,
        "device_ops": [], "idle_gaps": []})
    monkeypatch.setattr(run, "load_metric_reader",
                        lambda name: records.append)
    res = run.run_cell(run.load_cell("phi3m-2L.decode"), 2147493001, 0.5,
                       True, overrides=SMALL)
    assert res["correct"], res["compared"]
    rec = records[0]
    m, steps = rec.serve_metrics, rec.phases["mixed_step"].n
    assert m.weight_walks == m.requests_admitted + steps
    assert m.weight_walks == rec.phases["weights.materialize"].n
    assert m.weight_walk_tiles % m.weight_walks == 0
    assert _reader("walk_tiles_per_step")(rec) == \
        m.weight_walk_tiles / steps
    assert rec.phases["admit.walk"].n == m.requests_admitted
    host_ms = _reader("sched_host_ms")(rec)
    assert 0 < host_ms < rec.phases["mixed_step"].mean() * 1e3
