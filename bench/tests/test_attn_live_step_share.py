"""``attn_live_step_share``: the share of the paged-attention kernel's
grid steps that computed, read from the program's own counters -- on a
hand-built record with a known answer, on a program without the counters
(it reads nothing), and on the record of a small traced run on the CPU."""

import importlib.util
import pathlib

import run
from repro.runtime.metrics import ServeMetrics
from test_harness import SMALL

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def _read(rec):
    spec = importlib.util.spec_from_file_location(
        "reader_attn_live_step_share", METRICS / "attn_live_step_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


class _Record:
    def __init__(self, serve_metrics):
        self.serve_metrics = serve_metrics


def test_reads_live_over_walked():
    m = ServeMetrics()
    m.record_attn_grid_steps(1024, 100)
    m.record_attn_grid_steps(2048, 28)
    assert _read(_Record(m)) == 100.0 * 128 / 3072


class _OlderMetrics:
    """A program's ServeMetrics from before the grid-step counters."""


def test_reads_nothing_without_the_counters_or_a_step():
    assert _read(_Record(_OlderMetrics())) is None
    assert _read(_Record(ServeMetrics())) is None


def test_traced_small_run_feeds_the_reader(monkeypatch):
    """A small traced run on the CPU: every mixed step of the window
    counted its grid steps, some but not all of them live."""
    records = []
    monkeypatch.setattr(run.trace_reduce, "reduce", lambda *a, **kw: {
        "busy_s": 0.0, "window_s": 1.0, "idle_share": 1.0,
        "device_ops": [], "idle_gaps": []})
    monkeypatch.setattr(run, "load_metric_reader",
                        lambda name: records.append)
    res = run.run_cell(run.load_cell("phi3m-2L.decode"), 2147493001, 0.5,
                       True, overrides=SMALL)
    assert res["correct"], res["compared"]
    m = records[0].serve_metrics
    assert 0 < m.attn_grid_steps_live < m.attn_grid_steps
    assert 0 < _read(records[0]) < 100
