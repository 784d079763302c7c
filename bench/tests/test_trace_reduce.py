"""The reduction from trace to metrics, on a small trace with known
answers (``data/small_trace.pbtxt``)."""

import pathlib

import numpy as np
import pytest

import trace_reduce
from run import RunRecord

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    text = (DATA / "small_trace.pbtxt").read_text()
    return trace_reduce.from_profile(ProfileData.from_text_proto(text))


def test_reads_device_ops_and_harness_spans(trace):
    assert len(trace.devices) == 1 and len(trace.devices[0]) == 5
    assert sorted(s.name for s in trace.spans) == [
        "bench.materialize", "bench.run", "bench.window"]


def test_busy_idle_and_gap_owners(trace):
    r = trace_reduce.reduce(trace, window_span="bench.window")
    # busy: 1000-3000, 4000-6000 (union of the overlap), 7000-7500,
    # 9800-10000
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4700e-9)
    assert r["idle_share"] == pytest.approx(0.53)
    # gaps: 0-1000 in the window alone, 3000-4000 in run, 6000-7000 and
    # 7500-9800 in materialize (the innermost span open at the gap's
    # middle)
    assert dict(r["idle_gaps"]) == pytest.approx({
        "bench.materialize": 3300e-9, "bench.run": 1000e-9,
        "bench.window": 1000e-9})
    # fusion.2 overlaps the kernel: it is not nested in it, so both keep
    # their own time; the layer loop holds fusion.3 and keeps the rest
    assert dict(r["device_ops"]) == pytest.approx({
        "fusion.1": 2200e-9, "paged_mixed_attention.3": 1000e-9,
        "fusion.2": 1500e-9, "fusion.4": 500e-9})


def _roofline_reader():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "roofline", DATA.parents[1] / "metrics" / "paged_attn_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_is_matched_by_its_own_name_not_as_an_operand(trace):
    mod = _roofline_reader()
    picked = [trace_reduce.short_name(e.name) for e in trace.devices[0]
              if mod.is_kernel(e)]
    assert picked == ["paged_mixed_attention.3"]


def test_kernel_time_and_roofline_share(trace):
    mod = _roofline_reader()
    seconds, calls = trace_reduce.kernel_time(
        trace, window_span="bench.window", kernel=mod.is_kernel)
    assert (seconds, calls) == (pytest.approx(1000e-9), 1)
    # one query at position 3 sees 4 keys: 4 * 2 heads * 4 dims * 4 keys
    # = 128 operations; one 4-row page of K and V in bf16 (64 bytes) and
    # the query in and out in f32 (64 bytes) = 128 bytes.  At 128 GFLOP/s
    # and 64 GB/s the bytes bound it: 2 ns of the kernel's 1000 ns.
    model = dict(num_heads=2, num_kv_heads=1, head_dim=4, num_layers=1,
                 window=0)
    run = RunRecord(model=model, page_size=4,
                    peaks={"flops_bf16": 128e9, "hbm_bytes_per_s": 64e9},
                    setup_s=0, build_s=0, first_materialize_s=0,
                    window_s=1e-5, requests=[], serve_metrics=None,
                    phases={}, ticks=[(np.array([3]), np.array([1]))],
                    trace=None, trace_events=trace)
    assert mod.read(run) == pytest.approx(0.2)
