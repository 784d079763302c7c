"""Reduction of a profiler trace to the numbers the benchmark reports.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; ``jax.profiler.ProfileData`` reads it.
A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose "XLA
Ops" line has one event per operation run on the chip, and a host plane
(``/host:CPU``) whose lines hold the host's spans, among them the
``jax.profiler.TraceAnnotation`` spans the harness puts around its own
calls.  Times are in nanoseconds on one clock.

What is computed here, and nowhere else:

* busy time -- the union of the intervals in which an operation ran on a
  chip, inside the traced window, averaged over the chips;
* idle share -- 1 - busy / window;
* each idle gap, attributed to the innermost harness span open on the
  host at the gap's middle ("unattributed" where none is);
* the operations that took the most time, each by its own time (less the
  operations nested in it, as a layer loop holds its body's), and the
  time and count of the events a predicate picks (a kernel).

Nothing here imports the program.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns


@dataclass
class Trace:
    devices: list[list[Event]]      # per chip, its "XLA Ops" events
    spans: list[Event]              # the harness's host spans


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def short_name(name: str) -> str:
    """An operation's name without its HLO text: "%fusion.3 = f32[...]
    fusion(...)" -> "fusion.3"."""
    return name.split(" = ", 1)[0].lstrip("%")


def from_profile(pd) -> Trace:
    """``jax.profiler.ProfileData`` -> the events this module reads."""
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = [Event(ev.name, ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            devices.append(ops)
        elif plane.name == HOST_PLANE:
            spans += [Event(ev.name, ev.start_ns, ev.end_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)]
    return Trace(devices=devices, spans=spans)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events: list[Event], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def window_of(trace: Trace, span: str) -> tuple[float, float]:
    """(start, end) of the harness span named ``span`` -- the window the
    trace measures.  It must appear exactly once."""
    found = [e for e in trace.spans if e.name == span]
    if len(found) != 1:
        raise RuntimeError(f"span {span!r} appears {len(found)} times")
    return found[0].start, found[0].end


def busy_ns(events: list[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in _union(_clip(events, lo, hi)))


def idle_gaps(events: list[Event], lo: float, hi: float
              ) -> list[tuple[float, float]]:
    """Intervals of [lo, hi] in which no operation ran."""
    gaps, t = [], lo
    for a, b in _union(_clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(spans: list[Event], times: list[float]) -> list[str]:
    """For each of the ascending ``times``, the name of the innermost
    (latest-starting) harness span open then, or "unattributed"."""
    order = sorted(spans, key=lambda s: s.start)
    names, active, i = [], [], 0
    for t in times:
        while i < len(order) and order[i].start <= t:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > t]
        names.append(active[-1].name if active else "unattributed")
    return names


def self_times(events: list[Event], lo: float, hi: float
               ) -> dict[str, float]:
    """Time of each operation inside [lo, hi] less the time of the
    operations nested in it (a loop and its body's operations), by short
    name."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [event, start, end, child time]

    def close(frame):
        ev, a, b, child = frame
        out[short_name(ev.name)] = out.get(short_name(ev.name), 0.0) \
            + (b - a) - child
        if stack:
            stack[-1][3] += b - a

    for e in sorted((e for e in events if e.end > lo and e.start < hi),
                    key=lambda e: (e.start, -e.end)):
        a, b = max(e.start, lo), min(e.end, hi)
        while stack and stack[-1][2] < b:     # e is not nested in the top
            close(stack.pop())
        stack.append([e, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def kernel_time(trace: Trace, *, window_span: str, kernel
                ) -> tuple[float, float]:
    """(seconds, calls) of the events the predicate ``kernel`` picks in
    the window, per chip; the seconds are the union of their intervals."""
    lo, hi = window_of(trace, window_span)
    ns, calls = 0.0, 0
    for ev in trace.devices:
        picked = [e for e in ev if e.end > lo and e.start < hi and kernel(e)]
        ns += busy_ns(picked, lo, hi)
        calls += len(picked)
    n = max(len(trace.devices), 1)
    return ns / n * 1e-9, calls / n


def reduce(trace: Trace, *, window_span: str, top: int = 10) -> dict:
    """The trace's numbers over the window span: seconds, averaged over
    the chips, and the ``top`` operations and idle-gap owners."""
    if not trace.devices or not any(trace.devices):
        raise RuntimeError("the trace holds no operation on a TPU")
    lo, hi = window_of(trace, window_span)
    n = len(trace.devices)
    busy = sum(busy_ns(ev, lo, hi) for ev in trace.devices) / n
    per_op: dict[str, float] = {}
    gaps_by: dict[str, float] = {}
    for ev in trace.devices:
        for name, t in self_times(ev, lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + t
        gaps = idle_gaps(ev, lo, hi)
        names = attribute(trace.spans, [(a + b) / 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            gaps_by[name] = gaps_by.get(name, 0.0) + (b - a)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "idle_share": 1.0 - busy / (hi - lo),
        "device_ops": [[k, v / n * 1e-9] for k, v in top_ops],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in top_gaps],
    }
