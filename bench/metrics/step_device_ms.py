"""Milliseconds the chip was busy per mixed step: the union of the
device-operation intervals in the traced window over the number of mixed
steps the harness saw dispatched in it."""


def read(run):
    if run.trace is None or not run.ticks:
        return None
    return run.trace["busy_s"] / len(run.ticks) * 1e3
