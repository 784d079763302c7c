"""Percent of the traced window in which no operation ran on the chip
(1 - busy / window, from the device trace)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace["idle_share"]
