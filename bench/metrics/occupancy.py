"""Percent of decode lanes that carried an active request, over the
window's mixed steps: the program's ``ServeMetrics.occupancy()``."""


def read(run):
    m = run.serve_metrics
    return 100.0 * m.occupancy() if m.capacity_steps else None
