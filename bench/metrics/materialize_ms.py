"""Mean milliseconds of the program's ``weights.materialize`` telemetry
span over the window: the weight store's per-tick walk of every decode
tile (all cache hits after set-up) on the host."""


def read(run):
    h = run.phases.get("weights.materialize")
    return h.mean() * 1e3 if h is not None and h.n else None
