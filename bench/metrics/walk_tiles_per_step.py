"""Weight tiles the weight store looked up per mixed step over the
window: the program's ``ServeMetrics.weight_walk_tiles`` (every tile of
every ``step_params()`` walk: each mixed step's and each admitted
request's) over the count of its ``mixed_step`` telemetry phase."""


def read(run):
    tiles = getattr(run.serve_metrics, "weight_walk_tiles", None)
    h = run.phases.get("mixed_step")
    if tiles is None or h is None or not h.n:
        return None
    return tiles / h.n
