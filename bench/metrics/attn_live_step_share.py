"""Share of the paged-attention kernel's grid steps that computed over
the window: the program's ``ServeMetrics.attn_grid_steps_live`` (steps
with a real query in their q block and a key one of them may see in
their pages) over ``attn_grid_steps`` (every step the grids walked), for
every paged layer call of every mixed step.  A program without these
counters reads nothing."""


def read(run):
    m = run.serve_metrics
    walked = getattr(m, "attn_grid_steps", None)
    live = getattr(m, "attn_grid_steps_live", None)
    if walked is None or live is None or not walked:
        return None
    return 100.0 * live / walked
