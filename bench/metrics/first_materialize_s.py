"""Seconds of the first ``ServeEngine.step_params()``: every weight tile
decoded and the dense sign * alpha MLP weights rebuilt.  Host clock,
around the harness's call, until the weights are on the chip."""


def read(run):
    return run.first_materialize_s
