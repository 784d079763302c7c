"""Seconds to build the engine: weights drawn from the seed on the chip
and the program's host compression of every MLP matrix into the weight
store (``ServeEngine(..., compress=True)``).  Host clock, around the
harness's call."""


def read(run):
    return run.build_s
