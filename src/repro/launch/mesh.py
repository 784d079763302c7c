"""Production meshes (a FUNCTION, not a module constant — importing this
module never touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# the model code places arrays with with_sharding_constraint, which needs
# Auto mesh axes (jax.make_mesh defaults to Explicit ones)
_AUTO = (AxisType.Auto, AxisType.Auto)


def make_production_mesh(*, multi_pod: bool = False):
    """Target: TPU v5e pods. 16x16 = 256 chips per pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_serving_mesh():
    """The one device a serving process runs on: a (1, 1) mesh over the
    first device, so one process holds one chip and the mesh axes the
    model code names still exist."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_AUTO,
                         devices=jax.devices()[:1])


def make_host_mesh(model: int = 1):
    """Whatever this process actually has (tests / examples on CPU)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_AUTO)
