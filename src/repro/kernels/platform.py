"""Which way the Pallas kernels run on the backend JAX found.

One decision for every call site: the Pallas interpreter on the CPU (how
the tests run the kernels), the compiled kernel on a TPU, and an error
anywhere else -- a kernel never falls back to the interpreter silently on
an accelerator it was not written for.
"""

from __future__ import annotations

import jax


def interpret_mode(platform: str | None = None) -> bool:
    """True on ``cpu``, False on ``tpu``; raises for any other platform.

    ``platform`` defaults to ``jax.default_backend()``."""
    platform = platform or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel path for platform {platform!r}: the kernels "
        "compile for TPU and run interpreted on CPU only")
