"""Where the kernels run and where compiled programs are cached.

``interpret_mode`` is the one decision between the Pallas interpreter
(CPU) and the compiled kernel (TPU); any other platform is an error, not
a silent interpreter fallback.  ``enable_compile_cache`` leaves a set
``JAX_COMPILATION_CACHE_DIR`` alone and otherwise points JAX at the
checkout's fixed ``.jax_cache``; both cases are run in a child process on
the CPU so that this process's JAX configuration is untouched.
"""

import os
import pathlib
import subprocess
import sys

import pytest

from repro.kernels.platform import interpret_mode
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_interpret_mode_per_platform(platform, want):
    assert interpret_mode(platform) is want


@pytest.mark.parametrize("platform", ["gpu", "cuda", "metal"])
def test_interpret_mode_refuses_other_platforms(platform):
    with pytest.raises(RuntimeError, match=platform):
        interpret_mode(platform)


def test_interpret_mode_defaults_to_this_backend():
    import jax

    assert interpret_mode() is (jax.default_backend() == "cpu")


def test_default_cache_dir_is_fixed_in_the_checkout():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"


# compile one small program with the cache on and list what it wrote;
# the default directory is redirected into the test's tmp dir so the
# checkout is not written to
_CHILD = """
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.DEFAULT_DIR = pathlib.Path(sys.argv[1])
print("DIR", compile_cache.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T + 1.0)(jnp.ones((64, 64))).block_until_ready()
"""


def _run_child(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(tmp_path / "default")],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split("DIR ")[1].split()[0]


def _entries(path):
    return sorted(p.name for p in pathlib.Path(path).glob("*")) \
        if pathlib.Path(path).exists() else []


def test_cache_follows_env_var(tmp_path):
    chosen = _run_child(tmp_path, tmp_path / "from_env")
    assert chosen == str(tmp_path / "from_env")
    assert _entries(tmp_path / "from_env")
    assert not _entries(tmp_path / "default")


def test_cache_defaults_to_fixed_dir(tmp_path):
    chosen = _run_child(tmp_path, None)
    assert chosen == str(tmp_path / "default")
    assert _entries(tmp_path / "default")
