"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The TPU compiler is installed on CPU hosts too and compiles for a chip
that is described rather than present.  What it refuses here -- block
shapes off the (8, 128) tiling, reshapes Mosaic cannot lay out, a step
that does not fit the device -- the interpret-mode tests cannot see.
These cases compile the serving path's paged-attention kernel at
gemma2-2b's published widths for decode (Q = 1), a 128-token prefill
chunk and a 1 + 4 speculative verify, with the KV codec off and on, and
one whole mixed step of the full 26-layer model.  Nothing runs; results
and times need the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.paged_attention import paged_mixed_attention

PAGE = 16                 # tokens per KV page on the serving path
SLOTS = 4
PAGES_PER_SLOT = 34       # 512 prompt + 32 generated tokens per slot
N_PAGES = SLOTS * PAGES_PER_SLOT + 1


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host; the persistent compilation
    cache is off meanwhile (an entry written for a described chip cannot
    be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("codec", ["none", "cluster"])
@pytest.mark.parametrize("qn", [1, 128, 5])
def test_paged_kernel_compiles_at_gemma2_widths(one_chip, qn, codec):
    cfg = get_config("gemma2-2b")
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pool_dtype = jnp.int8 if codec == "cluster" else jnp.bfloat16
    args = [_sds((SLOTS, qn, h, d), jnp.float32, one_chip),
            _sds((N_PAGES, PAGE, kh, d), pool_dtype, one_chip),
            _sds((N_PAGES, PAGE, kh, d), pool_dtype, one_chip),
            _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip)]
    kw = dict(softcap_val=cfg.attn_logit_softcap, page_size=PAGE)
    if codec == "cluster":
        args += [_sds((N_PAGES, PAGE), jnp.float32, one_chip)] * 2

        def fn(q, k, v, t, ln, ql, ks, vs):
            return paged_mixed_attention(q, k, v, t, ln, ql, k_scales=ks,
                                         v_scales=vs, **kw)
    else:
        def fn(q, k, v, t, ln, ql):
            return paged_mixed_attention(q, k, v, t, ln, ql, **kw)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("qn,codec", [(128, "none"), (5, "cluster")])
def test_full_mixed_step_compiles_and_fits(one_chip, qn, codec):
    """All 26 layers of gemma2-2b in one mixed step over the page pools
    the scheduler builds for 4 slots of 544 tokens: compiled for the
    chip, holding the kernel, and within its 16 GB."""
    from repro.models.api import get_model
    from repro.runtime.metrics import ServeMetrics
    from repro.runtime.scheduler import SlotPool
    from repro.runtime.telemetry import NULL_TELEMETRY

    cfg = get_config("gemma2-2b").scaled(binarize_mlp=True)
    api = get_model(cfg)
    engine = type("Engine", (), dict(
        api=api, cfg=cfg, telemetry=NULL_TELEMETRY, metrics=ServeMetrics(),
        pos_offset=staticmethod(lambda n: n)))()
    pool = SlotPool(engine, SLOTS, PAGES_PER_SLOT * PAGE, page_size=PAGE,
                    backend="pallas_paged", kv_codec=codec)

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    step = functools.partial(api.mixed_step, cfg,
                             paged_flags=pool.paged_flags, page_size=PAGE,
                             interpret=False)
    args = [params, shaped(pool.kcache),
            _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip),
            _sds((SLOTS, qn), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip)]
    if pool.codec:
        args.append(shaped(pool.kscales))
        fn = jax.jit(lambda p, c, t, tok, pos, ql, sc:
                     step(p, c, t, tok, pos, ql, scales=sc),
                     donate_argnums=(1, 6))
    else:
        fn = jax.jit(lambda p, c, t, tok, pos, ql:
                     step(p, c, t, tok, pos, ql), donate_argnums=(1,))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert total < 16e9 * 0.9, total
