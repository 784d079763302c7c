"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The TPU compiler is installed on CPU hosts too and compiles for a chip
that is described rather than present.  What it refuses here -- block
shapes off the (8, 128) tiling, reshapes Mosaic cannot lay out, a step
that does not fit the device -- the interpret-mode tests cannot see.
These cases compile the serving path's paged-attention kernel at
gemma2-2b's published widths for decode (Q = 1), a 128-token prefill
chunk and a 1 + 4 speculative verify, and at phi3-medium's for decode
and a 64-token chunk, each with the KV codec off and on; at both
widths for a verify under a q block pinned for the chunk; one whole mixed
step of the full 26-layer gemma2-2b; the shared-latent kernel at
DeepSeek-V2's widths; and a scan of DeepSeek-V2 MoE layers, whose
grouped products must read each layer's experts in place.  Nothing runs; results
and times need the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os
import re

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
    kw = dict(softcap_val=cfg.attn_logit_softcap)
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


@pytest.mark.parametrize("codec", ["none", "cluster"])
@pytest.mark.parametrize("qn", [1, 64])
def test_paged_kernel_compiles_at_phi3_medium_widths(one_chip, qn, codec):
    """The GQA kernel at phi3-medium's widths: 40 query heads over 10 KV
    heads of 128, so a KV head's 4 query heads are the rows of one
    product per page; 16-token pages, 32 slots of 32 pages; decode and a
    64-token chunk (two q blocks of 128 rows)."""
    cfg = get_config("phi3-medium-14b")
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    slots, pages = 32, 32
    n_pages = slots * pages + 1
    pool_dtype = jnp.int8 if codec == "cluster" else jnp.bfloat16
    args = [_sds((slots, qn, h, d), jnp.float32, one_chip),
            _sds((n_pages, PAGE, kh, d), pool_dtype, one_chip),
            _sds((n_pages, PAGE, kh, d), pool_dtype, one_chip),
            _sds((slots, pages), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip)]
    if codec == "cluster":
        args += [_sds((n_pages, PAGE), jnp.float32, one_chip)] * 2

        def fn(q, k, v, t, ln, ql, ks, vs):
            return paged_mixed_attention(q, k, v, t, ln, ql, k_scales=ks,
                                         v_scales=vs)
    else:
        def fn(q, k, v, t, ln, ql):
            return paged_mixed_attention(q, k, v, t, ln, ql)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q_block", [4, 32])
@pytest.mark.parametrize("arch", ["phi3-medium-14b", "gemma2-2b"])
def test_explicit_q_block_compiles_at_verify_width(one_chip, arch, q_block):
    """A q block pinned for the chunk width applied at a 1 + 4
    speculative verify: ``gcd(5, q_block) = 1`` token is 4 or 2 query
    rows a KV head, which the chip refuses, so the kernel runs the block
    sized from the shapes instead."""
    cfg = get_config(arch)
    h, kh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qn = 5
    args = [_sds((SLOTS, qn, h, d), jnp.float32, one_chip),
            _sds((N_PAGES, PAGE, kh, d), jnp.bfloat16, one_chip),
            _sds((N_PAGES, PAGE, kh, d), jnp.bfloat16, one_chip),
            _sds((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip),
            _sds((SLOTS,), jnp.int32, one_chip)]

    def fn(q, k, v, t, ln, ql):
        return paged_mixed_attention(
            q, k, v, t, ln, ql, softcap_val=cfg.attn_logit_softcap,
            window=cfg.window, q_block=q_block)
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


@pytest.mark.parametrize("qn", [1, 16])
def test_latent_kernel_compiles_at_deepseek_v2_widths(one_chip, qn):
    """MLA's shared latent head at DeepSeek-V2's widths: 128 query heads
    over one 512-wide latent (the value pool too) and a 64-wide rope key,
    64-token pages, 128 slots of 8 pages -- every
    head of a q block in one product per page, within scoped VMEM."""
    cfg = get_config("deepseek-v2-236b")
    h, r, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.rope_head_dim
    slots, pages, page = 128, 8, 64
    n_pages = slots * pages + 1
    args = [_sds((slots, qn, h, r), jnp.float32, one_chip),
            _sds((n_pages, page, 1, r), jnp.bfloat16, one_chip),
            _sds((slots, pages), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip),
            _sds((slots, qn, h, dr), jnp.float32, one_chip),
            _sds((n_pages, page, 1, dr), jnp.bfloat16, one_chip)]

    def fn(q, c, t, ln, ql, q2, pe):
        return paged_mixed_attention(q, c, None, t, ln, ql, q2, pe,
                                     scale=0.1)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_scanned_moe_layers_read_experts_in_place(one_chip):
    """Two scanned DeepSeek-V2 MoE layers at the published widths (MLA,
    20 held experts of 5120 x 1536 in bf16, top-6 of 160) for a 128-slot
    decode tick, 768 pairs: the grouped products read each layer's
    experts from the whole stack, so no instruction makes a copy of a
    layer's expert stack, and the step's temporaries stay under one."""
    from repro.models import transformer
    from repro.models.api import get_model

    cfg = get_config("deepseek-v2-236b").scaled(
        num_layers=2, prefix_kinds=(), scan_repeats=2, experts_held=20,
        expert_start=0)
    api = get_model(cfg)
    slots = 128

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    cache = shaped(transformer.init_cache_specs(cfg, slots, 64))

    def fn(p, c, x):
        out, _, _, load = transformer._run_stack(cfg, p, c, x, pos=0)
        return out, load
    compiled = jax.jit(fn).lower(
        params, cache, _sds((slots, 1, cfg.d_model), jnp.bfloat16,
                            one_chip)).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    one_stack = 20 * 5120 * 1536 * 2
    assert not re.search(r"= bf16\[20,5120,1536\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < one_stack
