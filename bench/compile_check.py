"""Compile each cell's mixed step for a described TPU v5e chip, with no
chip attached, and print what the compiler says of it.

    JAX_PLATFORMS=cpu python bench/compile_check.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named), the program's
``mixed_step`` is compiled at the cell's real shapes -- its configuration,
its mix's slots and slot length, the page pools the scheduler builds --
for a decode tick (Q = 1) and a prefill-chunk tick (Q = the chunk), with
the weights as the engine serves them (bfloat16, binarised MLP).  It
prints the compile time and ``memory_analysis()`` of each; nothing runs,
so it says nothing of results or times on the chip.
"""

from __future__ import annotations

import functools
import os
import sys
import time

import run


def compile_cell(name: str, one_chip):
    """Yield one line per compiled step shape of the cell ``name``."""
    import jax
    import jax.numpy as jnp
    from repro.models.api import get_model
    from repro.runtime.metrics import ServeMetrics
    from repro.runtime.scheduler import SlotPool
    from repro.runtime.telemetry import NULL_TELEMETRY

    cell = run.load_cell(name)
    config, mix = cell["config"], cell["mix"]
    page = config["engine"]["kv_page_size"]
    chunk = config["engine"]["prefill_chunk"]
    slot_len = run.slot_length(config["engine"], mix)
    cfg = run.program_config(config, config["model"]).scaled(
        binarize_mlp=True)
    api = get_model(cfg)
    engine = type("Engine", (), dict(
        api=api, cfg=cfg, telemetry=NULL_TELEMETRY, metrics=ServeMetrics(),
        pos_offset=staticmethod(lambda n: n)))()
    pool = SlotPool(engine, mix["slots"], slot_len, page_size=page,
                    backend="pallas_paged")

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    kcache = jax.tree_util.tree_map(sds, pool.kcache)
    step = functools.partial(api.mixed_step, cfg,
                             paged_flags=pool.paged_flags, page_size=page,
                             interpret=False)
    fn = jax.jit(lambda p, c, t, tok, pos, ql: step(p, c, t, tok, pos, ql),
                 donate_argnums=(1,))
    s = mix["slots"]
    yield (f"{name}: {sum(pool.paged_flags)}/{len(pool.paged_flags)} "
           f"cache leaves paged, {pool.n_pages} pages of {page}")
    for qn in (1, chunk):
        args = (params, kcache,
                jax.ShapeDtypeStruct((s, pool.pages_per_slot), jnp.int32,
                                     sharding=one_chip),
                jax.ShapeDtypeStruct((s, qn), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one_chip))
        t0 = time.monotonic()
        compiled = fn.lower(*args).compile()
        dt = time.monotonic() - t0
        mem = compiled.memory_analysis()
        kernel = "tpu_custom_call" in compiled.as_text()
        yield (
            f"  Q={qn}: compiled in {dt:.1f} s, kernel in step: {kernel}, "
            f"arguments {mem.argument_size_in_bytes / 2**30:.3f} GiB, "
            f"outputs {mem.output_size_in_bytes / 2**30:.3f} GiB, "
            f"temporaries {mem.temp_size_in_bytes / 2**30:.3f} GiB, "
            f"aliased {mem.alias_size_in_bytes / 2**30:.3f} GiB")


def main(names: list[str]) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cells = names or [w["name"] for w in run.load_benchmark()["workloads"]]
    for name in cells:
        for line in compile_cell(name, one_chip):
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
