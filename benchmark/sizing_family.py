"""``sizing.py``'s serve half for a configuration of any family:
``JAX_PLATFORMS=cpu python3 benchmark/sizing_family.py <cell>
[--hlo DIR]``. The weights, the cache and the engine's two programs are
the ones ``ray_tpu.serve.llm_engine.model.family(config)`` gives (one
paged pool, or a hybrid's three caches), compiled at their real size for
a described v5e with no chip attached; prints ``memory_analysis()`` and,
with ``--hlo``, writes each program's optimised HLO text there (the
shapes the ``trace_op_share`` selectors are written against). A compile
that passes is not a chip run. Run by hand; not a test."""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_programs(cell, devices) -> dict:
    """{"decode_step", "prefill_chunk"} -> the compiled program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine import model as paged_model

    config = cell.config
    model_config = spec.build_model_config(config)
    family = paged_model.family(model_config)
    engine = config["engine"]
    rows = engine["max_batch_size"]
    block = engine.get("block_size") or GLOBAL_CONFIG.llm_block_size
    chunk = engine.get("prefill_chunk") or GLOBAL_CONFIG.llm_prefill_chunk
    table = -(-engine["max_seq_len"] // block)
    chip = SingleDeviceSharding(devices[0])

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(lambda: family.init_params(
        model_config, jax.random.PRNGKey(0))), model_config.dtype)
    cache = on_chip(jax.eval_shape(lambda: family.init_cache(
        model_config, 1 + rows * table, block, rows, chunk)))
    # What each packer makes says how long each program's host array is.
    decode_rows = family.pack_decode_rows(rows, table, [])
    chunk_array = family.pack_prefill_chunk(chunk, table, [0], 0, [0], 0)
    i32 = jnp.int32
    return {
        "decode_step": family.make_engine_decode_step(
            model_config, block).lower(
                params, cache, on_chip(decode_rows, i32),
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip),
                None).compile(),
        "prefill_chunk": family.make_engine_prefill_chunk(
            model_config, block, chunk).lower(
                params, cache, on_chip(chunk_array, i32), None).compile(),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("cell")
    parser.add_argument("--hlo", default=None, metavar="DIR")
    args = parser.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from benchmark import sizing, spec
    from ray_tpu._private import jax_compat

    jax.config.update("jax_enable_compilation_cache", False)
    jax_compat.interpret_kernels = lambda: False
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    cell = spec.load_cell(args.cell)
    for name, compiled in compile_programs(cell, devices).items():
        sizing.report(cell.name, name, compiled)
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, f"{cell.name}.{name}.hlo.txt"),
                      "w") as f:
                f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
