"""Sizing tool, run by hand: ``JAX_PLATFORMS=cpu python3
benchmark/sizing.py [cell ...] [--set key=value ...]``. Compiles each
cell's programs at their real size for a described v5e (no chip
attached; guide ``on-chip-measurement``, section 2) and prints
``memory_analysis()``. A compile that passes is not a chip run. Not a
test: ``tests/test_chip_compile.py`` is the one test file that may
describe a topology. ``--set num_hidden_layers=10`` tries another value
of a configuration's key; ``--set batch=4`` of the traffic's or the
engine's."""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GIB = 2.0 ** 30
V5E_USABLE = 15.75 * GIB  # memory_stats()["bytes_limit"] on the chip, PR 21


def report(cell: str, program: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.generated_code_size_in_bytes
    print(json.dumps({
        "cell": cell, "program": program,
        "arguments_gib": m.argument_size_in_bytes / GIB,
        "outputs_gib": m.output_size_in_bytes / GIB,
        "aliased_gib": m.alias_size_in_bytes / GIB,
        "temporaries_gib": m.temp_size_in_bytes / GIB,
        "arguments_plus_temporaries_gib": total / GIB,
        "share_of_15.75_gib": total / V5E_USABLE}), flush=True)


def size_train(cell, devices) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import spec
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.parallel.train_step import (
        TrainState,
        build_train_step,
        default_optimizer,
    )

    config, traffic = cell.config, cell.traffic
    trainer = config["trainer"]
    model_config = spec.build_model_config(config)
    init, loss_fn, axes = (spec.resolve(trainer[k])
                           for k in ("init", "loss", "logical_axes"))
    mesh = build_mesh(MeshConfig(**trainer["mesh"]),
                      devices=list(devices)[:cell.chips])
    optimizer = default_optimizer(**trainer["optimizer"])
    shardings = tree_shardings(mesh, axes(model_config))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(lambda: init(model_config, jax.random.PRNGKey(0))),
        shardings)
    by_shape = {(p.shape, p.dtype): p.sharding for p in jax.tree.leaves(params)}
    replicated = NamedSharding(mesh, P())
    # AdamW's moments mirror the parameters and take their shardings.
    opt_state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype,
            sharding=by_shape.get((s.shape, s.dtype), replicated)
            if s.ndim else replicated),
        jax.eval_shape(optimizer.init, params))
    state = TrainState(params, opt_state,
                       jax.ShapeDtypeStruct((), jnp.int32,
                                            sharding=replicated))
    rows = jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), "sp")))
    step = build_train_step(
        lambda p, b: loss_fn(p, b["tokens"], b["targets"], model_config),
        optimizer)
    with jax.set_mesh(mesh):
        compiled = step.lower(state, {"tokens": rows,
                                      "targets": rows}).compile()
        reference = spec.load_module(cell.roots, "reference",
                                     config["reference"])
        model = spec.model_numbers(config)
        # The correctness check's program runs beside the whole state.
        checked = jax.jit(lambda p, b: reference.loss(
            p, b["tokens"], b["targets"], model)).lower(
                params, {"tokens": rows, "targets": rows}).compile()
    text = compiled.as_text()
    report(cell.name, "step", compiled)
    report(cell.name, "reference_loss (beside the optimizer's "
           f"{sum(x.size * 4 for x in jax.tree.leaves(opt_state)) / cell.chips / GIB:.2f} GiB)",
           checked)
    print(json.dumps({
        "cell": cell.name, "layers": model_config.num_layers,
        "params": model_config.num_params,
        "has_tpu_custom_call": "tpu_custom_call" in text,
        "collectives": {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                        for k in ("all-gather", "all-reduce",
                                  "reduce-scatter", "all-to-all",
                                  "collective-permute")}}), flush=True)


def size_serve(cell, devices) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import model as paged_model

    config = cell.config
    model_config = spec.build_model_config(config)
    engine = config["engine"]
    batch, block = engine["max_batch_size"], GLOBAL_CONFIG.llm_block_size
    chunk = GLOBAL_CONFIG.llm_prefill_chunk
    table = -(-engine["max_seq_len"] // block)
    chip = SingleDeviceSharding(devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(
        lambda s: on_chip(s.shape, model_config.dtype),
        jax.eval_shape(lambda: llama.init_params(model_config,
                                                 jax.random.PRNGKey(0))))
    pool_shape = (model_config.num_layers, 1 + batch * table, block,
                  model_config.num_kv_heads, model_config.head_dim)
    pool = {"k": on_chip(pool_shape, model_config.dtype),
            "v": on_chip(pool_shape, model_config.dtype)}
    i32 = jnp.int32
    report(cell.name, "decode_step",
           paged_model.make_decode_step(model_config, block).lower(
               params, pool, on_chip((batch, 1), i32), on_chip((batch,), i32),
               on_chip((batch, table), i32), on_chip((2,), jnp.uint32),
               on_chip((batch,), jnp.float32)).compile())
    report(cell.name, "prefill_chunk",
           paged_model.make_prefill_chunk(model_config, block).lower(
               params, pool, on_chip((1, chunk), i32), on_chip((1, chunk), i32),
               on_chip((1, table), i32), on_chip((), i32),
               on_chip((), i32)).compile())


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("cells", nargs="*")
    parser.add_argument("--set", action="append", default=[],
                        metavar="KEY=VALUE")
    args = parser.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from benchmark import spec
    from ray_tpu._private import jax_compat

    jax.config.update("jax_enable_compilation_cache", False)
    # The kernels ask the backend whether to interpret, and see the CPU
    # during such a compile: steered here, not by an option of theirs.
    jax_compat.interpret_kernels = lambda: False
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in args.cells or names:
        cell = spec.load_cell(name)
        for item in args.set:
            key, _, value = item.partition("=")
            for block in (cell.config, cell.traffic,
                          cell.config.get("engine", {})):
                if key in block:
                    block[key] = json.loads(value)
        {"train": size_train, "serve": size_serve}[cell.config["kind"]](
            cell, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
