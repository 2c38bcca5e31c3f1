"""A train cell: the program's own entry points, as a user calls them
(``JaxTrainer`` -> ``session.get_mesh`` -> ``create_train_state`` ->
``build_train_step`` -> ``shard_batch``), with the measured window
inside the user's train loop. The worker is a thread of this process,
which holds the chips and can therefore trace them."""

from __future__ import annotations

import math
import os
import time

from benchmark import harness, spec, stats, traffic_gen


def train_loop(cfg: dict) -> None:
    """What a user's ``train_loop_per_worker`` looks like, plus the
    clock. Everything it learns goes back through ``session.report``."""
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.parallel.train_step import (
        build_train_step,
        create_train_state,
        default_optimizer,
        shard_batch,
    )
    from ray_tpu.train import session

    config, traffic, seed = cfg["config"], cfg["traffic"], cfg["seed"]
    trainer = config["trainer"]
    model_config = spec.build_model_config(config)
    init, loss_fn, axes = (spec.resolve(trainer[k])
                           for k in ("init", "loss", "logical_axes"))
    reference = spec.load_module(cfg["roots"], "reference",
                                 config["reference"])
    model = spec.model_numbers(config)
    mesh = session.get_mesh(MeshConfig(**trainer["mesh"]))
    with jax.set_mesh(mesh):
        optimizer = default_optimizer(**trainer["optimizer"])
        # The weights are made on the device in one jitted call that
        # takes the key as an argument, sharded from birth by the
        # program's own rules. create_train_state's init function takes
        # no argument, so a seed would close over as a constant: every
        # new seed then compiled for 24 s on the chip (my chip runs,
        # PR 22) and missed the compile cache.
        params = jax.jit(
            lambda key: init(model_config, key),
            out_shardings=tree_shardings(mesh, axes(model_config)),
        )(jax.random.PRNGKey(seed))
        state = create_train_state(params, optimizer, mesh,
                                   axes(model_config))
        del params
        jax.block_until_ready(state)
        cfg["say"]("setup", step="weights and optimizer state on the device")

        def loss(params, batch):
            return loss_fn(params, batch["tokens"], batch["targets"],
                           model_config)

        step = build_train_step(loss, optimizer)
        batches = traffic_gen.train_batches(traffic, seed,
                                            model_config.vocab_size)
        probe = shard_batch(next(batches), mesh)
        # Before the first step, which donates the state: the plain
        # float32 loss on the same placed weights and batch.
        want = float(jax.jit(
            lambda p, b: reference.loss(p, b["tokens"], b["targets"], model)
        )(state.params, probe))
        cfg["say"]("setup", step="reference loss computed")
        warm_losses = []
        for _ in range(cfg["warm_steps"]):
            state, metrics = step(state, probe)
            warm_losses.append(float(metrics["loss"]))
        jax.block_until_ready(state)

        cfg["say"]("setup", step="warm-up steps done; window opens")
        compiles_before = cfg["compiles"]()
        fence_every, losses = traffic["fence_every"], []
        tokens_per_step = traffic["batch"] * traffic["seq_len"]
        tracing = False
        opened = now = time.perf_counter()
        groups, group_s = 0, []
        while now - opened < cfg["seconds"]:
            if cfg["trace_dir"] and groups == 1:
                harness.start_trace(cfg["trace_dir"])
                tracing = True
            for _ in range(fence_every):
                with jax.profiler.TraceAnnotation("bench.make_batch"):
                    batch = shard_batch(next(batches), mesh)
                state, metrics = step(state, batch)
                losses.append(metrics["loss"])
            with jax.profiler.TraceAnnotation("bench.fence"):
                jax.block_until_ready(state)
            group_s.append(time.perf_counter() - now)
            now += group_s[-1]
            groups += 1
            if tracing and groups == 1 + cfg["trace_groups"]:
                jax.profiler.stop_trace()
                tracing = False
        if tracing:
            jax.profiler.stop_trace()
        window_s = now - opened
        losses = [float(x) for x in np.asarray(jax.device_get(losses))]
        session.report({
            "pid": os.getpid(), "mesh": dict(mesh.shape),
            "setup_s": opened - cfg["started"], "window_s": window_s,
            "steps": len(losses),
            # The median over the fenced groups, not tokens over the
            # window: a machine that stands still for seconds, as the
            # chip's did now and then (PERF.md, PR 22), moves one group
            # and not the median. The plain rate is kept beside it.
            "train_tokens_per_s": fence_every * tokens_per_step
            / stats.median(group_s),
            "train_tokens_per_s_mean":
                len(losses) * tokens_per_step / window_s,
            "group_s_min_max": [min(group_s), max(group_s)],
            "reference_loss": want, "warm_losses": warm_losses,
            "losses_min_max": [min(losses), max(losses)],
            "nonfinite": sum(not math.isfinite(x) for x in losses),
            "compiles_in_window": cfg["compiles"]() - compiles_before,
            "memory": harness.fullest_chip_memory(),
            "params": int(sum(x.size for x in jax.tree.leaves(state.params))),
        })


def run(cell, args, started: float, say, compiles) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config = spec.rehearsed(cell.config, args.rehearse)
    traffic = spec.rehearsed(cell.traffic, args.rehearse)
    trainer = config["trainer"]
    trace_dir = args.trace_dir if args.trace else None
    # The chips are detected without JAX; a rehearsal declares the CPU's
    # virtual devices as the chips.
    ray_tpu.init(num_cpus=4, num_tpus=cell.chips if args.rehearse else None)
    try:
        found = ray_tpu.cluster_resources().get("TPU")
        if found != cell.chips:
            raise SystemExit(f"the runtime sees {found} TPU chips, the "
                             f"cell needs {cell.chips}")
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "config": config, "traffic": traffic, "roots": cell.roots,
                "seed": args.seed, "seconds": args.seconds,
                "started": started, "trace_dir": trace_dir,
                "trace_groups": traffic["trace_groups"],
                "warm_steps": trainer["warm_steps"], "compiles": compiles,
                "say": say},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                chips_per_worker=cell.chips)).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    out = result.metrics
    if out.get("pid") != os.getpid():
        raise SystemExit("the train loop ran in another process than the "
                         "one that holds and traces the chip")
    want, warm = out["reference_loss"], out["warm_losses"]
    rtol = trainer["loss_rtol"]
    checks = {
        "step0_loss_matches_reference":
            abs(warm[0] - want) <= rtol * abs(want),
        "loss_falls_on_repeated_batch": warm[-1] < warm[0],
        "all_losses_finite": out["nonfinite"] == 0
            and all(math.isfinite(x) for x in warm),
        "no_compile_in_window": out["compiles_in_window"] == 0,
    }
    say("train", mesh=out["mesh"], params=out["params"],
        batch=[traffic["batch"], traffic["seq_len"]], steps=out["steps"],
        window_s=out["window_s"],
        train_tokens_per_s_mean=out["train_tokens_per_s_mean"],
        group_s_min_max=out["group_s_min_max"],
        warm_losses=warm, reference_loss=want,
        step0_rel_diff=abs(warm[0] - want) / abs(want), loss_rtol=rtol,
        losses_min_max=out["losses_min_max"], checks=checks)
    return {
        "correct": all(checks.values()),
        "attempted": out["steps"], "failed": out["nonfinite"],
        "setup_s": out["setup_s"],
        "values": {"train_tokens_per_s": out["train_tokens_per_s"]},
        "memory": out["memory"], "counters": {},
        "harness": {"train_tokens_per_s": out["train_tokens_per_s"],
                    "seq_len": traffic["seq_len"]},
        "config": config, "traffic": traffic,
    }
