"""Does the system still start on the chip?  One process, one TPU chip.

Drives the main path once through the entry points a user calls, at the
full width of Llama-2-7B (``LlamaConfig.llama2_7b()``: hidden 4096, 32
heads of 128, MLP 11008, vocabulary 32000) cut in depth only, with
random weights made from ``--seed``:

  device   a TPU must be there, or the run ends before any phase;
  kernels  each pallas kernel of the main path, compiled by the chip's
           compiler, against the repo's plain references, forward and
           gradients;
  train    ``ray_tpu.init()`` -> ``JaxTrainer`` -> ``build_train_step``:
           a few AdamW steps on a repeated seeded batch;
  serve    ``serve.run(LLMEngineServer)``: concurrent requests through
           the deployment handle, checked against a plain full-context
           float32 forward on the same weights.

``--chips 4`` (run by hand; four chips cost four times as much) runs
instead only the sharded train step on an fsdp=2 x tp=2 mesh and the
one-device trajectory it must reproduce.  ``--paged-logits <file>`` runs
instead, for a serve configuration of the benchmark at the size its
file gives, the paged engine's two programs over shuffled block tables
(prefill in chunks, then batched decode, for the file's probe lengths
and for one context as long as the table) against the configuration's
plain float32 reference: logits, and for a sparse model the expert
choices that differ (for a latent-attention configuration every row of
the engine busy, a context that ends at each of the table's three
widths, prefilled at that width, and every decode step through the one
width its program is built at, the whole table).  ``--cell <workload>
--round-weights <dtype>`` runs instead
that serve cell of the benchmark as ``benchmark/run.py`` does, with the
replica's weights rounded and its check's left as the seed gives them:
the control of the cell's ``logit_atol``, which has to come out not
correct.  ``--rehearse`` is the only way
this script accepts a CPU: tiny sizes, interpreted kernels, to find wrong
paths and arguments before any chip time is spent.  Every phase fails
the run; nothing is caught and continued.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else is on earlier lines, each naming what it measured.  A
time printed here is information about this run, not a benchmark result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time

ARGS = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ARGS.add_argument("--rehearse", action="store_true",
                  help="tiny sizes on the CPU (the only way a CPU is "
                       "accepted)")
ARGS.add_argument("--chips", type=int, default=1, choices=(1, 4),
                  help="4: only the sharded train step and its "
                       "one-device comparison")
ARGS.add_argument("--paged-logits", metavar="CONFIG_JSON", default=None,
                  help="instead of the phases: a serve configuration of "
                       "the benchmark at its real size, the paged "
                       "engine's programs against the configuration's "
                       "plain reference, logits and expert choices")
ARGS.add_argument("--round-weights", metavar="DTYPE", default=None,
                  help="with --paged-logits of a hybrid, block-diffusion or "
                       "latent-attention configuration: the "
                       "programs run on weights rounded through this dtype "
                       "(float8_e4m3fn: the nearest precision below "
                       "bfloat16) while the reference keeps the weights as "
                       "they are; the comparison then has to FAIL")
ARGS.add_argument("--state-dtype", metavar="DTYPE", default=None,
                  help="with --paged-logits or --cell of a configuration "
                       "whose model keeps a recurrent state in float32 "
                       "(``state_dtype``): the programs keep it in this "
                       "dtype (bfloat16) instead; a second control, run as "
                       "--round-weights is (it exits 1 where the comparison "
                       "passed: at Jamba's published widths it does at two "
                       "seeds of three, PERF.md 6, PR 60)")
ARGS.add_argument("--cell", metavar="WORKLOAD", default=None,
                  help="instead of the phases, with --round-weights: the "
                       "benchmark's own run of that serve cell "
                       "(benchmark/run.py's) with the weights the REPLICA "
                       "builds rounded and the weights its check rebuilds "
                       "as the seed gives them: the control of the cell's "
                       "logit_atol, which has to come out not correct")
ARGS.add_argument("--seed", type=int, default=0)

# Stated tolerances. bf16 keeps 8 bits of mantissa (2^-8 = 4e-3 a
# rounding); the kernels round q, k, v, p and dO to bf16 and accumulate
# in float32, the references below compute in float32 throughout.
KERNEL_REL_TOL = 2e-2    # max|got - want| / max|want|, outputs and grads
LOGIT_ATOL = 8e-2        # engine (bf16) against float32 forward, logits ~N(0,1)
# Sharded against one-device (loss, grad_norm). __graft_entry__'s
# PARITY_RTOL of 2e-3 was set for float32 on the CPU; bf16 at width 4096
# needs 1e-2, measured on four v5e chips (PR 21): the loss agrees to
# 8e-5 at every step and grad_norm to 5e-4 through step 1, but at step 2
# grad_norm has jumped from 5.4 to 19.9 (lr=1e-3 with no warm-up
# overshoots) and there the two differ by 4.2e-3 — tp halves every
# 4096- and 11008-wide bf16 contraction into two partial sums, and an
# unstable step amplifies the different rounding.
SHARDED_RTOL = 1e-2

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses",
                "/jax/compilation_cache/compile_requests_use_cache":
                    "requests"}
COUNTS = {"compiles": 0, "compile_s": 0.0, "hits": 0, "misses": 0,
          "requests": 0, "by_name": {}, "seconds_by_name": {}}


def say(phase: str, **fields) -> None:
    print(f"smoke[{phase}] " + " ".join(
        f"{k}={json.dumps(v) if isinstance(v, (dict, list, str)) else v}"
        for k, v in fields.items()), flush=True)


def check(ok: bool, message: str) -> None:
    """A failed check ends the run (assert would vanish under -O)."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {message}")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the run is cut to. Widths are the published ones."""
    model: object            # LlamaConfig of the train phase
    batch: int
    seq: int
    serve_layers: int
    serve_max_seq_len: int
    serve_batch: int
    prompts: tuple           # (prompt length, new tokens) per request
    kernel_shapes: tuple     # (L, heads, kv heads, head dim)
    cuts: str


def sizes(rehearse: bool) -> Sizes:
    from ray_tpu.models.llama import LlamaConfig

    if rehearse:
        model = dataclasses.replace(
            LlamaConfig.tiny(), attention="flash", remat=True)
        return Sizes(model, batch=2, seq=128, serve_layers=2,
                     serve_max_seq_len=64, serve_batch=4,
                     prompts=((3, 4), (9, 6), (17, 5), (20, 4), (30, 6)),
                     kernel_shapes=((128, 4, 4, 16), (128, 4, 2, 16)),
                     cuts="rehearsal: LlamaConfig.tiny(), nothing here "
                          "is a real size")
    # Depth from compiled.memory_analysis() of the whole AdamW step on a
    # described v5e chip at batch 1x2048: 2 layers need 7.45 GiB of
    # arguments (float32 weights and two Adam moments of 667M
    # parameters) + 3.08 GiB of temporaries of 15.75 GiB; 3 layers need
    # 9.72 + 4.25 and would leave under 2 GiB. Serving holds bf16
    # weights only, so 4 layers (2.0 GiB, as much again for the
    # reference copy here; decode step 3.5 GiB in all).
    model = dataclasses.replace(
        LlamaConfig.llama2_7b(), num_layers=2, max_seq_len=2048,
        attention="flash")
    return Sizes(model, batch=1, seq=2048, serve_layers=4,
                 serve_max_seq_len=1024, serve_batch=8,
                 prompts=((5, 8), (37, 12), (64, 16), (100, 10),
                          (150, 8)),
                 kernel_shapes=((2048, 32, 32, 128), (2048, 32, 8, 128)),
                 cuts="Llama-2-7B widths (hidden 4096, 32x128 heads, MLP "
                      "11008, vocab 32000); depth 32 -> 2 (train) and 4 "
                      "(serve); train batch 1x2048; serve max_seq_len "
                      "4096 -> 1024; weights random from --seed")


# ------------------------------------------------------------------ device


def phase_device(args) -> dict:
    import jax
    import jaxlib

    device = jax.devices()[0]
    want = "cpu" if args.rehearse else "tpu"
    check(device.platform == want,
          f"needs a {want.upper()}, JAX found platform="
          f"{device.platform!r} ({device.device_kind}); "
          "a CPU is accepted only with --rehearse")
    check(len(jax.devices()) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX found "
          f"{len(jax.devices())}")
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    say("device", platform=device.platform, kind=device.device_kind,
        count=len(jax.devices()), jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu_version)
    return {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}


def watch_compiles():
    """Count backend compilations and persistent-cache traffic.
    Returns a function that stops counting."""
    import jax

    def on_duration(event, duration, fun_name=None, **_):
        # One event per program built, or fetched from the persistent
        # cache; ``fun_name`` is the jitted function's name.
        if event == COMPILE_EVENT:
            COUNTS["compiles"] += 1
            COUNTS["compile_s"] += duration
            COUNTS["by_name"][fun_name] = \
                COUNTS["by_name"].get(fun_name, 0) + 1
            COUNTS["seconds_by_name"][fun_name] = \
                COUNTS["seconds_by_name"].get(fun_name, 0.0) + duration

    def on_event(event, **_):
        if event in CACHE_EVENTS:
            COUNTS[CACHE_EVENTS[event]] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    def stop() -> None:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)

    return stop


def device_bytes() -> "int | None":
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_in_use") if stats else None


# ----------------------------------------------------------------- kernels


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def phase_kernels(sz: Sizes, seed: int, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.fused import rms_norm
    from ray_tpu.parallel.ring_attention import plain_attention

    def compiled_kernel(fn, *inputs) -> None:
        """The kernel must have reached the chip's compiler."""
        if on_tpu:
            check("tpu_custom_call" in jax.jit(fn).lower(*inputs).as_text(),
                  "no tpu_custom_call in the lowered kernel: it was "
                  "interpreted or replaced")

    for L, h, kvh, d in sz.kernel_shapes:
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(keys[0], (1, L, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (1, L, kvh, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (1, L, kvh, d), jnp.bfloat16)
        w = jax.random.normal(keys[3], (1, L, h, d), jnp.float32)

        # ``w`` is an argument, not a closure: a closed-over array is
        # baked into the program and into its compile-cache entry.
        def plain(q, k, v):
            # The oracle in float32 on the same bf16 values; it wants
            # kv heads repeated.
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            k, v = (jnp.repeat(x, h // kvh, axis=2) for x in (k, v))
            return plain_attention(q, k, v, causal=True)

        def flash_loss(q, k, v, w):
            return jnp.sum(flash_attention(q, k, v, causal=True) * w)

        def plain_loss(q, k, v, w):
            return jnp.sum(plain(q, k, v) * w)

        compiled_kernel(jax.grad(flash_loss, argnums=(0, 1, 2)), q, k, v, w)
        start = time.perf_counter()
        out = jax.jit(flash_attention)(q, k, v)
        grads = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v, w)
        jax.block_until_ready((out, grads))
        elapsed = time.perf_counter() - start
        want = jax.jit(plain)(q, k, v)
        want_grads = jax.jit(
            jax.grad(plain_loss, argnums=(0, 1, 2)))(q, k, v, w)
        errs = {"out": rel_err(out, want)}
        for name, got, ref in zip(("dq", "dk", "dv"), grads, want_grads):
            errs[name] = rel_err(got, ref)
        say("kernels", kernel="flash_attention fwd+bwd",
            shape=[1, L, h, kvh, d], dtype="bfloat16",
            rel_err={k_: round(e, 5) for k_, e in errs.items()},
            tol=KERNEL_REL_TOL, compile_and_run_s=round(elapsed, 2))
        check(all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                  for g in (out, *grads)), "flash_attention: not finite")
        check(max(errs.values()) <= KERNEL_REL_TOL,
              f"flash_attention {(L, h, kvh, d)} disagrees with "
              f"plain_attention: {errs}")

    rows, width = sz.batch * sz.seq, sz.model.hidden_size
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    x = jax.random.normal(keys[0], (rows, width), jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(keys[1], (width,), jnp.float32)
    w = jax.random.normal(keys[2], (rows, width), jnp.float32)
    eps = sz.model.rms_norm_eps

    def fused_loss(x, scale, w):
        return jnp.sum(rms_norm(x, scale, eps) * w)

    def plain_loss(x, scale, w):
        return jnp.sum(llama.rms_norm(x, scale, eps) * w)

    compiled_kernel(fused_loss, x, scale, w)
    out = jax.jit(lambda x, s: rms_norm(x, s, eps))(x, scale)
    want = jax.jit(lambda x, s: llama.rms_norm(x, s, eps))(x, scale)
    grads = jax.jit(jax.grad(fused_loss, argnums=(0, 1)))(x, scale, w)
    want_grads = jax.jit(jax.grad(plain_loss, argnums=(0, 1)))(x, scale, w)
    errs = {"out": rel_err(out, want), "dx": rel_err(grads[0], want_grads[0]),
            "dscale": rel_err(grads[1], want_grads[1])}
    say("kernels", kernel="fused rms_norm fwd+bwd", shape=[rows, width],
        dtype="bfloat16", rel_err={k_: round(e, 5) for k_, e in errs.items()},
        tol=KERNEL_REL_TOL,
        note="checked here only; models/ uses llama.rms_norm")
    check(max(errs.values()) <= KERNEL_REL_TOL,
          f"fused rms_norm disagrees with llama.rms_norm: {errs}")


# ------------------------------------------------------------------- train

WARM_STEPS = 4  # per timing window of the train loop


def train_loop(cfg: dict) -> None:
    """What a user's ``train_loop_per_worker`` looks like."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel.train_step import (
        build_train_step,
        create_train_state,
        default_optimizer,
        shard_batch,
    )
    from ray_tpu.train import session

    config, seed = cfg["model"], cfg["seed"]
    mesh = session.get_mesh()
    with jax.set_mesh(mesh):
        optimizer = default_optimizer(
            learning_rate=cfg["lr"], warmup_steps=0, total_steps=100)
        key = jax.random.PRNGKey(seed)
        state = create_train_state(
            lambda: llama.init_params(config, key), optimizer, mesh,
            llama.param_logical_axes(config))

        def loss(params, batch):
            return llama.loss_fn(params, batch["tokens"], batch["targets"],
                                 config)

        step = build_train_step(loss, optimizer)
        tokens = jax.random.randint(
            jax.random.PRNGKey(seed + 1), (cfg["batch"], cfg["seq"] + 1), 0,
            config.vocab_size)
        batch = shard_batch(
            {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}, mesh)
        has_kernel = "tpu_custom_call" in step.lower(state, batch).as_text()

        def timed(n: int, fetch_each: bool):
            nonlocal state
            losses = []
            start = time.perf_counter()
            for _ in range(n):
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]) if fetch_each
                              else metrics["loss"])
            jax.block_until_ready(state)
            return time.perf_counter() - start, [float(x) for x in losses]

        step_compiles = COUNTS["by_name"].get("jit(step)", 0)
        first_s, losses = timed(1, fetch_each=True)
        compiles_before = COUNTS["compiles"]
        # Does block_until_ready wait? The same steps, each fetched to
        # the host, and closed by one block_until_ready, must take alike.
        fetched_s, more = timed(WARM_STEPS, fetch_each=True)
        losses += more
        blocked_s, more = timed(WARM_STEPS, fetch_each=False)
        losses += more
        for i, value in enumerate(losses):
            session.report({"step": i, "loss": value})
        leaves = jax.tree.leaves(state)
        stats = jax.devices()[0].memory_stats() or {}
        session.report({
            "summary": True, "losses": losses, "has_kernel": has_kernel,
            "first_step_s": first_s, "fetched_s": fetched_s,
            "blocked_s": blocked_s,
            "step_programs": COUNTS["by_name"].get("jit(step)", 0) - step_compiles,
            "steady_compiles": COUNTS["compiles"] - compiles_before,
            "state_platforms": sorted(
                {d.platform for x in leaves for d in x.devices()}),
            "state_bytes": sum(x.nbytes for x in leaves),
            "peak_bytes": stats.get("peak_bytes_in_use"),
            "mesh": dict(mesh.shape),
        })


def phase_train(sz: Sizes, seed: int, device: dict) -> None:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    result = JaxTrainer(
        train_loop,
        train_loop_config={"model": sz.model, "batch": sz.batch,
                           "seq": sz.seq, "seed": seed, "lr": 3e-4},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True)).fit()
    if result.error is not None:
        raise result.error
    out = result.metrics
    check(out.get("summary") is True, f"no summary report: {out}")
    losses = out["losses"]
    kind = device["kind"]
    say("train", entry="JaxTrainer -> build_train_step", mesh=out["mesh"],
        layers=sz.model.num_layers, params=sz.model.num_params,
        batch=[sz.batch, sz.seq], device=kind,
        losses=[round(x, 4) for x in losses])
    say("train", device=kind,
        first_step_s_with_compile=round(out["first_step_s"], 2),
        step_s_each_fetched=round(out["fetched_s"] / WARM_STEPS, 4),
        step_s_one_block_until_ready=round(out["blocked_s"] / WARM_STEPS, 4),
        step_programs=out["step_programs"],
        compiles_after_first_step=out["steady_compiles"],
        state_on=out["state_platforms"],
        state_gb=round(out["state_bytes"] / 1e9, 2),
        peak_device_gb=out["peak_bytes"] and round(out["peak_bytes"] / 1e9, 2))
    check(len(result.metrics_history) == len(losses) + 1,
          "session reports went missing")
    check(all(x == x and abs(x) < 1e4 for x in losses),
          f"loss not finite: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    check(out["state_platforms"] == [device["platform"]],
          f"train state lives on {out['state_platforms']}")
    check(out["step_programs"] == 1 and out["steady_compiles"] == 0,
          f"the step compiled more than once: {out['step_programs']} "
          f"programs, {out['steady_compiles']} compiles after the first")
    if device["platform"] == "tpu":
        check(out["has_kernel"], "no tpu_custom_call in the train step")
        # An early return would make the unfenced window many times
        # shorter; host noise on a shared machine is far inside this.
        check(out["blocked_s"] > 0.5 * out["fetched_s"],
              "block_until_ready returned before the device finished: "
              f"{out['blocked_s']:.3f}s against {out['fetched_s']:.3f}s "
              "with every step fetched")


# ------------------------------------------------------------------- serve


def probe_chunk(engine: dict, block: int) -> int:
    """The chunk the ``--paged-logits`` phases feed the prefill program
    in: the configuration's, else the engine's default held to half the
    table, so that at a rehearsal's size too the context as long as the
    table is several chunks and its tail is written by decode steps."""
    from ray_tpu.serve.llm_engine.engine import default_prefill_chunk

    return engine.get("prefill_chunk") or default_prefill_chunk(
        engine["max_seq_len"] // 2, block)


def chunk_inputs(context, start: int, n: int, chunk: int):
    """Tokens and positions [1, chunk] of ``context[start:start + n]``,
    zero-padded, as the engine hands a prefill chunk to its program."""
    import jax.numpy as jnp
    import numpy as np

    tokens = np.zeros((1, chunk), np.int32)
    tokens[0, :n] = context[start:start + n]
    positions = np.zeros((1, chunk), np.int32)
    positions[0, :n] = np.arange(start, start + n)
    return jnp.asarray(tokens), jnp.asarray(positions)


def shuffled_tables(rng, rows: int, width: int, contexts, block: int):
    """A table ``[rows, width]`` a context, dealt a block a turn from one
    shuffled deck of the pool's blocks, so that none is contiguous."""
    import numpy as np

    deck = [int(b) for b in rng.permutation(np.arange(1, 1 + rows * width))]
    tables = np.zeros((rows, width), np.int32)
    for turn in range(width):
        for i, context in enumerate(contexts):
            if turn < -(-len(context) // block):
                tables[i, turn] = deck.pop()
    return tables


def drive_forward(model_config, block: int, chunk: int, served, cache,
                  contexts, prefilled, tables, steps: int, first_kept,
                  compared, table_width=lambda i: None):
    """Every row of the engine busy through its family's ONE forward
    (``model.Family.forward``), as the engine drives the two programs
    that wrap it: context ``i`` in row slot ``i``, its first
    ``prefilled[i]`` positions chunk by chunk (on the first
    ``table_width(i)`` blocks of its table, as the engine hands a chunk
    the narrowest width that holds it; the whole where None), then all
    rows ``steps`` decode steps together through the whole table,
    teacher-forced. A recurrent state advances with every call, so the
    forward is run once, showing every position's logits. Returns
    (position -> logits row of each compared context from
    ``first_kept[i]`` on, position -> its chosen experts [expert layers,
    k]: none of a dense model, the cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_engine import model as paged_model

    family = paged_model.family(model_config)
    rows = len(tables)

    def forward(params, cache, tokens, positions, tables, **of_a_chunk):
        logits, cache, _, routing = family.forward(
            params, cache, tokens, positions, tables, model_config, block,
            **of_a_chunk)
        if routing is not None:
            # [expert layers, B, T, k], however the family groups them
            # (the linear family's: by period).
            routing = routing.reshape(-1, *routing.shape[-3:])
        return logits, cache, routing

    shown_chunk = jax.jit(
        lambda params, cache, tokens, positions, table, slot, n_valid:
        forward(params, cache, tokens, positions, table, slot=slot,
                n_valid=n_valid), donate_argnums=(1,))
    shown_step = jax.jit(
        lambda params, cache, tokens, positions, tables:
        forward(params, cache, tokens, positions[:, None], tables),
        donate_argnums=(1,))

    got = [{} for _ in contexts]               # position -> logits row
    chosen = [{} for _ in contexts]            # position -> [layers, k]
    for i, context in enumerate(contexts):
        table = jnp.asarray(tables[i:i + 1, :table_width(i)])
        for start in range(0, prefilled[i], chunk):
            n = min(chunk, prefilled[i] - start)
            logits, cache, routing = shown_chunk(
                served, cache, *chunk_inputs(context, start, n, chunk),
                table, np.int32(i), np.int32(n))
            if i not in compared:
                continue
            if routing is not None:
                routing = np.asarray(routing)
                for j in range(n):
                    chosen[i][start + j] = routing[:, 0, j]
            if start + n > first_kept[i]:
                logits = np.asarray(logits[0], np.float32)
                for j in range(n):
                    if start + j >= first_kept[i]:
                        got[i][start + j] = logits[j]
    for step in range(steps):
        last = np.zeros((rows, 1), np.int32)
        positions = np.zeros((rows,), np.int32)
        for i, context in enumerate(contexts):
            last[i, 0] = context[prefilled[i] + step]
            positions[i] = prefilled[i] + step
        logits, cache, routing = shown_step(
            served, cache, jnp.asarray(last), jnp.asarray(positions),
            jnp.asarray(tables))
        logits = np.asarray(logits[:, 0], np.float32)
        routing = None if routing is None else np.asarray(routing)
        for i in compared:
            got[i][int(positions[i])] = logits[i]
            if routing is not None:
                chosen[i][int(positions[i])] = routing[:, i, 0]
    return got, chosen, cache


def paged_prefill_logits(config, params, prompt, block_size: int,
                         chunk: int, blocks_per_seq: int):
    """Logits of the first generated position from the engine's own
    prefill program, chunk by chunk as the engine feeds it."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_engine import model as paged_model
    from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache

    prefill = paged_model.make_prefill_chunk(config, block_size)
    pool = PagedKVCache.init_pool(config, blocks_per_seq + 1, block_size)
    table = np.zeros((1, blocks_per_seq), np.int32)
    used = -(-len(prompt) // block_size)
    table[0, :used] = np.arange(1, used + 1)
    logits = None
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        logits, pool, _ = prefill(
            params, pool, *chunk_inputs(prompt, start, n, chunk),
            jnp.asarray(table), np.int32(n), np.int32(n - 1))
    return logits


def phase_serve(sz: Sizes, seed: int, device: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import serve
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngineServer
    from ray_tpu.serve.llm_engine import model as paged_model
    from ray_tpu.serve.llm_engine.engine import default_prefill_chunk

    config = dataclasses.replace(
        sz.model, num_layers=sz.serve_layers,
        max_seq_len=sz.serve_max_seq_len, attention="plain")
    block = GLOBAL_CONFIG.llm_block_size
    blocks_per_seq = -(-sz.serve_max_seq_len // block)
    chunk = default_prefill_chunk(blocks_per_seq * block, block)
    pool_bytes = (2 * config.num_layers * (1 + sz.serve_batch * blocks_per_seq)
                  * block * config.num_kv_heads * config.head_dim * 2)
    gathered = (sz.serve_batch * blocks_per_seq * block * config.num_heads
                * config.head_dim * 4)
    say("serve", layers=config.num_layers, max_seq_len=sz.serve_max_seq_len,
        weights_gb=round(config.num_params * 2 / 1e9, 2),
        kv_pool_gb=round(pool_bytes / 1e9, 3),
        gathered_f32_keys_gb_per_layer=round(gathered / 1e9, 3),
        weights="built inside the replica from the seed, bf16")

    # The weights reach the replica as a user's would: built (or loaded)
    # inside it, from the config and a seed; bind() carries no arrays.
    deployment = serve.deployment(LLMEngineServer).options(
        name="smoke_llm", max_ongoing_requests=32,
        ray_actor_options={"resources": {"TPU": 1}})
    handle = serve.run(
        deployment.bind(config, None, max_batch_size=sz.serve_batch,
                        max_seq_len=sz.serve_max_seq_len, seed=seed),
        name="smoke_llm_app", route_prefix="/smoke_llm", _wait_s=600.0)

    rng = np.random.default_rng(seed)
    start = time.perf_counter()
    handle.remote({"tokens": [1, 2, 3], "max_new_tokens": 2}).result(
        timeout_s=900)
    say("serve", device=device["kind"],
        first_request_s_with_compiles=round(time.perf_counter() - start, 2))

    requests = []
    for i, (n_prompt, n_new) in enumerate(sz.prompts):
        requests.append({
            "tokens": rng.integers(1, config.vocab_size, n_prompt).tolist(),
            "max_new_tokens": n_new,
            "temperature": 0.8 if i == 3 else 0.0})
    outputs: list = [None] * len(requests)
    errors: list = []

    def client(i: int) -> None:
        try:
            if i == 2:  # streamed
                outputs[i] = list(handle.options(stream=True)
                                  .generate.remote(requests[i]))
            else:
                outputs[i] = handle.remote(requests[i]).result(
                    timeout_s=600)["tokens"]
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            errors.append(exc)

    start = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    elapsed = time.perf_counter() - start
    check(not any(t.is_alive() for t in threads), "a request hung")
    if errors:
        raise errors[0]
    stats = handle.engine_stats.remote().result(timeout_s=60)
    served = sum(len(o) for o in outputs)
    say("serve", entry="serve.run(LLMEngineServer) -> handle",
        device=device["kind"], requests=len(requests),
        prompt_lens=[len(r["tokens"]) for r in requests],
        tokens_served=served, wall_s=round(elapsed, 2),
        streamed=1, sampled=1,
        stats={k: stats[k] for k in (
            "admitted", "finished", "prefill_chunks", "prefill_tokens",
            "decode_steps", "batched_decode_steps", "decode_tokens",
            "preemptions", "shed_cache", "deadline_expired")})
    for request, output in zip(requests, outputs):
        check(len(output) == request["max_new_tokens"],
              f"asked {request['max_new_tokens']} tokens, got {len(output)}")
        check(all(0 <= t < config.vocab_size for t in output),
              "token out of range")
    check(stats["batched_decode_steps"] > 0,
          f"no batched decode step: {stats}")
    check(stats["prefill_chunks"] >= sum(
        -(-len(r["tokens"]) // chunk) for r in requests),
        f"prefill chunks not counted: {stats}")
    check(stats["finished"] == stats["admitted"] == len(requests) + 1
          and stats["shed_cache"] == 0 and stats["deadline_expired"] == 0,
          f"a request did not finish cleanly: {stats}")

    # Reference: a plain full-context float32 forward on the same
    # weights (rebuilt here from the same seed by the same function).
    params = paged_model.serving_params(config, None, seed)
    ref_config = dataclasses.replace(config, dtype=jnp.float32, remat=False)
    reference = jax.jit(lambda p, t: llama.forward(p, t, ref_config)[0])

    near_ties = 0
    for i in (1, 2):  # two greedy requests, one of them streamed
        prompt, output = requests[i]["tokens"], outputs[i]
        # Teacher-forced: position len(prompt)-1+j predicts output[j].
        ref = reference(params, jnp.asarray([prompt + output[:-1]]))
        ref = np.asarray(ref[len(prompt) - 1:])
        if i == 1:
            got = np.asarray(paged_prefill_logits(
                config, params, prompt, block, chunk, blocks_per_seq))
            diff = float(np.max(np.abs(got - ref[0])))
            say("serve", check="first generated position, engine prefill "
                "program against float32 llama.forward",
                max_abs_logit_diff=round(diff, 4), atol=LOGIT_ATOL,
                logit_std=round(float(ref[0].std()), 3),
                device=device["kind"])
            check(np.all(np.isfinite(got)) and diff <= LOGIT_ATOL,
                  f"prefill logits off by {diff} (atol {LOGIT_ATOL})")
        for j, token in enumerate(output):
            best = int(np.argmax(ref[j]))
            if token == best:
                continue
            gap = float(ref[j][best] - ref[j][token])
            say("serve", near_tie=f"request {i} position {j}: served token "
                f"{token} (reference logit {ref[j][token]:.4f}), reference "
                f"argmax {best} ({ref[j][best]:.4f}); the bf16 engine "
                f"picks the other side of a gap of {gap:.4f}")
            check(gap <= LOGIT_ATOL,
                  f"greedy token {token} at position {j} is not the "
                  f"reference argmax {best}, and no near-tie: gap {gap}")
            near_ties += 1
    say("serve", check="greedy continuation equals the reference argmax",
        positions=sum(len(outputs[i]) for i in (1, 2)),
        bf16_near_ties=near_ties)
    serve.shutdown()


# ---------------------------------------- paged programs against a reference

# bf16 programs against the float32 reference on the same bf16 weights,
# in standard deviations of the reference's logits. Up to the first
# position of a sequence at which some layer chose other experts than
# the reference, only roundings differ (a dense model has no such
# position): the serve phase's LOGIT_ATOL, 8e-2; 0.046 in the CPU
# rehearsals of PR 25. A differing expert choice (two router
# probabilities closer than the bf16 noise of the hidden state) swaps
# one of a token's experts for a near-equal one; that token's output
# moves by about one expert's contribution and every later position of
# the sequence sees it through attention. At OLMoE's widths (12 layers,
# 8 of 64 experts) on the v5e, 0.91% of 288,000 choices differed (0.43%
# in the first layer, about 1% from the second on) and every sequence
# had one within its first tokens; the worst logit difference was 0.127
# (PR 25). The bounds are about twice what was measured: a step
# computed in a lower precision than bf16 flips far more.
PAGED_SAME_EXPERTS = 8e-2
PAGED_OTHER_EXPERT = 0.25
PAGED_DIFFERING_SHARE = 0.02
# A rehearsal's model is 3 of 8 experts at width 64: one expert is a
# third of a token's feed-forward and not a tenth (0.325 seen).
REHEARSED_OTHER_EXPERT = 0.5


def phase_paged_logits(path: str, seed: int, rehearse: bool,
                       device: dict, round_weights: "str | None" = None,
                       state_dtype: "str | None" = None) -> None:
    """The engine's jitted steps at a benchmark configuration's size,
    driven as the engine drives them, outside any timed window."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import spec
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine import model as paged_model
    from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache

    with open(path) as f:
        config = spec.rehearsed(json.load(f), rehearse)
    model_config = spec.build_model_config(config)
    if state_dtype:
        model_config = dataclasses.replace(
            model_config, state_dtype=jnp.dtype(state_dtype))
    model = spec.model_numbers(config)
    reference = spec.load_module(
        [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "benchmark")], "reference", config["reference"])
    if getattr(model_config, "family", "") in ("latent", "linear"):
        return phase_latent_logits(config, model_config, model, reference,
                                   seed, rehearse, device, round_weights)
    if paged_model.family(model_config).recurrent:
        return phase_hybrid_logits(config, model_config, model, reference,
                                   seed, rehearse, device, round_weights,
                                   state_dtype)
    check(not state_dtype, "--state-dtype is for a configuration whose "
          "model keeps a recurrent state")
    if model_config.block_length:
        return phase_block_logits(config, model_config, model, reference,
                                  seed, rehearse, device, round_weights)
    sparse = model_config.num_experts > 0
    rows, max_len = (config["engine"][k]
                     for k in ("max_batch_size", "max_seq_len"))
    block = GLOBAL_CONFIG.llm_block_size
    chunk = probe_chunk(config["engine"], block)
    width = -(-max_len // block)
    steps = config["probes"]["max_new_tokens"]
    # A row each, and one for the long context.
    lengths = list(config["probes"]["prompt_lengths"])[:rows - 1]
    # One context as long as the table: its last `tail` positions are
    # compared, the last `chunk` of them written by decode steps.
    tail = 8 * chunk if max_len >= 16 * chunk else 2 * chunk
    long_prefill = max_len - chunk
    rng = np.random.default_rng([seed, 25])
    contexts = [rng.integers(1, model_config.vocab_size, n + steps)
                for n in lengths]
    prefilled = list(lengths)
    if hasattr(reference, "forward_tail"):
        contexts.append(rng.integers(1, model_config.vocab_size, max_len))
        prefilled.append(long_prefill)

    params = paged_model.serving_params(model_config, None, seed)
    widest = max((x for x in jax.tree.leaves(params)), key=lambda x: x.size)
    say("paged", config=config["name"], layers=model_config.num_layers,
        params=model_config.num_params, rows=rows, table=max_len,
        weights_gb=round(sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9,
                         2),
        weight_dtypes=sorted({str(x.dtype) for x in jax.tree.leaves(params)}),
        largest_weight=[list(widest.shape), str(widest.dtype)],
        device_bytes_in_use=device_bytes(),
        device_peak_bytes_after_serving_params=(
            jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"))
    check(all(x.dtype == model_config.dtype for x in jax.tree.leaves(params)),
          "serving_params left a weight wider than the compute dtype")
    # The programs take the tree as an engine holds it; the reference
    # reads ``params``, which shares every other leaf with it.
    laid = paged_model.lay_for_serving(params)

    tables = shuffled_tables(rng, rows, width, contexts, block)
    pool = PagedKVCache.init_pool(model_config, 1 + rows * width, block)
    prefill = paged_model.make_prefill_chunk(model_config, block)
    decode = paged_model.make_decode_step(model_config, block)
    # The same forward the two programs wrap, showing every position's
    # logits and the experts chosen; the pool donated as they donate it.
    forward = paged_model.family(model_config).forward
    shown_chunk = jax.jit(
        lambda params, pool, tokens, positions, table, n_valid:
        forward(params, pool, tokens, positions, table, model_config, block,
                n_valid=n_valid), donate_argnums=(1,))
    shown_step = jax.jit(
        lambda params, pool, tokens, positions, tables:
        forward(params, pool, tokens, positions[:, None], tables,
                model_config, block), donate_argnums=(1,))

    got = [[] for _ in contexts]        # (position, logits row)
    chosen = [{} for _ in contexts]     # position -> experts [layers, k]
    for i, context in enumerate(contexts):
        for start in range(0, prefilled[i], chunk):
            n = min(chunk, prefilled[i] - start)
            args = (*chunk_inputs(context, start, n, chunk),
                    jnp.asarray(tables[i:i + 1]))
            last, pool, _ = prefill(laid, pool, *args, np.int32(n),
                                    np.int32(n - 1))
            # Again through the showing forward: the same values written
            # where they were, every position's logits and choices out.
            logits, pool, _, routing = shown_chunk(laid, pool, *args,
                                                   np.int32(n))
            logits = np.asarray(logits[0], np.float32)
            routing = np.asarray(routing) if sparse else None
            # The program's head runs on that one row: the same float32
            # sum of the same products, in whatever order its shape gives.
            check(np.allclose(logits[n - 1], np.asarray(last, np.float32),
                              rtol=0, atol=1e-3),
                  "the prefill program and its forward disagree")
            in_tail = i == len(lengths) and start + n > max_len - tail
            for j in range(n):
                if sparse:
                    chosen[i][start + j] = routing[:, 0, j]
                if in_tail or start + j == prefilled[i] - 1:
                    got[i].append((start + j, logits[j]))
    at = list(prefilled)
    for step in range(max(steps, chunk)):
        active = [i for i, context in enumerate(contexts)
                  if at[i] < len(context)]
        last = np.zeros((rows, 1), np.int32)
        positions = np.zeros((rows,), np.int32)
        for i in active:
            last[i, 0], positions[i] = contexts[i][at[i]], at[i]
        # Rows that are done carry an inactive row's zeros.
        step_tables = np.where(positions[:, None] > 0, tables, 0)
        args = (jnp.asarray(last), jnp.asarray(positions),
                jnp.asarray(step_tables))
        nxt, pool, _ = decode(laid, pool, *args, jax.random.PRNGKey(0),
                              jnp.zeros((rows,), jnp.float32))
        logits, pool, _, routing = shown_step(laid, pool, *args)
        logits, nxt = np.asarray(logits[:, 0], np.float32), np.asarray(nxt)
        routing = np.asarray(routing) if sparse else None
        for i in active:
            check(int(nxt[i]) == int(logits[i].argmax()),
                  "the decode program's token is not its logits' argmax")
            got[i].append((at[i], logits[i]))
            if sparse:
                chosen[i][at[i]] = routing[:, i, 0]
            at[i] += 1
    check(np.isfinite(np.asarray(pool["k"][:, :, 0, 0, 0],
                                 np.float32)).all(), "pool not finite")
    del pool, prefill, decode, shown_chunk, shown_step, laid
    gc.collect()
    say("paged", programs="prefill chunks over shuffled tables, then batched "
        "decode steps through the pool", sequences=[len(c) for c in contexts],
        positions_compared=sum(len(g) for g in got),
        device_bytes_in_use=device_bytes())

    # The reference: the short contexts in one padded batch (causal, so
    # padding changes nothing before it), the long one by its tail.
    short = contexts[:len(lengths)]
    padded = np.zeros((len(short), -(-max(map(len, short)) // 32) * 32),
                      np.int32)
    for i, context in enumerate(short):
        padded[i, :len(context)] = context
    out = jax.jit(lambda p, t: reference.forward(
        p, t, model, with_routing=True) if sparse
        else (reference.forward(p, t, model), None))(params,
                                                     jnp.asarray(padded))
    want = [(np.asarray(out[0][i]),
             None if not sparse else np.asarray(out[1][:, i]))
            for i in range(len(lengths))]
    if len(contexts) > len(lengths):
        out = jax.jit(lambda p, t: reference.forward_tail(
            p, t, model, tail))(params, jnp.asarray(contexts[-1][None]))
        want.append((np.asarray(out[0][0]), np.asarray(out[1][:, 0])
                     if sparse else None))
    offsets = [0] * len(lengths) + [max_len - tail]

    differing = choices = 0
    differing_by_layer = np.zeros(model_config.num_layers, np.int64)
    worst_same = worst_other = 0.0
    for i, context in enumerate(contexts):
        ref_logits, ref_routing = want[i]
        std = float(ref_logits.std())
        # The first position at which any layer chose other experts
        # than the reference: from there on the sequence is downstream.
        first_differing = len(context)
        if sparse:
            ours = np.stack([chosen[i][p] for p in range(len(context))], 1)
            theirs = ref_routing[:, :len(context)]
            # A choice differs if the reference did not make it too.
            other = ~(ours[..., :, None] == theirs[..., None, :]).any(-1)
            differing += int(other.sum())                   # [n, L, k]
            differing_by_layer += other.sum(axis=(1, 2))
            choices += other.size
            if other.any():
                first_differing = int(np.argmax(other.any(axis=(0, 2))))
        for position, logits in got[i]:
            error = float(np.abs(
                logits - ref_logits[position - offsets[i]]).max()) / std
            if position >= first_differing:
                worst_other = max(worst_other, error)
            else:
                worst_same = max(worst_same, error)
    other_expert = REHEARSED_OTHER_EXPERT if rehearse else PAGED_OTHER_EXPERT
    say("paged", check="logits of the paged programs against the float32 "
        f"reference {config['reference']}", device=device["kind"],
        worst_diff_same_experts_in_std=round(worst_same, 4),
        worst_diff_after_a_differing_choice_in_std=round(worst_other, 4),
        expert_choices_differing=differing, expert_choices=choices,
        differing_by_layer=differing_by_layer.tolist(),
        logit_std=round(float(want[0][0].std()), 3),
        bounds=[PAGED_SAME_EXPERTS, other_expert, PAGED_DIFFERING_SHARE])
    check(worst_same <= PAGED_SAME_EXPERTS,
          f"logits off by {worst_same} standard deviations with the same "
          "experts chosen")
    check(worst_other <= other_expert,
          f"logits off by {worst_other} standard deviations after a "
          "differing expert choice")
    check(differing <= PAGED_DIFFERING_SHARE * max(choices, 1),
          f"{differing} of {choices} expert choices differ")


def round_mantissa(tree, dtype_name: str):
    """bfloat16 weights rounded to the nearest value with
    ``dtype_name``'s mantissa, by the bits: the v5e's compiler folds a
    conversion to float8 and back away (PR 33's first control read the
    same as its sound run). The narrower exponent range is not imitated,
    which only flatters the control. In place of the weights: both sets
    do not fit."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def rounded(x):
        drop = jnp.finfo(x.dtype).nmant - jnp.finfo(dtype_name).nmant
        bits = lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        bits = (bits + (1 << (drop - 1))) & ~((1 << drop) - 1)
        return lax.bitcast_convert_type(bits.astype(jnp.uint16), x.dtype)

    return jax.jit(lambda tree: jax.tree.map(rounded, tree),
                   donate_argnums=(0,))(tree)


# Hybrid family (PR 33), on the v5e at the published widths: worst
# difference of a logit over the reference's logit standard deviation.
HYBRID_LOGITS = 0.4
REHEARSED_HYBRID_LOGITS = 0.35  # 64 wide: a rounding is a larger share


# A state-space stack's FIRST layer's state behind a context's last
# position (``cache["ssm"][0]``; before it lie the embedding and one
# norm), against the reference's position-by-position scan from zero:
# the distance over the norm, of the state's SLOWEST column (``A = -1``:
# an entry lives 1 / dt positions, 10 to 1,000, so a rounding a position
# adds up there first), the worse of the two compared contexts (1,164
# and 3,584 positions). On the v5e at the published widths (my chip
# runs, PR 60, call F; three seeds each): sound 0.0041 to 0.0050 (what
# bfloat16 ``u``, ``dt``, ``B`` and ``C`` put in; the same at both
# contexts); the state kept in bfloat16 0.0143, 0.0275, 0.0406 (a single
# context at least 0.0123); weights rounded to float8 0.113. The bound is
# the middle of 0.0050 and 0.0143 in ratio, 1.7 times from either. The
# whole state's distance reads 0.0046 to 0.0053 sound and 0.0082 to
# 0.0238 in bfloat16: it separates too, by less. This is what tells the
# state's precision: the logits do not (the configuration's
# ``probes.logit_atol_why``).
MAMBA_FIRST_STATE = 0.0085


def first_state_error(states: dict, contexts: list, reference, params,
                      model: dict) -> float:
    """The worst compared context's error of ``states`` (row -> the
    cache's first layer's state, float32) by the measure above; 0.0
    where the reference has no ``first_state`` (the hybrid family)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    worst, whole, slowest = 0.0, [], []
    first_state = jax.jit(lambda p, t: reference.first_state(p, t, model))
    for i, state in states.items():
        want = np.asarray(first_state(params, jnp.asarray(contexts[i][None])))[0]
        off = state - want
        whole.append(float(np.linalg.norm(off) / np.linalg.norm(want)))
        slowest.append(float(np.linalg.norm(off[:, 0])
                             / np.linalg.norm(want[:, 0])))
        worst = max(worst, slowest[-1])
    if states:
        say("hybrid", check="the first layer's state behind each compared "
            "context's last position against the reference's scan",
            slowest_column=[round(x, 6) for x in slowest],
            whole_state=[round(x, 6) for x in whole],
            bound=MAMBA_FIRST_STATE)
    return worst


def phase_hybrid_logits(config: dict, model_config, model: dict, reference,
                        seed: int, rehearse: bool, device: dict,
                        round_weights: "str | None",
                        state_dtype: "str | None" = None) -> None:
    """A hybrid configuration's three caches (``llm_engine/hybrid.py``),
    or a state-space stack's state and pools (``llm_engine/mamba.py``:
    a forward of the same signature), at its real size: every
    row of the engine busy (the probes' lengths, one context nearly as
    long as the table, the rest a few chunks long), prefilled chunk by
    chunk and then decoded together, as the engine drives its two
    programs (``drive_forward``); every compared position's logits
    against the float32 reference's full forward (the cell's probes hold
    the programs themselves to the reference)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine import model as paged_model

    family = paged_model.family(model_config)
    control = round_weights or (state_dtype and f"a state in {state_dtype}")
    engine = config["engine"]
    rows, max_len = engine["max_batch_size"], engine["max_seq_len"]
    block = engine.get("block_size") or GLOBAL_CONFIG.llm_block_size
    chunk = probe_chunk(engine, block)
    width = -(-max_len // block)
    steps = config["probes"]["max_new_tokens"]
    lengths = list(config["probes"]["prompt_lengths"])[:rows - 1]
    # One long context, a multiple of the reference's query block; its
    # last `tail` positions are compared, the last `steps` decoded.
    long_len = (max_len - max_len // 8) // 512 * 512 or max_len - 2 * chunk
    tail = 4 * chunk
    rng = np.random.default_rng([seed, 33])
    filler = rng.integers(chunk, 20 * chunk, max(0, rows - len(lengths) - 1))
    filler = np.minimum(filler, max_len - steps)
    contexts = [rng.integers(1, model_config.vocab_size, int(n) + steps)
                for n in (*lengths, long_len - steps, *filler)]
    prefilled = [len(c) - steps for c in contexts]
    compared = range(len(lengths) + 1)        # the probes and the long one

    served = paged_model.serving_params(model_config, None, seed)
    if round_weights:
        served = round_mantissa(served, round_weights)
    say("hybrid", config=config["name"], layers=model_config.num_layers,
        params=model_config.num_params, rows=rows, table=max_len,
        contexts=[len(c) for c in contexts], control=control,
        ring=family.ring_positions(model_config, block, chunk),
        device_bytes_in_use=device_bytes())
    cache = family.init_cache(model_config, 1 + rows * width, block, rows,
                              chunk)
    say("hybrid", cache={k: [list(v.shape), str(v.dtype)]
                         for k, v in cache.items()},
        cache_gib=round(sum(v.nbytes for v in cache.values()) / 2 ** 30, 3))
    tables = shuffled_tables(rng, rows, width, contexts, block)
    got, _, cache = drive_forward(
        model_config, block, chunk, served, cache, contexts, prefilled,
        tables, steps, [prefilled[i] - 1 if i < len(lengths)
                        else len(context) - tail
                        for i, context in enumerate(contexts)], compared)
    check(all(bool(jnp.isfinite(v.astype(jnp.float32)).all())
              for v in cache.values()), "a cache is not finite")
    # Row ``i`` lies in row slot ``i``: the first layer's state behind
    # each compared context's last position.
    states = {i: np.asarray(cache["ssm"][0, i], np.float32)
              for i in compared} if hasattr(reference, "first_state") else {}
    del cache
    if round_weights:
        del served
        gc.collect()
        served = paged_model.serving_params(model_config, None, seed)
    params = served            # the reference's: as the seed gives them
    gc.collect()
    say("hybrid", programs="prefill chunks over shuffled tables, then "
        f"{steps} decode steps of {rows} busy rows",
        positions_compared=sum(len(g) for g in got),
        device_bytes_in_use=device_bytes())

    short = contexts[:len(lengths)]
    padded = np.zeros((len(short), -(-max(map(len, short)) // 128) * 128),
                      np.int32)
    for i, context in enumerate(short):
        padded[i, :len(context)] = context
    want = list(np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, model))(params,
                                                     jnp.asarray(padded))))
    want.append(np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, model, tail=tail))(
            params, jnp.asarray(contexts[len(lengths)][None])))[0])
    offsets = [0] * len(lengths) + [long_len - tail]
    worst, worst_gap, by_context = 0.0, 0.0, []
    for i in compared:
        std = float(want[i][:len(contexts[i]) - offsets[i]].std())
        here = 0.0
        for position, logits in got[i].items():
            row = want[i][position - offsets[i]]
            here = max(here, float(np.abs(logits - row).max()) / std)
            # What the cell's probes measure: how far under the
            # reference's best logit the program's own choice lies.
            worst_gap = max(worst_gap,
                            float(row.max() - row[logits.argmax()]))
        by_context.append(round(here, 4))
        worst = max(worst, here)
    state_error = first_state_error(states, contexts, reference, params,
                                    model)
    bound = REHEARSED_HYBRID_LOGITS if rehearse else HYBRID_LOGITS
    say("hybrid", check="logits through the three caches against the "
        f"float32 reference {config['reference']}", device=device["kind"],
        worst_diff_in_std=round(worst, 4), by_context=by_context,
        contexts=[len(contexts[i]) for i in compared],
        worst_argmax_gap=round(worst_gap, 4),
        logit_std=round(float(want[0].std()), 3), bound=bound,
        control=control)
    if state_dtype and not rehearse:
        # The state's own control: the logits cannot tell it (PR 60).
        check(state_error > MAMBA_FIRST_STATE, f"a state in {state_dtype} "
              f"read {state_error} <= {MAMBA_FIRST_STATE}: the comparison "
              "cannot tell the state's precision")
    elif control:
        check(worst > bound, f"the programs on {control} "
              f"stayed inside the bound ({worst} <= {bound}): the "
              "comparison cannot tell a lower precision")
    else:
        check(worst <= bound, f"logits off by {worst} standard deviations")
        check(rehearse or state_error <= MAMBA_FIRST_STATE,
              f"the first layer's state off by {state_error}")


# Diffusion over blocks (PR 35), on the v5e at the published widths
# (my chip runs, PR 35; four seeds sound, three with weights rounded to
# float8's mantissa). The cell's own measure, how far under the
# reference's best logit the program's choice lies: 0.10 to 0.234
# sound, 0.95 to 2.21 rounded; its limit in the cell is 0.45. The worst
# difference of a logit over the reference's logit standard deviation,
# a heavy-tailed measure here (a mixture of 128 experts with
# renormalised weights moves far on one differing choice): 0.37 to 1.47
# sound (0.20 to 0.23 where both sides chose the same experts), 2.36 to
# 3.20 rounded.
BLOCK_ARGMAX_GAP = 0.45
BLOCK_LOGITS = 1.9
REHEARSED_BLOCK_LOGITS = 0.35


def phase_block_logits(config: dict, model_config, model: dict, reference,
                       seed: int, rehearse: bool, device: dict,
                       round_weights: "str | None") -> None:
    """A block-diffusion configuration at its real size, every row of
    the engine busy: the probes' lengths, one context half the table
    long, the rest a few chunks to most of the table. Each row's whole
    blocks are prefilled chunk by chunk; then its last
    ``probes.max_new_tokens`` positions are made block by block as the
    engine makes them under the ``sequential`` rule (a pass with the
    block's earlier-fixed positions known and the rest masked, for each
    of ``denoising_steps``, then the finishing pass), the rows staggered
    so that every pass carries rows in every phase, the tokens fed
    being the context's own. Every pass goes through the engine's block
    program (whose fixed tokens must be its logits' argmax) and through
    the forward it wraps, showing the logits, which are held to the
    float32 reference's ``forward`` (entry p - 1: the logits that
    decided p) for the probes and the long context."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine import model as paged_model
    from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache

    engine = config["engine"]
    rows, max_len = engine["max_batch_size"], engine["max_seq_len"]
    block = engine.get("block_size") or GLOBAL_CONFIG.llm_block_size
    chunk = probe_chunk(engine, block)
    width = -(-max_len // block)
    size, mask_id = model_config.block_length, model_config.mask_token_id
    steps, new = model_config.denoising_steps, \
        config["probes"]["max_new_tokens"]
    family = paged_model.family(model_config)
    lengths = list(config["probes"]["prompt_lengths"])[:rows - 1]
    long_len = max_len // 2
    rng = np.random.default_rng([seed, 35])
    filler = rng.integers(chunk // size, (max_len - new) // size,
                          max(0, rows - len(lengths) - 1)) * size
    contexts = [rng.integers(1, model_config.vocab_size, int(n) + new)
                for n in (*lengths, long_len - new, *filler)]
    prefilled = [len(c) - new for c in contexts]
    compared = range(len(lengths) + 1)

    served = paged_model.serving_params(model_config, None, seed)
    if round_weights:
        served = round_mantissa(served, round_weights)
    laid = paged_model.lay_for_serving(served)   # as an engine holds it
    say("blocks", config=config["name"], layers=model_config.num_layers,
        params=model_config.num_params, rows=rows, table=max_len,
        contexts=[len(c) for c in contexts], round_weights=round_weights,
        block_length=size, denoising_steps=steps,
        device_bytes_in_use=device_bytes())
    pool = PagedKVCache.init_pool(model_config, 1 + rows * width, block)
    tables = shuffled_tables(rng, rows, width, contexts, block)
    prefill = paged_model.make_prefill_chunk(model_config, block)
    step = family.make_engine_decode_step(model_config, block)

    def show(params, pool, tokens, positions, tables, busy):
        logits, pool, _, routing = family.forward(
            params, pool, tokens, positions, tables, model_config, block,
            busy=busy)
        return logits, pool, routing

    shown = jax.jit(show, donate_argnums=(1,))
    for i, context in enumerate(contexts):
        for start in range(0, prefilled[i], chunk):
            n = min(chunk, prefilled[i] - start)
            _, pool, _ = prefill(
                laid, pool, *chunk_inputs(context, start, n, chunk),
                jnp.asarray(tables[i:i + 1]), np.int32(n), np.int32(n - 1))

    # Row i's turn t (its t-th pass) is given at global pass t + i % 3:
    # turn t is pass t % (steps + 1) of block t // (steps + 1).
    fixed_before = np.cumsum([0] + [paged_model.fix_count(size, steps, t)
                                    for t in range(steps)])
    turns = new // size * (steps + 1)
    got = [{} for _ in contexts]               # position -> logits row
    chosen = [{} for _ in contexts]            # position -> [layers, k]
    key = jax.random.PRNGKey(seed)
    for global_pass in range(turns + 2):
        active, at, done = [], {}, {}
        for i, context in enumerate(contexts):
            turn = global_pass - i % 3
            if 0 <= turn < turns:
                start = prefilled[i] + turn // (steps + 1) * size
                t = turn % (steps + 1)
                known = size if t == steps else int(fixed_before[t])
                at[i], done[i] = start, t
                active.append((
                    [int(x) for x in context[start:start + known]]
                    + [-1] * (size - known), start, 0.0,
                    paged_model.fix_count(size, steps, t) if t < steps
                    else 0, 0, 0.9, tables[i]))
        slots = sorted(at)
        after, pool, _, key = step(laid, pool, jnp.asarray(
            family.pack_decode_rows(rows, width, active, slots)), key)
        # The same pass through the showing forward, from the same rows
        # (an inactive row: zeros, as the packer leaves it).
        tokens = np.zeros((rows, size), np.int32)
        starts = np.zeros((rows, 1), np.int32)
        for i, (row_block, start, *_) in zip(slots, active):
            tokens[i] = [mask_id if t < 0 else t for t in row_block]
            starts[i] = start
        busy = np.isin(np.arange(rows), slots)
        logits, pool, routing = shown(
            laid, pool, jnp.asarray(tokens),
            jnp.asarray(starts + np.arange(size)),
            jnp.asarray(np.where(busy[:, None], tables, 0)),
            jnp.asarray(busy))
        logits, after = np.array(logits, np.float32), np.asarray(after)
        routing = np.asarray(routing)                  # [layers, B, T, k]
        logits[..., mask_id] = -np.inf
        for i in slots:
            if done[i] == steps:
                continue
            for o in range(int(fixed_before[done[i]]),
                           int(fixed_before[done[i] + 1])):
                check(int(after[i, o]) == int(logits[i, o].argmax()),
                      "the block program's token is not its logits' argmax")
                if i in compared:
                    got[i][at[i] + o] = logits[i, o]
                    chosen[i][at[i] + o] = routing[:, i, o]
    check(np.isfinite(np.asarray(pool["k"][:, :, 0, 0, 0],
                                 np.float32)).all(), "pool not finite")
    del pool, prefill, step, shown, laid
    if round_weights:
        del served
        gc.collect()
        served = paged_model.serving_params(model_config, None, seed)
    params = served            # the reference's: as the seed gives them
    gc.collect()
    say("blocks", programs="prefill chunks over shuffled tables, then "
        f"{turns + 2} passes of up to {rows} busy rows in every phase",
        positions_compared=sum(len(g) for g in got),
        device_bytes_in_use=device_bytes())

    short = contexts[:len(lengths)]
    padded = np.zeros((len(short), -(-max(map(len, short)) // 128) * 128),
                      np.int32)
    for i, context in enumerate(short):
        padded[i, :len(context)] = context
    forward = jax.jit(lambda p, t: reference.forward(p, t, model,
                                                     with_routing=True))
    short_logits, short_routing = forward(params, jnp.asarray(padded))
    want = list(np.asarray(short_logits))
    theirs = list(np.moveaxis(np.asarray(short_routing), 1, 0))
    long_logits, long_routing = forward(
        params, jnp.asarray(contexts[len(lengths)][None]))
    want.append(np.asarray(long_logits)[0])
    theirs.append(np.asarray(long_routing)[:, 0])       # [layers, L, k]
    kept = np.arange(model_config.vocab_size) != mask_id
    worst, by_context, gaps = 0.0, [], []
    differing = choices = 0
    worst_same = worst_other = 0.0
    for i in compared:
        std = float(want[i][:len(contexts[i]) - 1][:, kept].std())
        here = gap = 0.0
        for position, logits in got[i].items():
            row = want[i][position - 1]
            error = float(np.abs(logits - row)[kept].max()) / std
            # A choice differs if the reference did not make it too, at
            # the same layer, for this position's deciding pass.
            ours, ref = chosen[i][position], theirs[i][:, position - 1]
            other = ~(ours[:, :, None] == ref[:, None, :]).any(-1)
            differing += int(other.sum())
            choices += other.size
            if other.any():
                worst_other = max(worst_other, error)
            else:
                worst_same = max(worst_same, error)
            here = max(here, error)
            # What the cell's probes measure: how far under the
            # reference's best logit the program's own choice lies.
            gap = max(gap, float(row.max() - row[logits.argmax()]))
        by_context.append(round(here, 4))
        gaps.append(round(gap, 4))
        worst = max(worst, here)
    worst_gap = max(gaps)
    bound = REHEARSED_BLOCK_LOGITS if rehearse else BLOCK_LOGITS
    say("blocks", check="logits of the block passes against the float32 "
        f"reference {config['reference']}", device=device["kind"],
        worst_diff_in_std=round(worst, 4), by_context=by_context,
        contexts=[len(contexts[i]) for i in compared],
        worst_argmax_gap=worst_gap, argmax_gap_by_context=gaps, bound=bound,
        own_expert_choices_differing=differing, own_expert_choices=choices,
        worst_diff_own_choices_same=round(worst_same, 4),
        worst_diff_own_choice_differing=round(worst_other, 4),
        round_weights=round_weights)
    if round_weights:
        check(worst > bound and (rehearse or worst_gap > BLOCK_ARGMAX_GAP),
              f"weights rounded through {round_weights} stayed inside the "
              f"bounds ({worst} <= {bound} or {worst_gap} <= "
              f"{BLOCK_ARGMAX_GAP}): the comparison cannot tell a lower "
              "precision")
    else:
        check(worst <= bound, f"logits off by {worst} standard deviations")
        check(worst_gap <= BLOCK_ARGMAX_GAP, f"the program's choice lies "
              f"{worst_gap} under the reference's best logit")


# Latent attention under hyper-connections (PR 44), on the v5e at the
# published widths: the worst difference of a logit over the reference's
# logit standard deviation. The cell's own measure (how far under the
# reference's best logit the program's choice lies) is held to the
# configuration's ``probes.logit_atol``, which says how it was set.
# The share of expert choices the reference did not make too: 3.3, 3.4
# and 3.7% of 313,360 sound (1.8% in the first expert layer, 5.5% in
# the fifth: a token that took another expert once routes differently
# after), 33.8% with float8-rounded weights (three seeds and one). A
# rehearsal's toy of two expert layers reads 0.5% and 9.3%.
LATENT_SAME_EXPERTS = 0.15
# A configuration whose REHEARSAL cannot be held to it says its own under
# ``rehearsal.probes.smoke_same_experts`` with the readings that set it;
# every other run, and every run on the chip, is held to this one.
LATENT_DIFFERING_SHARE = 0.10
REHEARSED_DIFFERING_SHARE = 0.03
# The linear family's state (PR 50): the worst head's error over its
# norm, after the last position of a context of up to the whole table,
# float32 state and bfloat16 inputs against the float32 reference, of
# the FIRST layer, which lies before any expert layer: a later layer's
# state sums what 5% of tokens that routed otherwise upstream wrote (on
# the v5e at the published widths a head of layers 2 to 10 read 0.17 to
# 0.24 sound and 0.78 with float8-rounded weights, all ten layers
# together; my chip runs, PR 50) and is printed, not held. A rehearsal's
# toy (width 64, 3 of 16 experts in 4 layers) is held to neither this
# nor the toy share above: it shows that the path holds.
STATE_ERROR = 0.05


def phase_latent_logits(config: dict, model_config, model: dict, reference,
                        seed: int, rehearse: bool, device: dict,
                        round_weights: "str | None") -> None:
    """A latent-attention configuration at its real size, every row of
    the engine busy: the file's ``probes.smoke_prompt_lengths`` (inside a
    chunk and a paged block, across each), one context that ENDS at each
    of the table's three widths (a quarter, a half, the whole), the rest
    a few chunks long. Each is prefilled chunk by chunk (expanded, at
    the narrowest width that holds it, as the engine hands a chunk its
    table), then all rows decode their last ``probes.max_new_tokens``
    positions together (absorbed, every step through the whole table:
    the one width an engine builds that program at, since the step
    reads each row's own pages), teacher-forced. Compared with the
    float32 reference: the probes' decoded positions, and the last two
    chunks' worth of each long context, the reference computed with its
    queries in blocks and its head on the tail alone.

    A configuration of the linear family (``llm_engine/linear.py``:
    Kimi-Linear's delta-rule state a row beside a latent pool that some
    layers own, or Solar-Open2's beside key and value pools) is
    driven the same way, row ``i`` in row slot ``i``, the chunks in the
    chunkwise form and the steps against the state; of the long contexts
    the STATE after the last position is compared too, every KDA layer
    and head, with the reference's token-by-token one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine import model as paged_model
    from ray_tpu.serve.llm_engine.engine import table_widths

    stateful = model_config.family == "linear"
    name = model_config.family

    engine = config["engine"]
    rows, max_len = engine["max_batch_size"], engine["max_seq_len"]
    block = engine.get("block_size") or GLOBAL_CONFIG.llm_block_size
    chunk = probe_chunk(engine, block)
    width = -(-max_len // block)
    widths = table_widths(width)
    steps = config["probes"]["max_new_tokens"]
    atol = config["probes"]["logit_atol"]
    ends = [w * block for w in widths]
    # The cell's own probe is one long context (the check's logits leave
    # room for no more); the lengths that straddle a chunk and a paged
    # block are this phase's, under their own key.
    lengths = list(config["probes"].get(
        "smoke_prompt_lengths", config["probes"]["prompt_lengths"]))[
            :max(0, rows - len(ends))]
    tail = min(2 * chunk, ends[0])
    rng = np.random.default_rng([seed, 44])
    filler = rng.integers(chunk, max(12 * chunk, chunk + 1),
                          max(0, rows - len(lengths) - len(ends)))
    filler = np.minimum(filler, max_len - steps)
    contexts = [rng.integers(1, model_config.vocab_size, int(n))
                for n in (*(n + steps for n in lengths), *ends,
                          *(n + steps for n in filler))]
    prefilled = [len(c) - steps for c in contexts]
    long_rows = range(len(lengths), len(lengths) + len(ends))
    compared = range(len(lengths) + len(ends))

    served = paged_model.serving_params(model_config, None, seed)
    if round_weights:
        served = round_mantissa(served, round_weights)
    cache = paged_model.family(model_config).init_cache(
        model_config, 1 + rows * width, block, rows, chunk)
    say(name, config=config["name"], layers=model_config.num_layers,
        params=model_config.num_params, rows=rows, table=max_len,
        contexts=[len(c) for c in contexts], round_weights=round_weights,
        cache={k: [list(v.shape), str(v.dtype)] for k, v in cache.items()},
        cache_gib=round(sum(v.nbytes for v in cache.values()) / 2 ** 30, 3),
        device_bytes_in_use=device_bytes())
    tables = shuffled_tables(rng, rows, width, contexts, block)
    got, chosen, cache = drive_forward(
        model_config, block, chunk, served, cache, contexts, prefilled,
        tables, steps, [len(context) - tail if i in long_rows
                        else prefilled[i] - 1
                        for i, context in enumerate(contexts)], compared,
        table_width=lambda i: next(w for w in widths
                                   if w * block >= prefilled[i]))
    # The pool: the latent family's and Kimi-Linear's ``latent``, or the
    # keys and values of a linear configuration of grouped full layers.
    for part in sorted(set(cache) - {"kda", "conv"}):
        check(bool(jax.jit(lambda pool: jnp.isfinite(pool).all())(
            cache[part])), f"the pool ({part}) is not finite")
    states = {i: np.asarray(cache["kda"][:, i]) for i in long_rows} \
        if stateful else {}
    del cache
    if round_weights:
        del served
        gc.collect()
        served = paged_model.serving_params(model_config, None, seed)
    params = served            # the reference's: as the seed gives them
    gc.collect()
    say(name, programs="prefill chunks (expanded) over shuffled tables "
        f"at the widths {ends}, then {steps} decode steps (absorbed, at "
        f"the whole table) of {rows} busy rows",
        positions_compared=sum(len(g) for g in got),
        device_bytes_in_use=device_bytes())

    def layers_first(routing):
        """The reference's chosen experts as [expert layers, ...]."""
        routing = np.asarray(routing)
        return routing.reshape(-1, *routing.shape[2:]) if stateful \
            else routing

    short = contexts[:len(lengths)]
    want, theirs, offsets, state_errors = [], [], [], []
    if short:
        padded = np.zeros((len(short), -(-max(map(len, short)) // 128) * 128),
                          np.int32)
        for i, context in enumerate(short):
            padded[i, :len(context)] = context
        logits, routing = jax.jit(lambda p, t: reference.forward(
            p, t, model, with_routing=True))(params, jnp.asarray(padded))
        want += list(np.asarray(logits))
        theirs += list(np.moveaxis(layers_first(routing), 1, 0))
        offsets += [0] * len(short)
    for i in long_rows:
        logits, routing, *after = jax.jit(lambda p, t: reference.forward(
            p, t, model, with_routing=True, tail=tail,
            **({"with_states": True} if stateful else {})))(
                params, jnp.asarray(contexts[i][None]))
        want.append(np.asarray(logits)[0])
        theirs.append(layers_first(routing)[:, 0])      # [layers, L, k]
        offsets.append(len(contexts[i]) - tail)
        if stateful:
            # The state itself, a KDA layer: the worst head's error over
            # its norm.
            theirs_state = np.stack([np.asarray(x)[0] for x in after[0]])
            state_errors.append((
                np.linalg.norm(states[i] - theirs_state, axis=(-2, -1))
                / np.linalg.norm(theirs_state, axis=(-2, -1))).max(-1))
        say(name, reference=f"context {len(contexts[i])} done")

    worst, worst_gap, by_context, gaps = 0.0, 0.0, [], []
    differing = choices = 0
    differing_by_layer = np.zeros(model_config.sparse_layers, np.int64)
    worst_same = 0.0
    for i in compared:
        length = len(contexts[i])
        std = float(want[i][:length - offsets[i]].std())
        ours = np.stack([chosen[i][p] for p in range(length)], 1)
        other = ~(ours[..., :, None]
                  == theirs[i][:, :length, None, :]).any(-1)   # [n, L, k]
        differing += int(other.sum())
        differing_by_layer += other.sum(axis=(1, 2))
        choices += other.size
        first_differing = int(np.argmax(other.any(axis=(0, 2)))) \
            if other.any() else length
        here = gap = 0.0
        for position, logits in got[i].items():
            row = want[i][position - offsets[i]]
            error = float(np.abs(logits - row).max()) / std
            if position < first_differing:
                worst_same = max(worst_same, error)
            here = max(here, error)
            # What the cell's probes measure: how far under the
            # reference's best logit the program's own choice lies.
            gap = max(gap, float(row.max() - row[logits.argmax()]))
        by_context.append(round(here, 4))
        gaps.append(round(gap, 4))
        worst, worst_gap = max(worst, here), max(worst_gap, gap)
    bound = config["probes"].get("smoke_same_experts", LATENT_SAME_EXPERTS) \
        if rehearse else LATENT_SAME_EXPERTS
    share = LATENT_DIFFERING_SHARE if not rehearse or stateful \
        else REHEARSED_DIFFERING_SHARE
    say(name, check="logits through the latent pool against the "
        f"float32 reference {config['reference']}", device=device["kind"],
        worst_diff_in_std=round(worst, 4), by_context=by_context,
        contexts=[len(contexts[i]) for i in compared],
        worst_argmax_gap=round(worst_gap, 4), argmax_gap_by_context=gaps,
        logit_atol=atol, bound=bound,
        worst_diff_before_a_differing_choice=round(worst_same, 4),
        expert_choices_differing=differing, expert_choices=choices,
        differing_by_layer=differing_by_layer.tolist(),
        logit_std=round(float(want[0].std()), 3),
        round_weights=round_weights,
        **({"state_error_by_long_context_and_layer":
            [[round(float(e), 4) for e in layers] for layers in state_errors]}
           if stateful else {}))
    if round_weights:
        # A rehearsal's limit is loose (its file says why): there the
        # share of differing expert choices alone tells the control.
        check(differing > share * choices
              and (rehearse or worst_gap > atol),
              f"weights rounded through {round_weights} stayed inside the "
              f"bounds ({differing} of {choices} choices differ, "
              f"{worst_gap} <= {atol}): the comparison cannot tell a lower "
              "precision")
    else:
        check(worst_same <= bound, f"logits off by {worst_same} standard "
              "deviations with the same experts chosen")
        check(worst_gap <= atol, f"the program's choice lies {worst_gap} "
              "under the reference's best logit")
        check(differing <= share * choices,
              f"{differing} of {choices} expert choices differ")
        check(rehearse or all(e[0] <= STATE_ERROR for e in state_errors),
              "a head's state of the FIRST layer is off by "
              f"{[float(e[0]) for e in state_errors]} of its norm")


# ------------------------------------- a cell's check, its control (--cell)


class _LastLine:
    """A stream passed on, its last whole line that starts with
    ``prefix`` kept."""

    def __init__(self, stream, prefix: str):
        self.stream, self.prefix, self.last, self._open = \
            stream, prefix, "", ""

    def write(self, text: str) -> int:
        *whole, self._open = (self._open + text).split("\n")
        self.last = next((line for line in reversed(whole)
                          if line.startswith(self.prefix)), self.last)
        return self.stream.write(text)

    def flush(self) -> None:
        self.stream.flush()


def cell_with_rounded_replica(args, started: float) -> int:
    """``--cell <workload> --round-weights <dtype>``: one run of the cell
    as ``benchmark/run.py`` makes it (``benchmark/harness.py:main``: the
    deployment, the probes served by the replica, the window, the check
    against the plain reference), but the FIRST set of weights built in
    this process, the replica's, is rounded to the dtype's mantissa, and
    the second, which ``serve_cell.check_against_reference`` rebuilds
    from the seed, is left as it is. The run has to come out not
    correct, by ``reference_argmax_or_near_tie`` and by nothing else:
    that is the lower reading a configuration's ``logit_atol`` is set
    against."""
    import contextlib
    import dataclasses

    from benchmark import harness, spec
    from ray_tpu.serve.llm_engine import model

    check(bool(args.round_weights or args.state_dtype), "--cell is the "
          "control of a cell's check: give --round-weights or --state-dtype "
          "(benchmark/run.py makes the sound run)")
    real, built = model.serving_params, []
    real_config = spec.build_model_config

    def rounded_first(config, params=None, seed=0, **how):
        weights = real(config, params, seed, **how)
        built.append(seed)
        return round_mantissa(weights, args.round_weights) \
            if len(built) == 1 and args.round_weights else weights

    def other_state(config):
        import jax.numpy as jnp

        return dataclasses.replace(real_config(config),
                                   state_dtype=jnp.dtype(args.state_dtype))

    model.serving_params = rounded_first
    if args.state_dtype:
        # The replica keeps its state so; the weights and the reference,
        # which ask the configuration nothing of it, are what they were.
        spec.build_model_config = other_state
    out, err = _LastLine(sys.stdout, "{"), \
        _LastLine(sys.stderr, "bench[correct] ")
    # The window is short: the control reads no speed.
    argv = ["--workload", args.cell, "--seed", str(args.seed),
            "--seconds", "2" if args.rehearse else "20", "--trace", "0"]
    try:
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            harness.main(argv + ["--rehearse"] * args.rehearse, started)
    finally:
        model.serving_params = real
        spec.build_model_config = real_config
    result = json.loads(out.last)
    compared = json.loads(err.last[len(err.prefix):])
    failed = sorted(k for k, v in compared.items() if v is False)
    say("cell", workload=args.cell, round_weights=args.round_weights,
        state_dtype=args.state_dtype, weights_built=len(built), correct=result["correct"],
        worst_gap=compared["worst_gap"], mean_gap=compared["mean_gap"],
        gap_statistic=compared["gap_statistic"],
        logit_atol=compared["logit_atol"],
        checks_failed=failed)
    check(len(built) == 2, f"{len(built)} sets of weights were built, not "
          "the replica's and the check's")
    # A rehearsal's limit is loose (its file says why): there the run
    # shows only that the path holds.
    check(args.rehearse or failed == ["reference_argmax_or_near_tie"],
          f"the replica ran on weights rounded through "
          f"{args.round_weights} (its state in {args.state_dtype}) and the "
          f"cell's checks failed {failed}: "
          "the limit has to refuse a lower precision, and nothing else "
          "may fail")
    print(json.dumps({"ok": True, "device": result["device"]}), flush=True)
    return 0


# ------------------------------------------------------- four chips: sharded


def phase_sharded(sz: Sizes, seed: int, device: dict) -> None:
    """fsdp=2 x tp=2 over four chips against the one-device trajectory,
    by the parity rule of ``__graft_entry__``."""
    import jax
    import numpy as np

    import __graft_entry__ as graft
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.train_step import (
        build_train_step,
        create_train_state,
        default_optimizer,
        shard_batch,
    )

    config = sz.model
    batch_size = 2  # divisible by the data axes (dp x fsdp = 2)
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch_size, sz.seq + 1), 0,
        config.vocab_size)

    def trajectory(mesh):
        with jax.set_mesh(mesh):
            # warmup_steps=0 as in graft._trajectory_train_step: a
            # warmed-up first step would apply lr=0.
            optimizer = default_optimizer(
                learning_rate=1e-3, warmup_steps=0, total_steps=10)
            key = jax.random.PRNGKey(seed)
            state = create_train_state(
                lambda: llama.init_params(config, key), optimizer, mesh,
                llama.param_logical_axes(config))
            params = state.params

            def loss(p, batch):
                return llama.loss_fn(p, batch["tokens"], batch["targets"],
                                     config)

            step = build_train_step(loss, optimizer)
            batch = shard_batch(
                {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}, mesh)
            hlo = step.lower(state, batch).as_text()
            per_device: dict = {}
            for x in jax.tree.leaves(params):
                check(len({s.device for s in x.addressable_shards})
                      == len(mesh.devices.flat),
                      "a parameter is not on every device of the mesh")
                for s in x.addressable_shards:
                    per_device[s.device] = \
                        per_device.get(s.device, 0) + s.data.nbytes
            placement = (max(per_device.values()),
                         sum(x.nbytes for x in jax.tree.leaves(params)))
            out, times = [], []
            for _ in range(graft.PARITY_STEPS):
                start = time.perf_counter()
                state, metrics = step(state, batch)
                out.append((float(metrics["loss"]),
                            float(metrics["grad_norm"])))
                times.append(time.perf_counter() - start)
            return out, times, placement, hlo

    mesh4 = build_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
    traj, times, placement, hlo = trajectory(mesh4)
    say("sharded", mesh=dict(mesh4.shape), device=device["kind"],
        devices=4, batch=[batch_size, sz.seq],
        trajectory=[[round(a, 5), round(b, 5)] for a, b in traj],
        step_s=[round(t, 3) for t in times])
    # Every weight matrix is split four ways (fsdp x tp); only the norm
    # scales, a few KB, are replicated.
    share = placement[0] / placement[1]
    say("sharded", param_bytes=placement[1], per_device_bytes=placement[0],
        per_device_share=round(share, 4))
    check(0.25 <= share < 0.26,
          f"a device holds {share:.3f} of the parameters, not a quarter")
    if device["platform"] == "tpu":
        check("tpu_custom_call" in hlo, "no flash kernel in the sharded step")
    check("shard_map" in hlo or "manual" in hlo.lower(),
          "flash_attention_gspmd did not take its shard_map path")
    gc.collect()
    one = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    ref, ref_times, _, _ = trajectory(one)
    say("sharded", mesh="one device", device=device["kind"],
        trajectory=[[round(a, 5), round(b, 5)] for a, b in ref],
        step_s=[round(t, 3) for t in ref_times])
    worst = max(abs(g - w) / abs(w) for got, want in zip(traj, ref)
                for g, w in zip(got, want))
    say("sharded", parity_rule="__graft_entry__._assert_parity",
        rtol=SHARDED_RTOL, atol=graft.PARITY_ATOL,
        worst_rel_diff=float(np.round(worst, 6)))
    graft._assert_parity("fsdp=2 x tp=2", traj, ref, rtol=SHARDED_RTOL)
    check(all(np.isfinite(v) for pair in traj for v in pair),
          "sharded trajectory not finite")


# -------------------------------------------------------------------- main


def main(argv: "list[str] | None" = None) -> int:
    args = ARGS.parse_args(argv)
    if args.rehearse:
        # Before jax is imported: the rehearsal owns its platform.
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    started = time.perf_counter()
    if args.cell:
        return cell_with_rounded_replica(args, started)
    # The program first (it does not touch jax): without it there is
    # nothing to smoke, and nothing is printed.
    import ray_tpu
    from ray_tpu import _native
    from ray_tpu._private import compile_cache

    device = phase_device(args)
    on_tpu = device["platform"] == "tpu"

    # A rehearsal compiles tiny CPU programs: not worth keeping, and the
    # CPU backend warns on every entry it loads back.
    cache_dir = "off (rehearsal)" if args.rehearse else compile_cache.enable()
    watch_compiles()
    sz = sizes(args.rehearse)
    say("setup", cuts=sz.cuts, seed=args.seed, compile_cache=cache_dir,
        cache_dir_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)

    if args.paged_logits:
        phase_paged_logits(args.paged_logits, args.seed, args.rehearse,
                           device, args.round_weights, args.state_dtype)
    elif args.chips == 4:
        phase_sharded(sz, args.seed, device)
    else:
        phase_kernels(sz, args.seed, on_tpu)
        say("kernels", device_bytes_in_use_after=device_bytes())
        gc.collect()

        # The chip is detected without JAX (the detecting process need
        # not be the computing one); a rehearsal declares the CPU as one.
        ray_tpu.init(num_cpus=4, num_tpus=1 if args.rehearse else None)
        _native.load()
        resources = ray_tpu.cluster_resources()
        say("runtime", resources={k: v for k, v in resources.items()
                                  if k.startswith(("TPU", "CPU"))},
            native_library=_native.status(),
            chip_owner="this process (thread actors)")
        check(resources.get("TPU") == device["count"],
              f"the runtime sees {resources.get('TPU')} TPU chips, JAX "
              f"{device['count']}")
        try:
            phase_train(sz, args.seed, device)
            gc.collect()
            say("train", device_bytes_in_use_after=device_bytes())
            phase_serve(sz, args.seed, device)
        finally:
            ray_tpu.shutdown()

    say("compile", backend_compiles=COUNTS["compiles"],
        backend_compile_s=round(COUNTS["compile_s"], 1),
        persistent_cache=dict(requests=COUNTS["requests"],
                              hits=COUNTS["hits"], misses=COUNTS["misses"]),
        slowest={name: round(s, 1) for name, s in sorted(
            COUNTS["seconds_by_name"].items(), key=lambda kv: -kv[1])[:6]},
        wall_s=round(time.perf_counter() - started, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
