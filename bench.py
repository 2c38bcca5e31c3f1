"""Llama training-step MFU: one cell, on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

What it is until the benchmark of ROADMAP S1 replaces it: the training
step (GSPMD jit, bf16, flash kernels, remat, AdamW) of a ~350M Llama at
an invented width, sized for one chip's HBM, as MFU against the 35%
target of BASELINE.md. It measures a device, so it fails without a TPU
and for a device whose peak it does not know; it has no CPU mode. JAX
stays in this one process. ``chip_smoke.py`` is the check that the main
path runs at a published width.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax

# bf16 peak FLOP/s of one chip, by ``device_kind`` as JAX reports it
# (a v5e is "TPU v5 lite", a v5p is "TPU v5"). Source: Google Cloud
# documentation, "TPU v5e" / "TPU v5p" / "TPU v4" system architecture
# pages. A device that is not here is an error, never a default.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v4": 275e12,
}


def peak_flops(device) -> float:
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind="
            f"{device.device_kind!r} (platform {device.platform!r}); "
            f"this benchmark measures a TPU from {sorted(PEAK_FLOPS)}"
        ) from None


def bench_config():
    from ray_tpu.models.llama import LlamaConfig

    # ~350M params: fits params+AdamW(f32)+activations in 16GB HBM.
    # flash (pallas kernels, fwd + fused bwd, GQA-native via a
    # rep-axis vmap into the launch grid) + "dots" remat.
    return dataclasses.replace(
        LlamaConfig(),
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=16, num_kv_heads=8, head_dim=64,
        max_seq_len=2048, attention="flash", remat_policy="dots")


def main() -> None:
    from ray_tpu._private import compile_cache
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.train_step import (
        build_train_step,
        create_train_state,
        default_optimizer,
        shard_batch,
    )

    device = jax.devices()[0]
    peak = peak_flops(device)  # before any work: no TPU, no benchmark
    compile_cache.enable()
    config = bench_config()
    batch_size, seq_len = 8, 2048

    mesh = build_mesh(MeshConfig(dp=1), devices=[device])
    with jax.set_mesh(mesh):
        optimizer = default_optimizer(learning_rate=3e-4, warmup_steps=10,
                                      total_steps=1000)
        key = jax.random.PRNGKey(0)
        state = create_train_state(
            lambda: llama.init_params(config, key), optimizer, mesh,
            llama.param_logical_axes(config))

        def loss(params, batch):
            return llama.loss_fn(params, batch["tokens"], batch["targets"],
                                 config)

        step = build_train_step(loss, optimizer)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch_size, seq_len + 1), 0,
            config.vocab_size)
        batch = shard_batch(
            {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}, mesh)

        # Warm-up: compiles. Every timing below ends in
        # block_until_ready, which waits for the device (chip_smoke.py
        # checks that on the chip: the same steps, each fetched to the
        # host, take alike).
        state, metrics = step(state, batch)
        jax.block_until_ready(state)

        # The headline is the MEDIAN of 3 windows' median step, with the
        # spread reported alongside.
        n_windows, steps_per_window = 3, 6
        window_times = []
        for _ in range(n_windows):
            times = []
            for _ in range(steps_per_window):
                start = time.perf_counter()
                state, metrics = step(state, batch)
                jax.block_until_ready(state)
                times.append(time.perf_counter() - start)
            times.sort()
            window_times.append(times[len(times) // 2])

    tokens_per_step = batch_size * seq_len

    def window_mfu(step_time: float) -> float:
        tps = tokens_per_step / step_time
        return tps * llama.flops_per_token(config, seq_len) / peak

    window_times.sort()
    step_time = window_times[len(window_times) // 2]
    tokens_per_sec = tokens_per_step / step_time
    mfu = window_mfu(step_time)
    mfus = sorted(window_mfu(t) for t in window_times)
    spread = (mfus[-1] - mfus[0]) / mfu if mfu else 0.0

    print(json.dumps({
        "metric": "llama_350m_train_mfu",
        "value": round(mfu, 4),
        "unit": "mfu_fraction",
        "vs_baseline": round(mfu / 0.35, 4),
        "detail": {
            "platform": device.platform,
            "device": device.device_kind,
            "device_count": len(jax.devices()),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "step_time_s": round(step_time, 4),
            "params": config.num_params,
            "batch": [batch_size, seq_len],
            "loss": float(metrics["loss"]),
            "windows_mfu": [round(m, 4) for m in mfus],
            "spread_frac": round(spread, 4),
        },
    }))


if __name__ == "__main__":
    sys.exit(main())
