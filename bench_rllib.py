"""RLlib throughput benchmarks.

Measures env-steps/sec against BASELINE.md's 1M env-steps/sec north
star (reference: rllib's IMPALA throughput on CPU rollout fleets):

1. raw sampling throughput — N process-isolated env-runner actors
   (``.options(process=True)``: real OS processes, so the fleet scales
   past one GIL) each stepping a vectorized CartPole;
2. IMPALA end-to-end — async sample + V-trace learner updates + weight
   broadcast, measured as env-steps consumed by the learner per second.

Run: python bench_rllib.py [num_runners]  (CPU-only)
Prints one JSON line per metric (same format as bench_core.py).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("RAY_TPU_SKIP_TPU_DETECTION", "1")
# CPU-only by contract: these are host counts of the framework, so the
# learner jit must not land on an accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

import ray_tpu


def bench_raw_sampling(num_runners: int, num_envs: int = 512,
                       fragment: int = 200, rounds: int = 5) -> dict:
    from ray_tpu.rllib import RLModuleSpec, SingleAgentEnvRunner

    spec = RLModuleSpec(observation_size=4, num_actions=2,
                        model_config={"hidden": (64, 64)})
    module = spec.build()
    import jax

    weights = module.init(jax.random.PRNGKey(0))

    RemoteRunner = ray_tpu.remote(SingleAgentEnvRunner).options(
        process=True)
    runners = [
        RemoteRunner.remote(
            env_id="CartPole-v1", module_spec=spec, num_envs=num_envs,
            rollout_fragment_length=fragment, seed=i, worker_index=i)
        for i in range(num_runners)]
    ref = ray_tpu.put(weights)
    ray_tpu.get([r.set_weights.remote(ref, 0) for r in runners])
    # Warmup at the REAL fragment length (the policy step re-jits
    # per shape; warming at a different T would time compilation).
    ray_tpu.get([r.sample.remote(fragment) for r in runners])

    start = time.perf_counter()
    total_steps = 0
    for _ in range(rounds):
        batches = ray_tpu.get([r.sample.remote() for r in runners])
        for b in batches:
            T, B = np.shape(b["rewards"])
            total_steps += T * B
    elapsed = time.perf_counter() - start
    for r in runners:
        ray_tpu.kill(r)
    return {"metric": "rllib_sampling_env_steps_per_s",
            "value": round(total_steps / elapsed, 1),
            "unit": "steps/s",
            "detail": {"num_runners": num_runners, "num_envs": num_envs,
                       "fragment": fragment}}


def bench_impala_e2e(num_runners: int, num_envs: int = 512,
                     fragment: int = 200, iters: int = 8) -> dict:
    """Tuned rollout geometry: 512 env lanes x 200-step fragments
    amortize per-batch transport/update overhead (the reference's tuned
    IMPALA examples scale fragment and env counts the same way); the
    runners ship only the columns the V-trace learner consumes."""
    from ray_tpu.rllib import IMPALAConfig

    config = (IMPALAConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=num_runners,
                           num_envs_per_env_runner=num_envs,
                           rollout_fragment_length=fragment)
              .training(num_batches_per_step=4))
    algo = config.build()
    algo.train()  # warmup: compile policy + learner
    start = time.perf_counter()
    trained = 0
    for _ in range(iters):
        result = algo.train()
        trained += result["num_env_steps_trained"]
    elapsed = time.perf_counter() - start
    algo.cleanup()
    return {"metric": "rllib_impala_env_steps_per_s",
            "value": round(trained / elapsed, 1),
            "unit": "steps/s",
            "detail": {"num_runners": num_runners, "num_envs": num_envs,
                       "fragment": fragment,
                       "topology": "driver-local learner + "
                       f"{num_runners} process env-runner actors, "
                       "batches via shm object transport",
                       "broadcast_interval": 1}}


def bench_learner_only(num_envs: int = 512, fragment: int = 200,
                       iters: int = 30) -> dict:
    """Learner-path ceiling: V-trace updates on ONE pre-collected batch
    in a tight loop — no sampling, no transport. Together with the raw
    sampling number this bounds the achievable e2e rate on this host:
    e2e <= 1 / (1/sampling + 1/learner) when both share the same
    core(s), which is exactly the single-box regime."""
    import jax

    from ray_tpu.rllib import IMPALAConfig

    config = (IMPALAConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=0,
                           num_envs_per_env_runner=num_envs,
                           rollout_fragment_length=fragment))
    algo = config.build()
    batch = algo.local_env_runner.sample(fragment)
    from ray_tpu.rllib.utils.sample_batch import Columns, SampleBatch

    sb = SampleBatch({k: batch[k] for k in (
        Columns.OBS, Columns.ACTIONS, Columns.REWARDS,
        Columns.TERMINATEDS, Columns.TRUNCATEDS, Columns.ACTION_LOGP)})
    sb["bootstrap_value"] = batch["bootstrap_value"]
    steps_per_batch = int(np.shape(batch[Columns.REWARDS])[0]
                          * np.shape(batch[Columns.REWARDS])[1])
    metrics = algo.learner_group.update_from_batch(
        sb, shard=False, sync_metrics=False)  # compile
    jax.device_get(metrics)
    start = time.perf_counter()
    for _ in range(iters):
        metrics = algo.learner_group.update_from_batch(
            sb, shard=False, sync_metrics=False)
    jax.device_get(metrics)
    elapsed = time.perf_counter() - start
    algo.cleanup()
    return {"metric": "rllib_learner_only_env_steps_per_s",
            "value": round(iters * steps_per_batch / elapsed, 1),
            "unit": "steps/s",
            "detail": {"batch_shape": [fragment, num_envs],
                       "iters": iters}}


def main() -> None:
    positional = [a for a in sys.argv[1:] if not a.startswith("-")]
    num_runners = int(positional[0]) if positional else min(
        8, max(2, (os.cpu_count() or 4) - 2))
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=max(num_runners + 2, os.cpu_count() or 4))

    results = [
        bench_raw_sampling(num_runners),
        bench_impala_e2e(num_runners),
        bench_learner_only(),
    ]

    # Runner-count scaling curve: on a multi-core host the e2e number
    # climbs with the fleet; on a 1-core host it plateaus at the
    # serial-composition bound the learner-only/sampling ceilings
    # predict — the curve is the evidence either way.
    if "--no-scaling" not in sys.argv:
        curve = []
        for n in (1, 2, 4):
            e2e = bench_impala_e2e(n, iters=4)
            curve.append({"num_runners": n, "e2e_steps_per_s":
                          e2e["value"]})
            print(json.dumps({"scaling_point": curve[-1]}), flush=True)
        sampling = next(r for r in results
                        if r["metric"] == "rllib_sampling_env_steps_per_s")
        learner = next(r for r in results
                       if r["metric"] == "rllib_learner_only_env_steps_per_s")
        bound = 1.0 / (1.0 / sampling["value"] + 1.0 / learner["value"])
        results.append({
            "metric": "rllib_impala_scaling_curve",
            "value": curve[-1]["e2e_steps_per_s"],
            "unit": "steps/s",
            "detail": {
                "curve": curve,
                "host_cpus": os.cpu_count(),
                "sampling_ceiling": sampling["value"],
                "learner_ceiling": learner["value"],
                "serial_composition_bound": round(bound, 1),
                "note": "on a single-core host sampling and learning "
                        "share the core, so e2e is bounded by the "
                        "serial composition of the two ceilings; the "
                        "1M steps/s target (BASELINE.md:29) assumes a "
                        "multi-core rollout fleet",
            }})

    for r in results:
        r["detail"]["host_cpus"] = os.cpu_count()
        print(json.dumps(r), flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_RLLIB.json"), "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")

    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
