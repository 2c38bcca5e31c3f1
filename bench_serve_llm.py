"""LLM serving benchmark: closed-loop TTFT / per-token latency /
tokens/s, plus typed shedding under 2x overload (ISSUE 14).

Drives the paged-KV continuous-batching engine
(``serve/llm_engine/``) through the real serve path (deployment
handle, streaming generate) with a tiny float32 model, so the numbers
measure the ENGINE + serve plumbing, not matmul width:

- phase 1 (closed loop): N clients each stream requests back to back;
  TTFT is submit -> first streamed token, per-token latency the gap
  between consecutive tokens, tokens/s the aggregate emission rate.
- phase 2 (2x overload): a deliberately small engine
  (max_waiting bound) driven by 2x the clients its queue admits —
  the excess MUST shed typed (CacheExhaustedError -> 503 path) while
  every accepted stream completes exactly (no hung requests, no
  lost/doubled tokens).

Writes BENCH_SERVE_LLM.json (one JSON row per metric);
tests/test_bench_regression.py refuses refreshes recorded with the
engine disarmed, zero batched-decode steps, zero overload sheds, or
any hung/lost/doubled stream.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
import threading
import time

os.environ.setdefault("RAY_TPU_SKIP_TPU_DETECTION", "1")
# CPU-only by contract: host counts of the engine and serve plumbing on a
# toy model; the engine on the chip is chip_smoke.py's serve phase.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu.exceptions import SystemOverloadedError, TaskTimeoutError
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm_engine import LLMEngineServer

N_CLIENTS = int(os.environ.get("LLM_BENCH_CLIENTS", "4"))
REQUESTS_PER_CLIENT = int(os.environ.get("LLM_BENCH_REQUESTS", "5"))
MAX_NEW_TOKENS = int(os.environ.get("LLM_BENCH_NEW_TOKENS", "16"))
OVERLOAD_DURATION_S = float(os.environ.get("LLM_BENCH_OVERLOAD_S", "6"))
RESULTS: list[dict] = []


def _pct(sorted_vals: list, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))
    return sorted_vals[idx]


def bench_closed_loop(handle) -> None:
    ttfts: list[float] = []
    gaps: list[float] = []
    total_tokens = [0]
    lock = threading.Lock()

    def client(i: int) -> None:
        for n in range(REQUESTS_PER_CLIENT):
            prompt = [1 + i, 2 + n, 3, 4, 5, 6, 7, 8]
            t0 = time.perf_counter()
            stream = handle.options(stream=True).generate.remote(
                {"tokens": prompt, "max_new_tokens": MAX_NEW_TOKENS})
            last = t0
            first = True
            count = 0
            for _tok in stream:
                now = time.perf_counter()
                with lock:
                    if first:
                        ttfts.append((now - t0) * 1e3)
                        first = False
                    else:
                        gaps.append((now - last) * 1e3)
                    total_tokens[0] += 1
                last = now
                count += 1
            assert count == MAX_NEW_TOKENS, (i, n, count)

    # Warm the jit cache (compile) outside the measured window.
    handle.remote({"tokens": [9, 9], "max_new_tokens": 2}).result(
        timeout_s=300)
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(N_CLIENTS)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    elapsed = time.perf_counter() - start
    ttfts.sort()
    gaps.sort()
    detail = {"clients": N_CLIENTS,
              "requests_per_client": REQUESTS_PER_CLIENT,
              "max_new_tokens": MAX_NEW_TOKENS,
              "streams": len(ttfts),
              "elapsed_s": round(elapsed, 2),
              "host_cpus": os.cpu_count()}
    RESULTS.append({
        "metric": "llm_ttft_p50_ms",
        "value": round(_pct(ttfts, 0.5), 1), "unit": "ms",
        "detail": detail})
    RESULTS.append({
        "metric": "llm_ttft_p99_ms",
        "value": round(_pct(ttfts, 0.99), 1), "unit": "ms",
        "detail": {"p50_ms": round(_pct(ttfts, 0.5), 1), **detail}})
    RESULTS.append({
        "metric": "llm_per_token_ms",
        "value": round(_pct(gaps, 0.5), 2), "unit": "ms/token",
        "detail": {"p99_ms": round(_pct(gaps, 0.99), 2),
                   "samples": len(gaps), **detail}})
    engine = handle.engine_stats.remote().result(timeout_s=60)
    RESULTS.append({
        "metric": "llm_tokens_per_s",
        "value": round(total_tokens[0] / elapsed, 1),
        "unit": "tokens/s",
        "detail": {**detail, "engine": engine}})


def bench_overload() -> None:
    """2x closed-loop overload against a deliberately small engine:
    the waiting-queue bound (4) + decode batch (4) admit ~8 in flight;
    16 closed-loop clients oversubscribe 2x."""
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    dep = serve.deployment(LLMEngineServer).options(
        name="llm_overload", max_ongoing_requests=64)
    handle = serve.run(
        dep.bind(cfg, max_batch_size=4, max_seq_len=64, block_size=8,
                 prefill_chunk=8, max_waiting=4),
        name="llm_overload_app", route_prefix="/llm_overload")
    handle.remote({"tokens": [9, 9], "max_new_tokens": 2}).result(
        timeout_s=300)  # compile outside the window

    capacity = 8  # decode rows + waiting bound
    n_clients = 2 * capacity
    counts = {"ok": 0, "shed": 0, "timeout": 0, "other": 0,
              "lost": 0, "doubled": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def client(i: int) -> None:
        n = 0
        while not stop.is_set():
            try:
                out = handle.remote(
                    {"tokens": [1 + i, 2 + n, 3], "max_new_tokens": 8}
                ).result(timeout_s=60)
                tokens = out["tokens"]
                with lock:
                    if len(tokens) == 8:
                        counts["ok"] += 1
                    elif len(tokens) < 8:
                        counts["lost"] += 1
                    else:
                        counts["doubled"] += 1
            except SystemOverloadedError:
                with lock:
                    counts["shed"] += 1
                time.sleep(0.02)  # typed retry-after backoff
            except (TaskTimeoutError, TimeoutError):
                with lock:
                    counts["timeout"] += 1
            except Exception:  # noqa: BLE001 — anything else is a bug
                with lock:
                    counts["other"] += 1
            n += 1

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(OVERLOAD_DURATION_S)
    stop.set()
    hung = 0
    for t in threads:
        t.join(timeout=120)
        if t.is_alive():
            hung += 1
    elapsed = time.perf_counter() - start
    engine = handle.engine_stats.remote().result(timeout_s=60)
    RESULTS.append({
        "metric": "llm_overload_shed",
        "value": counts["shed"],
        "unit": "typed sheds",
        "detail": {"clients": n_clients, "overload_factor": 2,
                   "capacity": capacity,
                   "duration_s": OVERLOAD_DURATION_S,
                   "elapsed_s": round(elapsed, 2),
                   "ok": counts["ok"], "shed": counts["shed"],
                   "timeouts": counts["timeout"],
                   "other": counts["other"], "hung": hung,
                   "lost": counts["lost"],
                   "doubled": counts["doubled"],
                   "ok_qps": round(counts["ok"] / elapsed, 1),
                   "engine": engine,
                   "host_cpus": os.cpu_count()}})


def main() -> None:
    ray_tpu.init(ignore_reinit_error=True)
    serve.start()
    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32)
    dep = serve.deployment(LLMEngineServer).options(
        name="llm", max_ongoing_requests=64)
    handle = serve.run(
        dep.bind(cfg, max_batch_size=8, max_seq_len=64, block_size=8,
                 prefill_chunk=16),
        name="llm_bench_app", route_prefix="/llm")
    bench_closed_loop(handle)
    bench_overload()
    serve.shutdown()
    ray_tpu.shutdown()
    for row in RESULTS:
        print(json.dumps(row), flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "BENCH_SERVE_LLM.json")
    with open(out, "w") as f:
        for row in RESULTS:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
