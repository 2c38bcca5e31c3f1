"""Find the knee of an open-loop serve cell, once, by hand, on the chip:
``python3 benchmark/knee_sweep.py --workload serve-chat-steady --seconds
45 --rates 1,1.5,2,2.5,3``. One engine, one window per rate, each
drained before the next. The knee is the highest rate at which the
number of requests still waiting for their first token does not grow
over the window and nothing is shed; the cell's traffic file then gets
0.8 of it as a number. No run of the benchmark does this."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    from benchmark import harness, serve_cell, spec, stats, traffic_gen

    cell = spec.load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    device = harness.device_or_refuse(cell.chips, args.rehearse)

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import compile_cache

    if not args.rehearse:
        compile_cache.enable()
    config = spec.rehearsed(cell.config, args.rehearse)
    traffic = spec.rehearsed(cell.traffic, args.rehearse)
    model_config = spec.build_model_config(config)
    ray_tpu.init(num_cpus=4, num_tpus=cell.chips if args.rehearse else None)
    try:
        handle = serve_cell.deploy(config, model_config, args.seed)
        serve_cell.warm_up(serve_cell.Clients(handle), config, model_config,
                           args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            requests = traffic_gen.open_poisson(
                dict(traffic, rate_per_s=rate), args.seconds, args.seed,
                model_config.vocab_size)
            clients = serve_cell.Clients(handle)
            before = handle.engine_stats.remote().result(timeout_s=60)
            opened = time.perf_counter() + 0.05
            clients.open_loop(requests, opened)
            time.sleep(max(0.0, opened + args.seconds - time.perf_counter()))
            closed = time.perf_counter()
            drained = serve_cell.wait_until(
                lambda: all(r.finished or r.error is not None
                            for r in clients.records), 180.0, 0.05)
            after = handle.engine_stats.remote().result(timeout_s=60)
            seen = serve_cell.reduce_window(clients.records, opened, closed)

            def waiting(at: float) -> int:
                return sum(r.due <= at and (not r.arrivals
                                            or r.arrivals[0] > at)
                           for r in clients.records)

            def unfinished(at: float) -> int:
                return sum(r.due <= at and (not r.finished
                                            or r.arrivals[-1] > at)
                           for r in clients.records)

            quarters = [opened + args.seconds * q
                        for q in (0.25, 0.5, 0.75, 1.0)]
            print(json.dumps({
                "rate_per_s": rate, "device": device["kind"],
                "window_s": closed - opened, "due": seen["due"],
                "errors": sum(r.error is not None for r in clients.records),
                "waiting_for_first_token_at_quarters":
                    [waiting(t) for t in quarters],
                "in_system_at_quarters": [unfinished(t) for t in quarters],
                "drain_s": time.perf_counter() - closed, "drained": drained,
                "tokens_per_s_in_window": seen["tokens_per_s"],
                "ttft_ms": stats.summary(seen["ttft_ms"]),
                "token_gap_ms": stats.summary(seen["gaps_ms"]),
                "lateness_ms": stats.summary(seen["lateness_ms"]),
                "engine": {k: after[k] - before[k] for k in (
                    "admitted", "shed_queue_full", "shed_cache",
                    "prefill_chunks", "prefill_tokens", "decode_steps",
                    "decode_tokens", "preemptions", "finished")},
            }), flush=True)
            clients.closing.set()
            clients.join(30.0)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
