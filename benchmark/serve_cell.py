"""A serve cell: ``serve.run(serve.deployment(LLMEngineServer).bind(...))``
and every request streamed through the deployment handle, timed at the
client. The replica is a thread of this process, which holds the chip
and can therefore trace it; load comes from this process too, one
dispatcher and one short-lived thread per request in flight (the
handle's stream is a blocking iterator)."""

from __future__ import annotations

import dataclasses
import gc
import threading
import time

from benchmark import harness, spec, stats, traffic_gen

APP = "bench_llm"


@dataclasses.dataclass
class Record:
    """One request as its client saw it. Times are ``perf_counter``."""
    request: traffic_gen.Request
    due: float = 0.0
    sent: float = 0.0
    arrivals: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    error: "BaseException | None" = None
    finished: bool = False


class Clients:
    """Sends requests through the handle and records what comes back."""

    def __init__(self, handle):
        self.handle = handle
        self.closing = threading.Event()
        self.threads: list = []
        self.records: list = []

    def stream(self, record: Record) -> None:
        record.sent = time.perf_counter()
        try:
            for token in self.handle.options(stream=True).generate.remote(
                    record.request.payload()):
                record.arrivals.append(time.perf_counter())
                record.tokens.append(token)
            record.finished = True
        except Exception as exc:  # noqa: BLE001 — counted as a failure
            if not self.closing.is_set():
                record.error = exc

    def start(self, target, *args) -> None:
        thread = threading.Thread(target=target, args=args, daemon=True)
        self.threads.append(thread)
        thread.start()

    def open_loop(self, requests: list, opened: float) -> None:
        """The dispatcher: each request leaves at its due instant,
        whatever became of the earlier ones."""
        records = [Record(r, due=opened + r.due_s) for r in requests]
        self.records += records

        def dispatch():
            for record in records:
                delay = record.due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                if self.closing.is_set():
                    return
                self.start(self.stream, record)

        self.start(dispatch)

    def closed_loop(self, per_client: list) -> None:
        """Each client sends its next request when the last completed."""
        def client(requests):
            for request in requests:
                if self.closing.is_set():
                    return
                record = Record(request, due=time.perf_counter())
                self.records.append(record)
                self.stream(record)
                if record.error is not None:
                    return

        for requests in per_client:
            self.start(client, requests)

    def join(self, timeout_s: float) -> int:
        deadline = time.perf_counter() + timeout_s
        for thread in self.threads:
            thread.join(max(0.0, deadline - time.perf_counter()))
        return sum(t.is_alive() for t in self.threads)


def wait_until(condition, timeout_s: float, poll_s: float = 0.01) -> bool:
    deadline = time.perf_counter() + timeout_s
    while not condition():
        if time.perf_counter() > deadline:
            return False
        time.sleep(poll_s)
    return True


def block_rates(times: list, block: int) -> list:
    """Tokens per second of each consecutive block of ``block`` tokens,
    from their sorted arrival instants."""
    return [block / (times[i + block] - times[i])
            for i in range(0, len(times) - block, block)]


def reduce_window(records: list, opened: float, closed: float,
                  block: int = 256) -> dict:
    """What the clients saw, cut to the window [opened, closed].

    ``tokens_per_s`` is the median over consecutive blocks of ``block``
    tokens of the block's rate, not tokens over seconds: twice in
    twelve runs on the chip the whole engine stood still for 2.4 s and
    12 s inside the window (my chip runs, PR 22), which moved the plain
    rate by 5% and 26% and the median by nothing. The plain rate is
    kept beside it as ``tokens_per_s_mean``."""
    seconds = closed - opened
    due = [r for r in records if opened <= r.due < closed]
    ttft_ms, failed = [], 0
    for r in due:
        if r.error is not None or not r.arrivals:
            failed += 1
            ttft_ms.append(seconds * 1e3)  # as bad as the window is long
        else:
            ttft_ms.append((r.arrivals[0] - r.due) * 1e3)
    gaps_ms = [(b - a) * 1e3 for r in records
               for a, b in zip(r.arrivals, r.arrivals[1:])
               if opened <= b <= closed]
    times = sorted(t for r in records for t in r.arrivals
                   if opened <= t <= closed)
    tokens, rates = len(times), block_rates(times, block)
    # Sent before the window closed and not over before it opened.
    in_flight = [r for r in records if r.sent and r.sent < closed
                 and not (r.finished and r.arrivals[-1] < opened)]
    return {
        "due": len(due), "failed_due": failed, "ttft_ms": ttft_ms,
        "gaps_ms": gaps_ms, "tokens": tokens,
        "tokens_per_s": stats.median(rates) if len(rates) >= 3
        else tokens / seconds,
        "tokens_per_s_mean": tokens / seconds,
        "lateness_ms": [(r.sent - r.due) * 1e3 for r in due if r.sent],
        "in_flight": len(in_flight),
        "errors": sum(r.error is not None for r in in_flight),
        "completed": sum(r.finished and opened <= r.arrivals[-1] <= closed
                         for r in records if r.arrivals),
    }


def check_against_reference(cell, config, model_config, probes, seed,
                            say) -> dict:
    """After the engine is gone: the same weights rebuilt from the seed,
    and the plain reference's teacher-forced logits. Each served token
    must be the reference's argmax or within ``logit_atol`` of it (a
    bf16 engine may take the other side of a near-tie against a float32
    reference; PR 21 measured gaps of 0.013 and 0.029 on logits of std
    1.0 and a worst logit difference of 0.039)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_engine.model import serving_params

    reference = spec.load_module(cell.roots, "reference", config["reference"])
    model = spec.model_numbers(config)
    atol = config["probes"]["logit_atol"]
    params = serving_params(model_config, None, seed)
    say("serve", check="weights rebuilt for the reference")
    rows = [r.request.tokens + r.tokens[:-1] for r in probes]
    width = -(-max(len(r) for r in rows) // 128) * 128
    padded = np.zeros((len(rows), width), np.int32)  # causal: padding
    for i, row in enumerate(rows):                   # changes nothing before it
        padded[i, :len(row)] = row
    logits = np.asarray(jax.jit(
        lambda p, t: reference.forward(p, t, model))(params,
                                                     jnp.asarray(padded)))
    near_ties, worst, positions = 0, 0.0, 0
    for i, r in enumerate(probes):
        first = len(r.request.tokens) - 1
        for j, token in enumerate(r.tokens):
            row = logits[i, first + j]
            gap = float(row.max() - row[token])
            positions += 1
            near_ties += gap > 0
            worst = max(worst, gap)
    say("serve", check="each served greedy token is the float32 "
        "reference's argmax, or within logit_atol of it",
        positions=positions, bf16_near_ties=int(near_ties),
        worst_gap=worst, logit_atol=atol,
        logit_std=float(logits[0, :len(rows[0])].std()))
    return {"reference_argmax_or_near_tie": worst <= atol}


def deploy(config: dict, model_config, seed: int):
    """The deployment as a user starts it; returns its handle. The
    weights are built inside the replica from the seed, as a user's
    would be loaded there; bind() carries no arrays."""
    from ray_tpu import serve

    deployment = serve.deployment(spec.resolve(config["server"])).options(
        name=APP, **config["deployment_options"])
    return serve.run(
        deployment.bind(model_config, None, seed=seed, **config["engine"]),
        name=APP + "_app", route_prefix="/" + APP, _wait_s=600.0)


def warm_up(clients: Clients, config: dict, model_config, seed: int) -> list:
    """Greedy probes whose prompts straddle chunk and block boundaries
    run the prefill and decode programs and the first token's sampler;
    they are also what the reference checks. Then: is the replica in
    this process, which is the one that can trace the chip?"""
    import jax
    import numpy as np

    probing = config["probes"]
    rng = np.random.default_rng([seed, 4])
    probes = [Record(traffic_gen.Request(
        i, 0.0, rng.integers(1, model_config.vocab_size, n).tolist(),
        probing["max_new_tokens"]))
        for i, n in enumerate(probing["prompt_lengths"])]
    for record in probes:
        clients.start(clients.stream, record)
    if clients.join(600.0):
        raise SystemExit("a warm-up request hung")
    clients.threads.clear()
    live = sum(x.nbytes for x in jax.live_arrays())
    if live < model_config.num_params * 2:
        raise SystemExit(
            f"this process holds {live} bytes of arrays, less than the "
            "served weights: the replica is in another process and "
            "cannot be traced")
    return probes


def run(cell, args, started: float, say, compiles) -> dict:
    import jax

    import ray_tpu
    from ray_tpu import serve

    config = spec.rehearsed(cell.config, args.rehearse)
    traffic = spec.rehearsed(cell.traffic, args.rehearse)
    engine_args, model_config = config["engine"], \
        spec.build_model_config(config)
    generator = traffic_gen.GENERATORS[traffic["generator"]]
    ray_tpu.init(num_cpus=4, num_tpus=cell.chips if args.rehearse else None)
    try:
        handle = deploy(config, model_config, args.seed)
        say("setup", step="deployment up, weights in the replica")
        clients = Clients(handle)
        probes = warm_up(clients, config, model_config, args.seed)
        say("setup", step="probes served: programs warm")
        probing = config["probes"]
        served = all(r.error is None
                     and len(r.tokens) == probing["max_new_tokens"]
                     and all(0 <= t < model_config.vocab_size
                             for t in r.tokens) for r in probes)

        def engine_stats() -> dict:
            return handle.engine_stats.remote().result(timeout_s=60)

        if traffic["generator"] == "closed_clients":
            # Rows are ramped full before the window opens.
            clients.closed_loop(generator(
                traffic, args.seconds, args.seed, model_config.vocab_size))
            rows = min(traffic["clients"], engine_args["max_batch_size"])
            wait_until(lambda: sum(bool(r.arrivals) for r in
                                   list(clients.records)) >= rows,
                       traffic["ramp_timeout_s"])
            opened = time.perf_counter()
        else:
            opened = time.perf_counter() + 0.05
            clients.open_loop(generator(
                traffic, args.seconds, args.seed, model_config.vocab_size),
                opened)
            time.sleep(max(0.0, opened - time.perf_counter()))
        compiles_before, stats_before = compiles(), engine_stats()

        if args.trace:
            time.sleep(traffic["trace_after_share"] * args.seconds)
            harness.start_trace(args.trace_dir)
            time.sleep(traffic["trace_seconds"])
            jax.profiler.stop_trace()
        time.sleep(max(0.0, opened + args.seconds - time.perf_counter()))
        closed = time.perf_counter()
        stats_after, compiles_after = engine_stats(), compiles()
        if traffic["generator"] == "open_poisson":
            # Drain: every request due in the window gets its chance of
            # a first token; what comes after the window is not counted.
            wait_until(lambda: all(r.arrivals or r.error is not None
                                   for r in clients.records
                                   if r.due < closed) and
                       len(clients.records) > 0, traffic["drain_s"])
        memory = harness.fullest_chip_memory()
        say("serve", window="closed and drained; shutting the engine down")
        clients.closing.set()
        serve.shutdown()
        hung = clients.join(30.0)
        say("serve", engine="down", client_threads_left=hung)
    finally:
        ray_tpu.shutdown()

    seen = reduce_window(clients.records, opened, closed,
                         traffic.get("rate_block_tokens", 256))
    counters = {k: stats_after[k] - stats_before[k] for k in stats_after
                if isinstance(stats_after[k], int)
                and not isinstance(stats_after[k], bool)}
    # The engine is gone; free what it held before the weights are
    # built a second time for the reference.
    del handle, clients.handle
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    checks = {"probes_served": served, "no_client_thread_hung": hung == 0,
              "no_compile_in_window": compiles_after == compiles_before}
    checks.update(check_against_reference(
        cell, config, model_config, probes, args.seed, say))
    say("serve", window_s=closed - opened, due=seen["due"],
        in_flight=seen["in_flight"], completed=seen["completed"],
        tokens=seen["tokens"], tokens_per_s=seen["tokens_per_s"],
        tokens_per_s_mean=seen["tokens_per_s_mean"],
        ttft_ms=stats.summary(seen["ttft_ms"]),
        token_gap_ms=stats.summary(seen["gaps_ms"]),
        generator_lateness_ms=stats.summary(seen["lateness_ms"]),
        engine_counters=counters, checks=checks)
    open_loop = traffic["generator"] == "open_poisson"
    values = {"serve_tokens_per_s": seen["tokens_per_s"]}
    for name, series in (("ttft", seen["ttft_ms"]),
                         ("token_gap", seen["gaps_ms"])):
        for q in (50, 90, 95, 99):
            if series:
                values[f"{name}_p{q}_ms"] = stats.percentile(series, q)
    return {
        "correct": all(checks.values()),
        "attempted": seen["due"] if open_loop else seen["in_flight"],
        "failed": seen["failed_due"] if open_loop else seen["errors"],
        "setup_s": opened - started, "values": values,
        "memory": memory,
        "counters": {**counters, **engine_args},
        "harness": {"generator_lateness_ms": seen["lateness_ms"], **values},
        "config": config, "traffic": traffic,
    }
