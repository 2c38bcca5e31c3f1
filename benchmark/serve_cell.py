"""A serve cell: ``serve.run(serve.deployment(LLMEngineServer).bind(...))``
and every request streamed through the deployment handle, timed at the
client. The replica is a thread of this process, which holds the chip
and can therefore trace it; load comes from this process too, one
dispatcher and one short-lived thread per request in flight or about
to be due (the handle's stream is a blocking iterator)."""

from __future__ import annotations

import dataclasses
import gc
import os
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


def sleep_until(instant: float) -> None:
    delay = instant - time.perf_counter()
    if delay > 0:
        time.sleep(delay)


class Clients:
    """Sends requests through the handle and records what comes back."""

    LEAD_S = 0.25  # an open-loop client's thread starts this far ahead

    def __init__(self, handle):
        self.handle = handle
        self.closing = threading.Event()
        self.threads: list = []
        self.callers: list = []  # the closed loop's threads among them
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
        """Each request leaves at its due instant, whatever became of
        the earlier ones. Its client's thread is started ``LEAD_S``
        ahead and sleeps to that instant itself: a thread started AT
        the instant needs the interpreter twice more before it sends
        (``generator_lateness_p95_ms`` 3 to 6 ms at 5.5 requests/s,
        PR 67), and that wait is counted in the time to first token."""
        records = [Record(r, due=opened + r.due_s) for r in requests]
        self.records += records

        def client(record):
            sleep_until(record.due)
            if not self.closing.is_set():
                self.stream(record)

        def dispatch():
            for record in records:
                ahead = record.due - self.LEAD_S - time.perf_counter()
                if self.closing.wait(max(0.0, ahead)):
                    return
                self.start(client, record)

        self.start(dispatch)

    def closed_loop(self, per_client: list) -> None:
        """Each client sends its next request when the last completed,
        for as long as its iterable gives one
        (``traffic_gen.closed_clients``: without end)."""
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
            self.callers.append(self.threads[-1])

    def still_sending(self) -> int:
        """Callers of the closed loop that have not run out of requests
        (nor met an error)."""
        return sum(t.is_alive() for t in self.callers)

    def join(self, timeout_s: float) -> int:
        deadline = time.perf_counter() + timeout_s
        for thread in self.threads:
            thread.join(max(0.0, deadline - time.perf_counter()))
        return sum(t.is_alive() for t in self.threads)


class HostWatch(threading.Thread):
    """Sleeps 10 ms at a time through the window and keeps by how much
    each sleep overshot: a machine that stood still, or a process kept
    off its cores throughout, names itself on an earlier line. It only
    reports: no request leaves a statistic and no run fails for it."""

    STEP_S, SHOWN = 0.010, 5

    def __init__(self):
        super().__init__(daemon=True)
        self.done = threading.Event()
        self.opened = time.perf_counter()
        self.overshoots_ms: list = []  # (seconds into the window, ms)
        self.start()

    def run(self) -> None:
        before = time.perf_counter()
        while not self.done.wait(self.STEP_S):
            now = time.perf_counter()
            self.overshoots_ms.append(
                (before - self.opened, (now - before - self.STEP_S) * 1e3))
            before = now

    def close(self) -> dict:
        self.done.set()
        self.join()
        late = [ms for _, ms in self.overshoots_ms]
        worst = sorted(self.overshoots_ms, key=lambda x: -x[1])[:self.SHOWN]
        return {"sleeps_of_10_ms": len(late),
                "overshoot_max_ms": max(late, default=0.0),
                "overshoot_sum_ms": sum(late),
                "overshoot_p50_ms": stats.median(late) if late else 0.0,
                "largest_at_s": [[round(at, 2), round(ms, 1)]
                                 for at, ms in sorted(worst)]}


def host_state() -> dict:
    """What the process may run on, and how busy the machine is."""
    return {"loadavg": list(os.getloadavg()),
            "cpus_allowed": len(os.sched_getaffinity(0))}


def callers_outlast_window(sending: int, rows: int) -> dict:
    """A closed loop whose callers run out empties the rows sooner the
    faster the engine is (PERF.md, PR 27 and 28): at the window's close
    at least as many callers as rows must still be sending."""
    return {"callers_outlast_window": sending >= rows}


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


def streams_by_fifth(records: list, opened: float, closed: float) -> list:
    """The mean number of requests between their first and their last
    token, as the clients saw them, in each fifth of the window: rows
    that stand empty while callers wait show here."""
    parts = stats.FIFTHS
    width = (closed - opened) / parts
    busy = [0.0] * parts
    for r in records:
        if not r.arrivals:
            continue
        first, last = r.arrivals[0], r.arrivals[-1]
        for i in range(parts):
            start = opened + i * width
            busy[i] += max(0.0, min(last, start + width) - max(first, start))
    return [b / width for b in busy]


def reduce_window(records: list, opened: float, closed: float,
                  block: int = 256, over: str = "blocks") -> dict:
    """What the clients saw, cut to the window [opened, closed].

    ``tokens_per_s`` is the median over consecutive blocks of ``block``
    tokens of the block's rate, not tokens over seconds: twice in
    twelve runs on the chip the whole engine stood still for 2.4 s and
    12 s inside the window (my chip runs, PR 22), which moved the plain
    rate by 5% and 26% and the median by nothing. The plain rate is
    kept beside it as ``tokens_per_s_mean``.

    A traffic file may say ``rate_over: window``: ``tokens_per_s`` is
    then the plain rate, all the tokens over all the seconds. That is
    for a window whose pace is not one pace: where decode steps run
    alone or beside a prompt's chunks, and the prompts come thick at
    the window's start and thin at its end, the blocks' rates lie
    between 800 and 2,100 tokens/s and their median is the grain of
    the few blocks in the middle (4.4% between the quartiles of seven
    runs of ONE schedule at blocks of 4,096, 6.1% at 256, 6.7% at
    8,192, where the plain rate of the same runs read 1.3%; my chip
    runs, PR 54, PERF.md section 2). A standstill then shows in that
    run's rate, as its users would see it; the blocks' median stays on
    the ``bench[serve]`` line."""
    seconds = closed - opened
    due = sorted((r for r in records if opened <= r.due < closed),
                 key=lambda r: r.due)
    ttft_ms, failed = [], 0
    for r in due:
        if r.error is not None or not r.arrivals:
            failed += 1
            ttft_ms.append(seconds * 1e3)  # as bad as the window is long
        else:
            ttft_ms.append((r.arrivals[0] - r.due) * 1e3)
    gaps_ms = [ms for _, ms in sorted(
        (b, (b - a) * 1e3) for r in records
        for a, b in zip(r.arrivals, r.arrivals[1:])
        if opened <= b <= closed)]  # in the order they ended
    times = sorted(t for r in records for t in r.arrivals
                   if opened <= t <= closed)
    tokens, rates = len(times), block_rates(times, block)
    block_median = stats.median(rates) if len(rates) >= 3 else None
    # Sent before the window closed and not over before it opened (a
    # stream that ended with no token at all has no instant to be over
    # at, and counts as in flight).
    in_flight = [r for r in records if r.sent and r.sent < closed
                 and not (r.finished and r.arrivals
                          and r.arrivals[-1] < opened)]
    return {
        "due": len(due), "failed_due": failed, "ttft_ms": ttft_ms,
        "gaps_ms": gaps_ms, "tokens": tokens, "block_rates": rates,
        "streams_by_fifth": streams_by_fifth(records, opened, closed),
        "tokens_per_s": block_median
        if over == "blocks" and block_median is not None
        else tokens / seconds,
        "tokens_per_s_block_median": block_median,
        "tokens_per_s_mean": tokens / seconds,
        "lateness_ms": [(r.sent - r.due) * 1e3 for r in due if r.sent],
        "in_flight": len(in_flight),
        "errors": sum(r.error is not None for r in in_flight),
        "completed": sum(r.finished and opened <= r.arrivals[-1] <= closed
                         for r in records if r.arrivals),
    }


def window_values(seen: dict) -> dict:
    """The end-to-end numbers of a reduced window. A tail is the tail
    of ALL the window's requests, or of all its gaps: a run in which
    the engine stood still reads the worse for it, as its users would
    (404 ms for 291 in a run of PR 32 with a standstill of 3.5 s). Such
    a run names itself on the ``bench[host]`` and ``bench[serve]``
    lines; no statistic hides it."""
    values = {"serve_tokens_per_s": seen["tokens_per_s"]}
    for name, series in (("ttft", seen["ttft_ms"]),
                         ("token_gap", seen["gaps_ms"])):
        for q in (50, 90, 95, 99):
            if series:
                values[f"{name}_p{q}_ms"] = stats.percentile(series, q)
    return values


def gap_statistics(gaps: list) -> dict:
    """Of the gaps by which each served token's logit lies below the
    reference's best, the two numbers a configuration may hold to its
    ``logit_atol``. ``worst_gap``, the widest, is every cell's unless
    its file says otherwise. ``mean_gap``, over all the positions, is
    for a model whose widest gap has no upper reading: a sparse model
    of many experts at random weights, where a router's near-tie in an
    early layer, taken the other way in bfloat16, changes the choices
    of every layer after it at that one position, and the float32
    reference then ranks the served token as it would a stranger's
    (1.36 at one position of 64 beside 0.044 at the next, PERF.md
    section 2, PR 53), which is what a lower precision reads at its
    worst position too. The mean still moves by a sixty-fourth of any
    one position's gap and by the whole of a fault that shifts all."""
    return {"worst_gap": max(gaps), "mean_gap": stats.mean(gaps)}


def check_against_reference(cell, config, model_config, probes, seed,
                            say) -> dict:
    """After the engine is gone: the same weights rebuilt from the seed,
    and the plain reference's teacher-forced logits. Each served token
    must be the reference's argmax or within ``logit_atol`` of it (a
    bf16 engine may take the other side of a near-tie against a float32
    reference; PR 21 measured gaps of 0.013 and 0.029 on logits of std
    1.0 and a worst logit difference of 0.039). Which statistic of the
    positions' gaps is held to ``logit_atol`` is the configuration's to
    say (``probes.gap_statistic``, see ``gap_statistics``): the widest
    where the file says nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm_engine.model import serving_params

    reference = spec.load_module(cell.roots, "reference", config["reference"])
    model = spec.model_numbers(config)
    probing = config["probes"]
    atol = probing["logit_atol"]
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
    gaps = []
    for i, r in enumerate(probes):
        first = len(r.request.tokens) - 1
        for j, token in enumerate(r.tokens):
            row = logits[i, first + j]
            gaps.append(float(row.max() - row[token]))
    held = probing.get("gap_statistic", "worst")
    seen = gap_statistics(gaps)
    say("serve", check="each served greedy token is the float32 "
        "reference's argmax, or within logit_atol of it",
        positions=len(gaps), bf16_near_ties=sum(g > 0 for g in gaps),
        **seen, gap_statistic=held, logit_atol=atol,
        widest_gaps=sorted(gaps, reverse=True)[:4],
        logit_std=float(logits[0, :len(rows[0])].std()))
    return {"reference_argmax_or_near_tie": seen[held + "_gap"] <= atol}, \
        {**seen, "gap_statistic": held, "logit_atol": atol,
         "positions": len(gaps)}


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
        watch, host_at_opening = HostWatch(), host_state()
        compiles_before, stats_before = compiles(), engine_stats()

        if args.trace:
            time.sleep(traffic["trace_after_share"] * args.seconds)
            harness.start_trace(args.trace_dir)
            time.sleep(traffic["trace_seconds"])
            jax.profiler.stop_trace()
        time.sleep(max(0.0, opened + args.seconds - time.perf_counter()))
        closed = time.perf_counter()
        sending = clients.still_sending()
        say("host", **watch.close(), at_opening=host_at_opening,
            at_close=host_state())
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

    rate_block, rate_over = traffic.get("rate_block_tokens", 256), \
        traffic.get("rate_over", "blocks")
    seen = reduce_window(clients.records, opened, closed, rate_block,
                         rate_over)
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
    if traffic["generator"] == "closed_clients":
        checks.update(callers_outlast_window(sending, rows))
    near_tie, compared = check_against_reference(
        cell, config, model_config, probes, args.seed, say)
    checks.update(near_tie)
    say("serve", window_s=closed - opened, due=seen["due"],
        callers_still_sending=sending,
        in_flight=seen["in_flight"], completed=seen["completed"],
        tokens=seen["tokens"], tokens_per_s=seen["tokens_per_s"],
        tokens_per_s_mean=seen["tokens_per_s_mean"],
        tokens_per_s_block_median=seen["tokens_per_s_block_median"],
        rate_over=rate_over, rate_block_tokens=rate_block,
        ttft_ms=stats.summary(seen["ttft_ms"]),
        token_gap_ms=stats.summary(seen["gaps_ms"]),
        ttft_p90_ms_by_fifth=stats.percentile_by_fifth(seen["ttft_ms"], 90),
        block_rates=stats.summary(seen["block_rates"]),
        block_rate_p50_by_fifth=stats.percentile_by_fifth(
            seen["block_rates"], 50),
        streams_by_fifth=seen["streams_by_fifth"],
        generator_lateness_ms=stats.summary(seen["lateness_ms"]),
        engine_counters=counters, checks=checks)
    open_loop = traffic["generator"] == "open_poisson"
    values = window_values(seen)
    return {
        "correct": all(checks.values()),
        "compared": {**compared, "callers_still_sending": sending,
                     **checks},
        "attempted": seen["due"] if open_loop else seen["in_flight"],
        "failed": seen["failed_due"] if open_loop else seen["errors"],
        "setup_s": opened - started, "values": values,
        "memory": memory,
        "counters": {**counters, **engine_args},
        # Not the engine's: requests between their first and last token
        # at the clients, mean over the window [opened, closed].
        "clients": {"streams": stats.mean(seen["streams_by_fifth"])},
        "harness": {"generator_lateness_ms": seen["lateness_ms"], **values},
        "config": config, "traffic": traffic,
    }
