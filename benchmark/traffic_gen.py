"""The one general traffic generator: a traffic file's parameters and a
seed give the requests or batches of a run. Same seed, same traffic.

The amount of work is fixed by the file, and only its order and timing
are drawn from a seed: lengths are the quantiles of the stated
distribution (the same multiset in every run), dealt in a seeded order;
open-loop arrivals are a Poisson process conditioned on its count
(``rate * seconds`` sorted uniform instants), so every seed offers the
same load.

A file may fix ``schedule_seed``: arrivals and the order of lengths then
come from it and are the same in every run (the replay of one synthetic
trace), and ``--seed`` draws only the tokens (and the weights). A queue
near its capacity answers a reshuffled schedule with tails that differ
by tens of percent, which no bound could hold (PERF.md, PR 22); another
schedule is another traffic file.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


def quantile(dist: dict, u: float) -> int:
    """The ``u``-quantile (0..1) of a length distribution, clipped and
    rounded to whole tokens."""
    kind = dist["dist"]
    if kind == "lognormal":
        value = dist["median"] * math.exp(
            dist["sigma"] * statistics.NormalDist().inv_cdf(u))
    elif kind == "uniform":
        value = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        value = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(value), dist.get("min", 1)),
                   dist.get("max", 1 << 30)))


def lengths(dist: dict, n: int, rng: np.random.Generator) -> list:
    """``n`` lengths: the quantiles at (i + 1/2) / n, in a seeded
    order."""
    values = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    return [values[i] for i in rng.permutation(n)]


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # seconds after the window opens (open loop)
    tokens: list            # the prompt
    max_new_tokens: int
    temperature: float = 0.0

    def payload(self) -> dict:
        return {"tokens": self.tokens,
                "max_new_tokens": self.max_new_tokens,
                "temperature": self.temperature}


def _rngs(params: dict, seed: int, stream: int) -> tuple:
    """(the schedule's generator, the tokens' generator)."""
    return (np.random.default_rng(
        [params.get("schedule_seed", seed), stream]),
        np.random.default_rng([seed, stream, 1]))


def _requests(params: dict, n: int, due, vocab_size: int,
              schedule: np.random.Generator,
              content: np.random.Generator) -> list:
    prompts = lengths(params["prompt"], n, schedule)
    outputs = lengths(params["output"], n, schedule)
    return [Request(i, float(due[i]),
                    content.integers(1, vocab_size, prompts[i]).tolist(),
                    outputs[i], float(params.get("temperature", 0.0)))
            for i in range(n)]


def open_poisson(params: dict, seconds: float, seed: int,
                 vocab_size: int) -> list:
    """Requests of an open loop at ``rate_per_s``, in order of their due
    instants."""
    schedule, content = _rngs(params, seed, 1)
    n = max(1, round(params["rate_per_s"] * seconds))
    due = np.sort(schedule.uniform(0.0, seconds, n))
    return _requests(params, n, due, vocab_size, schedule, content)


def closed_clients(params: dict, seconds: float, seed: int,
                   vocab_size: int) -> list:
    """One list of requests per client of a closed loop; a client sends
    its next when the last completed. ``requests_per_client`` is more
    than a window can use."""
    clients, each = params["clients"], params["requests_per_client"]
    flat = _requests(params, clients * each, np.zeros(clients * each),
                     vocab_size, *_rngs(params, seed, 2))
    # A client found in steady state is somewhere inside its request:
    # the first request of each is cut to a seeded share of its length,
    # so that the rows do not all finish together.
    for c in range(clients):
        first = flat[c * each]
        first.max_new_tokens = max(
            1, round(first.max_new_tokens * (c + 0.5) / clients))
    return [flat[c * each:(c + 1) * each] for c in range(clients)]


def train_batches(params: dict, seed: int, vocab_size: int):
    """Endless fresh batches of ``batch`` x ``seq_len`` tokens with
    their shifted targets, made on the host."""
    rng = np.random.default_rng([seed, 3])
    shape = (params["batch"], params["seq_len"] + 1)
    while True:
        tokens = rng.integers(0, vocab_size, shape, dtype=np.int32)
        yield {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}


GENERATORS = {"open_poisson": open_poisson, "closed_clients": closed_clients,
              "train_batches": train_batches}
