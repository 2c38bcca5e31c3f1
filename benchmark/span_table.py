"""A kept trace's program spans as a table: ``python3
benchmark/span_table.py <trace directory or .xplane.pb>`` after a run
with ``--trace 1 --keep-trace`` (the directory is
``.bench_trace/<cell>``). One JSON line a span name: how many, the sum
and two percentiles of their durations, their own wall time (less the
spans nested in them on their thread), the percentiles of ``age_us``
and, for the spans that carry ``cpu_us`` (a ``phase`` made with
``cpu=True``: the two hops a request makes once, or every hop in a
tree changed to measure it), their own CPU time and the time they
stood (own wall less own CPU); then one line a kind of thread (the
engine's, the stream threads, the clients, the actors' threads) with
its spans' own wall and CPU; then the gaps between consecutive leaf
spans of the engine thread. With the device's plane in the trace the
CPU sums are also given per run of ``jit_decode_step``.

It is how a partition of a pass by layer is read by hand for a cell no
metric lists, and what a throw-away script did for PR 36's one
regression (a 2 ms gap from ``engine.decode.emit`` to the next
``engine.sweep``). The readers ``trace_span_attr`` and
``trace_span_pair`` read single numbers from the same spans."""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import stats, trace_reduce  # noqa: E402
from benchmark.readers import trace_span_attr  # noqa: E402

# The first pattern a thread's spans match names its kind.
THREAD_KINDS = (("engine", r"^engine\."),
                ("stream", r"^(llm\.stream\.|serve\.stream\.put$)"),
                ("client", r"^serve\.(stream\.get|handle\.send)$"),
                ("actor", r"^runtime\.actor\.run$"))


def exclusive(spans: list, value) -> list:
    """``value(span)`` for each of ``spans`` (None where it has none),
    with the values of the spans nested directly in it on its thread
    taken off (a ``serve.stream.put`` holds the ``runtime.actor.submit``
    and the ``runtime.get`` of its actor call), never below 0: sums over
    a thread's spans count nothing twice."""
    own = [value(span) for span in spans]
    by_thread: dict = {}
    for at, span in enumerate(spans):
        if own[at] is not None:
            by_thread.setdefault(span[4], []).append(at)
    for mine in by_thread.values():
        mine.sort(key=lambda at: (spans[at][1], -spans[at][2]))
        covering: list = []  # the spans open at this instant, outermost first
        for at in mine:
            while covering and spans[covering[-1]][2] <= spans[at][1]:
                covering.pop()
            if covering and spans[at][2] <= spans[covering[-1]][2]:
                own[covering[-1]] -= value(spans[at])
            covering.append(at)
    return [None if v is None else max(0.0, v) for v in own]


def own(spans: list) -> list:
    """(name, thread, wall ns, own wall ns, own cpu us or None, age us or
    None): own wall and CPU leave out the spans nested directly inside."""
    wall = exclusive(spans, lambda s: s[2] - s[1])
    cpu = exclusive(spans, lambda s: s[3].get("cpu_us"))
    return [(name, thread, end - start, wall[at], cpu[at],
             attrs.get("age_us"))
            for at, (name, start, end, attrs, thread) in enumerate(spans)]


def thread_kinds(spans: list) -> dict:
    """thread -> the kind of the first pattern one of its spans matches."""
    names: dict = {}
    for name, _, _, _, thread in spans:
        names.setdefault(thread, set()).add(name)
    out = {}
    for thread, seen in names.items():
        out[thread] = next(
            (kind for kind, pattern in THREAD_KINDS
             if any(re.search(pattern, name) for name in seen)), "other")
    return out


def table(spans: list, steps: int = 0) -> list:
    """The lines, as dicts; ``steps``: runs of the decode program in the
    window (0: not known, no per-step column)."""
    def ms(ns_or_us, unit):
        return ns_or_us / unit

    def per_step(value):
        return {"per_step": value / steps} if steps else {}

    rows, kinds = own(spans), thread_kinds(spans)
    lines = []
    for name in sorted({r[0] for r in rows}):
        mine = [r for r in rows if r[0] == name]
        walls = [ms(r[2], 1e6) for r in mine]
        line = {"span": name, "n": len(mine), "wall_ms": sum(walls),
                "wall_ms_p50": stats.percentile(walls, 50),
                "wall_ms_p95": stats.percentile(walls, 95),
                "own_wall_ms": sum(ms(r[3], 1e6) for r in mine)}
        timed = [r for r in mine if r[4] is not None]
        if timed:
            cpu = sum(ms(r[4], 1e3) for r in timed)
            own_wall = sum(ms(r[3], 1e6) for r in timed)
            line.update(own_cpu_ms=cpu, own_stood_ms=own_wall - cpu,
                        **per_step(cpu))
        ages = [ms(r[5], 1e3) for r in mine if r[5] is not None]
        if ages:
            line.update(age_ms_p50=stats.percentile(ages, 50),
                        age_ms_p95=stats.percentile(ages, 95),
                        age_ms_max=max(ages))
        lines.append(line)
    for kind in [k for k, _ in THREAD_KINDS] + ["other"]:
        mine = [r for r in rows if kinds[r[1]] == kind]
        if mine:
            line = {"threads": kind, "n": len({r[1] for r in mine}),
                    "own_wall_ms": sum(ms(r[3], 1e6) for r in mine)}
            timed = [r for r in mine if r[4] is not None]
            if timed:
                cpu = sum(ms(r[4], 1e3) for r in timed)
                line.update(own_cpu_ms=cpu, **per_step(cpu))
            lines.append(line)
    leaves = sorted((s[1], s[2], s[0]) for s in spans
                    if s[0].startswith("engine.")
                    and s[0] != "engine.iteration")
    gaps: dict = {}
    for (_, end, before), (start, _, after) in zip(leaves, leaves[1:]):
        gaps.setdefault(f"{before} -> {after}", []).append(
            ms(start - end, 1e6))
    for name, found in sorted(gaps.items()):
        if len(found) >= 20:
            lines.append({"gap": name, "n": len(found),
                          "ms_mean": stats.mean(found),
                          "ms_p50": stats.percentile(found, 50),
                          "ms_p95": stats.percentile(found, 95)})
    return lines


def main(argv: list) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if not path or not os.path.exists(path):
        raise SystemExit(f"no .xplane.pb under {argv[0]}")
    device = trace_reduce.first_device(trace_reduce.load(path))
    steps = len(trace_reduce.module_runs(device, "^jit_decode_step")) \
        if device else 0
    print(json.dumps({"trace": path, "decode_steps": steps}))
    for line in table(trace_span_attr.attributed_spans(path), steps):
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
