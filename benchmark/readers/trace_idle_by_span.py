"""Milliseconds of device idle, per run of the program matching
``module``, that fall inside the program's own spans matching ``span``
(a regular expression; ``null``: inside no program span).

The idle gaps are those between the busy intervals of the first chip
(``trace_reduce.busy_intervals``). The program's spans are the host
events, on any host line, whose name matches ``among`` (default
``PROGRAM_SPANS``); they come from ``ray_tpu.util.tracing.phase`` and
lie on the device trace's clock. Each piece of a gap goes, by overlap
and not by the gap's middle, to the innermost span covering it: of the
spans open at that instant, the one that started last. A pattern that
matches no span of the trace reads nothing: a program without the
spans, as before they were added.

``trace_reduce.load`` keeps the harness's ``bench.*`` spans only and
``run`` carries no path, so the trace file is found again under
``<checkout>/.bench_trace/<cell>`` for the cells the metric lists;
with ``--trace-dir`` elsewhere there is nothing to read."""

from __future__ import annotations

import bisect
import functools
import heapq
import os
import re

from benchmark import spec, trace_reduce

PROGRAM_SPANS = r"^(engine|serve|llm)\."


def find_trace(metric: dict) -> "str | None":
    for cell in metric.get("workloads", []):
        path = trace_reduce.find_xplane(
            os.path.join(spec.ROOT, ".bench_trace", cell))
        if path is not None:
            return path
    return None


@functools.lru_cache(maxsize=2)  # one trace a run, read by ten metrics
def program_spans(path: str, among: str) -> list:
    """(name, start_ns, end_ns) of every host event whose name matches."""
    from jax.profiler import ProfileData

    rx = re.compile(among)
    return [(e.name, float(e.start_ns),
             float(e.start_ns) + float(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if rx.search(e.name)]


def innermost(spans: list) -> list:
    """Disjoint, sorted (start, end, name) pieces of the time the spans
    cover, each named after the span that started last among those
    covering it (of two that started together, the one ending first)."""
    opening = sorted(spans, key=lambda s: (s[1], -s[2]))
    points = sorted({t for _, start, end in spans for t in (start, end)})
    pieces, covering, nxt = [], [], 0
    for left, right in zip(points, points[1:]):
        while nxt < len(opening) and opening[nxt][1] <= left:
            name, start, end = opening[nxt]
            heapq.heappush(covering, (-start, end, name))
            nxt += 1
        while covering and covering[0][1] <= left:
            heapq.heappop(covering)  # the innermost has ended
        if covering:
            pieces.append((left, right, covering[0][2]))
    return pieces


def idle_ns(gaps: list, spans: list, pattern: "str | None") -> float:
    """Nanoseconds of the ``gaps`` ([start, end] pairs) inside the
    innermost spans whose name matches ``pattern``; with no pattern,
    inside no span at all."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    rx = pattern and re.compile(pattern)
    total = 0.0
    for gap_start, gap_end in gaps:
        inside = 0.0
        at = max(0, bisect.bisect_right(starts, gap_start) - 1)
        while at < len(pieces) and pieces[at][0] < gap_end:
            start, end, name = pieces[at]
            if rx is None or rx.search(name):
                inside += max(0.0, min(end, gap_end) - max(start, gap_start))
            at += 1
        total += inside if rx else (gap_end - gap_start) - inside
    return total


def read(metric: dict, run: dict):
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    path = find_trace(metric)
    if not runs or path is None:
        return None
    spans = program_spans(path, metric.get("among", PROGRAM_SPANS))
    pattern = metric["span"]
    if pattern and not any(re.search(pattern, s[0]) for s in spans):
        return None
    busy = trace_reduce.busy_intervals(device)
    gaps = [(end, start) for (_, end), (start, _) in zip(busy, busy[1:])]
    return idle_ns(gaps, spans, pattern) / 1e6 / len(runs)
