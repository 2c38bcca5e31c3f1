"""The ``percentile``-th percentile, in milliseconds, of an attribute in
microseconds (``attr``: ``age_us``, how long the oldest item a hand-off
took had waited) over the program's own spans whose name matches
``spans`` and that carry it (host events on the device trace's clock,
from ``ray_tpu.util.tracing.phase``) in the traced window. A program
without the spans or the attribute (the parent commit) reads nothing.

The pattern's key is ``spans``: ``span`` is the older readers', whose
every file ``test_program_spans.py`` holds to ``engine.py`` and
``replica.py``; ``test_stream_path_metrics.py`` holds these to the files
that open them."""

from __future__ import annotations

import functools
import re

from benchmark import stats, trace_reduce

PROGRAM_SPANS = r"^(engine|serve|llm|runtime)\."


@functools.lru_cache(maxsize=1)  # one trace a run, read by ten metrics
def attributed_spans(path: str) -> list:
    """(name, start_ns, end_ns, attributes, thread) of every program
    span of the trace's host planes; ``thread`` names its line."""
    from jax.profiler import ProfileData

    rx = re.compile(PROGRAM_SPANS)
    return [(e.name, float(e.start_ns),
             float(e.start_ns) + float(e.duration_ns), dict(e.stats),
             (plane.name, at))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for at, line in enumerate(plane.lines) for e in line.events
            if rx.search(e.name)]


def values(spans: list, pattern: str, attr: str) -> list:
    rx = re.compile(pattern)
    return [float(s[3][attr]) for s in spans
            if attr in s[3] and rx.search(s[0])]


def read(metric: dict, run: dict):
    path = trace_reduce.find_trace(metric)
    found = path and values(attributed_spans(path), metric["spans"],
                            metric["attr"])
    if not found:
        return None
    return stats.percentile(found, metric["percentile"]) / 1e3
