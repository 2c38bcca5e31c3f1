"""Per ``request``, the time from one of the program's spans to
another, in milliseconds, as the mean over the requests of the traced
window that have both: from the ``from_edge`` (``start`` or ``end``) of
the request's first span matching ``from_spans`` to the ``to_edge`` of
its first span matching ``to_spans`` that ends no earlier (and, with
``to_carrying``, whose attribute of that name is above 0: the first
``serve.stream.get`` that carried a token). The spans are host events
on the device trace's clock whose attribute ``request`` names the
streamed request (``ray_tpu.util.tracing.phase``). A program without
them (the parent commit), or a window in which no request has both,
reads nothing."""

from __future__ import annotations

import re

from benchmark import stats, trace_reduce
from benchmark.readers import trace_span_attr

EDGES = {"start": 1, "end": 2}


def by_request(spans: list, pattern: str, carrying: "str | None" = None
               ) -> dict:
    """request -> its spans matching ``pattern``, earliest first."""
    rx = re.compile(pattern)
    out: dict = {}
    for span in sorted(spans, key=lambda s: s[1]):
        attrs = span[3]
        if "request" in attrs and rx.search(span[0]) \
                and (carrying is None or float(attrs.get(carrying, 0)) > 0):
            out.setdefault(attrs["request"], []).append(span)
    return out


def pairs_ns(spans: list, metric: dict) -> list:
    """One distance a request that has both ends."""
    from_edge, to_edge = EDGES[metric["from_edge"]], EDGES[metric["to_edge"]]
    sent = by_request(spans, metric["from_spans"])
    landed = by_request(spans, metric["to_spans"], metric.get("to_carrying"))
    out = []
    for request, firsts in sent.items():
        left = firsts[0][from_edge]
        after = [s[to_edge] for s in landed.get(request, [])
                 if s[2] >= left]
        if after:
            out.append(after[0] - left)
    return out


def read(metric: dict, run: dict):
    path = trace_reduce.find_trace(metric)
    if path is None:
        return None
    found = pairs_ns(trace_span_attr.attributed_spans(path), metric)
    return stats.mean(found) / 1e6 if found else None
