"""100 x the seconds of the first chip's idle gaps that
``benchmark/idle_causes.py`` puts under the causes matching the metric
file's ``cause`` pattern (``^runtime\\.gc$``: a collection ran in the
serving process; a leaf of the engine's loop; ``outside the loop's
spans``), over the traced window's seconds: ``busy_and_window``'s
window, the denominator of ``device_idle_share.*``, so the two
subtract. The spans are read from the newest kept trace among the
metric's cells (``kept_trace``). Nothing from a run with no trace, from a device with no gap,
and from a program that does not put the causes on the record
(``idle_causes.records_causes``: the parent commit opens no
``runtime.gc`` and says ``starved`` on no launch, and a 0 would read
as "no collection ran").

The pattern's key is ``cause``: ``tests/benchmark/test_idle_causes.py``
holds it to the file that opens the span, as ``test_program_spans.py``
holds the older readers' ``span``."""

from __future__ import annotations

import os
import re

from benchmark import idle_causes, spec, trace_reduce
from benchmark.readers import trace_span_attr


def kept_trace(metric: dict) -> "str | None":
    """The NEWEST kept trace file among the metric's cells: the run's
    own, just written. ``trace_reduce.find_trace`` takes the first
    listed cell's that has one, and with seven cells on one entry a
    trace kept from an earlier run of another cell was read for this
    one (my chip runs, PR 57: the Kimi cell read 0.0 from a rehearsal's
    trace of ``serve-longgen-closed`` left in the checkout)."""
    found = [path for path in (
        trace_reduce.find_xplane(os.path.join(spec.ROOT, ".bench_trace", cell))
        for cell in metric.get("workloads", [])) if path]
    return max(found, key=lambda path: os.path.getmtime(path)
               if os.path.exists(path) else 0.0, default=None)


def read(metric: dict, run: dict):
    trace = run["trace"]
    path = trace and kept_trace(metric)
    seen = path and trace_reduce.busy_and_window(trace)
    if not seen:
        return None
    spans = trace_span_attr.attributed_spans(path)
    if not idle_causes.records_causes(spans):
        return None
    by_cause = idle_causes.idle_causes(trace_reduce.first_device(trace),
                                       spans)
    if not by_cause:
        return None
    rx = re.compile(metric["cause"])
    return 100.0 * sum(line["seconds"] for cause, line in by_cause.items()
                       if rx.search(cause)) / seen[1]
