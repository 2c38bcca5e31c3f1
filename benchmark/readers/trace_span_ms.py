"""The ``percentile``-th percentile, in milliseconds, of the durations
of the program's spans matching ``span`` (host events on the device
trace's clock, from ``ray_tpu.util.tracing.phase``) in the traced
window."""

from benchmark import stats
from benchmark.readers import trace_idle_by_span


def read(metric: dict, run: dict):
    path = trace_idle_by_span.find_trace(metric)
    spans = path and trace_idle_by_span.program_spans(path, metric["span"])
    if not spans:
        return None
    return stats.percentile([end - start for _, start, end in spans],
                            metric["percentile"]) / 1e6
