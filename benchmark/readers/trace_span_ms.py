"""The ``percentile``-th percentile, in milliseconds, of the durations
of the program's spans matching ``span`` (host events on the device
trace's clock, from ``ray_tpu.util.tracing.phase``) in the traced
window."""

from benchmark import stats, trace_reduce


def read(metric: dict, run: dict):
    path = trace_reduce.find_trace(metric)
    spans = path and trace_reduce.program_spans(path, metric["span"])
    if not spans:
        return None
    return stats.percentile([end - start for _, start, end in spans],
                            metric["percentile"]) / 1e6
