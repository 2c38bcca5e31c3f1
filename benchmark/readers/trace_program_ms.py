"""Median device time of one run of a jitted program, found on the
first chip's ``XLA Modules`` line by ``module`` (a regular
expression)."""

from benchmark import stats, trace_reduce


def read(metric: dict, run: dict):
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    if not runs:
        return None
    return stats.median(runs) / 1e6
