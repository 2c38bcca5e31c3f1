"""1 - (union of the device's operation intervals / traced window),
averaged over the chips used."""

from benchmark import trace_reduce


def read(metric: dict, run: dict):
    seen = run["trace"] and trace_reduce.busy_and_window(run["trace"])
    if not seen:
        return None
    busy_s, window_s = seen
    return 100.0 * (1.0 - busy_s / window_s)
