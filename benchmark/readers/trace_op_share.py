"""Time of the operations matching ``ops`` (a regular expression over
an operation's name and its HLO text) as a share of the device time of
the programs matching ``module``, on the first chip. Self time of the
synchronous operations; with ``"in_flight": true`` the union of the
intervals in which a matching operation, synchronous or asynchronous,
was in flight."""

from benchmark import trace_reduce


def read(metric: dict, run: dict):
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    if not runs:
        return None
    seconds = (trace_reduce.op_in_flight_seconds
               if metric.get("in_flight") else trace_reduce.op_self_seconds)
    return 100.0 * seconds(device, metric["ops"]) / (sum(runs) / 1e9)
