"""The decode step's share of its roofline: the least time the chip
could take for what ONE decode step of the hybrid decoder must do
(``phi_cost.decode_step_cost``: every weight once, the live positions
of the shared pool once for each of its readers, the window layers'
positions, the states, and the arithmetic) over the traced device time
of a run of the program matching ``module``.

How many rows a step carries and how many positions their contexts hold
are the window's means, from the engine's counters (``decode_tokens``
and ``kv_positions_live`` over ``decode_steps``): the mean of
``min(context, window)`` is taken as ``min`` of the mean context, which
can only count too much where some rows are still inside the window.
A program without the counters, as before they were added, reads
nothing."""

from benchmark import flops, peaks, phi_cost, trace_reduce


def read(metric: dict, run: dict):
    counters = run["counters"]
    steps, tokens = counters.get("decode_steps"), counters.get("decode_tokens")
    live = counters.get("kv_positions_live")
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    if run.get("rehearse") or not steps or not tokens or not live \
            or not runs:
        return None  # (a rehearsal's CPU has no peak in the table)
    cost = phi_cost.decode_step_cost(run["config"], rows=tokens / steps,
                                     context=live / tokens)
    least, _ = flops.least_seconds(cost, peaks.peaks(run["device_kind"]))
    return 100.0 * least * len(runs) / (sum(runs) / 1e9)
