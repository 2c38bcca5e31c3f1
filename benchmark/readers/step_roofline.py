"""A share of its roofline for a decode step or a part of it, from the
cost module the metric's file names (``cost``: a module of
``benchmark/``, e.g. ``jamba_cost``), by the metric's ``part``:

``"step"`` (or none): the least time the chip could take for what ONE
decode step must do (the module's ``decode_step_cost(model, rows=,
context=)``) over the traced device time of a run of the programs
matching ``module``.

any other ``part``: the least time for that part of the traced runs of
the programs matching ``module`` (the module's ``<part>_cost(model,
rows=)``, once per layer of that kind, ``layers(model)[part]``, and
run) over the traced self time of the operations matching ``ops``.

How many rows a step carries and how many positions their contexts hold
are the window's means, from the engine's counters (``decode_tokens``
and ``kv_positions_live`` over ``decode_steps``): the step's cost is
handed both, a part's the rows. Written once here so that the next
family's step roofline is a cost file and a metric file; a cost that
needs further means of the window (the experts a layer touched: the
per-family readers ``kimi_step_roofline`` and ``solar_step_roofline``)
brings that with it when it is pointed here (``ROADMAP.md`` B2 (12)). A
program without the counters, or a tree without the cost module, reads
nothing."""

import importlib

from benchmark import flops, peaks, trace_reduce


def read(metric: dict, run: dict):
    counters = run["counters"]
    steps, tokens = counters.get("decode_steps"), counters.get("decode_tokens")
    live = counters.get("kv_positions_live")
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    if run.get("rehearse") or not steps or not tokens or not live \
            or not runs:
        return None  # (a rehearsal's CPU has no peak in the table)
    try:
        cost_of = importlib.import_module("benchmark." + metric["cost"])
    except ModuleNotFoundError:
        return None
    model, part = run["config"], metric.get("part", "step")
    rows, peak = tokens / steps, peaks.peaks(run["device_kind"])
    if part == "step":
        cost = cost_of.decode_step_cost(model, rows=rows,
                                        context=live / tokens)
        return 100.0 * flops.least_seconds(cost, peak)[0] * len(runs) \
            / (sum(runs) / 1e9)
    traced = trace_reduce.op_self_seconds(device, metric["ops"])
    if not traced:
        return None
    cost = getattr(cost_of, part + "_cost")(model, rows=rows)
    return 100.0 * flops.least_seconds(cost, peak)[0] \
        * cost_of.layers(model)[part] * len(runs) / traced
