"""A share of its roofline for the Solar-Open2 decoder
(``solar_cost.py``), by the metric's ``part``:

``"step"``: the least time the chip could take for what ONE decode step
must do (``solar_cost.decode_step_cost``: every weight touched once,
each row's state read and written a KDA layer, each LIVE position's key
and value once a full layer, the held experts that got a choice) over
the traced device time of a run of the programs matching ``module`` (the
decode program at whichever table width a step ran).

``"kda"``: the least time for the KDA mixers of the traced runs of the
decode program matching ``module`` (``solar_cost.kda_cost``, once per
KDA layer and run) over the traced self time of the operations matching
``ops``.

``"experts"``: the least time for the sparse feed-forward of the traced
runs of the programs matching ``module`` (``solar_cost.expert_ffn_cost``,
once per layer and run) over the traced self time of the operations
matching ``ops``.

How many rows a step carries, how many positions their contexts hold in
a full layer and how many held experts a layer touches are the window's
means, from the engine's counters (``decode_tokens`` and
``kv_positions_live`` over ``decode_steps``; the expert counters, which
count the experts HELD (``n_routed_experts`` of the file), summed on the
device over decode steps and prefill chunks alike, over their
layer-steps). A program without the counters reads nothing."""

from benchmark import flops, peaks, solar_cost, trace_reduce


def read(metric: dict, run: dict):
    counters = run["counters"]
    steps, tokens = counters.get("decode_steps"), counters.get("decode_tokens")
    live, slots = counters.get("kv_positions_live"), \
        counters.get("expert_slots")
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    if run.get("rehearse") or not steps or not tokens or not live \
            or not slots or not runs:
        return None  # (a rehearsal's CPU has no peak in the table)
    model = run["config"]
    peak = peaks.peaks(run["device_kind"])
    kinds = solar_cost.layers(model)
    layer_steps = slots / model["n_routed_experts"]
    touched = counters["experts_touched"] / layer_steps
    landed = counters["expert_choices"] / layer_steps
    rows, context = tokens / steps, live / tokens
    all_tokens = tokens + counters.get("prefill_tokens", 0)
    passes = steps + counters.get("prefill_chunks", 0)
    if metric["part"] == "step":
        # A decode step's own share of the choices: by its tokens.
        cost = solar_cost.decode_step_cost(
            model, rows=rows, context=context, experts_read=touched,
            choices=landed * passes * tokens / (all_tokens * steps))
        return 100.0 * flops.least_seconds(cost, peak)[0] * len(runs) \
            / (sum(runs) / 1e9)
    traced = trace_reduce.op_self_seconds(device, metric["ops"])
    if not traced:
        return None
    if metric["part"] == "kda":
        cost, count = solar_cost.kda_cost(model, rows=rows), kinds["kda"]
    else:
        cost, count = solar_cost.expert_ffn_cost(
            model, experts_read=touched, choices=landed,
            tokens=all_tokens / passes), kinds["sparse"]
    return 100.0 * flops.least_seconds(cost, peak)[0] * count * len(runs) \
        / traced
