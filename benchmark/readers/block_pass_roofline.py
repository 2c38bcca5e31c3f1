"""A share of its roofline for a decoder that generates by diffusion
over blocks (``sdar_cost.py``), by the metric's ``part``:

``"pass"``: the least time the chip could take for what ONE pass of the
decode program must do (``sdar_cost.block_pass_cost``) over the traced
device time of a run of the program matching ``module``.

``"experts"``: the least time for the sparse feed-forward of the traced
runs of the programs matching ``module``
(``sdar_cost.expert_ffn_cost``, once per layer and run) over the traced
self time of the operations matching ``ops``.

How many rows a pass carries, how many positions their contexts hold
and how many experts a layer touches are the window's means, from the
engine's counters (``block_rows`` and ``kv_positions_live`` over
``decode_steps``; the expert counters, summed on the device over decode
passes and prefill chunks alike, over their layer-steps). A program
without the counters, as before they were added, reads nothing."""

from benchmark import flops, peaks, sdar_cost, trace_reduce


def read(metric: dict, run: dict):
    counters = run["counters"]
    passes, rows = counters.get("decode_steps"), counters.get("block_rows")
    live, slots = counters.get("kv_positions_live"), \
        counters.get("expert_slots")
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and trace_reduce.module_runs(device, metric["module"])
    if run.get("rehearse") or not passes or not rows or not live \
            or not slots or not runs:
        return None  # (a rehearsal's CPU has no peak in the table)
    model = run["config"]
    layer_steps = slots / model["num_experts"]
    touched = counters["experts_touched"] / layer_steps
    peak = peaks.peaks(run["device_kind"])
    if metric["part"] == "pass":
        cost = sdar_cost.block_pass_cost(
            model, rows=rows / passes, context=live / rows,
            experts_read=touched)
        return 100.0 * flops.least_seconds(cost, peak)[0] * len(runs) \
            / (sum(runs) / 1e9)
    traced = trace_reduce.op_self_seconds(device, metric["ops"])
    if not traced:
        return None
    steps = passes + counters.get("prefill_chunks", 0)
    tokens = (rows * model["block_length"]
              + counters.get("prefill_tokens", 0)) / steps
    cost = sdar_cost.expert_ffn_cost(
        model, experts_read=touched,
        choices=counters["expert_choices"] / layer_steps, tokens=tokens)
    return 100.0 * flops.least_seconds(cost, peak)[0] \
        * model["num_hidden_layers"] * len(runs) / traced
