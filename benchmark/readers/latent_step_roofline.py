"""A share of its roofline for the latent-attention decoder
(``xing_cost.py``), by the metric's ``part``:

``"step"``: the least time the chip could take for what ONE decode step
must do (``xing_cost.decode_step_cost``: every weight touched once, each
LIVE latent once, the absorbed arithmetic) over the traced device time
of a run of the program matching ``module``.

``"latent"``: the least time for the absorbed attention of the traced
runs of the decode program matching ``module``
(``xing_cost.latent_attention_cost``, once per layer and run) over the
traced self time of the operations matching ``ops``.

``"experts"``: the least time for the sparse feed-forward of the traced
runs of the programs matching ``module`` (``xing_cost.expert_ffn_cost``,
once per expert layer and run) over the traced self time of the
operations matching ``ops``.

How many rows a step carries, how many positions their contexts hold
and how many experts a layer touches are the window's means, from the
engine's counters (``decode_tokens`` and ``kv_positions_live`` over
``decode_steps``; the expert counters, summed on the device over decode
steps and prefill chunks alike, over their layer-steps). A program
without the counters, as before they were added, reads nothing."""

from benchmark import flops, peaks, trace_reduce, xing_cost


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
    dense, sparse = xing_cost.layers(model)
    layer_steps = slots / model["n_routed_experts"]
    touched = counters["experts_touched"] / layer_steps
    rows, context = tokens / steps, live / tokens
    if metric["part"] == "step":
        cost = xing_cost.decode_step_cost(model, rows=rows, context=context,
                                          experts_read=touched)
        return 100.0 * flops.least_seconds(cost, peak)[0] * len(runs) \
            / (sum(runs) / 1e9)
    traced = trace_reduce.op_self_seconds(device, metric["ops"])
    if not traced:
        return None
    if metric["part"] == "latent":
        cost = xing_cost.latent_attention_cost(model, rows=rows,
                                               context=context)
        return 100.0 * flops.least_seconds(cost, peak)[0] \
            * (dense + sparse) * len(runs) / traced
    passes = steps + counters.get("prefill_chunks", 0)
    cost = xing_cost.expert_ffn_cost(
        model, experts_read=touched,
        choices=counters["expert_choices"] / layer_steps,
        tokens=(tokens + counters.get("prefill_tokens", 0)) / passes)
    return 100.0 * flops.least_seconds(cost, peak)[0] * sparse * len(runs) \
        / traced
