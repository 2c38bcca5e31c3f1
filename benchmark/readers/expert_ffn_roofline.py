"""The expert feed-forward's share of its roofline: the least time the
chip could take for what the sparse layers must do in the traced runs
of the programs matching ``module`` (``moe_cost.expert_ffn_cost``: the
three matrices of every expert that got a choice read once per layer
and run, and the chosen experts' arithmetic), over the traced self time
of the operations matching ``ops``.

How many experts a run touches and how many choices it makes are the
window's means, from the engine's expert counters (``expert_choices``,
``expert_slots``, ``experts_touched`` over ``decode_steps`` +
``prefill_chunks``): the counters are summed on the device and say
nothing of a single run. A program without the counters, as before
they were added, reads nothing."""

from benchmark import flops, moe_cost, peaks, trace_reduce


def read(metric: dict, run: dict):
    counters = run["counters"]
    slots = counters.get("expert_slots")
    steps = counters.get("decode_steps", 0) + counters.get("prefill_chunks", 0)
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    runs = device and len(trace_reduce.module_runs(device, metric["module"]))
    traced = device and trace_reduce.op_self_seconds(device, metric["ops"])
    if run.get("rehearse") or not slots or not steps or not runs \
            or not traced:
        return None  # (a rehearsal's CPU has no peak in the table)
    model = run["config"]
    layer_steps = slots / model["num_experts"]
    tokens = (counters["decode_tokens"] + counters["prefill_tokens"]) / steps
    cost = moe_cost.expert_ffn_cost(
        model, experts_read=counters["experts_touched"] / layer_steps,
        choices=counters["expert_choices"] / layer_steps, tokens=tokens)
    least, _ = flops.least_seconds(cost, peaks.peaks(run["device_kind"]))
    return 100.0 * least * model["num_hidden_layers"] * runs / traced
