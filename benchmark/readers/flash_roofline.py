"""The flash kernels' share of their roofline: the least time the chip
could take for what the three kernels must do in the traced steps
(forward, dq and dk/dv once per layer and step, from the shapes; a
forward run again for recomputation is not counted), over the traced
self time of the operations matching ``ops``."""

from benchmark import flops, peaks, trace_reduce


def read(metric: dict, run: dict):
    if run["rehearse"]:
        return None
    device = run["trace"] and trace_reduce.first_device(run["trace"])
    steps = device and len(trace_reduce.module_runs(device, metric["module"]))
    traced = device and trace_reduce.op_self_seconds(device, metric["ops"])
    if not steps or not traced:
        return None
    model, traffic = run["config"], run["traffic"]
    peak = peaks.peaks(run["device_kind"])
    least = sum(
        flops.least_seconds(flops.flash_kernel_cost(
            kernel, traffic["batch"], traffic["seq_len"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"]), peak)[0]
        for kernel in flops.FLASH_MATMULS)
    return 100.0 * least * model["num_hidden_layers"] * steps / traced
