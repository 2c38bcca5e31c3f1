"""Model FLOP/s utilization: tokens per second as the harness clocked
them, times the benchmark's own operations per token (``flops.py``: no
embedding lookup, causal attention, no recomputation), over chips times
the published peak."""

from benchmark import flops, peaks


def read(metric: dict, run: dict):
    if run["rehearse"]:
        return None  # a CPU has no peak in the table
    rate = run["harness"].get("train_tokens_per_s")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(
        run["config"], run["harness"]["seq_len"])
    peak = peaks.peaks(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate * per_token / (run["chips"] * peak)
