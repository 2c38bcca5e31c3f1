"""Operations and bytes the algorithm needs, from shapes. The
benchmark's own count: ``llama.flops_per_token`` counts the embedding
table (a lookup, no matmul) and non-causal attention."""

from __future__ import annotations


def matmul_params(model: dict) -> dict:
    """Parameters that sit in a matrix multiplication, by part. ``model``
    holds the Hugging Face keys of the configuration file."""
    e, m = model["hidden_size"], model["intermediate_size"]
    h, kv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    layer = e * h * d + 2 * e * kv * d + h * d * e + 3 * e * m
    return {"layer": layer, "head": e * model["vocab_size"],
            "layers": model["num_hidden_layers"]}


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward of one token in a sequence of ``seq_len``:
    6 per matmul parameter (embedding lookup excluded), plus causal
    attention (a token attends to seq_len/2 positions on average:
    QK^T and PV are 2*h*d*seq_len forward, three times that with the
    backward). Recomputation is not counted."""
    p = matmul_params(model)
    dense = 6.0 * (p["layers"] * p["layer"] + p["head"])
    attention = (6.0 * p["layers"] * model["num_attention_heads"]
                 * model["head_dim"] * seq_len)
    return dense + attention


def head_share(model: dict, seq_len: int) -> float:
    """The output head's share of a step's operations: large at a depth
    cut, small in the whole model."""
    return (6.0 * matmul_params(model)["head"]
            / train_flops_per_token(model, seq_len))


# The three flash kernels as ``ops/flash_attention.py`` splits them; a
# causal [L, L] product of head width d costs L*L*d operations (half of
# 2*L*L*d). Forward: QK^T, PV. dq kernel: QK^T again, dO V^T, dS K.
# dk/dv kernel: QK^T again, P^T dO, dO V^T, dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_kernel_cost(kernel: str, batch: int, seq_len: int, heads: int,
                      kv_heads: int, head_dim: int,
                      bytes_per_value: int = 2) -> dict:
    """Operations and least bytes moved (each operand read once, each
    result written once) of one call of one kernel."""
    flops = FLASH_MATMULS[kernel] * batch * heads * seq_len * seq_len \
        * head_dim
    q = batch * seq_len * heads * head_dim * bytes_per_value
    kv = batch * seq_len * kv_heads * head_dim * bytes_per_value
    lse = batch * seq_len * heads * 4
    moved = {"fwd": q + 2 * kv + q + lse,              # q k v -> o lse
             "dq": q + 2 * kv + q + lse + q + q,       # q k v o lse do -> dq
             "dkv": q + 2 * kv + q + lse + q + 2 * kv,  # ... -> dk dv
             }[kernel]
    return {"flops": float(flops), "bytes": float(moved)}


def least_seconds(cost: dict, peak: dict) -> tuple:
    """Roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two it was."""
    by_compute = cost["flops"] / peak["bf16_flops_per_s"]
    by_memory = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory \
        else (by_memory, "memory")
