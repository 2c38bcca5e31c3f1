"""Operations and bytes a sparse feed-forward needs, from shapes: the
counts ``expert_ffn_roofline.moe`` holds the traced expert operations
against. ``model`` holds the Hugging Face keys of the configuration
file. Beside ``flops.py``, whose ``least_seconds`` turns a cost into
the roofline's least time."""

from __future__ import annotations


def expert_matrix_values(model: dict) -> int:
    """Values in one expert's three matrices (gate, up, down)."""
    return 3 * model["hidden_size"] * model["intermediate_size"]


def expert_ffn_cost(model: dict, experts_read: float, choices: float,
                    tokens: float, bytes_per_value: int = 2) -> dict:
    """One layer of one step. Least bytes: each of the ``experts_read``
    experts' three matrices once (an expert nobody chose need not be
    read), the tokens' hidden states in and out. Operations: each of
    the ``choices`` (token x expert) is three products of hidden_size x
    intermediate_size, 2 operations a multiply-add; an all-experts
    product computes num_experts / experts_per_token times that, which
    is not what the algorithm needs and is not counted."""
    hidden = model["hidden_size"]
    moved = (experts_read * expert_matrix_values(model)
             + 2 * tokens * hidden) * bytes_per_value
    return {"flops": 2.0 * choices * expert_matrix_values(model),
            "bytes": float(moved)}

