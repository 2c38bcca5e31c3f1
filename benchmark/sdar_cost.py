"""Bytes and operations ONE pass of the block-diffusion decoder
(``sdar_moe``: a Qwen3-MoE layer, ``block_length`` query positions a
row) must move and do, from shapes: what ``block_step_roofline.sdar``
holds the traced decode program against, and ``expert_ffn_roofline.sdar``
the traced expert operations. ``model`` holds the Hugging Face keys of
the configuration file; an expert is ``moe_intermediate_size`` wide
(``intermediate_size``, which ``moe_cost.py`` reads, is a width this
model publishes and no layer uses). Beside ``flops.py``, whose
``least_seconds`` turns a cost into the roofline's least time. Its own
count: it calls nothing of ``ray_tpu``."""

from __future__ import annotations


def expert_matrix_values(model: dict) -> int:
    """Values in one expert's three matrices (gate, up, down)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def attention_values(model: dict) -> int:
    """Values in one layer's four attention matrices."""
    heads = model["num_attention_heads"] + model["num_key_value_heads"]
    return 2 * model["hidden_size"] * heads * model["head_dim"]


def kv_bytes_per_position(model: dict, bytes_per_value: int = 2) -> int:
    """One layer's keys and values of one position."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * bytes_per_value


def expert_ffn_cost(model: dict, experts_read: float, choices: float,
                    tokens: float, bytes_per_value: int = 2) -> dict:
    """The sparse feed-forward of one layer of one pass. Least bytes:
    each of the ``experts_read`` experts' three matrices once (an expert
    nobody chose need not be read), the tokens' hidden states in and
    out. Operations: each of the ``choices`` (token x expert) is three
    products of hidden_size x moe_intermediate_size, 2 operations a
    multiply-add; an all-experts product computes num_experts /
    experts_per_token times that, which is not what the algorithm needs
    and is not counted."""
    moved = (experts_read * expert_matrix_values(model)
             + 2 * tokens * model["hidden_size"]) * bytes_per_value
    return {"flops": 2.0 * choices * expert_matrix_values(model),
            "bytes": float(moved)}


def block_pass_cost(model: dict, rows: float, context: float,
                    experts_read: float, bytes_per_value: int = 2) -> dict:
    """One pass of ``rows`` busy rows, each a block of ``block_length``
    positions over a context of ``context`` live positions (the block
    among them), ``experts_read`` experts touched a layer. Least bytes:
    a layer's attention matrices, router and norms once, the three
    matrices of every expert touched, the head once (the embedding is a
    lookup of the pass's tokens), the live positions' keys and values
    once a layer, the block's written. Operations, 2 a multiply-add: the
    projections and the router for every token, the ``tokens x
    experts_per_token`` chosen experts, the head over every token, and
    the attention's two products over the positions read."""
    hidden, layers = model["hidden_size"], model["num_hidden_layers"]
    tokens = rows * model["block_length"]
    router = hidden * model["num_experts"]
    norms = 2 * hidden + 2 * model["head_dim"]
    head = hidden * model["vocab_size"]
    per_position = kv_bytes_per_position(model, bytes_per_value)
    moved = {
        "attention": layers * (attention_values(model) + router + norms)
        * bytes_per_value,
        "experts": layers * experts_read * expert_matrix_values(model)
        * bytes_per_value,
        "head": (head + hidden) * bytes_per_value,
        "kv_read": layers * rows * context * per_position,
        "kv_written": layers * tokens * per_position,
        "tokens": tokens * hidden * bytes_per_value,
    }
    products = layers * (
        attention_values(model) + router
        + model["num_experts_per_tok"] * expert_matrix_values(model)) + head
    # Per token and position read: the scores and the weighted sum,
    # each heads x head_dim multiply-adds.
    attention = 2.0 * 2 * model["num_attention_heads"] * model["head_dim"] \
        * tokens * context * layers
    return {"flops": 2.0 * tokens * products + attention,
            "bytes": float(sum(moved.values())), "moved": moved}
