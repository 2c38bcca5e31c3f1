"""Bytes and operations ONE decode step of the Xing4.0 decoder
(``xing4_0``: latent attention, hyper-connections, sigmoid-routed
experts beside a shared one, behind leading dense layers) must move and
do, from shapes: what ``decode_step_roofline.xing`` holds the traced
decode program against, ``latent_attn_roofline.xing`` the traced
operations on the latent pool, and ``expert_ffn_roofline.xing`` the
traced expert operations. ``model`` holds the Hugging Face keys of the
configuration file. Beside ``flops.py``, whose ``least_seconds`` turns a
cost into the roofline's least time. Its own count: it calls nothing of
``ray_tpu``. The same work whatever implements it: a latent is the 576
values the model defines, read once, not the lanes a layout pads it to
nor the copies a gather makes."""

from __future__ import annotations


def layers(model: dict) -> tuple:
    """(leading dense layers, expert layers)."""
    dense = model["first_k_dense_replace"]
    return dense, model["num_hidden_layers"] - dense


def latent_values(model: dict) -> int:
    """Values one position leaves in the pool, a layer."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def attention_values(model: dict) -> int:
    """Values in one layer's attention: q_a and its norm, q_b, kv_a and
    its norm, kv_b, o."""
    hidden, heads = model["hidden_size"], model["num_attention_heads"]
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return (hidden * q_rank + q_rank + q_rank * heads * (nope + rope)
            + hidden * (kv_rank + rope) + kv_rank
            + kv_rank * heads * (nope + v) + heads * v * hidden)


def mix_values(model: dict) -> int:
    """Values in one sublayer's hyper-connection: phi, b, a, the norm's
    scale over all streams; and the sublayer's own input norm."""
    n, hidden = model["hc_mult"], model["hidden_size"]
    width = n * (2 + n)
    return n * hidden * width + width + 3 + n * hidden + hidden


def expert_matrix_values(model: dict) -> int:
    """Values in one expert's three matrices (gate, up, down)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_ffn_values(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def latent_attention_cost(model: dict, rows: float, context: float,
                          bytes_per_value: int = 2) -> dict:
    """The absorbed attention of one layer of one decode step: ``rows``
    busy rows, each over ``context`` live positions (its own among
    them). Least bytes: each live latent once, the rows' written, the
    up-projections ``W_kvb`` once, the queries in and the heads' outputs
    out. Operations, 2 a multiply-add: the query carried into the latent
    space and the sum carried out of it (heads x rank x nope and heads x
    rank x v a row), and a head's score and weighted sum over each
    position (latent values + rank)."""
    heads, rank = model["num_attention_heads"], model["kv_lora_rank"]
    nope, v = model["qk_nope_head_dim"], model["v_head_dim"]
    entry = latent_values(model)
    moved = (rows * context * entry + rows * entry
             + rank * heads * (nope + v)
             + rows * heads * (nope + model["qk_rope_head_dim"] + v)) \
        * bytes_per_value
    absorbed = rows * heads * rank * (nope + v)
    attended = rows * context * heads * (entry + rank)
    return {"flops": 2.0 * (absorbed + attended), "bytes": float(moved)}


def expert_ffn_cost(model: dict, experts_read: float, choices: float,
                    tokens: float, bytes_per_value: int = 2) -> dict:
    """The sparse feed-forward of one layer of one pass. Least bytes:
    each of the ``experts_read`` routed experts' three matrices once (an
    expert nobody chose need not be read), the shared experts' always,
    the tokens' hidden states in and out. Operations: each of the
    ``choices`` (token x routed expert) and each token through the
    shared experts is three products of hidden_size x
    moe_intermediate_size, 2 operations a multiply-add; an all-experts
    product computes n_routed_experts / num_experts_per_tok times that,
    which is not what the algorithm needs and is not counted."""
    shared = model["n_shared_experts"]
    moved = ((experts_read + shared) * expert_matrix_values(model)
             + 2 * tokens * model["hidden_size"]) * bytes_per_value
    return {"flops": 2.0 * (choices + shared * tokens)
            * expert_matrix_values(model), "bytes": float(moved)}


def decode_step_cost(model: dict, rows: float, context: float,
                     experts_read: float, bytes_per_value: int = 2) -> dict:
    """One decode step of ``rows`` busy rows over contexts of
    ``context`` live positions, ``experts_read`` routed experts touched
    a layer. Least bytes: every weight touched once (the head; each
    layer's attention, its two mixes and norms; a dense layer's
    feed-forward; an expert layer's router, shared experts and the
    routed experts touched; the embedding is a lookup of the step's
    tokens), each LIVE latent once a layer and the rows' written.
    Operations, 2 a multiply-add: every matrix a token passes (of the
    routed experts its num_experts_per_tok), the head, and the absorbed
    attention."""
    hidden = model["hidden_size"]
    dense, sparse = layers(model)
    total = dense + sparse
    router = hidden * model["n_routed_experts"] + model["n_routed_experts"]
    shared = model["n_shared_experts"] * expert_matrix_values(model)
    head = hidden * model["vocab_size"] + hidden
    latent = latent_attention_cost(model, rows, context, bytes_per_value)
    moved = {
        "head": head * bytes_per_value,
        "attention_and_mixes": total * (
            attention_values(model) + 2 * mix_values(model))
        * bytes_per_value,
        "dense_ffn": dense * dense_ffn_values(model) * bytes_per_value,
        "router_and_shared": sparse * (router + shared) * bytes_per_value,
        "experts": sparse * experts_read * expert_matrix_values(model)
        * bytes_per_value,
        "latents": total * rows * (context + 1) * latent_values(model)
        * bytes_per_value,
        "tokens": rows * hidden * bytes_per_value,
    }
    per_token = (
        total * (attention_values(model) + 2 * mix_values(model))
        + dense * dense_ffn_values(model)
        + sparse * (router + shared + model["num_experts_per_tok"]
                    * expert_matrix_values(model))
        + head)
    return {"flops": 2.0 * rows * per_token + total * latent["flops"],
            "bytes": float(sum(moved.values())), "moved": moved}
