"""Bytes and operations ONE decode step of the Kimi-Linear decoder
(``kimi_linear``: KDA layers, a gated delta rule over a float32 matrix
state a row, beside latent attention without positions, and expert
layers that HOLD a share of the experts they route over) must move and
do, from shapes: what ``decode_step_roofline.kimi`` holds the traced
decode program against, ``kda_state_roofline.kimi`` the traced KDA
operations, and ``expert_ffn_roofline.kimi`` the traced expert
operations. ``model`` holds the Hugging Face keys of the configuration
file (``num_experts`` the experts held here, ``linear_attn_config`` the
published group with its two lists of layers). Beside ``flops.py``,
whose ``least_seconds`` turns a cost into the roofline's least time.
Its own count: it calls nothing of ``ray_tpu``. The same work whatever
implements it: a row's state is read once and written once a KDA layer,
each held expert that got a choice is read once, a latent is the 576
values the model defines, read once."""

from __future__ import annotations

from benchmark.xing_cost import latent_attention_cost  # the same keys


def layers(model: dict) -> dict:
    """How many of the built layers are of each kind."""
    built, group = model["num_hidden_layers"], model["linear_attn_config"]
    dense = model["first_k_dense_replace"]
    return {"kda": sum(n <= built for n in group["kda_layers"]),
            "latent": sum(n <= built for n in group["full_attn_layers"]),
            "dense": dense, "sparse": built - dense}


def latent_values(model: dict) -> int:
    """Values one position leaves in the pool, a latent layer."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def kda_matrix_values(model: dict) -> int:
    """Values in one KDA mixer: q, k and v, their convolutions, A_log,
    dt_bias, the decay's and the gate's low-rank pairs, beta, the head
    norm, o."""
    hidden, group = model["hidden_size"], model["linear_attn_config"]
    heads, d = group["num_heads"], group["head_dim"]
    width = heads * d
    return (3 * hidden * width + 3 * width * group["short_conv_kernel_size"]
            + heads + width + 2 * (hidden * d + d * width) + hidden * heads
            + d + width * hidden)


def latent_matrix_values(model: dict) -> int:
    """Values in one latent mixer: q (no down-projection), kv_a and its
    norm, kv_b, o."""
    hidden, heads = model["hidden_size"], model["num_attention_heads"]
    rank = model["kv_lora_rank"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    return (hidden * heads * (nope + rope) + hidden * (rank + rope) + rank
            + rank * heads * (nope + v) + heads * v * hidden)


def expert_matrix_values(model: dict) -> int:
    """Values in one expert's three matrices (gate, up, down)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def dense_ffn_values(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def state_bytes(model: dict) -> int:
    """One row's float32 state of one KDA layer: heads x d x d."""
    group = model["linear_attn_config"]
    return group["num_heads"] * group["head_dim"] ** 2 * 4


def kda_cost(model: dict, rows: float, bytes_per_value: int = 2) -> dict:
    """The KDA mixer of one layer of one decode step of ``rows`` busy
    rows. Least bytes: the mixer's matrices once, each row's state read
    once and written once, of the convolutions' carried inputs the three
    read and the one written, the rows' hidden states in and out.
    Operations, 2 a multiply-add: every matrix a token passes, and the
    rule on the state (the decay, what the state predicts for k, the
    correction written, the output: 7 operations a state value)."""
    group = model["linear_attn_config"]
    width = group["num_heads"] * group["head_dim"]
    kernel = group["short_conv_kernel_size"]
    state = state_bytes(model)
    moved = (kda_matrix_values(model) * bytes_per_value
             + rows * 2 * state
             + rows * kernel * 3 * width * bytes_per_value
             + 2 * rows * model["hidden_size"] * bytes_per_value)
    return {"flops": 2.0 * rows * kda_matrix_values(model)
            + 7.0 * rows * state / 4, "bytes": float(moved)}


def expert_ffn_cost(model: dict, experts_read: float, choices: float,
                    tokens: float, bytes_per_value: int = 2) -> dict:
    """The sparse feed-forward of one layer of one pass. Least bytes:
    each of the ``experts_read`` HELD routed experts' three matrices
    once (a held expert nobody chose need not be read; an expert held
    elsewhere is another chip's read), the shared expert's always, the
    tokens' hidden states in and out. Operations: each of the
    ``choices`` that landed here (token x held expert) and each token
    through the shared expert is three products of hidden_size x
    moe_intermediate_size, 2 operations a multiply-add; an
    all-held-experts product computes more, which is not what the
    algorithm needs and is not counted."""
    shared = model["num_shared_experts"]
    moved = ((experts_read + shared) * expert_matrix_values(model)
             + 2 * tokens * model["hidden_size"]) * bytes_per_value
    return {"flops": 2.0 * (choices + shared * tokens)
            * expert_matrix_values(model), "bytes": float(moved)}


def decode_step_cost(model: dict, rows: float, context: float,
                     experts_read: float, choices: float,
                     bytes_per_value: int = 2) -> dict:
    """One decode step of ``rows`` busy rows over contexts of
    ``context`` live positions, ``experts_read`` held routed experts
    touched and ``choices`` landed an expert layer. Least bytes: every
    weight touched once (the head; each mixer; the dense feed-forward;
    an expert layer's router, shared expert and the held experts
    touched; two norms a layer; the embedding is a lookup of the step's
    tokens), each row's state read and written a KDA layer, each LIVE
    latent once a latent layer and the rows' written. Operations, 2 a
    multiply-add: every matrix a token passes (of the routed experts
    those of its choices that are held), the head, the rule and the
    absorbed attention."""
    hidden, kinds = model["hidden_size"], layers(model)
    routed = model["num_experts_routed_over"]
    router = hidden * routed + routed
    shared = model["num_shared_experts"] * expert_matrix_values(model)
    head = hidden * model["vocab_size"] + hidden
    kda = kda_cost(model, rows, bytes_per_value)
    latent = latent_attention_cost(model, rows, context, bytes_per_value)
    total = kinds["dense"] + kinds["sparse"]
    moved = {
        "head": head * bytes_per_value,
        "kda": kinds["kda"] * kda["bytes"],
        "latent_matrices": kinds["latent"] * latent_matrix_values(model)
        * bytes_per_value,
        "latents": kinds["latent"] * rows * (context + 1)
        * latent_values(model) * bytes_per_value,
        "norms": total * 2 * hidden * bytes_per_value,
        "dense_ffn": kinds["dense"] * dense_ffn_values(model)
        * bytes_per_value,
        "router_and_shared": kinds["sparse"] * (router + shared)
        * bytes_per_value,
        "experts": kinds["sparse"] * experts_read
        * expert_matrix_values(model) * bytes_per_value,
        "tokens": rows * hidden * bytes_per_value,
    }
    per_token = (kinds["latent"] * latent_matrix_values(model)
                 + kinds["dense"] * dense_ffn_values(model)
                 + kinds["sparse"] * (router + shared) + head)
    flops = (2.0 * rows * per_token + kinds["kda"] * kda["flops"]
             + kinds["latent"] * latent["flops"]
             + 2.0 * kinds["sparse"] * choices * expert_matrix_values(model))
    return {"flops": flops, "bytes": float(sum(moved.values())),
            "moved": moved}
