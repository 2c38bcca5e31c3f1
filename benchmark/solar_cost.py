"""Bytes and operations ONE decode step of the Solar-Open2 decoder
(``solar_open2``: KDA layers, a gated delta rule with ``beta`` to 2 over
a float32 matrix state a row, behind gated softmax attention of grouped
queries over key and value pools that the full layers own, and in every
layer experts of which a share is HELD) must move and do, from shapes:
what ``decode_step_roofline.solar`` holds the traced decode program
against, ``kda_state_roofline.solar`` the traced KDA operations and
``expert_ffn_roofline.solar`` the traced expert operations. ``model``
holds the Hugging Face keys of the configuration file
(``n_routed_experts`` the experts held here, ``linear_attn_config`` the
published group, ``gqa_layers`` the published list counted from 0).
Beside ``flops.py``, whose ``least_seconds`` turns a cost into the
roofline's least time. Its own count: it calls nothing of ``ray_tpu``.
The same work whatever implements it: a row's state is read once and
written once a KDA layer, each held expert that got a choice is read
once, a LIVE position's keys and values (2 x 8 x 128 values) are read
once a full layer, every other weight once. The KDA mixer's count is
the Kimi cell's (``kimi_cost.kda_cost``: the group's keys are the same);
``beta`` to 2 changes no byte and no operation."""

from __future__ import annotations

from benchmark.kimi_cost import (  # noqa: F401 — the same keys
    expert_matrix_values, kda_cost, kda_matrix_values, state_bytes)


def layers(model: dict) -> dict:
    """How many of the built layers are of each kind."""
    built = model["num_hidden_layers"]
    full = sum(n < built for n in model["gqa_layers"])
    dense = model["first_k_dense_replace"]
    return {"kda": built - full, "full": full, "dense": dense,
            "sparse": built - dense}


def kv_values(model: dict) -> int:
    """Values one position leaves in the pools, a full layer: its key
    and its value, every key-value head."""
    return 2 * model["num_key_value_heads"] * model["head_dim"]


def full_matrix_values(model: dict) -> int:
    """Values in one full mixer: q, k, v, the gate (a value a head and
    channel), o."""
    hidden, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return hidden * d * (3 * heads + 2 * kv)


def attention_cost(model: dict, rows: float, context: float,
                   bytes_per_value: int = 2) -> dict:
    """The full mixer of one layer of one decode step of ``rows`` busy
    rows over contexts of ``context`` live positions. Least bytes: the
    mixer's matrices once, each live position's key and value once and
    the rows' own written, the rows' hidden states in and out.
    Operations, 2 a multiply-add: every matrix a token passes, scores
    and sums over the live positions (a query head against its group's
    key and value)."""
    heads, d = model["num_attention_heads"], model["head_dim"]
    moved = (full_matrix_values(model)
             + rows * (context + 1) * kv_values(model)
             + 2 * rows * model["hidden_size"]) * bytes_per_value
    return {"flops": 2.0 * rows * full_matrix_values(model)
            + 4.0 * rows * (context + 1) * heads * d, "bytes": float(moved)}


def expert_ffn_cost(model: dict, experts_read: float, choices: float,
                    tokens: float, bytes_per_value: int = 2) -> dict:
    """The sparse feed-forward of one layer of one pass. Least bytes:
    each of the ``experts_read`` HELD routed experts' three matrices
    once (a held expert nobody chose need not be read; an expert held
    elsewhere is another chip's read), the shared expert's always, the
    tokens' hidden states in and out. Operations: each of the
    ``choices`` that landed here (token x held expert) and each token
    through the shared expert is three products of hidden_size x
    moe_intermediate_size, 2 operations a multiply-add."""
    shared = model["n_shared_experts"]
    moved = ((experts_read + shared) * expert_matrix_values(model)
             + 2 * tokens * model["hidden_size"]) * bytes_per_value
    return {"flops": 2.0 * (choices + shared * tokens)
            * expert_matrix_values(model), "bytes": float(moved)}


def decode_step_cost(model: dict, rows: float, context: float,
                     experts_read: float, choices: float,
                     bytes_per_value: int = 2) -> dict:
    """One decode step of ``rows`` busy rows over contexts of
    ``context`` live positions, ``experts_read`` held routed experts
    touched and ``choices`` landed a layer. Least bytes: every weight
    touched once (the head; each mixer; a layer's router, shared expert
    and the held experts touched; two norms a layer; the embedding is a
    lookup of the step's tokens), each row's state read and written a
    KDA layer, each LIVE position's key and value once a full layer and
    the rows' written. Operations, 2 a multiply-add: every matrix a
    token passes (of the routed experts those of its choices that are
    held), the head, the rule and the attention."""
    hidden, kinds = model["hidden_size"], layers(model)
    routed = model["n_routed_experts_routed_over"]
    router = hidden * routed + routed
    shared = model["n_shared_experts"] * expert_matrix_values(model)
    head = hidden * model["vocab_size"] + hidden
    kda = kda_cost(model, rows, bytes_per_value)
    full = attention_cost(model, rows, context, bytes_per_value)
    moved = {
        "head": head * bytes_per_value,
        "kda": kinds["kda"] * kda["bytes"],
        "full_matrices": kinds["full"] * full_matrix_values(model)
        * bytes_per_value,
        "keys_and_values": kinds["full"] * rows * (context + 1)
        * kv_values(model) * bytes_per_value,
        "norms": (kinds["dense"] + kinds["sparse"]) * 2 * hidden
        * bytes_per_value,
        "router_and_shared": kinds["sparse"] * (router + shared)
        * bytes_per_value,
        "experts": kinds["sparse"] * experts_read
        * expert_matrix_values(model) * bytes_per_value,
        "tokens": rows * hidden * bytes_per_value,
    }
    per_token = kinds["sparse"] * (router + shared) + head
    flops = (2.0 * rows * per_token + kinds["kda"] * kda["flops"]
             + kinds["full"] * full["flops"]
             + 2.0 * kinds["sparse"] * choices * expert_matrix_values(model))
    return {"flops": flops, "bytes": float(sum(moved.values())),
            "moved": moved}
