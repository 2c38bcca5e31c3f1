"""Bytes and operations ONE decode step of the Jamba decoder (``jamba``:
Mamba-1 layers whose ``dt``, ``B`` and ``C`` are normed, around
attention layers of many query heads on ONE key-value head; a dense
gated MLP in every layer) must move and do, from shapes: what
``decode_step_roofline.jamba`` holds the traced decode program against
and ``ssm_state_roofline.jamba`` the traced state-space operations.
``model`` holds the Hugging Face keys of the configuration file.
Beside ``flops.py``, whose ``least_seconds`` turns a cost into the
roofline's least time. Its own count: it calls nothing of ``ray_tpu``.
The same work whatever implements it: a row's float32 state ``[Di, N]``
is read once and written once a Mamba layer (a program that reduces the
output from the state in one pass and updates it in another reads it
twice: that is its gap, not the work's), the convolution's last inputs
likewise, a LIVE position's key and value (2 x 1 x 128 values) once an
attention layer, every weight once."""

from __future__ import annotations


def sizes(model: dict) -> dict:
    e = model["hidden_size"]
    return {"e": e, "m": model["intermediate_size"],
            "di": model["mamba_expand"] * e, "n": model["mamba_d_state"],
            "rank": model["mamba_dt_rank"], "taps": model["mamba_d_conv"],
            "heads": model["num_attention_heads"],
            "kv": model["num_key_value_heads"],
            "d": e // model["num_attention_heads"]}


def layers(model: dict) -> dict:
    """How many of the layers are of each kind: layer ``i`` is attention
    where ``i % attn_layer_period == attn_layer_offset``."""
    built = model["num_hidden_layers"]
    attention = sum(i % model["attn_layer_period"]
                    == model["attn_layer_offset"] for i in range(built))
    return {"mamba": built - attention, "attention": attention}


def mamba_matrix_values(model: dict) -> dict:
    """Values of one Mamba mixer, those a token passes in a matrix
    product and those read beside them."""
    s = sizes(model)
    e, di, n, r, taps = s["e"], s["di"], s["n"], s["rank"], s["taps"]
    products = e * 2 * di + di * (r + 2 * n) + r * di + di * e
    return {"products": products,
            # the convolution and its bias, dt's bias, A_log, D, the norms
            "beside": taps * di + di + di + di * n + di + r + 2 * n}


def state_bytes(model: dict, bytes_per_value: int = 2) -> int:
    """One row's state of ONE Mamba layer: the float32 ``[Di, N]`` and
    the convolution's last ``taps - 1`` inputs."""
    s = sizes(model)
    return s["di"] * s["n"] * 4 + (s["taps"] - 1) * s["di"] * bytes_per_value


def mamba_cost(model: dict, rows: float, bytes_per_value: int = 2) -> dict:
    """The mixer of one Mamba layer of one decode step of ``rows`` busy
    rows. Least bytes: the mixer's values once, each row's state read
    once and written once, the rows' hidden states in and out.
    Operations, 2 a multiply-add: every matrix a token passes, the
    convolution's taps, and the recurrence: a state entry is decayed
    (an exponential and a product), takes its input (a product and a
    sum) and gives to the output (a product and a sum): 6."""
    s, values = sizes(model), mamba_matrix_values(model)
    moved = (sum(values.values()) * bytes_per_value
             + 2 * rows * state_bytes(model, bytes_per_value)
             + 2 * rows * s["e"] * bytes_per_value)
    flops = rows * (2.0 * values["products"] + 2.0 * s["taps"] * s["di"]
                    + 6.0 * s["di"] * s["n"])
    return {"flops": flops, "bytes": float(moved)}


def attention_matrix_values(model: dict) -> int:
    s = sizes(model)
    return s["e"] * s["d"] * 2 * (s["heads"] + s["kv"])


def kv_values(model: dict) -> int:
    """Values one position leaves in the pools, an attention layer."""
    s = sizes(model)
    return 2 * s["kv"] * s["d"]


def attention_cost(model: dict, rows: float, context: float,
                   bytes_per_value: int = 2) -> dict:
    """The mixer of one attention layer of one decode step over contexts
    of ``context`` live positions: its matrices once, each live
    position's key and value once and the rows' own written, the rows'
    hidden states in and out; scores and sums over the live positions
    for every query head."""
    s = sizes(model)
    moved = (attention_matrix_values(model)
             + rows * (context + 1) * kv_values(model)
             + 2 * rows * s["e"]) * bytes_per_value
    return {"flops": 2.0 * rows * attention_matrix_values(model)
            + 4.0 * rows * (context + 1) * s["heads"] * s["d"],
            "bytes": float(moved)}


def decode_step_cost(model: dict, rows: float, context: float,
                     bytes_per_value: int = 2) -> dict:
    """One decode step of ``rows`` busy rows over contexts of
    ``context`` live positions. Least bytes: every weight once (the tied
    table as the head: the embedding is a lookup of the step's tokens;
    each mixer; each layer's MLP and two norms), each row's state read
    and written a Mamba layer, each LIVE position's key and value once
    an attention layer. Operations, 2 a multiply-add: every matrix a
    token passes, the head, the recurrence and the attention."""
    s, kinds = sizes(model), layers(model)
    built = kinds["mamba"] + kinds["attention"]
    mlp = 3 * s["e"] * s["m"]
    head = s["e"] * model["vocab_size"] + s["e"]
    mamba = mamba_cost(model, rows, bytes_per_value)
    attention = attention_cost(model, rows, context, bytes_per_value)
    moved = {
        "head": head * bytes_per_value,
        "mamba": kinds["mamba"] * mamba["bytes"],
        "attention": kinds["attention"] * attention["bytes"],
        "mlp_and_norms": built * (mlp + 2 * s["e"]) * bytes_per_value,
        "tokens": rows * s["e"] * bytes_per_value,
    }
    flops = (2.0 * rows * (built * mlp + head)
             + kinds["mamba"] * mamba["flops"]
             + kinds["attention"] * attention["flops"])
    return {"flops": flops, "bytes": float(sum(moved.values())),
            "moved": moved}
