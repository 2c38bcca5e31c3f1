"""Bytes and operations ONE decode step of the hybrid decoder
(``phi4flash``) must move and do, from shapes: what
``decode_step_roofline.phi`` holds the traced decode program against.
``model`` holds the Hugging Face keys of the configuration file; the
state-space sizes are not among them (``assumed`` in that file) and are
this file's constants. Beside ``flops.py``, whose ``least_seconds``
turns a cost into the roofline's least time. Its own count: it calls
nothing of ``ray_tpu``."""

from __future__ import annotations

import math

D_STATE, D_CONV, EXPAND = 16, 4, 2


def sizes(model: dict) -> dict:
    e, layers = model["hidden_size"], model["num_hidden_layers"]
    head = e // model["num_attention_heads"]
    return {"e": e, "m": model["intermediate_size"], "di": EXPAND * e,
            "rank": math.ceil(e / 16), "head": head,
            "kv": model["num_key_value_heads"] * head,
            "ssm_layers": layers // 4 + 1, "window_layers": layers // 4,
            "readers": layers // 4,          # the full layer and the crosses
            "cross_layers": layers // 4 - 1, "layers": layers}


def parameters(model: dict) -> dict:
    """Parameters by part; every one of them sits in a matrix product of
    a decode step or is read beside one, and is read once a step."""
    s = sizes(model)
    e, m, di, kv, n = s["e"], s["m"], s["di"], s["kv"], D_STATE
    lambdas = 4 * s["head"] + 2 * s["head"]
    return {
        "table": model["vocab_size"] * e,     # the tied head reads it whole
        "blocks": s["layers"] * (3 * e * m + 4 * e) + 2 * e,
        "ssm": s["ssm_layers"] * (
            e * 2 * di + D_CONV * di + di + di * (s["rank"] + 2 * n)
            + s["rank"] * di + di + di * n + di + di * e),
        "attention": (s["window_layers"] + 1) * (
            e * (e + 2 * kv) + e + 2 * kv + lambdas + e * e + e),
        "cross": s["cross_layers"] * (2 * e * e + 2 * e + lambdas),
        "gmu": s["cross_layers"] * 2 * e * di,
    }


def kv_bytes_per_position(model: dict, bytes_per_value: int = 2) -> int:
    """One layer's keys and values of one position."""
    return 2 * sizes(model)["kv"] * bytes_per_value


def state_bytes_per_row(model: dict, bytes_per_value: int = 2) -> int:
    """The recurrent state of one row, all state-space layers: the
    float32 ``[Di, N]`` state and the convolution's last inputs."""
    s = sizes(model)
    return s["ssm_layers"] * (s["di"] * D_STATE * 4
                              + (D_CONV - 1) * s["di"] * bytes_per_value)


def decode_step_cost(model: dict, rows: float, context: float,
                     bytes_per_value: int = 2) -> dict:
    """One decode step of ``rows`` rows whose contexts hold ``context``
    positions each (the window's means). Least bytes: every weight once;
    the live positions of the shared pool once for EACH of its readers
    (the full-attention layer and the cross-attention layers: eight
    matrix products over the same keys cannot share one read of them
    unless they are fused into one operation, which no such program
    is); ``min(context, window)`` positions for each window layer; each
    row's state read and written; the new keys and values written.
    Operations: 2 a parameter of every matrix product and row (the
    table counts once, as the head; the lookup is no product), and the
    attention's two products over the positions read."""
    s, p = sizes(model), parameters(model)
    per_position = kv_bytes_per_position(model, bytes_per_value)
    windowed = min(context, model["sliding_window"])
    moved = {
        "weights": sum(p.values()) * bytes_per_value,
        "shared_kv": rows * context * per_position * s["readers"],
        "window_kv": rows * windowed * per_position * s["window_layers"],
        "state": 2 * rows * state_bytes_per_row(model, bytes_per_value),
        "kv_written": rows * per_position * (s["window_layers"] + 1),
    }
    # Per position read: the scores (heads x head) and the weighted sum
    # (heads / 2 pairs x 2 head), 2 operations a multiply-add.
    attention = 2.0 * 2 * s["e"] * rows * (
        context * s["readers"] + windowed * s["window_layers"])
    return {"flops": 2.0 * rows * sum(p.values()) + attention,
            "bytes": float(sum(moved.values())), "moved": moved}
