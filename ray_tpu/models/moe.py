"""Mixture-of-Experts SwiGLU layer: top-k routing, nothing dropped.

Router logits in float32, a score for ALL experts, the ``k`` best with
their indices, and every token served by all ``k`` of its experts (no
capacity, no dropped token). The scoring is one of two:

- ``"softmax"``, the block as OLMoE publishes it
  (``OlmoeSparseMoeBlock``), which is also Mixtral's and SDAR's with
  ``norm_topk_prob``: a softmax over all experts, the ``k`` largest
  probabilities, left as they are or renormalised to sum to one;
- ``"sigmoid"``, DeepSeek-V3's ``noaux_tc`` without groups, as
  Xing4.0 publishes it: an expert's score is the sigmoid of its logit;
  the ``k`` experts are those of the largest score PLUS a learned bias,
  which takes no part in the weight; the weights are the chosen
  experts' scores, renormalised with ``norm_topk_prob``, times a
  ``scale`` (``routed_scaling_factor``).

Beside the routed experts a model may have a shared expert, a plain
SwiGLU every token passes with weight one (``shared_ffn``).

One routing function (``route``) serves ``llama.forward`` (training)
and the paged engine (``serve/llm_engine/model.py``, and ``latent.py``
and ``linear.py`` for the sigmoid scoring and the shared expert); the
expert feed-forward has two forms of the same arithmetic.

``expert_ffn`` (training's ``moe_mlp``, and the plain form the tests
hold the other to) is an all-experts product: every resident expert is
applied to every token and the result is weighted by the (mostly zero)
combine weights. That reads EVERY held expert's three matrices once,
whatever share of them the tokens chose, and costs ``E / k`` times the
arithmetic of a sparse dispatch, which is why training at scale wants
tokens sorted by expert and a grouped product instead (ROADMAP R1,
open). With experts sharded over ``ep`` (logical axis "expert") each
shard applies its own experts and the contraction over experts becomes
an all-reduce.

``touched_expert_ffn`` (serving: the engine's forward has no gradient,
and a decode step or a prefill chunk carries 16 to 128 tokens) reads
only the experts that some token weighs above zero, each once, which is
the floor of such a pass: a step's tokens x ``k`` choices leave a tenth
to a half of the held experts unchosen. It is one kernel an expert layer
(``ops/grouped_expert_ffn.py``) over the layers' STACKED expert tensors
and the layer's index, so a family's scan closes over the three
tensors (``split_experts``) and hands the index; a layer's slice handed
to a kernel would be a copy of all its experts.

A load-balancing auxiliary loss (fraction of the choices x mean router
probability per expert, scaled by E: Switch Transformer eq. 4, with the
``k`` choices of a token counted ``1/k`` each) keeps the router from
collapsing in training.

Params per MoE layer (leading E = expert dim, logical "expert" -> ep):
  w_router [H, E]; w_gate/w_up [E, H, M]; w_down [E, M, H]; with the
  sigmoid scoring router_bias [E]; with a shared expert of width S
  shared_gate/shared_up [H, S]; shared_down [S, H].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# A layer's expert tensors, [E, H, M], [E, H, M] and [E, M, H].
EXPERT_TENSORS = ("w_gate", "w_up", "w_down")
# The engine's expert counters, in the order ``routing_counts`` fills
# them (``engine.ENGINE_STAT_KEYS`` documents each).
EXPERT_COUNTERS = ("expert_choices", "expert_slots", "experts_touched",
                    "expert_peak_choices")
_LOW_BITS = 30  # an accumulator word holds 30 bits; the carry goes up


def init_moe_params(key: jax.Array, hidden: int, mlp: int,
                    num_experts: int, num_layers: int,
                    router_scale: float = 1.0) -> dict:
    """Random weights; a router's logits on a unit-RMS input have the
    standard deviation ``router_scale``."""
    keys = jax.random.split(key, 4)

    def dense(k, fan_in, *shape):
        return jax.random.normal(k, shape, dtype=jnp.float32) * fan_in ** -0.5

    return {
        "w_router": dense(keys[0], hidden / router_scale ** 2, num_layers,
                          hidden, num_experts),
        "w_gate": dense(keys[1], hidden, num_layers, num_experts, hidden, mlp),
        "w_up": dense(keys[2], hidden, num_layers, num_experts, hidden, mlp),
        "w_down": dense(keys[3], mlp, num_layers, num_experts, mlp, hidden),
    }


def moe_logical_axes() -> dict:
    """Leading scan (layer) dim = None; expert dim -> ep via rules."""
    return {
        "w_router": (None, "embed", None),
        "w_gate": (None, "expert", "embed", "mlp"),
        "w_up": (None, "expert", "embed", "mlp"),
        "w_down": (None, "expert", "mlp", "embed"),
    }


def route(x: jax.Array, w_router: jax.Array, experts_per_token: int,
          norm_topk_prob: bool = False, *, scoring: str = "softmax",
          bias: "jax.Array | None" = None, scale: float = 1.0):
    """x [..., H] -> (scores [..., E], idx [..., k], weights [..., k]).

    All float32: the logits are a float32 product at the highest
    precision (on a TPU a float32 matmul otherwise rounds its operands
    to bf16). ``scoring="softmax"``: the scores are a softmax over all
    experts and ``lax.top_k`` takes the ``k`` largest (ties to the
    lower index). ``scoring="sigmoid"``: the scores are the logits'
    sigmoids, the ``k`` experts those of the largest ``score + bias``
    (``bias`` [E], or none), and the weights the scores of the chosen,
    without the bias. ``weights`` are those scores as they are, or
    divided by their sum with ``norm_topk_prob``, then times ``scale``.
    """
    logits = jnp.einsum("...h,he->...e", x.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = lax.top_k(probs, experts_per_token)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        biased = probs if bias is None else probs + bias.astype(jnp.float32)
        _, idx = lax.top_k(biased, experts_per_token)
        weights = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        raise ValueError(f"scoring={scoring!r}: 'softmax' or 'sigmoid'")
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return probs, idx, weights


def _chosen(idx: jax.Array, num_experts: int, held, dtype) -> jax.Array:
    """[..., k] choices over ``num_experts`` -> [..., k, held experts]
    one-hot; a choice of an expert that is not held is a row of zeros
    (``one_hot`` of an index outside its range)."""
    if held is None:
        return jax.nn.one_hot(idx, num_experts, dtype=dtype)
    first, count = held
    if not 0 <= first <= first + count <= num_experts:
        raise ValueError(f"held={held}: outside the {num_experts} experts")
    return jax.nn.one_hot(idx - first, count, dtype=dtype)


def combine_weights(idx: jax.Array, weights: jax.Array, num_experts: int,
                    held: "tuple[int, int] | None" = None) -> jax.Array:
    """[..., k] choices -> [..., E] float32: a token's weight for each
    expert, zero for the experts it did not choose. ``held=(first,
    count)``: the layer holds experts ``first .. first + count - 1`` of
    the ``num_experts`` it routes over (one chip's share of a layer
    whose experts are split over chips); the result is ``[..., count]``,
    and a choice that landed on another chip's expert weighs nothing
    here: that chip adds it."""
    chosen = _chosen(idx, num_experts, held, jnp.float32)
    return jnp.einsum("...ke,...k->...e", chosen, weights)


def expert_ffn(layer: dict, x: jax.Array, combine: jax.Array,
               dtype=jnp.bfloat16) -> jax.Array:
    """sum_e combine[..., e] * W_down_e(silu(W_gate_e x) * W_up_e x).

    x [B, T, H], combine [B, T, E] -> [B, T, H] in ``dtype``; ``E`` is
    the experts HELD (``combine_weights``' ``held``), which are the
    layer's arrays' leading axis. Every
    expert with a non-zero weight contributes: nothing is dropped. An
    expert a token did not choose contributes exactly zero, whatever
    its activations come to (``where``, not a product with zero). The weight
    is applied before the down-projection (which is linear), so the sum
    over experts is one contraction accumulated in float32.
    """
    b, t, h = x.shape
    num_experts = combine.shape[-1]
    # Experts as the batch dimension of every product: an expert's
    # matrices are then read where and as they lie ([E, H, M] with the
    # contraction over H is otherwise transposed whole before the
    # product, which on the chip tripled what a decode step moves).
    tokens = jnp.broadcast_to(x.astype(dtype).reshape(1, b * t, h),
                              (num_experts, b * t, h))
    gate = jnp.einsum("enh,ehm->enm", tokens, layer["w_gate"].astype(dtype))
    up = jnp.einsum("enh,ehm->enm", tokens, layer["w_up"].astype(dtype))
    weight = combine.reshape(b * t, num_experts).T[..., None]    # [E, N, 1]
    hidden = (jax.nn.silu(gate) * up).astype(jnp.float32) * weight
    hidden = jnp.where(weight > 0, hidden, 0.0).astype(dtype)
    out = jnp.einsum("enm,emh->nh", hidden, layer["w_down"].astype(dtype))
    return out.reshape(b, t, h)


def split_experts(layers: dict) -> "tuple[dict, dict]":
    """A stack of expert layers (every array's leading axis the layer)
    -> (its three expert tensors, which ``touched_expert_ffn`` takes
    whole; the rest, which a scan slices a layer at a time)."""
    return ({k: layers[k] for k in EXPERT_TENSORS},
            {k: v for k, v in layers.items() if k not in EXPERT_TENSORS})


def touched_expert_ffn(experts: dict, index, x: jax.Array,
                       combine: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """``expert_ffn`` of layer ``index`` (an int32 scalar) of ``experts``,
    the layers' stacked tensors (w_gate, w_up ``[L, E, H, M]``, w_down
    ``[L, E, M, H]``, held in ``dtype``), reading only the experts with
    a weight above zero in ``combine``. x [B, T, H], combine [B, T, E]
    -> [B, T, H] in ``dtype``; the same rounding points, an unchosen
    expert contributing exactly zero, nothing dropped."""
    from ray_tpu.ops.grouped_expert_ffn import grouped_expert_ffn

    b, t, h = x.shape
    out = grouped_expert_ffn(
        *(experts[k].astype(dtype) for k in EXPERT_TENSORS), index,
        x.astype(dtype).reshape(b * t, h),
        combine.reshape(b * t, combine.shape[-1]))
    return out.reshape(b, t, h)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """``W_down(silu(W_gate x) * W_up x)``: x [..., H] -> [..., H] in
    ``dtype``; w_gate, w_up [H, M], w_down [M, H]."""
    x = x.astype(dtype)
    gate = jnp.einsum("...h,hm->...m", x, w_gate.astype(dtype))
    up = jnp.einsum("...h,hm->...m", x, w_up.astype(dtype))
    return jnp.einsum("...m,mh->...h", jax.nn.silu(gate) * up,
                      w_down.astype(dtype))


def shared_ffn(layer: dict, x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """The shared expert: a plain SwiGLU of every token, weight one,
    unrouted."""
    return swiglu(x, layer["shared_gate"], layer["shared_up"],
                  layer["shared_down"], dtype)


def load_balance_loss(probs: jax.Array, idx: jax.Array) -> jax.Array:
    """E * mean over batch rows of sum_e(fraction_e * mean_prob_e):
    1 when the choices are spread evenly, E when one expert takes all.
    probs [B, T, E], idx [B, T, k]."""
    num_experts = probs.shape[-1]
    chosen = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)  # [B,T,k,E]
    fraction = jnp.mean(chosen, axis=(1, 2))           # [B, E]
    mean_prob = jnp.mean(probs, axis=1)                # [B, E]
    return num_experts * jnp.mean(jnp.sum(fraction * mean_prob, axis=-1))


def moe_mlp(layer: dict, x: jax.Array, *, experts_per_token: int = 1,
            norm_topk_prob: bool = False,
            dtype=jnp.bfloat16) -> tuple[jax.Array, jax.Array]:
    """Top-k MoE SwiGLU: x [B, T, H] -> (out [B, T, H], aux_loss scalar).

    ``layer`` holds one layer's slice: w_router [H, E],
    w_gate/w_up [E, H, M], w_down [E, M, H].
    """
    probs, idx, weights = route(x, layer["w_router"], experts_per_token,
                                norm_topk_prob)
    combine = combine_weights(idx, weights, probs.shape[-1])
    out = expert_ffn(layer, x, combine, dtype)
    return out.astype(x.dtype), load_balance_loss(probs, idx)


# ------------------------------------------------ the engine's counters


def routing_counts(idx: jax.Array, valid: jax.Array, num_experts: int,
                   held: "tuple[int, int] | None" = None) -> jax.Array:
    """What one layer's routing of one step did, int32 in the order of
    ``EXPERT_COUNTERS``. idx [B, T, k]; valid [B, T]: tokens that
    carry a request (padding and inactive rows route too, uncounted).

    choices: valid tokens x k. slots: the experts on offer (E).
    touched: experts that got at least one choice. peak choices: E x
    the busiest expert's load, so that over ``choices`` it is the
    largest load over the mean. With ``held`` (``combine_weights``)
    all four count the experts held: the choices that landed on them,
    and ``E`` their number.
    """
    chosen = _chosen(idx, num_experts, held, jnp.int32)         # [B,T,k,E]
    offered = chosen.shape[-1]
    load = jnp.sum(chosen * valid[..., None, None], axis=(0, 1, 2))  # [E]
    return jnp.stack([jnp.sum(load), jnp.int32(offered),
                      jnp.sum(load > 0), offered * jnp.max(load)])


def init_stats() -> jax.Array:
    """The accumulator a step carries: int32 [2, len(EXPERT_COUNTERS)],
    low 30 bits and the carries above them, so that it never wraps."""
    return jnp.zeros((2, len(EXPERT_COUNTERS)), jnp.int32)


def accumulate(stats: jax.Array, counts: jax.Array) -> jax.Array:
    """``stats`` plus one step's ``counts`` (each under 2**30)."""
    low = stats[0] + counts
    return jnp.stack([low & ((1 << _LOW_BITS) - 1),
                      stats[1] + (low >> _LOW_BITS)])


def read_stats(stats) -> dict:
    """The accumulator as Python ints (one transfer, when asked)."""
    import numpy as np

    low, high = np.asarray(stats).astype(object)
    return {key: int(hi) * (1 << _LOW_BITS) + int(lo)
            for key, lo, hi in zip(EXPERT_COUNTERS, low, high)}
