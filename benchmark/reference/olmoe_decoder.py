"""Plain reference of the sparse decoder OLMoE-1B-7B publishes
(``OlmoeForCausalLM``, Hugging Face ``modeling_olmoe.py``, written from
memory: there is no network here). For a layer with input ``x``:

    n = RMSNorm(x)
    q = n Wq,  k = n Wk,  v = n Wv               no bias, clip_qkv null
    q = RMSNorm_q(q),  k = RMSNorm_k(k)          over the WHOLE projection
                                                 (all heads together), each
                                                 with its own scale
    split into heads of head_dim; rotary embedding on halves
    (rotate_half, theta) on q and k
    h = x + (causal softmax attention at scale head_dim^-0.5) Wo
    m = RMSNorm(h)
    r = m Wr                                     router logits, no bias
    p = softmax(r) over ALL experts, float32
    the num_experts_per_tok largest p, with their indices
    norm_topk_prob false: the weights are those values of p as they are
                   true:  divided by their sum
    y = h + sum_k p_k * Wdown_k(silu(Wgate_k m) * Wup_k m)

Every token is served by all of its experts: no capacity, nothing
dropped. Then a final RMSNorm and an untied output head. Grouped-query
attention is written for generality (OLMoE has as many key-value heads
as query heads).

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. The expert sum is a loop over the experts in which
expert ``e`` is applied to every token and kept where the token chose
it (``where`` on the chosen set, not arithmetic with the weights), which
is the equation above term by term. It calls nothing of
``ray_tpu.models``; it shares only the layout of the parameter tree
(``embed.tokens [V, E]``, ``layers.{attn_norm, wq [n, E, H, D], wk, wv
[n, E, KV, D], q_norm [n, H, D], k_norm [n, KV, D], wo [n, H, D, E],
mlp_norm, w_router [n, E, X], w_gate, w_up [n, X, E, M], w_down [n, X,
M, E]}``, ``final_norm``, ``lm_head [E, V]``; a ``[H, D]`` scale read
row by row is the published ``[H * D]`` one). ``model`` is the
configuration file's dictionary of Hugging Face keys
(``norm_topk_prob`` false where it is left out, ``OlmoeConfig``'s
default: the harness hands over the file's numbers only); ``qk_norm``
is not one of them and is read from the parameter tree: a tree without
``q_norm`` has none.

``forward(..., with_routing=True)`` also returns each layer's chosen
experts ``[n, B, L, k]`` (sorted), for counting the choices on which a
bf16 program and this reference differ. ``forward_tail`` gives the
logits of the last positions of one long context against all of it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, positions, theta):
    """x: [B, L, H, D]; the token at position p rotates the pair
    (x[i], x[i + D/2]) by the angle p * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * freqs             # [L, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_positions):
    """Causal softmax attention of the queries at ``q_positions`` [Lq]
    over keys at positions 0..Lk-1. q: [B, Lq, H, D]; k, v: [B, Lk, KV,
    D]; query head h reads key-value head h // (H / KV)."""
    b, lq, h, d = q.shape
    lk, kv = k.shape[1], k.shape[2]
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    causal = jnp.arange(lk)[None, :] <= q_positions[:, None]    # [Lq, Lk]
    scores = jnp.where(causal, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def route(m, w_router, k, norm_topk_prob):
    """m [B, L, E] -> (indices [B, L, k], weights [B, L, k])."""
    probs = jax.nn.softmax(m @ w_router, axis=-1)
    weights, idx = lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return idx, weights


def experts(m, w, idx, weights):
    """sum over a token's chosen experts of weight * expert(m)."""
    def one_expert(total, expert):
        e, w_gate, w_up, w_down = expert
        out = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down   # [B, L, E]
        chose = idx == e                                        # [B, L, k]
        weight = jnp.sum(jnp.where(chose, weights, 0.0), axis=-1)
        return total + jnp.where(jnp.any(chose, axis=-1)[..., None],
                                 weight[..., None] * out, 0.0), None

    count = w["w_gate"].shape[0]
    total, _ = lax.scan(one_expert, jnp.zeros_like(m),
                        (jnp.arange(count), w["w_gate"], w["w_up"],
                         w["w_down"]))
    return total


def layer(x, context, w, model, q_positions):
    """One decoder layer for the tokens ``x`` [B, Lq, E] at
    ``q_positions``, attending over ``context`` [B, Lk, E] (the layer's
    input at positions 0..Lk-1; ``x`` itself in a full forward pass).
    Returns (output, the chosen experts [B, Lq, k] sorted)."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    w = jax.tree.map(lambda a: a.astype(F32), w)

    def project(tokens, name, norm):
        out = jnp.einsum("ble,ehd->blhd",
                         rms_norm(tokens, w["attn_norm"], eps), w[name])
        if norm in w:
            flat = rms_norm(out.reshape(*out.shape[:2], -1),
                            w[norm].reshape(-1), eps)
            out = flat.reshape(out.shape)
        return out

    q = rope(project(x, "wq", "q_norm"), q_positions, theta)
    k = rope(project(context, "wk", "k_norm"),
             jnp.arange(context.shape[1]), theta)
    v = project(context, "wv", None)
    h = x + jnp.einsum("blhd,hde->ble", attention(q, k, v, q_positions),
                       w["wo"])
    m = rms_norm(h, w["mlp_norm"], eps)
    idx, weights = route(m, w["w_router"], model["num_experts_per_tok"],
                         model.get("norm_topk_prob", False))
    return h + experts(m, w, idx, weights), jnp.sort(idx, axis=-1)


def forward(params, tokens, model, with_routing: bool = False):
    """tokens [B, L] -> logits [B, L, V], float32 (and, with
    ``with_routing``, the chosen experts [n, B, L, k])."""
    positions = jnp.arange(tokens.shape[1])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(F32)[tokens]
        x, routing = lax.scan(
            lambda x, w: layer(x, x, w, model, positions), x,
            params["layers"])
        x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
        logits = x @ params["lm_head"].astype(F32)
    return (logits, routing) if with_routing else logits


def forward_tail(params, tokens, model, tail: int):
    """One long context, its last ``tail`` positions against all of it:
    tokens [1, L] -> (logits [1, tail, V], routing [n, 1, L, k]: the
    choices of every position, since an early one reaches the tail
    through attention). Every layer is run over the whole context in
    blocks of ``tail`` positions (what the last positions attend to has
    to be computed), so no [L, L] score matrix and no [L, V] logits are
    ever formed."""
    length = tokens.shape[1]
    assert length % tail == 0, (length, tail)
    starts = jnp.arange(0, length, tail)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(F32)[tokens]

        def one_layer(x, w):
            def block(start):
                rows = lax.dynamic_slice_in_dim(x, start, tail, axis=1)
                return layer(rows, x, w, model, start + jnp.arange(tail))

            out, routing = lax.map(block, starts)  # [blocks, 1, tail, ...]
            return (jnp.moveaxis(out, 0, 1).reshape(x.shape),
                    jnp.moveaxis(routing, 0, 1).reshape(1, length, -1))

        x, routing = lax.scan(one_layer, x, params["layers"])
        x = rms_norm(x[:, -tail:], params["final_norm"],
                     model["rms_norm_eps"])
        return x @ params["lm_head"].astype(F32), routing
