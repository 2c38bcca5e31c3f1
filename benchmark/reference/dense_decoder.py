"""Plain reference of a dense decoder-only transformer as Mistral-7B
publishes it (``MistralForCausalLM`` without a sliding window): RMSNorm,
rotary embedding on halves (``rotate_half``), grouped-query causal
attention, SwiGLU, no biases, untied output head, mean next-token
cross-entropy.

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. It does not call ``ray_tpu.models.llama``; it shares
only the layout of the parameter tree (``embed.tokens [V, E]``,
``layers.{attn_norm, wq [n, E, H, D], wk, wv [n, E, KV, D], wo [n, H, D,
E], mlp_norm, w_gate, w_up [n, E, M], w_down [n, M, E]}``,
``final_norm``, ``lm_head [E, V]``). ``model`` is the configuration
file's dictionary of Hugging Face keys.

One departure, for memory only: attention scores are formed one group of
query heads at a time (``lax.map`` over the key-value heads), because
[B, 32, L, L] in float32 at L=4096 does not fit beside the train state.
The arithmetic of each head is unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """x: [B, L, H, D]; position l rotates the pair (x[i], x[i + D/2])
    by the angle l * theta^(-2i/D)."""
    length, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(length, dtype=F32)[:, None] * freqs     # [L, D/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention. q: [B, L, H, D]; k, v: [B, L, KV, D];
    query head h reads key-value head h // (H / KV)."""
    b, length, h, d = q.shape
    kv = k.shape[2]
    causal = jnp.tril(jnp.ones((length, length), bool))

    def one_group(qkv):
        qg, kg, vg = qkv              # [B, L, H/KV, D], [B, L, D], [B, L, D]
        scores = jnp.einsum("bqgd,bkd->bgqk", qg, kg) * d ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bqgd", jax.nn.softmax(scores, -1), vg)

    groups = q.reshape(b, length, kv, h // kv, d).transpose(2, 0, 1, 3, 4)
    out = lax.map(one_group, (groups, k.transpose(2, 0, 1, 3),
                              v.transpose(2, 0, 1, 3)))  # [KV, B, L, G, D]
    return out.transpose(1, 2, 0, 3, 4).reshape(b, length, h, d)


def forward(params, tokens, model) -> jax.Array:
    """tokens [B, L] -> logits [B, L, V], float32."""
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"].astype(F32)[tokens]

        def layer(x, w):
            w = jax.tree.map(lambda a: a.astype(F32), w)
            n = rms_norm(x, w["attn_norm"], eps)
            q = rope(jnp.einsum("ble,ehd->blhd", n, w["wq"]), theta)
            k = rope(jnp.einsum("ble,ekd->blkd", n, w["wk"]), theta)
            v = jnp.einsum("ble,ekd->blkd", n, w["wv"])
            x = x + jnp.einsum("blhd,hde->ble", attention(q, k, v), w["wo"])
            n = rms_norm(x, w["mlp_norm"], eps)
            hidden = jax.nn.silu(n @ w["w_gate"]) * (n @ w["w_up"])
            return x + hidden @ w["w_down"], None

        x, _ = lax.scan(layer, x, params["layers"])
        x = rms_norm(x, params["final_norm"], eps)
        return x @ params["lm_head"].astype(F32)


def loss(params, tokens, targets, model) -> jax.Array:
    """Mean cross-entropy of ``targets`` (the tokens shifted by one)."""
    logits = forward(params, tokens, model)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(log_probs, targets[..., None], -1)[..., 0]
    return -jnp.mean(picked)
