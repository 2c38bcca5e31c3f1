"""Plain reference of the decoder AI21-Jamba2-3B publishes (``model_type``
``jamba``; the published modelling code and arXiv:2403.19887, written
from memory: there is no network here). ``E`` hidden size, ``Di = 2 E``,
``N`` the state's size, ``R`` the step's rank, ``H`` query heads of ``d =
E / H`` on ``KV`` key-value heads (one).

    every layer:  x = x + Mixer(RMS(x));  x = x + Wd (silu(Wg h) * (Wu h)), h = RMS(x)
    at the end:   RMS, then logits = x . Embed^T (tied, no bias)
    no positional encoding anywhere
    layer i is attention where i % attn_layer_period == attn_layer_offset, else Mamba

    Mamba:  [u, z] = Win h;  u = silu(conv_causal_depthwise(u) + b_conv)
            [dt_r, B, C] = Wx u;  dt_r = RMS_R(dt_r), B = RMS_N(B), C = RMS_N(C)
            dt = softplus(Wdt dt_r + b_dt);  A = -exp(A_log)
            per position  s = exp(dt * A) * s + (dt * u) (x) B;  y = s . C + D * u
            out = Wout (y * silu(z))
    attention:  q = Wq h [H x d], k = Wk h, v = Wv h [KV x d]; query head j
            reads key-value head j // (H / KV); causal
            softmax(q k^T / sqrt(d)) v; out = Wo; no rotation, no bias, no gate

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernels, no cache, one full
pass over the whole context, the recurrence a plain loop over the
positions from a zero state. It calls nothing of ``ray_tpu``; it shares
only the layout of the parameter tree (``ray_tpu/models/jamba.py``'s
docstring: ``mamba`` and ``attn`` hold the layers of each kind stacked,
in the stack's order), from whose shapes it reads ``N``, ``R`` and the
convolution's length. ``model`` is the configuration file's dictionary
of Hugging Face numbers.

For size alone, and changing no value: the layers run as loops over the
stacked weights (a period's Mamba layers before its attention layer,
that layer, those after it); a long context's attention runs over blocks
of query positions; ``tail`` keeps only the last positions' logits.
Departures from the equations above: none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True) + eps) \
        * scale


def mlp(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def mamba_and_state(w, h, eps):
    """h [B, L, E] -> ([B, L, E], the state behind the last position
    [B, Di, N]), from a zero state."""
    length = h.shape[1]
    taps, rank, n = w["conv_w"].shape[0], w["dt_proj"].shape[0], \
        w["A_log"].shape[1]
    u, z = jnp.split(h @ w["in_proj"], 2, axis=-1)
    before = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(before[:, k:k + length] * w["conv_w"][k]
                        for k in range(taps)) + w["conv_b"])
    proj = u @ w["x_proj"]
    dt_r = rms(proj[..., :rank], w["dt_norm"], eps)
    b = rms(proj[..., rank:rank + n], w["b_norm"], eps)
    c = rms(proj[..., rank + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(dt_r @ w["dt_proj"] + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def position(s, at):
        dt_t, u_t, b_t, c_t = at                        # [B, Di], .., [B, N]
        s = jnp.exp(dt_t[..., None] * a) * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s0 = jnp.zeros((h.shape[0], *a.shape), F32)
    s, y = lax.scan(position, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (dt, u, b, c)))
    y = jnp.moveaxis(y, 0, 1) + w["D"] * u
    return (y * jax.nn.silu(z)) @ w["out_proj"], s


def mamba(w, h, eps):
    return mamba_and_state(w, h, eps)[0]


def attention(w, h):
    """h [B, L, E] -> [B, L, E]: causal softmax attention of grouped
    queries over the same positions."""
    q = jnp.einsum("ble,ehd->blhd", h, w["wq"])
    k = jnp.einsum("ble,ekd->blkd", h, w["wk"])
    v = jnp.einsum("ble,ekd->blkd", h, w["wv"])
    length, heads, d = q.shape[1:]
    reps = heads // k.shape[2]
    k, v = jnp.repeat(k, reps, axis=2), jnp.repeat(v, reps, axis=2)

    def rows(start, q):
        i = start + jnp.arange(q.shape[1])[:, None]
        mask = jnp.arange(length)[None, :] <= i
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(1.0 * d)
        weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    if length > 2 * QUERY_BLOCK and length % QUERY_BLOCK == 0:
        out = lax.map(
            lambda i: rows(i * QUERY_BLOCK, lax.dynamic_slice_in_dim(
                q, i * QUERY_BLOCK, QUERY_BLOCK, axis=1)),
            jnp.arange(length // QUERY_BLOCK))          # [blocks, B, Q, ..]
        out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    else:
        out = rows(0, q)
    return jnp.einsum("blhd,hde->ble", out, w["wo"])


def first_state(params, tokens, model):
    """tokens [B, L] -> the FIRST layer's state behind the last position,
    [B, Di, N] float32 (layer 0 is a Mamba layer wherever
    ``attn_layer_offset > 0``): what a cache's ``ssm[0]`` is held to,
    with nothing but the embedding and one norm before it."""
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda t: t[0].astype(F32), params["mamba"])
        x = params["embed"]["tokens"].astype(F32)[tokens]
        return mamba_and_state(w["mixer"], rms(x, w["mixer_norm"], eps),
                               eps)[1]


def forward(params, tokens, model, tail=None):
    """tokens [B, L] -> logits [B, L, V] float32; with ``tail`` those of
    the last ``tail`` positions alone, [B, tail, V]."""
    eps = model["rms_norm_eps"]
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    after = period - offset - 1

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(F32), tree)

    def block(x, w, mix):
        x = x + mix(w["mixer"], rms(x, w["mixer_norm"], eps))
        return x + mlp(rms(x, w["ffn_norm"], eps), w["ffn"])

    def mamba_layers(x, first, count):
        def one(i, x):
            w = f32(jax.tree.map(lambda t: t[first + i], params["mamba"]))
            return block(x, w, lambda w, h: mamba(w, h, eps))

        return lax.fori_loop(0, count, one, x)

    with jax.default_matmul_precision("highest"):
        table = params["embed"]["tokens"].astype(F32)
        x = table[tokens]
        periods = jax.tree.leaves(params["attn"])[0].shape[0]

        def one_period(x, p):
            x = mamba_layers(x, p * (period - 1), offset)
            w = f32(jax.tree.map(lambda t: t[p], params["attn"]))
            x = block(x, w, attention)
            return mamba_layers(x, p * (period - 1) + offset, after), None

        x, _ = lax.scan(one_period, x, jnp.arange(periods))
        if tail is not None:
            x = x[:, -tail:]
        return rms(x, f32(params["final_norm"]), eps) @ table.T
