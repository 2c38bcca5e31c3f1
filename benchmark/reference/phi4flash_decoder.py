"""Plain reference of the decoder-hybrid-decoder Phi-4-mini-flash-reasoning
publishes (``model_type`` ``phi4flash``; arXiv:2507.06607 "SambaY" and the
published ``modeling_phi4flash.py``, written from memory: there is no
network here). ``E`` hidden size, ``d = E / heads`` (64), ``Di = 2 E``,
``N`` 16, ``R = ceil(E / 16)``, ``n`` layers, ``half = n / 2``.

    every layer l:  x = x + Mixer_l(LN(x));  x = x + MLP(LN(x))
    LN: LayerNorm with scale and bias;  MLP(u) = W2 (silu(g) * y), [g, y] = W1 u
    at the end: LN, then logits = x . Embed^T (tied, no bias)
    no positional encoding anywhere

    l <  half, even: state-space           l <  half, odd: window attention
    l == half:       state-space, gives M  l == half + 1:  full attention
    l >= half + 2, even: gated memory unit; odd: cross-attention

    state-space (Mamba-1): [u, z] = Win h;  u = silu(conv_causal_depthwise_4(u) + b)
        [dt_r, B, C] = Wx u;  dt = softplus(Wdt dt_r + b_dt);  A = -exp(A_log)
        per position  s = exp(dt * A) * s + (dt * u) (x) B;  y = s . C + D * u
        out = Wout (y * silu(z));  layer half's memory is M = y, before the gate
    differential attention: [q, k, v] = Wqkv h + b; q is 20 pairs (q1, q2) of
        heads, k 10 pairs (k1, k2), v 10 pairs concatenated to vv of 2 d; query
        pair p reads key-value pair p // 2
        A1 = softmax(q1 k1^T / sqrt(d) + mask), A2 likewise; o = (A1 - lam A2) vv
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,  lam_init = 0.8 - 0.6 exp(-0.3 l)
        o = RMSNorm_2d(o) * (1 - lam_init);  out = Wo concat(o) + b
        mask: causal; a window layer also i - j < sliding_window
    cross-attention: q = Wq h + b, the same attention with its own lam, norm
        and Wo over layer half + 1's keys and values; no key or value of its own
    gated memory unit: out = W2 (silu(W1 h) * M), M of the same position

Straightforward ``jax.numpy`` in float32 with
``default_matmul_precision("highest")``: no kernels, no cache, one full
pass over the whole context. It calls nothing of ``ray_tpu``; it shares
only the layout of the parameter tree (``ray_tpu/models/phi4flash.py``'s
docstring: ``front`` and ``back`` hold the periods stacked, a period's
two blocks as ``block_a`` and ``block_b``), from whose shapes it reads ``N``,
``R`` and the convolution's length. ``model`` is the configuration
file's dictionary of Hugging Face numbers.

For size alone, and changing no value: the layers run as scans over the
stacked periods; a long context's attention runs over blocks of query
positions; the head is computed in blocks of the vocabulary into one
result; ``tail`` keeps only the last positions' logits. Departures from
the equations above: none.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512


def layer_norm(x, w, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w["scale"] + w["bias"]


def mlp(u, w):
    gate, up = jnp.split(u @ w["w1"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["w2"]


def state_space(w, h):
    """h [B, L, E] -> (out [B, L, E], memory [B, L, Di])."""
    length = h.shape[1]
    taps, (rank, n) = w["conv_w"].shape[0], \
        (w["dt_proj"].shape[0], w["A_log"].shape[1])
    u, z = jnp.split(h @ w["in_proj"], 2, axis=-1)
    before = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(before[:, k:k + length] * w["conv_w"][k]
                        for k in range(taps)) + w["conv_b"])
    proj = u @ w["x_proj"]
    dt_r, b, c = proj[..., :rank], proj[..., rank:rank + n], \
        proj[..., rank + n:]
    dt = jax.nn.softplus(dt_r @ w["dt_proj"] + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def position(s, at):
        dt_t, u_t, b_t, c_t = at                        # [B, Di], .., [B, N]
        s = jnp.exp(dt_t[..., None] * a) * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s0 = jnp.zeros((h.shape[0], *a.shape), F32)
    _, y = lax.scan(position, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (dt, u, b, c)))
    y = jnp.moveaxis(y, 0, 1) + w["D"] * u
    return (y * jax.nn.silu(z)) @ w["out_proj"], y


def differential_attention(w, layer, q, k, v, window, eps):
    """q [B, L, H, d]; k, v [B, L, KV, d] of the same positions 0..L-1;
    ``window`` None: causal alone."""
    length, d = q.shape[1], q.shape[-1]
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]               # [B, L, H/2, d]
    k1, k2 = k[:, :, 0::2], k[:, :, 1::2]               # [B, L, KV/2, d]
    vv = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
    # Query pair p reads key-value pair p // 2.
    k1, k2, vv = (jnp.repeat(t, 2, axis=2) for t in (k1, k2, vv))
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init

    def rows(start, q1, q2):
        i = start + jnp.arange(q1.shape[1])[:, None]
        j = jnp.arange(length)[None, :]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)

        def probabilities(q, k):
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(1.0 * d)
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)

        weights = probabilities(q1, k1) - lam * probabilities(q2, k2)
        return jnp.einsum("bhqk,bkhf->bqhf", weights, vv)

    if length > 2 * QUERY_BLOCK and length % QUERY_BLOCK == 0:
        blocks = length // QUERY_BLOCK
        out = lax.map(
            lambda i: rows(i * QUERY_BLOCK,
                           *(lax.dynamic_slice_in_dim(t, i * QUERY_BLOCK,
                                                      QUERY_BLOCK, axis=1)
                             for t in (q1, q2))),
            jnp.arange(blocks))                         # [blocks, B, Q, ..]
        out = jnp.moveaxis(out, 0, 1).reshape(q.shape[0], length,
                                              *out.shape[3:])
    else:
        out = rows(0, q1, q2)
    out = out / jnp.sqrt(jnp.mean(out ** 2, axis=-1, keepdims=True) + eps) \
        * w["subln"] * (1.0 - lam_init)
    return out.reshape(*out.shape[:2], -1) @ w["wo"] + w["bo"]


def split_heads(x, heads):
    return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)


def forward(params, tokens, model, tail=None):
    """tokens [B, L] -> logits [B, L, V] float32; with ``tail`` those of
    the last ``tail`` positions alone, [B, tail, V]."""
    eps, window = model["layer_norm_eps"], model["sliding_window"]
    heads, kv_heads = model["num_attention_heads"], \
        model["num_key_value_heads"]
    hidden, half = model["hidden_size"], model["num_hidden_layers"] // 2
    kv_width = hidden // heads * kv_heads

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(F32), tree)

    def block(x, w, mix):
        x = x + mix(layer_norm(x, w["ln1"], eps))
        return x + mlp(layer_norm(x, w["ln2"], eps), w)

    def attention(w, layer, h, window):
        qkv = h @ w["wqkv"] + w["bqkv"]
        q, k, v = (qkv[..., :hidden], qkv[..., hidden:hidden + kv_width],
                   qkv[..., hidden + kv_width:])
        k, v = split_heads(k, kv_heads), split_heads(v, kv_heads)
        out = differential_attention(w, layer, split_heads(q, heads), k, v,
                                     window, eps)
        return out, (k, v)

    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)

        def front(x, period):
            w, p = f32(period[0]), period[1]
            x = block(x, w["block_a"],
                      lambda h: state_space(w["ssm"], h)[0])
            x = block(x, w["block_b"],
                      lambda h: attention(w["attn"], 2.0 * p + 1, h,
                                          window)[0])
            return x, None

        periods = jax.tree.leaves(params["front"])[0].shape[0]
        x, _ = lax.scan(front, x, (params["front"], jnp.arange(periods)))

        kept = {}

        def memory_layer(h):
            out, kept["memory"] = state_space(f32(params["mid_ssm"]["ssm"]),
                                              h)
            return out

        def full_layer(h):
            out, kept["kv"] = attention(f32(params["mid_attn"]["attn"]),
                                        half + 1.0, h, None)
            return out

        x = block(x, f32(params["mid_ssm"]["block"]), memory_layer)
        x = block(x, f32(params["mid_attn"]["block"]), full_layer)
        memory, (keys, values) = kept["memory"], kept["kv"]

        def back(x, period):
            w, p = f32(period[0]), period[1]

            def cross(h):
                q = split_heads(h @ w["cross"]["wq"] + w["cross"]["bq"],
                                heads)
                return differential_attention(
                    w["cross"], half + 3.0 + 2 * p, q, keys, values, None,
                    eps)

            x = block(x, w["block_a"],
                      lambda h: (jax.nn.silu(h @ w["gmu"]["w1"]) * memory)
                      @ w["gmu"]["w2"])
            return block(x, w["block_b"], cross), None

        periods = jax.tree.leaves(params["back"])[0].shape[0]
        x, _ = lax.scan(back, x, (params["back"], jnp.arange(periods)))
        if tail is not None:
            x = x[:, -tail:]
        x = layer_norm(x, f32(params["final_norm"]), eps)
        return head_in_blocks(x, params["embed"]["tokens"])


def head_in_blocks(x, table, most_blocks: int = 16):
    """``x . table^T`` [B, L, V] in float32, a block of the vocabulary at
    a time (the table is cast a block at a time, and each block's
    logits are written into the one result where they belong)."""
    vocabulary = table.shape[0]
    blocks = max(n for n in range(1, most_blocks + 1) if vocabulary % n == 0)
    rows = vocabulary // blocks

    def one_block(i, logits):
        part = lax.dynamic_slice_in_dim(table, i * rows, rows, axis=0)
        return lax.dynamic_update_slice_in_dim(
            logits, jnp.einsum("ble,ve->blv", x, part.astype(F32)),
            i * rows, axis=2)

    return lax.fori_loop(0, blocks, one_block,
                         jnp.zeros((*x.shape[:2], vocabulary), F32))
