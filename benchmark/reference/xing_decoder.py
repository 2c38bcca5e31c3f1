"""Plain reference of the decoder Xing4.0-29B-A4B publishes
(``model_type`` ``xing4_0``), written from its configuration's keys and
the two papers they name; there is no network here, and where a key
does not settle a detail the choice is listed below.

``C = hidden_size``, ``n = hc_mult``. A token's residual state is ``X``
``[n, C]``: the embedding copied into all ``n`` streams; after the last
layer the streams are summed, then a final RMSNorm and an untied head.

A sublayer ``F`` under hyper-connections (arXiv:2409.19606) with the
manifold constraint of mHC (arXiv:2512.24880):

    x~ = RMSNorm_{nC}(vec(X))                         one scale [nC]
    H~pre  = a_pre  * (x~ phi_pre)  + b_pre           [n]
    H~post = a_post * (x~ phi_post) + b_post          [n]
    H~res  = a_res  * mat(x~ phi_res) + b_res         [n, n]
    H_pre = sigmoid(H~pre),  H_post = 2 sigmoid(H~post)
    H_res = Sinkhorn(clip(H~res, mhc_h_res_clamp_min, .._max)):
            M = exp(.), then hc_sinkhorn_iters times
            M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    h = H_pre X;   y = F(RMSNorm_C(h));   X' = H_res X + H_post^T y

Attention (multi-head latent attention, DeepSeek-V3's keys), always
EXPANDED here, over the full causal sequence:

    c_q = RMSNorm(h W_qa);  [q_nope | q_rope] = c_q W_qb  per head
    [c_kv | k_rope] = h W_kva;  c_kv <- RMSNorm(c_kv)
    rotary (YaRN) on q_rope and on the ONE k_rope all heads share
    k = [c_kv W_uk | k_rope],  v = c_kv W_uv    W_kvb = [W_uk | W_uv]
    o = softmax(q k^T * scale) v,   then o W_o;  no bias
    scale = (nope + rope)^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2

Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU of
``intermediate_size``; the others

    s = sigmoid(h W_r)                           float32, all experts
    the num_experts_per_tok largest of s + bias  (n_group 1: no groups)
    w = s of the chosen, / their sum (norm_topk_prob), * routed_scaling_factor
    y = sum_k w_k E_k(h) + E_shared(h)           each a SwiGLU of
                                                 moe_intermediate_size

Every token is served by all of its experts: nothing dropped.

Choices the keys do not settle (the configuration's file lists them
under ``assumed``): the embedding's copy into the streams and the
read-out by sum, the factor 2 on ``H_post``, where the clamp and
``hc_eps`` enter (the papers'); the softmax scale and YaRN's ramp
(DeepSeek-V3's published modelling code, from memory); the rotary
embedding pairs value ``i`` with ``i + d/2`` (halves), a permutation of
random columns against the interleaved pairing. **Departure:** the
multi-token-prediction module (``num_nextn_predict_layers``) is left
out; it adds nothing to the next token's logits.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. The layers of each stack and the experts of a layer
are loops (``lax.scan``, as ``olmoe_decoder.py``'s: written as Python
loops the 325 expert bodies of this configuration took the chip's
compiler 400 s and kept whole layers' copies alive): expert ``e``'s
float32 copy is made, applied to every token, kept where the token
chose it (``where`` on the chosen set) and dropped, so that it fits
beside the served weights on the chip. Two
things serve memory only and change no number: a context longer than
``QUERY_BLOCK`` has its queries attended a block at a time (against the
keys up to the block's end), and ``tail`` computes the head for the
last positions alone.

It imports nothing of ``ray_tpu`` and shares only the layout of the
parameter tree: ``embed.tokens [V, C]``, ``final_norm [C]``, ``lm_head
[C, V]``, and ``dense`` / ``sparse``, the leading and the expert layers
each stacked on a first axis, with ``hc_attn`` and ``hc_ffn`` (``norm
[nC]``, ``phi [2n + n*n, nC]`` whose rows are pre, post, then res row
by row, ``a [3]``, ``b [2n + n*n]``), ``attn_norm``, ``wq_a [C, rq]``,
``q_norm``, ``wq_b [rq, H, nope + rope]``, ``wkv_a [C, rkv + rope]``,
``kv_norm``, ``wkv_b [rkv, H, nope + v]``, ``wo [H, v, C]``,
``mlp_norm``, and ``w_gate, w_up [C, M]``, ``w_down [M, C]`` (dense) or
``w_router [C, E]``, ``router_bias [E]``, ``w_gate, w_up [E, C, m]``,
``w_down [E, m, C]``, ``shared_gate, shared_up [C, m]``, ``shared_down
[m, C]`` (sparse). ``model`` is the configuration file's dictionary of
numbers under their Hugging Face keys; the ``rope_scaling`` group's
numbers are read from the file's flat copies ``rope_scaling_<key>``
(the harness hands over top-level numbers only).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate.astype(F32)) * (m @ w_up.astype(F32))) \
        @ w_down.astype(F32)


# ------------------------------------------------------------ residual path


def sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def mix(streams, w, model):
    """streams [B, L, n, C] -> (H_pre [B, L, n], H_post [B, L, n],
    H_res [B, L, n, n]) of one sublayer."""
    n = model["hc_mult"]
    flat = streams.reshape(*streams.shape[:2], -1)
    x = rms_norm(flat, w["norm"], model["rms_norm_eps"])
    raw = x @ w["phi"].astype(F32).T                        # [B, L, 2n + n*n]
    a, b = w["a"].astype(F32), w["b"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * raw[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * raw[..., 2 * n:] + b[2 * n:]).reshape(
        *raw.shape[:2], n, n)
    res = jnp.clip(res, model["mhc_h_res_clamp_min"],
                   model["mhc_h_res_clamp_max"])
    return pre, post, sinkhorn(res, model["hc_sinkhorn_iters"],
                               model["hc_eps"])


def sublayer(streams, w_mix, norm_scale, model, f):
    """``X' = H_res X + H_post^T F(RMSNorm(H_pre X))``; ``f`` may return
    (y, something to pass on)."""
    pre, post, res = mix(streams, w_mix, model)
    h = jnp.einsum("bln,blnc->blc", pre, streams)
    y, kept = f(rms_norm(h, norm_scale, model["rms_norm_eps"]))
    return jnp.einsum("blij,bljc->blic", res, streams) \
        + post[..., :, None] * y[..., None, :], kept


# ----------------------------------------------------------------- attention


def attention_factor(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(model):
    """[rope / 2]: a pair that turns more than beta_fast times in the
    original context keeps theta^(-2i/d); one that turns fewer than
    beta_slow times has it divided by factor; a linear ramp between."""
    d, base = model["qk_rope_head_dim"], model["rope_theta"]
    original = model["rope_scaling_original_max_position_embeddings"]

    def pair(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(model["rope_scaling_beta_fast"])), 0)
    high = min(math.ceil(pair(model["rope_scaling_beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=F32)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = base ** (-i / (d // 2))
    return plain / model["rope_scaling_factor"] * ramp + plain * (1 - ramp)


def rotate(x, positions, model):
    """x [B, L, ..., d] at positions [L]: the pair (x[i], x[i + d/2])
    turned by position * frequency_i."""
    factor = model["rope_scaling_factor"]
    ratio = attention_factor(factor, model["rope_scaling_mscale"]) \
        / attention_factor(factor, model["rope_scaling_mscale_all_dim"])
    angles = positions.astype(F32)[:, None] * yarn_frequencies(model)
    angles = angles.reshape(angles.shape[0], *(1,) * (x.ndim - 3), -1)
    cos, sin = jnp.cos(angles) * ratio, jnp.sin(angles) * ratio
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, model):
    """h [B, L, C] (normed) -> [B, L, C]: expanded, causal, all of the
    sequence."""
    eps = model["rms_norm_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank = model["kv_lora_rank"]
    length = h.shape[1]
    positions = jnp.arange(length)
    c_q = rms_norm(h @ w["wq_a"].astype(F32), w["q_norm"], eps)
    q = jnp.einsum("blr,rhd->blhd", c_q, w["wq_b"].astype(F32))
    q = jnp.concatenate([q[..., :nope],
                         rotate(q[..., nope:], positions, model)], -1)
    kv = h @ w["wkv_a"].astype(F32)
    c_kv = rms_norm(kv[..., :rank], w["kv_norm"], eps)
    k_rope = rotate(kv[..., rank:], positions, model)       # one for all
    w_kvb = w["wkv_b"].astype(F32)
    heads = w_kvb.shape[1]
    k = jnp.concatenate([
        jnp.einsum("blc,chd->blhd", c_kv, w_kvb[..., :nope]),
        jnp.broadcast_to(k_rope[:, :, None, :],
                         (*k_rope.shape[:2], heads, rope))], -1)
    v = jnp.einsum("blc,chd->blhd", c_kv, w_kvb[..., nope:])
    scale = (nope + rope) ** -0.5 * attention_factor(
        model["rope_scaling_factor"],
        model["rope_scaling_mscale_all_dim"]) ** 2
    out = []
    for start in range(0, length, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, length)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end],
                            k[:, :end]) * scale
        causal = jnp.arange(end)[None, :] <= positions[start:end, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, -1), v[:, :end]))
    return jnp.einsum("blhd,hdc->blc", jnp.concatenate(out, axis=1),
                      w["wo"].astype(F32))


# -------------------------------------------------------------- feed-forward


def route(m, w, model):
    """m [B, L, C] -> (indices [B, L, k], weights [B, L, k])."""
    s = jax.nn.sigmoid(m @ w["w_router"].astype(F32))
    _, idx = lax.top_k(s + w["router_bias"].astype(F32),
                       model["num_experts_per_tok"])
    weights = jnp.take_along_axis(s, idx, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return idx, weights * model["routed_scaling_factor"]


def experts(m, w, idx, weights):
    """sum over a token's chosen experts of weight * expert(m), and the
    shared expert once. A loop over the experts (``lax.scan``: one
    expert's float32 copy at a time)."""
    def one_expert(total, expert):
        e, w_gate, w_up, w_down = expert
        out = swiglu(m, w_gate, w_up, w_down)
        chose = idx == e                                        # [B, L, k]
        weight = jnp.sum(jnp.where(chose, weights, 0.0), axis=-1)
        return total + jnp.where(jnp.any(chose, axis=-1)[..., None],
                                 weight[..., None] * out, 0.0), None

    total, _ = lax.scan(one_expert, jnp.zeros_like(m),
                        (jnp.arange(w["w_gate"].shape[0]), w["w_gate"],
                         w["w_up"], w["w_down"]))
    if "shared_gate" in w:
        total = total + swiglu(m, w["shared_gate"], w["shared_up"],
                               w["shared_down"])
    return total


def layer(streams, w, model):
    """One decoder layer. streams [B, L, n, C] -> (streams, the chosen
    experts [B, L, k] sorted, or None for a dense layer)."""
    streams, _ = sublayer(streams, w["hc_attn"], w["attn_norm"], model,
                          lambda h: (attention(h, w, model), None))

    def feed_forward(m):
        if "w_router" not in w:
            return swiglu(m, w["w_gate"], w["w_up"], w["w_down"]), None
        idx, weights = route(m, w, model)
        return experts(m, w, idx, weights), jnp.sort(idx, axis=-1)

    return sublayer(streams, w["hc_ffn"], w["mlp_norm"], model, feed_forward)


def forward(params, tokens, model, with_routing: bool = False,
            tail: "int | None" = None):
    """tokens [B, L] -> logits [B, L, V] float32 (of the last ``tail``
    positions alone if given; and, with ``with_routing``, the chosen
    experts [expert layers, B, L, k])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        streams = jnp.broadcast_to(
            x[:, :, None, :], (*x.shape[:2], model["hc_mult"], x.shape[-1]))
        routing = None
        for group in ("dense", "sparse"):   # each a loop over its layers
            if group in params:
                streams, routing = lax.scan(
                    lambda streams, w: layer(streams, w, model), streams,
                    params[group])
        x = jnp.sum(streams, axis=2)
        if tail is not None:
            x = x[:, -tail:]
        x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
        logits = x @ params["lm_head"].astype(F32)
    return (logits, routing) if with_routing else logits
