"""Plain reference of the decoder Kimi-Linear-48B-A3B-Instruct publishes
(``model_type`` ``kimi_linear``, arXiv:2510.26692), written from its
configuration's keys and the paper's equations; there is no network
here, and where a key does not settle a detail the choice is listed
below.

``C = hidden_size``. Block, pre-norm, one residual stream: ``x +=
mixer(rms(x))``, ``x += ffn(rms(x))``; a final RMSNorm and an untied
head.

A **KDA** layer (Kimi Delta Attention), ``H`` heads of ``d``
(``linear_attn_num_heads`` of ``linear_attn_head_dim``):

    [q~ | k~ | v~] = h W_qkv; each channel through a causal convolution
        over time of ``linear_attn_short_conv_kernel_size`` taps (its
        own weights, no bias), then SiLU
    q = q~ / |q~| * d^-1/2,  k = k~ / |k~|,  v = v~            a head
    g = -exp(A_log_h) * softplus(h W_fa W_fb + dt_bias)        a head
        AND channel
    beta = sigmoid(h W_beta)                                   a head
    TOKEN BY TOKEN, S [d, d] a head, zero before the first token:
        S' = diag(exp(g_t)) S;   S = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S^T q_t
    y = [RMSNorm_d(o_t; w) * sigmoid(h W_ga W_gb)] W_o

A **latent** layer (multi-head latent attention WITHOUT positions,
``mla_use_nope``; ``q_lora_rank`` null), always EXPANDED here, over the
full causal sequence:

    [q_n | q_r] = h W_q  per head;  [c | k_r] = h W_kva;  c <- RMSNorm(c)
    k = [c W_uk | k_r],  v = c W_uv            W_kvb = [W_uk | W_uv]
    o = softmax(q k^T (nope + rope)^-1/2) v,   then o W_o;   nothing rotated

Feed-forward: a layer with ``w_router`` routes

    s = sigmoid(h W_r)                       float32, over ALL the
                                             router's outputs (256)
    the num_experts_per_token largest of s + bias (one group)
    w = s of the chosen / their sum (moe_renormalize) * routed_scaling_factor
    y = sum over the chosen experts THAT ARE HELD of w_k E_k(h) + E_shared(h)

with the held experts those numbered ``first_expert_held ..
first_expert_held + (the experts in the tree) - 1``; the other layers a
SwiGLU of ``intermediate_size``.

Choices the keys do not settle (the configuration's file lists them
under ``assumed``; flash-linear-attention's ``KimiDeltaAttention``, from
memory): SiLU after the convolution and l2norm after SiLU; ``d^-1/2`` on
the query; no bias on the convolutions nor on the low-rank pairs; the
gated head norm's gate is a sigmoid. **Departures:** (1) this chip's
SHARE of the experts: what the experts held elsewhere would add is left
out, here as in the engine, and that partial sum goes on to the next
layer (model-configs guide, section 4); (2) logits over the share of
the vocabulary the tree holds.

Straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no chunkwise form, no cache,
no kernels. The recurrence is a ``lax.scan`` over positions with the
state as carry; the periods, and the experts of a layer, are loops
(``lax.scan``: one expert's float32 copy at a time). Two things serve
memory only and change no number: a context longer than ``QUERY_BLOCK``
has its queries attended a block at a time, and ``tail`` computes the
head for the last positions alone.

It imports nothing of ``ray_tpu`` and shares only the layout of the
parameter tree, from which it also reads each layer's kind (a mixer
with ``w_qkv`` is KDA, one with ``wq`` latent; a feed-forward with
``w_router`` is sparse): ``embed.tokens [V, C]``, ``final_norm [C]``,
``lm_head [C, V]``, ``first`` a list of the leading layers and
``periods`` a list of the layers of one period, each stacked on a first
axis over the periods. A layer is ``mixer_norm``, ``mixer``,
``ffn_norm``, ``ffn``; a KDA mixer ``w_qkv [C, 3Hd]``, ``conv_w [taps,
3Hd]`` (tap ``i`` weighs the input ``taps - 1 - i`` positions back),
``a_log [H]``, ``dt_bias [Hd]``, ``f_a [C, d]``, ``f_b [d, Hd]``,
``g_a``, ``g_b``, ``w_beta [C, H]``, ``o_norm [d]``, ``wo [Hd, C]``; a
latent mixer ``wq [C, H, nope + rope]``, ``wkv_a [C, rank + rope]``,
``kv_norm``, ``wkv_b [rank, H, nope + v]``, ``wo [H, v, C]``; a
feed-forward ``w_gate, w_up [C, M]``, ``w_down [M, C]`` (dense) or
``w_router [C, E]``, ``router_bias [E]``, ``w_gate, w_up [held, C,
m]``, ``w_down [held, m, C]``, ``shared_gate, shared_up [C, m]``,
``shared_down [m, C]``. ``model`` is the configuration file's
dictionary of numbers under their Hugging Face keys; the
``linear_attn_config`` group's numbers are read from the file's flat
copies ``linear_attn_<key>`` (the harness hands over top-level numbers
only).
"""

from __future__ import annotations

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


# ----------------------------------------------------------------- KDA


def short_convolution(x, taps):
    """x [B, L, D], taps [k, D] -> [B, L, D]: channel by channel,
    ``sum_i taps[i] x[t - (k - 1 - i)]``, zeros before the first token."""
    k, length = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + length] * taps[i].astype(F32)
               for i in range(k))


def delta_rule(q, k, v, g, beta):
    """q, k, v, g [B, L, H, d], beta [B, L, H] -> (o [B, L, H, d], the
    state after the last token [B, H, d, d]), one token at a time."""
    def token(state, inputs):
        q, k, v, g, beta = inputs                           # [B, H, ...]
        state = jnp.exp(g)[..., :, None] * state            # S'
        predicted = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k, beta[..., None] * (v - predicted))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    batch, _, heads, d = q.shape
    state, o = lax.scan(
        token, jnp.zeros((batch, heads, d, d), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda(h, w, model):
    """h [B, L, C] (normed) -> ([B, L, C], the final state)."""
    heads, d = model["linear_attn_num_heads"], model["linear_attn_head_dim"]
    lead = h.shape[:2]
    qkv = jax.nn.silu(short_convolution(h @ w["w_qkv"].astype(F32),
                                        w["conv_w"]))
    q, k, v = (x.reshape(*lead, heads, d) for x in jnp.split(qkv, 3, -1))
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = (h @ w["f_a"].astype(F32)) @ w["f_b"].astype(F32)
    g = -jnp.exp(w["a_log"].astype(F32))[:, None] * jax.nn.softplus(
        (f + w["dt_bias"].astype(F32)).reshape(*lead, heads, d))
    beta = jax.nn.sigmoid(h @ w["w_beta"].astype(F32))
    o, state = delta_rule(q, k, v, g, beta)
    gate = (h @ w["g_a"].astype(F32)) @ w["g_b"].astype(F32)
    o = rms_norm(o, w["o_norm"], model["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate).reshape(o.shape)
    return o.reshape(*lead, heads * d) @ w["wo"].astype(F32), state


# ----------------------------------------------------------------- latent


def attention(h, w, model):
    """h [B, L, C] (normed) -> [B, L, C]: expanded, causal, all of the
    sequence, nothing rotated."""
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank = model["kv_lora_rank"]
    length = h.shape[1]
    positions = jnp.arange(length)
    q = jnp.einsum("blc,chd->blhd", h, w["wq"].astype(F32))
    kv = h @ w["wkv_a"].astype(F32)
    c_kv = rms_norm(kv[..., :rank], w["kv_norm"], model["rms_norm_eps"])
    k_rope = kv[..., rank:]                                  # one for all
    w_kvb = w["wkv_b"].astype(F32)
    heads = w_kvb.shape[1]
    k = jnp.concatenate([
        jnp.einsum("blc,chd->blhd", c_kv, w_kvb[..., :nope]),
        jnp.broadcast_to(k_rope[:, :, None, :],
                         (*k_rope.shape[:2], heads, rope))], -1)
    v = jnp.einsum("blc,chd->blhd", c_kv, w_kvb[..., nope:])
    out = []
    for start in range(0, length, QUERY_BLOCK):
        end = min(start + QUERY_BLOCK, length)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end],
                            k[:, :end]) * (nope + rope) ** -0.5
        causal = jnp.arange(end)[None, :] <= positions[start:end, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, -1), v[:, :end]))
    return jnp.einsum("blhd,hdc->blc", jnp.concatenate(out, axis=1),
                      w["wo"].astype(F32))


# -------------------------------------------------------------- feed-forward


def route(m, w, model):
    """m [B, L, C] -> (indices [B, L, k] among ALL the router's experts,
    weights [B, L, k])."""
    s = jax.nn.sigmoid(m @ w["w_router"].astype(F32))
    _, idx = lax.top_k(s + w["router_bias"].astype(F32),
                       model["num_experts_per_token"])
    weights = jnp.take_along_axis(s, idx, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return idx, weights * model["routed_scaling_factor"]


def experts(m, w, idx, weights, first_held):
    """sum over a token's chosen experts that are HELD of weight *
    expert(m), and the shared expert once. A loop over the held experts,
    the ``i``-th of which is expert ``first_held + i`` of the router's."""
    def one_expert(total, expert):
        e, w_gate, w_up, w_down = expert
        out = swiglu(m, w_gate, w_up, w_down)
        chose = idx == e                                        # [B, L, k]
        weight = jnp.sum(jnp.where(chose, weights, 0.0), axis=-1)
        return total + jnp.where(jnp.any(chose, axis=-1)[..., None],
                                 weight[..., None] * out, 0.0), None

    held = w["w_gate"].shape[0]
    total, _ = lax.scan(one_expert, jnp.zeros_like(m),
                        (first_held + jnp.arange(held), w["w_gate"],
                         w["w_up"], w["w_down"]))
    if "shared_gate" in w:
        total = total + swiglu(m, w["shared_gate"], w["shared_up"],
                               w["shared_down"])
    return total


def layer(x, w, model):
    """One decoder layer. x [B, L, C] -> (x, the chosen experts [B, L,
    k] sorted or None, the KDA state [B, H, d, d] or None)."""
    eps = model["rms_norm_eps"]
    h = rms_norm(x, w["mixer_norm"], eps)
    if "w_qkv" in w["mixer"]:
        y, state = kda(h, w["mixer"], model)
    else:
        y, state = attention(h, w["mixer"], model), None
    x = x + y
    m, ffn = rms_norm(x, w["ffn_norm"], eps), w["ffn"]
    if "w_router" not in ffn:
        return x + swiglu(m, ffn["w_gate"], ffn["w_up"], ffn["w_down"]), \
            None, state
    idx, weights = route(m, ffn, model)
    y = experts(m, ffn, idx, weights, int(model.get("first_expert_held", 0)))
    return x + y, jnp.sort(idx, axis=-1), state


def forward(params, tokens, model, with_routing: bool = False,
            tail: "int | None" = None, with_states: bool = False):
    """tokens [B, L] -> logits [B, L, V] float32 (of the last ``tail``
    positions alone if given). With ``with_routing`` also the chosen
    experts [periods, layers of a period, B, L, k]; with ``with_states``
    also the KDA states after the last token, a list in the order of
    the KDA layers, each [B, H, d, d]."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        states = []
        for w in params["first"]:
            x, _, state = layer(x, w, model)
            states += [state] if state is not None else []

        def period(x, layers):
            chosen, after = [], []
            for w in layers:
                x, idx, state = layer(x, w, model)
                chosen.append(idx)
                after += [state] if state is not None else []
            return x, (jnp.stack(chosen), after)

        x, (routing, after) = lax.scan(period, x, params["periods"])
        # [periods, ...] a KDA layer of the period -> the stack's order.
        states += [layer_states[p] for p in range(routing.shape[0])
                   for layer_states in after]
        if tail is not None:
            x = x[:, -tail:]
        x = rms_norm(x, params["final_norm"], model["rms_norm_eps"])
        logits = x @ params["lm_head"].astype(F32)
    out = (logits,)
    if with_routing:
        out += (routing,)
    if with_states:
        out += (states,)
    return out if len(out) > 1 else logits
