"""Plain reference of the decoder Solar-Open2-250B publishes
(``model_type`` ``solar_open2``), written from its configuration's keys;
there is no network here, and what the keys leave open is listed below
and in the configuration file's ``assumed``.

``C = hidden_size``. Every layer: ``x = x + mixer(rms(x))``, then ``x = x
+ experts(rms(x))``; after the last layer an RMSNorm and an untied head.

A layer whose mixer has ``w_qkv`` is **Kimi Delta Attention** with
negative eigenvalues allowed, ``H = linear_attn_num_heads`` heads of ``d =
linear_attn_head_dim``:

    u = h W_qkv                      [L, 3 H d]
    c_t = sum_i taps[i] * u_(t - (K - 1 - i))     K taps a channel, zeros
                                                  before the first token
    [q~ | k~ | v~] = silu(c)
    q = q~ / |q~| / sqrt(d),  k = k~ / |k~|,  v = v~           a head
    g = -exp(A_log) * softplus(h W_fa W_fb + dt_bias)  <= 0    a head and
                                                               channel
    beta = kda_beta_scale * sigmoid(h W_beta)   in (0, 2)      a head
    for t = 0, 1, ...:   (S [d, d] a head, zero before the first token)
        S <- diag(exp(g_t)) S
        S <- S + beta_t k_t (v_t - S^T k_t)^T
        o_t = S^T q_t
    y = (rms_d(o; w) * sigmoid(h W_ga W_gb)) W_o

A layer whose mixer has ``wg`` is **gated softmax attention of grouped
queries without positions**: ``q = h W_q`` as ``num_attention_heads`` of
``head_dim``, ``k = h W_k`` and ``v = h W_v`` as ``num_key_value_heads``;
query head ``j * (heads / kv heads) + r`` reads key-value head ``j``;
``P = softmax(q k^T / sqrt(head_dim))`` over the positions at or before
the query's, ONE masked softmax over the whole sequence; ``y = ((P v) *
sigmoid(h W_g)) W_o`` with a gate value for every head and channel.

The feed-forward routes: ``s = sigmoid(m W_r)`` over ALL the router's
outputs (``n_routed_experts_routed_over``), the ``num_experts_per_tok``
largest of ``s + bias``, weights ``s`` of the chosen over their sum times
``routed_scaling_factor``; the output is the shared expert (a SwiGLU)
plus the weighted experts among the chosen THAT THE TREE HOLDS (experts
``first_expert_held ..``), each a SwiGLU.

**Hardwired of the published booleans** (the harness hands over numbers
only): ``use_rope`` false (nothing is rotated, no position enters
anywhere), ``use_gqa_gate`` true (a full mixer has ``wg``),
``norm_topk_prob`` true (the weights are renormalised),
``kda_use_full_proj`` false (the low-rank pairs); ``kda_allow_neg_eigval``
arrives as the number ``kda_beta_scale`` (2). **Chosen where no key
decides** (the file's ``assumed``): SiLU behind the convolution, l2norm
behind SiLU with 1e-6 under the root, ``1/sqrt(d)`` on the query, no bias
anywhere, no QK-norm in the full layers, the gate's sigmoid, a router
bias that selects and does not weigh. **Departures from the published
model**: (1) this chip's SHARE of the experts: what the experts held
elsewhere would add is left out, as in the engine, and the partial sum
goes on (model-configs guide, section 4); (2) logits over the share of the
vocabulary the tree holds.

``jax.numpy`` in float32 under ``default_matmul_precision("highest")``;
no chunkwise form, no cache, no kernel: the recurrence is a ``lax.scan``
over positions with the state its carry, the held experts a loop with
one expert's float32 copy alive at a time. For memory alone, and
changing no number: a sequence longer than ``ROWS_AT_ONCE`` has its
queries attended that many at a time, and ``tail`` runs the head on the
last positions only. It imports nothing of ``ray_tpu`` nor of another
reference here, and knows the parameter tree alone: ``embed.tokens [V,
C]``, ``final_norm``, ``lm_head [C, V]``, ``first`` (a list of leading
layers, empty as published) and ``periods`` (the layers of one period,
each array stacked over the periods); a layer ``mixer_norm``, ``mixer``,
``ffn_norm``, ``ffn``; a KDA mixer ``w_qkv [C, 3Hd]``, ``conv_w [K,
3Hd]`` (tap ``i`` weighs the input ``K - 1 - i`` back), ``a_log [H]``,
``dt_bias [Hd]``, ``f_a [C, d]``, ``f_b [d, Hd]``, ``g_a``, ``g_b``,
``w_beta [C, H]``, ``o_norm [d]``, ``wo [Hd, C]``; a full mixer ``wq [C,
heads, head_dim]``, ``wk``, ``wv [C, kv heads, head_dim]``, ``wg`` as
``wq``, ``wo [heads, head_dim, C]``; a feed-forward ``w_router [C, E]``,
``router_bias [E]``, ``w_gate``, ``w_up [held, C, m]``, ``w_down [held,
m, C]``, ``shared_gate``, ``shared_up [C, m]``, ``shared_down [m, C]``.
``model`` is the configuration file's top-level numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

ROWS_AT_ONCE = 1024


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * f32(scale)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)


def unit(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


# ------------------------------------------------------------------ KDA


def causal_taps(u, taps):
    """u [B, L, D], taps [K, D]: position t gets sum_i taps[i] u[t - (K -
    1 - i)], with zeros before the sequence."""
    kernel = taps.shape[0]
    out = jnp.zeros_like(u)
    for i in range(kernel):
        back = kernel - 1 - i
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
        out = out + shifted * f32(taps[i])
    return out


def delta_rule_by_token(q, k, v, g, beta):
    """q, k, v, g [B, L, H, d], beta [B, L, H]: the rule one position at
    a time from a zero state. Returns (o [B, L, H, d], the state after
    the last position [B, H, d, d], keys along the first d)."""
    def one(state, now):
        q_t, k_t, v_t, g_t, beta_t = now
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.sum(state * k_t[..., None], axis=-2)        # S^T k
        state = state + (beta_t[..., None, None] * k_t[..., None]
                         * (v_t - seen)[..., None, :])
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    batch, _, heads, d = q.shape
    by_time = [jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta)]
    state, o = lax.scan(one, jnp.zeros((batch, heads, d, d), jnp.float32),
                        tuple(by_time))
    return jnp.swapaxes(o, 0, 1), state


def kda_mixer(h, w, model):
    heads = int(model["linear_attn_num_heads"])
    d = int(model["linear_attn_head_dim"])
    batch, length, _ = h.shape
    mixed = jax.nn.silu(causal_taps(h @ f32(w["w_qkv"]), w["conv_w"]))
    q, k, v = (mixed[..., i * heads * d:(i + 1) * heads * d].reshape(
        batch, length, heads, d) for i in range(3))
    q, k = unit(q) / d ** 0.5, unit(k)
    rate = ((h @ f32(w["f_a"])) @ f32(w["f_b"]) + f32(w["dt_bias"])).reshape(
        batch, length, heads, d)
    g = -jnp.exp(f32(w["a_log"]))[:, None] * jax.nn.softplus(rate)
    beta = model["kda_beta_scale"] * jax.nn.sigmoid(h @ f32(w["w_beta"]))
    o, state = delta_rule_by_token(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ f32(w["g_a"])) @ f32(w["g_b"])).reshape(o.shape)
    o = rms(o, w["o_norm"], model["rms_norm_eps"]) * gate
    return o.reshape(batch, length, heads * d) @ f32(w["wo"]), state


# ------------------------------------------------------- full attention


def gated_attention(h, w, model):
    """One masked softmax over the sequence a head; no positions."""
    heads, d = f32(w["wq"]).shape[1:]
    kv_heads = w["wk"].shape[1]
    group = heads // kv_heads
    length = h.shape[1]
    q = jnp.einsum("blc,chd->bhld", h, f32(w["wq"]))
    k = jnp.einsum("blc,cjd->bjld", h, f32(w["wk"]))
    v = jnp.einsum("blc,cjd->bjld", h, f32(w["wv"]))
    # Query head j * group + r reads key-value head j.
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    read = []
    for start in range(0, length, ROWS_AT_ONCE):
        stop = min(start + ROWS_AT_ONCE, length)
        scores = jnp.einsum("bhqd,bhsd->bhqs", q[:, :, start:stop],
                            k[:, :, :stop]) / d ** 0.5
        at_or_before = jnp.arange(stop)[None, :] \
            <= jnp.arange(start, stop)[:, None]
        weights = jax.nn.softmax(jnp.where(at_or_before, scores, -jnp.inf),
                                 axis=-1)
        read.append(jnp.einsum("bhqs,bhsd->bqhd", weights, v[:, :, :stop]))
    read = jnp.concatenate(read, axis=1)                    # [B, L, H, d]
    gate = jax.nn.sigmoid(jnp.einsum("blc,chd->blhd", h, f32(w["wg"])))
    return jnp.einsum("blhd,hdc->blc", read * gate, f32(w["wo"]))


# ---------------------------------------------------------- feed-forward


def choose(m, w, model):
    """The experts a token takes among ALL the router's, and their
    weights: [B, L, k] each."""
    s = jax.nn.sigmoid(m @ f32(w["w_router"]))
    _, chosen = lax.top_k(s + f32(w["router_bias"]),
                          int(model["num_experts_per_tok"]))
    weights = jnp.take_along_axis(s, chosen, -1)
    weights = weights / weights.sum(-1, keepdims=True)
    return chosen, weights * model["routed_scaling_factor"]


def held_experts(m, w, chosen, weights, model):
    """The shared expert, and of a token's chosen experts those the
    tree holds, each at its weight."""
    routed_over = w["w_router"].shape[-1]
    assert routed_over == int(model.get("n_routed_experts_routed_over",
                                        routed_over))
    first = int(model.get("first_expert_held", 0))
    held = w["w_gate"].shape[0]
    # A token's weight on each of the router's experts (0 if unchosen).
    on_all = jnp.sum(jax.nn.one_hot(chosen, routed_over) * weights[..., None],
                     axis=-2)
    on_held = on_all[..., first:first + held]               # [B, L, held]

    def add(e, total):
        return total + on_held[..., e, None] * swiglu(
            m, w["w_gate"][e], w["w_up"][e], w["w_down"][e])

    return lax.fori_loop(0, held, add, swiglu(
        m, w["shared_gate"], w["shared_up"], w["shared_down"]))


def decoder_layer(x, w, model):
    """x [B, L, C] -> (x, the chosen experts [B, L, k], the KDA state
    after the last position or None)."""
    eps = model["rms_norm_eps"]
    h = rms(x, w["mixer_norm"], eps)
    if "w_qkv" in w["mixer"]:
        y, state = kda_mixer(h, w["mixer"], model)
    else:
        y, state = gated_attention(h, w["mixer"], model), None
    x = x + y
    m = rms(x, w["ffn_norm"], eps)
    chosen, weights = choose(m, w["ffn"], model)
    return x + held_experts(m, w["ffn"], chosen, weights, model), chosen, \
        state


def forward(params, tokens, model, with_routing: bool = False,
            tail: "int | None" = None, with_states: bool = False):
    """tokens [B, L] -> logits [B, L, V] float32 (of the last ``tail``
    positions alone if given). With ``with_routing`` also the chosen
    experts [periods, layers of a period, B, L, k]; with ``with_states``
    also the KDA states after the last token, a list in the order of
    the KDA layers, each [B, H, d, d]."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"]["tokens"][tokens])
        assert not params["first"], "solar_open2 has no leading dense layer"
        periods = jax.tree.leaves(params["periods"])[0].shape[0]
        routing, states = [], []
        for p in range(periods):
            chosen_here = []
            for stacked in params["periods"]:
                x, chosen, state = decoder_layer(
                    x, jax.tree.map(lambda a: a[p], stacked), model)
                chosen_here.append(chosen)
                if state is not None:
                    states.append(state)
            routing.append(jnp.stack(chosen_here))
        if tail is not None:
            x = x[:, -tail:]
        logits = rms(x, params["final_norm"], model["rms_norm_eps"]) \
            @ f32(params["lm_head"])
    out = (logits,)
    if with_routing:
        out += (jnp.stack(routing),)
    if with_states:
        out += (states,)
    return out if len(out) > 1 else logits
