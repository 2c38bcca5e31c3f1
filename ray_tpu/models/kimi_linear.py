"""Kimi-Linear-48B-A3B (``model_type`` ``kimi_linear``, arXiv:2510.26692):
layers of **Kimi Delta Attention** (KDA: a gated delta rule whose decay
is a number for every CHANNEL of a head's key, over a matrix-valued
state) beside, every fourth layer, **latent attention without
positions**, and behind a leading dense layer a sigmoid-routed mixture
of experts of which this chip may hold a SHARE. What the serving engine
computes of it (``serve/llm_engine/linear.py`` puts these functions over
the row slots and the paged latent pool); there is no training path.

**Block**: pre-norm, one residual stream (float32): ``x += mixer(rms(x))``,
``x += ffn(rms(x))``; final ``rms``, an untied head.

**KDA mixer**, ``H`` heads of ``d`` (32 of 128):

    [q~ | k~ | v~] = x W_qkv, each through its own causal depthwise
        convolution over time (kernel 4, no bias), then SiLU; a row
        carries its last 3 inputs (``conv``)
    q = l2norm(q~) d^-1/2,  k = l2norm(k~),  v = v~          a head
    g = -exp(A_log_h) softplus(x W_fa W_fb + dt_bias)        float32,
        a head AND channel;  a = exp(g) in (0, 1)^d
    beta = sigmoid(x W_beta)                                 a head
        (times ``kda_beta_scale``: 2 where a configuration lets the
        transition's eigenvalue along k, 1 - beta, go negative)
    S' = diag(a_t) S_(t-1);  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                S [d, d] a head, float32, zero at a
                                   request's start
    y = W_o [rms_head(o_t; w) * sigmoid(x W_ga W_gb)]

Two forms give the same numbers. ``kda_step``: one token against each
row's state (a decode step; the rule itself inside
``ops/kda_state_update.py``, which reads a head's state once and writes
it once; ``kda_position`` is its plain form). ``kda_chunk``: one row's
chunk in the
CHUNKWISE form, sub-chunks of ``kda_subchunk`` (64) positions. With
``G_i`` the running sum of ``g`` inside a sub-chunk and ``u_i = beta_i
(v_i - S'_i^T k_i)`` the correction a position writes,

    S_i = diag(e^{G_i}) S_0 + sum_{j<=i} diag(e^{G_i - G_j}) k_j u_j^T
    (I + diag(beta) A) U = diag(beta) (V - (K e^G) S_0),
        A_ij = sum_c k_ic k_jc e^{G_ic - G_jc}   (j < i)
    O = (Q e^G) S_0 + B U,   B_ij = sum_c q_ic k_jc e^{G_ic - G_jc}  (j <= i)

so a sub-chunk is one triangular system a head (solved once for all
sub-chunks: its matrix does not hold ``S_0``) and one state update, not
64 sequential steps. Exponents are taken ONLY of differences ``G_i -
G_j`` with ``i >= j`` (at most 0): the factored form ``k e^{-G}``
overflows float32 at the decays ``A_log`` in log(1..16) gives (``g``
down to -100 a position). Padding (positions at or past ``n_valid``) is
given ``g = 0`` and ``beta = 0``: it writes nothing and decays nothing,
so the state after the chunk is the state after its last real position.
All of the recurrence is float32 at the highest matmul precision (the
chip otherwise rounds a float32 product's operands to bfloat16).

**Latent mixer**: Xing's (``models/xing.py``: ``latent_queries``,
``latent_entries``, ``attend_expanded``, the absorbed forms) WITHOUT a
query down-projection (``q_lora_rank`` None: one ``wq``) and WITHOUT
rotation (``mla_use_nope``: ``rotary`` False): a position's pool entry
is ``[rms(c_kv) | k_r]``, 576 values, scores ``(q_n . k_n + q_r . k_r)
(nope + rope)^-1/2``.

**Feed-forward**: the first ``first_k_dense`` layers a SwiGLU of
``intermediate_size``; the others ``moe.route`` under the sigmoid
scoring over ``num_experts`` (256) outputs, ``experts_per_token`` (8)
chosen, renormalised, times ``routed_scaling_factor``, plus the shared
expert; the layer HOLDS experts ``first_expert .. first_expert +
experts_held - 1`` (``held``) and adds the chosen experts that are held.
What the experts on other chips would add is left out: that partial sum
goes on to the next layer (model-configs guide, section 4).

**The stack and its parameter tree.** Each layer's kind is read from
the published ``linear_attn_config`` (``kda_layers`` and
``full_attn_layers``, counted from 1; the first ``num_layers`` of them
are built). ``params["first"]`` is a LIST of the ``first_k_dense``
leading layers, each a dict; ``params["periods"]`` a LIST of the layers
of one period (KDA, KDA, latent, KDA), each stacked on a leading axis
over the periods: the engine SCANS over periods with the period's four
layers written out in the body. A scan over single layers would need
one body for both mixers (a ``cond`` whose two sides carry the state
AND the pool, each copied where the other side leaves it untouched);
the period is the unit that repeats, and its body compiles once
whatever the depth. A layer is ``{"mixer_norm", "mixer", "ffn_norm",
"ffn"}``; a KDA mixer has ``w_qkv [C, 3Hd]``, ``conv_w [4, 3Hd]`` (tap
``i`` weighs the input ``3 - i`` positions back), ``a_log [H]``,
``dt_bias [Hd]``, ``f_a [C, d]``, ``f_b [d, Hd]``, ``g_a``, ``g_b``,
``w_beta [C, H]``, ``o_norm [d]``, ``wo [Hd, C]``; a latent mixer ``wq
[C, H, nope + rope]``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo [H, v,
C]``; a feed-forward Xing's keys (``w_router [C, E]`` and
``router_bias [E]`` at the router's width, the experts' arrays at the
held count).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.kda_state_update import kda_state_update

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
KDA, LATENT = "kda", "latent"
_PUBLISHED_KINDS = {
    "kda_layers": (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26),
    "full_attn_layers": (4, 8, 12, 16, 20, 24, 27),
    "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}


def frozen_group(group) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                        for k, v in dict(group).items()))


class DeltaStack:
    """What a configuration of the linear family says of its stack and
    of its KDA layers, from ``kinds`` (the mixer of each built layer),
    ``first_k_dense``, ``num_layers``, the published
    ``linear_attn_config`` group and the experts held: this module's
    ``KimiLinearConfig`` and ``solar_open2.SolarOpen2Config`` (whose
    full layers are of another kind) both are one."""

    #: ``beta = kda_beta_scale * sigmoid(.)``: 2 where the published
    #: configuration lets the transition's eigenvalues go negative.
    kda_beta_scale = 1.0

    @property
    def _group(self) -> dict:
        return dict(self.linear_attn_config)

    @property
    def period_kinds(self) -> tuple:
        """The shortest run of layers the stack behind the leading
        layers repeats: (kda, kda, latent, kda) as Kimi-Linear
        publishes it."""
        rest = self.kinds[self.first_k_dense:]
        for period in range(1, len(rest) + 1):
            if len(rest) % period == 0 and all(
                    kind == rest[i % period] for i, kind in enumerate(rest)):
                return rest[:period]

    @property
    def periods(self) -> int:
        return (self.num_layers - self.first_k_dense) \
            // len(self.period_kinds)

    @property
    def sparse_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def kda_layers(self) -> int:
        return self.kinds.count(KDA)

    @property
    def full_layers(self) -> int:
        """The layers that own an entry of the paged pool."""
        return self.num_layers - self.kda_layers

    # ------------------------------------------------------ the widths

    @property
    def kda_heads(self) -> int:
        return self._group["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        return self._group["head_dim"]

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_kernel(self) -> int:
        return self._group["short_conv_kernel_size"]

    @property
    def held(self) -> tuple:
        """(first, count): the experts this chip holds of the
        ``num_experts`` the router chooses among (``moe.combine_weights``)."""
        return self.first_expert, self.experts_held

    def _settle_held(self) -> None:
        """``experts_held`` None is every expert; the share is checked."""
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.num_experts)
        first, count = self.held
        if not 0 <= first <= first + count <= self.num_experts:
            raise ValueError(f"experts {first}..{first + count - 1} held of "
                             f"{self.num_experts}")
        if self.experts_per_token > self.num_experts:
            raise ValueError("more experts a token than experts")

    @property
    def kda_mixer_params(self) -> int:
        c, hd, d = self.hidden_size, self.kda_width, self.kda_head_dim
        return (3 * c * hd + 3 * hd * self.conv_kernel + self.kda_heads + hd
                + 2 * (c * d + d * hd) + c * self.kda_heads + d + hd * c)

    @property
    def sparse_ffn_params(self) -> int:
        """One expert layer's router, held and shared experts."""
        expert = 3 * self.hidden_size * self.moe_intermediate_size
        return (self.hidden_size * self.num_experts + self.num_experts
                + (self.experts_held + self.num_shared_experts) * expert)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(DeltaStack):
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216       # the leading dense layer's SwiGLU
    moe_intermediate_size: int = 1024   # one expert's
    num_layers: int = 27
    first_k_dense: int = 1
    num_heads: int = 32                 # of the latent layers
    q_lora_rank: "int | None" = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The router's width, and the share of it this chip holds.
    num_experts: int = 256
    experts_held: "int | None" = None   # None: every expert
    first_expert: int = 0
    experts_per_token: int = 8
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    # The published group: which layers are KDA and which latent
    # (counted from 1), KDA's heads, their size, the convolution's
    # kernel. A dict; kept as a sorted tuple so that the configuration
    # stays hashable.
    linear_attn_config: Any = None
    kda_subchunk: int = 64
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    # Of RANDOM weights only (``init_params``), as ``XingConfig``'s.
    router_init_scale: float = 1.0
    router_bias_scale: float = 0.05
    expert_init_scale: float = 1.0

    #: Which forward, cache and weights ``serve/llm_engine`` gives it.
    family = "linear"
    #: ``mla_use_nope``: nothing is rotated (``xing.latent_queries``).
    rotary = False
    #: The kind of the layers that own the pool (``linear.FAMILIES``).
    full_kind = LATENT

    def __post_init__(self):
        object.__setattr__(self, "linear_attn_config", frozen_group(
            self.linear_attn_config or _PUBLISHED_KINDS))
        self._settle_held()
        # ``kinds`` raises where a built layer is in neither list.
        if not 0 <= self.first_k_dense < len(self.kinds):
            raise ValueError("no layer behind the leading ones")

    @staticmethod
    def tiny(vocab_size: int = 256, **changes) -> "KimiLinearConfig":
        """Test size: the dense layer and two periods, 4 KDA heads of
        16, 16 experts routed over of which 8 are held and a token takes
        3, sub-chunks of 4."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_layers=9, num_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, num_experts=16, experts_held=8, first_expert=4,
            experts_per_token=3, kda_subchunk=4, max_seq_len=128,
            linear_attn_config={**_PUBLISHED_KINDS, "head_dim": 16,
                                "num_heads": 4})
        return KimiLinearConfig(**{**base, **changes})

    # ------------------------------------------------------- the stack

    @property
    def kinds(self) -> tuple:
        """The mixer of each built layer, ``"kda"`` or ``"latent"``."""
        group = self._group
        kda, full = set(group["kda_layers"]), set(group["full_attn_layers"])
        out = []
        for layer in range(1, self.num_layers + 1):
            if (layer in kda) == (layer in full):
                raise ValueError(f"layer {layer} is in one list exactly of "
                                 "kda_layers and full_attn_layers")
            out.append(KDA if layer in kda else LATENT)
        return tuple(out)

    @property
    def latent_layers(self) -> int:
        return self.full_layers

    # ------------------------------------------------------ the widths

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_lanes(self) -> int:
        """``latent_dim`` in whole lanes of 128 (``XingConfig.pool_lanes``
        says why)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def num_params(self) -> int:
        c, kda = self.hidden_size, self.kda_mixer_params
        latent = (c * self.num_heads * self.qk_head_dim
                  + c * self.latent_dim + self.kv_lora_rank
                  + self.kv_lora_rank * self.num_heads
                  * (self.qk_nope_head_dim + self.v_head_dim)
                  + self.num_heads * self.v_head_dim * c)
        total = 2 * self.vocab_size * c + c
        for layer, kind in enumerate(self.kinds):
            total += 2 * c + (kda if kind == KDA else latent)
            total += 3 * c * self.intermediate_size \
                if layer < self.first_k_dense else self.sparse_ffn_params
        return total


# ---------------------------------------------------------------------- init


def dense_init(key, fan_in, *shape):
    return jax.random.normal(key, shape, F32) * fan_in ** -0.5


def norm_init(key, *shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, F32)


def init_kda_mixer(config, key, *lead) -> dict:
    """``a_log`` is log U(1, 16) and ``dt_bias`` the inverse softplus of
    a step drawn log-uniformly in 0.001..0.1, so that a state forgets
    over tens to thousands of positions and not at once."""
    c, hd, d = config.hidden_size, config.kda_width, config.kda_head_dim
    keys = jax.random.split(key, 11)
    step = jnp.exp(jax.random.uniform(
        keys[3], (*lead, hd), F32, math.log(0.001), math.log(0.1)))
    return {
        "w_qkv": dense_init(keys[0], c, *lead, c, 3 * hd),
        "conv_w": dense_init(keys[1], config.conv_kernel, *lead,
                             config.conv_kernel, 3 * hd),
        "a_log": jnp.log(jax.random.uniform(
            keys[2], (*lead, config.kda_heads), F32, 1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "f_a": dense_init(keys[4], c, *lead, c, d),
        "f_b": dense_init(keys[5], d, *lead, d, hd),
        "g_a": dense_init(keys[6], c, *lead, c, d),
        "g_b": dense_init(keys[7], d, *lead, d, hd),
        "w_beta": dense_init(keys[8], c, *lead, c, config.kda_heads),
        "o_norm": norm_init(keys[9], *lead, d),
        "wo": dense_init(keys[10], hd, *lead, hd, c),
    }


def init_latent_mixer(config, key, *lead) -> dict:
    c, heads, rank = config.hidden_size, config.num_heads, config.kv_lora_rank
    keys = jax.random.split(key, 5)
    return {
        "wq": dense_init(keys[0], c, *lead, c, heads, config.qk_head_dim),
        "wkv_a": dense_init(keys[1], c, *lead, c, config.latent_dim),
        "kv_norm": norm_init(keys[2], *lead, rank),
        "wkv_b": dense_init(keys[3], rank, *lead, rank, heads,
                            config.qk_nope_head_dim + config.v_head_dim),
        "wo": dense_init(keys[4], heads * config.v_head_dim, *lead,
                         heads, config.v_head_dim, c),
    }


def init_ffn(config, key, sparse: bool, *lead) -> dict:
    """The router, its bias and the experts' down-projections as
    ``xing.init_params`` draws them."""
    c = config.hidden_size
    keys = jax.random.split(key, 8)
    if not sparse:
        m = config.intermediate_size
        return {"w_gate": dense_init(keys[0], c, *lead, c, m),
                "w_up": dense_init(keys[1], c, *lead, c, m),
                "w_down": dense_init(keys[2], m, *lead, m, c)}
    m, held = config.moe_intermediate_size, config.experts_held
    out = {
        "w_router": dense_init(
            keys[0], c / config.router_init_scale ** 2, *lead, c,
            config.num_experts),
        "router_bias": config.router_bias_scale * jax.random.normal(
            keys[1], (*lead, config.num_experts), F32),
        "w_gate": dense_init(keys[2], c, *lead, held, c, m),
        "w_up": dense_init(keys[3], c, *lead, held, c, m),
        "w_down": dense_init(keys[4], m, *lead, held, m, c)
        * config.expert_init_scale,
    }
    m = config.num_shared_experts * m
    if m:
        out.update({"shared_gate": dense_init(keys[5], c, *lead, c, m),
                    "shared_up": dense_init(keys[6], c, *lead, c, m),
                    "shared_down": dense_init(keys[7], m, *lead, m, c)})
    return out


def init_stack(config, key: jax.Array, mixers: dict) -> dict:
    """Random float32 weights of a stack of the linear family (the
    module's head has the tree): ``mixers`` gives each kind of layer its
    mixer's ``init(config, key, *lead)``. Norm scales are drawn about
    one."""
    c = config.hidden_size

    def layer(key, kind, sparse, *lead):
        keys = jax.random.split(key, 4)
        return {"mixer_norm": norm_init(keys[0], *lead, c),
                "mixer": mixers[kind](config, keys[1], *lead),
                "ffn_norm": norm_init(keys[2], *lead, c),
                "ffn": init_ffn(config, keys[3], sparse, *lead)}

    keys = jax.random.split(key, 5)
    kinds, dense = config.kinds, config.first_k_dense
    return {
        "embed": {"tokens": dense_init(keys[0], c, config.vocab_size, c)},
        "final_norm": norm_init(keys[1], c),
        "lm_head": dense_init(keys[2], c, c, config.vocab_size),
        "first": [layer(k, kinds[i], False) for i, k in enumerate(
            jax.random.split(keys[3], dense))],
        "periods": [layer(k, kind, True, config.periods)
                    for kind, k in zip(config.period_kinds, jax.random.split(
                        keys[4], len(config.period_kinds)))],
    }


def init_params(config: KimiLinearConfig, key: jax.Array) -> dict:
    return init_stack(config, key, {KDA: init_kda_mixer,
                                    LATENT: init_latent_mixer})


# ----------------------------------------------------------------- KDA mixer


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _project(x, w, dtype):
    return jnp.einsum("...c,cd->...d", x.astype(dtype), w.astype(dtype),
                      preferred_element_type=F32)


def _kda_inputs(w: dict, x, conved, config: KimiLinearConfig):
    """x [..., C] (normed) and the convolutions' outputs [..., 3Hd]
    (float32, before SiLU) -> q, k, v, g [..., H, d] and beta [..., H],
    all float32."""
    heads, d, dtype = config.kda_heads, config.kda_head_dim, config.dtype
    lead = x.shape[:-1]
    q, k, v = jnp.split(
        jax.nn.silu(conved.astype(dtype)).astype(F32).reshape(
            *lead, 3 * heads, d), 3, axis=-2)
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    f = _project(_project(x, w["f_a"], dtype), w["f_b"], dtype)
    g = -jnp.exp(w["a_log"].astype(F32))[:, None] * jax.nn.softplus(
        (f + w["dt_bias"].astype(F32)).reshape(*lead, heads, d))
    beta = jax.nn.sigmoid(_project(x, w["w_beta"], dtype))
    if config.kda_beta_scale != 1.0:
        beta = config.kda_beta_scale * beta
    return q, k, v, g, beta


def _kda_output(w: dict, x, o, config: KimiLinearConfig):
    """The heads' outputs o [..., H, d] float32 under the gated head
    norm, through ``wo``. Returns [..., C] in ``dtype``."""
    dtype = config.dtype
    gate = _project(_project(x, w["g_a"], dtype), w["g_b"], dtype)
    o = rms_norm(o, w["o_norm"], config.rms_norm_eps) \
        * jax.nn.sigmoid(gate).reshape(o.shape)
    return jnp.einsum("...d,dc->...c", o.reshape(*x.shape[:-1], -1).astype(
        dtype), w["wo"].astype(dtype))


def kda_step(w: dict, x, s, conv, active, config: KimiLinearConfig,
             layer=None):
    """One token for every row. x [B, C] (normed); s [B, H, d, d]
    float32, or with ``layer`` (an int32 scalar) the layers' stacked [L,
    B, H, d, d], of which that layer alone is advanced, where it lies
    (``ops/kda_state_update.py``); conv [kernel - 1, B, 3Hd] (the
    convolutions' last inputs, the oldest first); active [B] bool: an
    inactive row's state and inputs stay as they are. Returns (out [B,
    C], s as it came, conv)."""
    u = _project(x, w["w_qkv"], config.dtype)
    window = jnp.concatenate([conv, u[None].astype(conv.dtype)], axis=0)
    conv = jnp.where(active[None, :, None], window[1:], conv)
    conved = jnp.einsum("kbd,kd->bd", window.astype(F32),
                        w["conv_w"].astype(F32))
    q, k, v, g, beta = _kda_inputs(w, x, conved, config)
    # An inactive row decays nothing and writes nothing: its state is
    # what it was, to the bit.
    g = jnp.where(active[:, None, None], g, 0.0)
    beta = jnp.where(active[:, None], beta, 0.0)
    o, stacked = kda_state_update(
        (s[None] if layer is None else s).astype(F32),
        0 if layer is None else layer, q, k, v, g, beta)
    s = stacked[0] if layer is None else stacked
    return _kda_output(w, x, o, config), s.astype(config.state_dtype), conv


def kda_position(q, k, v, g, beta, s):
    """One position of the rule for every row: q, k, v, g [B, H, d],
    beta [B, H], s [B, H, d, d], all float32 -> (o [B, H, d], s). The
    plain form ``ops/kda_state_update.py`` is held to; no program calls
    it (it passes over the state three times: a reduce for both
    readings, then the update's read and its write)."""
    s = s * jnp.exp(g)[..., None]                                   # S'
    # Both readings of S': what the state predicts for k, and the
    # output but for this position's own correction.
    predicted = jnp.sum(s * k[..., None], axis=-2)              # S'^T k
    carried = jnp.sum(s * q[..., None], axis=-2)                # S'^T q
    update = beta[..., None] * (v - predicted)                  # [B, H, d]
    s = s + k[..., None] * update[..., None, :]
    return carried + update * jnp.sum(q * k, axis=-1, keepdims=True), s


def kda_recurrence(q, k, v, g, beta, s):
    """The rule, a position at a time: q, k, v, g [T, H, d], beta [T,
    H], s [H, d, d], all float32 -> (o [T, H, d], s). The plain form the
    chunkwise one is held to."""
    def position(s, inputs):
        q, k, v, g, beta = inputs
        s = s * jnp.exp(g)[..., None]
        update = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
        s = s + k[..., None] * update[..., None, :]
        return s, jnp.sum(s * q[..., None], axis=-2)

    s, o = lax.scan(position, s, (q, k, v, g, beta))
    return o, s


def kda_chunkwise(q, k, v, g, beta, s, subchunk: int):
    """The same rule in the chunkwise form (the module's head has the
    algebra): q, k, v, g [T, H, d], beta [T, H], s [H, d, d], ``T`` a
    multiple of ``subchunk`` -> (o [T, H, d], s)."""
    length, heads, d = q.shape
    n, c = length // subchunk, subchunk

    def split(x):   # [T, H, ...] -> [n, H, c, ...]
        return jnp.moveaxis(x.reshape(n, c, *x.shape[1:]), 1, 2)

    q, k, v, g, beta = map(split, (q, k, v, g, beta))
    running = jnp.cumsum(g, axis=2)                       # G [n, H, c, d]
    lower = jnp.tril(jnp.ones((c, c), bool))              # j <= i
    # e^{G_i - G_j} for j <= i, 0 above the diagonal: [n, H, c, c, d].
    decay = jnp.exp(jnp.where(
        lower[..., None], running[:, :, :, None] - running[:, :, None],
        -jnp.inf))
    keys_to = k[:, :, None] * decay
    a = jnp.sum(k[:, :, :, None] * keys_to, axis=-1)      # [n, H, c, c]
    b = jnp.sum(q[:, :, :, None] * keys_to, axis=-1)
    eye = jnp.eye(c, dtype=F32)
    system = eye + beta[..., None] * a * (1.0 - eye)      # a is 0 above
    solved = jax.scipy.linalg.solve_triangular(
        system, jnp.broadcast_to(eye, system.shape), lower=True,
        unit_diagonal=True)                               # [n, H, c, c]
    from_start = jnp.exp(running)                         # e^{G_i}
    to_end = jnp.exp(running[:, :, -1:] - running)        # e^{G_c - G_j}
    outs = []
    for i in range(n):
        held = jnp.einsum("hik,hkv->hiv", k[i] * from_start[i], s,
                          precision=HIGHEST)
        written = jnp.einsum(
            "hij,hjv->hiv", solved[i], beta[i][..., None] * (v[i] - held),
            precision=HIGHEST)                            # U [H, c, d]
        outs.append(
            jnp.einsum("hik,hkv->hiv", q[i] * from_start[i], s,
                       precision=HIGHEST)
            + jnp.einsum("hij,hjv->hiv", b[i], written, precision=HIGHEST))
        s = s * from_start[i][:, -1, :, None] + jnp.einsum(
            "hjk,hjv->hkv", k[i] * to_end[i], written, precision=HIGHEST)
    o = jnp.moveaxis(jnp.stack(outs), 2, 1).reshape(length, heads, d)
    return o, s


def kda_chunk(w: dict, x, s, conv, n_valid, config: KimiLinearConfig):
    """A chunk of one row from a carried state, in the chunkwise form.
    x [T, C] (normed); s [H, d, d]; conv [kernel - 1, 3Hd]; positions at
    or past ``n_valid`` are padding and advance nothing. Returns (out
    [T, C], s, conv)."""
    length, kernel = x.shape[0], config.conv_kernel
    u = _project(x, w["w_qkv"], config.dtype)
    window = jnp.concatenate([conv, u.astype(conv.dtype)], axis=0)
    conv = lax.dynamic_slice_in_dim(window, n_valid, kernel - 1, axis=0)
    conv_w = w["conv_w"].astype(F32)
    window = window.astype(F32)
    conved = sum(window[i:i + length] * conv_w[i] for i in range(kernel))
    q, k, v, g, beta = _kda_inputs(w, x, conved, config)
    real = jnp.arange(length) < n_valid
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    subchunk = math.gcd(config.kda_subchunk, length)
    o, s = kda_chunkwise(q, k, v, g, beta, s.astype(F32), subchunk)
    return _kda_output(w, x, o, config), s.astype(config.state_dtype), conv
