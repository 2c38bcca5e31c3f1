"""Solar-Open2-250B (``model_type`` ``solar_open2``): layers of Kimi Delta
Attention whose ``beta`` reaches 2, so that the transition's eigenvalue
along the key goes NEGATIVE, beside, every fourth layer from the first,
**gated softmax attention of grouped queries without positions**, and in
EVERY layer a sigmoid-routed mixture of experts of which this chip may
hold a SHARE. What the serving engine computes of it
(``serve/llm_engine/linear.py`` puts it over the row slots and the paged
key and value pools); there is no training path.

A module of its own beside ``models/kimi_linear.py``, whose KDA functions
it USES (``kda_step``, ``kda_chunk``, ``kda_chunkwise`` through
``linear.py``; ``init_kda_mixer``, ``init_ffn``, ``init_stack``; the
``DeltaStack`` of properties both configurations share): the delta rule
is one rule, and the two models differ in what a CONFIGURATION says
(the published keys, the lists counted from 0 here and from 1 there, the
kind and the widths of the full layers, no leading dense layer), which
one class with both sets of fields would say twice over.

**Block**: pre-norm, one residual stream (float32): ``x += mixer(rms(x))``,
``x += ffn(rms(x))``; final ``rms``, an untied head.

**KDA mixer** (layers 1, 2, 3 of every four), ``H`` = 64 heads of 128:
``kimi_linear``'s, with ``beta = 2 sigmoid(x W_beta)`` in (0, 2)
(``kda_allow_neg_eigval``): with ``k`` of unit length a step's transition
``diag(e^g)(I - beta k k^T)`` has the eigenvalue ``1 - beta`` in (-1, 1)
along ``k``, so a state can flip sign (arXiv:2411.12537). The decay's
and the gate's projections are the low-rank pairs (``kda_use_full_proj``
false; true is refused), and there are as many key and value heads as
query heads (``linear_attn_config.num_kv_heads`` null; a number is
refused).

**Full mixer** (layers 0, 4, 8, ...: ``gqa_layers``, counted from 0):
``q = x W_q`` as 64 x 128, ``k = x W_k``, ``v = x W_v`` as 8 x 128, no
bias, no QK-norm, NO rotation (``use_rope`` false); query head ``8 j +
r`` reads key-value head ``j``; scores ``q . k 128^-1/2``, causal softmax
in float32; ``y = W_o [(P v) * sigmoid(x W_g)]``, one gate value a head
and channel from the layer's normed input (``use_gqa_gate``). It is
``llm_engine.model.paged_attention``, the dense family's, told "no
rotation" (``rotary``) and given a gate (``wg``); a position's pool entry
is its ``k`` and ``v``, 2 x 1024 values, in the pools of the layers that
ARE full alone.

**Feed-forward** (every layer: ``first_k_dense`` 0): ``moe.route`` under
the sigmoid scoring over ``num_experts`` (320), 8 chosen of ``s + bias``,
weights ``s`` of the chosen over their sum times
``routed_scaling_factor`` (1), plus the shared expert; the layer adds the
chosen experts it HOLDS (``held``), as Kimi-Linear's.

**The parameter tree** is ``kimi_linear``'s (``first`` an empty list
here, ``periods`` the four layers (full, KDA, KDA, KDA) stacked over the
periods); a full mixer has ``wq [C, H, d]``, ``wk``, ``wv [C, KV, d]``,
``wg [C, H, d]``, ``wo [H, d, C]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import kimi_linear as kimi
from ray_tpu.models.kimi_linear import KDA

#: The kind of a full layer: grouped softmax attention over gathered
#: key and value pools (``kimi_linear.LATENT`` is the other).
GQA = "gqa"
_PUBLISHED_GROUP = {"head_dim": 128, "num_heads": 64,
                    "short_conv_kernel_size": 4, "num_kv_heads": None}


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(kimi.DeltaStack):
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240      # published; no layer is dense
    moe_intermediate_size: int = 1280   # one expert's
    num_layers: int = 48
    first_k_dense: int = 0
    # The full layers.
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    rope_theta: float = 10000.0         # published; unused without rope
    use_gqa_gate: bool = True
    # Which layers are full, counted from 0, as published (those below
    # ``num_layers`` are built), and how many KDA layers lie between two.
    gqa_layers: tuple = tuple(range(0, 48, 4))
    gqa_interval: int = 3
    # The published group: KDA's heads, their size, the convolution's
    # kernel; kept as a sorted tuple so the configuration stays hashable.
    linear_attn_config: Any = None
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    # The router's width, and the share of it this chip holds.
    num_experts: int = 320
    experts_held: "int | None" = None   # None: every expert
    first_expert: int = 0
    experts_per_token: int = 8
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    kda_subchunk: int = 64
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    # Of RANDOM weights only (``init_params``), as ``KimiLinearConfig``'s.
    router_init_scale: float = 1.0
    router_bias_scale: float = 0.05
    expert_init_scale: float = 1.0

    #: Which forward, cache and weights ``serve/llm_engine`` gives it.
    family = "linear"
    #: The kind of the layers that own the pool (``linear.FAMILIES``).
    full_kind = GQA
    #: What ``model.paged_attention`` reads beside the heads: no QK-norm,
    #: one token a row a pass.
    qk_norm = False
    block_length = 0

    def __post_init__(self):
        object.__setattr__(self, "linear_attn_config", kimi.frozen_group(
            self.linear_attn_config or _PUBLISHED_GROUP))
        object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        self._settle_held()
        if self.kda_use_full_proj or self._group.get("num_kv_heads"):
            raise ValueError(
                "kda_use_full_proj and linear_attn_config.num_kv_heads: the "
                "decay and the gate are low-rank pairs and every KDA head "
                "has its own key and value, as published")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("whole groups of query heads a key-value head")
        if not 0 <= self.first_k_dense < self.num_layers:
            raise ValueError("no layer behind the leading ones")
        gaps = {b - a for a, b in zip(self.gqa_layers, self.gqa_layers[1:])}
        if gaps - {self.gqa_interval + 1}:
            raise ValueError(f"gqa_layers {self.gqa_layers} are not "
                             f"{self.gqa_interval} KDA layers apart")

    @staticmethod
    def tiny(vocab_size: int = 256, **changes) -> "SolarOpen2Config":
        """Test size: two periods of (full, KDA, KDA, KDA), 4 heads of
        16 over 2 key-value heads, 4 KDA heads of 16, 16 experts routed
        over of which 8 are held and a token takes 3, sub-chunks of 4."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, num_experts=16, experts_held=8,
            first_expert=4, experts_per_token=3, kda_subchunk=4,
            max_seq_len=128,
            linear_attn_config={**_PUBLISHED_GROUP, "head_dim": 16,
                                "num_heads": 4})
        return SolarOpen2Config(**{**base, **changes})

    @property
    def kinds(self) -> tuple:
        """The mixer of each built layer, ``"gqa"`` or ``"kda"``."""
        full = set(self.gqa_layers)
        return tuple(GQA if layer in full else KDA
                     for layer in range(self.num_layers))

    @property
    def rotary(self) -> bool:
        return self.use_rope

    @property
    def kda_beta_scale(self) -> float:
        return 2.0 if self.kda_allow_neg_eigval else 1.0

    @property
    def full_mixer_params(self) -> int:
        c, d = self.hidden_size, self.head_dim
        return c * d * ((2 + self.use_gqa_gate) * self.num_heads
                        + 2 * self.num_kv_heads)

    @property
    def num_params(self) -> int:
        c = self.hidden_size
        total = 2 * self.vocab_size * c + c
        for layer, kind in enumerate(self.kinds):
            total += 2 * c + (self.kda_mixer_params if kind == KDA
                              else self.full_mixer_params)
            total += 3 * c * self.intermediate_size \
                if layer < self.first_k_dense else self.sparse_ffn_params
        return total


def init_gqa_mixer(config: SolarOpen2Config, key, *lead) -> dict:
    c, d = config.hidden_size, config.head_dim
    heads, kv = config.num_heads, config.num_kv_heads
    keys = jax.random.split(key, 5)
    out = {"wq": kimi.dense_init(keys[0], c, *lead, c, heads, d),
           "wk": kimi.dense_init(keys[1], c, *lead, c, kv, d),
           "wv": kimi.dense_init(keys[2], c, *lead, c, kv, d),
           "wo": kimi.dense_init(keys[3], heads * d, *lead, heads, d, c)}
    if config.use_gqa_gate:
        out["wg"] = kimi.dense_init(keys[4], c, *lead, c, heads, d)
    return out


def init_params(config: SolarOpen2Config, key: jax.Array) -> dict:
    """Random float32 weights: ``kimi_linear.init_stack``'s draws (the
    KDA mixers', the routers', the experts' as Kimi-Linear's), the full
    mixers' matrices at their fan-in's scale."""
    return kimi.init_stack(config, key, {KDA: kimi.init_kda_mixer,
                                         GQA: init_gqa_mixer})
