"""AI21-Jamba2-3B (``model_type`` ``jamba``): a stack of Mamba-1
state-space layers whose ``dt``, ``B`` and ``C`` pass through an RMSNorm
each, around a softmax-attention layer every ``attn_layer_period``
layers (at ``attn_layer_offset`` inside each period: layers 7 and 21 of
28) of many query heads on ONE key-value head, without positions. Every
layer's feed-forward is the dense gated MLP (``num_experts`` 1). What
the serving engine computes of it (``serve/llm_engine/mamba.py`` puts it
over the row slots and the paged key and value pools); there is no
training path.

**Block**, every layer, pre-norm on one float32 residual stream: ``x +=
mixer(rms(x))``, ``x += W_down (silu(W_gate h) * W_up h)``, ``h =
rms(x)``; a final ``rms`` and the tied head ``x . Embed^T``.

**Mamba mixer** (``E`` hidden, ``Di = expand E``, ``N`` state, ``R``
rank): ``[u, z] = W_in h``; ``u = silu(conv_causal_depthwise(u) + b)``;
``[dt_r, B, C] = W_x u`` and ``dt_r = RMS_R(dt_r)``, ``B = RMS_N(B)``,
``C = RMS_N(C)``; ``dt = softplus(W_dt dt_r + b_dt)``, ``A =
-exp(A_log)``; a position: ``s = exp(dt A) s + (dt u) (x) B``, ``y = s .
C + D u``; ``out = W_out (y * silu(z))``. It IS
``models/phi4flash.py``'s state-space mixer (the same sizes even:
2560, 5120, 16, 4, 160) with the three norms, so it is that module's
functions that run (``ssm_step`` a token a row, ``ssm_chunk`` a chunk of
one row from a carried state; they take the norms where a mixer has
them): a change to either model's recurrence is both models'. The state
is float32.

**Attention mixer**: ``q = W_q h`` as ``H`` heads of ``d``, ``k = W_k
h``, ``v = W_v h`` as ONE head, no bias, no rotation, no gate, causal
softmax of ``q . k d^-1/2`` in float32, ``W_o``. It is
``llm_engine.model.paged_attention``, the dense family's, told "no
rotation" (``rotary``); a position's pool entry is one key and one value
of ``d``, in the pools of the layers that ARE attention alone.

**The parameter tree** stacks what repeats: ``mamba`` holds the Mamba
layers (norms, mixer, feed-forward) stacked over ALL of them, in the
stack's order, ``attn`` the attention layers likewise. The forward
(``llm_engine/mamba.py``) is a scan over the periods whose body is a
scan over the Mamba layers before the period's attention layer, that
layer, and a scan over those after it; each takes its layer of the
stacks by index, which costs no copy.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.phi4flash import scaled_normal

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    # Published; with ONE expert every layer's feed-forward is the dense
    # MLP and the two keys below select nothing.
    ffn_experts: int = 1
    ffn_experts_per_token: int = 1
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    tie_word_embeddings: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    # The recurrent state's precision (a dtype or its name): float32 as
    # the cell's configuration file states it under ``builder.kwargs``;
    # another is the control that ``chip_smoke.py --state-dtype`` must
    # see FAIL.
    state_dtype: Any = jnp.float32

    #: Which forward, cache and weights ``serve/llm_engine`` gives it.
    family = "mamba"
    #: What the engine and ``model.paged_attention`` read: no routed
    #: experts to count, no QK-norm, no rotation, one token a row a pass.
    num_experts = 0
    qk_norm = False
    rotary = False
    block_length = 0

    def __post_init__(self):
        object.__setattr__(self, "state_dtype", jnp.dtype(self.state_dtype))
        if self.ffn_experts != 1 or self.ffn_experts_per_token != 1:
            raise ValueError("only the dense feed-forward (num_experts 1) "
                             "is written down")
        if not (self.mamba_conv_bias and not self.mamba_proj_bias
                and self.tie_word_embeddings):
            raise ValueError("as published: a bias on the convolution, "
                             "none on the projections, a tied head")
        if self.num_layers % self.attn_layer_period \
                or not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"{self.num_layers} layers are no whole number of periods "
                f"of {self.attn_layer_period} with their attention layer "
                f"at {self.attn_layer_offset}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("whole groups of query heads a key-value head")

    @staticmethod
    def tiny(vocab_size: int = 256, **changes) -> "JambaConfig":
        """Test size: two periods of (Mamba, Mamba, attention, Mamba), 5
        query heads of 16 (no power of two, as the model's 20) on one
        key-value head, a state of 4 and a rank of 8."""
        base = dict(vocab_size=vocab_size, hidden_size=80,
                    intermediate_size=96, num_layers=8, num_heads=5,
                    num_kv_heads=1, attn_layer_period=4,
                    attn_layer_offset=2, mamba_d_state=4, mamba_dt_rank=8,
                    max_seq_len=128)
        return JambaConfig(**{**base, **changes})

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    # The state-space sizes under ``models/phi4flash.py``'s names.
    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def d_state(self) -> int:
        return self.mamba_d_state

    @property
    def d_conv(self) -> int:
        return self.mamba_d_conv

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank

    @property
    def periods(self) -> int:
        return self.num_layers // self.attn_layer_period

    @property
    def attn_layers(self) -> int:
        return self.periods

    @property
    def mamba_layers(self) -> int:
        return self.num_layers - self.periods

    @property
    def kinds(self) -> tuple:
        """The mixer of each layer, ``"attention"`` or ``"mamba"``."""
        return tuple(
            "attention" if layer % self.attn_layer_period
            == self.attn_layer_offset else "mamba"
            for layer in range(self.num_layers))

    @property
    def mamba_mixer_params(self) -> int:
        e, di, n, r = self.hidden_size, self.d_inner, self.d_state, \
            self.dt_rank
        return (e * 2 * di + self.d_conv * di + di + di * (r + 2 * n)
                + r * di + di + di * n + di + di * e + r + 2 * n)

    @property
    def attn_mixer_params(self) -> int:
        return self.hidden_size * self.head_dim * 2 * (
            self.num_heads + self.num_kv_heads)

    @property
    def num_params(self) -> int:
        e = self.hidden_size
        layer = 2 * e + 3 * e * self.intermediate_size
        return (self.vocab_size * e + e + self.num_layers * layer
                + self.mamba_layers * self.mamba_mixer_params
                + self.attn_layers * self.attn_mixer_params)


def init_params(config: JambaConfig, key: jax.Array, dtype=F32) -> dict:
    """Seeded weights, held in ``dtype``, in the tree the module's
    docstring describes. Matrices are normal with variance 1 / fan-in,
    the embedding too (it is also the head: behind the final norm,
    logits of unit variance); norm scales ``1 + 0.1 N``; ``A_log`` and
    ``dt_bias`` as Mamba initialises them (``A = -(1..N)``, a step
    log-uniform between 1e-3 and 1e-1), ``D`` one."""
    e, m, di = config.hidden_size, config.intermediate_size, config.d_inner
    n, r, d = config.d_state, config.dt_rank, config.head_dim
    heads, kv = config.num_heads, config.num_kv_heads
    keys = iter(jax.random.split(key, 64))

    def dense(fan_in, *shape):
        return scaled_normal(next(keys), shape, fan_in ** -0.5, dtype)

    def scale(*shape):  # vectors: float32 until the tree is cast
        return 1.0 + scaled_normal(next(keys), shape, 0.1)

    def block(mixer: dict, *lead):
        return {"mixer_norm": scale(*lead, e), "mixer": mixer,
                "ffn_norm": scale(*lead, e),
                "ffn": {"w_gate": dense(e, *lead, e, m),
                        "w_up": dense(e, *lead, e, m),
                        "w_down": dense(m, *lead, m, e)}}

    def mamba(*lead):
        step = jnp.exp(jax.random.uniform(next(keys), (*lead, di), F32)
                       * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "in_proj": dense(e, *lead, e, 2 * di),
            "conv_w": dense(config.d_conv, *lead, config.d_conv, di),
            "conv_b": scaled_normal(next(keys), (*lead, di), 0.1),
            "x_proj": dense(di, *lead, di, r + 2 * n),
            "dt_norm": scale(*lead, r), "b_norm": scale(*lead, n),
            "c_norm": scale(*lead, n),
            "dt_proj": dense(r, *lead, r, di),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=F32)), (*lead, di, n)),
            "D": jnp.ones((*lead, di), F32),
            "out_proj": dense(di, *lead, di, e)}

    def attention(*lead):
        return {"wq": dense(e, *lead, e, heads, d),
                "wk": dense(e, *lead, e, kv, d),
                "wv": dense(e, *lead, e, kv, d),
                "wo": dense(heads * d, *lead, heads, d, e)}

    return jax.tree.map(lambda x: x.astype(dtype), {
        "embed": {"tokens": dense(e, config.vocab_size, e)},
        "mamba": block(mamba(config.mamba_layers), config.mamba_layers),
        "attn": block(attention(config.attn_layers), config.attn_layers),
        "final_norm": scale(e),
    })
