"""Llama-family decoder-only transformer, TPU-first.

Flagship model for the Train/Serve/bench paths (the reference has no
model zoo of its own — it launches torch models; BASELINE.json's
north-star configs are Llama-2-7B SFT + serving, so the model family
lives here as a first-class framework component).

Design choices for TPU:
- pure-JAX functional (params = pytree), bf16 activations / f32 params
  and optimizer, f32 logits for the loss;
- every param carries a *logical* sharding axis tuple
  (``param_logical_axes``) consumed by ray_tpu.parallel.sharding rules →
  GSPMD: tp shards heads/mlp/vocab, fsdp shards embed, sp shards the
  sequence via ring attention, dp replicates;
- layers run under ``lax.scan`` with ``jax.checkpoint`` (remat) so the
  whole stack compiles to one fused loop and activation memory stays
  O(1) in depth — the XLA-idiomatic equivalent of activation
  checkpointing wrappers;
- GQA (num_kv_heads < num_heads), RoPE, RMSNorm, SwiGLU — the Llama-2/3
  architecture family.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention,
    ring_attention_gspmd,
)

# checkpoint_name tags of q, k, v as _attention_block hands them to the
# attention; remat_policy="attention" keeps them (and the flash
# kernel's SAVED_NAMES) through the layer scan.
ATTENTION_SAVED = ("attention_q", "attention_k", "attention_v")


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # What the layer scan keeps for the backward besides a layer's
    # input (8 KB a token a layer at hidden 4096 in bf16).
    # "attention": q, k, v as the attention takes them (after QK-norm
    # and rope) and, under attention="flash", the kernel's output and
    # lse: 20 KB a token a layer more (32 + 8 heads of 128), and the
    # backward starts a layer at the flash backward kernels instead of
    # running the projections and the forward kernel again; the norms,
    # wo and the MLP are recomputed.
    # "full": nothing but the input, everything recomputed: the floor
    # for a model at its memory's edge.
    remat_policy: str = "attention"
    # "plain" (full attention), "flash" (pallas blockwise kernel), or
    # "ring" (context parallel over sp axis — requires running inside
    # shard_map with an "sp" axis; "ring_local" when already inside).
    attention: str = "plain"
    # Chunked-vocab loss: >0 computes the training CE over sequence
    # chunks of this many tokens so the [B, L, V] f32 logits are never
    # materialized (the single biggest activation at training shapes —
    # ~2 GiB at [8, 2048, 32000]); the per-chunk logits are recomputed
    # in backward. 0 = classic full-logits path.
    ce_chunk: int = 0
    # Mixture-of-Experts: >0 replaces the dense SwiGLU MLP with a routed
    # expert layer (models/moe.py; experts sharded over the ep mesh
    # axis): softmax over all experts, the experts_per_token largest,
    # their weights renormalised only with norm_topk_prob, no token
    # dropped.
    num_experts: int = 0
    experts_per_token: int = 1
    norm_topk_prob: bool = False
    moe_aux_loss_coef: float = 0.01
    # Of RANDOM weights only (``init_params``): the standard deviation
    # of a router's logits on a unit-RMS input. At 1, of 128 logits the
    # 8th and 9th largest lie 0.06 apart on average and the 8 chosen
    # hold a quarter of the softmax; a trained router is more decided.
    router_init_scale: float = 1.0
    # QK-norm, before the heads are rotated. True, as OLMoE applies
    # it: an RMSNorm with its own scale over the WHOLE query projection
    # (all heads together) and another over the whole key projection.
    # "head", as Qwen3 and SDAR apply it: an RMSNorm over each head's
    # head_dim values, with one scale [head_dim] for all query heads and
    # one for all key heads.
    qk_norm: "bool | str" = False
    # Generation by diffusion over blocks (SDAR): > 0 makes a position
    # see ALL of its own block of this many positions, both ways, and
    # every earlier block; the logits at a position are then for the
    # token AT it (a masked position is filled in place). The serving
    # engine alone generates so (serve/llm_engine/model.py); the other
    # three are a request's defaults there: passes a block, which masked
    # positions a pass fixes, and the confidence that fixes one early.
    block_length: int = 0
    mask_token_id: int = 0
    denoising_steps: int = 1
    remasking: str = "sequential"
    confidence_threshold: float = 0.9

    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
            max_seq_len=8192, rope_theta=500000.0)

    @staticmethod
    def small_1b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_layers=22, num_heads=32, num_kv_heads=4, head_dim=64)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        """Test-size config; every sharded dim is divisible by 2 and 4."""
        return LlamaConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
            max_seq_len=128, remat=False)

    def _param_count(self, experts_counted: int) -> int:
        e, m, v = self.hidden_size, self.intermediate_size, self.vocab_size
        h, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        if self.num_experts > 0:
            mlp = e * self.num_experts + 3 * e * m * experts_counted
        else:
            mlp = 3 * e * m  # dense swiglu
        qk_norms = {False: 0, True: (h + kv) * d, "head": 2 * d}[self.qk_norm]
        per_layer = (e * h * d + 2 * e * kv * d + h * d * e  # attention
                     + mlp
                     + 2 * e + qk_norms)  # norms
        return v * e + self.num_layers * per_layer + e + e * v

    @property
    def num_params(self) -> int:
        return self._param_count(max(self.num_experts, 1))

    @property
    def num_active_params(self) -> int:
        """Params touched per token: routing activates
        ``experts_per_token`` of the experts, so MFU accounting must use
        this, not total params."""
        return self._param_count(self.experts_per_token
                                 if self.num_experts > 0 else 1)


# ---------------------------------------------------------------------- init


def init_params(config: LlamaConfig, key: jax.Array) -> dict:
    """Initialize a param pytree. Per-layer params are stacked on a
    leading ``num_layers`` dim (consumed by lax.scan)."""
    e, m, v = config.hidden_size, config.intermediate_size, config.vocab_size
    h, kv, d = config.num_heads, config.num_kv_heads, config.head_dim
    n = config.num_layers
    keys = jax.random.split(key, 9)

    def norm_init(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense_init(key, fan_in, *shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) * fan_in ** -0.5

    layers = {
        "attn_norm": norm_init(n, e),
        "wq": dense_init(keys[1], e, n, e, h, d),
        "wk": dense_init(keys[2], e, n, e, kv, d),
        "wv": dense_init(keys[3], e, n, e, kv, d),
        "wo": dense_init(keys[4], h * d, n, h, d, e),
        "mlp_norm": norm_init(n, e),
    }
    if config.qk_norm == "head":
        layers.update({"q_norm": norm_init(n, d), "k_norm": norm_init(n, d)})
    elif config.qk_norm:
        layers.update({"q_norm": norm_init(n, h, d),
                       "k_norm": norm_init(n, kv, d)})
    if config.num_experts > 0:
        from ray_tpu.models.moe import init_moe_params

        layers.update(init_moe_params(keys[5], e, m, config.num_experts, n,
                                      config.router_init_scale))
    else:
        layers.update({
            "w_gate": dense_init(keys[5], e, n, e, m),
            "w_up": dense_init(keys[6], e, n, e, m),
            "w_down": dense_init(keys[7], m, n, m, e),
        })
    return {
        "embed": {"tokens": dense_init(keys[0], e, v, e)},
        "layers": layers,
        "final_norm": norm_init(e),
        "lm_head": dense_init(keys[8], e, e, v),
    }


def param_logical_axes(config: LlamaConfig | None = None) -> dict:
    """Logical sharding axes per param (leading scan dim = None).

    tp → heads/mlp/vocab; fsdp → embed; ep → experts; norms replicated.
    """
    layers = {
        "attn_norm": (None, "norm"),
        "wq": (None, "embed", "heads", None),
        "wk": (None, "embed", "kv_heads", None),
        "wv": (None, "embed", "kv_heads", None),
        "wo": (None, "heads", None, "embed"),
        "mlp_norm": (None, "norm"),
    }
    if config is not None and config.qk_norm == "head":
        layers.update({"q_norm": (None, None), "k_norm": (None, None)})
    elif config is not None and config.qk_norm:
        layers.update({"q_norm": (None, "heads", None),
                       "k_norm": (None, "kv_heads", None)})
    if config is not None and config.num_experts > 0:
        from ray_tpu.models.moe import moe_logical_axes

        layers.update(moe_logical_axes())
    else:
        layers.update({
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed"),
        })
    return {
        "embed": {"tokens": ("vocab", "embed")},
        "layers": layers,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


# ------------------------------------------------------------------- forward


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * lax.rsqrt(var + eps)
    return (out * scale).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [B, L, H, D], positions: [B, L]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, L, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _norm_over_heads(x: jax.Array, scale: jax.Array,
                     eps: float) -> jax.Array:
    """RMSNorm over a whole projection: x [B, L, H, D] is normalised
    over its H * D values together, scale [H, D]."""
    flat = rms_norm(x.reshape(*x.shape[:2], -1), scale.reshape(-1), eps)
    return flat.reshape(x.shape)


def qkv_projections(layer: dict, x: jax.Array, positions: jax.Array,
                    config: LlamaConfig):
    """The attention block up to the scores, the same on every path
    (training, the dense cache, the paged pool): input norm, q/k/v
    projections, QK-norm where the configuration has it, rotary
    embedding on q and k. x [B, L, E] -> q [B, L, H, D], k, v
    [B, L, KV, D] (local heads inside a manual-tp body)."""
    return qkv_of_normed(
        layer, rms_norm(x, layer["attn_norm"], config.rms_norm_eps),
        positions, config)


def qkv_of_normed(layer: dict, normed: jax.Array, positions: jax.Array,
                  config):
    """``qkv_projections`` behind the input norm, for a block that
    norms its own input (``serve/llm_engine/linear.py``). A
    configuration that says ``rotary = False`` (``models/solar_open2.py``:
    ``use_rope`` false) rotates nothing.

    Takes the projections' weights in either layout, by what the layer
    holds: ``wq`` [E, H, D], ``wk`` and ``wv`` [E, KV, D], as
    ``init_params`` lays them (training, ``forward``, the families that
    bring their own layer dicts: three products), or ``wqkv`` [E, (H + 2
    KV) D], the three side by side in that order, as
    ``serve/llm_engine/model.py:lay_for_serving`` lays them for an
    engine (ONE product, split after it: the same sums of the same
    products). Norms and rotation come after the split, the same for
    both."""
    dtype = config.dtype
    if "wqkv" in layer:
        h, kv, d = config.num_heads, config.num_kv_heads, config.head_dim
        qkv = jnp.einsum("ble,ef->blf", normed, layer["wqkv"].astype(dtype))
        q, k, v = (t.reshape(*t.shape[:2], -1, d) for t in jnp.split(
            qkv, [h * d, (h + kv) * d], axis=-1))
    else:
        q = jnp.einsum("ble,ehd->blhd", normed, layer["wq"].astype(dtype))
        k = jnp.einsum("ble,ekd->blkd", normed, layer["wk"].astype(dtype))
        v = jnp.einsum("ble,ekd->blkd", normed, layer["wv"].astype(dtype))
    if config.qk_norm == "head":
        q = rms_norm(q, layer["q_norm"], config.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], config.rms_norm_eps)
    elif config.qk_norm:
        q = _norm_over_heads(q, layer["q_norm"], config.rms_norm_eps)
        k = _norm_over_heads(k, layer["k_norm"], config.rms_norm_eps)
    if getattr(config, "rotary", True):
        q = rope(q, positions, config.rope_theta)
        k = rope(k, positions, config.rope_theta)
    return q, k, v


def _attention_block(layer: dict, x: jax.Array, positions: jax.Array,
                     config: LlamaConfig,
                     tp_axis: str | None = None) -> jax.Array:
    """``tp_axis``: Megatron-style manual tensor parallelism for use
    INSIDE a shard_map body (the pipelined path; GSPMD handles tp
    automatically elsewhere): q/k/v/o arrive head-sharded over the axis
    and the output projection psums the partial sums."""
    dtype = config.dtype
    h, kv = config.num_heads, config.num_kv_heads
    if tp_axis is not None:
        if config.qk_norm is True:
            raise NotImplementedError(
                "QK-norm spans all heads: under manual tp each shard "
                "sees only its own")
        tp = jax.lax.psum(1, tp_axis)
        h, kv = h // tp, kv // tp
    q, k, v = qkv_projections(layer, x, positions, config)
    # Named here and not in qkv_projections, which the paged engine
    # shares: the serving programs stay what they are.
    q, k, v = (checkpoint_name(t, name)
               for t, name in zip((q, k, v), ATTENTION_SAVED))
    if config.remat:
        # See forward's layer_step.
        q, k, v = lax.optimization_barrier((q, k, v))
    if kv != h and config.attention != "flash":
        # flash_attention is GQA-native (kernels index head groups);
        # the other paths want materialized full-head kv.
        reps = h // kv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    if config.attention == "ring":
        # Context-parallel path: shard_map ring over the ambient mesh's
        # sp axis (requires jax.set_mesh).
        out = ring_attention_gspmd(q, k, v, causal=True)
    elif config.attention == "ring_local":
        # Already inside a shard_map with an "sp" axis.
        out = ring_attention(q, k, v, axis_name="sp", causal=True)
    elif config.attention == "flash":
        # Pallas blockwise kernel (ray_tpu.ops.flash_attention). Inside
        # a manual-tp shard_map body (tp_axis set) arrays are already
        # local shards — call the kernel directly; under plain GSPMD jit
        # the wrapper drops into shard_map itself (mosaic kernels can't
        # be auto-partitioned on a real multi-chip mesh).
        from ray_tpu.ops.flash_attention import (
            flash_attention,
            flash_attention_gspmd,
        )

        if tp_axis is not None:
            out = flash_attention(q, k, v, causal=True)
        else:
            out = flash_attention_gspmd(q, k, v, causal=True)
    else:
        out = plain_attention(q, k, v, causal=True)
    proj = jnp.einsum("blhd,hde->ble", out, layer["wo"].astype(dtype))
    if tp_axis is not None:
        proj = jax.lax.psum(proj, tp_axis)  # partial sums over head shards
    return x + proj


def _mlp_block(layer: dict, x: jax.Array, config: LlamaConfig,
               tp_axis: str | None = None) -> jax.Array:
    dtype = config.dtype
    normed = rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
    gate = jnp.einsum("ble,em->blm", normed, layer["w_gate"].astype(dtype))
    up = jnp.einsum("ble,em->blm", normed, layer["w_up"].astype(dtype))
    hidden = jax.nn.silu(gate) * up
    proj = jnp.einsum("blm,me->ble", hidden, layer["w_down"].astype(dtype))
    if tp_axis is not None:
        proj = jax.lax.psum(proj, tp_axis)  # partial sums over mlp shards
    return x + proj


def _moe_block(layer: dict, x: jax.Array,
               config: LlamaConfig) -> tuple[jax.Array, jax.Array]:
    from ray_tpu.models.moe import moe_mlp

    normed = rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
    out, aux = moe_mlp(
        layer, normed, experts_per_token=config.experts_per_token,
        norm_topk_prob=config.norm_topk_prob, dtype=config.dtype)
    return x + out, aux


def _remat_policy(name: str):
    if name == "attention":
        from ray_tpu.ops.flash_attention import SAVED_NAMES

        return jax.checkpoint_policies.save_only_these_names(
            *ATTENTION_SAVED, *SAVED_NAMES)
    if name == "full":
        return None
    raise ValueError(
        f"remat_policy={name!r}: expected 'attention' or 'full'")


def forward(params: dict, tokens: jax.Array, config: LlamaConfig,
            positions: jax.Array | None = None,
            with_aux: bool = False, return_features: bool = False):
    """tokens [B, L] (local shard if under sp) -> logits [B, L, V] f32.

    When ``positions`` is provided they are the *global* token positions
    (needed for RoPE + causal masking under sequence parallelism).
    ``with_aux=True`` additionally returns the summed MoE load-balancing
    loss (0.0 for dense configs). ``return_features=True`` returns the
    final-norm hidden states INSTEAD of logits (the chunked-CE loss
    applies the lm head itself, chunk by chunk).
    """
    if positions is None:
        b, l = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(l), (b, l))
    x = params["embed"]["tokens"].astype(config.dtype)[tokens]
    moe = config.num_experts > 0

    def layer_step(carry, layer):
        x, aux_sum = carry
        x = _attention_block(layer, x, positions, config)
        if config.remat:
            # Two barriers a layer under remat, here and on q, k, v in
            # _attention_block: the values the backward starts from
            # exist once, whole, before what reads them. Without them
            # the backward body's schedule, once it no longer runs the
            # attention's forward, loses the MLP input's place in fast
            # memory and the weight-gradient products slow by a third:
            # the step came out SLOWER than under "full" (v5e, PERF.md
            # section 6, PR 41). They change no value.
            x = lax.optimization_barrier(x)
        if moe:
            x, aux = _moe_block(layer, x, config)
            aux_sum = aux_sum + aux
        else:
            x = _mlp_block(layer, x, config)
        return (x, aux_sum), None

    step = layer_step
    if config.remat:
        step = jax.checkpoint(layer_step, prevent_cse=False,
                              policy=_remat_policy(config.remat_policy))
    (x, aux_sum), _ = lax.scan(
        step, (x, jnp.zeros((), dtype=jnp.float32)), params["layers"])
    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    if return_features:
        return (x, aux_sum) if with_aux else x
    # bf16 operands on the MXU with f32 accumulation: same numerics as
    # mixed-precision matmul everywhere else in the stack, ~2x the
    # throughput of an f32 matmul on v5e, and logits still come out f32.
    logits = jnp.einsum("ble,ev->blv", x,
                        params["lm_head"].astype(config.dtype),
                        preferred_element_type=jnp.float32)
    if with_aux:
        return logits, aux_sum
    return logits


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            config: LlamaConfig, positions: jax.Array | None = None,
            mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross-entropy (targets already shifted).

    Written as ``logsumexp(logits) - logits[target]`` so XLA fuses the
    reduction instead of materializing a second [B, L, V] log-softmax
    array in HBM (the [B, L, V] f32 logits alone are ~2 GiB at the bench
    shape — HBM bandwidth, not FLOPs, dominates this tail). With
    ``config.ce_chunk > 0`` even the logits themselves stay chunk-sized
    (see _chunked_nll) — the freed HBM buys a larger batch.

    MoE configs add the router load-balancing loss scaled by
    ``moe_aux_loss_coef``.
    """
    if config.ce_chunk > 0 and tokens.shape[1] % config.ce_chunk != 0:
        # Silent fallback would materialize the very logits the user
        # configured chunking to avoid — fail loudly instead.
        raise ValueError(
            f"ce_chunk={config.ce_chunk} must divide the sequence "
            f"length {tokens.shape[1]}")
    if config.ce_chunk > 0:
        x, aux = forward(params, tokens, config, positions,
                         with_aux=True, return_features=True)
        nll = _chunked_nll(x, params["lm_head"], targets, config)
        if mask is not None:
            ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            ce = jnp.mean(nll)
    else:
        logits, aux = forward(params, tokens, config, positions,
                              with_aux=True)
        ce = cross_entropy(logits, targets, mask)
    if config.num_experts > 0:
        return ce + config.moe_aux_loss_coef * aux
    return ce


def _chunked_nll(x: jax.Array, lm_head: jax.Array, targets: jax.Array,
                 config: LlamaConfig) -> jax.Array:
    """Per-token NLL from final-norm features WITHOUT ever forming the
    full [B, L, V] logits: lax.map over sequence chunks keeps one
    [B, chunk, V] buffer live, and jax.checkpoint recomputes it in
    backward (the lm-head matmul is ~9% of the model's FLOPs; the 2 GiB
    f32 logits it would otherwise pin are the largest single activation
    at training shapes)."""
    B, L, E = x.shape
    chunk = config.ce_chunk
    n = L // chunk
    w = lm_head.astype(config.dtype)
    xs = x.reshape(B, n, chunk, E).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_nll(xc, tc):
        logits = jnp.einsum("bce,ev->bcv", xc, w,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tc[..., None],
                                     axis=-1)[..., 0]
        return lse - picked

    nll = lax.map(lambda args: chunk_nll(*args), (xs, ts))  # [n, B, c]
    return nll.transpose(1, 0, 2).reshape(B, L)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Fused mean next-token CE: logsumexp(logits) - logits[target]
    (no second [B, L, V] log-softmax materialized — see loss_fn)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
