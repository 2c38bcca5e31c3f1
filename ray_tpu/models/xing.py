"""Xing4.0-29B-A4B (``model_type`` ``xing4_0``): latent attention, a
residual path of several streams mixed by hyper-connections, and a
sigmoid-routed mixture of experts beside a shared expert, behind
leading dense layers. What the serving engine computes of it
(``serve/llm_engine/latent.py`` puts these functions over the paged
latent pool); there is no training path.

**The residual path** (hyper-connections, arXiv:2409.19606, under the
manifold constraint of mHC, arXiv:2512.24880). A token's state is ``X``
``[n, C]``, ``n = hc_mult`` streams: the embedding copied into each,
summed after the last layer. A sublayer ``F`` (attention or the
feed-forward) reads a learned mix of the streams and writes back
through a doubly stochastic matrix:

    x~ = RMSNorm_{nC}(vec(X))                     one scale [nC]
    [H~pre | H~post | H~res] = a * (x~ phi) + b   phi [nC, 2n + n*n]
    H_pre = sigmoid(H~pre)          [n]
    H_post = 2 sigmoid(H~post)      [n]
    H_res = Sinkhorn(clip(H~res))   [n, n]: M = exp(.), then
            hc_sinkhorn_iters times  M /= rowsum(M) + hc_eps,
                                     M /= colsum(M) + hc_eps
    h = H_pre X;  y = F(RMSNorm_C(h));  X' = H_res X + H_post^T y

``a`` is one learned scalar for each of the three. All of it runs in
float32: it is ``2n + n*n`` numbers a token and sublayer, and the
streams themselves are carried in float32.

**Attention** (MLA, DeepSeek-V3's keys). ``c_q = RMSNorm(h W_qa)``,
``[q_nope | q_rope] = c_q W_qb`` per head; ``[c_kv | k_rope] = h
W_kva``, ``c_kv <- RMSNorm(c_kv)``; rotary (YaRN) on ``q_rope`` and on
the ONE ``k_rope`` all heads share. **What a position leaves in the
cache is** ``[c_kv | rotated k_rope]``, ``kv_lora_rank +
qk_rope_head_dim`` values (``latent_entries``; in ``pool_lanes`` lanes,
the tail zero). ``W_kvb [rank, heads,
nope + v]`` splits a head into ``W_uk`` and ``W_uv``. Two readings of
the same latents give the same output:

- ``attend_expanded``: ``k = [c_kv W_uk | k_rope]``, ``v = c_kv W_uv``,
  ``softmax(q k^T * scale) v`` (a prefill chunk: many queries a
  context, the expansion is paid once for all of them);
- ``attend_absorbed``: ``q' = q_nope W_uk^T`` carries the query into
  the latent space, the scores ``q' . c_kv + q_rope . k_rope`` and the
  values ``u = softmax(.) c_kv`` are taken on the latents where they
  lie, then ``o = u W_uv`` (a decode step: one query a context, and
  every head shares one read of the latents; the engine's step takes
  ``u`` in ``ops/paged_latent_attention.py``, which reads the pool
  through the block tables, and this is its plain form).

**Feed-forward.** The first ``first_k_dense`` layers a SwiGLU of
``intermediate_size``; the others ``models/moe.py``'s routed experts
under the sigmoid scoring (the ``experts_per_token`` largest of
``sigmoid + bias``, weights the sigmoids renormalised and times
``routed_scaling_factor``) plus the shared expert.

The multi-token-prediction module the source publishes
(``num_nextn_predict_layers``) is not built: it adds nothing to the
next token's logits.

The parameter tree stacks what repeats: ``dense`` holds the leading
layers and ``sparse`` the expert layers, each on a leading layer axis
(two scans). The rotary embedding pairs a head's value ``i`` with ``i +
d/2`` (halves, as ``llama.rope``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import moe
from ray_tpu.models.llama import rms_norm

F32 = jnp.float32
_YARN = {"factor": 1.0, "original_max_position_embeddings": 4096,
         "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
         "mscale_all_dim": 0.0}


@dataclasses.dataclass(frozen=True)
class XingConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216       # a leading dense layer's SwiGLU
    moe_intermediate_size: int = 1024   # one expert's
    num_layers: int = 40
    first_k_dense: int = 2
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 64
    experts_per_token: int = 4
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    rope_theta: float = 10000.0
    # The published ``rope_scaling`` group (YaRN), a dict; kept as a
    # sorted tuple of its items so that the configuration stays hashable.
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    # Of RANDOM weights only (``init_params``): the standard deviation
    # of a router's logits on a unit-RMS input, and of its bias.
    router_init_scale: float = 1.0
    router_bias_scale: float = 0.05
    # ... and a routed expert's down-projection drawn this many times
    # its fan-in scale: how large a routed expert's output is beside the
    # shared expert's (which is drawn at one).
    expert_init_scale: float = 1.0

    #: Which forward, cache and weights ``serve/llm_engine`` gives it.
    family = "latent"
    #: What the attention and feed-forward functions below ask of any
    #: configuration they are given (``models/kimi_linear.py`` answers
    #: otherwise): the rotary part of a query and a key is rotated, and
    #: an expert layer holds every expert it routes over.
    rotary = True
    held = None

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense lies outside the layers")
        if self.num_layers > self.first_k_dense \
                and self.experts_per_token > self.num_experts:
            raise ValueError("more experts a token than experts")

    @staticmethod
    def tiny(vocab_size: int = 256, **changes) -> "XingConfig":
        """Test size: one dense layer and two expert layers, 8 experts
        of which a token takes 3, YaRN that stretches 32 positions."""
        base = dict(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_layers=3, first_k_dense=1,
            num_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=8, experts_per_token=3, max_seq_len=128,
            rope_scaling={"type": "yarn", "factor": 4,
                          "original_max_position_embeddings": 32,
                          "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1})
        return XingConfig(**{**base, **changes})

    @property
    def latent_dim(self) -> int:
        """Values a position leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_lanes(self) -> int:
        """The width of a position's pool entry: ``latent_dim`` rounded
        up to the chip's 128 lanes, the tail zero. The chip tiles a
        ``[.., 576]`` array as ``[.., 640]`` in any case; declared 576
        wide, the runtime's compact layout would instead lay the pool
        out with the BLOCKS along the lanes, and every program would
        copy the whole pool into block-major order and back (two copies
        of 2 GiB a decode step, seen in the v5e compiler's output,
        PR 44)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sparse_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def hc_width(self) -> int:
        """Numbers a sublayer's mix is made of: pre, post, res."""
        return self.hc_mult * (2 + self.hc_mult)

    @property
    def yarn(self) -> dict:
        return {**_YARN, **{k: v for k, v in (self.rope_scaling or ())
                            if k != "type"}}

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-0.5`` times the square of YaRN's attention
        factor ``0.1 mscale_all_dim ln(factor) + 1``."""
        yarn = self.yarn
        return self.qk_head_dim ** -0.5 \
            * _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2

    @property
    def num_params(self) -> int:
        c, h = self.hidden_size, self.num_heads
        attention = (c * self.q_lora_rank + self.q_lora_rank
                     + self.q_lora_rank * h * self.qk_head_dim
                     + c * self.latent_dim + self.kv_lora_rank
                     + self.kv_lora_rank * h
                     * (self.qk_nope_head_dim + self.v_head_dim)
                     + h * self.v_head_dim * c)
        mix = self.hc_mult * c * (self.hc_width + 1) + self.hc_width + 3
        shared = 2 * (mix + c) + attention  # both sublayers' mix and norm
        expert = 3 * c * self.moe_intermediate_size
        dense = shared + 3 * c * self.intermediate_size
        sparse = (shared + c * self.num_experts + self.num_experts
                  + (self.num_experts + self.num_shared_experts) * expert)
        return (2 * self.vocab_size * c + c + self.first_k_dense * dense
                + self.sparse_layers * sparse)


# ---------------------------------------------------------------------- init


def init_params(config: XingConfig, key: jax.Array) -> dict:
    """Random float32 weights. Norm scales are drawn about one (a scale
    of exactly one would leave the scale untested), a mix's ``phi`` so
    that ``H~`` has unit spread, its three scalars apart from one
    another, the router's bias so that it changes some choices."""
    c, h = config.hidden_size, config.num_heads
    n, width = config.hc_mult, config.hc_width

    def dense_init(key, fan_in, *shape):
        return jax.random.normal(key, shape, F32) * fan_in ** -0.5

    def norm_init(key, *shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, F32)

    def mix(key, layers):
        keys = jax.random.split(key, 3)
        return {
            "norm": norm_init(keys[0], layers, n * c),
            # [width, nC]: the long axis minor, as the chip tiles it.
            "phi": dense_init(keys[1], n * c, layers, width, n * c),
            "a": jnp.broadcast_to(jnp.asarray([1.0, 0.75, 1.25], F32),
                                  (layers, 3)),
            "b": 0.5 * jax.random.normal(keys[2], (layers, width), F32),
        }

    def layers(key, count, sparse):
        keys = jax.random.split(key, 20)
        rank_q, rank_kv = config.q_lora_rank, config.kv_lora_rank
        out = {
            "hc_attn": mix(keys[0], count),
            "attn_norm": norm_init(keys[1], count, c),
            "wq_a": dense_init(keys[2], c, count, c, rank_q),
            "q_norm": norm_init(keys[3], count, rank_q),
            "wq_b": dense_init(keys[4], rank_q, count, rank_q, h,
                               config.qk_head_dim),
            "wkv_a": dense_init(keys[5], c, count, c, config.latent_dim),
            "kv_norm": norm_init(keys[6], count, rank_kv),
            "wkv_b": dense_init(keys[7], rank_kv, count, rank_kv, h,
                                config.qk_nope_head_dim + config.v_head_dim),
            "wo": dense_init(keys[8], h * config.v_head_dim, count, h,
                             config.v_head_dim, c),
            "hc_ffn": mix(keys[9], count),
            "mlp_norm": norm_init(keys[10], count, c),
        }
        if not sparse:
            m = config.intermediate_size
            out.update({"w_gate": dense_init(keys[11], c, count, c, m),
                        "w_up": dense_init(keys[12], c, count, c, m),
                        "w_down": dense_init(keys[13], m, count, m, c)})
            return out
        out.update(moe.init_moe_params(
            keys[11], c, config.moe_intermediate_size, config.num_experts,
            count, config.router_init_scale))
        out["w_down"] = out["w_down"] * config.expert_init_scale
        out["router_bias"] = config.router_bias_scale * jax.random.normal(
            keys[12], (count, config.num_experts), F32)
        m = config.num_shared_experts * config.moe_intermediate_size
        if m:
            out.update({
                "shared_gate": dense_init(keys[13], c, count, c, m),
                "shared_up": dense_init(keys[14], c, count, c, m),
                "shared_down": dense_init(keys[15], m, count, m, c)})
        return out

    keys = jax.random.split(key, 5)
    params = {
        "embed": {"tokens": dense_init(keys[0], c, config.vocab_size, c)},
        "final_norm": norm_init(keys[1], c),
        "lm_head": dense_init(keys[2], c, c, config.vocab_size),
    }
    if config.first_k_dense:
        params["dense"] = layers(keys[3], config.first_k_dense, False)
    if config.sparse_layers:
        params["sparse"] = layers(keys[4], config.sparse_layers, True)
    return params


# ------------------------------------------------------------ residual path


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """[..., n, n] -> the doubly stochastic matrix ``iters`` rounds of
    row then column normalisation make of ``exp(logits)``. The entries
    are kept apart and the sums written out, so that a round is
    elementwise work on ``n * n`` arrays and five rounds compile to ONE
    fused operation: as reductions over a [.., n, n] array each round
    was four operations of a few microseconds, 80 a sublayer, 1,100 a
    decode step of seven layers. Five rounds a turn of the loop, not
    all of them: unrolled whole, a program took the CPU's compiler 40 s
    where it takes 3."""
    n = logits.shape[-1]

    def one_round(_, m):
        m = [list(row) for row in m]
        for i in range(n):
            total = sum(m[i][1:], m[i][0]) + eps
            m[i] = [x / total for x in m[i]]
        for j in range(n):
            total = sum((m[i][j] for i in range(1, n)), m[0][j]) + eps
            for i in range(n):
                m[i][j] = m[i][j] / total
        return tuple(tuple(row) for row in m)

    m = tuple(tuple(jnp.exp(logits[..., i, j]) for j in range(n))
              for i in range(n))
    m = lax.fori_loop(0, iters, one_round, m, unroll=5)
    return jnp.stack([jnp.stack(row, axis=-1) for row in m], axis=-2)


def hyper_mix(w: dict, streams: jax.Array, config: XingConfig):
    """What a sublayer reads and how it writes back: streams [..., n,
    C] float32 -> (h [..., C], H_post [..., n], H_res [..., n, n]), all
    float32. ``w``: one sublayer's ``norm``, ``phi``, ``a``, ``b``."""
    n = config.hc_mult
    flat = streams.reshape(*streams.shape[:-2], -1)
    mixed = jnp.einsum(
        "...c,kc->...k", rms_norm(flat, w["norm"], config.rms_norm_eps),
        w["phi"].astype(F32), precision=lax.Precision.HIGHEST)
    a, b = w["a"].astype(F32), w["b"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * mixed[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * mixed[..., n:2 * n] + b[n:2 * n])
    res = a[2] * mixed[..., 2 * n:] + b[2 * n:]
    res = sinkhorn(
        jnp.clip(res.reshape(*res.shape[:-1], n, n), config.hc_clamp_min,
                 config.hc_clamp_max),
        config.hc_sinkhorn_iters, config.hc_eps)
    return _mixed(pre, streams), post, res


def _mixed(weights: jax.Array, streams: jax.Array) -> jax.Array:
    """``sum_j weights[..., j] * streams[..., j, :]``, written out: as a
    matrix product the chip would round both float32 operands to
    bfloat16 (its default for a float32 product), and ``n`` terms are
    no work for the vector unit."""
    terms = [weights[..., j, None] * streams[..., j, :]
             for j in range(streams.shape[-2])]
    return sum(terms[1:], terms[0])


def hyper_write(streams: jax.Array, y: jax.Array, post: jax.Array,
                res: jax.Array) -> jax.Array:
    """``H_res X + H_post^T y``: streams [..., n, C], y [..., C]."""
    mixed = jnp.stack([_mixed(res[..., i, :], streams)
                       for i in range(streams.shape[-2])], axis=-2)
    return mixed + post[..., :, None] * y.astype(F32)[..., None, :]


# ----------------------------------------------------------------- attention


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(config: XingConfig) -> np.ndarray:
    """The rotary frequencies [d/2] under YaRN: a pair that turns more
    than ``beta_fast`` times within the original context keeps its
    frequency, one that turns fewer than ``beta_slow`` times has it
    divided by ``factor``, with a linear ramp between."""
    d, base, yarn = config.qk_rope_head_dim, config.rope_theta, config.yarn
    original = yarn["original_max_position_embeddings"]

    def pair_turning(turns):
        return d * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_turning(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(yarn["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = base ** (-np.arange(d // 2) / (d // 2))
    return (freq / yarn["factor"] * ramp + freq * (1 - ramp)).astype(
        np.float32)


def rope(x: jax.Array, positions: jax.Array, config: XingConfig):
    """x [B, T, ..., d] at positions [B, T]: halves, YaRN's
    frequencies, the cosines and sines times ``mscale / mscale_all_dim``'s
    attention factors' ratio (one, as published)."""
    yarn = config.yarn
    ratio = _yarn_mscale(yarn["factor"], yarn["mscale"]) \
        / _yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
    angles = positions.astype(F32)[..., None] * yarn_inv_freq(config)
    angles = angles.reshape(*positions.shape, *(1,) * (x.ndim - 3), -1)
    cos, sin = jnp.cos(angles) * ratio, jnp.sin(angles) * ratio
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def latent_queries(w: dict, x: jax.Array, positions: jax.Array,
                   config: XingConfig):
    """x [B, T, C] (normed) -> (q_nope [B, T, H, nope], q_rope [B, T,
    H, rope], rotated where the configuration rotates). Without a
    ``q_lora_rank`` the queries are one projection ``wq`` [C, H, nope +
    rope]."""
    dtype, eps = config.dtype, config.rms_norm_eps
    if config.q_lora_rank is None:
        q = jnp.einsum("btc,chd->bthd", x, w["wq"].astype(dtype))
    else:
        c_q = rms_norm(jnp.einsum("btc,cr->btr", x, w["wq_a"].astype(dtype)),
                       w["q_norm"], eps)
        q = jnp.einsum("btr,rhd->bthd", c_q, w["wq_b"].astype(dtype))
    nope = config.qk_nope_head_dim
    q_rope = q[..., nope:]
    if config.rotary:
        q_rope = rope(q_rope, positions, config)
    return q[..., :nope], q_rope


def latent_entries(w: dict, x: jax.Array, positions: jax.Array,
                   config: XingConfig) -> jax.Array:
    """x [B, T, C] (normed) -> [B, T, pool_lanes]: what these positions
    leave in the cache, ``[RMSNorm(c_kv) | k_rope]`` (rotated where the
    configuration rotates) and zeros up to the pool's width."""
    rank = config.kv_lora_rank
    kv = jnp.einsum("btc,cr->btr", x, w["wkv_a"].astype(config.dtype))
    k_rope = kv[..., rank:]
    if config.rotary:
        k_rope = rope(k_rope, positions, config)
    entry = [rms_norm(kv[..., :rank], w["kv_norm"], config.rms_norm_eps),
             k_rope]
    pad = config.pool_lanes - config.latent_dim
    if pad:
        entry.append(jnp.zeros((*kv.shape[:-1], pad), kv.dtype))
    return jnp.concatenate(entry, axis=-1)


def _probabilities(scores, mask, config):
    scores = jnp.where(mask[:, None], scores * config.softmax_scale, -1e30)
    return jax.nn.softmax(scores, axis=-1).astype(config.dtype)


def _out_projection(w, o, config):
    return jnp.einsum("bthd,hde->bte", o, w["wo"].astype(config.dtype))


def attend_expanded(w: dict, q_nope, q_rope, latents, mask,
                    config: XingConfig):
    """Queries [B, T, H, .] over latents [B, S, pool_lanes] (mask [B,
    T, S]): every position's keys and values expanded through ``W_kvb``.
    The rotary part of the score is its own product, on the one
    ``k_rope`` all heads share. Returns [B, T, C]."""
    dtype, rank = config.dtype, config.kv_lora_rank
    w_kvb = w["wkv_b"].astype(dtype)
    nope = config.qk_nope_head_dim
    c_kv = latents[..., :rank]
    k_rope = latents[..., rank:config.latent_dim]
    k_nope = jnp.einsum("bsc,chd->bshd", c_kv, w_kvb[..., :nope])
    values = jnp.einsum("bsc,chd->bshd", c_kv, w_kvb[..., nope:])
    scores = jnp.einsum("bthd,bshd->bhts", q_nope, k_nope,
                        preferred_element_type=F32) \
        + jnp.einsum("bthr,bsr->bhts", q_rope, k_rope,
                     preferred_element_type=F32)
    o = jnp.einsum("bhts,bshd->bthd", _probabilities(scores, mask, config),
                   values)
    return _out_projection(w, o, config)


def absorbed_queries(w: dict, q_nope, q_rope, lanes: int,
                     config: XingConfig):
    """Queries [B, T, H, .] carried into the latent space: ``[q_nope
    W_uk^T | q_rope | 0]`` [B, T, H, lanes], a pool entry's width, so
    that ONE product with the entries as they lie gives the scores."""
    dtype, nope = config.dtype, config.qk_nope_head_dim
    q_latent = jnp.einsum("bthd,chd->bthc", q_nope,
                          w["wkv_b"].astype(dtype)[..., :nope])
    pad = lanes - config.latent_dim
    return jnp.concatenate(
        [q_latent, q_rope, jnp.zeros((*q_rope.shape[:-1], pad), dtype)],
        axis=-1)


def absorbed_output(w: dict, u, config: XingConfig):
    """The heads' sums over the latents ``u`` [B, T, H, rank] through
    ``W_uv`` and the output projection. Returns [B, T, C]."""
    dtype, nope = config.dtype, config.qk_nope_head_dim
    o = jnp.einsum("bthc,chd->bthd", u,
                   w["wkv_b"].astype(dtype)[..., nope:])
    return _out_projection(w, o, config)


def attend_absorbed(w: dict, q_nope, q_rope, latents, mask,
                    config: XingConfig):
    """The same attention with ``W_uk`` carried into the query and
    ``W_uv`` applied after the sum: scores and values are taken on the
    latents as they lie, ONE read of a position's entry for all heads
    (the query is padded with zeros to the entry's width, and the value
    product runs over the whole entry, its rotary tail dropped after: a
    slice of the gathered view would be a copy of it). The plain form,
    over a gathered view: the engine's decode step computes it in
    ``ops/paged_latent_attention.py``, through the tables."""
    q = absorbed_queries(w, q_nope, q_rope, latents.shape[-1], config)
    scores = jnp.einsum("bthc,bsc->bhts", q, latents,
                        preferred_element_type=F32)
    u = jnp.einsum("bhts,bsc->bthc", _probabilities(scores, mask, config),
                   latents)[..., :config.kv_lora_rank]
    return absorbed_output(w, u, config)


# -------------------------------------------------------------- feed-forward


def dense_ffn(w: dict, x: jax.Array, config: XingConfig) -> jax.Array:
    """A leading layer's SwiGLU. x [B, T, C] (normed)."""
    return moe.swiglu(x, w["w_gate"], w["w_up"], w["w_down"], config.dtype)


def sparse_ffn(w: dict, x: jax.Array, config: XingConfig, experts: dict,
               index):
    """The routed experts and the shared one. x [B, T, C] (normed,
    float32: the router reads it unrounded, the experts in ``dtype``).
    ``w`` is the layer's router and shared expert; its routed experts
    are layer ``index`` of ``experts``, the layers' stacked tensors
    (``moe.split_experts``), of which only the chosen are read.
    Where the layer holds a share of the experts it routes over
    (``config.held``), the chosen experts that are held. Returns (out
    [B, T, C], the chosen experts [B, T, k])."""
    dtype = config.dtype
    _, idx, weights = moe.route(
        x, w["w_router"], config.experts_per_token, config.norm_topk_prob,
        scoring="sigmoid", bias=w["router_bias"],
        scale=config.routed_scaling_factor)
    combine = moe.combine_weights(idx, weights, config.num_experts,
                                  config.held)
    out = moe.touched_expert_ffn(experts, index, x, combine,
                                 dtype).astype(F32)
    if config.num_shared_experts:
        out = out + moe.shared_ffn(w, x, dtype).astype(F32)
    return out, idx
