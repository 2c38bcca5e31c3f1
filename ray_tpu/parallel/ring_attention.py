"""Ring attention: sequence/context parallelism over the ICI ring.

Absent from the reference (SURVEY §5: no ring attention / Ulysses /
sequence parallelism anywhere in Ray) — built natively here because a
TPU-first ML platform must handle long context as a core capability.

Design (Liu et al., Ring Attention; blockwise flash accumulation):
each of the N devices on the ``sp`` axis holds a sequence shard
``[B, L/N, H, D]`` of Q, K, V. K/V shards rotate around the ring via
``lax.ppermute`` while each device accumulates its queries' attention
over every K/V block with numerically stable log-sum-exp rescaling.
Communication (neighbor ppermute over ICI) overlaps with the per-block
attention compute that XLA schedules between permutes.

Also provides Ulysses-style all-to-all sequence parallelism: resharding
[B, L/N, H, D] -> [B, L, H/N, D] so each device runs full-sequence
attention for a head subset — cheaper at moderate L, while ring wins at
very long L (no full-sequence materialization).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _block_attention(q, k, v, bias, scale):
    """One (q-block, kv-block) flash step: returns (unnormalized o, lse-max
    pieces). Shapes: q [B,Lq,H,D], k/v [B,Lk,H,D], bias broadcastable to
    [B,H,Lq,Lk]."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        scores = scores + bias
    block_max = jnp.max(scores, axis=-1)  # [B,H,Lq]
    # Fully-masked rows have block_max = -inf; subtracting it from -inf
    # scores would produce NaN, so use 0 there (exp(-inf - 0) = 0).
    safe_max = jnp.where(jnp.isfinite(block_max), block_max, 0.0)
    probs = jnp.exp(scores - safe_max[..., None])
    block_sum = jnp.sum(probs, axis=-1)  # [B,H,Lq]
    block_out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return block_out, block_max, block_sum


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp", causal: bool = True,
                   scale: float | None = None) -> jax.Array:
    """Ring attention over ``axis_name``; call inside shard_map/pjit.

    Args are local shards [B, L_local, H, D]; sequence order along the
    ring follows axis index (device i holds tokens [i*L_local,
    (i+1)*L_local)).
    """
    num_shards = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, l_local, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5

    o_acc = jnp.zeros((b, l_local, h, d), dtype=jnp.float32)
    l_acc = jnp.zeros((b, h, l_local), dtype=jnp.float32)
    m_acc = jnp.full((b, h, l_local), -jnp.inf, dtype=jnp.float32)

    q_pos = my_idx * l_local + jnp.arange(l_local)

    def step(i, carry):
        o_acc, l_acc, m_acc, k_cur, v_cur = carry
        # Block i came from device (my_idx + i) mod N (ppermute shifts
        # shards "down" the ring: after s rotations we hold the shard that
        # started s positions up).
        src = (my_idx + i) % num_shards
        if causal:
            kv_pos = src * l_local + jnp.arange(l_local)
            mask = q_pos[:, None] >= kv_pos[None, :]  # [Lq, Lk]
            bias = jnp.where(mask, 0.0, -jnp.inf)[None, None]
        else:
            bias = None
        blk_o, blk_m, blk_s = _block_attention(q, k_cur, v_cur, bias, scale)
        new_m = jnp.maximum(m_acc, blk_m)
        # Guard fully-masked blocks (all -inf) against NaN rescaling.
        safe = jnp.isfinite(new_m)
        alpha = jnp.where(safe, jnp.exp(m_acc - jnp.where(safe, new_m, 0.0)), 0.0)
        beta = jnp.where(safe, jnp.exp(blk_m - jnp.where(safe, new_m, 0.0)), 0.0)
        l_new = l_acc * alpha + blk_s * beta
        o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
                 + blk_o.astype(jnp.float32) * beta.transpose(0, 2, 1)[..., None])
        perm = [(j, (j - 1) % num_shards) for j in range(num_shards)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return o_new, l_new, new_m, k_next, v_next

    o_acc, l_acc, m_acc, _, _ = lax.fori_loop(
        0, num_shards, step, (o_acc, l_acc, m_acc, k, v))
    denom = jnp.where(l_acc > 0, l_acc, 1.0).transpose(0, 2, 1)[..., None]
    return (o_acc / denom).astype(q.dtype)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, causal: bool = True) -> jax.Array:
    """shard_map wrapper: [B, L, H, D] global arrays, B over dp/fsdp, L over
    sp, H over tp."""
    spec = P(("dp", "fsdp"), "sp", "tp", None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    def inner(q, k, v):
        return ring_attention(q, k, v, axis_name="sp", causal=causal)

    return inner(q, k, v)


def ring_attention_gspmd(q: jax.Array, k: jax.Array, v: jax.Array,
                         causal: bool = True) -> jax.Array:
    """Ring attention callable from *inside* a GSPMD-jitted model.

    Uses the ambient context mesh (``jax.set_mesh``): the surrounding
    model runs under plain jit with sharding propagation, while this op
    drops into shard_map to run the explicit ppermute ring over ``sp``.
    Batch stays over (dp, fsdp), heads over tp.
    """
    spec = P(("dp", "fsdp"), "sp", "tp", None)

    @functools.partial(jax.shard_map,
                       in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def inner(q, k, v):
        return ring_attention(q, k, v, axis_name="sp", causal=causal)

    return inner(q, k, v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "sp", causal: bool = True,
                      attn_fn: Callable | None = None) -> jax.Array:
    """Ulysses-style SP: all-to-all seq->heads, local full attention,
    all-to-all back. Requires H % axis_size == 0. Call inside shard_map."""
    n = lax.psum(1, axis_name)
    b, l_local, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"num heads {h} not divisible by sp axis size {n}")

    def seq_to_heads(x):
        # [B, L/n, H, D] -> [B, L, H/n, D]
        x = x.reshape(b, l_local, n, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=False)
        return x.reshape(b, l_local * n, h // n, d)

    def heads_to_seq(x):
        # Inverse of seq_to_heads: [B, L, H/n, D] -> [B, L/n, H, D].
        x = x.reshape(b, n, l_local, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=False)
        return x.reshape(b, l_local, h, d)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if attn_fn is None:
        attn_fn = functools.partial(plain_attention, causal=causal)
    og = attn_fn(qg, kg, vg)
    return heads_to_seq(og)


def plain_attention(q, k, v, causal: bool = True,
                    scale: float | None = None) -> jax.Array:
    """Reference full attention [B, L, H, D] (the correctness oracle)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).astype(q.dtype)
