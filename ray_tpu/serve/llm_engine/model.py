"""Jitted paged-attention steps: gather-by-block-table prefill/decode.

The kernel discipline mirrors ``models.llama.forward_with_cache`` but
reads/writes the PAGED pool instead of per-slot cache rows:

- **scatter**: each new token's k/v lands at
  ``pool[block_table[pos // bs], pos % bs]`` — a 2-level indexed write
  (``.at[blocks, offsets].set``), one per layer inside the scan;
- **gather**: attention keys/values materialize as
  ``pool[block_table]`` → ``[B, M, bs, kv, d]`` reshaped to the flat
  ``[B, S, kv, d]`` view where flat index ``s`` IS the token's global
  position (tables are append-ordered), so the standard causal mask
  ``s <= position`` is unchanged from the dense path;
- **fixed shapes**: batch ``B``, table width ``M`` and chunk length
  ``C`` are compile-time constants — ONE decode program and ONE
  prefill program total, every step hits the jit cache (the
  ``serve.llm`` prototype's discipline, kept);
- **donation**: the pool is donated through every call (decode updates
  in place in HBM); on TPU wrap the calls in
  ``jax_compat.set_mesh(mesh)`` and the same jitted fns become pjit
  (params/pool sharded via ``ray_tpu.parallel.sharding``).

Runs on CPU under tier-1 (plain jnp/einsum — no pallas dependency);
the block/gather structure is what the Ragged Paged Attention kernel
(arxiv 2604.15464) implements natively on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama


def serving_params(config, params: "dict | None" = None,
                   seed: int = 0) -> dict:
    """The weights an engine serves, held in the compute dtype.

    ``params=None`` builds them here from ``PRNGKey(seed)``: weights at
    a real width are made (or loaded) inside the replica, not pickled
    through ``bind()``. The cast runs in the same jit as the
    initialisation, so the float32 model never exists whole; every use
    in the step functions casts to ``config.dtype`` anyway, so holding
    that dtype changes no result and halves what each step reads.
    A caller that wants a reference rebuilds the same weights by calling
    this with the same seed.
    """
    def cast(tree):
        return jax.tree.map(lambda x: x.astype(config.dtype), tree)

    if params is None:
        return jax.jit(lambda key: cast(llama.init_params(config, key)))(
            jax.random.PRNGKey(seed))
    if all(x.dtype == config.dtype for x in jax.tree.leaves(params)):
        return params
    return jax.jit(cast)(params)


def _paged_attention_block(layer: dict, x: jax.Array,
                           positions: jax.Array, pk: jax.Array,
                           pv: jax.Array, block_tables: jax.Array,
                           config, block_size: int,
                           n_valid: "jax.Array | None" = None):
    """One attention block over the paged pool.

    x: [B, T, E] new-token activations at global ``positions`` [B, T]
    (T=1 decode, T=chunk prefill). pk/pv: [num_blocks, bs, kv, d].
    block_tables: [B, M] (append-ordered block ids, 0-padded).
    ``n_valid``: optional scalar — positions at/after it scatter to the
    scratch block instead of the table (prefill chunk padding).
    Returns (out, pk, pv).
    """
    dtype = config.dtype
    h, kv_heads = config.num_heads, config.num_kv_heads
    normed = llama.rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    q = jnp.einsum("ble,ehd->blhd", normed, layer["wq"].astype(dtype))
    k = jnp.einsum("ble,ekd->blkd", normed, layer["wk"].astype(dtype))
    v = jnp.einsum("ble,ekd->blkd", normed, layer["wv"].astype(dtype))
    q = llama.rope(q, positions, config.rope_theta)
    k = llama.rope(k, positions, config.rope_theta)

    # Scatter: token at global position p writes block_table[p // bs]
    # offset p % bs. Padding/inactive rows redirect to scratch block 0
    # (never gathered past the causal mask).
    blocks = jnp.take_along_axis(block_tables, positions // block_size,
                                 axis=1)                      # [B, T]
    offsets = positions % block_size
    if n_valid is not None:
        in_range = jnp.arange(positions.shape[1])[None, :] < n_valid
        blocks = jnp.where(in_range, blocks, 0)
        offsets = jnp.where(in_range, offsets, 0)
    pk = pk.at[blocks, offsets].set(k.astype(pk.dtype))
    pv = pv.at[blocks, offsets].set(v.astype(pv.dtype))

    # Gather: the request's whole context, by block table. Flat index
    # s == global position (append-ordered tables).
    B, M = block_tables.shape
    S = M * block_size
    keys = pk[block_tables].reshape(B, S, kv_heads, config.head_dim)
    values = pv[block_tables].reshape(B, S, kv_heads, config.head_dim)
    if kv_heads != h:
        reps = h // kv_heads
        keys = jnp.repeat(keys, reps, axis=2)
        values = jnp.repeat(values, reps, axis=2)

    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        keys.astype(jnp.float32))
    scores *= config.head_dim ** -0.5
    s_pos = jnp.arange(S)
    mask = s_pos[None, None, None, :] <= positions[:, None, :, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, values.astype(dtype))
    out = jnp.einsum("blhd,hde->ble", out, layer["wo"].astype(dtype))
    return x + out, pk, pv


def _forward_paged(params: dict, pool: dict, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   config, block_size: int,
                   n_valid: "jax.Array | None" = None):
    """Shared prefill/decode forward over the paged pool. Returns
    (logits [B, T, V] f32, updated pool)."""
    x = params["embed"]["tokens"].astype(config.dtype)[tokens]

    def layer_step(x, layer_and_pool):
        layer, pk, pv = layer_and_pool
        x, pk, pv = _paged_attention_block(
            layer, x, positions, pk, pv, block_tables, config,
            block_size, n_valid=n_valid)
        x = llama._mlp_block(layer, x, config)
        return x, (pk, pv)

    x, (k_new, v_new) = lax.scan(
        layer_step, x, (params["layers"], pool["k"], pool["v"]))
    x = llama.rms_norm(x, params["final_norm"], config.rms_norm_eps)
    logits = jnp.einsum("ble,ev->blv", x,
                        params["lm_head"].astype(config.dtype),
                        preferred_element_type=jnp.float32)
    return logits, {"k": k_new, "v": v_new}


def make_decode_step(config, block_size: int):
    """The ONE batched decode program: every active ragged request
    advances one token through a shared ``[B, 1]`` step. Inactive rows
    carry all-zero tables/positions (scratch writes, discarded
    samples)."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step(params, pool, tokens, positions, block_tables, key,
                    temps):
        # tokens [B, 1]; positions [B]; block_tables [B, M]; temps [B].
        logits, pool = _forward_paged(
            params, pool, tokens, positions[:, None], block_tables,
            config, block_size)
        last = logits[:, -1, :]
        greedy = jnp.argmax(last, axis=-1)
        sampled = jax.random.categorical(
            key, last / jnp.maximum(temps, 1e-4)[:, None], axis=-1)
        nxt = jnp.where(temps > 0, sampled, greedy)
        return nxt.astype(jnp.int32), pool

    return decode_step


def make_prefill_chunk(config, block_size: int):
    """The ONE prefill program: a fixed-length chunk of one request's
    prompt scatters into its block table; only the final chunk's
    ``last_idx`` logits row is consumed (the first generated token)."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, pool, tokens, positions, block_table,
                      n_valid, last_idx):
        # tokens [1, C]; positions [1, C]; block_table [1, M];
        # n_valid/last_idx scalars (chunk padding past n_valid goes to
        # scratch; last_idx indexes the final REAL token's logits).
        logits, pool = _forward_paged(
            params, pool, tokens, positions, block_table, config,
            block_size, n_valid=n_valid)
        return logits[0, last_idx, :], pool

    return prefill_chunk
