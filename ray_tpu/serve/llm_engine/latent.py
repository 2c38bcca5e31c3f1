"""The paged steps of a latent-attention model (``models/xing.py``):
one pool that holds, a position and layer, ONE vector of
``kv_lora_rank + qk_rope_head_dim`` values: no heads, no values of
their own.

- ``latent`` ``[layers, num_blocks, bs, pool_lanes]``: a position's
  ``latent_dim`` values (576) in lanes rounded up to the chip's 128
  (640), the tail zero: the chip tiles ``[.., 576]`` as ``[.., 640]``
  in any case, and declared so the pool keeps its blocks contiguous
  (``XingConfig.pool_lanes`` says what the other way cost). Paged by the
  same block tables and allocator as a dense model's keys and values
  (``kv_cache.PagedKVCache``), block 0 the scratch block. A pass writes
  its positions' entries where they belong.
- The two programs read the pool differently. The **decode step**
  reads it absorbed, one query a row, the query carried into the
  latent space, scores and values taken on the latents where they lie,
  every head sharing the one read, and THROUGH THE TABLES
  (``ops/paged_latent_attention.py``): each row its own live pages,
  each latent once, the row's fresh entry beside them: the step has
  one program, at the whole table; no gathered view exists
  (``xing.attend_absorbed`` is the plain form over one). The **prefill
  chunk** gathers its one row's view ``[1, S, pool_lanes]`` with the
  chunk's entries written first, as ``model._forward_paged`` does, and
  expands it (``xing.attend_expanded``) to keys and values a head for
  its many queries.
- The carry of the layer scans is the token's residual STREAMS ``[B, T,
  hc_mult, C]`` in float32 beside the pool and the expert counters; the
  stack is the leading dense layers, then the expert layers (two scans
  over ``params["dense"]`` and ``params["sparse"]``).

One token a row a pass, a row ends by its count: the row bookkeeping
and the step in flight (``ahead``) are the dense model's, the two
programs ``model.py``'s, over ``forward`` here.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ray_tpu.models import moe, xing
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.paged_latent_attention import paged_latent_attention
from ray_tpu.serve.llm_engine.model import Family, row_beside_zeros

F32 = jnp.float32


def init_cache(config, num_blocks: int, block_size: int, rows: int,
               chunk_len: int) -> dict:
    return {"latent": jnp.zeros((config.num_layers, num_blocks, block_size,
                                 config.pool_lanes), config.dtype)}


def _attention(w: dict, x, positions, pool, li, tables, config,
               block_size: int, n_valid, absorbed: bool):
    """One attention sublayer over layer ``li`` of the pool. x [B, T,
    C] (normed) at ``positions`` [B, T]; tables [B, M]; positions of a
    chunk at or past ``n_valid`` write the scratch block. Returns (out
    [B, T, C], pool)."""
    (B, T), M = positions.shape, tables.shape[1]
    q_nope, q_rope = xing.latent_queries(w, x, positions, config)
    entries = xing.latent_entries(w, x, positions, config).astype(pool.dtype)
    blocks = jnp.take_along_axis(tables, positions // block_size, axis=1)
    offsets = positions % block_size
    if n_valid is not None:
        in_range = jnp.arange(T)[None, :] < n_valid
        blocks = jnp.where(in_range, blocks, 0)
        offsets = jnp.where(in_range, offsets, 0)
    written = pool.at[li, blocks, offsets].set(entries)
    if absorbed:
        # One position a row (T == 1). The kernel walks each row's own
        # pages of the pool as the step found it and takes the row's
        # fresh entry beside them; a row at position 0 is inactive.
        at = positions[:, 0]
        q = xing.absorbed_queries(w, q_nope, q_rope, pool.shape[-1], config)
        u = paged_latent_attention(
            q[:, 0], entries[:, 0], pool, tables,
            jnp.where(at > 0, at + 1, 0), li, scale=config.softmax_scale,
            width=config.kv_lora_rank)
        return xing.absorbed_output(w, u[:, None], config), written
    # Flat index s == global position (append-ordered tables).
    S = M * block_size
    latents = written[li, tables].reshape(B, S, -1)
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    return xing.attend_expanded(w, q_nope, q_rope, latents, mask,
                                config), written


def forward(params: dict, cache: dict, tokens, positions, tables, config,
            block_size: int, *, slot=None, n_valid=None, logits_at=None,
            absorbed: "bool | None" = None):
    """tokens and positions [B, T], tables [B, M] -> (logits [B, T, V]
    float32, or [B, V] of position ``logits_at`` alone; the cache;
    the expert counters of this pass; the chosen experts [sparse layers,
    B, T, k], which only a check reads). Without ``n_valid`` it is a
    decode step (``T == 1``, a row at position 0 inactive), absorbed;
    with it a chunk whose first ``n_valid`` positions are real,
    expanded; the tokens counted are those, or the rows past position
    0. ``slot`` is of no use to it: no row owns a cache. ``absorbed`` is
    a keyword only because the expanded step is the reference of the
    absorbed one (``tests/test_xing.py``)."""
    if absorbed is None:
        absorbed = n_valid is None
    dtype, eps = config.dtype, config.rms_norm_eps
    x = params["embed"]["tokens"][tokens].astype(F32)
    streams = jnp.broadcast_to(x[:, :, None, :],
                               (*x.shape[:2], config.hc_mult, x.shape[-1]))
    if n_valid is not None:
        valid = jnp.broadcast_to(jnp.arange(tokens.shape[1]) < n_valid,
                                 tokens.shape)
    else:
        valid = positions > 0
    counts = jnp.zeros((len(moe.EXPERT_COUNTERS),), jnp.int32)

    def layer_step(experts: "dict | None"):
        sparse = experts is not None

        def step(carry, layer_and_index):
            streams, pool, counts = carry
            w, li = layer_and_index
            h, post, res = xing.hyper_mix(w["hc_attn"], streams, config)
            y, pool = _attention(
                w, rms_norm(h, w["attn_norm"], eps).astype(dtype), positions,
                pool, li, tables, config, block_size, n_valid, absorbed)
            streams = xing.hyper_write(streams, y, post, res)
            h, post, res = xing.hyper_mix(w["hc_ffn"], streams, config)
            normed = rms_norm(h, w["mlp_norm"], eps)
            if sparse:
                y, idx = xing.sparse_ffn(w, normed, config, experts,
                                         li - config.first_k_dense)
                counts = counts + moe.routing_counts(idx, valid,
                                                     config.num_experts)
            else:
                y, idx = xing.dense_ffn(w, normed, config), None
            return (xing.hyper_write(streams, y, post, res), pool,
                    counts), idx
        return step

    carry, routing = (streams, cache["latent"], counts), None
    dense = config.first_k_dense
    if dense:
        carry, _ = lax.scan(layer_step(None), carry,
                            (params["dense"], jnp.arange(dense)))
    if config.sparse_layers:
        # The expert tensors stay out of the scanned ``xs``: the kernel
        # takes them stacked and the layer's index among the sparse.
        experts, layers = moe.split_experts(params["sparse"])
        carry, routing = lax.scan(
            layer_step(experts), carry,
            (layers, jnp.arange(dense, config.num_layers)))
    streams, pool, counts = carry
    x = jnp.sum(streams, axis=-2)
    if logits_at is not None:
        x = row_beside_zeros(x, logits_at)
    x = rms_norm(x, params["final_norm"], eps).astype(dtype)
    logits = jnp.einsum("ble,ev->blv", x, params["lm_head"].astype(dtype),
                        preferred_element_type=F32)
    if logits_at is not None:
        logits = logits[:, 0]
    return logits, {"latent": pool}, counts, routing


FAMILY = Family(
    init_params=xing.init_params,
    init_cache=init_cache,
    forward=forward,
    reads_by_row=True,
)
