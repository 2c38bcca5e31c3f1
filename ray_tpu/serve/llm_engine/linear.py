"""The paged steps of a model of linear-attention layers beside full
attention every few layers (``models/kimi_linear.py``: latent attention;
``models/solar_open2.py``: gated softmax attention of grouped queries):
a matrix-valued recurrent state a row, and a pool that only the full
layers own, in ONE cache dict donated through every step. A full layer
knows its pool index, its place among the full layers.

- ``latent`` ``[latent layers, num_blocks, bs, pool_lanes]`` (where the
  full layers are latent): the latent family's pool (``latent.py``),
  with an entry for the layers that ARE latent alone (3 of 13); the
  index is what ``latent._attention`` and the kernel behind it
  (``ops/paged_latent_attention.py``) are handed as the layer. Paged by
  the same tables and allocator as every pool, block 0 the scratch
  block. The decode step reads it absorbed, through the tables, each
  row its own live pages; the prefill chunk gathers its row's view and
  expands it.
- ``k`` and ``v`` ``[full layers, num_blocks, bs, kv heads, d]`` (where
  they are grouped softmax attention): the dense family's pools
  (``model.paged_attention``, which is handed the index as the layer),
  1 of 4 layers here. The decode step reads them through the tables,
  each row its own live pages (``by_row``: the kernel of
  ``ops/paged_kv_attention.py``), so it is ONE program at the whole
  table (``FAMILIES``: ``reads_by_row``); the prefill chunk gathers
  the width of the table it is handed.
- ``kda`` ``[KDA layers, rows, H, d, d]`` float32 and ``conv`` ``[KDA
  layers, kernel - 1, rows, 3Hd]``: the delta rule's state and the
  short convolutions' last inputs, one slot a row, as the hybrid
  family's state (``hybrid.py``). (The rows lie BEFORE the minor
  dimension of ``conv``: with the kernel's 3 there, ``[.., rows, 3,
  3Hd]``, the chip tiles 3 rows as 4 or 8 and its compiler copied the
  whole array into another tiling and back around every program, 0.35
  ms of a decode step; my chip run, PR 50.) A request's row slot
  (``EngineRequest.slot``) is its row of the decode step and its index
  here, so the step reads and writes the state where it lies. A chunk
  at position 0 starts from zeros IN THE PROGRAM, so a slot's last
  tenant and a preempted request's stale state can never show (the
  request is prefilled again from 0 on resume); a chunk's padding and
  an inactive decode row (position 0) advance nothing.

A state does not grow with the context and the pool does: both hang on
the one allocator and the one set of row slots. The stack is the
leading dense layer(s) written out (Solar-Open2 has none), then a scan
over PERIODS ((KDA, KDA, latent, KDA), or (full, KDA, KDA, KDA)) with
the period's layers written out in the body (``kimi_linear`` says why);
the carry is the residual stream in float32, the pool's entries of the
cache dict, the state, the convolutions' inputs and the expert
counters. An expert layer adds the chosen experts it HOLDS
(``config.held``); the counters count those.

One token a row a pass, a row ends by its count: the row bookkeeping
and the step in flight (``ahead``) are the dense model's, the two
programs ``model.py``'s, over ``forward`` here (the prefill chunk's
array carries the row slot, as every ``recurrent`` family's).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import lax

from ray_tpu.models import kimi_linear as kimi
from ray_tpu.models import moe, solar_open2, xing
from ray_tpu.models.llama import rms_norm
from ray_tpu.serve.llm_engine.latent import _attention
from ray_tpu.serve.llm_engine.model import (
    Family,
    paged_attention,
    row_beside_zeros,
)

F32 = jnp.float32


def init_cache(config, num_blocks: int, block_size: int, rows: int,
               chunk_len: int) -> dict:
    heads, d = config.kda_heads, config.kda_head_dim
    if config.full_kind == kimi.LATENT:
        pool = {"latent": jnp.zeros((config.full_layers, num_blocks,
                                     block_size, config.pool_lanes),
                                    config.dtype)}
    else:
        shape = (config.full_layers, num_blocks, block_size,
                 config.num_kv_heads, config.head_dim)
        pool = {"k": jnp.zeros(shape, config.dtype),
                "v": jnp.zeros(shape, config.dtype)}
    return {
        **pool,
        "kda": jnp.zeros((config.kda_layers, rows, heads, d, d),
                         config.state_dtype),
        "conv": jnp.zeros((config.kda_layers, config.conv_kernel - 1, rows,
                           3 * config.kda_width), config.dtype),
    }


def forward(params: dict, cache: dict, tokens, positions, tables, config,
            block_size: int, *, slot=None, n_valid=None, logits_at=None):
    """tokens and positions [B, T], tables [B, M] -> (logits [B, T, V]
    float32, or [B, V] of position ``logits_at`` alone; the cache; the
    expert counters of this pass; the chosen experts [periods, layers of
    a period, B, T, k], which only a check reads). Without ``n_valid``
    it is a decode step: ``T == 1``, row ``i`` is row slot ``i``, a row
    at position 0 is inactive. With it, one request's chunk in row slot
    ``slot``, its first ``n_valid`` positions real, from a zero state
    where it starts at position 0."""
    dtype, eps = config.dtype, config.rms_norm_eps
    decode = n_valid is None
    if decode:
        valid = positions > 0
    else:
        valid = jnp.broadcast_to(jnp.arange(tokens.shape[1]) < n_valid,
                                 tokens.shape)
        fresh = positions[0, 0] == 0
    # What the family declares is what its step does: the engine builds
    # ONE decode program and counts the reads by row on the same word.
    by_row = decode and FAMILIES[config.full_kind].reads_by_row

    def kda(w, h, state, conv, si):
        if decode:
            out, state, c = kimi.kda_step(w, h[:, 0], state, conv[si],
                                          valid[:, 0], config, layer=si)
            return out[:, None], state, conv.at[si].set(c)
        s = jnp.where(fresh, 0, state[si, slot])
        c = jnp.where(fresh, 0, conv[si, :, slot])
        out, s, c = kimi.kda_chunk(w, h[0], s, c, n_valid, config)
        return out[None], state.at[si, slot].set(s), \
            conv.at[si, :, slot].set(c)

    def layer(carry, w, kind, si, pi, experts=None, p=None):
        """One layer: ``si`` its index among the KDA layers or ``pi``
        among the full ones, whichever it is; an expert layer is layer
        ``p`` of ``experts``, its place in the period's stacked expert
        tensors. ``pool`` is the cache dict's pool entries: the latent
        pool, or the keys' and the values'."""
        x, pool, state, conv, counts = carry
        h = rms_norm(x, w["mixer_norm"], eps).astype(dtype)
        if kind == kimi.KDA:
            y, state, conv = kda(w["mixer"], h, state, conv, si)
        elif kind == kimi.LATENT:
            y, latent = _attention(
                w["mixer"], h, positions, pool["latent"], pi, tables, config,
                block_size, n_valid, decode)
            pool = {"latent": latent}
        else:
            y, k, v = paged_attention(
                w["mixer"], h, positions, pool["k"], pool["v"], pi, tables,
                config, block_size, n_valid, by_row=by_row)
            pool = {"k": k, "v": v}
        x = x + y.astype(F32)
        h = rms_norm(x, w["ffn_norm"], eps)
        if experts is not None:
            y, idx = xing.sparse_ffn(w["ffn"], h, config, experts, p)
            counts = counts + moe.routing_counts(
                idx, valid, config.num_experts, config.held)
        else:
            y, idx = xing.dense_ffn(w["ffn"], h, config), None
        return (x + y.astype(F32), pool, state, conv, counts), idx

    kinds, first = config.kinds, config.first_k_dense
    carry = (params["embed"]["tokens"][tokens].astype(F32),
             {k: v for k, v in cache.items() if k not in ("kda", "conv")},
             cache["kda"], cache["conv"],
             jnp.zeros((len(moe.EXPERT_COUNTERS),), jnp.int32))
    for i, w in enumerate(params["first"]):
        carry, _ = layer(carry, w, kinds[i], kinds[:i].count(kimi.KDA),
                         i - kinds[:i].count(kimi.KDA))
    period = config.period_kinds
    kda_before = kinds[:first].count(kimi.KDA)
    full_before = first - kda_before
    kda_a_period = period.count(kimi.KDA)

    # The expert tensors stay out of the scanned ``xs``: the kernel takes
    # a place's stack over the periods whole, and the period's index.
    stacks, rest = zip(*(moe.split_experts(w["ffn"])
                         for w in params["periods"]))
    layers = [{**w, "ffn": ffn} for w, ffn in zip(params["periods"], rest)]

    def one_period(carry, layers_and_index):
        layers, p = layers_and_index
        chosen = []
        for i, w in enumerate(layers):
            kda_so_far = period[:i].count(kimi.KDA)
            carry, idx = layer(
                carry, w, period[i],
                kda_before + p * kda_a_period + kda_so_far,
                full_before + p * (len(period) - kda_a_period)
                + (i - kda_so_far), stacks[i], p)
            chosen.append(idx)
        return carry, jnp.stack(chosen)

    carry, routing = lax.scan(
        one_period, carry, (layers, jnp.arange(config.periods)))
    x, pool, state, conv, counts = carry
    if logits_at is not None:
        x = row_beside_zeros(x, logits_at)
    x = rms_norm(x, params["final_norm"], eps).astype(dtype)
    logits = jnp.einsum("ble,ev->blv", x, params["lm_head"].astype(dtype),
                        preferred_element_type=F32)
    if logits_at is not None:
        logits = logits[:, 0]
    return logits, {**pool, "kda": state, "conv": conv}, counts, routing


FAMILY = Family(
    init_params=kimi.init_params,
    init_cache=init_cache,
    forward=forward,
    recurrent=True,
    reads_by_row=True,
)

#: By the kind of a configuration's full layers (``config.full_kind``).
#: Either kind's decode step reads its pool by row (``forward``).
FAMILIES = {
    kimi.LATENT: FAMILY,
    solar_open2.GQA: dataclasses.replace(
        FAMILY, init_params=solar_open2.init_params),
}
