"""The paged steps of a state-space stack around a few attention layers
(``models/jamba.py``: 26 Mamba-1 layers, 2 of softmax attention on ONE
key-value head): a float32 state a row beside key and value pools that
only the attention layers own, in ONE cache dict donated through every
step. An attention layer knows its pool index, its place among them.

- ``k`` and ``v`` ``[attention layers, num_blocks, bs * kv heads, d]``:
  the dense family's pools (``model.paged_attention``, which is handed
  the index as the layer) with a page's positions and heads in ONE
  dimension, row ``s * kv + k`` head ``k`` of position ``s``: with one
  key-value head the dense family's ``[.., bs, 1, d]`` would put that 1
  where the chip tiles 2 to 16 rows (the kernel's memory view of it is
  ``[.., bs, 2, d]``, and its compiler refuses the slice of one; my
  compile for the v5e, PR 60), and ``[bs, d]`` is the matrix the kernel
  multiplies anyway. Paged by the same tables and allocator as every
  pool, block 0 the scratch block. The decode step reads them through
  the tables, each row its own live pages (``by_row``:
  ``ops/paged_kv_attention.py``), so it is ONE program at the whole
  table; the prefill chunk gathers its row's view.
- ``ssm`` ``[Mamba layers, rows, Di, N]`` float32 and ``conv`` ``[Mamba
  layers, d_conv - 1, rows, Di]``: the recurrence's state and the short
  convolution's last inputs, one slot a row, as the linear family's
  (``linear.py`` says why the rows lie before ``conv``'s minor
  dimension). A request's row slot (``EngineRequest.slot``) is its row
  of the decode step and its index here, so the step reads and writes
  the state where it lies. A chunk at position 0 starts from zeros IN
  THE PROGRAM, so a slot's last tenant and a preempted request's stale
  state can never show; a chunk's padding and an inactive decode row
  (position 0) advance nothing.

The stack is a scan over the PERIODS; a period is a scan over the Mamba
layers before its attention layer, that layer, and a scan over the
Mamba layers after it, so a program's text holds the Mamba layer's body
twice and the attention layer's once, whatever the depth. Each takes
its layer of the stacked weights by index (the stacks are closed over,
not scanned: a period's slice of them would be a copy of seven layers).
The carry is the residual stream in float32, the pools, the state and
the convolutions' inputs.

One token a row a pass, a row ends by its count: the row bookkeeping
and the step in flight are the dense model's, the two programs
``model.py``'s, over ``forward`` here (the prefill chunk's array carries
the row slot, as every ``recurrent`` family's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import jamba, moe
from ray_tpu.models import phi4flash as phi
from ray_tpu.models.llama import rms_norm
from ray_tpu.serve.llm_engine.model import (
    Family,
    paged_attention,
    row_beside_zeros,
)

F32 = jnp.float32


def init_cache(config, num_blocks: int, block_size: int, rows: int,
               chunk_len: int) -> dict:
    pool = (config.attn_layers, num_blocks,
            block_size * config.num_kv_heads, config.head_dim)
    return {
        "k": jnp.zeros(pool, config.dtype),
        "v": jnp.zeros(pool, config.dtype),
        "ssm": jnp.zeros((config.mamba_layers, rows, config.d_inner,
                          config.d_state), config.state_dtype),
        "conv": jnp.zeros((config.mamba_layers, config.d_conv - 1, rows,
                           config.d_inner), config.dtype),
    }


def forward(params: dict, cache: dict, tokens, positions, tables, config,
            block_size: int, *, slot=None, n_valid=None, logits_at=None):
    """tokens and positions [B, T], tables [B, M] -> (logits [B, T, V]
    float32, or [B, V] of position ``logits_at`` alone; the cache; None
    twice: no experts). Without ``n_valid`` it is a decode step: ``T == 1``, row ``i`` is row
    slot ``i``, a row at position 0 is inactive. With it, one request's
    chunk in row slot ``slot``, its first ``n_valid`` positions real,
    from a zero state where it starts at position 0."""
    dtype, eps = config.dtype, config.rms_norm_eps
    decode = n_valid is None
    if decode:
        valid = positions[:, 0] > 0
    else:
        fresh = positions[0, 0] == 0
    by_row = decode and FAMILY.reads_by_row

    def ssm(w, h, state, conv, si):
        if decode:
            out, _, s, c = phi.ssm_step(w, h[:, 0], state[si], conv[si],
                                        valid, config, taps_first=True)
            return out[:, None], state.at[si].set(s), conv.at[si].set(c)
        s = jnp.where(fresh, 0, state[si, slot])
        c = jnp.where(fresh, 0, conv[si, :, slot])
        out, _, s, c = phi.ssm_chunk(w, h[0], s, c, n_valid, config)
        return out[None], state.at[si, slot].set(s), \
            conv.at[si, :, slot].set(c)

    def ffn(x, w):
        h = rms_norm(x, w["ffn_norm"], eps)
        return x + moe.swiglu(h, **w["ffn"], dtype=dtype).astype(F32)

    def layer_of(stack, index):
        return jax.tree.map(lambda t: t[index], stack)

    def mamba_run(carry, first, count: int):
        """``count`` Mamba layers, from the stack's layer ``first``."""
        def one(carry, si):
            x, state, conv = carry
            w = layer_of(params["mamba"], si)
            h = rms_norm(x, w["mixer_norm"], eps).astype(dtype)
            y, state, conv = ssm(w["mixer"], h, state, conv, si)
            return (ffn(x + y.astype(F32), w), state, conv), None

        if not count:
            return carry
        return lax.scan(one, carry, first + jnp.arange(count))[0]

    before = config.attn_layer_offset
    after = config.attn_layer_period - before - 1

    def one_period(carry, p):
        x, pool_k, pool_v, state, conv = carry
        x, state, conv = mamba_run((x, state, conv), p * (before + after),
                                   before)
        w = layer_of(params["attn"], p)
        h = rms_norm(x, w["mixer_norm"], eps).astype(dtype)
        y, pool_k, pool_v = paged_attention(
            w["mixer"], h, positions, pool_k, pool_v, p, tables, config,
            block_size, n_valid, by_row=by_row)
        x = ffn(x + y.astype(F32), w)
        x, state, conv = mamba_run(
            (x, state, conv), p * (before + after) + before, after)
        return (x, pool_k, pool_v, state, conv), None

    table = params["embed"]["tokens"]
    (x, pool_k, pool_v, state, conv), _ = lax.scan(
        one_period, (table[tokens].astype(F32), cache["k"], cache["v"],
                     cache["ssm"], cache["conv"]),
        jnp.arange(config.periods))
    if logits_at is not None:
        x = row_beside_zeros(x, logits_at)
    x = rms_norm(x, params["final_norm"], eps).astype(dtype)
    # The tied head, with the table as the product's LEFT operand
    # (``hybrid.forward`` says what the other order costs).
    logits = lax.dot_general(table.astype(dtype), x,
                             (((1,), (2,)), ((), ())),
                             preferred_element_type=F32)        # [V, B, T]
    logits = jnp.moveaxis(logits, 0, -1)
    if logits_at is not None:
        logits = logits[:, 0]
    return logits, {"k": pool_k, "v": pool_v, "ssm": state,
                    "conv": conv}, None, None


FAMILY = Family(
    # Held in the dtype it is served in from the draw on: what
    # ``serving_params`` then casts is already cast.
    init_params=lambda config, key: jamba.init_params(config, key,
                                                      config.dtype),
    init_cache=init_cache,
    forward=forward,
    recurrent=True,
    reads_by_row=True,
)
