"""Jitted paged-attention steps: a chunk gathers, a step reads by row.

The forward of ``models.llama`` for one chunk or one token per row,
reading and writing the PAGED pool:

- **the pool is a carry**: the whole ``[layers, num_blocks, bs, kv, d]``
  pool rides through the layer scan beside the activations, and each
  layer reads and writes its own slice by index. With the donation the
  jitted steps declare, the update happens in the donated buffer: no
  copy of the pool and no per-layer rewrite of a stacked output;
- **scatter**: each new token's k/v lands at
  ``pool[layer, block_table[pos // bs], pos % bs]`` — one indexed write
  (``.at[layer, blocks, offsets].set``) per layer inside the scan;
- **gather** (a chunk, a block pass): attention keys/values are
  ``pool[layer, block_table]`` → ``[B, M, bs, kv, d]`` reshaped to the
  flat ``[B, S, kv, d]`` view where flat index ``s`` IS the token's
  global position (tables are append-ordered), so the standard causal
  mask ``s <= position`` is the one a contiguous cache would use. They
  stay in the pool's dtype and are never repeated per query head;
- **grouped attention**: queries are viewed ``[B, T, kv, reps, d]`` and
  contracted against the gathered keys with float32 accumulation;
  scale, mask and softmax run in float32, the probabilities are cast
  to ``config.dtype`` and contracted with the gathered values. ``reps
  == 1`` (no grouping) is the same code;
- **by row** (a decode step, ``Family.reads_by_row``): nothing is
  gathered; ``ops/paged_kv_attention.py`` walks each busy row's own
  pages where they lie, in the same precisions, an inactive row none;
- **fixed shapes**: batch ``B``, table width ``M`` and chunk length
  ``C`` are compile-time constants: one prefill program a table width
  the engine hands it (``engine.table_widths``) and one decode program
  a width a step can be given (the whole table alone by row; the same
  three for the block pass, which gathers), every step hits the cache;
- **the head on one row**: a prefill chunk computes the final norm and
  the logits of the one position that is read (its last real token);
- **donation**: the pool is donated through every call (decode updates
  in place in HBM); on TPU wrap the calls in
  ``jax_compat.set_mesh(mesh)`` and the same jitted fns become pjit
  (params/pool sharded via ``ray_tpu.parallel.sharding``).

A configuration of another family (``family(config)``, the one place it
is looked up) brings its own cache and ONE forward of ``Family``'s
signature; the two programs and their one-array-a-pass contract are
written once, here, over it:
``hybrid.py`` for layers of several kinds over three caches,
``latent.py`` for latent attention over a pool of one vector a position
(absorbed in the decode step, expanded in the prefill chunk) under a
residual path of several streams, ``linear.py`` for a matrix-valued
recurrent state a row beside a pool that some layers own (a latent
pool, or key and value pools through ``paged_attention`` here; the
decode step reads either by row), ``mamba.py`` for a state-space stack
whose few attention layers own key and value pools of one head, and
``BLOCKWISE`` below for generation by diffusion over blocks: the same
layers and pool, ``T = block_length`` query rows a row of the batch, a
mask that lets a position see all of its own block, and a pass that
fixes 0 to ``block_length`` of a row's masked positions.

Runs on CPU under tier-1 (plain jnp/einsum, and the ``by_row`` kernel
interpreted); the block/gather structure is what the Ragged Paged
Attention kernel (arxiv 2604.15464) implements natively on TPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import llama, moe
from ray_tpu.serve.llm_engine.kv_cache import PagedKVCache
from ray_tpu.serve.llm_engine.scheduler import MASKED


#: In a decode row's token column: the row's token is the step before's,
#: still on the device (the decode program's ``prev``).
PREV = -1


def _row_of_one(req, ahead: bool = False):
    """An autoregressive row: its last token, at the next position.
    ``ahead``: the row one pass on, while the pass before it is unread:
    the token that pass makes, at the position after."""
    if ahead:
        return PREV, req.position + 1, req.temperature, req.block_table
    return req.last_token, req.position, req.temperature, req.block_table


def _advance_one(req, out):
    """An autoregressive row's pass: the next token, one position on."""
    req.last_token = int(out)
    req.position += 1
    return [req.last_token], False


def _lead_one(req, max_tokens: int):
    """An autoregressive row with a pass in flight: one position on,
    unless that pass's token is its last (it has one left to make, or
    its table ends)."""
    if req.remaining <= 1 or req.position + 1 >= max_tokens:
        return None
    return 1


# The engine's two programs and the packers of their host arrays,
# written ONCE over whatever forward the configuration's family names
# (``Family.forward``). Everything a pass decides on the host is in ONE
# int32 array, so that a pass is one call into JAX (the transfer rides
# the dispatch), and the sampling key is carried on the device. They are
# traced under the names ``decode_step`` and ``prefill_chunk``, by which
# the benchmark's readers find them in a trace. Each array's layout is
# known to its packer and its program, here, alone.


def pack_decode_rows(batch: int, width: int, active,
                     slots=None) -> np.ndarray:
    """The decode program's host array, int32 ``[batch, 3 + width]``:
    per row its token, position, temperature (the float32's bits) and
    block table, from ``active``'s ``(token, position, temperature,
    table)``, each in the row ``slots`` gives it (the engine: the
    request's row slot; without ``slots`` in order); the other rows stay
    zero (inactive). A token of ``PREV`` stands for the one the step
    before made for that row, which the host has not read."""
    rows = np.zeros((batch, 3 + width), dtype=np.int32)
    temps = rows[:, 2].view(np.float32)
    for i, (token, position, temperature, table) in zip(
            slots or range(batch), active):
        rows[i, 0], rows[i, 1], temps[i] = token, position, temperature
        rows[i, 3:3 + len(table)] = table
    return rows


def row_tokens(rows, prev=None):
    """The tokens ``[B, 1]`` of ``pack_decode_rows``' array, a row's
    entry of ``prev`` ``[B]`` where its token is ``PREV``."""
    tokens = rows[:, :1]
    if prev is None:
        return tokens
    return jnp.where(tokens < 0, prev[:, None], tokens)


def make_engine_decode_step(config, block_size: int):
    """The ONE batched decode program of the configuration's family:
    every busy row advances one token through a shared ``[B, 1]`` step
    of its ``forward`` (row ``i`` is row slot ``i``; an inactive row
    carries an all-zero table and position 0: scratch writes, no state
    moved, a discarded sample). On ``pack_decode_rows``' array and the
    carried sampling key, which is split here and comes back as the
    fourth result (not donated: a failed step leaves the caller's key
    usable). ``prev`` is the first result of the step before, ``[B]`` on
    the device and not donated either: a row whose token is ``PREV``
    takes its own entry of it. Without ``prev`` every token is the
    array's. ``expert_stats``: ``moe.init_stats()`` of a sparse model,
    or None."""
    forward = family(config).forward

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step(params, cache, rows, key, expert_stats=None, prev=None):
        key, sub = jax.random.split(key)
        temps = lax.bitcast_convert_type(rows[:, 2], jnp.float32)
        logits, cache, counts, _ = forward(
            params, cache, row_tokens(rows, prev), rows[:, 1:2],
            rows[:, 3:], config, block_size)
        return sample_next(logits[:, -1, :], sub, temps), cache, \
            _accumulated(expert_stats, counts), key

    return decode_step


def _chunk_layout(recurrent: bool, chunk_len: int):
    """Where the prefill program's host array holds the chunk's tokens,
    their positions and the block table: behind ``n_valid``,
    ``last_idx`` and, where a row owns a state, its row slot."""
    tokens_at = 2 + recurrent
    return tokens_at, tokens_at + chunk_len, tokens_at + 2 * chunk_len


def make_engine_prefill_chunk(config, block_size: int, chunk_len: int):
    """The prefill program of the configuration's family (one a table
    width the engine hands it): a fixed-length chunk of one request's
    prompt through its ``forward``, on ``Family.pack_prefill_chunk``'s
    array. Only the ``last_idx`` logits row is computed, and only the
    final chunk's is consumed (the first generated token)."""
    of = family(config)
    tokens_at, positions_at, table_at = _chunk_layout(of.recurrent, chunk_len)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill_chunk(params, cache, chunk, expert_stats=None):
        logits, cache, counts, _ = of.forward(
            params, cache, chunk[None, tokens_at:positions_at],
            chunk[None, positions_at:table_at], chunk[None, table_at:],
            config, block_size, slot=chunk[2] if of.recurrent else None,
            n_valid=chunk[0], logits_at=chunk[1])
        return logits[0], cache, _accumulated(expert_stats, counts)

    return prefill_chunk


@dataclasses.dataclass(frozen=True)
class Family:
    """What the engine asks of a model family, chosen ONCE from the
    configuration by ``family``. A family's module supplies its weights
    (``init_params``), its cache (``init_cache``: one dict, donated
    through every step) and ONE forward over it, of the one signature

        forward(params, cache, tokens [B, T], positions [B, T],
                tables [B, M], config, block_size, *, slot=None,
                n_valid=None, logits_at=None)
            -> (logits, cache, expert counts, routing)

    a decode step where ``n_valid`` is None (``T == 1``, row ``i`` is
    row slot ``i``, a row at position 0 inactive), else one request's
    chunk in row slot ``slot`` whose first ``n_valid`` positions are
    real; logits [B, T, V] float32, or [B, V] of position ``logits_at``
    alone; counts and routing None for a dense model. The two programs
    and the packers of their host arrays are this module's, over that
    forward: no family writes them, but one whose decode PASS is another
    (diffusion over blocks) names that pass's program and packer.

    ``ring_positions(config, block_size, chunk_len)`` is how many
    positions a row of its window cache holds (0: it has none);
    ``recurrent``: a row owns a state slot that a request's first chunk
    resets, so the chunk's host array carries the slot;
    ``reads_by_row``: its decode step reads the pool through the tables
    a row at a time, each busy row the whole pages that hold its
    positions before the step's own and its fresh entry beside them
    (``ops/paged_latent_attention.py`` a latent pool,
    ``ops/paged_kv_attention.py`` key and value pools), where the others
    gather the step's table width for every row: what
    ``kv_positions_read`` counts."""
    init_params: Callable
    init_cache: Callable    # (config, num_blocks, block_size, rows, chunk)
    forward: Callable
    # This module's, unless the family's decode PASS is another. A maker
    # is handed the configuration and finds the family by it; the
    # chunk's packer is handed none, and is the method below.
    make_engine_decode_step: Callable = make_engine_decode_step
    make_engine_prefill_chunk: Callable = make_engine_prefill_chunk
    pack_decode_rows: Callable = pack_decode_rows
    ring_positions: Callable = lambda config, block_size, chunk_len: 0
    recurrent: bool = False
    reads_by_row: bool = False
    # ``init_params``' tree in the layout the family's programs read
    # where it lies: pure, applied ONCE to the weights an engine will
    # hold (``serving_params(laid=True)``; this module's:
    # ``lay_for_serving``); a tree already so passes through.
    lay_params: Callable = lambda params: params
    # A busy row on the host: what ``pack_decode_rows`` is given for a
    # request (``row_of(req)``), and what a pass made of it
    # (``advance(req, out)``, ``out`` the row's slice of the program's
    # first result): the request's state moved on, and (the tokens the
    # pass made final for emission, whether it was a finishing pass).
    row_of: Callable = _row_of_one
    advance: Callable = _advance_one
    # The same row while its pass is in flight unread, from the state
    # that pass has not yet moved: ``ahead(req)``, whether what its NEXT
    # pass carries follows from counting alone, so that the engine can
    # launch it on this pass's result where it lies on the device (the
    # program's ``prev``); if so ``lead(req, max_tokens)``, the positions
    # the pass in flight moves the row on (None: that pass is its last,
    # by its count or its table's end), and ``row_of(req, True)``, the
    # row one pass on.
    ahead: Callable = lambda req: True
    lead: Callable = _lead_one

    def pack_prefill_chunk(self, chunk_len: int, width: int, tokens,
                           start: int, table, slot: int = 0) -> np.ndarray:
        """The prefill program's host array, int32 ``[2 + 2 * chunk_len
        + width]``, or 3 + for a ``recurrent`` family: ``n_valid``,
        ``last_idx``, for such a family the request's row ``slot`` (of
        no use to a model without a per-row cache), then the chunk's
        ``tokens`` (at most ``chunk_len``, at global positions
        ``start...``), their positions and the request's block table,
        each zero-padded."""
        tokens_at, positions_at, table_at = _chunk_layout(self.recurrent,
                                                          chunk_len)
        n = len(tokens)
        chunk = np.zeros((table_at + width,), dtype=np.int32)
        chunk[:tokens_at] = (n, n - 1, slot)[:tokens_at]
        chunk[tokens_at:tokens_at + n] = tokens
        chunk[positions_at:positions_at + n] = np.arange(start, start + n)
        chunk[table_at:table_at + len(table)] = table
        return chunk


def family(config) -> Family:
    """The one place a configuration's family is looked up: a
    configuration names it (``family = "hybrid"``:
    ``models/phi4flash.py``; ``"latent"``: ``models/xing.py``;
    ``"linear"``: ``models/kimi_linear.py`` and ``models/solar_open2.py``,
    by the kind of their full layers; ``"mamba"``: ``models/jamba.py``,
    a state-space stack around a few attention layers) or has a
    ``block_length`` (generation by diffusion over blocks); otherwise it
    is the stack of identical layers over one paged pool of this module,
    one token a row a step."""
    named = getattr(config, "family", "paged")
    if named == "hybrid":
        from ray_tpu.serve.llm_engine import hybrid

        return hybrid.FAMILY
    if named == "latent":
        from ray_tpu.serve.llm_engine import latent

        return latent.FAMILY
    if named == "linear":
        from ray_tpu.serve.llm_engine import linear

        return linear.FAMILIES[config.full_kind]
    if named == "mamba":
        from ray_tpu.serve.llm_engine import mamba

        return mamba.FAMILY
    if getattr(config, "block_length", 0) > 0:
        return _blockwise(config.block_length)
    return PAGED


def serving_params(config, params: "dict | None" = None,
                   seed: int = 0, laid: bool = False) -> dict:
    """The weights an engine serves, held in the compute dtype.

    ``params=None`` builds them here from ``PRNGKey(seed)``: weights at
    a real width are made (or loaded) inside the replica, not pickled
    through ``bind()``. The cast runs in the same jit as the
    initialisation, so the float32 model never exists whole; every use
    in the step functions casts to ``config.dtype`` anyway, so holding
    that dtype changes no result and halves what each step reads.
    A caller that wants a reference rebuilds the same weights by calling
    this with the same seed.

    Returns the layout of the family's ``init_params`` (this module's:
    ``wq`` [n, E, H, D], ``wk`` and ``wv`` [n, E, KV, D]) and keeps
    doing so: the benchmark's references, ``chip_smoke.py`` and
    ``benchmark/sizing.py`` read that one, as training and
    ``llama.forward`` do. ``laid`` is an ENGINE's call: the same values
    in the layout the family's programs take (``Family.lay_params``),
    which is what it holds. Built here, they are laid in the program
    that makes them: no weight exists twice, and an engine's start has
    no program more (the laying alone compiles for 0.9 s at Mistral's
    widths, under the second from which the persistent cache keeps a
    program: my chip run, PR 61). A caller's tree in the compute dtype
    is laid over the leaves that change alone, and never donated.
    """
    lay = family(config).lay_params if laid else lambda tree: tree

    def cast(tree):
        return jax.tree.map(lambda x: x.astype(config.dtype), tree)

    if params is None:
        init_params = family(config).init_params
        # The key is made IN the program: before it, two more to fetch.
        return jax.jit(lambda seed: lay(cast(init_params(
            config, jax.random.PRNGKey(seed)))))(np.uint32(seed % 2 ** 32))
    if all(x.dtype == config.dtype for x in jax.tree.leaves(params)):
        return lay(params)
    return jax.jit(lambda tree: lay(cast(tree)))(params)


#: A layer's three projections in front of the scores, in the order
#: ``lay_for_serving`` puts them side by side.
PROJECTIONS = ("wq", "wk", "wv")


@jax.jit
def _side_by_side(*stacked):
    """[n, E, heads, D] each -> [n, E, all their heads * D]."""
    n, e = stacked[0].shape[:2]
    return jnp.concatenate([w.reshape(n, e, -1) for w in stacked], axis=-1)


def lay_for_serving(params: dict) -> dict:
    """``llama.init_params``' tree as an engine holds it: the layers'
    ``wq`` [n, E, H, D], ``wk`` and ``wv`` [n, E, KV, D] become ONE
    ``wqkv`` [n, E, (H + 2 KV) D], side by side in that order, which
    ``llama.qkv_of_normed`` takes by finding it. Pure, and the same
    values; a tree that is laid already passes through. On arrays it is
    one program over those three leaves: every other leaf of the result
    IS the one given, and once the caller lets the three go the device
    holds each weight once (nothing is donated: no result is the size of
    an operand, so a donation would alias nothing, and a caller's arrays
    stay the caller's).

    Why: sliced off the stack, a layer of ``wq`` is tiled over (H, D)
    and the contracted E lies in no tile, so the chip's compiler copied
    each of the three into a layout its product could take in front of
    every product of every step (``constant_dynamic-slice_fusion``: 1.13
    of an 11.39 ms Mistral step; ledger, PR 60). A layer of ``wqkv`` is
    tiled over (E, width), as the feed-forward's weights are, and is
    sliced inside its product's fusion
    (``tests/test_chip_compile_paged.py``)."""
    layers = params["layers"]
    if "wqkv" in layers:
        return params
    kept = {k: v for k, v in layers.items() if k not in PROJECTIONS}
    kept["wqkv"] = _side_by_side(*(layers[name] for name in PROJECTIONS))
    return {**params, "layers": kept}


def _paged_attention_block(layer: dict, x: jax.Array,
                           positions: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, li: jax.Array,
                           block_tables: jax.Array, config,
                           block_size: int,
                           n_valid: "jax.Array | None" = None,
                           by_row: bool = False):
    """One attention block over layer ``li`` of the paged pool: the
    input norm, ``paged_attention`` and the residual. x: [B, T, E]
    new-token activations. Returns (out, pool_k, pool_v)."""
    normed = llama.rms_norm(x, layer["attn_norm"], config.rms_norm_eps)
    out, pool_k, pool_v = paged_attention(
        layer, normed, positions, pool_k, pool_v, li, block_tables, config,
        block_size, n_valid, by_row)
    return x + out, pool_k, pool_v


def _attend_gathered(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                     li: jax.Array, block_tables: jax.Array,
                     positions: jax.Array, config, block_size: int):
    """q [B, T, H, d] at ``positions`` over the gathered view of entry
    ``li`` of the WRITTEN pools, the table's whole width for every row.
    Returns [B, T, H, d]. (For a chunk or a block: ONE query row a head
    the TPU's compiler lowers over a float32 copy of the keys, PR 25.)"""
    dtype = config.dtype
    h, kv_heads, d = config.num_heads, config.num_kv_heads, config.head_dim
    (B, T), M = positions.shape, block_tables.shape[1]
    reps = h // kv_heads
    # Gather: the request's whole context, by block table. Flat index
    # s == global position (append-ordered tables).
    S = M * block_size
    keys = pool_k[li, block_tables].reshape(B, S, kv_heads, d)
    values = pool_v[li, block_tables].reshape(B, S, kv_heads, d)

    # Query head k * reps + r reads key-value head k: the mapping of
    # llama._attention_block's jnp.repeat(k, reps, axis=2).
    q = q.reshape(B, T, kv_heads, reps, d)
    scores = jnp.einsum("btkrd,bskd->bkrts", q, keys,
                        preferred_element_type=jnp.float32)
    scores *= d ** -0.5
    horizon = positions  # the last position a query sees: causal
    if config.block_length:
        # Diffusion over blocks: all of its own block, and the earlier.
        horizon = positions // config.block_length * config.block_length \
            + config.block_length - 1
    mask = jnp.arange(S)[None, None, :] <= horizon[:, :, None]    # [B,T,S]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bkrts,bskd->btkrd", probs, values.astype(dtype))
    return out.reshape(B, T, h, d)


def paged_attention(layer: dict, normed: jax.Array, positions: jax.Array,
                    pool_k: jax.Array, pool_v: jax.Array, li: jax.Array,
                    block_tables: jax.Array, config, block_size: int,
                    n_valid: "jax.Array | None" = None,
                    by_row: bool = False):
    """Grouped softmax attention over entry ``li`` of the paged pool,
    behind the block's input norm and before its residual.

    normed: [B, T, E] the new tokens' NORMED activations at global
    ``positions`` [B, T] (T=1 decode, T=chunk prefill). pool_k/pool_v:
    the WHOLE pool, [entries, num_blocks, bs, kv, d], written at ``[li,
    block, offset]`` and gathered at ``[li, block_tables]`` (no entry is
    taken out or put back), or [entries, num_blocks, bs * kv, d], a
    page's positions and heads in one dimension (``mamba.py``: ONE
    key-value head costs what it holds); an entry is a layer, or a
    layer's place among those that own one (``linear.py``,
    ``mamba.py``). block_tables: [B, M]
    (append-ordered block ids, 0-padded). ``n_valid``: optional scalar
    — positions at/after it scatter to the scratch block instead of
    the table (prefill chunk padding).

    The gathered keys/values stay ``[B, S, kv, d]`` in the pool's
    dtype; the queries are grouped ``[B, T, kv, reps, d]`` so each
    key-value head serves its ``reps`` query heads without being
    repeated. Scores accumulate in float32 and the softmax is float32.
    Queries and keys are rotated unless the configuration says ``rotary
    = False``; a layer with ``wg`` [E, H, d] (beside ``wq``: a dense
    layer's ``w_gate`` is its feed-forward's) weighs each head's output,
    channel by channel, by the sigmoid of that projection of the normed
    input (float32) before ``wo``.

    ``by_row`` (static; a decode step whose family says
    ``reads_by_row``, ``T == 1``): nothing is gathered. The kernel of
    ``ops/paged_kv_attention.py`` walks each row's own pages of the
    pools as the step found them and takes the row's fresh key and
    value beside them; a row at position 0 is inactive and attends
    over nothing. Returns (out [B, T, E], pool_k, pool_v).
    """
    dtype = config.dtype
    h, kv_heads, d = config.num_heads, config.num_kv_heads, config.head_dim
    B, T = positions.shape
    q, k, v = llama.qkv_of_normed(layer, normed, positions, config)

    # Scatter: token at global position p writes block_table[p // bs]
    # offset p % bs. Padding/inactive rows redirect to scratch block 0
    # (never gathered past the causal mask).
    blocks = jnp.take_along_axis(block_tables, positions // block_size,
                                 axis=1)                      # [B, T]
    offsets = positions % block_size
    if n_valid is not None:
        in_range = jnp.arange(T)[None, :] < n_valid
        blocks = jnp.where(in_range, blocks, 0)
        offsets = jnp.where(in_range, offsets, 0)
    k, v = k.astype(pool_k.dtype), v.astype(pool_v.dtype)
    at = (li, blocks, offsets)
    if pool_k.ndim == 4:
        # Pages that lie [bs * kv, d] (``mamba.py``): row s * kv + k.
        at = (li, blocks[..., None],
              offsets[..., None] * kv_heads + jnp.arange(kv_heads))
    written = (pool_k.at[at].set(k), pool_v.at[at].set(v))
    reps = h // kv_heads
    if by_row:
        # Imported where it is used: one that gathers never loads pallas.
        from ray_tpu.ops.paged_kv_attention import paged_kv_attention

        assert T == 1, "a row's pages are read for ONE query position"
        at = positions[:, 0]
        out = paged_kv_attention(
            q[:, 0].reshape(B, kv_heads, reps, d), k[:, 0], v[:, 0],
            pool_k, pool_v, block_tables, jnp.where(at > 0, at + 1, 0), li,
            scale=d ** -0.5).reshape(B, T, h, d)
    else:
        out = _attend_gathered(q, *written, li, block_tables, positions,
                               config, block_size)
    if "wg" in layer:
        gate = jnp.einsum("ble,ehd->blhd", normed, layer["wg"].astype(dtype),
                          preferred_element_type=jnp.float32)
        out = (out * jax.nn.sigmoid(gate)).astype(dtype)
    out = jnp.einsum("blhd,hde->ble", out, layer["wo"].astype(dtype))
    return (out, *written)


def _expert_block(layer: dict, experts: dict, index, x: jax.Array, config):
    """The sparse feed-forward of one layer (``models/moe.py``: every
    token through all ``experts_per_token`` of its experts, which are
    layer ``index`` of ``experts``, the layers' stacked expert tensors).
    Returns (out, the chosen experts [B, T, k])."""
    normed = llama.rms_norm(x, layer["mlp_norm"], config.rms_norm_eps)
    _, idx, weights = moe.route(normed, layer["w_router"],
                                config.experts_per_token,
                                config.norm_topk_prob)
    combine = moe.combine_weights(idx, weights, config.num_experts)
    return x + moe.touched_expert_ffn(experts, index, normed, combine,
                                      config.dtype), idx


def row_beside_zeros(x: jax.Array, at: jax.Array) -> jax.Array:
    """[B, T, E] -> [B, 2, E]: position ``at`` of each row, for a final
    norm and a head on the ONE position that is read (a chunk of 128
    tokens would else make 128 rows of the whole vocabulary in float32
    to return one; the caller takes ``[:, 0]`` of the logits), beside a
    row of zeros: a lone row against the head is a matrix-vector
    product, which the chip's compiler lowers as a float32
    multiply-reduce over a float32 copy of the whole table."""
    return jnp.stack([x[:, at], jnp.zeros_like(x[:, 0])], axis=1)


def _forward_paged(params: dict, pool: dict, tokens: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   config, block_size: int, *, slot=None,
                   n_valid: "jax.Array | None" = None,
                   logits_at: "jax.Array | None" = None,
                   busy: "jax.Array | None" = None,
                   by_row: "bool | None" = None):
    """The paged family's ``Family.forward``, prefill and decode, over
    the paged pool. Returns
    (logits [B, T, V] f32, or [B, V] of position ``logits_at`` alone;
    updated pool, expert counts, routing). The pool is part of the
    scan's carry, so every layer updates the one (donated) buffer.
    ``slot`` is of no use to it: no row owns a cache. ``by_row``
    (static) is ``paged_attention``'s: a decode step of a family that
    ``reads_by_row`` (the block pass gathers because its family says
    so), and a keyword only because the gathered step is the reference
    of the one by row (``tests/test_paged_kv_attention.py``).

    The feed-forward is the configuration's: dense SwiGLU, or the
    routed experts. For those, ``counts`` is ``moe.routing_counts``
    summed over the layers (it rides the carry) and ``routing`` the
    chosen experts [layers, B, T, k], which only a check reads; both
    are None for a dense model. The tokens counted are the chunk's
    first ``n_valid``, or without it (decode) the rows at a position
    past 0: an inactive row carries position 0, and a request's next
    token never does (a block in flight can start at 0, so its program
    says which rows are ``busy`` [B])."""
    if by_row is None:
        by_row = n_valid is None and family(config).reads_by_row
    x = params["embed"]["tokens"].astype(config.dtype)[tokens]
    sparse = config.num_experts > 0
    counts, layers = None, params["layers"]
    if sparse:
        # The expert tensors stay out of the scanned ``xs``: the kernel
        # takes them stacked and the layer's index.
        experts, layers = moe.split_experts(layers)
        counts = jnp.zeros((len(moe.EXPERT_COUNTERS),), jnp.int32)
        if n_valid is not None:
            valid = jnp.broadcast_to(jnp.arange(tokens.shape[1]) < n_valid,
                                     tokens.shape)
        elif busy is not None:
            valid = jnp.broadcast_to(busy[:, None], tokens.shape)
        else:
            valid = positions > 0

    def layer_step(carry, layer_and_index):
        x, pool_k, pool_v, counts = carry
        layer, li = layer_and_index
        x, pool_k, pool_v = _paged_attention_block(
            layer, x, positions, pool_k, pool_v, li, block_tables,
            config, block_size, n_valid=n_valid, by_row=by_row)
        if not sparse:
            return (llama._mlp_block(layer, x, config), pool_k, pool_v,
                    counts), None
        x, idx = _expert_block(layer, experts, li, x, config)
        counts = counts + moe.routing_counts(idx, valid, config.num_experts)
        return (x, pool_k, pool_v, counts), idx

    (x, pool_k, pool_v, counts), routing = lax.scan(
        layer_step, (x, pool["k"], pool["v"], counts),
        (layers, jnp.arange(config.num_layers)))
    if logits_at is not None:
        x = row_beside_zeros(x, logits_at)
    x = llama.rms_norm(x, params["final_norm"], config.rms_norm_eps)
    logits = jnp.einsum("ble,ev->blv", x,
                        params["lm_head"].astype(config.dtype),
                        preferred_element_type=jnp.float32)
    if logits_at is not None:
        logits = logits[:, 0]
    return logits, {"k": pool_k, "v": pool_v}, counts, routing


def _accumulated(stats, counts):
    """The expert accumulator after this step; None where the caller
    keeps none or the model has no experts."""
    if stats is None or counts is None:
        return None
    return moe.accumulate(stats, counts)


def sample_next(last, key, temps):
    """The next token of every row from its logits ``last`` [B, V]:
    the argmax at temperature 0, else a draw."""
    greedy = jnp.argmax(last, axis=-1)
    sampled = jax.random.categorical(
        key, last / jnp.maximum(temps, 1e-4)[:, None], axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


def _decode_body(config, block_size: int):
    """One decode step on its arguments apart, for ``make_decode_step``
    (the engine's program is ``make_engine_decode_step``)."""

    def decode_step(params, pool, tokens, positions, block_tables, key,
                    temps, expert_stats=None):
        # tokens [B, 1]; positions [B]; block_tables [B, M]; temps [B];
        # expert_stats: moe.init_stats() of a sparse model, or None.
        logits, pool, counts, _ = _forward_paged(
            params, pool, tokens, positions[:, None], block_tables,
            config, block_size)
        return sample_next(logits[:, -1, :], key, temps), pool, \
            _accumulated(expert_stats, counts)

    return decode_step


def _prefill_body(config, block_size: int):
    """One prefill chunk on its arguments apart, for
    ``make_prefill_chunk`` (the engine's: ``make_engine_prefill_chunk``)."""

    def prefill_chunk(params, pool, tokens, positions, block_table,
                      n_valid, last_idx, expert_stats=None):
        # tokens [1, C]; positions [1, C]; block_table [1, M];
        # n_valid/last_idx scalars (chunk padding past n_valid goes to
        # scratch; last_idx indexes the final REAL token, the one
        # position whose logits are computed).
        logits, pool, counts, _ = _forward_paged(
            params, pool, tokens, positions, block_table, config,
            block_size, n_valid=n_valid, logits_at=last_idx)
        return logits[0], pool, _accumulated(expert_stats, counts)

    return prefill_chunk


def make_decode_step(config, block_size: int):
    """The ONE batched decode program: every active ragged request
    advances one token through a shared ``[B, 1]`` step. Inactive rows
    carry all-zero tables/positions (scratch writes, discarded
    samples)."""
    return jax.jit(_decode_body(config, block_size), donate_argnums=(1,))


def make_prefill_chunk(config, block_size: int):
    """The prefill program (one a table width it is handed): a
    fixed-length chunk of one request's prompt scatters into its block
    table; only the ``last_idx`` logits row is computed, and only the
    final chunk's is consumed (the first generated token)."""
    return jax.jit(_prefill_body(config, block_size), donate_argnums=(1,))


# ------------------------------------------------------------------------
# Generation by diffusion over blocks (``config.block_length`` > 0)
# ------------------------------------------------------------------------
#
# A row carries a block of ``block_length`` positions in flight, from
# ``position`` on: its known tokens, and ``MASKED`` where none is fixed
# yet (which positions are fixed is this bookkeeping, never a comparison
# of a token with the mask's id). A DENOISING pass runs the block against
# the cache and itself and fixes some of its masked positions; when none
# is left the block's tokens are emitted, and a FINISHING pass runs it
# once more with its final tokens, which stores the keys and values
# later blocks read (inside a block every position's keys depend on all
# of its tokens, so what a denoising pass wrote is stale; each pass
# simply writes the block's positions again). The mask's id is never
# served: it is left out of the argmax, the sample and the confidence.
#
# What a pass needs of the pass before it is the block's tokens alone,
# and those lie on the device where that pass left them (its first
# result, the next one's ``prev``). The rest is counting: a pass under
# ``sequential`` or ``low_confidence_static`` fixes ``fix_count`` of the
# masked positions, whichever they are; a block with none left gets its
# finishing pass; after that the next block opens all masked at
# ``position + size``; a row ends by its count or its table's end. So
# the host packs a row's next pass while this one runs
# (``block_row_of(req, True)``, ``block_lead``), and only a denoising
# pass under ``low_confidence_dynamic``, which fixes as many positions
# as pass its threshold, has to be read first (``block_counts``).

#: Which masked positions a denoising pass fixes: the leftmost; those
#: of the largest confidence; every one whose confidence is over the
#: request's threshold, and the largest if fewer pass it.
REMASKING = ("sequential", "low_confidence_static", "low_confidence_dynamic")
_INACTIVE, _DENOISING, _FINISHING = 0, 1, 2
_BLOCK_HEAD = 6  # columns before a row's block: see pack_block_rows


def fix_count(block_length: int, denoising_steps: int, done: int) -> int:
    """How many masked positions the pass after ``done`` passes of a
    block fixes: ``block_length // steps``, the remainder on the first
    passes (a block that opened with known positions runs out of masked
    ones sooner, and takes fewer passes)."""
    steps = min(max(int(denoising_steps), 1), block_length)
    return block_length // steps + (done < block_length % steps)


def _masked_after(req) -> int:
    """Masked positions the block keeps after the denoising pass its
    schedule asks for next, under a rule that fixes by count."""
    return max(req.block.count(MASKED) - fix_count(
        len(req.block), req.denoising_steps, req.passes), 0)


def block_row_of(req, ahead: bool = False):
    """``Family.row_of``: the request's block in flight and the pass
    its schedule asks for next. ``ahead``: the row one pass on, while
    that pass is unread, by counting: after a finishing pass the next
    block, all masked, a block further; after a denoising pass the
    block that pass leaves on the device (no block here: the program
    takes it from ``prev``), fewer of it masked by what the pass fixes."""
    size = len(req.block)
    block, position, passes = req.block, req.position, req.passes
    masked = block.count(MASKED)
    if ahead and not masked:
        block, position, passes, masked = [MASKED] * size, position + size, \
            0, size
    elif ahead:
        block, passes, masked = None, passes + 1, _masked_after(req)
    fix = fix_count(size, req.denoising_steps, passes) if masked else 0
    return (block, position, req.temperature, fix,
            REMASKING.index(req.remasking), req.confidence_threshold,
            req.block_table)


def block_counts(req) -> bool:
    """``Family.ahead``: what the pass in flight leaves masked is known
    without its values, unless it is a denoising pass under the dynamic
    rule."""
    return MASKED not in req.block or req.remasking != REMASKING[2]


def block_lead(req, max_tokens: int):
    """``Family.lead``, of a row that ``block_counts``: a finishing pass
    in flight moves it a block on, unless its table ends there; a
    denoising pass moves it nowhere, and is its last if it makes whole
    a block that holds all the request still owes."""
    size = len(req.block)
    if MASKED not in req.block:
        return size if req.position + size < max_tokens else None
    if _masked_after(req):
        return 0
    known = len(req.tokens) + len(req.output) - req.position
    return 0 if size - known < req.remaining else None


def advance_block(req, out):
    """``Family.advance``: after a finishing pass the next block opens,
    all masked; after a denoising pass the block is what the program
    made of it, and once whole, its tokens past those the request
    already had (a prompt's remainder) are due, up to its limit."""
    size = len(req.block)
    if MASKED not in req.block:
        req.position += size
        req.block, req.passes = [MASKED] * size, 0
        return [], True
    req.block, req.passes = [int(t) for t in out], req.passes + 1
    if MASKED in req.block:
        return [], False
    known = len(req.tokens) + len(req.output) - req.position
    return req.block[known:known + req.remaining], False


def pack_block_rows(block_length: int, batch: int, width: int, active,
                    slots=None) -> np.ndarray:
    """The block-pass program's host array, int32 ``[batch, 6 +
    block_length + width]``: per row its block's first position, its
    temperature (the float32's bits), how many masked positions this
    pass fixes, the rule (an index into ``REMASKING``), the confidence
    threshold (bits), the phase (0 inactive, 1 denoising, 2 finishing:
    a block with nothing masked), the block's tokens (``MASKED`` where
    none is fixed) and the block table; from ``active``'s
    ``block_row_of`` tuples, placed as ``pack_decode_rows`` places
    them. A row with no block (None) runs on the one the pass before
    left for it, which the host has not read: its phase is negative (a
    finishing pass is the one that fixes nothing) and its block's
    columns stay zero. Rule, count and phase are values, not programs:
    rows in every phase share one pass."""
    table_at = _BLOCK_HEAD + block_length
    rows = np.zeros((batch, table_at + width), np.int32)
    floats = rows.view(np.float32)
    for i, (block, position, temperature, fix, rule, threshold, table) \
            in zip(slots or range(batch), active):
        rows[i, 0], floats[i, 1], rows[i, 2] = position, temperature, fix
        rows[i, 3], floats[i, 4] = rule, threshold
        if block is None:
            rows[i, 5] = -(_DENOISING if fix else _FINISHING)
        else:
            rows[i, 5] = _DENOISING if MASKED in block else _FINISHING
            rows[i, _BLOCK_HEAD:table_at] = block
        rows[i, table_at:table_at + len(table)] = table
    return rows


def denoise(logits, block, fix, rule, threshold, temps, key, mask_id: int):
    """One pass over every row's block: logits [B, T, V] float32 at the
    block's positions, block [B, T] (``MASKED`` where unfixed), fix /
    rule / threshold / temps [B]. ``x0`` is the argmax (or a draw at
    the row's temperature), never the mask's id; its confidence ``c``
    the softmax's probability of it, at that temperature. Of a row's
    masked positions the pass fixes, at ``x0``: ``sequential`` the
    ``fix`` leftmost; ``low_confidence_static`` the ``fix`` of largest
    ``c`` (ties to the left); ``low_confidence_dynamic`` those and every
    one with ``c`` over the threshold (if ``fix`` or more pass it, they
    are the largest, and if fewer, the largest hold them: the union is
    the rule). Returns the blocks after the pass."""
    T = block.shape[1]
    logits = logits.at[..., mask_id].set(-jnp.inf)
    scaled = logits / jnp.where(temps > 0, jnp.maximum(temps, 1e-4),
                                1.0)[:, None, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    x0 = jnp.where(temps[:, None] > 0, sampled,
                   jnp.argmax(logits, axis=-1)).astype(jnp.int32)
    picked = jnp.take_along_axis(scaled, x0[..., None], axis=-1)[..., 0]
    masked = block == MASKED
    c = jnp.where(masked, jnp.exp(picked - jax.nn.logsumexp(scaled, axis=-1)),
                  -1.0)
    # A position's rank among its row's masked ones, by place or by
    # confidence: how many of them come before it.
    at = jnp.arange(T)
    before = jnp.where(
        rule[:, None, None] == 0, at[None, :] < at[:, None],
        (c[:, None, :] > c[:, :, None])
        | ((c[:, None, :] == c[:, :, None]) & (at[None, :] < at[:, None])))
    rank = jnp.sum(before & masked[:, None, :], axis=-1)
    fixed = (rank < fix[:, None]) \
        | ((rule == 2)[:, None] & (c > threshold[:, None]))
    return jnp.where(masked & fixed, x0, block)


def make_engine_block_step(config, block_size: int):
    """The block family's ONE decode program, under the plain one's
    name: every busy row's block of ``block_length`` positions through
    the layers against the pool (its keys and values written at its
    positions first and gathered with the rest, as a decode step does,
    so a later pass and the finishing pass write them again), then
    ``denoise``. On ``pack_block_rows``' array and the carried key;
    returns the blocks after the pass ``[B, block_length]``, the pool,
    the expert counters and the key, as ``make_engine_decode_step``.
    ``prev`` is the first result of the pass before, on the device and
    not donated: a row whose phase is negative runs on its own row of
    it. Without ``prev`` every block is the array's."""
    size, mask_id = config.block_length, config.mask_token_id
    table_at = _BLOCK_HEAD + size

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode_step(params, pool, rows, key, expert_stats=None, prev=None):
        key, sub = jax.random.split(key)
        floats = lax.bitcast_convert_type(rows[:, :_BLOCK_HEAD], jnp.float32)
        block = rows[:, _BLOCK_HEAD:table_at]
        if prev is not None:
            block = jnp.where(rows[:, 5:6] < 0, prev, block)
        logits, pool, counts, _ = _forward_paged(
            params, pool, jnp.where(block == MASKED, mask_id, block),
            rows[:, :1] + jnp.arange(size), rows[:, table_at:], config,
            block_size, busy=rows[:, 5] != _INACTIVE)
        after = denoise(logits, block, rows[:, 2], rows[:, 3], floats[:, 4],
                        floats[:, 1], sub, mask_id)
        return after, pool, _accumulated(expert_stats, counts), key

    return decode_step


PAGED = Family(
    init_params=llama.init_params,
    init_cache=lambda config, num_blocks, block_size, rows, chunk_len:
    PagedKVCache.init_pool(config, num_blocks, block_size),
    forward=_forward_paged,
    reads_by_row=True,
    lay_params=lay_for_serving,
)


@functools.lru_cache(maxsize=None)
def _blockwise(block_length: int) -> Family:
    """The family of diffusion over blocks of ``block_length``: the
    paged pool and the plain prefill program (under the block mask: a
    paged block and a chunk hold whole blocks, the engine checks; what
    it returns of logits is not read, prefill yields no token), and the
    block pass for a decode step, which gathers (its ``T`` is not 1)."""
    return dataclasses.replace(
        PAGED,
        reads_by_row=False,
        make_engine_decode_step=make_engine_block_step,
        pack_decode_rows=functools.partial(pack_block_rows, block_length),
        row_of=block_row_of,
        advance=advance_block,
        ahead=block_counts,
        lead=block_lead,
    )
