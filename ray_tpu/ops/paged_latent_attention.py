"""Absorbed latent attention of a decode step as ONE pallas TPU kernel
that reads the paged pool through the block tables: each row its own
live pages, each latent once.

A latent-attention model (``models/xing.py``) leaves one vector a
position and layer in the pool ``[layers, num_blocks, block, lanes]``,
and a decode step's absorbed queries ``[B, H, lanes]`` score and sum the
latents where they lie (``xing.attend_absorbed``). Over a gathered view
``pool[li, tables]`` every row reads the step's whole table width, the
gather writes what it read, and the score and the value product each
read the copy again. Here, by the pallas guide
(/opt/skills/guides/pallas_guide.md) and in the manner of
``jax.experimental.pallas.ops.tpu.paged_attention``:

- scalar-prefetched (SMEM): the tables ``[B * M]``, each row's length
  (positions it attends over, its own among them; 0: an inactive row)
  and the layer index. The pool stays in HBM WHOLE: the layer is chosen
  in the page's own address (``pool[li]`` outside the kernel would be a
  copy of a layer of the pool);
- grid ``(B,)``, one program a row. A row's pool positions ``[0,
  length - 1)`` are walked in chunks of ``pages_per_chunk`` pages, one
  DMA a page into one of two VMEM buffers (a whole chunk's written out
  and waited for in one wait, a row's last chunk's in loops), the next
  chunk's pages (or the next row's first) in flight while this one is
  used;
- a chunk as it lies in VMEM ``[chunk, lanes]`` serves both products:
  scores ``[H, chunk]`` float32 from bf16 operands, the softmax kept
  online (running maximum and sum in float32), probabilities rounded to
  the pool's dtype, the weighted sum accumulated in float32. All heads
  share the one read;
- the step's OWN position is not read from the pool: the row's fresh
  entry is an operand, and starts the running maximum, sum and
  accumulator. The call does not wait on the pool's write, a row's
  running maximum is finite before its first chunk, and a row of
  length 1 fetches nothing;
- positions past a row's length are masked by the length (scores AND
  latents: what lies there is never multiplied, so it may be anything),
  pages past it are not fetched, a row of length 0 returns zeros.

Returns ``u [B, H, width]``: the weighted sums' first ``width`` lanes
(``kv_lora_rank``: the rotary tail dropped inside).

On the CPU platform the kernel interprets (``jax_compat.interpret_kernels``),
so the tests run its own logic; ``xing.attend_absorbed`` over the
gathered view is the plain form they compare against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import jax_compat

NEG_INF = -1e30
F32 = jnp.float32

#: Pages a chunk holds where the caller names none: 1,024 positions of
#: a block of 16 (a buffer of 1.25 MiB at 640 lanes in bf16, two of
#: them). On the v5e at the Xing cell's shapes (32 rows of 2,048 to
#: 7,936 positions, seven layers; my chip runs, PR 45) 64 took 2.46 to
#: 2.51 ms, 32 2.74: a chunk's fixed costs (its masks, the rescaled accumulator,
#: two loop turns) over more positions; 128 gained nothing further.
PAGES_PER_CHUNK = 64


def _kernel(tables_ref, lengths_ref, layer_ref,     # scalar prefetch
            q_ref, entries_ref, pool_ref,            # inputs
            u_ref,                                   # output
            buf, sems, state,                        # scratch
            *, scale: float, pages_per_chunk: int, table_width: int,
            block: int, width: int):
    b, rows = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    chunk = pages_per_chunk * block
    heads = q_ref.shape[1]

    def pages_of(row):
        """Pages of the pool row ``row`` reads: those that hold its
        positions before its own."""
        return pl.cdiv(jnp.maximum(lengths_ref[row] - 1, 0), block)

    def in_chunk(pages, c):
        return jnp.minimum(pages - c * pages_per_chunk, pages_per_chunk)

    def page_copy(row, c, slot, i):
        page = tables_ref[row * table_width + c * pages_per_chunk + i]
        return pltpu.make_async_copy(pool_ref.at[layer, page],
                                     buf.at[slot, i], sems.at[slot])

    def start(row, c, slot, n):
        """Start the DMAs of chunk ``c`` of ``row``, ``n`` pages. A
        whole chunk's are written out (the scalar unit issues them
        back to back: in a loop, a page cost as long to ask for as to
        move), a row's last chunk's are a loop of ``n``."""
        @pl.when(n == pages_per_chunk)
        def _():
            for i in range(pages_per_chunk):
                page_copy(row, c, slot, i).start()

        @pl.when(n < pages_per_chunk)
        def _():
            lax.fori_loop(0, n, lambda i, _: page_copy(
                row, c, slot, i).start(), None)

    def wait(row, c, slot, n):
        """Wait for them: a whole chunk's bytes at once (the semaphore
        counts bytes: a descriptor of the buffer's size, never started,
        waits for all of them), a last chunk's page by page."""
        @pl.when(n == pages_per_chunk)
        def _():
            pltpu.make_async_copy(
                pool_ref.at[layer, pl.ds(0, pages_per_chunk)],
                buf.at[slot], sems.at[slot]).wait()

        @pl.when(n < pages_per_chunk)
        def _():
            lax.fori_loop(0, n, lambda i, _: page_copy(
                row, c, slot, i).wait(), None)

    # state[0]: the buffer the next chunk to be used lies in; state[1]:
    # the row whose first chunk is in flight there (none: -1).
    @pl.when(b == 0)
    def _():
        state[0] = 0
        state[1] = -1

    length = lengths_ref[b]
    context = length - 1
    pages = pages_of(b)
    chunks = pl.cdiv(pages, pages_per_chunk)

    @pl.when(length <= 0)
    def _():
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(length > 0)
    def _():
        q = q_ref[0]                                   # [H, lanes]
        entries = entries_ref[...]                     # [B, lanes]
        # The row's own entry, by two small products: its score (column
        # b of q . entries^T) and the entry itself on every head's row.
        is_row = lax.broadcasted_iota(jnp.int32, (heads, rows), 1) == b
        own = lax.dot_general(q, entries, (((1,), (1,)), ((), ())),
                              preferred_element_type=F32)
        m0 = jnp.sum(jnp.where(is_row, own, 0.0), axis=1,
                     keepdims=True) * scale            # [H, 1]
        acc0 = jnp.dot(is_row.astype(entries.dtype), entries,
                       preferred_element_type=F32)     # [H, lanes]
        l0 = jnp.ones((heads, 1), F32)

        slot0 = state[0]

        @pl.when(jnp.logical_and(chunks > 0, state[1] != b))
        def _():
            start(b, 0, slot0, in_chunk(pages, 0))

        def body(c, carry):
            m_prev, l_prev, acc = carry
            slot = (slot0 + c) % 2
            n = in_chunk(pages, c)

            # What flies while this chunk is used: the row's next, or
            # after its last the first of the row after it, where that
            # row is known to read.
            after = jnp.minimum(b + 1, rows - 1)
            goes_on = c + 1 < chunks
            ahead = jnp.where(
                goes_on, in_chunk(pages, c + 1),
                jnp.where(b + 1 < rows, in_chunk(pages_of(after), 0), 0))
            start(jnp.where(goes_on, b, after),
                  jnp.where(goes_on, c + 1, 0), 1 - slot, ahead)

            @pl.when(jnp.logical_and(jnp.logical_not(goes_on), ahead > 0))
            def _():
                state[1] = after

            wait(b, c, slot, n)
            live = context - c * chunk                     # positions here

            # Only a row's last chunk holds positions past its length,
            # and pages no DMA wrote: zeros there, whatever lay there
            # (a weight of zero times it would still be a NaN's NaN).
            @pl.when(live < chunk)
            def _():
                at = lax.broadcasted_iota(
                    jnp.int32, (pages_per_chunk, block, 1), 0) * block \
                    + lax.broadcasted_iota(
                        jnp.int32, (pages_per_chunk, block, 1), 1)
                buf[slot] = jnp.where(at < live, buf[slot],
                                      jnp.zeros_like(buf[slot]))

            k = buf[slot].reshape(chunk, buf.shape[-1])    # [chunk, lanes]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
            s = jnp.where(lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
                          < live, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(k.dtype), k,
                                        preferred_element_type=F32)
            return m_new, l_new, acc

        _, l_fin, acc = lax.fori_loop(0, chunks, body, (m0, l0, acc0))
        state[0] = (slot0 + chunks) % 2
        u_ref[0] = (acc[:, :width] / l_fin).astype(u_ref.dtype)


def paged_latent_attention(q, entries, pool, tables, lengths, layer, *,
                           scale: float, width: int,
                           pages_per_chunk: "int | None" = None,
                           interpret: "bool | None" = None):
    """q ``[B, H, lanes]`` (the absorbed queries, ``q_latent | q_rope |
    0``), entries ``[B, lanes]`` (what the rows' own positions leave in
    the pool), pool ``[layers, num_blocks, block, lanes]``, tables ``[B,
    M]`` int32, lengths ``[B]`` int32 (positions a row attends over,
    its own the last; 0: none), ``layer`` an int32 scalar. Row ``b``
    attends over the pool's positions ``[0, lengths[b] - 1)`` of layer
    ``layer`` through ``tables[b]``, and over ``entries[b]``. Returns
    ``[B, H, width]`` in q's dtype."""
    (rows, heads, lanes), table_width = q.shape, tables.shape[1]
    block = pool.shape[2]
    if entries.shape != (rows, lanes) or pool.shape[3] != lanes:
        raise ValueError(
            f"paged_latent_attention: queries {q.shape}, entries "
            f"{entries.shape} and pool {pool.shape} differ in their lanes")
    if interpret is None:
        interpret = jax_compat.interpret_kernels()
    pages_per_chunk = min(pages_per_chunk or PAGES_PER_CHUNK, table_width)
    kernel = functools.partial(
        _kernel, scale=scale, pages_per_chunk=pages_per_chunk,
        table_width=table_width, block=block, width=width)
    vmem = {} if interpret else {"memory_space": pltpu.VMEM}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, heads, lanes), lambda b, *_: (b, 0, 0),
                             **vmem),
                pl.BlockSpec((rows, lanes), lambda b, *_: (0, 0), **vmem),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, width),
                                   lambda b, *_: (b, 0, 0), **vmem),
            scratch_shapes=[
                pltpu.VMEM((2, pages_per_chunk, block, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, entries, pool)
