"""Grouped softmax attention of a decode step as ONE pallas TPU kernel
that reads the paged key and value pools through the block tables: each
row its own live pages, each key and value once.

A model of grouped queries leaves a key and a value ``[kv, d]`` a
position in the pools ``[entries, num_blocks, block, kv, d]`` (an entry
is a layer, or a layer's place among those that own one), and a decode
step's one query position a row attends over the row's context
(``model.paged_attention``). Over the gathered views ``pool[li,
tables]`` every row reads the step's whole table width, the gathers
write what they read, and the score and the value product each read a
copy again. Here, by the pallas guide
(/opt/skills/guides/pallas_guide.md), in the manner of
``jax.experimental.pallas.ops.tpu.paged_attention`` and on the pattern
of ``paged_latent_attention.py`` (with which it shares no code: another
pool, two of them, another product):

- scalar-prefetched (SMEM): the tables ``[B * M]``, each row's length
  (positions it attends over, its own among them; 0: an inactive row)
  and the entry's index. The pools stay in HBM WHOLE: the entry is
  chosen in the page's own address (``pool[li]`` outside the kernel
  would be a copy of an entry of the pool);
- grid ``(B,)``, one program a row. A row's pool positions ``[0,
  length - 1)`` are walked in chunks of ``pages_per_chunk`` pages, one
  DMA a page and pool into one of two VMEM buffers a pool (a whole
  chunk's written out and waited for in one wait a pool, a row's last
  chunk's in loops), the next chunk's pages (or the next row's first)
  in flight while this one is used;
- a page lies ``[block, kv, d]``: the key-value heads in the
  second-minor dimension, so a chunk as it lies is a matrix ``[chunk *
  kv, d]`` whose row ``s * kv + k`` is head ``k`` of position ``s``. A
  row's ``kv * reps`` queries are laid against ALL of it, block
  diagonally: query ``k * reps + r`` keeps the columns of its own head
  ``k`` and the rest are masked before the softmax, so that their
  probabilities are exactly 0 in the value product. That is ``kv`` times
  the arithmetic of the grouped product and no strided read, no copy
  and no other layout of the pools, which are the cache's; either way
  every key and value passes the matrix unit once as the stationary
  operand, and the DMAs set the pace (``PERF.md`` 6, PR 56, has the
  table: the strided read of a head's keys was within 4% either way);
- scores float32 from the pool's dtype, the softmax kept online
  (running maximum and sum in float32), probabilities rounded to the
  pool's dtype, the weighted sum accumulated in float32: the numerics
  of the gathered form;
- the step's OWN position is not read from the pools: the row's fresh
  key and value are operands, and start the running maximum, sum and
  accumulator. The call does not wait on the pools' write, a row's
  running maximum is finite before its first chunk, and a row of
  length 1 fetches nothing;
- positions past a row's length are masked by the length (scores AND
  values: what lies there is never multiplied, so it may be anything),
  pages past it are not fetched, a row of length 0 returns zeros.

Returns ``[B, kv, reps, d]``. Rotation, a gate and the output
projection stay with the caller.

On the CPU platform the kernel interprets (``jax_compat.interpret_kernels``),
so the tests run its own logic; the gathered form of
``model.paged_attention`` is the plain form they compare against.
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

#: Pages a chunk holds where the caller names none: 256 positions of a
#: block of 16 (a buffer of 0.5 MiB at 8 heads of 128 in bf16, two a
#: pool). On the v5e at the Solar cell's shapes (64 rows of 192 to 3,968
#: positions, 0.49 GB live: 0.60 ms at the memory's peak; my chip runs,
#: PR 56) 16 took 0.73 ms, 32 0.72, 8 0.86 (a chunk's fixed costs), 64
#: 0.76; rows of 192 positions 0.22 at 16 and 0.25 at 32.
PAGES_PER_CHUNK = 16


def _kernel(tables_ref, lengths_ref, entry_ref,      # scalar prefetch
            q_ref, k_new_ref, v_new_ref, pool_k_ref, pool_v_ref,  # inputs
            out_ref,                                 # output
            kbuf, vbuf, sems, state,                 # scratch
            *, scale: float, pages_per_chunk: int, table_width: int,
            block: int, kv: int, reps: int):
    b, rows = pl.program_id(0), pl.num_programs(0)
    entry = entry_ref[0]
    chunk = pages_per_chunk * block
    heads, d = kv * reps, q_ref.shape[-1]
    pools = ((pool_k_ref, kbuf), (pool_v_ref, vbuf))

    def pages_of(row):
        """Pages of the pools row ``row`` reads: those that hold its
        positions before its own."""
        return pl.cdiv(jnp.maximum(lengths_ref[row] - 1, 0), block)

    def in_chunk(pages, c):
        return jnp.minimum(pages - c * pages_per_chunk, pages_per_chunk)

    def page_copies(row, c, slot, i):
        page = tables_ref[row * table_width + c * pages_per_chunk + i]
        return [pltpu.make_async_copy(pool.at[entry, page], buf.at[slot, i],
                                      sems.at[which, slot])
                for which, (pool, buf) in enumerate(pools)]

    def start(row, c, slot, n):
        """Start the DMAs of chunk ``c`` of ``row``, ``n`` pages of each
        pool. A whole chunk's are written out when LOWERED (the scalar
        unit issues them back to back; traced once: they were half the
        kernel's trace), a row's last chunk's are a loop of ``n``."""
        def one(i):
            for copy in page_copies(row, c, slot, i):
                copy.start()

        @pl.when(n == pages_per_chunk)
        def _():
            lax.fori_loop(0, pages_per_chunk, lambda i, _: one(i), None,
                          unroll=True)

        @pl.when(n < pages_per_chunk)
        def _():
            lax.fori_loop(0, n, lambda i, _: one(i), None)

    def wait(row, c, slot, n):
        """Wait for them: a whole chunk's bytes at once (the semaphore
        counts bytes: a descriptor of the buffer's size, never started,
        waits for all of them), a last chunk's page by page."""
        @pl.when(n == pages_per_chunk)
        def _():
            for which, (pool, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    pool.at[entry, pl.ds(0, pages_per_chunk)],
                    buf.at[slot], sems.at[which, slot]).wait()

        def one(i):
            for copy in page_copies(row, c, slot, i):
                copy.wait()

        @pl.when(n < pages_per_chunk)
        def _():
            lax.fori_loop(0, n, lambda i, _: one(i), None)

    # state[0]: the buffers the next chunk to be used lies in; state[1]:
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
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(length > 0)
    def _():
        q = q_ref[0]                                   # [heads, d]
        k_new, v_new = k_new_ref[0], v_new_ref[0]      # [kv, d]
        # Query ``k * reps + r`` is of key-value head ``k``.
        head_of_query = lax.broadcasted_iota(
            jnp.int32, (heads, 1), 0) // reps
        # The row's own position: its score is its own head's column of
        # q . k_new^T, its value its own head's row of v_new.
        if kv == 1:
            # ONE key-value head: every query is of it (a product with
            # one column or one row is no matrix product to the chip's
            # compiler, which refuses it).
            m0 = jnp.sum(q.astype(F32) * k_new.astype(F32), axis=1,
                         keepdims=True) * scale        # [heads, 1]
            acc0 = jnp.broadcast_to(v_new.astype(F32), (heads, d))
        else:
            own = head_of_query == lax.broadcasted_iota(
                jnp.int32, (1, kv), 1)
            s_own = lax.dot_general(q, k_new, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
            m0 = jnp.sum(jnp.where(own, s_own, 0.0), axis=1,
                         keepdims=True) * scale        # [heads, 1]
            acc0 = jnp.dot(own.astype(v_new.dtype), v_new,
                           preferred_element_type=F32)  # [heads, d]
        l0 = jnp.ones((heads, 1), F32)

        # A chunk as it lies: column ``s * kv + k`` is head ``k`` of the
        # chunk's position ``s``.
        column = lax.broadcasted_iota(jnp.int32, (1, chunk * kv), 1)
        own_head = head_of_query == column % kv        # [heads, chunk*kv]

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
                if vbuf.ndim == 5:      # a page lies [block, kv, d]
                    shape = (pages_per_chunk, block, 1, 1)
                    at = lax.broadcasted_iota(jnp.int32, shape, 0) * block \
                        + lax.broadcasted_iota(jnp.int32, shape, 1)
                else:                   # [block * kv, d]: row s * kv + k
                    shape = (pages_per_chunk, block * kv, 1)
                    at = lax.broadcasted_iota(jnp.int32, shape, 0) * block \
                        + lax.broadcasted_iota(jnp.int32, shape, 1) // kv
                vbuf[slot] = jnp.where(at < live, vbuf[slot],
                                       jnp.zeros_like(vbuf[slot]))

            k = kbuf[slot].reshape(chunk * kv, d)
            v = vbuf[slot].reshape(chunk * kv, d)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
            s = jnp.where(jnp.logical_and(own_head, column < live * kv),
                          s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=F32)
            return m_new, l_new, acc

        _, l_fin, acc = lax.fori_loop(0, chunks, body, (m0, l0, acc0))
        state[0] = (slot0 + chunks) % 2
        out_ref[0] = (acc / l_fin).astype(out_ref.dtype)


def paged_kv_attention(q, k_new, v_new, pool_k, pool_v, tables, lengths,
                       entry, *, scale: float,
                       pages_per_chunk: "int | None" = None,
                       interpret: "bool | None" = None):
    """q ``[B, kv, reps, d]`` (rotated or not, as the keys are), k_new
    and v_new ``[B, kv, d]`` (what the rows' own positions leave in the
    pools), pool_k and pool_v ``[entries, num_blocks, block, kv, d]``,
    tables ``[B, M]`` int32, lengths ``[B]`` int32 (positions a row
    attends over, its own the last; 0: none), ``entry`` an int32 scalar.
    Query head ``(k, r)`` of row ``b`` attends over head ``k`` of the
    pools' positions ``[0, lengths[b] - 1)`` of entry ``entry`` through
    ``tables[b]``, and over ``k_new[b, k]`` and ``v_new[b, k]``. Returns
    ``[B, kv, reps, d]`` in q's dtype."""
    (rows, kv, reps, d), table_width = q.shape, tables.shape[1]
    page = pool_k.shape[2:]
    block = page[0] if len(page) == 3 else page[0] // kv
    if not (k_new.shape == v_new.shape == (rows, kv, d)
            and pool_k.shape == pool_v.shape
            and page in ((block, kv, d), (block * kv, d))):
        raise ValueError(
            f"paged_kv_attention: queries {q.shape}, fresh keys "
            f"{k_new.shape} and values {v_new.shape}, pools "
            f"{pool_k.shape} and {pool_v.shape} differ in their "
            f"key-value heads or their head size")
    if interpret is None:
        interpret = jax_compat.interpret_kernels()
    pages_per_chunk = min(pages_per_chunk or PAGES_PER_CHUNK, table_width)
    kernel = functools.partial(
        _kernel, scale=scale, pages_per_chunk=pages_per_chunk,
        table_width=table_width, block=block, kv=kv, reps=reps)
    vmem = {} if interpret else {"memory_space": pltpu.VMEM}
    fresh = pl.BlockSpec((1, kv, d), lambda b, *_: (b, 0, 0), **vmem)
    buffer = pltpu.VMEM((2, pages_per_chunk, *page), pool_k.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, kv * reps, d), lambda b, *_: (b, 0, 0),
                             **vmem),
                fresh, fresh,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, kv * reps, d),
                                   lambda b, *_: (b, 0, 0), **vmem),
            scratch_shapes=[
                buffer, buffer,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, kv * reps, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_kv_attention",
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(entry, jnp.int32).reshape(1),
      q.reshape(rows, kv * reps, d), k_new, v_new, pool_k, pool_v)
    return out.reshape(rows, kv, reps, d)
