"""Paged KV cache: a preallocated block pool + per-request block tables.

The Ragged Paged Attention memory model (PAPERS.md: arxiv 2604.15464):
instead of one ``[max_batch, max_len]`` cache row per slot (every
admitted request reserving its WORST CASE length), the cache is a pool
of fixed-size blocks (``[num_blocks, block_size, kv_heads, head_dim]``
per layer) and each request holds an append-only table of block ids
covering exactly the tokens it has written. Ragged lengths pack tightly: a 7-token request
holds one 16-token block while its 900-token batchmate holds 57, and
blocks return to the free list the moment a request finishes — so the
SAME pool admits far more concurrent ragged requests than slot rows
would.

Block 0 is a reserved scratch block: inactive batch rows and padded
prefill positions scatter their k/v there (garbage nobody gathers —
real queries are causally masked to ``s <= position`` and scratch only
ever appears in a table's padding tail, past every real position).

Thread model: allocation/free runs ONLY on the engine loop thread (the
scheduler owns request lifecycles); the counters are read cross-thread
lock-free (GIL-atomic int loads) for stats.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from ray_tpu.exceptions import CacheExhaustedError


class PagedKVCache:
    """Host-side accounting for the paged pool; the device arrays live
    in the engine (they are donated through every jitted step, so the
    engine rebinds them each call — this class tracks block ownership,
    not buffers)."""

    def __init__(self, num_blocks: int, block_size: int,
                 max_blocks_per_seq: int):
        if num_blocks < 2:
            raise ValueError("paged cache needs >= 2 blocks "
                             "(block 0 is reserved scratch)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        # LIFO free list: freshly-freed (cache-warm on TPU HBM paging
        # schemes; here simply cheap) blocks are reused first. Block 0
        # is never in the list — reserved scratch.
        self._free = list(range(num_blocks - 1, 0, -1))
        self.blocks_allocated = 0
        self.blocks_freed = 0

    # ------------------------------------------------------------- queries

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def usable_blocks(self) -> int:
        """Blocks a single request could ever hold (pool minus scratch,
        capped by its table width)."""
        return min(self.num_blocks - 1, self.max_blocks_per_seq)

    def blocks_for_tokens(self, n_tokens: int) -> int:
        """Table length needed to hold ``n_tokens`` written tokens."""
        return -(-n_tokens // self.block_size)  # ceil div

    def fits_ever(self, total_tokens: int) -> bool:
        """Whether a request needing ``total_tokens`` KV slots can run
        even on an EMPTY pool — the admission-time typed-shed check (a
        request that can never fit must shed immediately, not preempt
        the world forever)."""
        return self.blocks_for_tokens(total_tokens) <= self.usable_blocks

    # ---------------------------------------------------------- alloc/free

    def grow(self, table: "list[int]", n_tokens: int) -> bool:
        """Extend ``table`` (in place) until it covers ``n_tokens``
        token slots. Returns True when blocks were appended. Raises
        :class:`CacheExhaustedError` when the free list runs dry —
        the caller (scheduler) preempts a victim and retries."""
        need = self.blocks_for_tokens(n_tokens)
        if need > self.max_blocks_per_seq:
            raise CacheExhaustedError(
                f"request needs {need} blocks, over the per-sequence "
                f"table limit {self.max_blocks_per_seq}")
        grew = False
        while len(table) < need:
            if not self._free:
                raise CacheExhaustedError(
                    f"KV block pool exhausted ({self.num_blocks - 1} "
                    f"blocks, 0 free)")
            table.append(self._free.pop())
            self.blocks_allocated += 1
            grew = True
        return grew

    def release(self, table: "list[int]") -> None:
        """Return every block in ``table`` to the free list (finish,
        preemption, shed, deadline expiry) and clear the table."""
        for block in table:
            if block != 0:
                self._free.append(block)
                self.blocks_freed += 1
        table.clear()

    # --------------------------------------------------------------- pools

    @staticmethod
    def init_pool(config: Any, num_blocks: int, block_size: int,
                  dtype: Any = None) -> dict:
        """Allocate the zeroed device pool:
        ``{"k","v"}: [layers, num_blocks, block_size, kv, d]``
        (static shapes, so the decode step compiles once)."""
        dtype = dtype or config.dtype
        shape = (config.num_layers, num_blocks, block_size,
                 config.num_kv_heads, config.head_dim)
        return {"k": jnp.zeros(shape, dtype=dtype),
                "v": jnp.zeros(shape, dtype=dtype)}
