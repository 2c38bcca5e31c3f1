"""Blockwise (flash) causal attention as pallas TPU kernels, fwd + bwd.

The reference framework has no attention kernels at all (SURVEY §5
long-context: absent — it launches torch models); this is a native
capability of the TPU build. Design per the pallas guide
(/opt/skills/guides/pallas_guide.md):

- forward: grid = (batch*heads, L/block_q); each program owns one q tile
  in VMEM and streams k/v tiles from the per-(b,h) VMEM block with
  online softmax (running max/denominator) — O(block) VMEM, no [L, L]
  scores materialized in HBM. Also emits the per-row logsumexp (LSE)
  residual for the backward, as a lane-dense row ``f32[BH, 1, L]``.
- backward: two fused kernels using the saved LSE (no online softmax
  needed — probabilities are recomputed exactly as exp(s - lse)):
  * dq kernel, grid (batch*heads, L/block_q): for one q tile, loop over
    k tiles at-or-left-of the diagonal accumulating
    dq += (p ∘ (dO·Vᵀ - D)) · K. It forms D = rowsum(dO ∘ O) for its q
    tile, once a row, and hands it on as a second lane-dense row.
  * dk/dv kernel, grid (batch*heads, L/block_k), in the keys' own
    orientation: for one k tile, loop over q tiles at-or-below the
    diagonal with sᵀ = K·Qᵀ, pᵀ = exp(sᵀ - lse), accumulating
    dv += pᵀ·dO and dk += (pᵀ ∘ (V·dOᵀ - D)) · Q. lse and D are rows
    there as they lie, and no score-sized tile is transposed.
- matmul operands stay bf16 (MXU native) with
  preferred_element_type=f32 accumulation; softmax statistics are f32.
- causal programs stop their k loop at the diagonal (work ∝ L²/2), and
  the dk/dv kernel starts its q loop there. Only the blocks the
  diagonal crosses are masked (``_k_block_bounds``, ``_crossed``): the
  blocks wholly on the visible side run with no iota, compare or select.

On the CPU platform (tests / virtual mesh) the kernels run in interpret
mode; on every other backend they are compiled, and a test or a
rehearsal that wants interpret mode elsewhere passes ``interpret=True``.
``_blockwise_reference`` remains as the correctness oracle for tests.

The k/v blocks of the forward and dq kernels, and the q/dO blocks and
lse/D rows of the dk/dv kernel, are whole-sequence blocks held in VMEM,
so the sequence length a call can take is bounded: ``check_vmem_fit``
refuses a shape beyond the bound at trace time (interpret mode never
notices, the chip's compiler says RESOURCE_EXHAUSTED).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ray_tpu._private import jax_compat

NEG_INF = -1e30

# Mosaic's scoped-VMEM limit for one kernel on a v5e (libtpu 0.0.34).
VMEM_LIMIT_BYTES = 16 * 2 ** 20
_LANES = 128


def _beside_the_blocks(head_dim: int) -> int:
    """What a kernel holds beside its whole-sequence blocks at tiles of
    512: three score-sized float32 tiles and the q, o, dO and
    accumulator tiles. Fitted to where the v5e's compiler draws the
    line (3.3 MiB at heads of 64, 3.6 at 128), not derived."""
    return 3 * 2 ** 20 + 9 * 2 ** 9 * head_dim


def check_vmem_fit(seq_len: int, head_dim: int, dtype,
                   backward: bool = False) -> None:
    """Raise ValueError for a shape whose whole-sequence blocks cannot
    stay resident under ``VMEM_LIMIT_BYTES``.

    Forward (and dq): the k and v blocks. Backward (dk/dv): the q and dO
    blocks and the lse and delta rows, which are lane-dense (4 bytes a
    position each). A head narrower than 128 pads to a full lane tile;
    all are double-buffered. This counts the kernel's own buffers; XLA
    also places operands in VMEM as it sees fit, so the chip's compiler
    draws the real line a little to either side. Checked by deviceless
    compiles for a v5e in bf16 (tests/test_chip_compile.py holds both
    sides at heads of 128): L=12288 is admitted and compiles, forward
    and backward, at heads of 64 and of 128 and at 1, 2 or 4 query
    groups per kv head; L=12800 is refused and the compiler refuses it
    at heads of 128 (the dq kernel), at heads of 64 it is admitted and
    compiles and 13312 is refused by both. A forward alone is refused a
    tile or two before the compiler would (13312 at heads of 128).
    Streaming these blocks lifts the bound (ROADMAP S2).
    """
    itemsize = jnp.dtype(dtype).itemsize
    width = max(head_dim, _LANES)
    if backward:
        row = 2 * (2 * width * itemsize + 2 * 4)
        what = "q and dO blocks and lse and delta rows of the dk/dv kernel"
    else:
        row = 2 * 2 * width * itemsize
        what = "k and v blocks"
    beside = _beside_the_blocks(head_dim)
    need = seq_len * row + beside
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"flash_attention: sequence length {seq_len} at head width "
            f"{head_dim} ({jnp.dtype(dtype).name}) keeps about "
            f"{need / 2 ** 20:.1f} MiB of whole-sequence {what} and tiles "
            f"resident, over the {VMEM_LIMIT_BYTES // 2 ** 20} MiB VMEM "
            f"limit of one kernel; the longest sequence this shape can "
            f"take is {(VMEM_LIMIT_BYTES - beside) // row}")


# ------------------------------------------------------------------ kernels

_NT = (((1,), (1,)), ((), ()))  # a @ b.T: the matrix unit reads b as it lies


def _dot_nt(a, b):
    return lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _as_row(column):
    """f32 [n, 1] -> [1, n]: one transpose of a lane tile's width, made
    once a q tile (never a block)."""
    n = column.shape[0]
    return jnp.broadcast_to(column, (n, _LANES)).T[:1]


def _as_column(row):
    """f32 [1, n] -> [n, 1], the same way."""
    n = row.shape[1]
    return jnp.broadcast_to(row, (_LANES, n)).T[:, :1]


def _positions(tile, row0, col0):
    """Positions of a score tile's rows, [n, 1], and columns, [1, m],
    from those of its first row and column."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, (tile.shape[0], 1), 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, (1, tile.shape[1]), 1)
    return rows, cols


def _crossed(body, carry, first, end, count):
    """The blocks [first, end) the diagonal crosses, masked. Where their
    number is known at trace time (``count``: the tiles of one side
    divide the other's) they are straight-line code, which the chip's
    scheduler overlaps with the kernel's head and tail: a loop of ONE
    iteration cost 0.3 to 0.5 us more than the block itself (v5e,
    PERF.md section 6, PR 64). A loop otherwise."""
    masked = functools.partial(body, masked=True)
    if count is None:
        return lax.fori_loop(first, end, masked, carry)
    for t in range(count):
        carry = masked(first + t, carry)
    return carry


def _whole_tiles(inner: int, outer: int):
    """How many tiles of ``inner`` make one of ``outer``, None if not a
    whole number."""
    return outer // inner if outer % inner == 0 else None


def _k_block_bounds(qi, block_q: int, block_k: int, causal: bool,
                    seq_len: int):
    """(unmasked, end, count) for the q tile ``qi``: k blocks
    [0, unmasked) lie wholly at or left of the tile's FIRST query and
    need no mask, blocks [unmasked, end) reach up to its last query and
    are masked (``count`` of them where that is known at trace time);
    those beyond are not visited. From positions, not from equal
    indices: block_q and block_k may differ."""
    if not causal:
        return seq_len // block_k, seq_len // block_k, 0
    first = qi * block_q
    end = lax.div(first + block_q + block_k - 1, block_k)
    count = _whole_tiles(block_k, block_q)
    if count is None:
        return lax.div(first + 1, block_k), end, None
    return end - count, end, count


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                block_k: int, scale: float, causal: bool, seq_len: int):
    qi = pl.program_id(1)
    q = q_ref[0]                                      # [bq, D] bf16
    d = q.shape[-1]

    def body(j, carry, masked):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]   # bf16
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        # bf16 x bf16 on the MXU, f32 accumulation; scale applied to the
        # f32 result (not the bf16 operand) to keep softmax numerics.
        s = _dot_nt(q, k) * scale
        if masked:
            q_pos, k_pos = _positions(s, qi * block_q, j * block_k)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    carry = (jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32),
             jnp.zeros((block_q, 1), dtype=jnp.float32),
             jnp.zeros((block_q, d), dtype=jnp.float32))
    unmasked, end, count = _k_block_bounds(qi, block_q, block_k, causal,
                                           seq_len)
    carry = lax.fori_loop(0, unmasked,
                          functools.partial(body, masked=False), carry)
    if causal:
        carry = _crossed(body, carry, unmasked, end, count)
    m_fin, l_fin, acc = carry
    l_safe = jnp.maximum(l_fin, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = _as_row(m_fin + jnp.log(l_safe))


def _fit_block(requested: int, seq_len: int) -> int:
    """Largest divisor of seq_len <= requested: the grid and k-loop use
    exact tiling, so a non-dividing block would silently drop tail rows/
    keys. Correctness over tile-shape preference."""
    b = min(requested, seq_len)
    while seq_len % b:
        b -= 1
    return b


def _specs(shapes_and_maps, interpret):
    kwargs = {} if interpret else {"memory_space": pltpu.VMEM}
    return [pl.BlockSpec(shape, index_map, **kwargs)
            for shape, index_map in shapes_and_maps]


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool):
    """q/k/v: [BH, L, D] -> (o [BH, L, D], lse [BH, 1, L] f32: a row a
    head, lane-dense in HBM and in the kernels)."""
    bh, seq_len, d = q.shape
    block_q = _fit_block(block_q, seq_len)
    block_k = _fit_block(block_k, seq_len)
    scale = d ** -0.5
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, seq_len=seq_len)
    in_specs = _specs([
        ((1, block_q, d), lambda b, i: (b, i, 0)),
        ((1, seq_len, d), lambda b, i: (b, 0, 0)),
        ((1, seq_len, d), lambda b, i: (b, 0, 0)),
    ], interpret)
    out_specs = _specs([
        ((1, block_q, d), lambda b, i: (b, i, 0)),
        ((1, 1, block_q), lambda b, i: (b, 0, i)),
    ], interpret)
    return pl.pallas_call(
        kernel,
        grid=(bh, seq_len // block_q),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_len), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# ----------------------------------------------------------------- backward


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref,
                   delta_ref, *, block_q: int, block_k: int, scale: float,
                   causal: bool, seq_len: int):
    qi = pl.program_id(1)
    q = q_ref[0]                                       # [bq, D] bf16
    do = do_ref[0]                                     # [bq, D] bf16
    o = o_ref[0]
    lse = _as_column(lse_ref[0])                       # [bq, 1] f32
    d = q.shape[-1]

    # D_i = rowsum(dO * O), f32: formed here, once a row, and handed to
    # the dk/dv kernel as a row.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [bq, 1]
    delta_ref[0] = _as_row(delta)

    def body(j, dq_acc, masked):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot_nt(q, k) * scale
        if masked:
            q_pos, k_pos = _positions(s, qi * block_q, j * block_k)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                           # [bq, bk] f32
        dp = _dot_nt(do, v)
        ds = p * (dp - delta)                          # [bq, bk] f32
        return dq_acc + jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    dq = jnp.zeros((block_q, d), dtype=jnp.float32)
    unmasked, end, count = _k_block_bounds(qi, block_q, block_k, causal,
                                           seq_len)
    if causal:
        # First (a sum's order is free): straight-line code here shares
        # its stretch with the relayouts above, which a loop would not
        # hide (dq 3.05 -> 2.81 ms a layer at 4k, PERF.md 6, PR 64; in
        # dk/dv, whose head is light, the crossed blocks last LOST).
        dq = _crossed(body, dq, unmasked, end, count)
    dq = lax.fori_loop(0, unmasked, functools.partial(body, masked=False),
                       dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, dk_ref,
                    dv_ref, *, block_q: int, block_k: int, scale: float,
                    causal: bool, seq_len: int):
    """In the keys' orientation: scores, probabilities and their
    gradients are [bk, bq] tiles, which the two accumulating products
    read as they lie; lse and delta come in as rows."""
    ki = pl.program_id(1)
    k = k_ref[0]                                       # [bk, D] bf16
    v = v_ref[0]
    d = k.shape[-1]

    def body(i, carry, masked):
        dk_acc, dv_acc = carry
        rows = pl.ds(i * block_q, block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, :, rows]                      # [1, bq] f32
        delta = delta_ref[0, :, rows]
        st = _dot_nt(k, q) * scale                     # [bk, bq] f32
        if masked:
            k_pos, q_pos = _positions(st, ki * block_k, i * block_q)
            st = jnp.where(q_pos >= k_pos, st, NEG_INF)
        pt = jnp.exp(st - lse)
        dv_acc = dv_acc + jnp.dot(pt.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
        dpt = _dot_nt(v, do)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk_acc = dk_acc + jnp.dot(dst, q,
                                  preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    carry = (jnp.zeros((block_k, d), dtype=jnp.float32),
             jnp.zeros((block_k, d), dtype=jnp.float32))
    num_q_blocks = seq_len // block_q
    unmasked = 0
    if causal:
        # q tiles before ``first`` never attend to this k tile; tiles
        # [first, unmasked) hold a query before its last key and are
        # masked; from ``unmasked`` on every query sees every key.
        first = lax.div(ki * block_k, block_q)
        count = _whole_tiles(block_q, block_k)
        if count is None:
            unmasked = jnp.minimum(
                lax.div((ki + 1) * block_k + block_q - 2, block_q),
                num_q_blocks)
        else:
            unmasked = first + count
        carry = _crossed(body, carry, first, unmasked, count)
    dk, dv = lax.fori_loop(unmasked, num_q_blocks,
                           functools.partial(body, masked=False), carry)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal: bool, block_q: int,
               block_k: int, interpret: bool):
    bh, seq_len, d = q.shape
    block_q = _fit_block(block_q, seq_len)
    block_k = _fit_block(block_k, seq_len)
    scale = d ** -0.5
    kw = dict(block_q=block_q, block_k=block_k, scale=scale, causal=causal,
              seq_len=seq_len)

    full = ((1, seq_len, d), lambda b, i: (b, 0, 0))
    full_row = ((1, 1, seq_len), lambda b, i: (b, 0, 0))
    q_tile = ((1, block_q, d), lambda b, i: (b, i, 0))
    q_row = ((1, 1, block_q), lambda b, i: (b, 0, i))
    k_tile = ((1, block_k, d), lambda b, i: (b, i, 0))

    dq, delta = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(bh, seq_len // block_q),
        in_specs=_specs([q_tile, full, full, q_tile, q_row, q_tile],
                        interpret),
        out_specs=_specs([q_tile, q_row], interpret),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, o, lse, do)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(bh, seq_len // block_k),
        in_specs=_specs([full, k_tile, k_tile, full_row, full_row, full],
                        interpret),
        out_specs=_specs([k_tile, k_tile], interpret),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, lse, delta, do)
    return dq, dk, dv


# ------------------------------------------------- reference (test oracle)


def _blockwise_reference(q, k, v, causal: bool, block_k: int):
    """Pure-JAX blockwise attention (same online-softmax math); the
    correctness oracle for the kernels in tests."""
    bh, seq_len, d = q.shape
    block_k = _fit_block(block_k, seq_len)
    scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(seq_len)[:, None]
    n_blocks = seq_len // block_k
    kb = k.astype(jnp.float32).reshape(bh, n_blocks, block_k, d)
    vb = v.astype(jnp.float32).reshape(bh, n_blocks, block_k, d)

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kj, vj, j = blk
        s = jnp.einsum("bqd,bkd->bqk", qf, kj)
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bqk,bkd->bqd", p, vj)
        return (m_new, l_new, acc), None

    m0 = jnp.full((bh, seq_len, 1), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((bh, seq_len, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((bh, seq_len, d), dtype=jnp.float32)
    (_, l_fin, acc), _ = lax.scan(
        jax.checkpoint(step, prevent_cse=False),
        (m0, l0, acc0),
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(n_blocks)))
    return (acc / jnp.maximum(l_fin, 1e-30)).astype(q.dtype)


# ------------------------------------------------------------- public entry


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    return o


# checkpoint_name tags of the two residuals only the kernel can make. A
# jax.checkpoint policy that saves them (models/llama.py,
# remat_policy="attention") starts its backward at the backward kernels;
# without one the forward kernel runs again there.
SAVED_NAMES = ("flash_o", "flash_lse")


def _core_fwd(q, k, v, causal, block_q, block_k, interpret):
    # Only a differentiated call gets here.
    check_vmem_fit(q.shape[1], q.shape[2], q.dtype, backward=True)
    o, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret)
    # On the very values that go into the residuals: a name on the
    # caller's transposed copy leaves these unnamed and the kernel
    # runs twice.
    o, lse = (checkpoint_name(t, name)
              for t, name in zip((o, lse), SAVED_NAMES))
    return o, (q, k, v, o, lse)


def _core_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g.astype(q.dtype), causal,
                      block_q, block_k, interpret)


_flash_core.defvjp(_core_fwd, _core_bwd)


def flash_attention_gspmd(q, k, v, causal: bool = True,
                          block_q: int = 512, block_k: int = 512,
                          interpret: bool | None = None):
    """Flash attention callable from inside a GSPMD-jitted model on a
    multi-device mesh.

    Mosaic kernels cannot be auto-partitioned by GSPMD, so on a mesh
    that actually splits batch/heads the pallas call must be dropped
    into shard_map explicitly: batch stays over (dp, fsdp), heads over
    tp, sequence unsharded (ring attention owns the sp axis). With no
    ambient mesh — or a mesh whose dp/fsdp/tp axes are all singleton —
    this is exactly ``flash_attention``.
    """
    mesh = jax_compat.ambient_mesh()
    if mesh is None or all(dict(mesh.shape).get(a, 1) == 1
                           for a in ("dp", "fsdp", "tp")):
        return flash_attention(q, k, v, causal, block_q, block_k,
                               interpret)
    spec = P(("dp", "fsdp"), None, "tp", None)

    @functools.partial(jax.shard_map,
                       in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def inner(q, k, v):
        return flash_attention(q, k, v, causal, block_q, block_k,
                               interpret)

    return inner(q, k, v)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool | None = None):
    """Flash attention over [B, L, H, D] (layout used by models/llama).

    GQA-native: with fewer kv heads than q heads the kernel runs once
    per query-head group over the SAME kv tensors — repeated kv heads
    are never materialized (a ``jnp.repeat`` would burn HBM bandwidth
    and capacity exactly where flash is supposed to save it); kv
    gradients from the groups accumulate through autodiff.
    Differentiable via fused pallas backward kernels. ``interpret=None``
    interprets on the CPU platform only.
    """
    b, l, h, d = q.shape
    kvh = k.shape[2]
    if interpret is None:
        interpret = jax_compat.interpret_kernels()
    check_vmem_fit(l, d, q.dtype)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, l, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, l, d)
    if kvh == h:
        qt = q.transpose(0, 2, 1, 3).reshape(b * h, l, d)
        out = _flash_core(qt, kt, vt, causal, block_q, block_k, interpret)
        return out.reshape(b, h, l, d).transpose(0, 2, 1, 3)
    reps = h // kvh
    # q head j attends kv head j // reps: regroup q as
    # [reps, B*kvh, L, D] and vmap the kernel over the rep axis with kv
    # UNMAPPED — pallas folds the vmap into the launch grid and every
    # rep reads the same kv blocks, so utilization matches the dense
    # call without the repeated-kv tensor ever existing. kv gradients
    # sum over the rep axis through the batched vjp.
    qg = q.reshape(b, l, kvh, reps, d).transpose(3, 0, 2, 1, 4)
    qg = qg.reshape(reps, b * kvh, l, d)
    out = jax.vmap(
        lambda qq: _flash_core(qq, kt, vt, causal, block_q, block_k,
                               interpret))(qg)
    out = out.reshape(reps, b, kvh, l, d).transpose(1, 3, 2, 0, 4)
    return out.reshape(b, l, h, d)
