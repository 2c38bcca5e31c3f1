"""The routed experts' feed-forward of a serving pass (``models/moe.py``)
as ONE pallas TPU kernel an expert layer: the three matrices of exactly
those held experts that some row of the pass weighs above zero are read
from HBM once each, where they lie in the layers' stacked weights.

``moe.expert_ffn`` is an all-experts product: it reads every expert the
layer holds, whatever share of them the pass chose (half of the Kimi
cell's 32 a decode step, a fifth of OLMoE's 64). Here, by the pallas
guide (/opt/skills/guides/pallas_guide.md):

- scalar-prefetched (SMEM): the layer's index in the stack, the held
  experts with a non-zero column of ``combine`` compacted to the front
  (``order`` ``[E]``) and their number (``count``). The stacked weights
  stay WHOLE in HBM and the layer and the expert are chosen in the
  blocks' index maps: ``w_gate[layer]`` around a kernel would be a copy
  of all the layer's experts, more than the skip saves;
- grid ``(E, M / block_m)``: slot ``s`` below ``count`` takes expert
  ``order[s]``, whole where its three matrices fit VMEM twice over
  (every cell's do: three contiguous reads), else a tile of its width
  at a time: ``w_gate`` and ``w_up`` ``[H, block_m]`` and ``w_down``
  ``[block_m, H]``, double-buffered by the pipeline, and adds ``(silu(x W_gate) * (x W_up) * weight) W_down``
  for all ``N`` rows to one float32 ``[N, H]`` accumulator in VMEM. A
  slot at or above ``count`` maps to the blocks the last touched expert's
  last tile had, so the pipeline issues no read for it, and skips its
  arithmetic (``pl.when``);
- ``moe.expert_ffn``'s arithmetic and rounding points: products of
  ``dtype`` operands accumulated in float32, gate and up rounded to
  ``dtype``, the combine weight applied in float32 before the
  down-projection, an expert a row did not choose contributing exactly
  zero (``where``), the sum over experts and tiles in float32, rounded
  to ``dtype`` once.

The weights are the matrix unit's stationary operand and the ``N`` rows
(16 to 128) stream through it, as in XLA's own code for the all-experts
product; the reads set the pace (``BLOCK_BYTES``, below). The other
orientation (the rows stationary, every product transposed) halted the
chip on its first call, and XLA's own ``while`` over the touched experts
with ``dynamic_slice``d operands reached 48 to 68% of the memory's peak
where this reaches 84 to 91 (my chip runs, PR 52).

On the CPU platform the kernel interprets
(``jax_compat.interpret_kernels``), so the tests run its own logic;
``moe.expert_ffn`` is the plain form they compare against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import jax_compat

F32 = jnp.float32

#: What a tile's three blocks may take of VMEM, double-buffered, and the
#: kernel's scoped VMEM: the blocks, the rows, the float32 accumulator
#: and the products' temporaries (the chip has 128 MiB). An expert goes
#: WHOLE where it fits (42 MiB at ``H`` 3584, ``M`` 1024): its three
#: matrices are then three contiguous reads. A tile of ``w_gate`` and
#: ``w_up`` is a strided read (``block_m`` columns of every row), which
#: the kernel alone hardly felt (four layers in a loop: 84 to 91% of the
#: memory's 819 GB/s at 512 and at 1024 alike, 75 to 85% at 128 and 256)
#: and the OLMoE cell's decode program did (twelve layers, 3.2 GB a
#: tensor: 19.2 us an expert at 512, 18.1 at 256, 16.5 whole, which is
#: 93% of the peak; my chip runs, PR 52). A kernel of the same blocks
#: that ONLY READS took 0.2 to 2.5% less at every tile: the arithmetic
#: hides behind the reads at 16 rows as at 128.
BLOCK_BYTES = 48 << 20
VMEM_LIMIT_BYTES = 64 << 20


def block_width(hidden: int, width: int, itemsize: int) -> int:
    """The tile of an expert's width ``M`` the kernel takes at a time:
    the whole width where its three blocks fit ``BLOCK_BYTES`` twice
    over, else the largest divisor of ``M`` that is a multiple of 128
    lanes and does; an ``M`` that is no multiple of 128 (a test's tiny
    expert) goes whole or not at all."""
    def fits(m):
        return 3 * 2 * hidden * m * itemsize <= BLOCK_BYTES

    if fits(width):
        return width
    if width % 128 == 0:
        for tm in range(width - 128, 0, -128):
            if width % tm == 0 and fits(tm):
                return tm
    raise ValueError(
        f"grouped_expert_ffn: no block of an expert [{hidden}, {width}] "
        f"(the whole, or a divisor of whole lanes) fits {BLOCK_BYTES} "
        f"bytes of VMEM")


def _kernel(layer_ref, order_ref, count_ref,            # scalar prefetch
            x_ref, combine_ref, gate_ref, up_ref, down_ref,     # inputs
            out_ref, acc_ref):                          # output, scratch
    del layer_ref                                       # the index maps' alone
    slot, tile = pl.program_id(0), pl.program_id(1)
    dtype = out_ref.dtype

    @pl.when((slot == 0) & (tile == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(slot < count_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=F32)
        up = jnp.dot(x, up_ref[...], preferred_element_type=F32)
        # This expert's column of the combine weights, [N, 1].
        combine = combine_ref[...]
        column = jax.lax.broadcasted_iota(jnp.int32, combine.shape, 1)
        weight = jnp.sum(jnp.where(column == order_ref[slot], combine, 0.0),
                         axis=1, keepdims=True)
        # expert_ffn's rounding points: gate, up, silu and their product.
        rounded = lambda a: a.astype(dtype).astype(F32)
        hidden = rounded(rounded(jax.nn.silu(rounded(gate))) * rounded(up))
        hidden = jnp.where(weight > 0, hidden * weight, 0.0).astype(dtype)
        acc_ref[...] += jnp.dot(hidden, down_ref[...],
                                preferred_element_type=F32)

    @pl.when((slot == pl.num_programs(0) - 1)
             & (tile == pl.num_programs(1) - 1))
    def _():
        out_ref[...] = acc_ref[...].astype(dtype)


def touched_order(combine: jax.Array):
    """combine ``[N, E]`` -> (``order`` int32 ``[E]``: the experts with a
    weight above zero in some row first, each group in its own order;
    their number, int32 ``[1]``)."""
    touched = jnp.any(combine > 0, axis=0)
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    return order, jnp.sum(touched, dtype=jnp.int32).reshape(1)


def grouped_expert_ffn(w_gate, w_up, w_down, layer, x, combine, *,
                       block_m: "int | None" = None,
                       interpret: "bool | None" = None):
    """w_gate, w_up ``[L, E, H, M]`` and w_down ``[L, E, M, H]`` (the
    layers' stacks, whole), ``layer`` an int32 scalar, x ``[N, H]`` of
    the weights' dtype and combine ``[N, E]`` float32. Returns
    ``sum_e combine[:, e] * W_down_e(silu(W_gate_e x) * W_up_e x)`` of
    layer ``layer``, ``[N, H]`` in x's dtype, reading only the experts
    with a weight above zero."""
    layers, experts, hidden, width = w_gate.shape
    rows = x.shape[0]
    dtype = x.dtype
    if w_up.shape != w_gate.shape or \
            w_down.shape != (layers, experts, width, hidden) or \
            x.shape != (rows, hidden) or combine.shape != (rows, experts) \
            or any(w.dtype != dtype for w in (w_gate, w_up, w_down)) \
            or combine.dtype != F32:
        raise ValueError(
            f"grouped_expert_ffn: stacks w_gate {w_gate.shape} "
            f"{w_gate.dtype}, w_up {w_up.shape} {w_up.dtype} and w_down "
            f"{w_down.shape} {w_down.dtype} take x [N, {hidden}] of their "
            f"dtype and a float32 combine [N, {experts}]; got x {x.shape} "
            f"{x.dtype}, combine {combine.shape} {combine.dtype}")
    tm = block_m or block_width(hidden, width, dtype.itemsize)
    if width % tm or (tm % 128 and tm != width):
        raise ValueError(
            f"grouped_expert_ffn: experts of width {width} in blocks of "
            f"{tm} (a divisor that is a multiple of 128, or the whole)")
    tiles = width // tm
    if interpret is None:
        interpret = jax_compat.interpret_kernels()
    vmem = {} if interpret else {"memory_space": pltpu.VMEM}
    order, count = touched_order(combine)

    def expert_tile(slot, tile, order, count):
        # A slot past the touched experts stays on the last one's last
        # tile: the same block as the step before, which is not read
        # again.
        live = slot < count[0]
        last = jnp.maximum(count[0] - 1, 0)
        return (order[jnp.where(live, slot, last)],
                jnp.where(live, tile, tiles - 1))

    def columns(slot, tile, layer, order, count):
        expert, tile = expert_tile(slot, tile, order, count)
        return layer[0], expert, 0, tile

    def rows_of(slot, tile, layer, order, count):
        expert, tile = expert_tile(slot, tile, order, count)
        return layer[0], expert, tile, 0

    whole = lambda *_: (0, 0)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(experts, tiles),
            in_specs=[
                pl.BlockSpec((rows, hidden), whole, **vmem),
                pl.BlockSpec((rows, experts), whole, **vmem),
                pl.BlockSpec((None, None, hidden, tm), columns, **vmem),
                pl.BlockSpec((None, None, hidden, tm), columns, **vmem),
                pl.BlockSpec((None, None, tm, hidden), rows_of, **vmem)],
            out_specs=pl.BlockSpec((rows, hidden), whole, **vmem),
            scratch_shapes=[pltpu.VMEM((rows, hidden), F32)]),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_expert_ffn",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, count,
      x, combine, w_gate, w_up, w_down)
