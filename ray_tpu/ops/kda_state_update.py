"""One position of the gated delta rule (``models/kimi_linear.py``) for
every row and head as ONE pallas TPU kernel: a head's float32 state is
read from HBM once and written once, where it lies.

A decode step of the linear family carries the KDA layers' states
stacked, ``[layers, rows, H, d, d]`` float32 (a head's ``[128, 128]`` is
64 KiB, a layer of 64 rows 128 MiB). Written in ``jnp`` the rule passes
over a layer three times: XLA fuses the two readings ``S'^T k`` and
``S'^T q`` into one reduce, then the rank-one update reads the state
again and writes it. Here, by the pallas guide
(/opt/skills/guides/pallas_guide.md):

- scalar-prefetched (SMEM): the layer's index and ``beta`` ``[rows *
  H]``. The stacked state stays WHOLE in HBM and is aliased to the
  output: the layer is chosen in the blocks' index maps
  (``state[layer]`` in and ``.at[layer].set`` out around a kernel would
  be two more passes over a layer), and the other layers are never
  touched;
- grid ``(rows, H / HEADS_PER_BLOCK)``: a block is a row's heads' states
  ``[1, 1, hb, d, d]`` in and out, double-buffered by the pipeline, so
  the next block's read and the last one's write are in flight while
  this one is used;
- per head, all float32 on the vector unit (no product is rounded; the
  matrix unit is not used): ``S' = S * exp(g)[:, None]``, ``predicted =
  S'^T k``, ``carried = S'^T q``, ``update = beta (v - predicted)``,
  ``S'' = S' + k[:, None] update[None, :]``, ``o = carried + update (q .
  k)``. ``q``, ``k`` and ``exp(g)`` weigh the state's ROWS, which lie
  along sublanes: the block's ``[3 hb, d]`` of them is transposed once
  and a head takes its three columns.

A row whose ``g`` and ``beta`` are 0 keeps its state to the bit (``S * 1
+ k * 0``); its block is read and written back like any other.

On the CPU platform the kernel interprets
(``jax_compat.interpret_kernels``), so the tests run its own logic;
``kimi_linear.kda_position`` is the plain form they compare against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu._private import jax_compat

F32 = jnp.float32

#: Heads a block holds (all of them where a model has fewer): 1 MiB in
#: and 1 MiB out at heads of 128, 4 MiB of VMEM double-buffered. On the
#: v5e at the Kimi cell's shapes (64 rows of 32 heads, ten layers; my
#: chip run, PR 51) 16 took 0.422 ms a layer and 32 0.424, 8 0.444; a
#: kernel of the same blocks that only copies took 0.412 (0.328 is 256
#: MiB at the memory's 819 GB/s): the rule's arithmetic hides behind
#: the DMAs, and a larger block gains nothing further.
HEADS_PER_BLOCK = 16


def _kernel(layer_ref, beta_ref,                    # scalar prefetch
            q_ref, k_ref, v_ref, g_ref, s_ref,      # inputs
            o_ref, s_out_ref):                      # outputs
    del layer_ref                                   # the index maps' alone
    hb = q_ref.shape[1]
    first = (pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)) * hb
    q, k, v = q_ref[0], k_ref[0], v_ref[0]          # [hb, d]
    # What weighs the state's rows, as columns: [d, 3 hb].
    columns = jnp.concatenate([q, k, jnp.exp(g_ref[0])], axis=0).T
    own = jnp.sum(q * k, axis=-1, keepdims=True)    # q . k [hb, 1]
    for h in range(hb):
        q_rows = columns[:, h:h + 1]                # [d, 1]
        k_rows = columns[:, hb + h:hb + h + 1]
        decayed = s_ref[0, 0, h] * columns[:, 2 * hb + h:2 * hb + h + 1]
        predicted = jnp.sum(decayed * k_rows, axis=0, keepdims=True)
        carried = jnp.sum(decayed * q_rows, axis=0, keepdims=True)
        update = beta_ref[first + h] * (v[h:h + 1] - predicted)   # [1, d]
        s_out_ref[0, 0, h] = decayed + k_rows * update
        o_ref[0, h:h + 1] = carried + update * own[h:h + 1]


def kda_state_update(state, layer, q, k, v, g, beta, *,
                     heads_per_block: "int | None" = None,
                     interpret: "bool | None" = None):
    """state ``[layers, rows, H, d, d]``, ``layer`` an int32 scalar, q,
    k, v, g ``[rows, H, d]`` and beta ``[rows, H]``, all float32.
    Advances layer ``layer`` of the state by one position of the rule
    in every row and head. Returns ``(o [rows, H, d], state)``; the
    state is the input's buffer where the caller donates it."""
    _, rows, heads, d, _ = state.shape
    if q.shape != (rows, heads, d) or beta.shape != (rows, heads) or any(
            x.dtype != F32 for x in (state, q, k, v, g, beta)):
        raise ValueError(
            f"kda_state_update: a float32 state {state.shape} takes float32 "
            f"q, k, v, g [{rows}, {heads}, {d}] and beta [{rows}, {heads}]; "
            f"got q {q.shape} {q.dtype}, beta {beta.shape} {beta.dtype}, "
            f"state {state.dtype}")
    hb = min(heads_per_block or HEADS_PER_BLOCK, heads)
    if heads % hb:
        raise ValueError(f"kda_state_update: {heads} heads in blocks of {hb}")
    if interpret is None:
        interpret = jax_compat.interpret_kernels()
    vmem = {} if interpret else {"memory_space": pltpu.VMEM}
    vector = pl.BlockSpec((1, hb, d), lambda r, j, *_: (r, j, 0), **vmem)
    matrix = pl.BlockSpec((1, 1, hb, d, d),
                          lambda r, j, layer, _: (layer[0], r, j, 0, 0),
                          **vmem)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, heads // hb),
            in_specs=[vector, vector, vector, vector, matrix],
            out_specs=[vector, matrix]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        # Among ALL operands, the scalar-prefetched ones too: the state.
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_state_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), beta.reshape(-1),
      q, k, v, g, state)
