"""The block family's decode program (diffusion over blocks: SDAR's
widths) as the engine runs it, compiled for a described ``v5e:2x2``
(``v5e_compile.py``); its prefill chunk is the paged family's
(``test_chip_compile_paged.py``)."""

import math

import jax
import jax.numpy as jnp
from v5e_compile import (  # noqa: F401 — the fixtures
    _memory_of, _sdar, assert_experts_reach_the_kernel_whole,
    compiled_kernels, v5e_chip, v5e_devices)


def test_block_step_with_prev_on_v5e(v5e_chip, compiled_kernels):
    """The block family's decode program as the engine runs it since it
    keeps a pass ahead (SDAR's widths, 2 of the cell's 7 layers, 32 rows
    at the whole table of 128 blocks of 16): the blocks the pass before
    left, ``[32, 4]`` int32, as a sixth argument and one select in front
    of the embedding. Beside the five-argument program (which
    ``benchmark/sizing_family.py`` still lowers): the pool aliased as
    much, the temporaries (76 MiB: the float32 logits of 128 positions)
    the same to within 0.5 MiB (the compiler assigns a few small buffers
    to other memory spaces: 250 KiB), and ``prev`` an argument that is
    read. A pass's expert layer is ONE call of
    ``ops/grouped_expert_ffn.py`` on the three stacked tensors (PR 52);
    nothing else takes an expert tensor, a layer of it or a copy."""
    from ray_tpu.models import moe
    from ray_tpu.serve.llm_engine import model as paged_model

    config, rows, block, table = _sdar(), 32, 16, 128
    family = paged_model.family(config)

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(lambda: family.init_params(
        config, jax.random.PRNGKey(0))), config.dtype)
    cache = on_chip(jax.eval_shape(lambda: family.init_cache(
        config, 1 + rows * table, block, rows, 128)))
    args = (params, cache,
            on_chip(family.pack_decode_rows(rows, table, ()), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip),
            on_chip(jax.eval_shape(moe.init_stats)))
    step = family.make_engine_decode_step(config, block)
    prev = jax.ShapeDtypeStruct((rows, config.block_length), jnp.int32,
                                sharding=v5e_chip)
    compiled, before = step.lower(*args, prev).compile(), \
        step.lower(*args).compile()
    alias, temp, arguments = _memory_of(compiled)
    alias_before, temp_before, arguments_before = _memory_of(before)
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize
                      for c in jax.tree.leaves(cache))
    assert alias == alias_before >= cache_bytes
    assert abs(temp - temp_before) < 512 * 2 ** 10 < temp / 100
    assert 0 < arguments - arguments_before <= 4096

    def entry_arguments(hlo):
        layout = hlo[hlo.index("entry_computation_layout={("):]
        return layout[:layout.index(")->")]

    for program in (compiled, before):
        assert_experts_reach_the_kernel_whole(
            program.as_text(), (2, 128, 2048, 768), 1)
    assert "s32[32,4]{" in entry_arguments(compiled.as_text())
    assert "s32[32,4]{" not in entry_arguments(before.as_text())
