"""The block family's decode program (diffusion over blocks: SDAR's
widths) as the engine runs it, compiled for a described ``v5e:2x2``
(``v5e_compile.py``), at the whole table and at each rung of the
gathered decode ladder, which it still runs; its prefill chunk is the
paged family's (``test_chip_compile_paged.py``)."""

import math

import jax
import jax.numpy as jnp
import pytest
from v5e_compile import (  # noqa: F401 — the fixtures
    _memory_of, _sdar, assert_experts_reach_the_kernel_whole,
    compiled_kernels, kernel_calls, kv_attention_calls, pallas_calls,
    v5e_chip, v5e_devices)


def _block_step(v5e_chip, width=128):
    """The block family's decode program at SDAR's widths (2 of the
    cell's 7 layers, 32 rows over a pool of 128 blocks of 16 a row) and
    its arguments without ``prev``, as shapes on the described chip, the
    rows' tables ``width`` blocks wide; the cache's shapes and ``prev``'s
    beside them."""
    from ray_tpu.models import moe
    from ray_tpu.serve.llm_engine import model as paged_model

    config, rows, block, table = _sdar(), 32, 16, 128
    family = paged_model.family(config)
    assert family is paged_model._blockwise(4) and not family.reads_by_row

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    # As the engine holds them: the family's laying of ``init_params``.
    params = on_chip(jax.eval_shape(lambda: family.lay_params(
        family.init_params(config, jax.random.PRNGKey(0)))), config.dtype)
    cache = on_chip(jax.eval_shape(lambda: family.init_cache(
        config, 1 + rows * table, block, rows, 128)))
    args = (params, cache,
            on_chip(family.pack_decode_rows(rows, width, ()), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip),
            on_chip(jax.eval_shape(moe.init_stats)))
    prev = jax.ShapeDtypeStruct((rows, config.block_length), jnp.int32,
                                sharding=v5e_chip)
    return family.make_engine_decode_step(config, block), args, cache, prev


def _cache_bytes(cache) -> int:
    return sum(math.prod(c.shape) * c.dtype.itemsize
               for c in jax.tree.leaves(cache))


@pytest.mark.parametrize("width", [32, 64, 128])
def test_block_step_at_each_table_width_on_v5e(v5e_chip, compiled_kernels,
                                               width):
    """The gathered decode ladder, which the block family still runs
    (``_blockwise`` says ``reads_by_row=False``: a pass has 4 query rows
    a row under the block's horizon; the paged family it is made of
    reads by row since PR 58 and has ONE decode program): the block
    pass at the three widths the engine builds it at
    (``engine.table_widths`` of 128 blocks), 32 rows over the whole
    pool. The gather is of this width and no wider, in the pool's dtype
    and never widened to float32; no call of
    ``ops/paged_kv_attention.py``; the pool is updated where it lies
    and never copied."""
    import re

    from ray_tpu.serve.llm_engine.engine import table_widths

    assert width in table_widths(128)
    step, args, cache, prev = _block_step(v5e_chip, width)
    compiled = step.lower(*args, prev).compile()
    assert _memory_of(compiled)[0] >= _cache_bytes(cache)
    text = compiled.as_text()
    pool_text = "= bf16[" + ",".join(map(str, cache["k"].shape)) + "]"
    assert [line for line in text.splitlines()
            if " copy(" in line and pool_text in line] == []
    gathered = 32 * width                       # pages a layer gathers
    assert re.search(rf"bf16\[({gathered},16|16,{gathered}),4,128\]",
                     text) is not None
    assert re.search(rf"= f32\[({gathered},16|16,{gathered}),4,128\]",
                     text) is None
    if width < 128:
        assert f"[{32 * 128},16,4,128]" not in text
    # By the call's line, not the whole text: the table of source files
    # names ``tests/test_paged_kv_attention.py`` where that file ran
    # first on this worker (``v5e_compile.kernel_calls``). Every kernel
    # the pass calls, by any name, is the experts'.
    assert kv_attention_calls(text) == []
    assert pallas_calls(text) == kernel_calls(text, "grouped_expert_ffn")
    assert_experts_reach_the_kernel_whole(text, (2, 128, 2048, 768), 1)


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
    step, args, cache, prev = _block_step(v5e_chip)
    compiled, before = step.lower(*args, prev).compile(), \
        step.lower(*args).compile()
    alias, temp, arguments = _memory_of(compiled)
    alias_before, temp_before, arguments_before = _memory_of(before)
    assert alias == alias_before >= _cache_bytes(cache)
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
