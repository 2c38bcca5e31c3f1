"""The hybrid family's two programs (Phi-4-mini-flash's three caches) at
the cell's real widths and its three table widths, compiled for a
described ``v5e:2x2`` (``v5e_compile.py``)."""

import math

import jax
import jax.numpy as jnp
import pytest
from v5e_compile import (  # noqa: F401 — the fixtures
    _memory_of, v5e_chip, v5e_devices)


@pytest.mark.parametrize("width", [64, 128, 256])
def test_hybrid_prefill_chunk_at_each_table_width_on_v5e(v5e_chip, width):
    """Phi-4-mini-flash's prefill program (published widths, 32 rows, a
    table of 256 blocks of 16; 8 of its 32 layers) at the default chunk
    and its three widths: the three caches updated where they lie, the
    rings as long as the window, the chunk and a block (656 positions a
    row), the one pool gathered at the chunk's width for its one
    request, and logits of one row of the 200,064 words."""
    import re

    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.models import phi4flash
    from ray_tpu.serve.llm_engine import hybrid

    config = phi4flash.Phi4FlashConfig(num_layers=8)
    rows, block, table = 32, 16, 256
    chunk = GLOBAL_CONFIG.llm_prefill_chunk

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(lambda: hybrid.FAMILY.init_params(
        config, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: hybrid.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    assert cache["win_k"].shape[2] == 512 + chunk + block == 656
    family = hybrid.FAMILY
    compiled = family.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache,
        on_chip(family.pack_prefill_chunk(chunk, width, (), 0, (), 0),
                jnp.int32), None).compile()
    memory = compiled.memory_analysis()
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize
                      for c in jax.tree.leaves(cache))
    assert memory.alias_size_in_bytes >= cache_bytes
    # The float32 scores of the 40 heads (116 MiB at the whole width).
    assert memory.temp_size_in_bytes < 160 * 2 ** 20
    text = compiled.as_text()
    positions = width * block
    assert f"bf16[{width},{block},1280]" in text
    assert f"f32[1,40,{chunk},{positions}]" in text
    assert re.search(rf"\[(1,)?{chunk},200064\]", text) is None
    assert "f32[1,2,200064]" in text
    assert [line for line in text.splitlines()
            if " copy(" in line and "= bf16[1,8193,16,1280]" in line] == []
    if width < table:
        assert re.search(r"\[[0-9,]*4096[0-9,]*\]", text) is None


@pytest.mark.parametrize("width", [64, 128, 256])
def test_hybrid_decode_step_with_prev_at_each_table_width_on_v5e(v5e_chip,
                                                                 width):
    """The same for Phi-4-mini-flash's decode program (32 rows, 8 of its
    32 layers): with the step before's tokens as a sixth argument the
    three caches are still updated where they lie and the temporaries
    are the five-argument program's."""
    from ray_tpu.models import phi4flash
    from ray_tpu.serve.llm_engine import hybrid

    config = phi4flash.Phi4FlashConfig(num_layers=8)
    rows, block, table, chunk = 32, 16, 256, 128

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(lambda: hybrid.FAMILY.init_params(
        config, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: hybrid.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    args = (params, cache,
            on_chip(hybrid.FAMILY.pack_decode_rows(rows, width, ()),
                    jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip), None)
    step = hybrid.FAMILY.make_engine_decode_step(config, block)
    prev = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    alias, temp, arguments = _memory_of(step.lower(*args, prev).compile())
    alias_before, temp_before, arguments_before = _memory_of(
        step.lower(*args).compile())
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize
                      for c in jax.tree.leaves(cache))
    assert alias == alias_before >= cache_bytes
    assert abs(temp - temp_before) < 64 * 2 ** 10
    assert 0 < arguments - arguments_before <= 4096


@pytest.mark.parametrize("width", [64, 128, 256])
def test_hybrid_decode_step_at_each_table_width_on_v5e(v5e_chip, width):
    """Phi-4-mini-flash's decode program (published widths, 32 rows, a
    table of 256 blocks of 16; 8 of its 32 layers: the scans make the
    program the same but for their length) at its three widths, 1024,
    2048 and 4096 positions a row: the three caches updated where they
    lie, the one pool gathered at the step's width in bf16 and never
    widened, and the temporaries (the gathered keys and values: 0.64 GiB
    at the whole width) shrinking with it."""
    import re

    from ray_tpu.models import phi4flash
    from ray_tpu.serve.llm_engine import hybrid
    from ray_tpu.serve.llm_engine.engine import table_widths

    assert width in table_widths(256)
    config = phi4flash.Phi4FlashConfig(num_layers=8)
    rows, block, table, chunk = 32, 16, 256, 128

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(lambda: hybrid.FAMILY.init_params(
        config, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: hybrid.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    compiled = hybrid.FAMILY.make_engine_decode_step(config, block).lower(
        params, cache,
        on_chip(hybrid.FAMILY.pack_decode_rows(rows, width, ()),
                jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip),
        None).compile()
    memory = compiled.memory_analysis()
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize
                      for c in jax.tree.leaves(cache))
    assert memory.alias_size_in_bytes >= cache_bytes
    positions = width * block
    gathered = 2 * rows * positions * 1280 * 2   # keys and values, bf16
    assert memory.temp_size_in_bytes < gathered + 64 * 2 ** 20
    text = compiled.as_text()
    assert f"bf16[{rows},{positions},1280]" in text
    assert re.search(rf"= f32\[{rows},{positions},1280\]", text) is None
    assert [line for line in text.splitlines()
            if " copy(" in line and "= bf16[1,8193,16,1280]" in line] == []
    if width < table:
        assert f"[{rows},4096,1280]" not in text
