"""The latent family's programs (Xing4.0: one vector a position, read
through the tables by ``ops/paged_latent_attention.py``) at the cell's
real widths, compiled for a described ``v5e:2x2`` (``v5e_compile.py``)."""

import math

import jax
import jax.numpy as jnp
import pytest
from v5e_compile import (  # noqa: F401 — the fixtures
    assert_experts_reach_the_kernel_whole, compiled_kernels, v5e_chip,
    v5e_devices)


@pytest.mark.parametrize("width", [128, 256, 512])
def test_latent_programs_at_each_table_width_on_v5e(v5e_chip, width,
                                                    compiled_kernels):
    """Xing4.0's programs as an engine builds them (published widths, 32
    rows, a table of 512 blocks of 16; one dense and one expert layer:
    the scans make the programs the same but for their length): the
    prefill chunk at its three widths, 2,048, 4,096 and 8,192 positions
    a row, and the ONE decode step, at the whole table (it reads by
    row: ``engine.table_widths``), compiled beside the chunk of that
    width. The pool of one vector a position (576 values in 640 lanes)
    is updated where it lies and never copied: declared 576 wide, the
    runtime lays it out with the blocks along the lanes and both
    programs copy all of it twice. The decode step reads it through
    the tables inside ``ops/paged_latent_attention.py`` (compiled here
    for the v5e: its tables of ``[32, 512]`` in SMEM, two buffers of 64
    pages in VMEM): no gathered view, no table-wide scores, no slice or
    copy of a layer of the pool, temporaries of a few MiB; the prefill
    chunk expands its one row's view inside the score product. In both
    programs the expert layer is ONE call of
    ``ops/grouped_expert_ffn.py`` on the sparse layers' three stacked
    tensors and the layer's index among them (PR 52): nothing else
    takes an expert tensor, a layer of it or a copy of it."""
    import re

    from ray_tpu.models import xing
    from ray_tpu.serve.llm_engine import latent
    from ray_tpu.serve.llm_engine.engine import table_widths

    assert width in table_widths(512)
    config = xing.XingConfig(
        num_layers=2, first_k_dense=1,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096})
    rows, block, table, chunk = 32, 16, 512, 128
    positions = width * block

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(lambda: xing.init_params(
        config, jax.random.PRNGKey(0))), config.dtype)
    cache = on_chip(jax.eval_shape(lambda: latent.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    pool = (2, 1 + rows * table, block, 640)
    assert cache["latent"].shape == pool
    pool_bytes, shape = math.prod(pool) * 2, ",".join(map(str, pool))
    family = latent.FAMILY
    prefill = family.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache,
        on_chip(family.pack_prefill_chunk(chunk, width, (), 0, (), 0),
                jnp.int32), None).compile()
    memory = prefill.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    # The float32 scores of the 32 heads (128 MiB at the whole width).
    assert memory.temp_size_in_bytes < 3 * 32 * chunk * positions * 4
    text = prefill.as_text()
    assert f"f32[32,{chunk},{positions}]" in text
    assert [line for line in text.splitlines()
            if " copy(" in line and f"= bf16[{shape}]" in line] == []
    assert re.search(rf"\[(1,)?{chunk},131072\]", text) is None
    assert "f32[1,2,131072]" in text
    assert_experts_reach_the_kernel_whole(text, (1, 64, 3584, 1024), 1)
    if width < table:
        return      # the engine builds no decode step there
    step = family.make_engine_decode_step(config, block).lower(
        params, cache,
        on_chip(family.pack_decode_rows(rows, width, ()), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip), None,
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    ).compile()
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    text = step.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "paged_latent_attention" in line]
    assert len(calls) == 2                  # a layer each, this short stack
    for call in calls:
        # The tables flat in SMEM, the rows' entries, the pool whole.
        assert f"s32[{rows * width}]" in call and "bf16[32,640]" in call
        assert f"bf16[{shape}]" in call
    for view in (rf"\[{rows},{positions},640\]",        # a gathered view
                 rf"\[{rows * width},{block},640\]",    # ... as gathered
                 rf"f32\[{rows},{positions},(1,)?32\]",  # table-wide scores
                 rf"pred\[{rows},{positions}\]",        # ... and their mask
                 rf"= bf16\[(1,)?{pool[1]},{block},640\]"):  # a layer
        assert re.search(view, text) is None, view
    assert [line for line in text.splitlines()
            if " copy(" in line and f"= bf16[{shape}]" in line] == []
    assert_experts_reach_the_kernel_whole(text, (1, 64, 3584, 1024), 1)
