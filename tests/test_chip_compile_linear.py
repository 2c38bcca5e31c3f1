"""The linear family's programs (Kimi-Linear: a float32 delta-rule state
a row beside a latent pool that only the latent layers own) at the
cell's real widths, compiled for a described ``v5e:2x2``
(``v5e_compile.py``)."""

import math
import re

import jax
import jax.numpy as jnp
from v5e_compile import (  # noqa: F401 — the fixtures
    HANDS_ON, assert_experts_reach_the_kernel_whole, compiled_kernels,
    kernel_calls, kv_attention_calls, v5e_chip, v5e_devices)


def test_linear_programs_at_the_cells_widths_on_v5e(v5e_chip,
                                                    compiled_kernels):
    """Kimi-Linear's two programs as an engine builds them (published
    widths, 32 of 256 experts held, an eighth of the vocabulary, 64
    rows, a table of 256 blocks of 16; the dense layer and ONE period:
    the scan makes the programs the same but for their length). The
    state ``f32[4,64,32,128,128]`` (0.5 GiB here, 1.25 at the cell's 10
    KDA layers), the convolutions' inputs and the pool of the ONE latent
    layer are updated where they lie: aliased, never copied whole. The
    decode step reads the pool through the tables inside
    ``ops/paged_latent_attention.py`` handed the POOL's layer index;
    the prefill chunk's chunkwise form keeps its sub-chunks' decays
    ``[2,32,64,64,128]`` (256 MiB in float32) inside the reductions
    that use them: temporaries stay under a quarter of a GiB. A KDA
    layer's rule is ONE call of ``ops/kda_state_update.py`` on the
    stacked state and the layer's index (PR 51): its ``q``, ``k``,
    ``v``, ``g`` keep the shape ``f32[64,32,128]`` the trace's selectors
    find the KDA operations by
    (``benchmark/metrics/kda_state_roofline.kimi.json``), and nothing
    else in the step takes the state, whole or a layer of it: the third
    pass over it is gone by construction, not by the compiler's mood.
    In both programs each of the period's four expert layers is ONE
    call of ``ops/grouped_expert_ffn.py`` on its place's three tensors,
    stacked over the periods, and the period's index (PR 52): nothing
    else takes an expert tensor, a layer of it or a copy of it."""
    from ray_tpu.models import kimi_linear as kimi
    from ray_tpu.serve.llm_engine import linear

    config = kimi.KimiLinearConfig(vocab_size=20480, num_layers=5,
                                   experts_held=32)
    assert config.kinds == ("kda", "kda", "kda", "latent", "kda")
    rows, block, table, chunk = 64, 16, 256, 128

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(lambda: kimi.init_params(
        config, jax.random.PRNGKey(0))), config.dtype)
    cache = on_chip(jax.eval_shape(lambda: linear.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (1, 1 + rows * table, block, 640),
        "kda": (4, rows, 32, 128, 128), "conv": (4, 3, rows, 12288)}
    cache_bytes = sum(math.prod(v.shape) * v.dtype.itemsize
                      for v in cache.values())
    state = "f32[4,64,32,128,128]"
    family = linear.FAMILY
    step = family.make_engine_decode_step(config, block).lower(
        params, cache,
        on_chip(family.pack_decode_rows(rows, table, ()), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip), None,
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    ).compile()
    prefill = family.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache,
        on_chip(family.pack_prefill_chunk(chunk, table, (), 0, (), 0),
                jnp.int32), None).compile()
    for program, limit in ((step, 0.125), (prefill, 0.25)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes
        assert memory.temp_size_in_bytes < limit * 2 ** 30
        text = program.as_text()
        assert [line for line in text.splitlines()
                if " copy(" in line and f"= {state}" in line] == []
        # The head on the rows that are read, over the share of the
        # vocabulary held.
        assert re.search(r"f32\[(64|1,2),20480\]", text)
        assert_experts_reach_the_kernel_whole(text, (1, 32, 2304, 1024), 4)
    lines = step.as_text().splitlines()
    rule = [line for line in lines
            if "custom-call(" in line and "kda_state_update" in line]
    assert len(rule) == 4                   # the four KDA layers
    for line in rule:
        # The four vectors of a row and head among the operands, the
        # output beside the state among the results; the state aliased.
        operands = line.split("operand_layout_constraints={")[1].split(
            "}, output_to_operand_aliasing")[0]
        assert operands.count("f32[64,32,128]{") == 4 and state in operands
        assert line.split(" custom-call(")[0].count("f32[64,32,128]{") == 1
        assert "output_to_operand_aliasing={{1}: (6, {})}" in line
    # What else names the state only hands it on (the entry's parameter,
    # a loop's tuple and its elements); no operation has a layer of it.
    assert [line[:200] for line in lines
            if state in line and line not in rule
            and not HANDS_ON.search(line)] == []
    assert not any("f32[64,32,128,128]" in line for line in lines)
    assert kernel_calls(prefill.as_text(), "kda_state_update") == []
    calls = [line for line in lines
             if "custom-call(" in line and "paged_latent_attention" in line]
    assert len(calls) == 1                  # the one latent layer
    assert "bf16[1,16385,16,640]" in calls[0] and "bf16[64,640]" in calls[0]
    assert kernel_calls(prefill.as_text(), "paged_latent_attention") == []
    # No gathered view of the pool in the step: it reads by row.
    assert re.search(r"\[64,4096,640\]", step.as_text()) is None


def test_solar_programs_at_the_cells_widths_on_v5e(v5e_chip,
                                                   compiled_kernels):
    """Solar-Open2's programs as an engine builds them (published
    widths, 40 of 320 experts held, an eighth of the vocabulary, 64
    rows, a table of 256 blocks of 16; one period, which IS the cell's
    depth): the ONE decode step, at the whole table, and the prefill
    chunk at the whole. The state ``f32[3,64,64,128,128]`` (0.75 GiB: 4
    MiB a row and layer), the convolutions' inputs and the key and value
    pools of the ONE full layer are updated where they lie: aliased,
    never copied whole. A KDA layer's rule is ONE call of
    ``ops/kda_state_update.py`` at 64 heads (a grid of 64 rows by 4
    blocks of 16 heads, 4,096 scalars of ``beta`` prefetched), and
    nothing else in the step takes the state. The full layer reads BY
    ROW (PR 56): ONE call of ``ops/paged_kv_attention.py`` in the step,
    handed both pools WHOLE, the grouped queries ``bf16[64,64,128]`` and
    the rows' fresh keys and values; no gathered view of the pools
    (``[64,4096,8,128]``, ``[16384,16,8,128]``) in the step, whose
    temporaries stay under a sixteenth of a GiB where the gathered
    step's were held under 0.75; the prefill chunk keeps the gathered
    view of its one row and calls no such kernel. No kernel of another
    family's pool is called. Each of the four expert layers is ONE call
    of ``ops/grouped_expert_ffn.py``."""
    from ray_tpu.models import solar_open2 as solar
    from ray_tpu.serve.llm_engine import linear
    from ray_tpu.serve.llm_engine import model as paged_model

    config = solar.SolarOpen2Config(vocab_size=24576, num_layers=4,
                                    experts_held=40)
    assert config.kinds == ("gqa", "kda", "kda", "kda")
    rows, block, table, chunk = 64, 16, 256, 128

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    family = paged_model.family(config)
    assert family is linear.FAMILIES["gqa"] and family.reads_by_row
    params = on_chip(jax.eval_shape(lambda: family.init_params(
        config, jax.random.PRNGKey(0))), config.dtype)
    cache = on_chip(jax.eval_shape(lambda: family.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    pool = (1, 1 + rows * table, block, 8, 128)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": pool, "v": pool, "kda": (3, rows, 64, 128, 128),
        "conv": (3, 3, rows, 24576)}
    cache_bytes = sum(math.prod(v.shape) * v.dtype.itemsize
                      for v in cache.values())
    assert round(cache_bytes / 2 ** 30, 2) == 1.78
    state = "f32[3,64,64,128,128]"

    step = family.make_engine_decode_step(config, block).lower(
        params, cache,
        on_chip(family.pack_decode_rows(rows, table, ()), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip), None,
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    ).compile()
    prefill = family.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache,
        on_chip(family.pack_prefill_chunk(chunk, table, (), 0, (), 0),
                jnp.int32), None).compile()
    for program, limit in ((step, 1 / 16), (prefill, 0.25)):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= cache_bytes
        assert memory.temp_size_in_bytes < limit * 2 ** 30
        text = program.as_text()
        assert [line for line in text.splitlines()
                if " copy(" in line and f"= {state}" in line] == []
        # The head on the rows that are read, over the share of the
        # vocabulary held.
        assert re.search(r"f32\[(64|1,2),24576\]", text)
        assert_experts_reach_the_kernel_whole(text, (1, 40, 4096, 1280), 4)
        assert kernel_calls(text, "paged_latent_attention") == []
    lines = step.as_text().splitlines()
    rule = [line for line in lines
            if "custom-call(" in line and "kda_state_update" in line]
    assert len(rule) == 3                   # the three KDA layers
    for line in rule:
        operands = line.split("operand_layout_constraints={")[1].split(
            "}, output_to_operand_aliasing")[0]
        assert operands.count("f32[64,64,128]{") == 4 \
            and state in operands and "f32[4096]{" in operands
        assert "output_to_operand_aliasing={{1}: (6, {})}" in line
    assert [line[:200] for line in lines
            if state in line and line not in rule
            and not HANDS_ON.search(line)] == []
    assert kernel_calls(prefill.as_text(), "kda_state_update") == []
    # The one full layer: the pools whole, the queries grouped, the
    # rows' fresh keys and values; the tables, the lengths, the entry.
    calls = kv_attention_calls(step.as_text())
    assert len(calls) == 1
    operands = calls[0].split("operand_layout_constraints={")[1]
    assert operands.count("bf16[1,16385,16,8,128]{") == 2
    assert operands.count("bf16[64,8,128]{") == 2
    assert operands.count("bf16[64,64,128]{") == 1
    assert "s32[16384]{" in operands and "s32[64]{" in operands
    assert kv_attention_calls(prefill.as_text()) == []
    # No gathered view of the pools in the step, and no copy of one:
    # what else names a pool hands it on or writes the step's position.
    assert re.search(r"\[64,4096,8,128\]|\[16384,16,8,128\]",
                     step.as_text()) is None
    assert [line[:200] for line in lines
            if " copy(" in line and "bf16[1,16385,16,8,128]" in line] == []
    # The chunk gathers its one row's view at the table it is handed.
    assert "bf16[256,16,8,128]" in prefill.as_text()
