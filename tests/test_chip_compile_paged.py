"""The paged family's programs (identical layers over one pool of keys
and values: the Mistral and OLMoE serve cells' two programs, and the
prefill chunk they share with SDAR), compiled for a described ``v5e:2x2``
(``v5e_compile.py``): what the chip's compiler makes of the pool, the
decode step's read by row, a chunk's gathered keys and the experts at
the cells' real widths."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest
from v5e_compile import (  # noqa: F401 — the fixtures
    _memory_of, _sdar, assert_experts_reach_the_kernel_whole,
    compiled_kernels, kernel_calls, kv_attention_calls, pallas_calls,
    projection_layers_made, v5e_chip, v5e_devices)

# An expert model's programs hold ``ops/grouped_expert_ffn.py``.
pytestmark = pytest.mark.usefixtures("compiled_kernels")


def _lower_paged_step(program, config, batch, block, table, chip,
                      width=None, prev=False):
    """``decode_step`` or ``prefill_chunk``, the plain program or, as
    ``engine_...``, the one the engine calls (one host array, the key
    carried), lowered on shapes placed on the described chip; the
    pool's shape beside it. A sparse configuration's step carries its
    expert accumulator. ``width``: the blocks of a row's table the
    engine's decode step or prefill chunk is given
    (``engine.table_widths``; the pool stays ``table`` blocks a row);
    the chunk is the default's length; ``prev``: with the step before's
    tokens ``[batch]`` (a block family's: blocks ``[batch,
    block_length]``) as the engine passes them (without: the
    five-argument call of ``benchmark/sizing.py``). The weights'
    shapes are ``llama.init_params``' for a plain program, which
    ``chip_smoke.py`` and ``benchmark/sizing.py`` hand that tree, and
    for an engine's what the ENGINE holds: the family's laying of it
    (``model.lay_for_serving``: ``wqkv`` for ``wq``, ``wk`` and ``wv``),
    and the decode program the configuration's family's own."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.models import llama, moe
    from ray_tpu.serve.llm_engine import model as paged_model

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    family = paged_model.family(config)
    lay = family.lay_params if program.startswith("engine_") \
        else lambda tree: tree
    params = jax.tree.map(
        lambda s: on_chip(s.shape, config.dtype),
        jax.eval_shape(
            lambda: lay(llama.init_params(config, jax.random.PRNGKey(0)))))
    pool_shape = (config.num_layers, 1 + batch * table, block,
                  config.num_kv_heads, config.head_dim)
    pool = {"k": on_chip(pool_shape, config.dtype),
            "v": on_chip(pool_shape, config.dtype)}
    stats = None
    if config.num_experts > 0:
        stats = on_chip(jax.eval_shape(moe.init_stats).shape)
    chunk = GLOBAL_CONFIG.llm_prefill_chunk
    if program == "engine_decode_step":
        rows = family.pack_decode_rows(batch, width or table, ())
        before = (batch, config.block_length) if config.block_length \
            else (batch,)
        lowered = family.make_engine_decode_step(config, block).lower(
            params, pool, on_chip(rows.shape), on_chip((2,), jnp.uint32),
            stats, *([on_chip(before)] if prev else []))
    elif program == "engine_prefill_chunk":
        lowered = paged_model.make_engine_prefill_chunk(
            config, block, chunk).lower(
                params, pool, on_chip((2 + 2 * chunk + (width or table),)),
                stats)
    elif program == "decode_step":
        lowered = paged_model.make_decode_step(config, block).lower(
            params, pool, on_chip((batch, 1)), on_chip((batch,)),
            on_chip((batch, table)), on_chip((2,), jnp.uint32),
            on_chip((batch,), jnp.float32), stats)
    else:
        lowered = paged_model.make_prefill_chunk(config, block).lower(
            params, pool, on_chip((1, chunk)), on_chip((1, chunk)),
            on_chip((1, table)), on_chip(()), on_chip(()), stats)
    return lowered, pool_shape


def test_engine_decode_compiles_for_v5e(v5e_chip):
    """The paged engine's ONE decode program at chip_smoke.py's widths
    (Llama-2-7B, 2 layers): fits one chip with room to spare."""
    from ray_tpu.models import llama

    config = dataclasses.replace(
        llama.LlamaConfig.llama2_7b(), num_layers=2, max_seq_len=1024)
    lowered, _ = _lower_paged_step("decode_step", config, 8, 16,
                                   1024 // 16, v5e_chip)
    memory = lowered.compile().memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 8 * 2 ** 30)


def _mistral_serve():
    """The Mistral serve cells' widths, 2 of their 16 layers."""
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=32768, hidden_size=4096, intermediate_size=14336,
        num_layers=2, num_heads=32, num_kv_heads=8, head_dim=128,
        max_seq_len=2048, rope_theta=1e6)


@pytest.mark.parametrize("program", [
    "decode_step", "prefill_chunk", "engine_decode_step",
    "engine_prefill_chunk"])
def test_paged_steps_update_the_pool_in_place_on_v5e(v5e_chip, program):
    """The serve cells' widths (Mistral-7B-v0.3: 32 query on 8 key-value
    heads of 128; 2 of its layers, 16 rows, 128 blocks of 16). The chip's
    compiler must keep the donated pool where it is and repeat or widen
    nothing the size of a chunk's gathered keys: at these sizes a
    repeated float32 copy of them is 0.5 GiB and a copy of the 2-layer
    pool 0.13 GiB, and neither shows in a CPU test."""
    lowered, pool_shape = _lower_paged_step(program, _mistral_serve(), 16,
                                            16, 128, v5e_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.25 * 2 ** 30
    # k and v, two bytes an element, both updated where they were given.
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool_shape)
    pool_text = "= bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert [line for line in compiled.as_text().splitlines()
            if " copy(" in line and pool_text in line] == []


def _olmoe(num_layers=12):
    """``benchmark/configs/olmoe-1b-7b-serve-1chip.json`` as the
    harness builds it: OLMoE-1B-7B's widths, 12 of its 16 layers."""
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=num_layers, num_heads=16, num_kv_heads=16, head_dim=128,
        max_seq_len=2048, num_experts=64, experts_per_token=8, qk_norm=True)


# A float32 tensor the size of one layer's experts, alone or stacked.
F32_EXPERTS = r"f32\[(\d+,)?64,(2048,1024|1024,2048)\]"


@pytest.mark.parametrize("program", [
    "decode_step", "prefill_chunk", "engine_decode_step",
    "engine_prefill_chunk"])
def test_sparse_paged_steps_fit_and_widen_no_expert_on_v5e(v5e_chip,
                                                           program):
    """The OLMoE serve cell's two programs at its real size (16 rows x
    2048 positions, 12 layers: 12.76 GiB of arguments). What must not
    appear: a float32 copy of an expert tensor (1.5 GiB a layer) or a
    transposed bf16 one (768 MiB a layer: a flat ``bth,ehm->btem``
    product made the compiler transpose each [64, 2048, 1024] whole);
    a copy of the donated pool; a float32 copy of a layer's gathered
    keys (256 MiB: a gathered decode step's lone query row per head
    made the scores a matrix-vector product, which the compiler widened
    the keys for; the step reads by row since PR 58 and gathers
    nothing: its 129 MiB of temporaries, one gathered
    ``bf16[2048,16,16,128]``, went with that); neither program has
    temporaries to speak of. An expert layer is ONE call of
    ``ops/grouped_expert_ffn.py`` on the three stacked tensors and the
    layer's index (PR 52), and nothing else in either program takes an
    expert tensor, a layer of it or a copy of it."""
    import re

    lowered, pool_shape = _lower_paged_step(program, _olmoe(), 16, 16, 128,
                                            v5e_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 16 * 2 ** 20
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 0.9 * 15.75 * 2 ** 30)
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool_shape)
    text = compiled.as_text()
    assert re.search(F32_EXPERTS, text) is None
    assert re.search(r"= f32\[(2048,16|16,2048),16,128\]", text) is None
    pool_text = "= bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert [line for line in text.splitlines()
            if " copy(" in line and pool_text in line] == []
    # The accumulator rides along: int32 [2, 4] in, the same out.
    assert "s32[2,4]" in text
    assert_experts_reach_the_kernel_whole(text, (12, 64, 2048, 1024), 1)


@pytest.mark.parametrize("program", ["decode_step", "engine_decode_step"])
@pytest.mark.parametrize("model", ["mistral", "olmoe"])
def test_the_one_decode_program_reads_by_row_on_v5e(v5e_chip, model,
                                                    program):
    """The paged family's ONE decode program (``PAGED.reads_by_row``,
    PR 58: the engine builds it at the whole table alone, 128 blocks of
    16, 16 rows), plain and as the engine calls it, for both 16-row
    serve configurations. The layers' scan holds ONE call of
    ``ops/paged_kv_attention.py``, handed both pools WHOLE, the grouped
    queries, the rows' fresh keys and values, the tables and the
    lengths; no gathered view of the pools at any rung the gathered step
    had (``[512 | 1024 | 2048, 16, kv, 128]``) in any dtype; the pool is
    updated where it lies and never copied; the temporaries stay under a
    sixteenth of a GiB (Solar's bound, ``test_chip_compile_linear.py``;
    OLMoE's gathered step held 129 MiB). The copy of a layer's ``wq``
    sliced off the stacked weights did NOT go with the gathered form (it
    was the projection's operand, not the read's): it went with the
    layout an engine holds (PR 61,
    ``test_no_layer_of_the_projections_is_copied_on_v5e``)."""
    import re

    config = _mistral_serve() if model == "mistral" else _olmoe(2)
    lowered, pool_shape = _lower_paged_step(program, config, 16, 16, 128,
                                            v5e_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2 ** 30 // 16
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool_shape)
    text = compiled.as_text()
    pool_text = "bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert [line for line in text.splitlines()
            if " copy(" in line and f"= {pool_text}" in line] == []
    heads, kv = config.num_heads, config.num_kv_heads
    calls = kv_attention_calls(text)
    assert len(calls) == 1
    operands = calls[0].split("operand_layout_constraints={")[1]
    assert operands.count(pool_text + "{") == 2
    assert operands.count(f"bf16[16,{kv},128]{{") == 2 + (heads == kv)
    assert operands.count(f"bf16[16,{heads},128]{{") == 1 + 2 * (heads == kv)
    assert "s32[2048]{" in operands and "s32[16]{" in operands
    assert re.search(rf"\[(512|1024|2048),16,{kv},128\]", text) is None
    assert re.search(F32_EXPERTS, text) is None
    if model == "olmoe":
        assert_experts_reach_the_kernel_whole(text, (2, 64, 2048, 1024), 1)
    else:           # no expert layer: no kernel of theirs, by any name
        assert kernel_calls(text, "grouped_expert_ffn") == []
        assert pallas_calls(text) == calls


@pytest.mark.parametrize("model,program", [
    ("mistral", "engine_decode_step"), ("mistral", "engine_prefill_chunk"),
    ("olmoe", "engine_decode_step"), ("sdar", "engine_decode_step")])
def test_no_layer_of_the_projections_is_copied_on_v5e(v5e_chip, model,
                                                      program):
    """On the shapes an ENGINE holds (``model.lay_for_serving`` over
    ``llama.init_params``: ``wqkv [n, E, (H + 2 KV) D]``), Mistral's
    decode step and prefill chunk, OLMoE's decode step and SDAR's block
    pass run no operation that makes a layer of the projections'
    weights, in any layout: the layer is sliced inside the ONE product's
    fusion, as the feed-forward's are. Until PR 61 each program held
    three to five (``constant_dynamic-slice_fusion.6/.7/.8``, ``bf16[1,
    E, H | KV, 128]`` in ``S(1)``, the chunk's second rewrite of ``wq``
    and asynchronous slices of ``wk`` and ``wv``: 1.13 ms of an 11.39 ms
    Mistral step; ledger, PR 60), and on ``init_params``' own shapes,
    which ``chip_smoke.py`` and ``benchmark/sizing.py`` still lower, it
    holds them yet: the helper has to see those."""
    config, rows = {"mistral": (_mistral_serve(), 16),
                    "olmoe": (_olmoe(2), 16), "sdar": (_sdar(), 32)}[model]
    lowered, _ = _lower_paged_step(program, config, rows, 16, 128, v5e_chip,
                                   prev=program == "engine_decode_step")
    assert projection_layers_made(lowered.compile().as_text(), config) == []
    if model == "sdar":
        return
    # The plain program on the tree ``serving_params`` returns: the same
    # step with the three weights apart, and the copies the ledger named.
    plain, _ = _lower_paged_step(program[len("engine_"):], config, rows, 16,
                                 128, v5e_chip)
    apart = projection_layers_made(plain.compile().as_text(), config)
    assert len(apart) >= 3 and any(
        "constant_dynamic-slice_fusion" in line for line in apart)


@pytest.mark.parametrize("width", [32, 64, 128])
@pytest.mark.parametrize("model", ["mistral", "olmoe", "sdar"])
def test_prefill_chunk_at_each_table_width_on_v5e(v5e_chip, model, width):
    """The engine's prefill program at the default chunk of 128 tokens
    and at the three widths it is built at, for the three serve
    configurations of identical layers (Mistral and OLMoE 16 rows, SDAR
    32, over the whole pool). The pool is updated where it lies and
    never copied; the head runs on the one row that is read, so nothing
    the size of a chunk's logits exists (float32 ``[128, vocabulary]``:
    16 MB for Mistral, 78 for SDAR); the scores are as wide as the rung
    and no wider; no expert tensor is widened or transposed; and the
    temporaries stay under 64 MiB."""
    import re

    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.llm_engine.engine import table_widths

    assert width in table_widths(128)
    chunk = GLOBAL_CONFIG.llm_prefill_chunk
    config, rows = {"mistral": (_mistral_serve(), 16),
                    "olmoe": (_olmoe(2), 16), "sdar": (_sdar(), 32)}[model]
    lowered, pool_shape = _lower_paged_step(
        "engine_prefill_chunk", config, rows, 16, 128, v5e_chip, width=width)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool_shape)
    text = compiled.as_text()
    pool_text = "= bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert [line for line in text.splitlines()
            if " copy(" in line and pool_text in line] == []
    vocabulary, positions = config.vocab_size, width * 16
    assert re.search(rf"\[(1,)?{chunk},{vocabulary}\]", text) is None
    assert re.search(rf"f32\[(1,)?2,{vocabulary}\]", text) is not None
    # The chunk's scores: every query row against the rung's positions.
    assert re.search(rf"f32\[[0-9,]*{chunk},{positions}\]", text) is not None
    if width < 128:
        assert re.search(rf"f32\[(\d+,){{2,}}{chunk},2048\]", text) is None
    assert re.search(
        rf"= f32\[({positions},16|16,{positions}),{config.num_kv_heads},128\]",
        text) is None
    if config.num_experts:
        e, m = config.num_experts, config.intermediate_size
        assert re.search(rf"f32\[(\d+,)?{e},(2048,{m}|{m},2048)\]",
                         text) is None
        assert_experts_reach_the_kernel_whole(text, (2, e, 2048, m), 1)
    else:           # a dense chunk gathers: no kernel at all, by any name
        assert kernel_calls(text, "grouped_expert_ffn") == []
        assert pallas_calls(text) == []


@pytest.mark.parametrize("model", ["mistral", "olmoe"])
def test_decode_step_with_prev_on_v5e(v5e_chip, model):
    """The program the engine runs since it keeps a step ahead: the
    step before's tokens ``[16]`` int32 as a sixth argument, one select
    in front of the embedding. Beside the five-argument program (which
    ``benchmark/sizing.py`` still lowers) at the one width a step that
    reads by row has, the whole table: the pool aliased as much, the
    temporaries the same to within a few vectors of 16, the arguments 64
    bytes more (the tokens, padded), ``prev`` an argument that is read,
    and the read the same ONE call of ``ops/paged_kv_attention.py``."""
    config = _mistral_serve() if model == "mistral" else _olmoe(2)
    without, pool_shape = _lower_paged_step(
        "engine_decode_step", config, 16, 16, 128, v5e_chip)
    with_prev, _ = _lower_paged_step(
        "engine_decode_step", config, 16, 16, 128, v5e_chip, prev=True)
    assert len(with_prev.in_avals[0]) == len(without.in_avals[0]) + 1 == 6
    compiled, before = with_prev.compile(), without.compile()
    alias, temp, arguments = _memory_of(compiled)
    alias_before, temp_before, arguments_before = _memory_of(before)
    assert alias == alias_before >= 2 * 2 * math.prod(pool_shape)
    assert abs(temp - temp_before) < 64 * 2 ** 10
    assert 0 < arguments - arguments_before <= 4096
    text = compiled.as_text()
    pool_text = "= bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert [line for line in text.splitlines()
            if " copy(" in line and pool_text in line] == []
    assert len(kv_attention_calls(text)) \
        == len(kv_attention_calls(before.as_text())) == 1
    # Kept by the program (jit drops an argument nothing reads).
    def entry_arguments(hlo):
        layout = hlo[hlo.index("entry_computation_layout={("):]
        return layout[:layout.index(")->")]

    assert "s32[16]{" in entry_arguments(text)
    assert "s32[16]{" not in entry_arguments(before.as_text())


def test_serving_params_never_hold_a_float32_expert_tensor_on_v5e(v5e_chip):
    """``[12, 64, 2048, 1024]`` is 6 GiB in float32: the cast runs in
    the initialisation's own program, which must keep no such buffer
    (float32 values exist inside its fusions only: the temporaries
    say so, the text cannot)."""
    from ray_tpu.models import llama

    config = _olmoe()
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip)
    compiled = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(config.dtype),
        llama.init_params(config, key))).lower(key).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    # bf16 throughout, to the tiling's padding of the small scales.
    assert 0 <= memory.output_size_in_bytes - 2 * config.num_params < 2 ** 20
