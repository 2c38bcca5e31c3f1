"""What the chip's compiler says, asked without the chip; and the rules
that keep one process on each chip.

The first half compiles the main path's kernels at real widths for a
DESCRIBED ``v5e:2x2`` (guide on-chip-measurement, 2.3): the TPU compiler
is installed here and refuses what the chip would refuse — a slice off
the tiling, too much VMEM — which interpret mode never notices. Nothing
runs, so these say nothing about results or times. Skipped where the
topology cannot be described. The second half are CPU tests of the
rules of PR 21: who may see a chip, where the compile cache lives, no
CPU fallback on the measuring path.
"""

import dataclasses
import importlib
import json
import math
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ray_tpu.ops re-exports the function under the module's name.
fa = importlib.import_module("ray_tpu.ops.flash_attention")


@pytest.fixture(scope="module")
def v5e_devices():
    """The four chips of a described v5e:2x2. The persistent
    compilation cache is off around these compiles: an entry written
    for a described device cannot be read back without the chip, and
    the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_chip(v5e_devices):
    """Sharding on one chip of the described v5e:2x2."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_devices[0])


def _flash_grad(seq, heads, kv_heads, head_dim, chip):
    """(jitted fwd+bwd of the compiled kernel, its argument shapes)."""
    q = jax.ShapeDtypeStruct((1, seq, heads, head_dim), jnp.bfloat16,
                             sharding=chip)
    kv = jax.ShapeDtypeStruct((1, seq, kv_heads, head_dim), jnp.bfloat16,
                              sharding=chip)

    def loss(q, k, v):
        # interpret=False to the kernel itself: default_backend() is
        # the CPU during a deviceless compile.
        out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, kv, kv)


@pytest.mark.parametrize("seq,heads,kv_heads,head_dim", [
    (2048, 16, 8, 64),     # short heads of 64: half a lane tile
    (2048, 32, 32, 128),   # Llama-2-7B widths: chip_smoke.py's train phase
    (4096, 32, 8, 128),    # Llama-3-8B widths at the longest admitted 4k
])
def test_flash_kernels_compile_for_v5e(v5e_chip, seq, heads, kv_heads,
                                       head_dim):
    fn, shapes = _flash_grad(seq, heads, kv_heads, head_dim, v5e_chip)
    compiled = fn.lower(*shapes).compile()
    # Forward, dq and dk/dv kernels all reached Mosaic.
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("side", ["rule", "compiler"])
def test_flash_backward_refused_at_8k(v5e_chip, monkeypatch, side):
    """L=8192 at 32/8 heads of 128 (the llama3_8b preset's own
    max_seq_len): the dk/dv kernel's whole-sequence blocks do not fit.
    The rule refuses it at trace time, by name; and with the rule out
    of the way the chip's compiler refuses it too, so the rule is not
    refusing something that would have worked."""
    if side == "rule":
        fn, shapes = _flash_grad(8192, 32, 8, 128, v5e_chip)
        with pytest.raises(ValueError) as info:
            fn.lower(*shapes)
        message = str(info.value)
        assert "8192" in message and "128" in message
        assert "16 MiB" in message and "6348" in message
        return
    monkeypatch.setattr(fa, "check_vmem_fit", lambda *a, **k: None)
    fn, shapes = _flash_grad(8192, 32, 8, 128, v5e_chip)
    with pytest.raises(Exception, match="(?i)vmem"):
        fn.lower(*shapes).compile()


def test_fused_rms_norm_compiles_for_v5e(v5e_chip):
    from ray_tpu.ops.fused import rms_norm

    x = jax.ShapeDtypeStruct((2048, 4096), jnp.bfloat16, sharding=v5e_chip)
    scale = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=v5e_chip)

    def loss(x, scale):
        return rms_norm(x, scale, interpret=False).astype(jnp.float32).sum()

    # The value too: the backward is plain JAX, so gradients alone
    # would leave the kernel dead code.
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, scale).compile()
    assert "tpu_custom_call" in compiled.as_text()


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
    tokens ``[batch]`` as the engine passes them (without: the
    five-argument call of ``benchmark/sizing.py``)."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.models import llama, moe
    from ray_tpu.serve.llm_engine import model as paged_model

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(
        lambda s: on_chip(s.shape, config.dtype),
        jax.eval_shape(
            lambda: llama.init_params(config, jax.random.PRNGKey(0))))
    pool_shape = (config.num_layers, 1 + batch * table, block,
                  config.num_kv_heads, config.head_dim)
    pool = {"k": on_chip(pool_shape, config.dtype),
            "v": on_chip(pool_shape, config.dtype)}
    stats = None
    if config.num_experts > 0:
        stats = on_chip(jax.eval_shape(moe.init_stats).shape)
    chunk = GLOBAL_CONFIG.llm_prefill_chunk
    if program == "engine_decode_step":
        lowered = paged_model.make_engine_decode_step(config, block).lower(
            params, pool, on_chip((batch, 3 + (width or table))),
            on_chip((2,), jnp.uint32), stats,
            *([on_chip((batch,))] if prev else []))
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
    nothing the size of the gathered keys: at these sizes a repeated
    float32 copy of them is 0.5 GiB and a copy of the 2-layer pool 0.13
    GiB, and neither shows in a CPU test."""
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


@pytest.mark.parametrize("program, temporaries_mib", [
    ("decode_step", 160), ("prefill_chunk", 16),
    ("engine_decode_step", 160), ("engine_prefill_chunk", 16)])
def test_sparse_paged_steps_fit_and_widen_no_expert_on_v5e(
        v5e_chip, program, temporaries_mib):
    """The OLMoE serve cell's two programs at its real size (16 rows x
    2048 positions, 12 layers: 12.76 GiB of arguments). What must not
    appear: a float32 copy of an expert tensor (1.5 GiB a layer) or a
    transposed bf16 one (768 MiB a layer: a flat ``bth,ehm->btem``
    product made the compiler transpose each [64, 2048, 1024] whole);
    a copy of the donated pool; a float32 copy of a layer's gathered
    keys (256 MiB: a decode step's lone query row per head made the
    scores a matrix-vector product, which the compiler widened the keys
    for, until ``_paged_attention_block`` put a row of zeros beside
    it). The decode program's 129 MiB of temporaries are one gathered
    ``bf16[2048,16,16,128]`` (with 16 key-value heads it no longer fits
    the memory space Mistral's 64 MiB ones live in); the chunk program
    has none to speak of."""
    import re

    lowered, pool_shape = _lower_paged_step(program, _olmoe(), 16, 16, 128,
                                            v5e_chip)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < temporaries_mib * 2 ** 20
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


@pytest.mark.parametrize("width", [32, 64, 128])
@pytest.mark.parametrize("model", ["mistral", "olmoe"])
def test_decode_step_at_each_table_width_on_v5e(v5e_chip, model, width):
    """The engine's decode program at the three widths it is built at
    (``engine.table_widths`` of 128 blocks: 512, 1024 and 2048 positions
    a row), 16 rows over the whole pool, for both 16-row serve
    configurations: what the cases above hold the whole width to holds
    at a quarter and a half of it. The pool is updated where it lies and
    never copied; the gathered keys are never widened to float32 (D9's
    row of zeros keeps the scores a bf16 matrix product with 16
    key-value heads and no grouping); under the whole width there are
    no temporaries to speak of."""
    import re

    from ray_tpu.serve.llm_engine.engine import table_widths

    assert width in table_widths(128)
    config = _mistral_serve() if model == "mistral" else _olmoe(2)
    lowered, pool_shape = _lower_paged_step(
        "engine_decode_step", config, 16, 16, 128, v5e_chip, width=width)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    # Under the whole width the gathered keys leave HBM's temporaries.
    assert memory.temp_size_in_bytes < (160 if width == 128 else 16) * 2 ** 20
    assert memory.alias_size_in_bytes >= 2 * 2 * math.prod(pool_shape)
    text = compiled.as_text()
    pool_text = "= bf16[" + ",".join(map(str, pool_shape)) + "]"
    assert [line for line in text.splitlines()
            if " copy(" in line and pool_text in line] == []
    positions, kv = width * 16, config.num_kv_heads
    assert re.search(rf"= f32\[({positions},16|16,{positions}),{kv},128\]",
                     text) is None
    assert re.search(F32_EXPERTS, text) is None
    # The gather is of this width, in the pool's dtype.
    assert re.search(rf"bf16\[({positions},16|16,{positions}),{kv},128\]",
                     text) is not None
    if width < 128:
        assert f"[2048,16,{kv},128]" not in text


def _sdar(num_layers=2):
    """``benchmark/configs/sdar-30b-a3b-serve-1chip.json`` as the
    harness builds it: SDAR-30B-A3B's widths, 2 of the cell's 7 layers."""
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=151936, hidden_size=2048, intermediate_size=768,
        num_layers=num_layers, num_heads=32, num_kv_heads=4, head_dim=128,
        max_seq_len=2048, rope_theta=1e6, rms_norm_eps=1e-6,
        num_experts=128, experts_per_token=8, norm_topk_prob=True,
        qk_norm="head", block_length=4, denoising_steps=2,
        mask_token_id=151669)


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
    compiled = hybrid.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache,
        on_chip(hybrid.pack_prefill_chunk(chunk, width, (), 0, (), 0),
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


def _memory_of(compiled) -> tuple:
    memory = compiled.memory_analysis()
    return (memory.alias_size_in_bytes, memory.temp_size_in_bytes,
            memory.argument_size_in_bytes)


@pytest.mark.parametrize("width", [32, 64, 128])
@pytest.mark.parametrize("model", ["mistral", "olmoe"])
def test_decode_step_with_prev_at_each_table_width_on_v5e(v5e_chip, model,
                                                          width):
    """The program the engine runs since it keeps a step ahead: the
    step before's tokens ``[16]`` int32 as a sixth argument, one select
    in front of the embedding. Beside the five-argument program (which
    ``benchmark/sizing.py`` still lowers) at the same width: the pool
    aliased as much, the temporaries the same to within a few vectors of
    16, the arguments 64 bytes more (the tokens, padded), and ``prev``
    an argument that is read."""
    config = _mistral_serve() if model == "mistral" else _olmoe(2)
    without, pool_shape = _lower_paged_step(
        "engine_decode_step", config, 16, 16, 128, v5e_chip, width=width)
    with_prev, _ = _lower_paged_step(
        "engine_decode_step", config, 16, 16, 128, v5e_chip, width=width,
        prev=True)
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
    # Kept by the program (jit drops an argument nothing reads).
    def entry_arguments(hlo):
        layout = hlo[hlo.index("entry_computation_layout={("):]
        return layout[:layout.index(")->")]

    assert "s32[16]{" in entry_arguments(text)
    assert "s32[16]{" not in entry_arguments(before.as_text())


def test_block_step_with_prev_on_v5e(v5e_chip):
    """The block family's decode program as the engine runs it since it
    keeps a pass ahead (SDAR's widths, 2 of the cell's 7 layers, 32 rows
    at the whole table of 128 blocks of 16): the blocks the pass before
    left, ``[32, 4]`` int32, as a sixth argument and one select in front
    of the embedding. Beside the five-argument program (which
    ``benchmark/sizing_family.py`` still lowers): the pool aliased as
    much, the temporaries (76 MiB: the float32 logits of 128 positions)
    the same to within 0.5 MiB (the compiler assigns a few small buffers
    to other memory spaces: 250 KiB), and ``prev`` an argument that is
    read."""
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

    assert "s32[32,4]{" in entry_arguments(compiled.as_text())
    assert "s32[32,4]{" not in entry_arguments(before.as_text())


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
            on_chip(hybrid.pack_decode_rows(rows, width, ()), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip), None)
    step = hybrid.make_engine_decode_step(config, block)
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
    compiled = hybrid.make_engine_decode_step(config, block).lower(
        params, cache,
        on_chip(hybrid.pack_decode_rows(rows, width, ()), jnp.int32),
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


@pytest.mark.parametrize("width", [128, 256, 512])
def test_latent_programs_at_each_table_width_on_v5e(v5e_chip, width,
                                                    monkeypatch):
    """Xing4.0's two programs (published widths, 32 rows, a table of 512
    blocks of 16; one dense and one expert layer: the scans make the
    programs the same but for their length) at their three widths,
    2,048, 4,096 and 8,192 positions a row. The pool of one vector a
    position (576 values in 640 lanes) is updated where it lies and
    never copied: declared 576 wide, the runtime lays it out with the
    blocks along the lanes and both programs copy all of it twice. The
    decode step reads it through the tables inside
    ``ops/paged_latent_attention.py`` (compiled here for the v5e: its
    tables of ``[32, 512]`` in SMEM, two buffers of 64 pages in VMEM):
    no gathered view, no table-wide scores, no slice or copy of a layer
    of the pool, temporaries of a few MiB; the prefill chunk expands
    its one row's view inside the score product."""
    import re

    from ray_tpu._private import jax_compat
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
    pool_bytes = math.prod(pool) * 2
    # default_backend() is the CPU during a deviceless compile, and the
    # decode step asks it whether its kernel interprets.
    monkeypatch.setattr(jax_compat, "interpret_kernels", lambda: False)
    step = latent.make_engine_decode_step(config, block).lower(
        params, cache,
        on_chip(latent.FAMILY.pack_decode_rows(rows, width, ()), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip), None,
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    ).compile()
    memory = step.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    text = step.as_text()
    shape = ",".join(map(str, pool))
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
    prefill = latent.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache,
        on_chip(latent.FAMILY.pack_prefill_chunk(chunk, width, (), 0, (), 0),
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


@pytest.mark.parametrize("cell, ceiling_gib", [
    ("train-4k-1chip", 14.2), ("train-4k-fsdp2tp2", 14.9)])
def test_train_step_fits_a_v5e_with_the_attention_kept(
        v5e_devices, monkeypatch, capsys, cell, ceiling_gib):
    """The two train configurations' whole steps, at the program's
    default remat_policy, for the described chips: arguments plus
    temporaries 14.00 GiB on one chip and 14.71 a chip on four (PR 41;
    13.76 and 13.25 under "full") of the 15.75 a chip gives. A PR that
    adds to what the layer scan keeps sees the memory here before the
    chip does; the ceilings are those figures and a margin of 0.2."""
    from benchmark import sizing, spec
    from ray_tpu._private import jax_compat

    # The kernels see the CPU backend during such a compile.
    monkeypatch.setattr(jax_compat, "interpret_kernels", lambda: False)
    sizing.size_train(spec.load_cell(cell), v5e_devices)
    lines = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
    step = next(x for x in lines if x.get("program") == "step")
    assert step["arguments_plus_temporaries_gib"] < ceiling_gib, step
    assert step["arguments_plus_temporaries_gib"] > 13.8, (
        "under what \"full\" takes: is the default policy in force?", step)
    assert lines[-1]["has_tpu_custom_call"], lines[-1]


def test_vmem_rule_admits_the_main_path():
    for seq, head_dim in ((2048, 64), (2048, 128), (4096, 128)):
        fa.check_vmem_fit(seq, head_dim, jnp.bfloat16)
        fa.check_vmem_fit(seq, head_dim, jnp.bfloat16, backward=True)
    fa.check_vmem_fit(8192, 128, jnp.bfloat16)  # forward alone still fits
    with pytest.raises(ValueError, match="sequence length 16384"):
        fa.check_vmem_fit(16384, 128, jnp.bfloat16)


def test_kernels_interpret_on_the_cpu_only(monkeypatch):
    """interpret=None means interpret on the CPU platform and compile on
    every other one, known or not."""
    from ray_tpu._private import jax_compat

    assert jax_compat.interpret_kernels() is True  # tests run on the CPU
    for backend in ("tpu", "gpu", "a-backend-nobody-heard-of"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert jax_compat.interpret_kernels() is False


# ---------------------------------------------------- one process per chip


def _fake_host(root, n_functions, vfio_groups=(), accel=0):
    """A sysfs/dev tree like a v5e host's: PCI functions of Google's
    vendor id, an IOMMU group each, and the device nodes given."""
    sys_root, dev_root = root / "sys", root / "dev"
    for i in range(n_functions):
        function = sys_root / "bus/pci/devices" / f"0000:00:{8 + i:02x}.0"
        function.mkdir(parents=True)
        (function / "vendor").write_text("0x1ae0\n")
        (function / "device").write_text("0x0063\n")
        group = sys_root / "kernel/iommu_groups" / str(i)
        group.mkdir(parents=True)
        (function / "iommu_group").symlink_to(group)
    # Another vendor's function with a VFIO group of its own.
    other = sys_root / "bus/pci/devices/0000:00:03.0"
    other.mkdir(parents=True)
    (other / "vendor").write_text("0x8086\n")
    (other / "device").write_text("0x0063\n")
    (dev_root / "vfio").mkdir(parents=True)
    (dev_root / "vfio/vfio").write_text("")
    for g in vfio_groups:
        (dev_root / "vfio" / str(g)).write_text("")
    for i in range(accel):
        (dev_root / f"accel{i}").write_text("")
    return str(sys_root), str(dev_root)


def test_chips_are_counted_from_device_nodes(tmp_path):
    from ray_tpu._private import accelerators

    # One chip of a four-chip host: four PCI functions, one VFIO group.
    roots = _fake_host(tmp_path / "one", 4, vfio_groups=(2,))
    assert accelerators.local_chips(*roots) == (1, "v5e")
    roots = _fake_host(tmp_path / "four", 4, vfio_groups=(0, 1, 2, 3))
    assert accelerators.local_chips(*roots) == (4, "v5e")
    roots = _fake_host(tmp_path / "accel", 4, accel=4)  # older driver
    assert accelerators.local_chips(*roots) == (4, "v5e")
    roots = _fake_host(tmp_path / "none", 0, vfio_groups=(7,))
    assert accelerators.local_chips(*roots) == (0, None)


def test_environment_names_the_slice_but_does_not_count(monkeypatch):
    """The one-chip machine's environment describes the four-chip host
    it was cut from; the runtime advertises what is really there, and a
    host without chips advertises none whatever the environment says."""
    from ray_tpu._private import accelerators

    monkeypatch.delenv("RAY_TPU_SKIP_TPU_DETECTION", raising=False)
    monkeypatch.delenv("RAY_TPU_NUM_TPU_CHIPS", raising=False)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setattr(accelerators, "local_chips", lambda: (1, "v5e"))
    assert accelerators.detect_resources() == {
        "TPU": 1.0, "TPU-v5litepod-4-head": 1.0}
    monkeypatch.setattr(accelerators, "local_chips", lambda: (0, None))
    monkeypatch.setattr(
        accelerators, "_gce_metadata",
        lambda *a, **k: pytest.fail("metadata asked on a chipless host"))
    assert accelerators.detect_resources() == {}


def test_detection_does_not_import_jax():
    """The detecting process is often not the computing one, and a
    process that initialised the TPU backend would hold the chip."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_SKIP_TPU_DETECTION",
                        "RAY_TPU_NUM_TPU_CHIPS")}
    script = ("import sys; from ray_tpu._private import accelerators; "
              "print(accelerators.detect_resources()); "
              "assert 'jax' not in sys.modules, 'detection imported jax'")
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_chip_leases_keep_one_owner():
    from ray_tpu._private.accelerators import ChipLeases, tpu_chip_demand
    from ray_tpu.exceptions import ChipOwnershipError

    assert tpu_chip_demand({"CPU": 1.0}, 4) == 0
    assert tpu_chip_demand({"TPU": 0.5}, 4) == 1  # a chip is not shared
    assert tpu_chip_demand({"TPU-v5litepod-4-head": 1.0}, 4) == 4

    leases = ChipLeases(4)
    first = leases.lease("a", 1, "actor a")
    second = leases.lease("b", 2, "actor b")
    assert not set(first) & set(second)
    with pytest.raises(ChipOwnershipError, match="1 of 4 are free"):
        leases.lease("c", 2, "actor c")
    with pytest.raises(ChipOwnershipError, match="leased to child"):
        leases.claim_in_process("thread actor t")
    leases.release("a")
    leases.release("b")
    leases.claim_in_process("thread actor t")  # now the host's, for life
    with pytest.raises(ChipOwnershipError, match="already holds"):
        leases.lease("d", 1, "actor d")


def test_worker_env_never_pins_a_tpu_worker_to_the_cpu():
    from ray_tpu._private import compile_cache
    from ray_tpu._private.worker_pool import _worker_env

    base = {"PATH": "/bin", "JAX_PLATFORMS": "tpu,cpu"}
    cpu_worker = _worker_env(base, None)
    assert cpu_worker["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in cpu_worker
    tpu_worker = _worker_env(base, [2])
    assert tpu_worker["JAX_PLATFORMS"] == "tpu,cpu"  # inherited, untouched
    assert tpu_worker["TPU_VISIBLE_CHIPS"] == "2"
    assert tpu_worker["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert tpu_worker["JAX_COMPILATION_CACHE_DIR"] == compile_cache.DEFAULT_DIR
    assert "JAX_PLATFORMS" not in _worker_env({"PATH": "/bin"}, [0, 1])
    kept = _worker_env({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}, [0])
    assert kept["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere"


def test_tpu_process_actors_get_disjoint_chips(monkeypatch):
    """End to end through the runtime: process actors that demand a TPU
    get a child that may see a chip — one each, never the same — and
    are not handed a CPU; a plain process actor stays pinned to it."""
    import ray_tpu

    monkeypatch.delenv("JAX_PLATFORMS")  # what the runtime itself sets
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=2)
    try:
        @ray_tpu.remote
        class Env:
            def read(self):
                return (os.environ.get("JAX_PLATFORMS"),
                        os.environ.get("TPU_VISIBLE_CHIPS"))

        on_tpu = [Env.options(process=True, resources={"TPU": 1}).remote()
                  for _ in range(2)]
        on_cpu = Env.options(process=True).remote()
        seen = ray_tpu.get([a.read.remote() for a in on_tpu], timeout=120)
        assert [platforms for platforms, _ in seen] == [None, None]
        assert sorted(chips for _, chips in seen) == ["0", "1"]
        assert ray_tpu.get(on_cpu.read.remote(), timeout=120) == ("cpu", None)
    finally:
        ray_tpu.shutdown()


def test_a_stalled_process_does_not_kill_its_own_node(monkeypatch):
    """A sibling process initialising a chip freezes the whole machine
    for seconds (seen on the v5e host: 4.8 s and 8.9 s). The in-process
    liveness check stood still with the beater, so heartbeats it finds
    stale right after prove nothing; a node whose beats really stopped
    is still found."""
    import threading
    import time

    from ray_tpu._private import recovery

    class Node:
        alive = True

        def __init__(self, node_id):
            self.node_id = node_id
            self.last_heartbeat = time.monotonic()

    class Gcs:
        def __init__(self):
            self.nodes = [Node("stalled-with-us"), Node("really-dead")]

        def list_nodes(self):
            return self.nodes

        def heartbeat(self, node_id):
            for node in self.nodes:
                if node.node_id == node_id:
                    node.last_heartbeat = time.monotonic()

    dead, real, jump = [], time.monotonic, [0.0]
    monkeypatch.setattr(time, "monotonic", lambda: real() + jump[0])
    monitor = recovery.NodeHealthMonitor(
        Gcs(), period_s=0.05, failure_threshold=3, on_node_dead=dead.append)
    try:
        time.sleep(0.2)
        jump[0] = 10.0  # every thread of the process lost ten seconds
        time.sleep(0.4)
        assert dead == []
        monitor.suppress("really-dead")
        deadline = real() + 5.0
        while not dead and real() < deadline:
            time.sleep(0.02)
        assert dead == ["really-dead"]
    finally:
        monitor.shutdown()
    assert threading.active_count() >= 1


# ------------------------------------------------ cache, bench, rehearsal


def test_compile_cache_has_one_home(monkeypatch, tmp_path):
    from ray_tpu._private import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert updates == []  # JAX reads the variable; nothing set in code

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir(tmp_path)
    first = compile_cache.enable()
    monkeypatch.chdir("/")
    assert compile_cache.enable() == first == os.path.join(REPO, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2


def test_native_library_is_named_by_its_sources():
    from ray_tpu import _native

    if _native.load() is None:
        pytest.skip("no toolchain: callers are on their Python paths")
    assert _native.status() in ("built", "reused")
    digest = os.path.basename(_native._lib_path())
    assert digest.startswith("libray_tpu_native-") and len(digest) == 37
    built = [f for f in os.listdir(os.path.dirname(_native._lib_path()))
             if f.endswith(".so")]
    assert built == [digest]  # nothing left over from another tree


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke.py's phases as functions at its rehearsal size. The
    whole rehearsal (`python chip_smoke.py --rehearse`, about 20 s, with
    the kernels phase that tests/test_ops.py covers here) is a manual
    step of the verify skill: tier-1 has no time to spare for it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    stop = chip_smoke.watch_compiles()
    yield chip_smoke
    stop()


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_chip_smoke_accepts_a_cpu_only_to_rehearse(smoke):
    """Without --rehearse a CPU ends the run before any phase."""
    with pytest.raises(SystemExit, match="only with --rehearse"):
        smoke.phase_device(smoke.ARGS.parse_args([]))
    found = smoke.phase_device(smoke.ARGS.parse_args(["--rehearse"]))
    assert found["platform"] == "cpu"
    with pytest.raises(SystemExit):  # the driver never gives --chips
        smoke.ARGS.parse_args(["--chips", "2"])


def test_chip_smoke_train_and_serve_phases(smoke, capsys):
    """JaxTrainer and LLMEngineServer through the entry points the real
    run uses, with the CPU declared as the one chip."""
    import ray_tpu

    # The virtual mesh has 8 devices here; the batch must split 8 ways.
    sz = dataclasses.replace(smoke.sizes(True), batch=8)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        smoke.phase_train(sz, 0, CPU_DEVICE)
        smoke.phase_serve(sz, 0, CPU_DEVICE)
    finally:
        ray_tpu.shutdown()
    out = capsys.readouterr().out
    assert "step_programs=1 compiles_after_first_step=0" in out
    assert "greedy continuation equals the reference argmax" in out


@pytest.mark.parametrize("config", ["olmoe-1b-7b-serve-1chip",
                                    "mistral7b-serve-1chip"])
def test_chip_smoke_paged_logits_mode(smoke, capsys, config):
    """``--paged-logits <configuration>`` at the file's rehearsal size:
    the engine's two programs against the configuration's own plain
    reference, a sparse one with its expert choices compared and its
    long context, a dense one without."""
    smoke.phase_paged_logits(
        os.path.join(REPO, "benchmark", "configs", config + ".json"), 7,
        True, CPU_DEVICE)
    out = capsys.readouterr().out
    assert "logits of the paged programs against the float32 reference" in out
    sparse = config.startswith("olmoe")
    assert ("expert_choices=0 " not in out) == sparse
    assert ("sequences=[9, 15, 23, 64]" in out) == sparse
