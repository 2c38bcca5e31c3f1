"""The state-space family's programs (Jamba2-3B: 26 Mamba-1 layers
around 2 attention layers of ONE key-value head, ``llm_engine/mamba.py``)
at the cell's real size, all 28 layers and the whole vocabulary,
compiled for a described ``v5e:2x2`` (``v5e_compile.py``)."""

import collections
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
from v5e_compile import (  # noqa: F401 — the fixtures
    compiled_kernels, kv_attention_calls, v5e_chip, v5e_devices)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402


def test_jamba_programs_at_the_cells_size_on_v5e(v5e_chip, compiled_kernels):
    """Jamba2-3B's two programs as an engine builds them (published
    widths, nothing cut; 64 rows, a table of 256 blocks of 16): the ONE
    decode step, at the whole table, and the prefill chunk at the whole.

    Memory: the weights (5.64 GiB), the state ``f32[26,64,5120,16]``
    (0.51 GiB: 8.3 MiB a row; float32 because the cell's configuration
    file says so: the config is built from that file, and a state in
    another precision fails the shapes and bytes below), the
    convolutions' inputs and the two attention layers' pools
    ``bf16[2,16385,16,128]`` lie as large as
    they are nominally: the program's arguments exceed the sum of their
    shapes by less than a tenth of the POOLS' 0.25 GiB (a page of
    ``[16, 1, 128]``, the dense family's layout at one key-value head,
    is what the chip's compiler pads and the kernel's refuses). The
    cache is updated where it lies: aliased, the state never copied
    whole; temporaries stay under a 64th of a GiB in the step (16 MiB:
    less than ONE layer's 20 MiB of state, so no copy of a layer of it
    can hide there) and an eighth in the chunk.

    The stack: ONE scan over the two periods whose body holds a scan
    over the seven Mamba layers before the attention layer and one over
    the six after it (3 loops in the step; in the chunk each run's body
    holds the scan over the chunk's positions: 5), not thirteen layers
    written out. A run makes the state in ONE fusion, the update in
    place, and nothing holds a layer of it on its own.

    The attention layer reads BY ROW: ONE call of
    ``ops/paged_kv_attention.py`` in the step's text, handed both pools
    WHOLE, the 20 queries ``bf16[64,20,128]`` and the rows' fresh key
    and value; no gathered view of the pools in the step; the chunk
    gathers its one row's view and calls no kernel. The head is on the
    rows that are read, over the whole vocabulary."""
    from ray_tpu.models import jamba
    from ray_tpu.serve.llm_engine import mamba
    from ray_tpu.serve.llm_engine import model as paged_model

    # The configuration as the cell builds it, from the file's own
    # ``builder``: what that file states of the state's precision is
    # what these programs keep (``state`` below).
    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2-3b-serve-1chip.json")) as f:
        file = json.load(f)
    assert file["builder"]["kwargs"]["state_dtype"] == "float32"
    config = spec.build_model_config(file)
    assert config == jamba.JambaConfig() and config.state_dtype == jnp.float32
    assert config.num_params == 3_029_337_472
    rows, block, table, chunk = 64, 16, 256, 128

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype, sharding=v5e_chip), tree)

    def nbytes(tree):
        return sum(math.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                   for v in jax.tree.leaves(tree))

    family = paged_model.family(config)
    assert family is mamba.FAMILY and family.reads_by_row
    params = on_chip(jax.eval_shape(lambda: family.init_params(
        config, jax.random.PRNGKey(0))), config.dtype)
    cache = on_chip(jax.eval_shape(lambda: family.init_cache(
        config, 1 + rows * table, block, rows, chunk)))
    pool = (2, 1 + rows * table, block, 128)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": pool, "v": pool, "ssm": (26, rows, 5120, 16),
        "conv": (26, 3, rows, 5120)}
    pools = nbytes([cache["k"], cache["v"]])
    assert round(pools / 2 ** 30, 2) == 0.25
    assert round(nbytes(cache) / 2 ** 30, 2) == 0.81
    state = "f32[26,64,5120,16]"

    step_rows = on_chip(family.pack_decode_rows(rows, table, ()), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e_chip)
    prev = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip)
    chunk_array = on_chip(
        family.pack_prefill_chunk(chunk, table, (), 0, (), 0), jnp.int32)
    step = family.make_engine_decode_step(config, block).lower(
        params, cache, step_rows, key, None, prev).compile()
    prefill = family.make_engine_prefill_chunk(config, block, chunk).lower(
        params, cache, chunk_array, None).compile()
    for program, limit, loops, host in (
            (step, 1 / 64, 3, [step_rows, key, prev]),
            (prefill, 1 / 8, 5, [chunk_array])):
        memory = program.memory_analysis()
        assert memory.alias_size_in_bytes >= nbytes(cache)
        assert memory.temp_size_in_bytes < limit * 2 ** 30
        nominal = nbytes(params) + nbytes(cache) + nbytes(host)
        assert 0 <= memory.argument_size_in_bytes - nominal < 0.1 * pools
        text = program.as_text()
        lines = text.splitlines()
        assert sum(" while(" in line for line in lines) == loops
        assert [line for line in lines
                if " copy(" in line and f"= {state}" in line] == []
        assert not any(" copy(" in line and "bf16[2,16385,16,128]" in line
                       for line in lines)
        assert re.search(r"f32\[(64|1,2),65536\]", text)
    lines = step.as_text().splitlines()
    # What makes a state: ONE fusion a run, the update in place (the
    # layer chosen inside it); what else has its shape hands it on.
    made = collections.Counter(
        found.group(1) for found in (re.search(
            r"= f32\[26,64,5120,16\]\S* ([\w-]+)\(", line)
            for line in lines) if found)
    assert set(made) <= {"fusion", "parameter", "get-tuple-element",
                         "dynamic-update-slice"}, made
    assert made["fusion"] == 2
    calls = kv_attention_calls(step.as_text())
    assert len(calls) == 1
    operands = calls[0].split("operand_layout_constraints={")[1]
    assert operands.count("bf16[2,16385,16,128]{") == 2
    assert operands.count("bf16[64,1,128]{") == 2
    assert operands.count("bf16[64,20,128]{") == 1
    assert "s32[16384]{" in operands and "s32[64]{" in operands
    assert kv_attention_calls(prefill.as_text()) == []
    assert re.search(r"\[64,4096,(1,)?128\]|\[16384,16,128\]",
                     step.as_text()) is None
    assert "bf16[256,16,128]" in prefill.as_text()
