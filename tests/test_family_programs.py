"""What ``benchmark/sizing.py`` and ``benchmark/sizing_family.py`` rely on
of ANY serving family, and what a family's module has to write for it: a
cache and ONE forward of ``model.Family``'s signature. The engine's two
programs and the packers of their host arrays are ``model.py``'s, over
that forward. One case a family, at the tiny configurations the
families' own tests have."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jamba_tiny  # noqa: E402
import kimi_tiny  # noqa: E402
import solar_tiny  # noqa: E402
import table_width_cases  # noqa: E402
import xing_tiny  # noqa: E402
from ray_tpu.serve.llm_engine import model as paged_model  # noqa: E402

ROWS, BLOCK, CHUNK, WIDTH = 4, 4, 8, 16

#: case -> (its tiny configuration, whether a row owns a state slot)
FAMILIES = {
    "paged": (lambda: table_width_cases.tiny("paged"), False),
    "block": (lambda: table_width_cases.tiny("block"), False),
    "hybrid": (lambda: table_width_cases.tiny("hybrid"), True),
    "latent": (xing_tiny.tiny, False),
    "linear-kimi": (kimi_tiny.tiny, True),
    "linear-solar": (solar_tiny.tiny, True),
    "mamba": (jamba_tiny.tiny, True),
}


@pytest.mark.parametrize("case", list(FAMILIES))
def test_a_family_is_a_cache_and_a_forward(case):
    """The four calls the benchmark's sizing tools make of a ``Family``,
    with their arguments: the arrays have the pinned lengths (a decode
    array ``[rows, 3 + width]``, a block pass's its own; a chunk's ``[2
    + 2 * chunk + width]`` and one more for the row slot where a row
    owns a state), the two programs lower under the names the
    benchmark's readers select them by, and ``forward`` gives four
    results as a decode step and as a chunk. No family but the block
    family, for its decode pass, names a program or a packer of its
    own."""
    make_config, recurrent = FAMILIES[case]
    config = make_config()
    family = paged_model.family(config)
    assert family.recurrent == recurrent
    fields = {f.name for f in dataclasses.fields(family)}
    assert "forward" in fields and "pack_prefill_chunk" not in fields
    own = (family.make_engine_decode_step, family.pack_decode_rows)
    if case == "block":
        assert own[0] is paged_model.make_engine_block_step
        head = 6 + config.block_length
    else:
        assert own == (paged_model.make_engine_decode_step,
                       paged_model.pack_decode_rows)
        head = 3
    assert family.make_engine_prefill_chunk \
        is paged_model.make_engine_prefill_chunk

    rows = family.pack_decode_rows(ROWS, WIDTH, [])
    chunk = family.pack_prefill_chunk(CHUNK, WIDTH, [0], 0, [0], 0)
    assert rows.shape == (ROWS, head + WIDTH) and rows.dtype == jnp.int32
    assert chunk.shape == (2 + recurrent + 2 * CHUNK + WIDTH,)
    assert chunk.dtype == jnp.int32

    params = jax.eval_shape(
        lambda: family.init_params(config, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: family.init_cache(
        config, 1 + ROWS * WIDTH, BLOCK, ROWS, CHUNK))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step = family.make_engine_decode_step(config, BLOCK).lower(
        params, cache, rows, key, None)
    prefill = family.make_engine_prefill_chunk(config, BLOCK, CHUNK).lower(
        params, cache, chunk, None)
    assert "jit_decode_step" in step.as_text()[:200]
    assert "jit_prefill_chunk" in prefill.as_text()[:200]
    if case == "paged":
        # ``sizing.py`` takes the paged family's at module level.
        assert paged_model.make_engine_decode_step(config, BLOCK).lower(
            params, cache, rows, key, None).as_text() == step.as_text()
        assert paged_model.make_engine_prefill_chunk(
            config, BLOCK, CHUNK).lower(
                params, cache, chunk, None).as_text() == prefill.as_text()

    ones = jnp.ones((ROWS, 1), jnp.int32)
    tables = jnp.zeros((ROWS, WIDTH), jnp.int32)
    as_step = jax.eval_shape(lambda p, c: family.forward(
        p, c, ones, ones, tables, config, BLOCK), params, cache)
    as_chunk = jax.eval_shape(lambda p, c: family.forward(
        p, c, jnp.ones((1, CHUNK), jnp.int32),
        jnp.arange(CHUNK, dtype=jnp.int32)[None], tables[:1], config, BLOCK,
        slot=jnp.int32(0), n_valid=jnp.int32(CHUNK),
        logits_at=jnp.int32(CHUNK - 1)), params, cache)
    for results, logits in ((as_step, (ROWS, 1, config.vocab_size)),
                            (as_chunk, (1, config.vocab_size))):
        assert len(results) == 4
        assert results[0].shape == logits and results[0].dtype == jnp.float32
        assert jax.tree.map(lambda x: (x.shape, x.dtype), results[1]) \
            == jax.tree.map(lambda x: (x.shape, x.dtype), cache)
