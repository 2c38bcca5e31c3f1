"""``ops/paged_latent_attention.py``, interpreted on the CPU, against
the plain form it replaces in the decode step: ``xing.attend_absorbed``
over the gathered view ``pool[li, tables]`` with the rows' entries
written first. Two shapes: ``XingConfig.tiny`` (4 heads, 40 values in
128 lanes, float32: only the order of summation differs, 1e-5) and the
published heads (32 of 128 + 64, rank 512, 576 values in 640 lanes,
blocks of 16, bfloat16: both sides round the probabilities and the sums
to bfloat16, the kernel before the division by the softmax's sum and the
plain form after: within 1e-2 of the output's largest value, where
they differ by 4e-3 and one position more or fewer moves it by 2e-1).

Tables are shuffled and non-contiguous throughout, block 0 is no row's.
A chunk is two pages here, so that a handful of pages walks several.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ray_tpu.models import xing  # noqa: E402
from ray_tpu.ops.paged_latent_attention import (  # noqa: E402
    paged_latent_attention,
)

ROWS, LAYERS, PAGES_PER_CHUNK = 3, 3, 2


def _config(shape):
    if shape == "tiny":
        return xing.XingConfig.tiny(dtype=jnp.float32), 4, 8
    # The published heads over a small hidden size and a table of 6.
    return xing.XingConfig.tiny(
        dtype=jnp.bfloat16, num_heads=32, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128), 16, 6


@functools.lru_cache(maxsize=None)
def case(shape):
    """(config, block, table width, pool, tables, the kernel's side
    and the plain form's, jitted) of one shape, over the same random
    weights, queries and entries; the pool, the lengths and the layer
    are arguments of the jitted sides, so each compiles once."""
    config, block, table = _config(shape)
    dtype, lanes = config.dtype, config.pool_lanes
    keys = jax.random.split(jax.random.PRNGKey(45), 6)
    heads, rank = config.num_heads, config.kv_lora_rank
    w = {"wkv_b": jax.random.normal(
            keys[0], (rank, heads, config.qk_nope_head_dim
                      + config.v_head_dim), dtype) * rank ** -0.5,
         "wo": jax.random.normal(
             keys[1], (heads, config.v_head_dim, config.hidden_size), dtype)
         * (heads * config.v_head_dim) ** -0.5}
    q_nope = jax.random.normal(
        keys[2], (ROWS, 1, heads, config.qk_nope_head_dim), dtype)
    q_rope = jax.random.normal(
        keys[3], (ROWS, 1, heads, config.qk_rope_head_dim), dtype)
    blocks = 1 + ROWS * table + 5          # five that no row owns
    live = jnp.arange(lanes) < config.latent_dim

    def entries_like(key, *shape):
        return jnp.where(live, jax.random.normal(key, (*shape, lanes), dtype),
                         0).astype(dtype)

    pool = entries_like(keys[4], LAYERS, blocks, block)
    entries = entries_like(keys[5], ROWS)
    tables = np.random.default_rng(45).permutation(
        np.arange(1, blocks))[:ROWS * table].reshape(ROWS, table)

    @jax.jit
    def kernel(pool, lengths, li):
        q = xing.absorbed_queries(w, q_nope, q_rope, lanes, config)
        u = paged_latent_attention(
            q[:, 0], entries, pool, jnp.asarray(tables), lengths, li,
            scale=config.softmax_scale, width=rank,
            pages_per_chunk=PAGES_PER_CHUNK)
        return u, xing.absorbed_output(w, u[:, None], config)[:, 0]

    @jax.jit
    def plain(pool, lengths, li):
        at = jnp.maximum(lengths - 1, 0)
        written = pool.at[li, jnp.asarray(tables)[jnp.arange(ROWS),
                                                  at // block],
                          at % block].set(entries)
        latents = written[li, jnp.asarray(tables)].reshape(ROWS, -1, lanes)
        mask = jnp.arange(table * block)[None, None, :] < lengths[:, None,
                                                                 None]
        return xing.attend_absorbed(w, q_nope, q_rope, latents, mask,
                                    config)[:, 0]

    return config, block, table, pool, tables, kernel, plain


def lengths_of(shape):
    _, block, table = _config(shape)
    chunk = PAGES_PER_CHUNK * block
    return {"inactive": 0, "own_only": 1, "a_page_less_one": block - 1,
            "a_page": block, "a_page_and_one": block + 1,
            "a_chunk_before_its_own": chunk + 1,
            "straddles_a_chunk": chunk + 2,
            "two_chunks_and_a_page": 2 * chunk + block,
            "whole_table": table * block}


def close(got, want, config):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if config.dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5)
    else:
        assert np.abs(got - want).max() <= 1e-2 * np.abs(want).max()


NAMES = list(lengths_of("tiny"))


@pytest.mark.parametrize("shape", ["tiny", "published_heads"])
@pytest.mark.parametrize("name", NAMES)
def test_a_row_of_each_length_matches_the_plain_form(shape, name):
    """The named length in each row's place in turn (first, between,
    last: the row after it is the one whose first chunk the kernel
    starts early), the other rows at other lengths."""
    config, block, table, pool, _, kernel, plain = case(shape)
    length = lengths_of(shape)[name]
    others = [3 * block + 1, table * block - 1]
    for place in range(ROWS):
        lengths = others[:place] + [length] + others[place:]
        lengths = jnp.asarray(lengths[:ROWS], jnp.int32)
        u, got = kernel(pool, lengths, 1)
        want = plain(pool, lengths, 1)
        busy = np.asarray(lengths) > 0
        close(got[busy], want[busy], config)
        assert not np.asarray(u[~busy], np.float32).any()


@pytest.mark.parametrize("shape", ["tiny", "published_heads"])
@pytest.mark.parametrize("li", range(LAYERS))
def test_each_layer_of_the_pool(shape, li):
    config, block, table, pool, _, kernel, plain = case(shape)
    lengths = jnp.asarray([2 * block + 3, block, table * block], jnp.int32)
    got = kernel(pool, lengths, li)[1]
    close(got, plain(pool, lengths, li), config)
    other = kernel(pool, lengths, (li + 1) % LAYERS)[1]
    assert np.abs(np.asarray(got - other, np.float32)).max() > 1e-2


@pytest.mark.parametrize("shape", ["tiny", "published_heads"])
@pytest.mark.parametrize("poison", [np.nan, 1e30])
def test_what_lies_past_a_row_is_never_read_into_the_sum(shape, poison):
    """Every position of the layer that no row attends over through the
    pool holds the poison: the rows' own positions (the kernel takes
    them from its operand), the rest of their last pages, the pages
    past them, the blocks no row owns and block 0; and the other layers
    whole. The result is the clean pool's, bit for bit; an inactive row
    over a table of poison returns zeros."""
    config, block, table, pool, tables, kernel, _ = case(shape)
    lengths = np.asarray([0, block + 2, 2 * PAGES_PER_CHUNK * block + 1])
    keep = np.zeros(pool.shape[1:3], bool)
    for row, length in enumerate(lengths):
        at = np.arange(max(length - 1, 0))
        keep[tables[row, at // block], at % block] = True
    poisoned = jnp.full_like(pool, poison).at[1].set(
        jnp.where(keep[..., None], pool[1], poison))
    lengths = jnp.asarray(lengths, jnp.int32)
    u, got = kernel(poisoned, lengths, 1)
    u_clean, clean = kernel(pool, lengths, 1)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert np.array_equal(np.asarray(u, np.float32),
                          np.asarray(u_clean, np.float32))
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(clean, np.float32))
    assert not np.asarray(u[0], np.float32).any()


def test_the_operands_must_agree_in_their_lanes():
    config, block, table, pool, tables, _, _ = case("tiny")
    q = jnp.zeros((ROWS, config.num_heads, config.pool_lanes))
    with pytest.raises(ValueError, match="differ in their lanes"):
        paged_latent_attention(
            q, jnp.zeros((ROWS, 64)), pool, jnp.asarray(tables),
            jnp.zeros((ROWS,), jnp.int32), 0, scale=1.0, width=8)
