"""``ops/paged_kv_attention.py``, interpreted on the CPU, against the
plain form it replaces in the decode step: the gathered view of
``model.paged_attention`` (``model._attend_gathered`` over ``pool[li,
tables]`` with the rows' fresh keys and values written first). Shapes:
a tiny float32 one (2 key-value heads of 2 queries, 32 wide, blocks of
4: only the order of summation differs, 1e-5) and the published head
size and block (128, 16) in bfloat16 for 8 queries a key-value head of
8 (Solar-Open2), 4 of 8 (Mistral), 1 of 16 (OLMoE), 1 of 8, and 20
queries on ONE key-value head over pools whose pages lie ``[block * kv,
d]`` (Jamba: ``llm_engine/mamba.py``; the tiny shape over such pools
too, two heads a position): both
sides round the probabilities to bfloat16, the kernel before the
division by the softmax's sum and the plain form after, so they are
held within 2e-2 of the output's largest value.

Tables are shuffled and non-contiguous throughout, block 0 is no row's.
A chunk is two pages here, so that a handful of pages walks several.
"""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ray_tpu.ops.paged_kv_attention import paged_kv_attention  # noqa: E402
from ray_tpu.serve.llm_engine import model  # noqa: E402

ROWS, ENTRIES, PAGES_PER_CHUNK, TABLE = 3, 3, 2, 6

#: name -> (key-value heads, queries of each, head size, block, dtype)
SHAPES = {
    "tiny": (2, 2, 32, 4, jnp.float32),
    "reps8_kv8": (8, 8, 128, 16, jnp.bfloat16),
    "reps4_kv8": (8, 4, 128, 16, jnp.bfloat16),
    "reps1_kv16": (16, 1, 128, 16, jnp.bfloat16),
    "reps1_kv8": (8, 1, 128, 16, jnp.bfloat16),
    "reps20_kv1_flat": (1, 20, 128, 16, jnp.bfloat16),
    "tiny_flat": (2, 2, 32, 4, jnp.float32),
}


def _config(shape):
    kv, reps, d, block, dtype = SHAPES[shape]
    return types.SimpleNamespace(
        num_heads=kv * reps, num_kv_heads=kv, head_dim=d, dtype=dtype,
        block_length=0), block


@functools.lru_cache(maxsize=None)
def case(shape):
    """(config, block, pools, tables, the kernel's side and the plain
    form's, jitted) of one shape, over the same random queries and
    fresh keys and values; the pools, the tables, the lengths and the
    entry are arguments of the jitted sides, so each compiles once. A
    ``_flat`` shape hands the kernel the same pools with a page's
    positions and heads in one dimension."""
    config, block = _config(shape)
    kv, d, dtype = config.num_kv_heads, config.head_dim, config.dtype
    reps = config.num_heads // kv
    keys = jax.random.split(jax.random.PRNGKey(56), 5)
    blocks = 1 + ROWS * TABLE + 5          # five that no row owns
    q = jax.random.normal(keys[0], (ROWS, kv, reps, d), dtype)
    k_new = jax.random.normal(keys[1], (ROWS, kv, d), dtype)
    v_new = jax.random.normal(keys[2], (ROWS, kv, d), dtype)
    pools = tuple(jax.random.normal(key, (ENTRIES, blocks, block, kv, d),
                                    dtype) for key in keys[3:])
    tables = np.random.default_rng(56).permutation(
        np.arange(1, blocks))[:ROWS * TABLE].reshape(ROWS, TABLE)

    def lies(pool):
        if not shape.endswith("_flat"):
            return pool
        return pool.reshape(ENTRIES, blocks, block * kv, d)

    @jax.jit
    def kernel(pool_k, pool_v, tables, lengths, li):
        return paged_kv_attention(
            q, k_new, v_new, lies(pool_k), lies(pool_v), tables, lengths,
            li, scale=d ** -0.5, pages_per_chunk=PAGES_PER_CHUNK)

    @jax.jit
    def plain(pool_k, pool_v, tables, lengths, li):
        at = jnp.maximum(lengths - 1, 0)
        page = tables[jnp.arange(ROWS), at // block]
        written = (pool_k.at[li, page, at % block].set(k_new),
                   pool_v.at[li, page, at % block].set(v_new))
        return model._attend_gathered(
            q.reshape(ROWS, 1, kv * reps, d), *written, li, tables,
            at[:, None], config, block).reshape(q.shape)

    return config, block, pools, jnp.asarray(tables), kernel, plain


def lengths_of(shape):
    block = SHAPES[shape][3]
    chunk = PAGES_PER_CHUNK * block
    return {"inactive": 0, "own_only": 1, "a_page_less_one": block - 1,
            "a_page": block, "a_page_and_one": block + 1,
            "a_chunk_before_its_own": chunk + 1,
            "straddles_a_chunk": chunk + 2,
            "two_chunks_and_a_page": 2 * chunk + block,
            "whole_table": TABLE * block}


def close(got, want, config):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if config.dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5)
    else:
        assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("name", list(lengths_of("tiny")))
def test_a_row_of_each_length_matches_the_gathered_form(shape, name):
    """The named length in each row's place in turn (first, between,
    last: the row after it is the one whose first chunk the kernel
    starts early), the other rows at other lengths."""
    config, block, pools, tables, kernel, plain = case(shape)
    length = lengths_of(shape)[name]
    others = [3 * block + 1, TABLE * block - 1]
    for place in range(ROWS):
        lengths = others[:place] + [length] + others[place:]
        lengths = jnp.asarray(lengths[:ROWS], jnp.int32)
        got = kernel(*pools, tables, lengths, 1)
        want = plain(*pools, tables, lengths, 1)
        busy = np.asarray(lengths) > 0
        close(got[busy], want[busy], config)
        assert not np.asarray(got[~busy], np.float32).any()


@pytest.mark.parametrize("shape", ["tiny", "reps8_kv8"])
@pytest.mark.parametrize("li", range(ENTRIES))
def test_each_entry_of_the_pools(shape, li):
    config, block, pools, tables, kernel, plain = case(shape)
    lengths = jnp.asarray([2 * block + 3, block, TABLE * block], jnp.int32)
    got = kernel(*pools, tables, lengths, li)
    close(got, plain(*pools, tables, lengths, li), config)
    other = kernel(*pools, tables, lengths, (li + 1) % ENTRIES)
    assert np.abs(np.asarray(got - other, np.float32)).max() > 1e-2


@pytest.mark.parametrize("shape", ["tiny", "reps8_kv8", "reps1_kv16",
                                   "reps20_kv1_flat", "tiny_flat"])
@pytest.mark.parametrize("poison", [np.nan, 1e30])
def test_what_lies_past_a_row_is_never_read_into_the_sum(shape, poison):
    """Every position of the entry that no row attends over through the
    pools holds the poison, keys and values: the rows' own positions
    (the kernel takes them from its operands), the rest of their last
    pages, the pages past them, the blocks no row owns and block 0; and
    the other entries whole. The result is the clean pools', bit for
    bit; an inactive row over a table of poison returns zeros."""
    config, block, pools, tables, kernel, _ = case(shape)
    lengths = np.asarray([0, block + 2, 2 * PAGES_PER_CHUNK * block + 1])
    keep = np.zeros(pools[0].shape[1:3], bool)
    for row, length in enumerate(lengths):
        at = np.arange(max(length - 1, 0))
        keep[np.asarray(tables)[row, at // block], at % block] = True
    poisoned = [jnp.full_like(pool, poison).at[1].set(
        jnp.where(keep[..., None, None], pool[1], poison)) for pool in pools]
    lengths = jnp.asarray(lengths, jnp.int32)
    got = np.asarray(kernel(*poisoned, tables, lengths, 1), np.float32)
    clean = np.asarray(kernel(*pools, tables, lengths, 1), np.float32)
    assert np.isfinite(got).all()
    assert np.array_equal(got, clean)
    assert not got[0].any()


@pytest.mark.parametrize("shape", ["tiny", "reps8_kv8"])
def test_a_table_whose_pages_are_permuted(shape):
    """The same contexts through other pages: every block of the pools
    moved by a permutation (block 0 kept) and the tables renamed by it
    give the same result, bit for bit; the tables renamed ALONE do not."""
    config, block, pools, tables, kernel, _ = case(shape)
    blocks = pools[0].shape[1]
    to = np.concatenate([[0], 1 + np.random.default_rng(7).permutation(
        blocks - 1)])
    moved = [jnp.zeros_like(pool).at[:, to].set(pool) for pool in pools]
    lengths = jnp.asarray([block + 3, TABLE * block, 3 * block], jnp.int32)
    want = np.asarray(kernel(*pools, tables, lengths, 2), np.float32)
    renamed = jnp.asarray(to)[tables]
    assert np.array_equal(
        np.asarray(kernel(*moved, renamed, lengths, 2), np.float32), want)
    assert not np.array_equal(
        np.asarray(kernel(*pools, renamed, lengths, 2), np.float32), want)


@pytest.mark.parametrize("what", ["head_size", "kv_heads", "pools"])
def test_operands_that_disagree_are_refused(what):
    config, block, pools, tables, _, _ = case("tiny")
    kv, d = config.num_kv_heads, config.head_dim
    q = jnp.zeros((ROWS, kv, 2, d))
    fresh = jnp.zeros((ROWS, kv, d))
    operands = {
        "head_size": (q, jnp.zeros((ROWS, kv, 2 * d)), fresh, *pools),
        "kv_heads": (jnp.zeros((ROWS, 2 * kv, 1, d)), fresh, fresh, *pools),
        "pools": (q, fresh, fresh, pools[0], pools[1][:, :, :, :1]),
    }[what]
    with pytest.raises(ValueError, match="differ in their key-value heads"):
        paged_kv_attention(*operands, tables, jnp.zeros((ROWS,), jnp.int32),
                           0, scale=1.0)


@pytest.mark.parametrize("gated", [False, True])
def test_paged_attention_by_row_is_its_gathered_form(gated):
    """``model.paged_attention`` itself, one position a row: ``by_row``
    against the gathered branch on the same layer, a row at position 0
    inactive (what it returns is never read; by row it is the output
    projection of zeros). The pools come back written alike."""
    config, block, pools, tables, _, _ = case("tiny")
    config = types.SimpleNamespace(
        **vars(config), qk_norm=False, rotary=not gated, rope_theta=1e4,
        rms_norm_eps=1e-6)
    h, kv, d, e = config.num_heads, config.num_kv_heads, config.head_dim, 48
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 6))
    layer = {name: jax.random.normal(next(keys), shape) * e ** -0.5
             for name, shape in (("wq", (e, h, d)), ("wk", (e, kv, d)),
                                 ("wv", (e, kv, d)), ("wo", (h, d, e)))}
    if gated:
        layer["wg"] = jax.random.normal(next(keys), (e, h, d)) * e ** -0.5
    normed = jax.random.normal(next(keys), (ROWS, 1, e))
    positions = jnp.asarray([[0], [block + 1], [TABLE * block - 1]])
    sides = [jax.jit(functools.partial(
        model.paged_attention, config=config, block_size=block,
        by_row=by_row))(layer, normed, positions, *pools, 1, tables)
        for by_row in (True, False)]
    (got, *written), (want, *gathered) = sides
    np.testing.assert_allclose(got[1:], want[1:], atol=1e-5)
    assert not np.asarray(got[0]).any()
    for a, b in zip(written, gathered):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kv", [1, 2])
def test_paged_attention_over_pages_of_positions_and_heads_in_one(kv):
    """``model.paged_attention`` over pools ``[entries, blocks, bs * kv,
    d]`` (``llm_engine/mamba.py``: ONE key-value head, 5 queries on it,
    and two heads for the layout's sake): by row and gathered agree
    with each other and with the gathered branch over the dense family's
    ``[.., bs, kv, d]`` pools of the same content, and the pools come
    back written alike, a chunk's padding on the scratch block."""
    block, e, d, reps = 4, 40, 8, 5
    config = types.SimpleNamespace(
        num_heads=kv * reps, num_kv_heads=kv, head_dim=d,
        dtype=jnp.float32, block_length=0, qk_norm=False, rotary=False,
        rms_norm_eps=1e-6)
    blocks = 1 + ROWS * TABLE
    keys = iter(jax.random.split(jax.random.PRNGKey(60), 8))
    layer = {name: jax.random.normal(next(keys), shape) * e ** -0.5
             for name, shape in (("wq", (e, kv * reps, d)),
                                 ("wk", (e, kv, d)), ("wv", (e, kv, d)),
                                 ("wo", (kv * reps, d, e)))}
    pools = [jax.random.normal(next(keys), (ENTRIES, blocks, block, kv, d))
             for _ in range(2)]
    flat = [pool.reshape(ENTRIES, blocks, block * kv, d) for pool in pools]
    tables = jnp.asarray(np.random.default_rng(60).permutation(
        np.arange(1, blocks)).reshape(ROWS, TABLE))
    normed = jax.random.normal(next(keys), (ROWS, 1, e))
    positions = jnp.asarray([[0], [block + 1], [TABLE * block - 1]])

    def attend(pools, by_row, normed=normed, positions=positions,
               tables=tables, n_valid=None):
        return jax.jit(functools.partial(
            model.paged_attention, config=config, block_size=block,
            by_row=by_row))(layer, normed, positions, *pools, 1, tables,
                            n_valid=n_valid)

    (got, *written), (want, *gathered) = attend(flat, True), \
        attend(flat, False)
    dense, *dense_written = attend(pools, False)
    np.testing.assert_allclose(got[1:], want[1:], atol=1e-5)
    np.testing.assert_array_equal(want, dense)
    for a, b, c in zip(written, gathered, dense_written):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a.reshape(c.shape), c)
    # A chunk of one row, its last two positions padding.
    chunk = jax.random.normal(next(keys), (1, 6, e))
    at = jnp.arange(3, 9)[None]
    (got, *written), (want, *dense_written) = (
        attend(p, False, chunk, at, tables[:1], 4) for p in (flat, pools))
    np.testing.assert_array_equal(got, want)
    for a, c in zip(written, dense_written):
        np.testing.assert_array_equal(a.reshape(c.shape), c)


#: name -> (query heads, key-value heads, experts, of them a token's)
ENGINE_SHAPES = {"mistral": (8, 2, 0, 0), "olmoe": (16, 16, 8, 2)}


@pytest.mark.parametrize("shape", list(ENGINE_SHAPES))
def test_the_paged_decode_step_by_row_is_its_gathered_form(shape):
    """The paged family's whole decode forward (``_forward_paged``, what
    ``make_decode_step`` and the engine's program sample from) by row
    against the same forward gathered, float32, on a tiny Mistral-shaped
    model (4 queries a key-value head) and a tiny OLMoE-shaped one (1 of
    16, QK-norm, routed experts), over one pool of random keys and
    values behind shuffled tables. The rows: an inactive one (position
    0: what it returns is never read), the shortest a step can carry
    (position 1: one position in the pool and its own), one whose own
    position is a page's last, one whose context ends with a page, one a
    position past a chunk of 16 pages, and one at its table's end. The
    logits agree to the order of summation, the pools come back written
    alike, and a sparse model's experts are the same choices."""
    from ray_tpu.models import llama
    from ray_tpu.ops.paged_kv_attention import PAGES_PER_CHUNK as CHUNK_PAGES

    heads, kv, experts, chosen = ENGINE_SHAPES[shape]
    block, table = 4, CHUNK_PAGES + 4
    config = llama.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48, num_layers=2,
        num_heads=heads, num_kv_heads=kv, head_dim=8,
        max_seq_len=table * block, remat=False, dtype=jnp.float32,
        num_experts=experts, experts_per_token=chosen,
        qk_norm=bool(experts))
    at = np.asarray([0, 1, block - 1, 2 * block, CHUNK_PAGES * block + 1,
                     table * block - 1], np.int32)
    rows, rng = len(at), np.random.default_rng(58)
    params = llama.init_params(config, jax.random.PRNGKey(58))
    pool_shape = (config.num_layers, 1 + rows * table + 3, block, kv, 8)
    pool = {name: jnp.asarray(rng.normal(size=pool_shape), jnp.float32)
            for name in ("k", "v")}
    tables = rng.permutation(np.arange(1, pool_shape[1]))[
        :rows * table].reshape(rows, table).astype(np.int32)
    tables[0] = 0                          # an inactive row's zeros
    tokens = rng.integers(1, config.vocab_size, (rows, 1)).astype(np.int32)
    (got, written, _, routed), (want, gathered, _, routing) = [
        jax.jit(functools.partial(
            model._forward_paged, config=config, block_size=block,
            by_row=by_row))(params, pool, tokens, at[:, None], tables)
        for by_row in (True, False)]
    assert 0.1 < float(np.asarray(want[1:]).std())
    np.testing.assert_allclose(got[1:], want[1:], atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(got[0])).all()
    for name in ("k", "v"):
        # The first layer's entries bit for bit; the second's are of
        # activations that differ by the order of a sum. Block 0 is the
        # scratch block: the inactive row's, whose output differs.
        np.testing.assert_array_equal(written[name][0], gathered[name][0])
        np.testing.assert_allclose(written[name][:, 1:],
                                   gathered[name][:, 1:], atol=2e-5, rtol=0)
    if experts:
        np.testing.assert_array_equal(routed[:, 1:], routing[:, 1:])
