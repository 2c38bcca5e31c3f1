"""``ops/grouped_expert_ffn.py`` (PR 52), interpreted on the CPU, held to
``moe.expert_ffn`` on the same inputs: a layer out of a stack, the
shares of a layer split over chips, every share of touched experts from
all to none, an expert nobody weighs left unread, and the refusals."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ray_tpu.models import moe  # noqa: E402
from ray_tpu.ops import grouped_expert_ffn as gef  # noqa: E402

F32 = jnp.float32
LAYERS, EXPERTS, HIDDEN, WIDTH, PER_TOKEN = 3, 8, 32, 256, 2


def stack(seed=0, layers=LAYERS, experts=EXPERTS, hidden=HIDDEN,
          width=WIDTH, dtype=F32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shapes = {"w_gate": (hidden, width), "w_up": (hidden, width),
              "w_down": (width, hidden)}
    return {name: (jax.random.normal(key, (layers, experts, *shape))
                   * shape[0] ** -0.5).astype(dtype)
            for key, (name, shape) in zip(keys, shapes.items())}


def tokens(rows, seed=1, experts=EXPERTS, among=EXPERTS, hidden=HIDDEN):
    """``rows`` tokens and their combine weights, the choices among the
    first ``among`` experts."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (1, rows, hidden))
    idx = jnp.argsort(jax.random.uniform(keys[1], (1, rows, among)),
                      axis=-1)[..., :min(PER_TOKEN, among)]
    weights = jax.random.uniform(keys[2], idx.shape, minval=0.1)
    return x, moe.combine_weights(idx, weights, experts)


def plain(experts, layer, x, combine, dtype=F32):
    return moe.expert_ffn({k: v[layer] for k, v in experts.items()}, x,
                          combine, dtype)


@pytest.mark.parametrize("layer", range(LAYERS))
def test_a_layer_is_chosen_out_of_the_stack(layer):
    """Layer ``layer`` of the stack is ``expert_ffn`` of that layer's
    slice, and the other layers' weights do not reach it."""
    experts = stack()
    x, combine = tokens(16)
    got = moe.touched_expert_ffn(experts, jnp.int32(layer), x, combine, F32)
    np.testing.assert_allclose(got, plain(experts, layer, x, combine),
                               atol=2e-5, rtol=0)
    others = {k: jnp.where(
        (jnp.arange(LAYERS) == layer).reshape(-1, 1, 1, 1), v, jnp.nan)
        for k, v in experts.items()}
    again = moe.touched_expert_ffn(others, jnp.int32(layer), x, combine, F32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


def test_the_layer_is_a_traced_index():
    """As a scan hands it: one program serves every layer."""
    experts = stack()
    x, combine = tokens(16)
    run = jax.jit(lambda index: moe.touched_expert_ffn(
        experts, index, x, combine, F32))
    for layer in range(LAYERS):
        np.testing.assert_allclose(
            run(jnp.int32(layer)), plain(experts, layer, x, combine),
            atol=2e-5, rtol=0)
    assert run._cache_size() == 1


@pytest.mark.parametrize("rows", [16, 64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_rows_of_a_step_and_of_a_chunk(rows, dtype):
    experts = stack(dtype=dtype)
    x, combine = tokens(rows)
    got = moe.touched_expert_ffn(experts, 1, x, combine, dtype)
    want = plain(experts, 1, x, combine, dtype)
    assert got.dtype == want.dtype == dtype and got.shape == x.shape
    # bfloat16: the same rounding points, the sum over experts in another
    # order (an ulp of the result's 4 is 0.03).
    np.testing.assert_allclose(
        got.astype(F32), want.astype(F32), rtol=0,
        atol=2e-5 if dtype == jnp.float32 else 0.04)


@pytest.mark.parametrize("among", [EXPERTS, 3, 1, 0],
                         ids=["every", "three", "one", "none"])
@pytest.mark.parametrize("block_m", [128, 256])
def test_the_touched_experts_from_all_to_none(among, block_m):
    experts = stack()
    x, combine = tokens(16, among=max(among, 1))
    if not among:
        combine = jnp.zeros_like(combine)
    order, count = gef.touched_order(combine[0])
    assert int(count[0]) == among
    assert sorted(np.asarray(order)) == list(range(EXPERTS))
    assert set(np.asarray(order)[:among]) == set(range(among))
    got = gef.grouped_expert_ffn(
        *(experts[k] for k in moe.EXPERT_TENSORS), 2, x[0], combine[0],
        block_m=block_m)
    np.testing.assert_allclose(got, plain(experts, 2, x, combine)[0],
                               atol=2e-5, rtol=0)
    if not among:
        assert not np.asarray(got).any()


def test_an_expert_nobody_weighs_is_not_read():
    """An expert with no weight above zero in any row contributes
    exactly zero whatever it holds (a row that "chose" it with weight 0
    included), and a row that did not choose an expert another row chose
    gets exactly zero from it."""
    experts = stack()
    x, combine = tokens(16, among=4)
    combine = combine.at[0, :, 5].set(0.0)          # chosen, unweighted
    poisoned = {k: v.at[:, 4:].set(jnp.nan) for k, v in experts.items()}
    got = moe.touched_expert_ffn(poisoned, 0, x, combine, F32)
    np.testing.assert_allclose(got, plain(experts, 0, x, combine),
                               atol=2e-5, rtol=0)
    # One row alone weighs expert 6, whose activations are no number: the
    # other rows' results are what they are without it (``where``).
    loud = {k: v if k == "w_down" else v.at[0, 6].set(jnp.nan)
            for k, v in experts.items()}
    lone = combine.at[0, 3, 6].set(0.5)
    with_it = moe.touched_expert_ffn(loud, 0, x, lone, F32)
    rest = np.arange(16) != 3
    np.testing.assert_array_equal(np.asarray(with_it)[0, rest],
                                  np.asarray(got)[0, rest])
    assert np.isnan(np.asarray(with_it)[0, 3]).all()


def test_the_shares_of_a_split_layer_add_up():
    """``combine_weights(held=)``: each chip reads the chosen experts it
    holds, out of its own stack, and the shares add up to the layer."""
    experts = stack()
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (2, 10, HIDDEN))
    idx = jnp.argsort(jax.random.uniform(keys[1], (2, 10, EXPERTS)),
                      axis=-1)[..., :PER_TOKEN]
    weights = jax.random.uniform(keys[2], idx.shape, minval=0.1)
    parts = []
    for first in range(0, EXPERTS, 2):
        combine = moe.combine_weights(idx, weights, EXPERTS, (first, 2))
        share = {k: v[:, first:first + 2] for k, v in experts.items()}
        parts.append(moe.touched_expert_ffn(share, 1, x, combine, F32))
        np.testing.assert_allclose(
            parts[-1], plain(share, 1, x, combine), atol=2e-5, rtol=0)
    whole = plain(experts, 1, x, moe.combine_weights(idx, weights, EXPERTS))
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5, rtol=0)


@pytest.mark.parametrize("cell,hidden,width", [
    ("kimi", 2304, 1024), ("olmoe", 2048, 1024), ("sdar", 2048, 768),
    ("xing", 3584, 1024)])
def test_a_cell_s_expert_in_the_kernel_s_own_blocks(cell, hidden, width):
    """An expert of the cell's own width in bfloat16, in the block the
    kernel takes there: the whole expert, three contiguous reads, under
    the VMEM budget."""
    tm = gef.block_width(hidden, width, 2)
    assert tm == width and tm % 128 == 0
    assert 6 * hidden * tm * 2 <= gef.BLOCK_BYTES < gef.VMEM_LIMIT_BYTES
    experts = stack(3, 1, 3, hidden, width, jnp.bfloat16)
    x, combine = tokens(16, experts=3, among=2, hidden=hidden)
    got = moe.touched_expert_ffn(experts, 0, x, combine, jnp.bfloat16)
    want = plain(experts, 0, x, combine, jnp.bfloat16)
    np.testing.assert_allclose(got.astype(F32), want.astype(F32),
                               atol=0.04, rtol=0)


def test_the_refusals():
    experts = stack()
    x, combine = tokens(16)
    args = [experts[k] for k in moe.EXPERT_TENSORS]
    with pytest.raises(ValueError, match="in blocks of 96"):
        gef.grouped_expert_ffn(*args, 0, x[0], combine[0], block_m=96)
    with pytest.raises(ValueError, match="in blocks of 64"):
        gef.grouped_expert_ffn(*args, 0, x[0], combine[0], block_m=64)
    with pytest.raises(ValueError, match="of their dtype"):
        gef.grouped_expert_ffn(*args, 0, x[0].astype(jnp.bfloat16),
                               combine[0])
    with pytest.raises(ValueError, match="float32 combine"):
        gef.grouped_expert_ffn(*args, 0, x[0], combine[0, :, :4])
    with pytest.raises(ValueError, match="w_down"):
        gef.grouped_expert_ffn(args[0], args[1], args[0], 0, x[0],
                               combine[0])
    # An expert goes whole where it fits, a tiny one (no multiple of 128
    # lanes) too; a wider one in the largest divisor of whole lanes that
    # fits; one that no such block holds is refused.
    assert gef.block_width(32, 24, 4) == 24
    assert gef.block_width(8192, 2048, 2) == 512
    with pytest.raises(ValueError, match="no block"):
        gef.block_width(8192, 1000, 2)
    with pytest.raises(ValueError, match="no block"):
        gef.block_width(1 << 18, 1024, 2)
    tiny = stack(width=24)
    np.testing.assert_allclose(
        moe.touched_expert_ffn(tiny, 0, x, combine, F32),
        plain(tiny, 0, x, combine), atol=2e-5, rtol=0)
