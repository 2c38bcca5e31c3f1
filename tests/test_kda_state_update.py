"""``ops/kda_state_update.py`` (one position of the gated delta rule
with a head's state read once and written once, where it lies in the
layers' stack) against the plain forms of ``models/kimi_linear.py``:
``kda_position`` (the lines the kernel took the place of) and
``kda_recurrence`` (a position at a time, a row). On the CPU the kernel
interprets, so this is its own logic."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_linear as kimi
from ray_tpu.ops.kda_state_update import HEADS_PER_BLOCK, kda_state_update

LAYERS, ROWS = 3, 5


def rule_inputs(rows, heads, d, seed, layers=LAYERS):
    """A stacked state and one position's q, k, v, g, beta as
    ``_kda_inputs`` makes them: unit keys, queries of norm d^-1/2,
    decays down to e^-12 a position."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    q, k, v = (normal(rows, heads, d) for _ in range(3))
    q, k = kimi._l2norm(q) * d ** -0.5, kimi._l2norm(k)
    g = -jnp.asarray(rng.uniform(0.0, 12.0, (rows, heads, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (rows, heads)), jnp.float32)
    return normal(layers, rows, heads, d, d), (q, k, v, g, beta)


def norm_error(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("hb", [2, 4, 8], ids=lambda hb: f"heads_{hb}_of_8")
@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
def test_a_position_is_the_rules_and_touches_its_layer_alone(layer, hb):
    """Blocks of ``hb`` heads, dividing the heads and all of them: the
    output and the layer's new state are the plain form's and the
    recurrence's to 1e-5 of their norm; the other layers come back
    bit-equal."""
    state, inputs = rule_inputs(ROWS, 8, 16, seed=10 * layer + hb)
    step = jax.jit(lambda state, li: kda_state_update(
        state, li, *inputs, heads_per_block=hb))
    o, new = step(state, jnp.int32(layer))
    want_o, want_s = kimi.kda_position(*inputs, state[layer])
    assert norm_error(o, want_o) < 1e-5
    assert norm_error(new[layer], want_s) < 1e-5
    # The recurrence takes [T, H, d] of one row: a position, row by row.
    rec_o, rec_s = jax.vmap(kimi.kda_recurrence)(
        *(x[:, None] for x in inputs), state[layer])
    assert norm_error(o, rec_o[:, 0]) < 1e-5
    assert norm_error(new[layer], rec_s) < 1e-5
    for other in set(range(LAYERS)) - {layer}:
        np.testing.assert_array_equal(new[other], state[other])
    assert not np.array_equal(new[layer], state[layer])


@pytest.mark.parametrize("active", [
    (True, False, True, True, False), (False, True, False, False, False),
    (False,) * 5, (True,) * 5], ids=["two_idle", "one_busy", "none", "all"])
def test_an_inactive_row_keeps_its_state_to_the_bit(active):
    """``kda_step`` forces an inactive row's ``g`` and ``beta`` to 0:
    ``S * 1 + k * 0``. Its state comes back bit-equal and its output is
    the carried reading ``S^T q`` alone; the busy rows beside it are the
    rule's."""
    state, (q, k, v, g, beta) = rule_inputs(ROWS, 4, 16, seed=3)
    busy = jnp.asarray(active)
    g = jnp.where(busy[:, None, None], g, 0.0)
    beta = jnp.where(busy[:, None], beta, 0.0)
    o, new = kda_state_update(state, 1, q, k, v, g, beta)
    want_o, want_s = kimi.kda_position(q, k, v, g, beta, state[1])
    idle = ~np.asarray(active)
    np.testing.assert_array_equal(new[1][idle], state[1][idle])
    np.testing.assert_allclose(
        o[idle], jnp.sum(state[1] * q[..., None], axis=-2)[idle], atol=1e-5)
    assert norm_error(o, want_o) < 1e-5
    assert norm_error(new[1], want_s) < 1e-5
    np.testing.assert_array_equal(new[0], state[0])
    np.testing.assert_array_equal(new[2], state[2])


def test_a_head_of_128_in_the_kernels_own_blocks():
    """The published head size (a whole lane tile) in blocks of the
    kernel's own ``HEADS_PER_BLOCK``, two blocks a row; ten positions in
    a row, the state carried from one to the next as a decode step
    carries it (donated), against the recurrence over the ten."""
    heads, d, steps = 2 * HEADS_PER_BLOCK, 128, 10
    state, _ = rule_inputs(2, heads, d, seed=7, layers=2)
    positions = [rule_inputs(2, heads, d, seed=20 + t, layers=0)[1]
                 for t in range(steps)]
    step = jax.jit(kda_state_update, donate_argnums=(0,))
    want_o, want_s = jax.vmap(kimi.kda_recurrence, in_axes=(1,) * 5 + (0,),
                              out_axes=(1, 0))(
        *(jnp.stack(x) for x in zip(*positions)), state[1])
    untouched = np.asarray(state[0])
    for t, inputs in enumerate(positions):
        o, state = step(state, jnp.int32(1), *inputs)
        assert norm_error(o, want_o[t]) < 1e-5
    assert norm_error(state[1], want_s) < 1e-5
    np.testing.assert_array_equal(state[0], untouched)


@pytest.mark.parametrize("change, match", [
    (lambda s, q, beta: (s.astype(jnp.bfloat16), q, beta), "float32"),
    (lambda s, q, beta: (s, q.astype(jnp.bfloat16), beta), "float32"),
    (lambda s, q, beta: (s, q.reshape(ROWS, -1), beta), "float32 q, k, v"),
    (lambda s, q, beta: (s, q, beta[..., None]), "beta"),
], ids=["bf16_state", "bf16_q", "flat_q", "beta_3d"])
def test_what_is_not_float32_or_not_by_head_is_refused(change, match):
    """The rule is float32 end to end and the operands keep their head
    axis (the trace's selectors find the call by ``f32[rows, H, d]``):
    anything else raises before a kernel is built."""
    state, (q, k, v, g, beta) = rule_inputs(ROWS, 4, 16, seed=1)
    state, q, beta = change(state, q, beta)
    with pytest.raises(ValueError, match=match):
        kda_state_update(state, 0, q, k, v, g, beta)


def test_heads_that_no_block_divides_are_refused():
    state, inputs = rule_inputs(ROWS, 6, 16, seed=1)
    with pytest.raises(ValueError, match="6 heads in blocks of 4"):
        kda_state_update(state, 0, *inputs, heads_per_block=4)
