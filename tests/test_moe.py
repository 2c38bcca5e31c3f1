"""``models/moe.py``'s expert layer told WHICH experts it holds
(``held=(first, count)``, PR 50): the shares of a layer whose experts
are split over chips add up to the uncut layer, the counters count the
experts held, and ``held=None`` is the code of before: the three sparse
families' programs lower to the same text."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import kimi_linear_decoder as reference  # noqa: E402
from ray_tpu.models import llama, moe, xing  # noqa: E402
from ray_tpu.serve.llm_engine import model as paged_model  # noqa: E402

HIDDEN, MLP, EXPERTS, PER_TOKEN, SHARES = 32, 24, 16, 3, 8


def one_layer(seed=0):
    """An expert layer of 16 routed experts and a shared one, float32,
    and 2 x 20 tokens."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    layer = {k: v[0] for k, v in moe.init_moe_params(
        keys[0], HIDDEN, MLP, EXPERTS, 1).items()}
    layer["router_bias"] = 0.05 * jax.random.normal(keys[1], (EXPERTS,))
    for name, key, shape in (("shared_gate", keys[2], (HIDDEN, MLP)),
                             ("shared_up", keys[3], (HIDDEN, MLP)),
                             ("shared_down", keys[4], (MLP, HIDDEN))):
        layer[name] = jax.random.normal(key, shape) * shape[0] ** -0.5
    return layer, jax.random.normal(keys[5], (2, 20, HIDDEN))


def routed(layer, x):
    return moe.route(x, layer["w_router"], PER_TOKEN, True, scoring="sigmoid",
                     bias=layer["router_bias"], scale=2.446)


def share_of(layer, first, count):
    """The layer as the chip that holds experts ``first .. first +
    count - 1`` has it: the whole router, its own experts' arrays."""
    held = {k: layer[k][first:first + count]
            for k in ("w_gate", "w_up", "w_down")}
    return {**layer, **held}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of the eight chips that share a layer, each
    adding the chosen experts it HOLDS, plus the shared expert ONCE,
    are the uncut plain reference's output for the whole layer: what a
    share leaves out is exactly what the other seven add."""
    layer, x = one_layer()
    _, idx, weights = routed(layer, x)
    per = EXPERTS // SHARES
    parts = []
    for share in range(SHARES):
        held = (share * per, per)
        combine = moe.combine_weights(idx, weights, EXPERTS, held)
        assert combine.shape == (2, 20, per)
        parts.append(moe.expert_ffn(share_of(layer, *held), x, combine,
                                    jnp.float32))
    total = sum(parts) + moe.shared_ffn(layer, x, jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref_idx, ref_weights = reference.route(
            x, layer, {"num_experts_per_token": PER_TOKEN,
                       "routed_scaling_factor": 2.446})
        want = reference.experts(x, layer, ref_idx, ref_weights, 0)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ref_idx, -1))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    # No one share is the layer, and every share adds something.
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in parts)
    # The whole layer held is the code of before.
    whole = moe.expert_ffn(layer, x, moe.combine_weights(
        idx, weights, EXPERTS), jnp.float32)
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5, rtol=0)
    # ... and the reference given ONE share routes over all 16 and adds
    # the two it holds.
    held = (6, 2)
    with jax.default_matmul_precision("highest"):
        one = reference.experts(x, share_of(layer, *held), ref_idx,
                                ref_weights, held[0])
    np.testing.assert_allclose(
        parts[3] + moe.shared_ffn(layer, x, jnp.float32), one, atol=2e-5,
        rtol=0)


def test_the_counters_count_the_experts_held():
    layer, x = one_layer(1)
    _, idx, _ = routed(layer, x)
    valid = jnp.ones((2, 20), bool).at[1, 3:].set(False)
    whole = np.asarray(moe.routing_counts(idx, valid, EXPERTS))
    assert whole[0] == 23 * PER_TOKEN and whole[1] == EXPERTS
    choices = touched = 0
    for share in range(SHARES):
        counts = np.asarray(moe.routing_counts(idx, valid, EXPERTS,
                                               (2 * share, 2)))
        landed = int(((np.asarray(idx) // 2 == share)
                      & np.asarray(valid)[..., None]).sum())
        assert counts[0] == landed and counts[1] == 2
        assert counts[2] <= 2 and counts[3] % 2 == 0
        choices, touched = choices + counts[0], touched + counts[2]
    # Every choice lands on exactly one chip.
    assert (choices, touched) == (whole[0], whole[2])
    with pytest.raises(ValueError):
        moe.combine_weights(idx, jnp.ones(idx.shape), EXPERTS, (12, 8))


def before_pr50(idx, num_experts, dtype):
    """``combine_weights``' and ``routing_counts``' one-hot as both had
    it before the layer could be told what it holds."""
    return jax.nn.one_hot(idx, num_experts, dtype=dtype)


def sparse_programs():
    """A tiny OLMoE, SDAR and Xing decode program, lowered."""
    olmoe = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=48,
        remat=False, num_experts=8, experts_per_token=3, qk_norm=True)
    sdar = dataclasses.replace(
        olmoe, num_kv_heads=2, norm_topk_prob=True, qk_norm="head",
        block_length=4, mask_token_id=255, denoising_steps=2)
    texts = []
    for config in (olmoe, sdar, xing.XingConfig.tiny()):
        family = paged_model.family(config)
        params = jax.eval_shape(lambda: family.init_params(
            config, jax.random.PRNGKey(0)))
        cache = jax.eval_shape(lambda: family.init_cache(config, 9, 8, 2, 8))
        span = getattr(config, "block_length", 0)
        prev = jax.ShapeDtypeStruct((2, span) if span else (2,), jnp.int32)
        texts.append(family.make_engine_decode_step(config, 8).lower(
            params, cache, family.pack_decode_rows(2, 4, ()),
            jax.ShapeDtypeStruct((2,), jnp.uint32), moe.init_stats(),
            prev).as_text())
    return texts


def test_held_none_leaves_the_sparse_programs_as_they_were(monkeypatch):
    """OLMoE's, SDAR's and Xing's decode programs, whose expert layers
    hold every expert: the same text with ``held=None`` as with the
    one-hot of before PR 50 put back in its place."""
    now = sparse_programs()
    monkeypatch.setattr(
        moe, "_chosen",
        lambda idx, num_experts, held, dtype:
        before_pr50(idx, num_experts, dtype))
    assert sparse_programs() == now
    assert all("one_hot" in text or "iota" in text for text in now)
