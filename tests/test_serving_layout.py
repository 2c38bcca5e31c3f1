"""The layout an engine holds a layer's query, key and value weights in
(PR 61): ``model.lay_for_serving`` puts ``wq``, ``wk`` and ``wv`` of
``llama.init_params`` side by side as ONE ``wqkv [n, E, (H + 2 KV) D]``,
``llama.qkv_of_normed`` takes either by what the layer holds, and
``LLMEngine`` asks ``serving_params`` for the laid tree (built in the
weights' own program, or laid once over what its caller hands it).
Over the three head arrangements the serve cells of this family have,
at toy widths: Mistral's (32 query heads on 8, no norm),
OLMoE's (16 on 16, the norm over all heads) and SDAR's (32 on 4, the
norm a head, generation by blocks). What the chip's compiler makes of
the layout is ``test_chip_compile_paged.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ARRANGEMENTS = {
    "32_on_8": dict(num_heads=32, num_kv_heads=8),
    "16_on_16_norm_over_heads": dict(num_heads=16, num_kv_heads=16,
                                     qk_norm=True),
    "32_on_4_norm_a_head": dict(num_heads=32, num_kv_heads=4, qk_norm="head",
                                block_length=4, denoising_steps=2,
                                mask_token_id=127),
}


def _config(arrangement: str, dtype=jnp.float32):
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=64, num_layers=2,
        head_dim=8, max_seq_len=64, remat=False, dtype=dtype,
        **ARRANGEMENTS[arrangement])


def _init(config, seed: int = 0) -> dict:
    """``init_params`` with norms that are not all ones."""
    from ray_tpu.models import llama

    params = llama.init_params(config, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))
    params["layers"] = {
        name: 1 + 0.3 * jax.random.normal(next(keys), w.shape, w.dtype)
        if name in ("q_norm", "k_norm") else w
        for name, w in params["layers"].items()}
    return params


def _bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("arrangement", sorted(ARRANGEMENTS))
def test_a_laid_layer_projects_as_the_three_did(arrangement):
    """``qkv_of_normed`` on a layer of the laid tree against the same on
    the layer ``init_params`` made: the same sums of the same products
    (float32 here, to the last bits), norms and rotation after the split
    as before it. Every leaf the laying does not replace IS the one it
    was given."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import model as paged_model

    config = _config(arrangement)
    params = _init(config)
    laid = paged_model.lay_for_serving(params)
    h, kv, d = config.num_heads, config.num_kv_heads, config.head_dim
    assert not set(paged_model.PROJECTIONS) & set(laid["layers"])
    assert laid["layers"]["wqkv"].shape == (2, 64, (h + 2 * kv) * d)
    assert set(params["layers"]) - set(laid["layers"]) \
        == set(paged_model.PROJECTIONS)
    for name, leaf in laid["layers"].items():
        assert name == "wqkv" or leaf is params["layers"][name]
    assert laid["lm_head"] is params["lm_head"]
    assert _bytes(laid) == _bytes(params)

    normed = jax.random.normal(jax.random.PRNGKey(7), (3, 5, 64))
    positions = jnp.arange(15).reshape(3, 5) * 3
    for index in range(config.num_layers):
        apart, together = (llama.qkv_of_normed(
            jax.tree.map(lambda w: w[index], tree["layers"]), normed,
            positions, config) for tree in (params, laid))
        for was, now, heads in zip(apart, together, (h, kv, kv)):
            assert now.shape == was.shape == (3, 5, heads, d)
            np.testing.assert_allclose(now, was, rtol=1e-5, atol=1e-6)


def test_laying_twice_is_laying_once():
    """A tree that is laid passes through, as arrays and as shapes: a
    second engine built from ``engine.params`` lays nothing."""
    from ray_tpu.serve.llm_engine import model as paged_model

    params = _init(_config("32_on_8"))
    laid = paged_model.lay_for_serving(params)
    assert paged_model.lay_for_serving(laid) is laid
    shapes = jax.eval_shape(paged_model.lay_for_serving, params)
    assert jax.eval_shape(paged_model.lay_for_serving, shapes) == shapes
    assert jax.tree.map(lambda x: x.shape, laid) \
        == jax.tree.map(lambda s: s.shape, shapes)


@pytest.mark.parametrize("arrangement", sorted(ARRANGEMENTS))
def test_an_engine_holds_each_projection_once(arrangement):
    """An engine that builds its own weights holds the laid tree and no
    byte more than ``init_params`` in the compute dtype; the programs
    take that tree; an engine handed ``engine.params`` holds the very
    same arrays. ``serving_params`` keeps returning ``init_params``'
    layout (the benchmark's references read that one), from the same
    seed the same values."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine
    from ray_tpu.serve.llm_engine import model as paged_model

    config = _config(arrangement, jnp.bfloat16)
    sizes = dict(max_batch_size=2, max_seq_len=32, block_size=8,
                 prefill_chunk=8)
    engine = LLMEngine(config, seed=3, **sizes)
    try:
        layers = engine.params["layers"]
        assert "wqkv" in layers and "wq" not in layers
        assert {x.dtype for x in jax.tree.leaves(engine.params)} \
            == {jnp.dtype(jnp.bfloat16)}
        shapes = jax.eval_shape(
            lambda: llama.init_params(config, jax.random.PRNGKey(0)))
        assert _bytes(engine.params) \
            == 2 * sum(s.size for s in jax.tree.leaves(shapes))
        served = paged_model.serving_params(config, None, 3)
        assert jax.tree.map(lambda x: x.shape, served) \
            == jax.tree.map(lambda s: s.shape, shapes)
        again = paged_model.lay_for_serving(served)["layers"]["wqkv"]
        assert (np.asarray(again, np.float32)
                == np.asarray(layers["wqkv"], np.float32)).all()
        second = LLMEngine(config, engine.params, **sizes)
        try:
            assert second.params is engine.params
            tokens = second.result(second.submit([5, 9, 2], max_new_tokens=4),
                                   timeout_s=120)
            assert len(tokens) == 4
        finally:
            second.shutdown()
    finally:
        engine.shutdown()


@pytest.mark.parametrize("arrangement", ["32_on_8",
                                         "16_on_16_norm_over_heads"])
def test_an_engine_on_a_callers_tree_leaves_it_whole(arrangement):
    """Handed ``init_params``' tree in the compute dtype, an engine lays
    it without touching the caller's arrays (nothing is donated: the
    three stay readable), shares every other leaf with the caller, and
    generates what ``llama.forward`` on the CALLER's tree does."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine

    config = _config(arrangement)
    params = _init(config, seed=11)
    engine = LLMEngine(config, params, max_batch_size=2, max_seq_len=32,
                       block_size=8, prefill_chunk=8)
    try:
        assert "wqkv" in engine.params["layers"]
        assert engine.params["layers"]["wo"] is params["layers"]["wo"]
        assert engine.params["embed"] is params["embed"]
        prompt = [5, 9, 2, 7]
        got = engine.result(engine.submit(prompt, max_new_tokens=5),
                            timeout_s=120)
    finally:
        engine.shutdown()
    tokens = list(prompt)
    for _ in range(5):
        logits = llama.forward(params, jnp.asarray([tokens], jnp.int32),
                               config)
        tokens.append(int(jnp.argmax(logits[0, -1])))
    assert not params["layers"]["wq"].is_deleted()
    assert got == tokens[len(prompt):]
