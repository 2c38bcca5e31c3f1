"""Llama model + sharded training-step tests on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import (
    build_train_step,
    create_train_state,
    default_optimizer,
    shard_batch,
)


@pytest.fixture(scope="module")
def tiny_cfg():
    return llama.LlamaConfig.tiny()


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return llama.init_params(tiny_cfg, jax.random.PRNGKey(0))


def test_forward_shapes(tiny_cfg, tiny_params):
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = llama.forward(tiny_params, tokens, tiny_cfg)
    assert logits.shape == (2, 16, tiny_cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_causality(tiny_cfg, tiny_params):
    """Changing a future token must not affect earlier logits."""
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (1, 16), 0, tiny_cfg.vocab_size)
    logits1 = llama.forward(tiny_params, tokens, tiny_cfg)
    tokens2 = tokens.at[0, 12].set((tokens[0, 12] + 7) % tiny_cfg.vocab_size)
    logits2 = llama.forward(tiny_params, tokens2, tiny_cfg)
    np.testing.assert_allclose(np.asarray(logits1[0, :12]),
                               np.asarray(logits2[0, :12]), atol=1e-3)
    assert not np.allclose(np.asarray(logits1[0, 12:]),
                           np.asarray(logits2[0, 12:]), atol=1e-3)


def test_loss_finite(tiny_cfg, tiny_params):
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    targets = jnp.ones((2, 16), dtype=jnp.int32)
    loss = llama.loss_fn(tiny_params, tokens, targets, tiny_cfg)
    assert jnp.isfinite(loss)
    # Untrained model: loss should be near ln(vocab).
    assert 0.5 * np.log(tiny_cfg.vocab_size) < float(loss) < 2.5 * np.log(
        tiny_cfg.vocab_size)


def test_sharded_train_step_dp_fsdp_tp(tiny_cfg, tiny_params):
    """Full GSPMD training step over dp×fsdp×tp; loss must decrease."""
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    with jax.set_mesh(mesh):
        optimizer = default_optimizer(learning_rate=1e-2, warmup_steps=1,
                                      total_steps=50)
        state = create_train_state(
            tiny_params, optimizer, mesh, llama.param_logical_axes(tiny_cfg))

        def loss(params, batch):
            return llama.loss_fn(params, batch["tokens"], batch["targets"],
                                 tiny_cfg)

        step = build_train_step(loss, optimizer)
        key = jax.random.PRNGKey(0)
        tokens = jax.random.randint(key, (4, 32), 0, tiny_cfg.vocab_size)
        batch = shard_batch(
            {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}, mesh)
        losses = []
        for _ in range(8):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses
        # Params kept their sharding through the step.
        flat = jax.tree.leaves(state.params)
        assert all(hasattr(p, "sharding") for p in flat)


def test_ring_attention_model_matches_plain(tiny_params):
    """config.attention='ring' over sp must match plain attention logits.

    Compared in f32 so the only difference is the attention algorithm,
    not bf16 accumulation order.
    """
    import dataclasses as dc

    cfg_plain = dc.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    cfg_ring = dc.replace(cfg_plain, attention="ring")
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    with jax.set_mesh(mesh):
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                    cfg_plain.vocab_size)
        expected = llama.forward(tiny_params, tokens, cfg_plain)
        got = jax.jit(
            lambda p, t: llama.forward(p, t, cfg_ring))(tiny_params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=3e-2, rtol=3e-2)


def test_gqa_config():
    cfg = llama.LlamaConfig.tiny()
    import dataclasses as dc

    cfg = dc.replace(cfg, num_kv_heads=2)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    logits = llama.forward(params, jnp.zeros((1, 8), dtype=jnp.int32), cfg)
    assert logits.shape == (1, 8, cfg.vocab_size)


def test_num_params_counts():
    cfg = llama.LlamaConfig.llama2_7b()
    assert 6.5e9 < cfg.num_params < 7.5e9


def test_param_axes_match_tree(tiny_cfg, tiny_params):
    axes = llama.param_logical_axes(tiny_cfg)
    jax.tree.map(lambda p, a: None, tiny_params, axes,
                 is_leaf=lambda x: isinstance(x, tuple))
