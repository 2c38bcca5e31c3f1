"""Llama model + sharded training-step tests on the virtual CPU mesh."""

import contextlib
import dataclasses
import re

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


# ------------------------------------------------- the scan's remat policy


def _flash_cfg(kv_heads, **kw):
    return dataclasses.replace(
        llama.LlamaConfig.tiny(), num_kv_heads=kv_heads, attention="flash",
        dtype=jnp.float32, **kw)


def _loss_of(cfg, tokens):
    return lambda p: llama.loss_fn(p, tokens[:, :-1], tokens[:, 1:], cfg)


@pytest.mark.parametrize("policy", ["attention", "full"])
@pytest.mark.parametrize("kv_heads", [4, 2], ids=["equal", "grouped"])
def test_remat_policy_changes_no_value(kv_heads, policy):
    """What the scan keeps decides what is computed twice, never what:
    loss and gradients under either policy are those of remat=False."""
    base = _flash_cfg(kv_heads)
    params = llama.init_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                base.vocab_size)
    cfg = _flash_cfg(kv_heads, remat=True, remat_policy=policy)
    want_loss, want = jax.jit(jax.value_and_grad(_loss_of(base, tokens)))(
        params)
    got_loss, got = jax.jit(jax.value_and_grad(_loss_of(cfg, tokens)))(
        params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("policy, forwards", [("attention", 1), ("full", 2)])
@pytest.mark.parametrize("path", ["equal", "grouped", "shard_map"])
def test_backward_runs_the_flash_forward_once(path, policy, forwards):
    """The differentiated step holds the forward kernel once a layer
    (the forward scan's body) when the scan keeps the kernel's o and
    lse by name, and twice (again in the backward scan's body) under
    "full". The names are set inside the kernel's custom_vjp rule and
    have to survive the grouped path's vmap and the mesh path's
    shard_map."""
    cfg = _flash_cfg(4 if path == "equal" else 2, remat=True,
                     remat_policy=policy)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 33), dtype=jnp.int32)
    mesh = contextlib.nullcontext()
    if path == "shard_map":
        mesh = jax.set_mesh(build_mesh(MeshConfig(dp=2, fsdp=2, tp=2)))
    with mesh:
        text = str(jax.make_jaxpr(jax.grad(_loss_of(cfg, tokens)))(params))
    assert ("shard_map" in text) == (path == "shard_map")
    kernels = re.findall(r"\bname=(flash_\w+)", text)
    assert kernels.count("flash_fwd") == forwards, kernels
    assert kernels.count("flash_bwd_dq") == 1, kernels
    assert kernels.count("flash_bwd_dkv") == 1, kernels


@pytest.mark.parametrize("policy", ["dots", "everything"])
def test_unknown_remat_policy_raises(policy):
    """"dots" went with PR 41: it had no caller and fits no cell."""
    cfg = _flash_cfg(4, remat=True, remat_policy=policy)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="remat_policy"):
        llama.forward(params, jnp.zeros((1, 8), dtype=jnp.int32), cfg)
