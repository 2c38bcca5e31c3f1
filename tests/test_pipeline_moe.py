"""Pipeline parallelism (pp) and MoE expert parallelism (ep).

Correctness oracles: the pipelined forward must match the sequential
scan-over-layers forward exactly (same params), and an ep-sharded MoE
must match its single-device execution.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.models.moe import init_moe_params, moe_mlp
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.pipeline import (
    llama_pipeline_forward,
    merge_stages,
    pipeline_apply,
    split_stages,
)


def _tiny(num_experts=0, experts_per_token=1):
    return dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32,
        num_experts=num_experts, experts_per_token=experts_per_token)


# Sparse cases run at one expert per token, as before top-k routing,
# and at two or more (tests/test_olmoe.py holds the block itself to
# the plain reference).
TOP_K = [1, 2]


def test_pipeline_stage_count_must_match_mesh():
    mesh = build_mesh(MeshConfig(pp=2, dp=4))
    w = jnp.ones((8, 4, 4))
    staged = split_stages(w, 4)  # 4 stages on a pp=2 mesh: reject

    def stage_fn(sw, h):
        return h

    with jax.set_mesh(mesh):
        staged = jax.device_put(staged, NamedSharding(mesh, P("pp")))
        x = jnp.ones((4, 4))
        with pytest.raises(ValueError, match="mesh axis size"):
            jax.jit(lambda p, h: pipeline_apply(
                stage_fn, p, h, num_microbatches=2))(staged, x)


@pytest.mark.parametrize("k", [1, 3])
def test_moe_flops_accounting_uses_active_params(k):
    dense = _tiny()
    moe = _tiny(num_experts=8, experts_per_token=k)
    # Total params grow with experts; active (compute) params with the
    # experts a token is routed to, not with the experts there are.
    assert moe.num_params > dense.num_params
    expert = 3 * dense.hidden_size * dense.intermediate_size
    assert moe.num_active_params == pytest.approx(
        dense.num_params + moe.num_layers * (
            dense.hidden_size * 8 + (k - 1) * expert), rel=0.01)
    assert moe.num_active_params < dense.num_active_params * (0.1 + k)


def test_split_merge_stages_roundtrip():
    params = {"w": jnp.arange(24.0).reshape(4, 3, 2)}
    staged = split_stages(params, 2)
    assert staged["w"].shape == (2, 2, 3, 2)
    np.testing.assert_array_equal(merge_stages(staged)["w"], params["w"])
    with pytest.raises(ValueError):
        split_stages(params, 3)


def test_pipeline_apply_matches_sequential():
    """Generic pipeline over a toy stage function == sequential apply."""
    mesh = build_mesh(MeshConfig(pp=4, dp=2))
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (8, 16, 16))  # 8 "layers" of matmul
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    def stage_fn(stage_w, h):
        def body(h, wi):
            return jnp.tanh(h @ wi), None

        h, _ = jax.lax.scan(body, h, stage_w)
        return h

    # Sequential oracle.
    expected = stage_fn(w, x)

    staged = split_stages(w, 4)
    with jax.set_mesh(mesh):
        staged = jax.device_put(staged, NamedSharding(mesh, P("pp")))
        xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "fsdp"))))
        out = jax.jit(lambda p, h: pipeline_apply(
            stage_fn, p, h, num_microbatches=2))(staged, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)


def test_llama_pipeline_forward_matches_sequential():
    cfg = _tiny()
    mesh = build_mesh(MeshConfig(pp=2, dp=2, tp=2))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    expected = llama.forward(params, tokens, cfg)
    with jax.set_mesh(mesh):
        logits = jax.jit(lambda p, t: llama_pipeline_forward(
            p, t, cfg, num_stages=2, num_microbatches=2))(params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_pipeline_is_differentiable():
    cfg = _tiny()
    mesh = build_mesh(MeshConfig(pp=2, dp=2, tp=2))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                cfg.vocab_size)

    def loss(p):
        logits = llama_pipeline_forward(
            p, tokens[:, :-1], cfg, num_stages=2, num_microbatches=2)
        return llama.cross_entropy(logits, tokens[:, 1:])

    with jax.set_mesh(mesh):
        val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    gnorm = float(jnp.sqrt(sum(
        jnp.sum(g ** 2) for g in jax.tree.leaves(grads))))
    assert gnorm > 0 and np.isfinite(gnorm)


# --------------------------------------------------------------------- MoE


@pytest.mark.parametrize("k", TOP_K)
def test_moe_layer_shapes_and_aux(k):
    params = init_moe_params(jax.random.PRNGKey(0), hidden=16, mlp=32,
                             num_experts=4, num_layers=1)
    layer = jax.tree.map(lambda p: p[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    out, aux = moe_mlp(layer, x, experts_per_token=k, dtype=jnp.float32)
    assert out.shape == x.shape
    # Perfectly balanced routing gives aux == 1; collapse gives ~E.
    assert 0.9 <= float(aux) <= 4.1


@pytest.mark.parametrize("k", TOP_K)
def test_moe_ep_sharded_matches_single_device(k):
    cfg = _tiny(num_experts=4, experts_per_token=k)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    logits_single, aux_single = llama.forward(params, tokens, cfg,
                                              with_aux=True)

    mesh = build_mesh(MeshConfig(dp=2, ep=4))
    from ray_tpu.parallel.sharding import shard_params

    with jax.set_mesh(mesh):
        sharded = shard_params(params, mesh, llama.param_logical_axes(cfg))
        logits, aux = jax.jit(
            lambda p, t: llama.forward(p, t, cfg, with_aux=True)
        )(sharded, tokens)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(logits_single),
                               atol=2e-4, rtol=2e-4)
    assert float(aux) == pytest.approx(float(aux_single), rel=1e-4)


@pytest.mark.parametrize("k", TOP_K)
def test_moe_train_step_learns(k):
    """A full train step over dp x ep decreases loss on a tiny corpus."""
    from ray_tpu.parallel.train_step import (
        build_train_step,
        create_train_state,
        default_optimizer,
        shard_batch,
    )

    cfg = dataclasses.replace(_tiny(num_experts=2, experts_per_token=k),
                              remat=False)
    mesh = build_mesh(MeshConfig(dp=2, ep=2, tp=2))
    with jax.set_mesh(mesh):
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        optimizer = default_optimizer(1e-2, warmup_steps=1, total_steps=50)
        state = create_train_state(params, optimizer, mesh,
                                   llama.param_logical_axes(cfg))

        def loss(p, batch):
            return llama.loss_fn(p, batch["tokens"], batch["targets"], cfg)

        step = build_train_step(loss, optimizer)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                    cfg.vocab_size)
        batch = shard_batch(
            {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}, mesh)
        state, m0 = step(state, batch)
        for _ in range(10):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"])


def test_llama_pipeline_tp_inside_stage_matches_sequential():
    """pp x tp composition (VERDICT r2 #8): Megatron-style tensor
    parallelism inside each pipeline stage must reproduce the plain
    sequential forward."""
    cfg = _tiny()
    mesh = build_mesh(MeshConfig(pp=2, dp=2, tp=2))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    expected = llama.forward(params, tokens, cfg)
    with jax.set_mesh(mesh):
        logits = jax.jit(lambda p, t: llama_pipeline_forward(
            p, t, cfg, num_stages=2, num_microbatches=2,
            tp_axis="tp"))(params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_llama_pipeline_tp_gqa_matches_sequential():
    """GQA under tp (kv heads sharded too): the per-shard head-group
    repeat must keep q/kv pairing intact."""
    cfg = dataclasses.replace(_tiny(), num_kv_heads=2)
    mesh = build_mesh(MeshConfig(pp=2, dp=2, tp=2))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    expected = llama.forward(params, tokens, cfg)
    with jax.set_mesh(mesh):
        logits = jax.jit(lambda p, t: llama_pipeline_forward(
            p, t, cfg, num_stages=2, num_microbatches=2,
            tp_axis="tp"))(params, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expected),
                               atol=2e-4, rtol=2e-4)


def test_llama_pipeline_tp_differentiable():
    cfg = _tiny()
    mesh = build_mesh(MeshConfig(pp=2, dp=2, tp=2))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                cfg.vocab_size)

    def loss(p):
        logits = llama_pipeline_forward(
            p, tokens[:, :-1], cfg, num_stages=2, num_microbatches=2,
            tp_axis="tp")
        return llama.cross_entropy(logits, tokens[:, 1:])

    with jax.set_mesh(mesh):
        val, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(val))
    gnorm = float(jnp.sqrt(sum(
        jnp.sum(g ** 2) for g in jax.tree.leaves(grads))))
    assert gnorm > 0 and np.isfinite(gnorm)


@pytest.mark.parametrize("k", TOP_K)
def test_llama_pipeline_moe_matches_sequential_with_aux(k):
    """MoE inside the pipeline (VERDICT r2 #8): logits AND the router
    aux loss (threaded through the scan carry) must match the
    unpipelined forward."""
    cfg = _tiny(num_experts=4, experts_per_token=k)
    mesh = build_mesh(MeshConfig(pp=2, dp=4))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    expected_logits, expected_aux = llama.forward(
        params, tokens, cfg, with_aux=True)
    with jax.set_mesh(mesh):
        logits, aux = jax.jit(lambda p, t: llama_pipeline_forward(
            p, t, cfg, num_stages=2, num_microbatches=2,
            with_aux=True))(params, tokens)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(expected_logits),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(float(aux), float(expected_aux),
                               atol=1e-5, rtol=1e-5)


def test_llama_pipeline_moe_rejects_tp():
    cfg = _tiny(num_experts=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, 16), jnp.int32)
    with pytest.raises(NotImplementedError):
        llama_pipeline_forward(params, tokens, cfg, num_stages=2,
                               num_microbatches=2, tp_axis="tp",
                               with_aux=True)
