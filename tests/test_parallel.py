"""Mesh/sharding/ring-attention tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import AXIS_ORDER, MeshConfig, build_mesh
from ray_tpu.parallel.ring_attention import (
    plain_attention,
    ring_attention_sharded,
)
from ray_tpu.parallel.sharding import (
    constrain,
    logical_to_spec,
    named_sharding,
    shard_params,
)


def test_mesh_config_wildcard():
    cfg = MeshConfig(tp=2, dp=-1).resolved(8)
    assert cfg.dp == 4 and cfg.tp == 2


def test_mesh_config_invalid():
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=2).resolved(8)


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    assert mesh.axis_names == AXIS_ORDER
    assert mesh.shape["dp"] == 2
    assert mesh.shape["tp"] == 4


def test_logical_to_spec_default_rules():
    spec = logical_to_spec(("batch", "embed", "heads"))
    assert spec == P(("dp", "fsdp"), None, "tp")  # embed->fsdp consumed by batch


def test_logical_to_spec_no_double_axis_use():
    # batch consumes dp+fsdp; embed (fsdp) must then be replicated.
    spec = logical_to_spec(("batch", "embed"))
    assert spec[1] is None


def test_shard_params_places_on_mesh():
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    params = {"w": jnp.ones((16, 32)), "b": jnp.ones((32,))}
    logical = {"w": ("embed", "mlp"), "b": (None,)}
    sharded = shard_params(params, mesh, logical)
    assert isinstance(sharded["w"].sharding, NamedSharding)
    assert sharded["w"].sharding.spec == P("fsdp", "tp")


def test_constrain_inside_jit():
    mesh = build_mesh(MeshConfig(dp=2, tp=4))

    @jax.jit
    def f(x):
        return constrain(x * 2, mesh, "batch", "embed")

    x = jnp.ones((8, 16))
    np.testing.assert_allclose(f(x), 2 * np.ones((8, 16)))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_plain(causal):
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    b, l, h, d = 2, 32, 4, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, l, h, d), dtype=jnp.float32)
    k = jax.random.normal(kk, (b, l, h, d), dtype=jnp.float32)
    v = jax.random.normal(kv, (b, l, h, d), dtype=jnp.float32)

    expected = plain_attention(q, k, v, causal=causal)
    with mesh:
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_flow():
    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    b, l, h, d = 2, 16, 2, 4
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, l, h, d))

    def loss_ring(q):
        with mesh:
            return ring_attention_sharded(q, q, q, mesh, causal=True).sum()

    def loss_plain(q):
        return plain_attention(q, q, q, causal=True).sum()

    g_ring = jax.grad(loss_ring)(q)
    g_plain = jax.grad(loss_plain)(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_plain),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_plain(causal):
    import functools

    from ray_tpu.parallel.ring_attention import ulysses_attention

    mesh = build_mesh(MeshConfig(sp=4, dp=2))
    b, l, h, d = 2, 32, 8, 4  # h divisible by sp=4
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (b, l, h, d))
    k = jax.random.normal(keys[1], (b, l, h, d))
    v = jax.random.normal(keys[2], (b, l, h, d))
    expected = plain_attention(q, k, v, causal=causal)

    from jax.sharding import PartitionSpec as P

    spec = P(("dp",), "sp", None, None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    def inner(q, k, v):
        return ulysses_attention(q, k, v, axis_name="sp", causal=causal)

    with jax.set_mesh(mesh):
        out = inner(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)
