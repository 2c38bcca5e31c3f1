"""Device-plane collectives: XLA over ICI via shard_map.

The reference's tensor plane is NCCL
(util/collective/collective_group/nccl_collective_group.py); on TPU the
equivalent plane is XLA collectives over the chip interconnect (ICI),
expressed as `jax.lax` ops inside `shard_map` over a
`jax.sharding.Mesh`. Two layers here:

1. In-SPMD primitives — use directly inside your own shard_map'd
   function: ``psum``, ``pmean``, ``all_gather``, ``ppermute``,
   ``all_to_all``, ``axis_index`` (re-exported from jax.lax so user
   code imports one namespace).
2. Host-level helpers — take a host array whose LEADING axis enumerates
   per-device shards (the moral equivalent of "each worker holds a
   tensor"), run ONE compiled collective over the mesh, return the
   result. These are what actor code calls when it wants a one-shot
   device-backed collective without writing shard_map by hand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# In-SPMD primitives (layer 1).
psum = lax.psum
pmean = lax.pmean
pmax = lax.pmax
pmin = lax.pmin
all_gather = lax.all_gather
ppermute = lax.ppermute
all_to_all = lax.all_to_all
axis_index = lax.axis_index


def default_mesh(num_devices: int | None = None,
                 axis_name: str = "x") -> Mesh:
    devices = jax.devices()
    n = num_devices or len(devices)
    return Mesh(np.asarray(devices[:n]), (axis_name,))


def _sharded(x, mesh: Mesh, axis_name: str):
    x = jnp.asarray(x)
    n = mesh.shape[axis_name]
    if x.shape[0] != n:
        raise ValueError(
            f"leading axis {x.shape[0]} must equal mesh axis "
            f"{axis_name}={n} (one shard per device)")
    return jax.device_put(
        x, NamedSharding(mesh, P(axis_name, *([None] * (x.ndim - 1)))))


def device_allreduce(x, mesh: Mesh | None = None, axis_name: str = "x"):
    """x: [n_devices, ...] (shard i lives on device i) → sum over shards,
    reduced on-device (psum over ICI), replicated result returned."""
    mesh = mesh or default_mesh(axis_name=axis_name)

    @jax.jit
    def fn(x):
        return jax.shard_map(
            lambda s: psum(s, axis_name), mesh=mesh,
            in_specs=P(axis_name), out_specs=P())(x)

    return np.asarray(fn(_sharded(x, mesh, axis_name)))[0]


def device_allgather(x, mesh: Mesh | None = None, axis_name: str = "x"):
    """x: [n_devices, ...] → [n_devices, ...] gathered on every device."""
    mesh = mesh or default_mesh(axis_name=axis_name)

    @jax.jit
    def fn(x):
        # all_gather's replication isn't statically inferred → check_vma
        # off for this one wrapper.
        return jax.shard_map(
            lambda s: all_gather(s, axis_name, axis=0, tiled=True),
            mesh=mesh, in_specs=P(axis_name), out_specs=P(),
            check_vma=False)(x)

    return np.asarray(fn(_sharded(x, mesh, axis_name)))


def device_reducescatter(x, mesh: Mesh | None = None,
                         axis_name: str = "x"):
    """x: [n_devices, m, ...] → each device ends with its [m/n] chunk of
    the sum; returned as [n_devices, m/n, ...] (chunk i from device i)."""
    mesh = mesh or default_mesh(axis_name=axis_name)

    @jax.jit
    def fn(x):
        return jax.shard_map(
            lambda s: lax.psum_scatter(
                s[0], axis_name, scatter_dimension=0, tiled=True)[None],
            mesh=mesh, in_specs=P(axis_name), out_specs=P(axis_name))(x)

    return np.asarray(fn(_sharded(x, mesh, axis_name)))


def device_ring_shift(x, mesh: Mesh | None = None, axis_name: str = "x",
                      shift: int = 1):
    """Ring ppermute: shard i moves to device (i+shift) % n — the
    building block of ring attention / pipeline comm."""
    mesh = mesh or default_mesh(axis_name=axis_name)
    n = mesh.shape[axis_name]
    perm = [(i, (i + shift) % n) for i in range(n)]

    @jax.jit
    def fn(x):
        return jax.shard_map(
            lambda s: ppermute(s, axis_name, perm), mesh=mesh,
            in_specs=P(axis_name), out_specs=P(axis_name))(x)

    return np.asarray(fn(_sharded(x, mesh, axis_name)))
