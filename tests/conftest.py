"""Test fixtures.

JAX is forced onto a virtual 8-device CPU platform so multi-chip sharding
logic (pjit/shard_map over a Mesh) is exercised without TPU hardware —
the same strategy as the reference's "many nodes on one box" fixtures
(reference: python/ray/cluster_utils.py:108).
"""

import os

# Must run before jax is imported anywhere. Force (not setdefault): a
# machine with a chip exports JAX_PLATFORMS=tpu,cpu, but tests always
# run on the virtual CPU mesh.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["RAY_TPU_SKIP_TPU_DETECTION"] = "1"

# Tier-1 runs with the lock-order witness ARMED (ISSUE 13): every lock
# the hot modules create — in this process AND in every daemon spawned
# through daemon_child_env, which inherits the environment — records
# acquisition order, and a cycle (potential deadlock) raises
# LockOrderError at its acquire site instead of surfacing as a CI
# timeout. Must be set before any ray_tpu import (the witness arms at
# module import, and locks are created at object construction).
# Export RAY_TPU_LOCK_WITNESS=0 to run tier-1 unwitnessed.
os.environ.setdefault("RAY_TPU_LOCK_WITNESS", "1")

import jax

assert jax.devices()[0].platform == "cpu" and len(jax.devices()) >= 8

import pytest


@pytest.fixture
def ray_start_regular():
    """A fresh single-node runtime per test (reference: conftest.py
    ray_start_regular)."""
    import ray_tpu

    ray_tpu.shutdown()
    runtime = ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield runtime
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """A runtime plus the ability to add virtual nodes."""
    import ray_tpu
    from ray_tpu._private import worker as worker_mod

    ray_tpu.shutdown()
    runtime = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield runtime
    ray_tpu.shutdown()


@pytest.fixture
def cpu_mesh8():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
    with Mesh(devices, ("dp", "tp")) as mesh:
        yield mesh
