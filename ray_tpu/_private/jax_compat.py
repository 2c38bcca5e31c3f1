"""Thin helpers over the ambient-mesh API of the installed jax (0.9):
``jax.set_mesh``, ``jax.shard_map`` and
``jax.sharding.get_abstract_mesh`` are used directly; what lives here is
the ``None``-mesh convenience the engine and the kernels share, the rule
for when a pallas kernel interprets, and the probe for multi-process
execution on the CPU backend.
"""

from __future__ import annotations

import contextlib

import jax

_CPU_MULTIPROCESS: "bool | None" = None

_CPU_MULTIPROCESS_PROBE = r"""
import os, sys
rank = int(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.distributed.initialize(sys.argv[2], num_processes=2,
                           process_id=rank)
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()), ("dp",))
arr = jax.make_array_from_callback(
    (4,), NamedSharding(mesh, P("dp")),
    lambda idx: np.ones((1,), np.float32))
assert float(jax.jit(jnp.sum)(arr)) == 4.0
"""


def has_cpu_multiprocess(timeout_s: float = 120.0) -> bool:
    """Whether this jax/jaxlib can EXECUTE computations over a device
    mesh spanning multiple CPU-backend processes.

    Older jaxlib builds form the jax.distributed world fine but die at
    execute time with "Multiprocess computations aren't implemented on
    the CPU backend" (even with gloo collectives requested), so no
    version/attribute sniff is trustworthy — the probe runs a minimal
    2-process 1-collective program once and memoizes the verdict.
    Tests that gang CPU processes into one mesh skip when False.
    ``RAY_TPU_ASSUME_CPU_MULTIPROCESS=0/1`` overrides (CI determinism,
    or boxes where the probe itself is unwanted)."""
    global _CPU_MULTIPROCESS
    if _CPU_MULTIPROCESS is not None:
        return _CPU_MULTIPROCESS
    import os

    override = os.environ.get("RAY_TPU_ASSUME_CPU_MULTIPROCESS")
    if override is not None:
        _CPU_MULTIPROCESS = override.strip().lower() in (
            "1", "true", "yes", "on")
        return _CPU_MULTIPROCESS
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CPU_MULTIPROCESS_PROBE, str(rank),
         coord], env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL) for rank in range(2)]
    ok = True
    try:
        for p in procs:
            if p.wait(timeout=timeout_s) != 0:
                ok = False
    except subprocess.TimeoutExpired:
        ok = False
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    _CPU_MULTIPROCESS = ok
    return ok


def set_mesh(mesh):
    """``jax.set_mesh(mesh)`` as a context manager; ``mesh=None`` is a
    no-op (single-chip)."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def ambient_mesh():
    """The ambient (abstract) mesh, or None when none is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def interpret_kernels() -> bool:
    """Whether a pallas kernel that was not told runs in interpret mode:
    only where the platform is the CPU (tests, rehearsals). Every other
    backend, a TPU or one nobody has heard of, gets the compiled kernel,
    so a platform it cannot run on fails in its compiler and never
    quietly interprets."""
    return jax.default_backend() == "cpu"
