"""Device mesh construction and axis conventions.

This is where the framework departs hardest from the reference: Ray's
"model parallelism story" is launch + NCCL (SURVEY §2.4); here TP/PP/DP/
SP/EP are first-class mesh axes consumed by GSPMD. The canonical axes:

- ``dp``   — pure data parallelism (params replicated)
- ``fsdp`` — data parallelism with parameter sharding (ZeRO-3 analogue)
- ``tp``   — tensor parallelism (Megatron-style column/row sharding)
- ``sp``   — sequence/context parallelism (ring attention over ICI)
- ``ep``   — expert parallelism (MoE expert sharding)
- ``pp``   — pipeline parallelism (stage sharding, scan-over-stages)

Collectives ride ICI when the mesh is laid out so that the fastest-
varying axes map to physically adjacent chips; ``build_mesh`` uses
jax.experimental.mesh_utils to get that layout on real TPU topologies.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("pp", "dp", "fsdp", "sp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each parallelism axis; -1 on at most one axis means
    "use all remaining devices"."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    def resolved(self, num_devices: int) -> "MeshConfig":
        sizes = {axis: getattr(self, axis) for axis in AXIS_ORDER}
        wildcard = [a for a, s in sizes.items() if s == -1]
        if len(wildcard) > 1:
            raise ValueError("At most one mesh axis may be -1")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcard:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes {fixed}")
            sizes[wildcard[0]] = num_devices // fixed
        total = math.prod(sizes.values())
        if total != num_devices:
            raise ValueError(
                f"Mesh axes {sizes} multiply to {total}, but {num_devices} "
                "devices are available")
        return MeshConfig(**{k: sizes[k] for k in ("dp", "fsdp", "tp", "sp", "ep", "pp")})

    @property
    def axis_sizes(self) -> dict[str, int]:
        return {axis: getattr(self, axis) for axis in AXIS_ORDER}


def build_mesh(config: MeshConfig | None = None,
               devices: Sequence[jax.Device] | None = None,
               axis_names: Sequence[str] | None = None) -> Mesh:
    """Build a Mesh with the canonical axis order.

    Axes of size 1 are kept (GSPMD treats them as free), so sharding
    rules can always reference any canonical axis name.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    config = (config or MeshConfig(dp=-1)).resolved(len(devices))
    shape = tuple(config.axis_sizes[a] for a in AXIS_ORDER)
    names = tuple(axis_names or AXIS_ORDER)
    if devices and devices[0].platform == "tpu":
        # A layout the physical topology cannot give is an error: a
        # naive ordering would run, with collectives off the fast links.
        from jax.experimental import mesh_utils

        mesh_devices = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        mesh_devices = np.array(devices).reshape(shape)
    return Mesh(mesh_devices, names)


def single_axis_mesh(axis: str = "dp",
                     devices: Sequence[jax.Device] | None = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(list(devices)), (axis,))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes actually sharding the batch dimension (size > 1)."""
    return tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
