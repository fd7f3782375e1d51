"""Device mesh construction with standard parallelism axes.

TPU-first core of the framework (no reference equivalent — the reference
relies on NCCL process groups; SURVEY.md §2.4 maps each strategy to the
mesh axis built here):

  dp    data parallelism (batch split; gradient psum)
  fsdp  parameter sharding (ZeRO-3 style, GSPMD handles gather/scatter)
  tp    tensor parallelism (sharded matmuls over ICI)
  cp    context parallelism (sequence split; ring attention)
  ep    expert parallelism (MoE all-to-all)

Multislice: an extra leading "dcn" axis maps data parallelism across
slices (DCN), with all other axes inside a slice (ICI) — the hierarchical
mesh the MEGASCALE env (accelerators/tpu.py get_tpu_coordinator_env_vars)
configures.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("dcn", "dp", "fsdp", "ep", "cp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes per axis; -1 on exactly one axis means "absorb the rest"."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    cp: int = 1
    ep: int = 1
    dcn: int = 1

    def resolve(self, num_devices: int) -> Dict[str, int]:
        sizes = {
            "dcn": self.dcn, "dp": self.dp, "fsdp": self.fsdp,
            "ep": self.ep, "cp": self.cp, "tp": self.tp,
        }
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"only one axis may be -1, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes {fixed}"
                )
            sizes[wild[0]] = num_devices // fixed
        if math.prod(sizes.values()) != num_devices:
            raise ValueError(
                f"mesh {sizes} does not cover {num_devices} devices"
            )
        return sizes


def build_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence] = None,
    axis_names: Sequence[str] = AXIS_ORDER,
) -> Mesh:
    """Build a Mesh over all (or given) devices with the standard axes.

    Axis order puts dcn outermost (slowest-varying = cross-slice DCN) and
    tp innermost (fastest-varying = nearest-neighbor ICI), matching the
    physical topology so TP collectives ride the shortest links.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in axis_names)
    try:
        from jax.experimental import mesh_utils

        if sizes.get("dcn", 1) > 1:
            per_slice = [s if a != "dcn" else 1 for a, s in zip(axis_names, shape)]
            dcn_shape = [sizes["dcn"] if a == "dcn" else 1 for a in axis_names]
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per_slice, dcn_shape, devices=devices
            )
        else:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError):
        if devices[0].platform == "tpu":
            # a real topology was refused: reshaping the device list
            # instead would lay mesh neighbours on distant chips and
            # never say so
            raise
        # virtual CPU devices have no topology: plain reshape
        dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def local_mesh(**axis_sizes) -> Mesh:
    """Convenience: mesh over jax.devices() with given sizes, e.g.
    local_mesh(dp=2, tp=4)."""
    return build_mesh(MeshConfig(**axis_sizes))


def data_axes() -> List[str]:
    """Mesh axes a batch dimension is sharded over."""
    return ["dcn", "dp", "fsdp"]


def num_data_shards(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes() if a in mesh.shape]))
