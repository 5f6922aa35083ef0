"""Device-mesh construction.

The reference's parallelism is Kubernetes-level only (SURVEY.md §2.10): Job
``parallelism: 2`` with one GPU per pod, no tensor-level sharding, NCCL never
configured.  The TPU build makes the mesh the center of the design instead:
one ``jax.sharding.Mesh`` with named axes

    ``dp``   — data parallel (across slices / DCN-friendly)
    ``fsdp`` — fully-sharded data parallel (param shards, ICI)
    ``tp``   — tensor parallel (megatron-style, innermost — highest traffic,
               so it gets the fastest ICI ring)
    ``sp``   — sequence/context parallel (ring attention)

Collectives ride whatever physical links the mesh axes map onto; keeping
``tp`` innermost matches `jax.experimental.mesh_utils`' device ordering so
tensor-parallel all-reduces stay on nearest-neighbor ICI.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES: Tuple[str, ...] = ("dp", "fsdp", "tp", "sp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape; -1 on ``dp`` absorbs remaining devices."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int]:
        if -1 in (self.fsdp, self.tp, self.sp):
            raise ValueError("only dp may be -1")
        prod = self.fsdp * self.tp * self.sp
        if self.dp == -1:
            if n_devices % prod:
                raise ValueError(f"{n_devices} devices not divisible by {prod}")
            return (n_devices // prod, self.fsdp, self.tp, self.sp)
        if prod * self.dp != n_devices:
            raise ValueError(
                f"mesh {self.dp}x{self.fsdp}x{self.tp}x{self.sp} != {n_devices} devices"
            )
        return (self.dp, self.fsdp, self.tp, self.sp)


def best_mesh_shape(n_devices: int, tp: int = 1, sp: int = 1, fsdp: Optional[int] = None) -> Tuple[int, int, int, int]:
    """Pick (dp, fsdp, tp, sp) for ``n_devices``: given tp/sp, put the rest on
    fsdp by default (params sharded, the common LLM-training choice)."""
    rest = n_devices // (tp * sp)
    if rest * tp * sp != n_devices:
        raise ValueError(f"tp*sp={tp*sp} does not divide {n_devices}")
    if fsdp is None:
        return (1, rest, tp, sp)
    if rest % fsdp:
        raise ValueError(f"fsdp={fsdp} does not divide {rest}")
    return (rest // fsdp, fsdp, tp, sp)


def data_parallel_size(mesh: Optional[Mesh]) -> int:
    """dp×fsdp ways of a mesh — the number of batch shards GSPMD will cut.
    Single source of truth for batch-padding (server) and divisibility
    checks (pipeline); 0 when ``mesh`` is None."""
    if mesh is None:
        return 0
    axes = [a for a in ("dp", "fsdp") if a in mesh.axis_names]
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 0


def build_mesh(
    shape: Optional[Sequence[int]] = None,
    *,
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Sequence[str] = AXES,
) -> Mesh:
    """Build a Mesh over all (or given) devices.

    ``mesh_utils.create_device_mesh`` maps the logical axes onto the
    physical torus on TPU backends and is a plain reshape elsewhere (the
    virtual-device CPU tests, where there is no topology to exploit).  A
    device set it cannot lay out raises: a mesh in arbitrary order would
    run, with tp collectives off nearest-neighbour ICI and nobody told.
    """
    from jax.experimental import mesh_utils

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if shape is None:
        shape = (config or MeshConfig()).resolve(n)
    shape = tuple(int(s) for s in shape)
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, tuple(axis_names))
