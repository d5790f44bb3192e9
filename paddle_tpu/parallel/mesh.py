"""Device mesh construction and axis conventions.

Replaces the reference's device topology plumbing: trainer_count GPU
threads (gserver/gradientmachines/MultiGradientMachine.h:168), pserver
shard maps (pserver/ParameterServer2.h:74-90), and etcd membership
(go/pserver/etcd_client.go). On TPU the topology is a jax.sharding.Mesh
over ICI; axis names are the vocabulary the rest of the framework uses:

  dp — data parallel (batch)            ≙ trainer_count / num trainers
  mp — model parallel (sharded params)  ≙ pserver parameter blocks
  sp — sequence parallel (long context) — parallel/ring_attention.py
  pp — pipeline stages                  ≙ ParallelNeuralNetwork device attr
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DP, MP, SP, PP = "dp", "mp", "sp", "pp"

# the full textual vocabulary — parse_mesh_spec rejects anything else so
# a typo ("ddp8") fails at the CLI instead of producing a mesh whose axis
# no sharding rule ever matches (silently replicated everything)
KNOWN_AXES = (DP, MP, SP, PP)


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (DP,),
    devices=None,
) -> Mesh:
    """Build a Mesh. Default: all local devices on one `dp` axis."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),)
    if int(np.prod(shape)) != len(devices):
        raise ValueError(f"mesh shape {shape} != {len(devices)} devices")
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def parse_mesh_spec(spec: str) -> Tuple[Tuple[str, int], ...]:
    """"dp4,pp2" -> (("dp", 4), ("pp", 2)) — the textual mesh vocabulary
    shared by `cli serve --mesh` and `cli train --mesh`. Axis names are restricted to KNOWN_AXES."""
    import re

    axes = []
    for part in filter(None, spec.split(",")):
        m = re.fullmatch(r"([a-z]+)(\d+)", part.strip())
        if not m:
            raise ValueError(
                f"bad mesh axis {part!r}; want e.g. dp4 or pp2")
        name, size = m.group(1), int(m.group(2))
        if name not in KNOWN_AXES:
            raise ValueError(
                f"unknown mesh axis {name!r} in {part!r}; "
                f"known axes: {', '.join(KNOWN_AXES)}")
        if size < 1:
            raise ValueError(f"mesh axis {part!r} must have size >= 1")
        if any(a == name for a, _ in axes):
            raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
        axes.append((name, size))
    if not axes:
        raise ValueError(f"empty mesh spec {spec!r}")
    return tuple(axes)


def mesh_from_spec(spec: str, devices=None) -> Mesh:
    """Build a Mesh from "dp2,mp4" over a PREFIX of the device list (a
    serving replica may own fewer chips than the host exposes; training
    takes them all by passing an exact-size device list)."""
    axes = parse_mesh_spec(spec)
    need = int(np.prod([n for _, n in axes]))
    devices = list(devices if devices is not None else jax.devices())
    if need > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {need} devices, have {len(devices)}")
    return make_mesh(
        shape=tuple(n for _, n in axes),
        axis_names=tuple(a for a, _ in axes),
        devices=devices[:need],
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharded(mesh: Mesh, axis: str = DP, ndim: int = 2) -> NamedSharding:
    """Shard dim 0 (batch) over `axis`, replicate the rest."""
    return NamedSharding(mesh, PartitionSpec(axis, *([None] * (ndim - 1))))


def dim_sharded(mesh: Mesh, dim: int, axis: str, ndim: int) -> NamedSharding:
    spec = [None] * ndim
    spec[dim] = axis
    return NamedSharding(mesh, PartitionSpec(*spec))
