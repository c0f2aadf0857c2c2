"""Mesh construction.

Single-pod: (16, 16) = 256 v5e chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
``"pod"`` axis is the paper's cloud-partition axis: cheap ICI inside a pod,
scarce inter-pod links across it, synchronized by the strategies in
``repro.core.sync``.

Every axis is ``AxisType.Auto``: the sharding rules place arrays with
``NamedSharding`` and leave the rest to the SPMD partitioner (newer JAX
defaults ``jax.make_mesh`` to ``Explicit`` axes, under which the model's
gathers fail to type-check).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

POD_AXES = ("pod", "data", "model")


def _mesh(shape: Sequence[int], axes: Sequence[str],
          devices: Optional[Sequence] = None) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = POD_AXES if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_pods: int = 2, data: int = 2, model: int = 2) -> Mesh:
    """Small mesh for CPU multi-device tests (8 host devices)."""
    if n_pods > 1:
        return _mesh((n_pods, data, model), POD_AXES)
    return _mesh((data, model), ("data", "model"))


def make_pod_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """(pod=len(devices), data=1, model=1) over the devices that are there
    (default: all of ``jax.devices()``): pods are chips, so the sync
    round's ring is a collective-permute over ``"pod"``."""
    devices = list(jax.devices() if devices is None else devices)
    return _mesh((len(devices), 1, 1), POD_AXES, devices=devices)


def mesh_info(mesh: Mesh) -> Dict[str, int]:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return {
        "n_devices": mesh.devices.size,
        "n_pods": sizes.get("pod", 1),
        "data": sizes.get("data", 1),
        "model": sizes.get("model", 1),
    }
