"""Mesh construction (single-pod and multi-pod production meshes).

Defined as functions — importing this module never touches jax device
state, so test processes keep their 1-device world unless they opt in.

Production target: TPU v5e pods of 256 chips. Single-pod mesh is
(data=16, model=16); multi-pod adds a leading "pod" axis (2, 16, 16)
whose collectives ride DCN — that is the slow/heterogeneous link where
the HetSeq capacity planner and the compressed hierarchical reduction
earn their keep.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The one mesh constructor: every axis ``Auto``-sharded.

    The step builders place arrays with ``NamedSharding`` +
    ``with_sharding_constraint`` and let XLA propagate the rest, which
    is what ``Auto`` axes mean. (``jax.make_mesh`` defaults to
    ``Explicit`` axes, which turn on sharding-in-types and reject that
    style.) ``devices``: an explicit device list, e.g.
    ``jax.devices()[:n]`` for a sub-mesh; ``None`` lets jax order all
    devices for the physical topology.
    """
    shape, axes = tuple(shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    devs = np.asarray(list(devices)).reshape(shape)
    return Mesh(devs, axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False, pipe: int = 1) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if pipe > 1:
        shape = (pipe,) + shape
        axes = ("pipe",) + axes
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pipe: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — for tests."""
    if pipe > 1:
        return make_mesh((pipe, data, model), ("pipe", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes: ("pod","data") when pod exists.

    Never includes "pipe" — pipeline stages replicate params/batch over
    the pipe axis and exchange only stage-boundary activations, so DP
    collectives (grad reduction, weighting sums) must not span it.
    """
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def pipe_axis(mesh: Mesh) -> Optional[str]:
    return "pipe" if "pipe" in mesh.axis_names else None


def pipe_size(mesh: Mesh) -> int:
    return mesh.shape["pipe"] if "pipe" in mesh.axis_names else 1


def dp_size(mesh: Mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
