"""Mesh construction over the (pod, data, model) axes.

The pod axis is the scarce DCN/optical fabric (the paper's IB analogue);
EP all-to-all is confined to intra-pod axes by construction (DESIGN.md
§5). Defined as functions so importing this module never touches jax
device state (device count is locked at first backend init). Meshes use
``Auto`` axes: GSPMD propagates the shardings the rules declare.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` over ``Auto`` axes (its default is Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def survivor_mesh(mesh: Mesh) -> Mesh:
    """Elastic re-mesh after a node failure (paper §6.1).

    Halves the first data-parallel axis with size > 1 ("pod" before
    "data"), keeping the model/EP axis intact so expert shards and weight
    blocks stay divisible — training resumes on the survivors from the
    last checkpoint with the batch re-sharded over the smaller DP degree.
    Returns the same mesh when no DP axis can shrink (restart in place).
    """
    names = list(mesh.axis_names)
    shape = [mesh.shape[a] for a in names]
    for i, a in enumerate(names):
        if a in ("pod", "data") and shape[i] > 1:
            shape[i] //= 2
            return make_mesh(tuple(shape), tuple(names))
    return mesh
