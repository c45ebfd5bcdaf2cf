"""Mesh construction: every mesh of the repo is built by :func:`make_mesh`.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.  Shapes:

  single pod : (16, 16)      axes ("data", "model")          = 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model")   = 512 chips

The ``pod`` axis is an outer data-parallel dimension (gradient all-reduce
over DCI); ``model`` carries TP/EP/SP collectives over ICI.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    """A mesh whose axes are all ``AxisType.Auto``.

    The model code places activations with ``with_sharding_constraint``,
    which accepts only Auto axes (``jax.make_mesh`` defaults to Explicit
    ones).  ``devices`` pins the mesh to that exact device list, in order
    (a FAR instance's sub-mesh); ``None`` takes all of ``jax.devices()``.
    """
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    arr = np.asarray(devices).reshape(tuple(shape))
    return jax.sharding.Mesh(arr, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_submesh(devices, data: int, model: int, pod: int = 1):
    """Mesh over an explicit device subset (FAR pod-slice instances)."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"), devices)
    return make_mesh((data, model), ("data", "model"), devices)


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
