"""Mesh construction: the one place the training path builds a mesh.

A function — never a module-level constant — so importing this module does
not touch jax device state.  Mesh axes:
  pod   : inter-pod boundary (slow DCI fabric)  [multi-pod only]
  data  : ADMM-worker / data-parallel axis (intra-pod ICI)
  model : tensor-parallel axis (intra-pod ICI, minor-most = fastest links)

Every axis is ``AxisType.Auto``: the engine places state with
``NamedSharding`` and lets GSPMD propagate the rest, so model code carries
no ``out_sharding=`` annotations.  (``jax.make_mesh`` defaults to
``Explicit`` axes, under which the first gather of a round raises
``ShardingTypeError``.)
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, data: Optional[int] = None,
                   devices: Optional[Sequence] = None):
    """Small (data, model) mesh over ``devices`` (default: every local
    device)."""
    devices = list(devices) if devices is not None else jax.devices()
    data = data or (len(devices) // model)
    return _mesh((data, model), ("data", "model"),
                 devices=devices[:data * model])
