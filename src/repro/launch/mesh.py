"""Mesh factories.

FUNCTIONS (not module-level state) so importing this module never touches
jax device initialisation.  Production (``launch/dryrun.py`` only): single
pod 16x16 = 256 chips, axes (data, model); multi-pod 2x16x16 = 512 chips,
axes (pod, data, model) — ``pod`` is a second data-parallel axis whose
gradient all-reduce crosses the DCI; nothing else communicates across pods.
The train and serve entry points run on ``make_host_mesh``: the devices
that exist.

Every axis is ``Auto``: GSPMD propagates shardings from the parameter and
activation constraints, which is what the sharding rules are written for
(``jax.make_mesh`` would otherwise give ``Explicit`` axes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1, devices=None):
    """``devices`` (default: all of this host's) as a (data, model) mesh."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    assert n % model_axis == 0, (n, model_axis)
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"),
                      devices)


__all__ = ["make_host_mesh", "make_production_mesh"]
