"""Mesh construction.

Every mesh of the repo is built by :func:`make_mesh`, with **Auto** axis
types.  (``jax.make_mesh`` defaults to Explicit axes, and then
``with_sharding_constraint`` may name no axis of the mesh, so the step's
activation constraint fails to trace.)

The builders are functions (never module-level constants) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import, and smoke tests/benches must keep seeing the real devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """A mesh over the first ``prod(shape)`` devices, every axis Auto."""
    shape, axis_names = tuple(shape), tuple(axis_names)
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh():
    """The devices present, data-parallel: ``data = device count``,
    ``model = 1`` (one client per chip; the train step is then manual over
    every axis)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


# TPU v5e per-chip constants used by the roofline report (see EXPERIMENTS.md)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
