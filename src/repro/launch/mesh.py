"""Mesh construction: every mesh in the repo is built here.

The sharding design is GSPMD's: activations get ``with_sharding_constraint``
hints and the Pallas kernels run under ``jax.shard_map``.  Both need mesh
axes of type ``Auto``; JAX 0.9's ``jax.make_mesh`` defaults to ``Explicit``
axes, under which a constraint becomes an assertion and an einsum that
contracts a sharded axis raises.  So meshes are built with ``make_mesh``
below, never with ``jax.make_mesh`` directly.

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as functions so importing this module never touches jax device
state (the dry-run launcher sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]
              ) -> jax.sharding.Mesh:
    """A mesh of the process's devices with ``Auto`` axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]) -> AbstractMesh:
    """The device-free counterpart of ``make_mesh`` (for sharding-rule
    checks at sizes no host has)."""
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, data: int = 1, model: int = 1):
    """Tiny mesh for CPU integration tests (needs data*model <= #devices)."""
    return make_mesh((data, model), ("data", "model"))


# hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
