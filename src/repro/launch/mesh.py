"""Mesh builders.

Functions (not module-level constants) so importing never touches jax
device state. Single pod: (data=16, model=16) = 256 chips (v5e-256-like).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the 'pod' axis carries
data parallelism (and joins the FSDP axis for the 1T-class models) over DCI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A mesh whose axes are all ``Auto``.

    The sharding rules here are GSPMD-style: parameter and batch placements
    plus ``with_sharding_constraint`` hints, with XLA propagating the rest.
    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which an op whose
    output sharding is ambiguous (the embedding gather of a vocab-sharded
    table by batch-sharded tokens) raises instead of propagating.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
