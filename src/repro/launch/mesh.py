"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Every axis is ``AxisType.Auto``: the GSPMD specs and the shard_map
executors in this repo leave sharding propagation to the compiler.
(``jax.make_mesh`` otherwise defaults every axis to ``Explicit``.)

Functions, not module-level constants — importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Mesh of ``shape`` over ``axes`` (all Auto), on ``devices`` when
    given (their count must equal the mesh size), else on all devices."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
