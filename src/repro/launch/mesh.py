"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init;
smoke tests must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: shardings are layouts the compiler
    propagates, which is what the step functions and sharding rules here are
    written for.  (``jax.make_mesh`` itself defaults to Explicit axes, under
    which un-annotated ops such as ``jnp.take`` on a sharded operand raise.)"""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
