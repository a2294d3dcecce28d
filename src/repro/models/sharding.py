"""Sharding helpers shared by the model zoo.

Models annotate activations with logical axis names; the launch layer binds
them to mesh axes via a context.  Outside a mesh (CPU smoke tests) the
annotations are no-ops, so model code is mesh-agnostic.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _axis_map() -> Optional[dict]:
    return getattr(_state, "axis_map", None)


def bound_mesh():
    """The mesh the current :func:`logical_axis_rules` scope binds to, or
    None.  Code that XLA cannot partition by itself (a Pallas kernel, a
    Mosaic custom call) places itself on it with ``shard_map``."""
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def logical_axis_rules(axis_map: dict, mesh=None):
    """Bind logical axis names → mesh axis names (or None) for the scope.

    ``axis_map`` example: {"batch": ("pod", "data"), "model": "model",
    "seq": None, "vocab": "model", "expert": "model"}.  ``mesh`` is the
    concrete mesh those names live on (see :func:`bound_mesh`).
    """
    prev = _axis_map(), bound_mesh()
    _state.axis_map, _state.mesh = axis_map, mesh
    try:
        yield
    finally:
        _state.axis_map, _state.mesh = prev


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op untethered)."""
    amap = _axis_map()
    if amap is None:
        return x
    spec = P(*[amap.get(a) if a is not None else None for a in logical_axes])
    return jax.lax.with_sharding_constraint(x, spec)


def spec(*logical_axes: Optional[str]) -> P:
    """PartitionSpec from logical names under the current rules (for pjit
    in/out shardings).  Without rules, fully replicated."""
    amap = _axis_map() or {}
    return P(*[amap.get(a) if a is not None else None for a in logical_axes])


def named_sharding(mesh, *logical_axes: Optional[str]):
    """A NamedSharding on ``mesh`` from logical names under the current
    rules — for placing INPUTS (e.g. a serving batch on the data axis)
    rather than constraining intermediates."""
    return jax.sharding.NamedSharding(mesh, spec(*logical_axes))
