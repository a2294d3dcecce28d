"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips;
multi-pod: (pod=2, data=16, model=16) = 512 chips.  The ``pod`` axis only
ever carries data-parallel all-reduces (DCN-crossing traffic); TP stays
intra-pod on ICI.
"""

from __future__ import annotations

import math
from typing import Optional

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the model zoo places activations
    with logical ``with_sharding_constraint`` annotations and lets XLA's
    partitioner propagate the rest, which the Explicit axes ``make_mesh``
    defaults to would refuse (an embedding gather over a batch-sharded
    index already asks for an explicit out-sharding)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_map_for(mesh) -> dict:
    """Logical→physical axis binding for the model zoo's annotations."""
    data = data_axes_of(mesh)
    return {
        "batch": data if len(data) > 1 else data[0],
        "model": "model",
        "vocab": "model",
        "expert": "model",
    }


def make_small_mesh(data: int = 1, model: int = 1) -> Optional[object]:
    """Tiny mesh for CPU smoke/integration runs (1 device → None)."""
    n = data * model
    if len(jax.devices()) < n:
        return None
    return _make_mesh((data, model), ("data", "model"))


def make_serve_mesh(batch: int, model: int = 1) -> Optional[object]:
    """Serving mesh for batch-of-requests traffic on whatever is available.

    The data axis takes the largest divisor of ``batch`` that fits the
    devices left after TP (requests shard evenly, no padding); returns
    ``None`` when that degenerates to a single device — serving then runs
    unsharded, the same no-op path the model zoo's annotations take
    outside a mesh."""
    avail = len(jax.devices())
    if model < 1 or avail < model:
        return None
    data = math.gcd(max(batch, 1), avail // model)
    if data * model <= 1:
        return None
    return _make_mesh((data, model), ("data", "model"))


def degraded_serve_mesh(batch: int, lost: int, model: int = 1
                        ) -> Optional[object]:
    """Serving mesh after losing ``lost`` devices (elastic re-mesh).

    The straggler/fault path: :func:`repro.runtime.fault.elastic_remesh`
    proposes the largest (data × model) shape the survivors sustain — TP
    degree pinned, data parallelism shrunk — and the mesh is built over an
    explicit device subset (the survivors; here simply the first ``avail``
    devices, since a real deployment passes the cordon list).  Raises
    ``ValueError`` when the survivors cannot sustain the TP degree;
    returns ``None`` when the proposal degenerates to one device, the
    same unsharded path :func:`make_serve_mesh` takes."""
    import numpy as np

    from repro.runtime.fault import elastic_remesh

    devices = jax.devices()
    avail = len(devices) - lost
    if avail < 1:
        raise ValueError(f"lost {lost} of {len(devices)} devices: "
                         "nothing left to serve on")
    data, model = elastic_remesh(avail, model)
    data = math.gcd(max(batch, 1), data)
    if data * model <= 1:
        return None
    grid = np.array(devices[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))
