"""Production meshes.  A FUNCTION (not a module-level constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes.

    The sharded stages place their arrays with ``NamedSharding`` and leave
    placement inside ``jit`` to the compiler; ``jax.make_mesh`` defaults
    to Explicit axes, under which plain jitted code that indexes a
    mesh-sharded array (e.g. the alias build on the per-shard totals)
    fails its sharding check."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_data_mesh(data: int = 0):
    """1-D "data" mesh for the sharded KNN pipeline (0 = all devices)."""
    n = len(jax.devices())
    data = n if data <= 0 else min(data, n)
    return make_mesh((data,), ("data",))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))
