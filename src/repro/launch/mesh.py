"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run pins its platform before first init.

Every axis is ``AxisType.Auto``: the repo's sharded code places arrays with
``NamedSharding``/``jit(in_shardings=...)`` inside ``jax.set_mesh`` and lets
GSPMD propagate the rest, which is what Auto axes mean.  (``jax.make_mesh``
defaults to Explicit axes, whose sharding-in-types rules that code does not
follow.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with Auto axes, over ``devices`` (default: all)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small (data, model) mesh over the first data*model devices."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} "
                         f"devices; this process has {n}")
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[: data * model])
