"""Production mesh builders (functions, never module-level constants —
importing this module must not touch jax device state)."""
from __future__ import annotations

import math

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh over the first prod(shape) available devices."""
    n = math.prod(shape)
    devs = jax.devices()
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devs[:n],
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_lane_mesh(
    lanes: int | None = None,
    model: int | None = None,
    *,
    multi_pod: bool = False,
):
    """Mesh with a dedicated ``lane`` axis for multi-lane NA (paper §4.2).

    The lane axis carries (semantic graph, dst-block row) work units —
    ``core/multilane.py:multilane_na_sharded`` shard_maps over it — and
    the ``model`` axis carries head/feature dims.  With no sizes given,
    builds the production geometry: 16 lane groups × 16 model chips per
    pod (a leading 2-pod axis when ``multi_pod``).  Explicit sizes serve
    tests and CPU smoke runs (``make_lane_mesh(1, 1)`` on one device).
    """
    if lanes is None and model is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    else:
        shape = ((2,) if multi_pod else ()) + (lanes or 1, model or 1)
    axes = ("pod", "lane", "model") if multi_pod else ("lane", "model")
    return make_mesh(shape, axes)
