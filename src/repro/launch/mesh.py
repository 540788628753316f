"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required by the dry-run contract.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes. The sharding rules of
    ``distributed.sharding`` are GSPMD hints (``with_sharding_constraint``),
    which only Auto mesh axes take — and ``jax.make_mesh`` defaults to
    Explicit axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``data`` = batch parallelism (ZeRO state sharding rides on it),
    ``model`` = tensor/expert parallelism, ``pod`` = the cross-pod data-
    parallel axis (gradient all-reduce over DCN/ICI-sparse links — kept as a
    distinct axis so cross-pod collectives are visible and compressible).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many (fake or real) local devices exist —
    used by tests (e.g. 8 forced host devices) and the CPU examples."""
    n = len(jax.devices())
    assert n % model == 0
    return auto_mesh((n // model, model), ("data", "model"))
