"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no jax device state. Single pod = 16x16 (256 v5e chips,
axes data x model); multi-pod adds a leading "pod" axis (2 x 256 = 512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires forced host devices)."""
    return _make_mesh(shape, axes)


def make_data_mesh(nshards=None, axis="data"):
    """1-D row-sharding mesh for the distributed join/transfer runtimes:
    `nshards` devices on a single `axis` (default: the largest
    power-of-two device count available — the shuffle partitioner
    requires a power of two)."""
    if nshards is None:
        n = jax.device_count()
        nshards = 1 << (max(n, 1).bit_length() - 1)
    return _make_mesh((nshards,), (axis,))
