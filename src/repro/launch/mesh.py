"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see 1 device).
Every mesh uses Auto axis types; activate one with ``jax.set_mesh`` and
enter manual regions with ``jax.shard_map``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_driver_mesh(kind: str = "none"):
    """Kind-dispatch mesh for the serve/train drivers: 'none' = 1x1 host mesh."""
    if kind == "none":
        return _mk_mesh((1, 1), ("data", "model"))
    return make_production_mesh(multi_pod=(kind == "multi"))


def make_serving_mesh(tp: int = 1):
    """(1, tp) mesh for tensor-parallel serving: one replica, `tp` model
    shards. Pass the result to ``ServeEngine(mesh=...)`` /
    ``build_engine_step`` — needs `tp` visible devices (on CPU, force them
    with XLA_FLAGS=--xla_force_host_platform_device_count=N before the
    first jax import)."""
    return _mk_mesh((1, tp), ("data", "model"))


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for multi-device unit tests (8 forced host devices)."""
    return _mk_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
