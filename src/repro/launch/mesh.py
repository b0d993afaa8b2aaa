"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state -- the dry-run sets
``--xla_force_host_platform_device_count=512`` *before* first jax init and
everything else sees the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis of type ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which indexing a
    sharded array and ``with_sharding_constraint`` raise
    ``ShardingTypeError``.  Every mesh in this repo feeds ``shard_map``
    bodies and GSPMD sharding rules written for ``Auto`` axes.
    """
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across two pods.

    Axes: ``data`` carries DP + FSDP, ``model`` carries TP / EP / SP, and
    ``pod`` (multi-pod only) carries pure data parallelism whose gradient
    reduction crosses the inter-pod links -- scaling pods never changes
    layer math (DESIGN.md S6).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many (host) devices exist -- for tests."""
    return auto_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
