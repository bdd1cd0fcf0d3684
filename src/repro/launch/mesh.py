"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialization while tests/benches must keep seeing 1 device.

Topology (TPU v5e class):
  * single-pod:  (16, 16)    axes ("data", "model")  — 256 chips
  * multi-pod:   (2, 16, 16) axes ("pod", "data", "model") — 512 chips;
    the "pod" axis is the slow WAN/DCN tier (the paper's core network).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple):
    """A mesh with Auto axes: the model and step code place arrays through
    ``in_shardings``/``out_shardings`` and let the compiler propagate, which
    ``jax.make_mesh``'s default Explicit axes refuse (a reshape or gather of
    a sharded batch then needs an ``out_sharding``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, multi_pod: bool = False):
    """Small mesh over whatever devices exist (tests / subprocess checks)."""
    n = n_devices or len(jax.devices())
    if multi_pod:
        assert n % 2 == 0 and n >= 4
        return _mesh((2, n // 4, 2), ("pod", "data", "model"))
    if n == 1:
        return _mesh((1, 1), ("data", "model"))
    return _mesh((n // 2, 2), ("data", "model"))
