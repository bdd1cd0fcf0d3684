"""Host-device mesh for sharding the Monte-Carlo ``runs`` axis.

Every ``*_many`` / ``sweep_*`` entry point replicates one simulation over a
(n_runs,) axis of PRNG keys. This module maps that axis across devices with
``jax.shard_map``:

* :func:`ensure_host_devices` — the ``XLA_FLAGS`` bootstrap idiom
  (``--xla_force_host_platform_device_count=8``): one process, eight CPU
  "pod" devices, CI-reproducible. Must run **before** jax initializes its
  backends; it raises loudly when called too late instead of letting the
  flag be ignored silently.
* :func:`runs_mesh` — a 1-D ``Mesh`` over host devices with axis ``"runs"``.
* :func:`sharded_runs` — ``vmap(one)(keys)`` partitioned over that mesh.

Determinism contract: the (n_runs,) key array is computed exactly as in the
single-device path (one ``jax.random.split`` at the entry point) and then
merely *laid out* across devices — no per-device folding enters the key
stream, and each run's trace build + simulation is elementwise in the runs
axis. Sharded outputs therefore match the single-device vmap per run at
every device count (pinned by ``tests/test_sharded.py``): bitwise, except
where XLA rounds a float sum differently at a different per-device batch.

Non-divisible ``n_runs`` pads the key axis by repeating the leading keys up
to a device multiple and slices the padding back off, so downstream
summaries see exactly the real runs — never a truncation, never a crash.
"""

from __future__ import annotations

import os
import re
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import Mesh, PartitionSpec as P

__all__ = [
    "RUNS_AXIS",
    "ensure_host_devices",
    "host_platform_flag",
    "runs_mesh",
    "sharded_runs",
]

#: Mesh axis name carrying the Monte-Carlo runs dimension.
RUNS_AXIS = "runs"

_FLAG = "--xla_force_host_platform_device_count"


def host_platform_flag(n_devices: int) -> str:
    """The XLA flag splitting the host CPU into ``n_devices`` devices."""
    return f"{_FLAG}={int(n_devices)}"


def ensure_host_devices(n_devices: int) -> int:
    """Request ``n_devices`` host CPU devices; must run before backend init.

    Installs ``--xla_force_host_platform_device_count=n`` into ``XLA_FLAGS``
    (replacing any previous count). XLA reads the flag once, at backend
    initialization — the first ``jax.devices()`` / jit dispatch — so this
    only works at process entry, before anything touches a device. Called
    too late it raises ``RuntimeError`` (unless the process already has
    enough devices, which is a no-op) rather than silently running on
    however many devices happened to exist.

    Returns the device count that will be (or already is) available.
    """
    n_devices = int(n_devices)
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        have = jax.device_count()
        if have >= n_devices:
            return have
        raise RuntimeError(
            f"jax backends already initialized with {have} device(s); "
            f"set XLA_FLAGS={host_platform_flag(n_devices)} (or call "
            "ensure_host_devices) before the first jax.devices()/jit "
            "dispatch — e.g. at process entry, before importing modules "
            "that touch jax device state."
        )
    flags = os.environ.get("XLA_FLAGS", "")
    stripped = re.sub(rf"{_FLAG}=\d+", "", flags).strip()
    sep = " " if stripped else ""
    os.environ["XLA_FLAGS"] = f"{stripped}{sep}{host_platform_flag(n_devices)}"
    return n_devices


def runs_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over host devices with Monte-Carlo axis ``"runs"``.

    ``n_devices=None`` takes every available device; an explicit count
    takes the first ``n_devices`` (raising if the process has fewer —
    see :func:`ensure_host_devices` for getting more on CPU).
    """
    devices = jax.devices()
    if n_devices is not None:
        n_devices = int(n_devices)
        if n_devices < 1 or n_devices > len(devices):
            raise ValueError(
                f"runs_mesh: asked for {n_devices} device(s) but the "
                f"process has {len(devices)} (hint: ensure_host_devices "
                "before jax initializes, or pass n_devices=None)"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (RUNS_AXIS,))


def sharded_runs(
    one: Callable[[Array], Any], keys: Array, mesh: Mesh
) -> Any:
    """``vmap(one)(keys)`` with the runs axis partitioned over ``mesh``.

    ``keys`` is the (n_runs,) PRNG key array the single-device path would
    vmap over — identical keys, so identical per-run streams. When
    ``n_runs`` is not a device multiple the key axis is padded by
    repeating the leading keys and the surplus rows are sliced off the
    stacked outputs, so every summary downstream weights exactly the real
    run count. Output pytrees keep the leading (n_runs,) axis.
    """
    if RUNS_AXIS not in mesh.shape:
        raise ValueError(
            f"sharded_runs needs a mesh with axis {RUNS_AXIS!r}; got axes "
            f"{tuple(mesh.axis_names)} (build one with runs_mesh())"
        )
    n_runs = keys.shape[0]
    n_dev = mesh.shape[RUNS_AXIS]
    pad = (-n_runs) % n_dev
    if pad:
        keys = jnp.concatenate([keys, keys[:pad]], axis=0)
    body = jax.shard_map(
        lambda ks: jax.vmap(one)(ks),
        mesh=mesh,
        in_specs=P(RUNS_AXIS),
        out_specs=P(RUNS_AXIS),
        check_vma=False,
    )
    outs = body(keys)
    if pad:
        outs = jax.tree_util.tree_map(lambda x: x[:n_runs], outs)
    return outs
