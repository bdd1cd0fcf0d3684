"""Unrolled threefry lowering on CPU (EXPERIMENTS.md §Perf v6).

jax lowers ``threefry2x32`` — the bit generator behind every
``jax.random`` call — as a *rolled* ``fori_loop`` over the 5 round-groups
on CPU (a compile-size tradeoff) and *unrolled* everywhere else. Both
lowerings compute the identical function (bitwise-equal streams — pinned
in tests/test_simulator.py), but on the CPU thunk executor the rolled
form costs a full while-loop execution (~5 x several kernel launches) per
``random.uniform`` / ``random.split`` call, which dominated the
Monte-Carlo trace builds (~25% of a simulated run).

:func:`enable_unrolled_threefry_cpu` re-registers jax's own unrolled rule
for the CPU platform — no custom math, just the other of jax's two
lowerings, ~4x faster bit generation here. Called at ``repro`` import;
set ``REPRO_ROLLED_THREEFRY=1`` to keep jax's default, and any failure to
reach the (internal, version-pinned: jax 0.9.0) registration APIs degrades
silently to that default.
"""

from __future__ import annotations

import os

_INSTALLED = False


def enable_unrolled_threefry_cpu() -> bool:
    """Swap CPU threefry to jax's unrolled lowering. Returns success."""
    global _INSTALLED
    if _INSTALLED:
        return True
    if os.environ.get("REPRO_ROLLED_THREEFRY"):
        return False
    try:
        from jax._src import prng as _prng
        from jax._src.interpreters import mlir as _mlir

        _mlir.register_lowering(
            _prng.threefry2x32_p,
            _prng._threefry2x32_lowering_rule,   # the unrolled rule
            platform="cpu",
        )
        _INSTALLED = True
        return True
    except Exception:  # pragma: no cover - newer jax moved the internals
        return False
