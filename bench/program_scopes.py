"""Attribute device ops to the program's own layer names.

The engines open a ``jax.named_scope`` at their own call site of each
layer, and each Pallas kernel carries a ``name=`` (``repro.telemetry.scopes``
in the program; the names are repeated here because the benchmark imports
nothing of ``src/``). XLA keeps them in each op's name stack, which the
trace shows as ``tf_op``: ``.../vmap(mc_draws)/vmap(bench_tracegen)/...``.

An op belongs to the *innermost* program name in its stack, so the kernel's
op lands in ``gmsa_score`` although it runs inside ``gmsa_decide``, and the
recovery epoch's rule in ``placed_rule`` although it runs inside
``placed_recovery``. A stack component counts as a name once the
transformations that wrap it (``vmap(...)``, ``jvp(...)``) are stripped; a
``jit(...)`` is a function, never a name. ``while`` and ``conditional`` ops
keep the scope ``trace_reduce`` gives them, the common prefix of the ops
nested in them. The names are disjoint, so the self times of the ops under
each, with those under none (``UNSCOPED``), add up to the device's busy time.
"""

from __future__ import annotations

import re

#: The program's layer names (``repro.telemetry.scopes``).
DRAWS = "mc_draws"
SCAN = "gmsa_scan"
DECIDE = "gmsa_decide"
EPOCHS = "placed_epochs"
RULE = "placed_rule"
RECOVERY = "placed_recovery"
KERNEL = "gmsa_score"
NAMES = (DRAWS, SCAN, DECIDE, EPOCHS, RULE, RECOVERY, KERNEL)
#: The bucket of ops under no program name.
UNSCOPED = ""

_WRAPPED = re.compile(r"(\w+)\((.*)\)")


def _name(component: str) -> str | None:
    """The named scope a name-stack component holds, or None."""
    part = component.rstrip(":")
    while True:
        m = _WRAPPED.fullmatch(part)
        if m is None:
            return part
        if m.group(1) in ("jit", "pjit"):
            return None
        part = m.group(2)


def innermost(scope: str) -> str:
    """The innermost program name in ``scope`` (an op's ``tf_op``), or
    ``UNSCOPED``."""
    found = UNSCOPED
    for component in scope.split(";")[0].split("/"):
        if _name(component) in NAMES:
            found = _name(component)
    return found


def ms_per_call(trace, cell, name: str) -> float | None:
    """Device ms per call of the ops whose innermost program name is
    ``name``: self time, averaged over the chips. None where no op of the
    trace carries any program name (a program without them) or none
    carries ``name``."""
    if not cell["calls"] or not any(innermost(op.scope) for _, op in trace.ops):
        return None
    s = trace.self_seconds(lambda scope: innermost(scope) == name)
    if s <= 0:
        return None
    return s * 1e3 / cell["calls"]
