"""The dispatch-policy contract: what a policy declares, and how engines read it.

A policy is a plain callable ``(key, q, arrivals, mu, e, aux, scalar) -> f``.
What an engine must know about it beyond that signature is one
:class:`PolicySpec`, attached by :func:`declare` and read by :func:`spec_of`.
The spec lives in the function's ``__dict__``, so a ``functools.wraps``
wrapper carries it too; an undeclared callable gets the default spec. This
module is the one place that knows the contract: the aux operand a spec
reads, the stale-ratio guard and the out-of-scan pass of state-independent
policies.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
from jax import Array

from repro.telemetry.scopes import GMSA_DECIDE


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """Everything an engine needs to know about a policy, declared once.

    Attributes:
        keyed: the policy consumes its PRNG key; ``False`` drops the
            per-slot key split (and the key chain) from the scan body.
        state_independent: the decision ignores the backlog, so every slot
            is decided in one vectorized pass before the scan.
        reads: the aux operand: ``"dist"`` the (K, N) dataset distribution,
            ``"wpue"`` the pair ``(data_dist, omega_t * pue_t)``, ``"wpue_r"``
            that pair plus the (K, N, N) ratio tensor in force this slot.
        binds_r: a ratio tensor is bound into the closure; the engines
            refuse it ratios that vary over the run.
        staged: the policy speaks the staged engines' (N, K, S) queues.
        returns: ``"f"`` the decision; ``"flow"`` ``(f, in_stack)`` with the
            per-stage inflows it walked; ``"flow_hedge"`` those plus the
            clone matrix.
    """

    keyed: bool = True
    state_independent: bool = False
    reads: str = "dist"
    binds_r: bool = False
    staged: bool = False
    returns: str = "f"

    def __post_init__(self):
        if self.reads not in ("dist", "wpue", "wpue_r"):
            raise ValueError(f"unknown reads={self.reads!r}")
        if self.returns not in ("f", "flow", "flow_hedge"):
            raise ValueError(f"unknown returns={self.returns!r}")
        if self.binds_r and self.reads == "wpue_r":
            raise ValueError("a policy binding its ratio tensor cannot read r")
        if self.staged and self.reads == "dist":
            raise ValueError("staged policies read (data_dist, wpue): the "
                             "staged engines always hand them the prices")
        if self.returns != "f" and not self.staged:
            raise ValueError("only staged policies return per-stage flows")
        if self.returns != "f" and self.state_independent:
            raise ValueError("flow-returning policies walk the live backlog, "
                             "so they cannot be state-independent")


def declare(fn: Callable, base: PolicySpec = PolicySpec(), **fields) -> Callable:
    """Attach ``base`` with ``fields`` replaced as ``fn``'s spec; returns ``fn``."""
    fn.spec = dataclasses.replace(base, **fields)
    return fn


def spec_of(policy: Callable) -> PolicySpec:
    """The spec ``policy`` declared, or the default for a plain callable."""
    return getattr(policy, "spec", PolicySpec())


def slot_aux(spec: PolicySpec, dist, wpue=None, r=None):
    """The aux operand a policy declared by ``spec`` is handed for one slot."""
    return {"dist": dist, "wpue": (dist, wpue), "wpue_r": (dist, wpue, r)}[
        spec.reads]


def split_aux(spec: PolicySpec, aux) -> tuple:
    """Inverse of :func:`slot_aux`: ``(dist, wpue, r)``, absent parts ``None``."""
    parts = (aux,) if spec.reads == "dist" else tuple(aux)
    return parts + (None,) * (3 - len(parts))


def check_ratios(spec: PolicySpec, r_varying: bool) -> None:
    """Refuse a policy with a bound ratio tensor where the ratios vary."""
    if r_varying and spec.binds_r:
        raise ValueError(
            "policy binds a static (K, N, N) ratio tensor but the ratios "
            "vary over the run (a time-varying (T, K, N, N) trace, or the "
            "placement controller's per-epoch rebuilds) — the kernel would "
            "silently dispatch on stale ratios. Build it with "
            "make_kernel_policy(r=None) so the per-slot r reaches the "
            "kernel through the policy aux."
        )


def decide_all_slots(policy, spec, key, q0, inputs, e_cost, wpue, scalar) -> Array:
    """Every slot's decision of a state-independent policy, in one vmap.

    Slot t gets ``split(key, T)[t]`` and the empty backlog ``q0``;
    ``inputs`` is the run's ``SimInputs`` (``data_dist`` and ``r`` static
    or time-varying), ``wpue`` (T, N) or ``None`` if the policy does not
    read it.
    """
    keys = jax.random.split(key, inputs.arrivals.shape[0])
    dist = inputs.data_dist
    r = inputs.r if spec.reads == "wpue_r" else None

    def call(kk, a, m, e, d, w, rr):
        with jax.named_scope(GMSA_DECIDE):
            return policy(kk, q0, a, m, e, slot_aux(spec, d, w, rr), scalar)

    return jax.vmap(
        call,
        in_axes=(0, 0, 0, 0, 0 if dist.ndim == 3 else None,
                 0 if wpue is not None else None,
                 0 if r is not None and r.ndim == 4 else None),
    )(keys, inputs.arrivals, inputs.mu, e_cost, dist, wpue, r)
