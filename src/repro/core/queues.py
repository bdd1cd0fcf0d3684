"""Queueing law of the GDA service engine (paper Eq. 1).

Each global-manager DC maintains one queue of unfinished jobs per job type.
Per slot, the backlog evolves as

    Q_i^k(t+1) = max[ Q_i^k(t) + f_i^k(t) A^k(t) - mu_i^k(t), 0 ].

All functions are pure, jit-safe, and operate on the shared (N, K) layout
documented in :mod:`repro.core`.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import Array


def queue_step(q: Array, f: Array, arrivals: Array, mu: Array) -> Array:
    """One application of the queueing law (Eq. 1).

    Args:
        q: (N, K) current backlogs.
        f: (N, K) dispatch fractions for this slot (columns sum to 1).
        arrivals: (K,) job arrivals A^k(t) in this slot.
        mu: (N, K) service rates mu_i^k(t) in this slot.

    Returns:
        (N, K) backlogs at the start of slot t+1.
    """
    return jnp.maximum(q + f * arrivals[None, :] - mu, 0.0)


def total_backlog(q: Array) -> Array:
    """Aggregate backlog sum_{i,k} Q_i^k — the quantity bounded by Eq. 2."""
    return jnp.sum(q)


def lyapunov(q: Array) -> Array:
    """Quadratic Lyapunov function L(t) = 1/2 * sum_{i,k} Q_i^k(t)^2."""
    return 0.5 * jnp.sum(jnp.square(q))
