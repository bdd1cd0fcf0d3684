"""Inter-site bandwidth traces (paper Sec. V-A: 100 Mb/s – 2 Gb/s).

The paper varies core-network<->site bandwidths uniformly in [100 Mb/s, 2 Gb/s]
(per Iridium's setup). Bandwidths feed the Iridium placement layer
(:mod:`repro.core.iridium`) and the service-rate model
(:mod:`repro.traces.datasets`).

Degraded-mode link health lives here too: :func:`link_fault_trace` and
:func:`scheduled_link_fault_trace` produce a ``(T, N, N)`` per-link
health factor in ``[0, 1]`` (1 = nominal, interior = degraded — the
link carries that fraction of its provisioned bandwidth and its traffic
is priced up by the reciprocal — 0 = severed; diagonal pinned to 1).
:mod:`repro.placement.wan` folds it into ``link_price_matrix`` /
``transfer_latency`` / ``evacuation_plan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

#: Paper's bandwidth range, in Gb/s.
BW_MIN_GBPS = 0.1
BW_MAX_GBPS = 2.0


def bandwidth_draw(
    key: Array,
    n_sites: int,
    lo: float = BW_MIN_GBPS,
    hi: float = BW_MAX_GBPS,
) -> tuple[Array, Array]:
    """Draw static (up, down) bandwidths per site, uniform in [lo, hi] Gb/s."""
    k_up, k_down = jax.random.split(key)
    up = jax.random.uniform(k_up, (n_sites,), minval=lo, maxval=hi)
    down = jax.random.uniform(k_down, (n_sites,), minval=lo, maxval=hi)
    return up, down


def link_fault_trace(
    key: Array,
    t_slots: int,
    n_sites: int,
    degrade_prob: float = 0.01,
    recover_prob: float = 0.25,
    sever_frac: float = 0.25,
    min_factor: float = 0.1,
) -> Array:
    """(T, N, N) seeded link-health factor: Markov degrade/recover per link.

    Each nominal directed link i→j independently degrades with
    ``degrade_prob`` per slot; a degrade event severs the link entirely
    (factor 0) with conditional probability ``sever_frac``, otherwise it
    drops to a factor drawn uniform in ``[min_factor, 1)``. A faulted
    link recovers to nominal with ``recover_prob``. The diagonal is
    pinned to 1 (local "transfers" are free and never fault).

    An all-nominal draw is exactly 1.0 everywhere, so degraded pricing
    (``price / health``) stays bit-exact with the nominal WAN bill.
    """
    if not 0.0 < min_factor <= 1.0:
        raise ValueError(f"min_factor={min_factor} must be in (0, 1]")
    keys = jax.random.split(key, t_slots)
    eye = jnp.eye(n_sites, dtype=bool)

    def slot(factor, kk):
        k_on, k_sev, k_cut, k_off = jax.random.split(kk, 4)
        shape = (n_sites, n_sites)
        nominal = factor >= 1.0
        faults = nominal & (jax.random.uniform(k_on, shape) < degrade_prob)
        sev = jax.random.uniform(k_sev, shape, minval=min_factor, maxval=1.0)
        cut = faults & (jax.random.uniform(k_cut, shape) < sever_frac)
        sev = jnp.where(cut, 0.0, sev)
        recovers = (~nominal) & (jax.random.uniform(k_off, shape)
                                 < recover_prob)
        nxt = jnp.where(faults, sev, jnp.where(recovers, 1.0, factor))
        nxt = jnp.where(eye, 1.0, nxt)
        return nxt, nxt.astype(jnp.float32)

    _, health = jax.lax.scan(slot, jnp.ones((n_sites, n_sites)), keys)
    return health                                              # (T, N, N)


def scheduled_link_fault_trace(
    t_slots: int,
    n_sites: int,
    events: list[tuple[int, int, int, int | None, float]],
    symmetric: bool = True,
) -> Array:
    """(T, N, N) link health from (src, dst, start, end, factor) events.

    ``end=None`` means the fault never clears; windows are half-open and
    overlapping windows take the minimum factor. ``symmetric=True``
    (default) applies each event to both directions of the link.
    Validation mirrors ``scheduled_failure_trace``: out-of-range sites,
    self-links, negative ``start``, empty windows, and factors outside
    ``[0, 1]`` all raise.
    """
    health = np.ones((t_slots, n_sites, n_sites), np.float32)
    for src, dst, start, end, factor in events:
        for site in (src, dst):
            if not 0 <= site < n_sites:
                raise ValueError(f"site {site} out of range for N={n_sites}")
        if src == dst:
            raise ValueError(f"self-link {src}->{dst} cannot fault")
        if start < 0:
            raise ValueError(f"start={start} must be >= 0")
        if end is not None and end <= start:
            raise ValueError(f"end={end} must be > start={start} (or None)")
        if not 0.0 <= factor <= 1.0:
            raise ValueError(f"factor={factor} must be in [0, 1]")
        stop = t_slots if end is None else min(end, t_slots)
        pairs = [(src, dst), (dst, src)] if symmetric else [(src, dst)]
        for i, j in pairs:
            health[start:stop, i, j] = np.minimum(
                health[start:stop, i, j], np.float32(factor))
    return jnp.asarray(health)
