"""PUE traces (paper Sec. III & V-A).

Facebook publishes near-real-time PUE dashboards for the four sites the paper
simulates; Google computes PUE every 30 seconds. This module synthesizes
dashboard-like traces: a site-specific base (climate-driven: Luleå lowest),
a diurnal cooling swing peaking in local mid-afternoon, and small
measurement noise. A CSV loader mirrors :mod:`repro.traces.price`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.traces.price import SiteSpec, FACEBOOK_SITES


def pue_trace(
    key: Array,
    t_slots: int,
    slot_minutes: float,
    sites: tuple[SiteSpec, ...] = FACEBOOK_SITES,
    start_hour_utc: float = 0.0,
) -> Array:
    """(T, N) synthetic PUE traces (dimensionless, ~1.04-1.12)."""
    hours = start_hour_utc + jnp.arange(t_slots) * (slot_minutes / 60.0)
    base = jnp.asarray([s.base_pue for s in sites], jnp.float32)
    amp = jnp.asarray([s.pue_amp for s in sites], jnp.float32)
    off = np.asarray([s.utc_offset_h for s in sites], np.float32)

    # Cooling load peaks mid-afternoon local time (15:00).
    diurnal = jnp.stack(
        [jnp.cos(2.0 * jnp.pi * (hours + float(o) - 15.0) / 24.0) for o in off],
        axis=1,
    )
    noise = 0.004 * jax.random.normal(key, (t_slots, len(sites)))
    trace = base[None, :] + amp[None, :] * diurnal + noise
    return jnp.maximum(trace, 1.0)  # PUE >= 1 by definition
