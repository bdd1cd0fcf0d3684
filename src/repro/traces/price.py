"""Electricity-price traces (paper Sec. V-A).

The paper uses real electricity prices "obtained from publicly available
government agencies" for the four Facebook DC regions. Those exact CSVs are
not redistributable, so this module provides:

  * a *calibrated synthesizer*: per-site diurnal price curves with realistic
    base levels (EIA state-level industrial rates for OR / NC / IA, Nord Pool
    area price for Luleå SE1), timezone-shifted diurnal swing, weekly
    modulation and AR(1) noise — the statistical shape GMSA exploits;
  * a CSV loader with the same output contract, for plugging in real traces.

Prices are in $/MWh. ``omega_j(t)`` in the paper is a *weight*; using $/MWh
directly with P^k = 1 MWh-equivalent per job reproduces the paper's cost
scale (hundreds of dollars per slot at ~40 jobs/slot).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array


@dataclasses.dataclass(frozen=True)
class SiteSpec:
    """Static description of one DC site's price/PUE climate."""

    name: str
    region: str
    utc_offset_h: float       # local-time shift for the diurnal component
    base_price: float         # $/MWh mean industrial price
    diurnal_amp: float        # peak-to-mean diurnal swing ($/MWh)
    noise_std: float          # AR(1) innovation std ($/MWh)
    base_pue: float           # site mean PUE (Facebook dashboards ~1.07-1.10)
    pue_amp: float            # diurnal PUE swing (cooling load)


#: The four Facebook DCs of the paper's evaluation. Relative price levels from
#: public EIA / Nord Pool ranges (Luleå cheapest, ForestCity priciest); PUE
#: levels from Facebook's public dashboards. The absolute scale is calibrated
#: so the baselines' time-average slot cost lands at the paper's ≈$750
#: (Fig. 6(a)) given 40.5 jobs/slot and P^k = 1 — see EXPERIMENTS.md
#: §Calibration.
FACEBOOK_SITES: tuple[SiteSpec, ...] = (
    SiteSpec("Prineville", "Oregon, US", -8.0, 15.98, 3.76, 0.8, 1.078, 0.02),
    SiteSpec("ForestCity", "North Carolina, US", -5.0, 24.44, 5.64, 1.0, 1.082, 0.03),
    SiteSpec("Lulea", "Sweden (SE1)", 1.0, 9.87, 3.29, 1.2, 1.046, 0.01),
    SiteSpec("Altoona", "Iowa, US", -6.0, 18.33, 4.70, 0.9, 1.071, 0.025),
)


def _diurnal(hours_utc: Array, utc_offset: float, phase_peak_h: float = 17.0) -> Array:
    """Unit diurnal curve peaking at local ``phase_peak_h`` (evening peak)."""
    local = hours_utc + utc_offset
    return jnp.cos(2.0 * jnp.pi * (local - phase_peak_h) / 24.0)


def price_trace(
    key: Array,
    t_slots: int,
    slot_minutes: float,
    sites: tuple[SiteSpec, ...] = FACEBOOK_SITES,
    start_hour_utc: float = 0.0,
) -> Array:
    """(T, N) synthetic electricity-price traces ($/MWh).

    Deterministic given the key; the AR(1) component gives each run's price
    path realistic short-term wiggle while the diurnal/weekly structure is
    shared (as with real market data, where day-ahead structure dominates).
    """
    n = len(sites)
    hours = start_hour_utc + jnp.arange(t_slots) * (slot_minutes / 60.0)   # (T,)
    base = jnp.asarray([s.base_price for s in sites], jnp.float32)
    amp = jnp.asarray([s.diurnal_amp for s in sites], jnp.float32)
    noise_std = jnp.asarray([s.noise_std for s in sites], jnp.float32)
    off = np.asarray([s.utc_offset_h for s in sites], np.float32)

    diurnal = jnp.stack([_diurnal(hours, float(o)) for o in off], axis=1)  # (T, N)
    weekly = 1.0 + 0.03 * jnp.sin(2.0 * jnp.pi * hours[:, None] / (24.0 * 7.0))

    # AR(1) noise, phi = 0.9, stationary init.
    phi = 0.9
    innov = jax.random.normal(key, (t_slots, n)) * noise_std

    def ar_step(prev, inn):
        cur = phi * prev + inn
        return cur, cur

    init = innov[0] / jnp.sqrt(1.0 - phi * phi)
    _, noise = jax.lax.scan(ar_step, init, innov)

    trace = base[None, :] * weekly + amp[None, :] * diurnal + noise
    return jnp.maximum(trace, 1.0)  # prices stay positive
