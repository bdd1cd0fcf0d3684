"""A configuration that lists its sites' climates and states its load as
jobs per month with fixed capacity shares (the paper's Sec. V-A form)."""

import numpy as np

_MINUTES_PER_MONTH = 30 * 24 * 60


def climates(cfg: dict) -> dict:
    """(N,) arrays of each listed site's climate parameters."""
    rows = cfg["sites"][: cfg["fields"]["n_sites"]]
    return {k: np.asarray([s[k] for s in rows], np.float64) for k in rows[0]
            if k not in ("name", "region")}


def load(cfg: dict) -> tuple:
    """(arrival rate per slot and type, (N,) capacity shares of it)."""
    f = cfg["fields"]
    lam = f["monthly_jobs"] * f["slot_minutes"] / _MINUTES_PER_MONTH
    return lam, np.asarray(f["capacity_shares"], np.float64)
