"""Job-arrival traces (paper Sec. V-A).

The paper drives its evaluation with the production rate of Facebook's Hadoop
cluster — 350K jobs/month — and models slot-level arrivals as Poisson, citing
the measurement study that validated the Poisson assumption.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

#: Facebook Hadoop production trace rate used by the paper.
FACEBOOK_MONTHLY_JOBS = 350_000

#: Minutes per month used to convert the monthly rate (30-day month).
_MINUTES_PER_MONTH = 30 * 24 * 60


def rate_per_slot(slot_minutes: float, monthly_jobs: float = FACEBOOK_MONTHLY_JOBS) -> float:
    """Poisson rate per slot for a given slot length (paper: 5-minute slots)."""
    return monthly_jobs * slot_minutes / _MINUTES_PER_MONTH


def poisson_arrivals(
    key: Array,
    t_slots: int,
    k_types: int,
    lam: float | Array,
    a_max: float | None = None,
) -> Array:
    """(T, K) Poisson arrival counts, optionally truncated at A_max.

    The paper assumes a finite A^k_max exists; truncation (rare for the
    defaults: P[X > 3*lam] ~ 1e-9) enforces it so the Lemma-1 constant B is
    finite and testable.
    """
    lam_arr = jnp.broadcast_to(jnp.asarray(lam, jnp.float32), (k_types,))
    draws = jax.random.poisson(key, lam_arr, (t_slots, k_types)).astype(jnp.float32)
    if a_max is not None:
        draws = jnp.minimum(draws, a_max)
    return draws


def admission_split(
    arrivals: Array, admit_max: float | Array | None
) -> tuple[Array, Array]:
    """Per-class per-slot admission control: (admitted, rejected).

    The serving front end caps each class's per-slot intake at
    ``admit_max`` (scalar broadcasts over classes; a (K,) array gives
    per-class caps; ``None`` admits everything). Rejected mass is load
    shed at the door — it never enters a queue and is never billed —
    and the split is exact: ``arrivals == admitted + rejected``
    elementwise, the conservation identity the serving tests pin.
    """
    arrivals = jnp.asarray(arrivals, jnp.float32)
    if admit_max is None:
        return arrivals, jnp.zeros_like(arrivals)
    cap = jnp.broadcast_to(
        jnp.asarray(admit_max, jnp.float32), arrivals.shape[-1:]
    )
    admitted = jnp.minimum(arrivals, cap[None, :])
    return admitted, arrivals - admitted


def serve_rate_tables(
    rates, shares, mu_headroom: float = 1.0
) -> tuple[np.ndarray, np.ndarray]:
    """Inverse-CDF tables for a serving front end's (arrivals, capacity).

    Args:
        rates: (K,) per-class Poisson request rates (jobs/slot).
        shares: (N,) per-pod capacity shares; pod i's per-class service
            rate is ``shares[i] * sum(rates) / K * mu_headroom`` — the
            same straggler-noise model the original ``FleetEngine`` drew
            per slot with ``np.random``, now precomputed so the whole
            horizon is ONE batched ``searchsorted``
            (:func:`poisson_pair_from_tables`).
        mu_headroom: fleet capacity / offered load multiplier.

    Returns:
        (arr_cdf (K, M+1), mu_cdf (N, K, M+1)) float32 CDF tables sharing
        one truncation width M (Poisson tails beyond mean + 8·sqrt(mean)
        are below ~1e-9 — the finite-A_max premise of Lemma 1).
    """
    rates = np.asarray(rates, np.float64)
    shares = np.asarray(shares, np.float64)
    k = rates.shape[0]
    mu_mean = shares[:, None] * rates.sum() / k * mu_headroom * np.ones((1, k))
    top = max(float(rates.max()), float(mu_mean.max()), 1.0)
    m = int(np.ceil(top + 8.0 * np.sqrt(top) + 8.0))
    return poisson_table(rates, m), poisson_table(mu_mean, m)


# ---------------------------------------------------------------------------
# Fast exact Poisson via inverse-CDF tables (EXPERIMENTS.md §Perf v4).
#
# jax.random.poisson's transformed-rejection sampler dominated the Monte-
# Carlo engine's wall time on XLA:CPU (~97%). The rates here are STATIC per
# configuration, so inverse-CDF sampling from a precomputed table is exact
# (the distribution is already truncated at A_max by the model) and turns
# 1.4M rejection loops into one vectorized table lookup (_inverse_cdf).
# ---------------------------------------------------------------------------

def _count_below(tables: Array, u: Array) -> Array:
    """(B, T) int32 count of each table's entries strictly below each draw.

    For non-decreasing, finite, non-negative tables and ``u`` in [0, 1)
    this is bitwise ``searchsorted(side="left")``. The (B, M+1, T) compare
    is never materialised: XLA fuses it into the reduce over the table
    axis. No gather, no loop.
    """
    return jnp.sum(tables[:, :, None] < u[:, None, :], axis=1, dtype=jnp.int32)


def _binary_search(tables: Array, u: Array) -> Array:
    """(B, T) int32 ``searchsorted(side="left")`` of each row's draws."""
    return jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="left"))(tables, u)


def _inverse_cdf(tables: Array, u: Array) -> Array:
    """(B, T) int32 inverse-CDF lookup of draws u (B, T) in tables (B, M+1).

    The lowering follows the platform compiled for (EXPERIMENTS.md §Perf
    v5, v14). On XLA:CPU the binary search (ceil(log2(M+2)) steps, 8 at 129
    columns) beats the compare over all M+1 entries 8-10x. On the TPU each
    search step is a data-dependent gather, the chip's weakest op, and the
    fused compare-and-count is ~80x faster (TPU v5e, facebook_4dc's tables
    over 1000 runs: 1.05 ms against 87.7 ms). Both return the same counts,
    bit for bit.
    """
    return jax.lax.platform_dependent(
        tables, u, tpu=_count_below, default=_binary_search
    )


def poisson_table(lam, max_value: int) -> np.ndarray:
    """(..., max_value+1) float32 CDF table(s) for static rate(s) ``lam``.

    Computed in float64 numpy at trace-build time (outside jit).
    """
    import scipy.special

    lam = np.asarray(lam, np.float64)[..., None]            # (..., 1)
    k = np.arange(max_value + 1, dtype=np.float64)
    logpmf = k * np.log(np.maximum(lam, 1e-300)) - lam - scipy.special.gammaln(k + 1)
    cdf = np.cumsum(np.exp(logpmf), axis=-1)
    cdf = cdf / cdf[..., -1:]                                # renormalize truncation
    return cdf.astype(np.float32)


def poisson_pair_from_tables(
    key_arr: Array,
    key_mu: Array,
    arr_cdf: Array,
    mu_cdf: Array,
    t_slots: int,
) -> tuple[Array, Array]:
    """Draw one run's (arrivals, mu) traces in ONE batched table lookup.

    §Perf v6: the per-run Monte-Carlo build used to run two separate
    ``searchsorted`` binary-search loops (arrivals' K tables, then mu's
    N·K tables) — two compiled while-loops per run on XLA:CPU. The tables
    share one truncation width, so both lookups batch into a single
    :func:`_inverse_cdf` over K + N·K rows. The uniform draws are bitwise the
    ones :func:`poisson_from_table` would consume (same keys, same
    shapes), so the realized traces are unchanged — this is purely a
    launch-count optimization.

    Args:
        key_arr / key_mu: the PRNG keys the two separate calls would use.
        arr_cdf: (K, M+1) arrival CDF tables.
        mu_cdf: (N, K, M+1) service-rate CDF tables (same M as arr_cdf).
        t_slots: T.

    Returns:
        (arrivals (T, K), mu (T, N, K)) float32 counts.
    """
    k_types = arr_cdf.shape[0]
    n, k2, m1 = mu_cdf.shape
    if arr_cdf.shape[-1] != m1:
        # Different truncation widths (e.g. fleet_256's a_max != mu_max):
        # pad the narrower CDF with trailing 1.0s — a monotone CDF padded
        # at 1.0 returns identical inverse-CDF counts for u in [0, 1).
        m1 = max(arr_cdf.shape[-1], m1)
        arr_cdf = jnp.pad(
            arr_cdf, ((0, 0), (0, m1 - arr_cdf.shape[-1])),
            constant_values=1.0,
        )
        mu_cdf = jnp.pad(
            mu_cdf, ((0, 0), (0, 0), (0, m1 - mu_cdf.shape[-1])),
            constant_values=1.0,
        )
    u_arr = jax.random.uniform(key_arr, (t_slots, k_types))        # (T, K)
    u_mu = jax.random.uniform(key_mu, (t_slots, n, k2))            # (T, N, K)
    tables = jnp.concatenate(
        [arr_cdf.reshape(-1, m1), mu_cdf.reshape(-1, m1)], axis=0
    )                                                              # (K+NK, M+1)
    u = jnp.concatenate(
        [u_arr.reshape(t_slots, -1).T, u_mu.reshape(t_slots, -1).T], axis=0
    )                                                              # (K+NK, T)
    out = _inverse_cdf(tables, u)
    arrivals = out[:k_types].T.astype(jnp.float32)                 # (T, K)
    mu = out[k_types:].T.reshape(t_slots, n, k2).astype(jnp.float32)
    return arrivals, mu


def poisson_from_table(key: Array, cdf: Array, shape: tuple) -> Array:
    """Exact truncated-Poisson draws via inverse CDF (:func:`_inverse_cdf`).

    Args:
        key: PRNG key.
        cdf: (..., M+1) tables; leading dims must equal ``shape``'s trailing
            dims (e.g. cdf (N, K, M+1) with shape (T, N, K)).
        shape: output shape (leading axis = time/slot axis).
    Returns: float32 counts in [0, M].
    """
    u = jax.random.uniform(key, shape)
    batch_dims = cdf.shape[:-1]
    m1 = cdf.shape[-1]
    # Flatten the table batch (a single table is a batch of one); move the
    # time axis last so each table looks up its own draw vector.
    t_axes = len(shape) - len(batch_dims)
    cdf_flat = cdf.reshape(-1, m1)                              # (B, M+1)
    u_moved = jnp.moveaxis(
        u.reshape(shape[:t_axes] + (-1,)), -1, 0
    ).reshape(-1, *shape[:t_axes])                              # (B, T...)
    out = _inverse_cdf(
        cdf_flat, u_moved.reshape(cdf_flat.shape[0], -1)
    )                                                           # (B, prod(T))
    out = out.reshape((-1,) + shape[:t_axes])                   # (B, T...)
    out = jnp.moveaxis(out, 0, -1).reshape(shape)
    return out.astype(jnp.float32)
