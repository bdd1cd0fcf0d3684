"""Plain reference of one Monte-Carlo evaluation call.

A straightforward re-statement of the scenario and of the simulation it
replays, written from the paper (arXiv:1708.03184, Sec. IV-V) and from the
configuration files under ``bench/configs``. It imports nothing of the
system under test and takes nothing it made: every trace, table and ratio
is rebuilt here from the configuration's numbers and the run keys. Random
numbers come from ``jax.random`` with the same key stream the call
consumes; everything else is numpy. The per-run uniforms are bitwise the
same on every backend and are drawn on the CPU. The scenario's own draws
(price and PUE noise, links, dataset layouts) are made on the default
device, where the program makes them: a Dirichlet draw's rounding differs
between a TPU and the CPU (by up to 2.3e-4 of a share at N=256), which
would move every per-job cost by more than the precision under test.

This module holds what every entry shares; the site climates and load of a
kind of configuration are ``bench/scenarios/<cfg["scenario"]>.py``, and the
slot loop of each entry point is ``reference_digest`` in
``bench/entries/<entry>.py``.

Semantics, per run and slot t (N sites, K job types):

* arrivals A(t) (K,) and service rates mu(t) (N, K) are truncated-Poisson
  draws by inverse CDF from one uniform per entry;
* the per-job energy cost e[t, k, i] = P^k sum_j r[k, i, j] omega_j(t)
  PUE_j(t), and the unpriced energy likewise with PUE alone;
* GMSA sends all type-k jobs to argmin_i A^k (Q_i^k - mu_i^k + V e[t,k,i]),
  ties to the lowest index;
* the slot bills sum_{i,k} f A e and the queues follow Eq. 1,
  Q(t+1) = max(Q(t) + f A - mu, 0).

The contractions are computed in float64 and rounded to float32 (the
configuration states float32 with contractions at ``highest``); every
elementwise step is float32, as stated. ``precision="high"`` computes the
contractions as three bfloat16 products, accumulated in float32: the
control of ``bench/control.py``.
"""

from __future__ import annotations

import jax
import ml_dtypes
import numpy as np
import scipy.special

import plugins

F32 = np.float32
EPS = F32(1e-12)


def _cpu():
    return jax.devices("cpu")[0]


def _np(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


# ---------------------------------------------------------------------------
# scenario: the deterministic traces of a configuration
# ---------------------------------------------------------------------------

def iridium_reduce(d, up, down, size=F32(1), iters=50, eps=F32(1e-12)):
    """Bottleneck-minimizing reduce fractions (Iridium) for each row of
    ``d`` (B, N): bisection on the bottleneck time z, then the remaining
    simplex mass spread over the feasible box in proportion to its slack."""
    d = np.atleast_2d(d).astype(F32)

    def bounds(z):
        with np.errstate(divide="ignore", invalid="ignore"):
            hi = np.where(d < 1.0, z[:, None] * down / np.maximum((F32(1) - d) * size, eps),
                          F32(np.inf)).astype(F32)
        lo = np.where(d > 0.0, F32(1) - z[:, None] * up / np.maximum(d * size, eps),
                      F32(0)).astype(F32)
        return np.maximum(lo, F32(0)), hi

    rows = d.shape[0]
    z_lo = np.zeros((rows,), F32)
    z_hi = np.full((rows,), size * (F32(1) / up.min() + F32(1) / down.min()), F32)
    for _ in range(iters):
        mid = (F32(0.5) * (z_lo + z_hi)).astype(F32)
        lo, hi = bounds(mid)
        ok = ((lo.sum(axis=1, dtype=F32) <= F32(1))
              & (np.minimum(hi, F32(1)).sum(axis=1, dtype=F32) >= F32(1))
              & np.all(lo <= hi + F32(1e-9), axis=1))
        z_lo, z_hi = np.where(ok, z_lo, mid), np.where(ok, mid, z_hi)
    lo, hi = bounds(z_hi)
    hi = np.minimum(hi, F32(1))
    slack = np.maximum(hi - lo, F32(0))
    missing = np.maximum(F32(1) - lo.sum(axis=1, dtype=F32), F32(0))
    tot = slack.sum(axis=1, dtype=F32)
    share = np.where((tot > eps)[:, None], slack / np.maximum(tot, eps)[:, None], F32(0))
    r = lo + missing[:, None] * share
    return (r / np.maximum(r.sum(axis=1, dtype=F32), eps)[:, None]).astype(F32)


def allocation(data, up, down, manager_share, map_share) -> np.ndarray:
    """(..., K, N, N) manager-conditioned task ratios for layouts (..., K, N):
    a manager-local share at i, data-local map work, Iridium-placed reduce."""
    ms, mp = F32(manager_share), F32(map_share)
    lead, n = data.shape[:-1], data.shape[-1]
    red = iridium_reduce(data.reshape(-1, n), up, down).reshape(data.shape)
    base = mp * data.astype(F32) + (F32(1) - mp) * red
    eye = np.eye(n, dtype=F32).reshape((1,) * len(lead) + (n, n))
    return (ms * eye + (F32(1) - ms) * base[..., None, :]).astype(F32)


def poisson_cdf(lam, max_value: int) -> np.ndarray:
    """(..., max_value+1) truncated-Poisson CDF, renormalized, as float32."""
    lam = np.asarray(lam, np.float64)[..., None]
    k = np.arange(max_value + 1, dtype=np.float64)
    logpmf = k * np.log(np.maximum(lam, 1e-300)) - lam - scipy.special.gammaln(k + 1)
    cdf = np.cumsum(np.exp(logpmf), axis=-1)
    return (cdf / cdf[..., -1:]).astype(F32)


#: How far a sound float32 computation of a Poisson rate may lie from the
#: reference's: 16 float32 ulps (the rates pass through a few float32
#: products and a division, and a TPU divides to within an ulp or two).
RATE_REL = 2.0 ** -20


def poisson_band(lam, max_value: int) -> tuple[np.ndarray, np.ndarray]:
    """(lo, hi) bounds on each entry of ``poisson_cdf(lam)`` over rates
    within ``RATE_REL`` of ``lam``, widened by one float32 ulp each way.

    A uniform that falls inside an entry's band is a draw whose count
    rounding decides: a rate computed in another sound order of float32
    operations draws the neighbouring count there."""
    lam = np.asarray(lam, np.float64)
    a = poisson_cdf(lam * (1 - RATE_REL), max_value)
    b = poisson_cdf(lam * (1 + RATE_REL), max_value)
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    return (np.nextafter(lo, F32(-np.inf)).astype(F32),
            np.nextafter(hi, F32(np.inf)).astype(F32))


def scenario(cfg: dict) -> dict:
    """Every deterministic trace and table of a configuration, as numpy."""
    f = cfg["fields"]
    t, n, k = f["t_slots"], f["n_sites"], f["k_types"]
    with jax.default_device(jax.devices()[0]):
        root = jax.random.key(f["trace_seed"])
        k_price, k_pue, k_bw, k_data, _, _ = jax.random.split(root, 6)
        z_price = _np(jax.random.normal(k_price, (t, n)))
        z_pue = _np(jax.random.normal(k_pue, (t, n)))
        k_up, k_down = jax.random.split(k_bw)
        lo, hi = cfg["bandwidth_gbps"]
        up = _np(jax.random.uniform(k_up, (n,), minval=lo, maxval=hi))
        down = _np(jax.random.uniform(k_down, (n,), minval=lo, maxval=hi))
        data = _np(jax.random.dirichlet(
            k_data, np.full((n,), cfg["dataset_conc"], F32), (k,)))

    kind = plugins.load("scenarios", cfg["scenario"])
    s = kind.climates(cfg)
    hours = (np.arange(t) * F32(f["slot_minutes"] / 60.0)).astype(F32)[:, None]
    off = s["utc_offset_h"].astype(F32)[None, :]
    two_pi = F32(2 * np.pi)
    diurnal = np.cos(two_pi * (hours + off - F32(17)) / F32(24))
    weekly = F32(1) + F32(0.03) * np.sin(two_pi * hours / F32(24 * 7))
    innov = z_price * s["noise_std"].astype(F32)[None, :]
    noise = np.empty_like(innov)
    prev = innov[0] / np.sqrt(F32(1) - F32(0.9) * F32(0.9))
    for i in range(t):                                  # AR(1), phi = 0.9
        prev = F32(0.9) * prev + innov[i]
        noise[i] = prev
    omega = np.maximum(s["base_price"].astype(F32)[None, :] * weekly
                       + s["diurnal_amp"].astype(F32)[None, :] * diurnal
                       + noise, F32(1))
    pue = np.maximum(
        s["base_pue"].astype(F32)[None, :]
        + s["pue_amp"].astype(F32)[None, :]
        * np.cos(two_pi * (hours + off - F32(15)) / F32(24))
        + F32(0.004) * z_pue, F32(1))

    r = allocation(data, up, down, f["manager_share"], f["map_share"])  # (K, N, N)

    # Service capacity: shares of the offered load, slowed by shuffle I/O.
    locality = data.mean(axis=0, dtype=F32)
    transfer = F32(5.0) * (F32(1) - locality) * F32(8) / np.maximum(down, F32(1e-6))
    slowdown = (F32(300) / (F32(300) + transfer)).astype(F32)
    lam, shares = kind.load(cfg)
    mu_mean = (shares[:, None] * slowdown.astype(np.float64)[:, None] * lam
               * np.ones((1, k)))
    lam_k = np.full((k,), lam)
    return {
        "omega": omega.astype(F32), "pue": pue.astype(F32), "r": r,
        "up": up, "down": down, "data_dist": data,
        "p_it": np.ones((k,), F32),
        "arr_cdf": poisson_cdf(lam_k, int(f["a_max"])),
        "mu_cdf": poisson_cdf(mu_mean, int(f["mu_max"])),
        "mu_rate": mu_mean,
        "arr_band": poisson_band(lam_k, int(f["a_max"])),
        "mu_band": poisson_band(mu_mean, int(f["mu_max"])),
    }


# ---------------------------------------------------------------------------
# the per-run draws and the slot loop
# ---------------------------------------------------------------------------

def call_key(seed: int, index: int):
    """The key of call ``index`` of a run with ``seed``: fold_in(key(seed), index),
    the seed's 64 bits held as two 32-bit words."""
    words = np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    with jax.default_device(_cpu()):
        base = jax.random.wrap_key_data(jax.device_put(words, _cpu()),
                                        impl="threefry2x32")
        return jax.random.fold_in(base, index)


def run_keys(call_key, n_runs: int):
    """(k_arrivals, k_service) per run, in the entry point's key stream."""
    with jax.default_device(_cpu()):
        keys = jax.random.split(jax.device_put(call_key, _cpu()), n_runs)
        k_build = jax.vmap(lambda kk: jax.random.split(kk)[0])(keys)
        pair = jax.vmap(jax.random.split)(k_build)
        return pair[:, 0], pair[:, 1]


def _ambiguous(name, u, count, band) -> list:
    """Per run, the draws of ``u`` (S, T, *B) that fall inside a band of
    their table (*B, M+1): ``(name, index within the run, other counts)``."""
    least = (band[1] < u[..., None]).sum(axis=-1)
    most = (band[0] < u[..., None]).sum(axis=-1)
    out = [[] for _ in range(u.shape[0])]
    for at in zip(*np.nonzero(least != most)):
        base = int(count[at])
        others = tuple(float(c) for c in range(least[at], most[at] + 1) if c != base)
        if others:
            out[at[0]].append((name, tuple(int(x) for x in at[1:]), others))
    return out


def draws(scen: dict, call_key, n_runs: int, t_slots: int, rows=None):
    """(arrivals (S, T, K), mu (S, T, N, K), ambiguous) for the runs ``rows``
    (all R runs when None) of one call: float32 counts, and per run the
    draws whose count rounding of the rates decides (``_ambiguous``)."""
    k_arr, k_mu = run_keys(call_key, n_runs)
    if rows is not None:
        k_arr, k_mu = k_arr[np.asarray(rows)], k_mu[np.asarray(rows)]
    n, k = scen["mu_cdf"].shape[:2]
    with jax.default_device(_cpu()):
        u_a = _np(jax.vmap(lambda kk: jax.random.uniform(kk, (t_slots, k)))(k_arr))
        u_m = _np(jax.vmap(lambda kk: jax.random.uniform(kk, (t_slots, n, k)))(k_mu))
    arr = np.empty(u_a.shape, F32)
    for j in range(k):
        arr[..., j] = np.searchsorted(scen["arr_cdf"][j], u_a[..., j], side="left")
    mu = np.empty(u_m.shape, F32)
    for i in range(n):
        for j in range(k):
            mu[..., i, j] = np.searchsorted(scen["mu_cdf"][i, j], u_m[..., i, j],
                                            side="left")
    amb = [a + m for a, m in zip(_ambiguous("arr", u_a, arr, scen["arr_band"]),
                                 _ambiguous("mu", u_m, mu, scen["mu_band"]))]
    return arr, mu, amb


def split_bf16(x: np.ndarray):
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float64)
    lo = (x.astype(np.float64) - hi).astype(F32).astype(
        ml_dtypes.bfloat16).astype(np.float64)
    return hi, lo


def contract(r: np.ndarray, w: np.ndarray, precision: str) -> np.ndarray:
    """(T, K, N) = sum_j r[k, i, j] w[t, j], at the stated precision."""
    if precision == "highest":
        return np.einsum("kij,tj->tki", r.astype(np.float64),
                         w.astype(np.float64)).astype(F32)
    if precision == "high":                 # bf16_3x: hi*hi + hi*lo + lo*hi
        rh, rl = split_bf16(r)
        wh, wl = split_bf16(w)
        out = (np.einsum("kij,tj->tki", rh, wh) + np.einsum("kij,tj->tki", rh, wl)
               + np.einsum("kij,tj->tki", rl, wh))
        return out.astype(F32)
    raise ValueError(f"unknown precision {precision!r}")


def energy_tables(scen: dict, precision: str = "highest"):
    """(T, K, N) per-job dispatch cost and unpriced energy."""
    p = scen["p_it"][None, :, None]
    e_cost = contract(scen["r"], scen["omega"] * scen["pue"], precision) * p
    e_raw = contract(scen["r"], scen["pue"], precision) * p
    return e_cost.astype(F32), e_raw.astype(F32)


def decide(score, e_cost, a, v, t, forced, alive=None):
    """(f (R, N, K), gap (R, K)) of one slot.

    Free-running (``forced`` None) GMSA takes the argmin of ``score``
    (R, K, N). Forced, the decision is the one given (``forced["choice"]``
    and ``forced["fmax"]``, one slot of them) and ``gap`` is how far its
    score lies above the best, in units of the slot's energy term
    A V mean_i e[k, i]. A decision that was spread over the survivors
    (fmax < 1: GMSA chose a dead site) is measured from the best dead site.
    """
    n = score.shape[-1]
    sites = np.arange(n)[None, None, :]
    best = np.argmin(score, axis=2)                                  # (R, K)
    gap = np.zeros(best.shape, np.float64)
    if forced is None:
        f = (sites == best[:, :, None]).astype(F32)                  # (R, K, N)
    else:
        choice, fmax = forced["choice"][:, t], forced["fmax"][:, t]
        s64 = score.astype(np.float64)
        s_min = s64.min(axis=2)
        s_c = np.take_along_axis(s64, choice[:, :, None].astype(np.int64), 2)[..., 0]
        spread = fmax < 1 - 1e-6
        if alive is not None and np.any(spread):
            s_dead = np.where(alive[None, None, :] < 0.5, s64, np.inf).min(axis=2)
            s_c = np.where(spread, s_dead, s_c)
        scale = (a.astype(np.float64) * float(v)
                 * e_cost.astype(np.float64).mean(axis=-1))          # (R, K)
        gap = np.where(scale > 0, (s_c - s_min) / np.where(scale > 0, scale, 1), 0.0)
        f = ((sites == choice[:, :, None]) & ~spread[:, :, None]).astype(F32)
    f = np.swapaxes(f, 1, 2)                                         # (R, N, K)
    if alive is not None and np.any(alive < 0.5):
        fb = np.broadcast_to((alive / max(alive.sum(), F32(1)))[None, :, None], f.shape)
        f = renorm(f * alive[None, :, None], fb, axis=1)
    return f, gap


def renorm(x, fallback, axis):
    """x normalized to sum 1 along ``axis``, or ``fallback`` where it sums to 0."""
    tot = x.sum(axis=axis, keepdims=True, dtype=F32)
    return np.where(tot > EPS, x / np.maximum(tot, EPS), fallback).astype(F32)


def slot_digest(rows: dict, f_all, q_tot) -> dict:
    out = {k: np.stack(v, axis=1) for k, v in rows.items()}
    f_all = np.stack(f_all, axis=1)                                  # (R, T, N, K)
    out["choice"] = np.argmax(f_all, axis=2).astype(np.int32)
    out["fmax"] = f_all.max(axis=2)
    out["slot_backlog"] = np.stack(q_tot, axis=1)
    return out


def simulate(arr, mu, e_cost, e_raw, v: float, forced=None) -> dict:
    """GMSA over T slots for R runs at once: per-slot digests.

    With ``forced`` (the program's decisions), the same loop replays them:
    its queues, bills and the gap of each decision to the best one."""
    n_runs, t_slots, k = arr.shape
    n = mu.shape[2]
    v = F32(v)
    q = np.zeros((n_runs, n, k), F32)
    rows = {"slot_cost": [], "slot_energy": [], "gap": []}
    f_all, q_tot = [], []
    for t in range(t_slots):
        a, m = arr[:, t], mu[:, t]                                   # (R,K), (R,N,K)
        ec = np.broadcast_to(e_cost[t], (n_runs, k, n))
        score = a[:, :, None] * (np.swapaxes(q - m, 1, 2) + v * ec)
        f, gap = decide(score, ec, a, v, t, forced)
        fa = f * a[:, None, :]
        rows["slot_cost"].append((fa * e_cost[t].T[None]).sum(axis=(1, 2), dtype=F32))
        rows["slot_energy"].append((fa * e_raw[t].T[None]).sum(axis=(1, 2), dtype=F32))
        rows["gap"].append(gap)
        q = np.maximum(q + fa - m, F32(0))
        f_all.append(f)
        q_tot.append(q.sum(axis=(1, 2), dtype=F32))
    return slot_digest(rows, f_all, q_tot)


def evaluate(cfg: dict, traffic: dict, scen: dict, arr, mu,
             precision: str = "highest", forced=None) -> dict:
    """Per-slot digests of the runs (arr, mu) of the traffic's entry point:
    free-running, or replaying the decisions ``forced``; ``arrived`` is the
    count of jobs that have arrived up to each slot, against which the
    backlog is measured (``floors``)."""
    entry = plugins.load("entries", traffic["entry"])
    out = entry.reference_digest(cfg, traffic, scen, arr, mu, precision, forced)
    out["floors"] = {"slot_backlog": np.cumsum(arr.sum(axis=-1, dtype=np.float64), axis=1)}
    return out
