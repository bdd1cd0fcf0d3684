"""Baseline global-manager-selection strategies (paper Sec. V-A).

The paper compares GMSA against:

* **DATA**   — the fraction of type-k jobs dispatched to DC i is proportional
  to the fraction of the type-k dataset stored at DC i.
* **RANDOM** — every job picks its manager uniformly at random. At the slot
  level with ``A^k(t)`` integral arrivals this is a multinomial split; we
  sample it exactly so small-A slots show the correct variance.

Two extra references (not in the paper, used for ablations in EXPERIMENTS.md):

* **JSQ**    — join-the-shortest-queue: all type-k jobs to argmin_i Q_i^k.
  Isolates the "drift-only" half of GMSA (V = 0).
* **GREEDY-COST** — all type-k jobs to argmin_i e_i^k. The V -> inf limit of
  GMSA; minimizes instantaneous cost with no regard for stability.

All policies share the simulator signature
``(key, q, arrivals, mu, e, aux) -> f`` where ``aux`` carries the (K, N)
dataset distribution (used only by DATA).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array
from jax.nn import one_hot

from repro.core.policy import declare

# Static upper bound on per-slot arrivals of one job type; the exact
# multinomial sampler draws this many candidate picks and masks the tail.
# Configs assert A_max <= MAX_SLOT_ARRIVALS.
MAX_SLOT_ARRIVALS = 512


def data_dispatch(key, q: Array, arrivals: Array, mu: Array, e: Array, aux: Array, scalar=0.0) -> Array:
    """DATA baseline: f[i, k] = dataset_fraction[k, i]."""
    del key, q, arrivals, mu, e, scalar
    return aux.T  # (K, N) -> (N, K)


declare(data_dispatch, keyed=False, state_independent=True)


def random_dispatch(key, q: Array, arrivals: Array, mu: Array, e: Array, aux: Array, scalar=0.0) -> Array:
    """RANDOM baseline: exact multinomial split of each slot's arrivals.

    For a slot with A^k jobs, each job independently picks one of N managers
    uniformly; f_i^k is the realized fraction. Empty slots (A^k = 0) fall back
    to the uniform vector (the choice is irrelevant since f multiplies A).
    """
    del mu, e, aux, scalar
    n, k_types = q.shape
    keys = jax.random.split(key, k_types)
    counts = jax.vmap(lambda kk, a: _multinomial_uniform(kk, a, n))(
        keys, arrivals
    )                                                    # (K, N)
    denom = jnp.maximum(arrivals[:, None], 1.0)
    frac = jnp.where(arrivals[:, None] > 0, counts / denom, 1.0 / n)
    return frac.T                                        # (N, K)


def _multinomial_uniform(key, count: Array, n: int) -> Array:
    """Exact Multinomial(count, uniform-over-n) with a static draw budget.

    Draws ``MAX_SLOT_ARRIVALS`` uniform categorical picks, masks picks beyond
    ``count`` into a scratch category, and histograms. jit-safe: all shapes
    static, ``count`` may be a traced (integral-valued) scalar.
    """
    picks = jax.random.randint(key, (MAX_SLOT_ARRIVALS,), 0, n)
    idx = jnp.arange(MAX_SLOT_ARRIVALS)
    masked = jnp.where(idx < count, picks, n)            # overflow bucket n
    hist = jnp.sum(one_hot(masked, n + 1, dtype=jnp.float32), axis=0)
    return hist[:n]


declare(random_dispatch, state_independent=True)


def jsq_dispatch(key, q: Array, arrivals: Array, mu: Array, e: Array, aux: Array, scalar=0.0) -> Array:
    """Join-the-shortest-queue (drift-only; GMSA with V = 0)."""
    del key, arrivals, mu, e, aux, scalar
    best = jnp.argmin(q, axis=0)                      # (K,)
    return one_hot(best, q.shape[0], dtype=q.dtype).T


declare(jsq_dispatch, keyed=False)


def greedy_cost_dispatch(key, q: Array, arrivals: Array, mu: Array, e: Array, aux: Array, scalar=0.0) -> Array:
    """Greedy instantaneous-cost minimizer (GMSA's V -> inf limit)."""
    del key, arrivals, mu, aux, scalar
    best = jnp.argmin(e, axis=1)                      # (K,)
    return one_hot(best, q.shape[0], dtype=q.dtype).T


declare(greedy_cost_dispatch, keyed=False, state_independent=True)


def static_placement_rule(d: Array, obs) -> Array:
    """STATIC-PLACEMENT baseline for the two-timescale controller.

    Never re-places: the dataset layout stays wherever the trace (initial
    Dirichlet draw + any exogenous ingest drift) puts it, exactly the frozen
    ``data_dist`` assumption of the base paper. Plugs into
    :func:`repro.placement.controller.simulate_placed` as the ``rule``
    operand; the adaptive counterpart is
    :func:`repro.placement.replica.make_adaptive_rule`.

    Survivor-aware: when the controller reports dead sites through
    ``obs.alive``, the layout renormalizes over the survivors (``drop_site``
    semantics — a static placement cannot keep data at a site that no
    longer exists), but it still never *optimizes*. With every site alive
    the input ``d`` is returned untouched, bit for bit.
    """
    alive = getattr(obs, "alive", None)
    if alive is None:
        return d
    alive = jnp.asarray(alive, d.dtype)
    masked = d * alive[None, :]
    held = jnp.sum(masked, axis=1, keepdims=True)
    n_alive = jnp.maximum(jnp.sum(alive), 1.0)
    uniform = jnp.broadcast_to(alive / n_alive, masked.shape)
    dropped = jnp.where(held > 1e-9, masked / jnp.maximum(held, 1e-9), uniform)
    return jnp.where(jnp.any(alive < 0.5), dropped, d)
