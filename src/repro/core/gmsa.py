"""GMSA — dynamic Global Manager Selection Algorithm (paper Sec. IV-B).

Per slot the algorithm observes (A, Q, mu, omega, PUE), and picks dispatch
fractions f(t) minimizing the drift-plus-penalty upper bound (Lemma 1):

    min_f  sum_{i,k} [ f_i^k A^k (Q_i^k - mu_i^k) - Q_i^k mu_i^k ]  +  V * Cost(t)
    s.t.   sum_i f_i^k = 1,   f_i^k >= 0.

Because the objective is linear in ``f`` and the constraint set is a product
of independent K simplices, the exact LP optimum is attained at a vertex:
all type-k mass goes to

    i*(k) = argmin_i  A^k * [ Q_i^k - mu_i^k + V * e_i^k ]

with ``e_i^k`` the per-job manager energy cost. We implement this closed form
(vectorized over K, vmappable over Monte-Carlo runs, kernelizable for fleet-
scale N — see ``repro.kernels.gmsa_score``) and verify it against
``scipy.optimize.linprog`` in the test suite.

The module also exposes the LP objective itself and the Lemma-1 drift bound
constant ``B`` so properties of the algorithm can be asserted directly.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.policy import declare


@dataclasses.dataclass(frozen=True)
class GMSAConfig:
    """Control knobs of GMSA.

    Attributes:
        v: the Lyapunov trade-off parameter V >= 0. Larger V weights energy
            cost more (cost -> within O(1/V) of optimal) at the price of
            O(V) average backlog.
    """

    v: float = 1.0


def drift_plus_penalty_scores(
    q: Array, arrivals: Array, mu: Array, e: Array, v: float | Array
) -> Array:
    """Per-(type, manager) score whose argmin is the exact LP solution.

    score[k, i] = A^k * ( Q_i^k - mu_i^k + V * e[k, i] )

    Args:
        q: (N, K) backlogs.
        arrivals: (K,) arrivals this slot.
        mu: (N, K) service rates this slot.
        e: (K, N) per-job manager energy costs.
        v: scalar V.

    Returns:
        (K, N) scores.
    """
    drift = (q - mu).T                       # (K, N)
    return arrivals[:, None] * (drift + v * e)


def gmsa_dispatch(
    q: Array,
    arrivals: Array,
    mu: Array,
    e: Array | None,
    v: float | Array,
    *,
    impl: str = "ref",
    r: Array | None = None,
    wpue: Array | None = None,
    p_it: Array | None = None,
    interpret: bool | None = None,
) -> Array:
    """Exact per-slot GMSA decision f(t).

    Returns the (N, K) one-hot-per-column dispatch matrix placing all type-k
    jobs on the score-minimizing manager. Ties break to the lowest index
    (deterministic; matches the LP vertex scipy reports for degenerate ties
    up to objective equality, which is what the tests assert).

    Two implementations share this entry point:

    * ``impl="ref"`` (default) — the pure-XLA closed form against the
      precomputed per-job cost table ``e`` (the simulator's hoisted-einsum
      path). This is the fastest route when ``e`` is already amortized
      across slots.
    * ``impl="kernel"`` — the fused Pallas path for fleet-scale N: score,
      cost matvec and argmin in ONE kernel pass over the raw ``(r, wpue)``
      operands (:mod:`repro.kernels.gmsa_score`), never materializing the
      (K, N) score matrix in HBM between them. Requires ``r`` (K, N, N)
      and ``wpue`` (N,) instead of ``e``; ``p_it`` defaults to ones.
      ``interpret=None`` auto-selects interpret mode off-TPU (the CI/CPU
      path — the compiled kernel is the TPU target), and the pure-jnp
      oracle :func:`repro.kernels.gmsa_score.gmsa_score_ref` remains the
      fallback for callers that want raw-(r, wpue) dispatch without
      Pallas: pass ``impl="ref"`` with ``r``/``wpue`` and no ``e``.
    """
    n = q.shape[0]
    if impl == "kernel" or (impl == "ref" and e is None):
        if r is None or wpue is None:
            raise ValueError(
                f"impl={impl!r} without a precomputed cost table needs the "
                "raw operands: pass r=(K, N, N) and wpue=(N,)"
            )
        p = jnp.ones_like(arrivals) if p_it is None else p_it
        vp = jnp.asarray(v, jnp.float32) * p                    # (K,) V·P^k
        if impl == "kernel":
            from repro.kernels import default_interpret
            from repro.kernels.gmsa_score.ops import gmsa_score

            if interpret is None:
                interpret = default_interpret()
            _, best = gmsa_score(
                q.T, mu.T, arrivals, vp, r, wpue, interpret=interpret
            )                                                   # best (K,)
        else:
            from repro.kernels.gmsa_score.ref import gmsa_score_ref

            _, best = gmsa_score_ref(q.T, mu.T, arrivals, vp, r, wpue)
    elif impl == "ref":
        scores = drift_plus_penalty_scores(q, arrivals, mu, e, v)  # (K, N)
        best = jnp.argmin(scores, axis=1)                          # (K,)
    else:
        raise ValueError(f"unknown impl {impl!r}; use 'ref' or 'kernel'")
    # One-hot built directly in (N, K) orientation — same values as
    # one_hot(best, N).T without the transpose kernel in the hot loop.
    return (jnp.arange(n)[:, None] == best[None, :]).astype(q.dtype)


def lp_objective(
    f: Array, q: Array, arrivals: Array, mu: Array, e: Array, v: float | Array
) -> Array:
    """The full per-slot LP objective (including the f-independent term).

    obj(f) = sum_{i,k} [ f_i^k A^k (Q_i^k - mu_i^k) - Q_i^k mu_i^k ]
             + V * sum_{i,k} f_i^k A^k e[k, i]
    """
    fa = f * arrivals[None, :]                     # (N, K)
    drift_term = jnp.sum(fa * (q - mu))
    const_term = -jnp.sum(q * mu)
    cost_term = v * jnp.sum(fa * e.T)
    return drift_term + const_term + cost_term


def lyapunov_drift_bound_B(a_max: Array, mu_max: Array, n: int) -> Array:
    """The Lemma-1 constant  B = N/2 * sum_k (A_max^k)^2 + N/2 * sum_k (mu_max^k)^2.

    Used by the property tests to check the one-slot drift inequality.
    """
    return 0.5 * n * (jnp.sum(jnp.square(a_max)) + jnp.sum(jnp.square(mu_max)))


def gmsa_policy(key, q, arrivals, mu, e, aux, scalar):
    """GMSA with V supplied as the simulator's *traced* scalar — a V-sweep
    (paper Fig. 6) reuses a single compiled simulation."""
    del key, aux
    return gmsa_dispatch(q, arrivals, mu, e, scalar)


declare(gmsa_policy, keyed=False)


def dispatch_fn(v: float):
    """Closure adapter binding a static V (one compilation per V).

    Returns a function with the simulator's policy signature
    ``(key, q, arrivals, mu, e, aux, scalar) -> f``; GMSA ignores the PRNG
    key, the auxiliary (dataset-distribution) operand and the traced scalar.
    """

    def _policy(key, q, arrivals, mu, e, aux, scalar):
        del key, aux, scalar
        return gmsa_dispatch(q, arrivals, mu, e, v)

    return declare(_policy, keyed=False)


def make_kernel_policy(
    r: Array | None = None,
    p_it: Array | None = None,
    impl: str = "kernel",
    interpret: bool | None = None,
):
    """GMSA policy driving dispatch through the fused Pallas kernel.

    Routes every slot's decision through ``gmsa_dispatch(..., impl=...)``
    on the raw ``(r, wpue)`` operands — the fleet-scale path where the
    kernel fuses the cost matvec, the drift score and the argmin in one
    pass (:mod:`repro.kernels.gmsa_score`). V rides in as the simulator's
    traced ``scalar``, exactly like :func:`gmsa_policy`.

    Two ratio-tensor modes, both declared ``reads`` of raw per-slot prices
    (:class:`repro.core.policy.PolicySpec`):

    * ``r=None`` (carried-r) — ``reads="wpue_r"``: the policy reads the
      ratio tensor in force *this slot* from its aux,
      ``aux = (data_dist, omega_t * pue_t, r_t)``: the engines slice a
      time-varying ``(T, K, N, N)`` trace per slot, and the placement
      controller hands the carried ``r_c``/``r_e`` its epoch rebuilds and
      recovery re-placements actually produced. This is the only mode the
      controller accepts.
    * explicit ``(K, N, N)`` ``r`` — ``reads="wpue"``, ``binds_r=True``:
      statically bound, and the engines raise loudly if a time-varying
      ratio trace reaches it (the kernel would silently dispatch on stale
      ratios).

    Reading raw prices is what lets an N = 256 ``configs.fleet_256`` run
    complete end-to-end through the kernel (interpret mode on CPU/CI,
    compiled on TPU; ``impl="ref"`` selects the pure-jnp oracle instead —
    the fallback when Pallas is unavailable).
    """
    bound = r is not None
    if bound:
        r = jnp.asarray(r, jnp.float32)

    def policy(key, q, arrivals, mu, e, aux, scalar):
        del key, e
        if bound:
            wpue, r_t = aux[1], r
        else:
            _, wpue, r_t = aux
        return gmsa_dispatch(
            q, arrivals, mu, None, scalar,
            impl=impl, r=r_t, wpue=wpue, p_it=p_it, interpret=interpret,
        )

    return declare(policy, keyed=False, reads="wpue" if bound else "wpue_r",
                   binds_r=bound)
