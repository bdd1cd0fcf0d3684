"""Energy-consumption / energy-cost model of GDA (paper Sec. III & IV-A).

The power drawn by a type-k job is fixed on the IT side (``P^k``) but its
*effective* energy — and the dollar cost of that energy — depends on where the
job's parallel tasks physically execute:

    energy(k, manager=i, t)  =  sum_j PUE_j(t) * r^k_{ij} * P^k
    cost(k, manager=i, t)    =  sum_j omega_j(t) * PUE_j(t) * r^k_{ij} * P^k

with the slot-level system cost

    Cost(t) = sum_k sum_i f_i^k(t) * A^k(t) * cost(k, i, t).

``r^k`` is the task-allocation-ratio matrix produced by the placement layer
(:mod:`repro.core.iridium`), ``PUE_j(t)`` / ``omega_j(t)`` come from the trace
pipeline (:mod:`repro.traces`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import Array

#: Precision of every cost contraction: float32 on every backend. A TPU's
#: default rounds matmul operands to bf16, which moves GMSA's argmin on near
#: ties (0.3% of the N=256 fleet's dispatch decisions on a v5e).
HIGHEST = jax.lax.Precision.HIGHEST


def manager_energy_cost(omega: Array, pue: Array, r: Array, p_it: Array) -> Array:
    """Per-job energy cost e[k, i] of choosing DC i as manager for type k.

    e[k, i] = P^k * sum_j omega_j * PUE_j * r[k, i, j]

    Args:
        omega: (N,) energy-price weights at this slot.
        pue:   (N,) PUE values at this slot.
        r:     (K, N, N) task-allocation ratios.
        p_it:  (K,) fixed IT energy per job.

    Returns:
        (K, N) per-job energy cost for every (type, manager) pair.
    """
    weighted = omega * pue                                # (N,)
    # einsum over the executor axis j; MXU-friendly batched matvec.
    e = jnp.einsum("kij,j->ki", r, weighted, precision=HIGHEST)  # (K, N)
    return e * p_it[:, None]


def manager_energy(pue: Array, r: Array, p_it: Array) -> Array:
    """Per-job *energy* (not cost): E[k, i] = P^k * sum_j PUE_j * r[k, i, j]."""
    return jnp.einsum("kij,j->ki", r, pue, precision=HIGHEST) * p_it[:, None]


def slot_cost(f: Array, arrivals: Array, e: Array) -> Array:
    """System energy cost of one slot, Cost(t) (scalar).

    Args:
        f: (N, K) dispatch fractions.
        arrivals: (K,) arrivals this slot.
        e: (K, N) per-job manager energy costs from :func:`manager_energy_cost`.
    """
    # sum_k sum_i f[i,k] * A[k] * e[k,i]
    return jnp.sum(f.T * arrivals[:, None] * e)


def slot_energy(f: Array, arrivals: Array, energy_ki: Array) -> Array:
    """System energy of one slot (same contraction, PUE-weighted only)."""
    return jnp.sum(f.T * arrivals[:, None] * energy_ki)


@dataclasses.dataclass(frozen=True)
class EnergyModel:
    """Bundles the static pieces of the cost model.

    Attributes:
        r: (K, N, N) task-allocation ratios (row-stochastic over the last axis).
        p_it: (K,) fixed per-job IT energy. The paper's evaluation sets this
            to 1 for its single job type; the fleet configuration derives it
            per workload class from the compiled step's roofline (DESIGN.md §7).
    """

    r: Array
    p_it: Array

    def cost_of_managers(self, omega: Array, pue: Array) -> Array:
        """(K, N) per-job cost table for one slot's (omega, pue)."""
        return manager_energy_cost(omega, pue, self.r, self.p_it)

    def slot_cost(self, f: Array, arrivals: Array, omega: Array, pue: Array) -> Array:
        return slot_cost(f, arrivals, self.cost_of_managers(omega, pue))

    def validate(self) -> None:
        """Eager sanity checks (not jit-safe; call at construction time)."""
        k, n, n2 = self.r.shape
        if n != n2:
            raise ValueError(f"r must be (K, N, N), got {self.r.shape}")
        if self.p_it.shape != (k,):
            raise ValueError(
                f"p_it must be (K,)={k}, got {self.p_it.shape}"
            )
        rowsum = jnp.sum(self.r, axis=-1)
        if not bool(jnp.allclose(rowsum, 1.0, atol=1e-5)):
            raise ValueError("task-allocation ratios must be row-stochastic")
        if bool(jnp.any(self.r < -1e-7)):
            raise ValueError("task-allocation ratios must be non-negative")
