"""Pure-jnp oracle for the fused GMSA dispatch score + argmin.

score[k, i] = a[k] * ( q[k, i] - mu[k, i] + vp[k] * sum_j r[k, i, j] * wpue[j] )
best[k]     = argmin_i score[k, i]

(q/mu arrive (K, N) pre-transposed; ``vp`` = V * P^k folded by the caller;
``wpue`` = omega ⊙ PUE.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array


def gmsa_score_ref(
    q: Array, mu: Array, a: Array, vp: Array, r: Array, wpue: Array
) -> tuple[Array, Array]:
    """Returns (scores (K, N) fp32, best (K,) int32).

    The contraction runs at HIGHEST precision: float32 on every backend
    (a TPU's default rounds matmul operands to bf16).
    """
    cost = jnp.einsum(
        "kij,j->ki", r.astype(jnp.float32), wpue.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    scores = a[:, None].astype(jnp.float32) * (
        q.astype(jnp.float32) - mu.astype(jnp.float32)
        + vp[:, None].astype(jnp.float32) * cost
    )
    return scores, jnp.argmin(scores, axis=1).astype(jnp.int32)
