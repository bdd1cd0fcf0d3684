"""Pure-jnp oracle for the chunked SSD scan: the O(S) sequential recurrence.

    h_t = exp(a_h * dt_t) * h_{t-1} + dt_t * (x_t ⊗ B_t)
    y_t = h_t @ C_t

Deliberately the *sequential* form (not the chunked algebra) so the kernel
and the chunked pure-JAX path (repro.models.ssm.ssd_chunked) are validated
against an independent formulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array


def ssd_scan_ref(
    x: Array,      # (B, S, H, P)
    dt: Array,     # (B, S, H)
    a: Array,      # (H,)
    b_mat: Array,  # (B, S, N)
    c_mat: Array,  # (B, S, N)
) -> tuple[Array, Array]:
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]

    hi = jax.lax.Precision.HIGHEST     # float32 contractions on a TPU too

    def step(state, t_in):
        xt, dtt, bt, ct = t_in
        decay = jnp.exp(dtt.astype(jnp.float32) * a.astype(jnp.float32))  # (B,H)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dtt.astype(jnp.float32),
                         xt.astype(jnp.float32), bt.astype(jnp.float32),
                         precision=hi)
        state = decay[:, :, None, None] * state + upd
        y = jnp.einsum("bhpn,bn->bhp", state, ct.astype(jnp.float32),
                       precision=hi)
        return state, y

    h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    final, ys = jax.lax.scan(
        step, h0,
        (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
         b_mat.transpose(1, 0, 2), c_mat.transpose(1, 0, 2)),
    )
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), final
