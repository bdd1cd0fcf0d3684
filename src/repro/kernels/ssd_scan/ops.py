"""jit'd public wrapper for the SSD scan kernel (layout, padding, dtypes)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array

from repro.kernels.ssd_scan.kernel import ssd_scan_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: Array,      # (B, S, H, P)
    dt: Array,     # (B, S, H)
    a: Array,      # (H,)
    b_mat: Array,  # (B, S, N)
    c_mat: Array,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Chunked SSD forward. Returns (y (B,S,H,P), final_state (B,H,P,N)).

    Sequence length is padded to a chunk multiple with dt=0 steps (exp(0)=1,
    zero update — exact no-ops for the recurrence). The kernel reads x and
    dt head-major, and a·dt is formed here, so its blocks tile as
    ``(chunk, feature)``. ``interpret=None`` resolves per backend
    (:func:`repro.kernels.default_interpret`): compiled on TPU, interpret
    elsewhere.
    """
    if interpret is None:
        from repro.kernels import default_interpret

        interpret = default_interpret()
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    dt_h = dt.astype(jnp.float32).transpose(0, 2, 1)[..., None]    # (B,H,S,1)
    adt_h = dt_h * a.astype(jnp.float32)[None, :, None, None]
    y, h_final = ssd_scan_kernel(
        x.transpose(0, 2, 1, 3), dt_h, adt_h, b_mat, c_mat,
        chunk=chunk, interpret=interpret,
    )
    return y.transpose(0, 2, 1, 3)[:, :s], h_final
