"""Pallas TPU kernel: Mamba-2 chunked SSD forward.

Grid (B, H, S/Q) — the chunk index innermost so the (P, N) recurrent state
lives in VMEM scratch across chunks of one (batch, head) stream:

  per chunk (Q = chunk length):
    cum   = cumsum(a * dt)                          (VPU, (Q,1))
    CB    = C @ Bᵀ                                  (MXU, (Q,Q))
    W     = CB ⊙ tril(exp(cum_t - cum_s)) ⊙ dt_s    (VPU)
    y     = W @ x  +  (C @ h_inᵀ) ⊙ exp(cum)        (MXU + MXU)
    h_out = exp(cum_Q) · h_in + (x ⊙ decay·dt)ᵀ @ B (MXU)

TPU adaptation of the paper's (GPU) SSD kernel shape: the (Q,Q) intra-chunk
"attention" matrix is sized to the MXU (Q=128 ⇒ 64 KiB fp32 in VMEM), state
(P×N = 64×128) stays resident in VMEM across the whole stream — HBM traffic
is exactly x/dt/B/C in and y out, the roofline floor for this op.

Layout: every operand is head-major, so each block's two minor dims are a
``(chunk, feature)`` tile that Mosaic accepts — ``(Q, P)`` for x/y,
``(Q, 1)`` columns for dt and a·dt, ``(Q, N)`` for B/C. The cumulative sum
and the column→row flips are masked (Q, Q) reductions, which Mosaic lowers
on the VPU (it has no cumsum or 1-D transpose). Matmuls run at HIGHEST
precision: the kernel is pinned against a float32 oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.telemetry.scopes import SSD_SCAN

_HI = jax.lax.Precision.HIGHEST


def _dot(lhs, rhs, contract):
    return jax.lax.dot_general(lhs, rhs, (contract, ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, dt_ref, adt_ref, b_ref, c_ref, y_ref, hout_ref, state_ref):
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0]                                # (Q, 1)
    adt = adt_ref[0, 0]                              # (Q, 1)
    bm = b_ref[0].astype(jnp.float32)                # (Q, N)
    cm = c_ref[0].astype(jnp.float32)                # (Q, N)

    q_len = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    tri = row >= col                                 # [t, s]: s <= t

    def to_row(v):                                   # (Q, 1) -> (1, Q)
        return jnp.sum(jnp.where(row == col, v, 0.0), axis=0, keepdims=True)

    # Inclusive prefix sums of a·dt as masked reductions, once with t on
    # sublanes (a column) and once with t on lanes (a row).
    cum = jnp.sum(jnp.where(tri, to_row(adt), 0.0), axis=1,
                  keepdims=True)                     # (Q, 1)
    cum_row = jnp.sum(jnp.where(row <= col, adt, 0.0), axis=0,
                      keepdims=True)                 # (1, Q)
    dt_row = to_row(dt)                              # (1, Q)

    # Intra-chunk attention-form term.
    l_mat = jnp.exp(jnp.where(tri, cum - cum_row, -jnp.inf))       # (Q, Q)
    cb = _dot(cm, bm, ((1,), (1,)))                                # (Q, Q)
    w = cb * l_mat * dt_row
    y = _dot(w, x, ((1,), (0,)))                                   # (Q, P)

    # Inter-chunk term from the carried state.
    h_in = state_ref[...]                                          # (P, N)
    y = y + _dot(cm, h_in, ((1,), (1,))) * jnp.exp(cum)            # (Q, P)

    # State update: h' = exp(cum_Q) h + sum_s decay_out_s dt_s x_s ⊗ B_s.
    cum_last = cum[q_len - 1:, :]                                  # (1, 1)
    xw = x * (jnp.exp(cum_last - cum) * dt)                        # (Q, P)
    upd = _dot(xw, bm, ((0,), (0,)))                               # (P, N)
    state_ref[...] = jnp.exp(cum_last) * h_in + upd

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x, dt, adt, b_mat, c_mat, *, chunk: int,
                    interpret: bool = False):
    """Head-major entry point. x: (B,H,S,P); dt, adt: (B,H,S,1) float32;
    b/c: (B,S,N). S % chunk == 0. Returns (y (B,H,S,P), h (B,H,P,N))."""
    bsz, h, s, p = x.shape
    n = b_mat.shape[-1]
    grid = (bsz, h, s // chunk)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, hh, c: (b, hh, c, 0)),  # x
            pl.BlockSpec((1, 1, chunk, 1), lambda b, hh, c: (b, hh, c, 0)),  # dt
            pl.BlockSpec((1, 1, chunk, 1), lambda b, hh, c: (b, hh, c, 0)),  # a·dt
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),         # B
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),         # C
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b, hh, c: (b, hh, c, 0)),  # y
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),      # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name=SSD_SCAN,
    )(x, dt, adt, b_mat, c_mat)
