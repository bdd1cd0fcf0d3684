"""Pallas TPU kernel: fused GMSA drift-plus-penalty score + argmin.

Grid (nk, ni, nj), row-major sequential on TPU (j innermost):

  * j loop  — accumulate the cost matvec  acc[kt, it] += r[kt, it, jt] @ wpue[jt]
              on the MXU ((K_T*N_T, J_T) x (J_T, 1));
  * at j=last — fuse the drift term (VPU), emit the score tile, and fold it
              into the running (min, argmin) scratch carried across i tiles;
  * at i=last — write best[kt].

One pass over the (K, N, N) ratio tensor in (K_T, N_T, J_T) VMEM tiles; the
(K, N) score matrix never round-trips to HBM between cost, drift and argmin
(the fusion the pure-XLA path cannot express across the argmin reduction).

VMEM budget/tile: r (8·128·128·4B = 512 KiB) + score/acc (2×4 KiB) + operand
tiles — comfortably under the ~16 MiB/core budget; J_T/N_T are lane-aligned
(128) and K_T sublane-aligned (8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.telemetry.scopes import GMSA_SCORE

K_T = 8      # job-type tile (sublane-aligned)
N_T = 128    # manager tile (lane-aligned)
J_T = 128    # executor tile (matvec contraction)


def _kernel(q_ref, mu_ref, a_ref, vp_ref, wpue_ref, r_ref,
            scores_ref, best_ref, acc_ref, minval_ref, minidx_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)
    ni = pl.num_programs(1)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Cost matvec on the MXU: (K_T*N_T, J_T) @ (J_T, 1).
    r_tile = r_ref[...].reshape(K_T * N_T, J_T)
    # HIGHEST: full-f32 MXU passes, the oracle's semantics (the default
    # rounds operands to bf16, which moves argmins near ties).
    partial = jax.lax.dot_general(
        r_tile, wpue_ref[...],                      # (J_T, 1)
        (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).reshape(K_T, N_T)
    acc_ref[...] += partial

    @pl.when(j == nj - 1)
    def _finalize_tile():
        score = a_ref[...] * (
            q_ref[...] - mu_ref[...] + vp_ref[...] * acc_ref[...]
        )
        scores_ref[...] = score
        row_min = jnp.min(score, axis=1, keepdims=True)            # (K_T, 1)
        local_arg = jnp.argmin(score, axis=1).astype(jnp.int32)
        row_arg = (local_arg + i * N_T).reshape(K_T, 1)

        @pl.when(i == 0)
        def _first():
            minval_ref[...] = row_min
            minidx_ref[...] = row_arg

        @pl.when(i > 0)
        def _update():
            better = row_min < minval_ref[...]
            minval_ref[...] = jnp.where(better, row_min, minval_ref[...])
            minidx_ref[...] = jnp.where(better, row_arg, minidx_ref[...])

        @pl.when(i == ni - 1)
        def _emit():
            best_ref[...] = minidx_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gmsa_score_kernel(q, mu, a, vp, wpue, r, *, interpret: bool = False):
    """Padded-shape entry point. q/mu: (K, N); a/vp: (K, 1); wpue: (N, 1);
    r: (K, N, N). K % K_T == 0, N % N_T == 0 (ops.py pads)."""
    k_dim, n_dim = q.shape
    grid = (k_dim // K_T, n_dim // N_T, n_dim // J_T)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K_T, N_T), lambda k, i, j: (k, i)),        # q
            pl.BlockSpec((K_T, N_T), lambda k, i, j: (k, i)),        # mu
            pl.BlockSpec((K_T, 1), lambda k, i, j: (k, 0)),          # a
            pl.BlockSpec((K_T, 1), lambda k, i, j: (k, 0)),          # vp
            pl.BlockSpec((J_T, 1), lambda k, i, j: (j, 0)),          # wpue
            pl.BlockSpec((K_T, N_T, J_T), lambda k, i, j: (k, i, j)),  # r
        ],
        out_specs=[
            pl.BlockSpec((K_T, N_T), lambda k, i, j: (k, i)),        # scores
            pl.BlockSpec((K_T, 1), lambda k, i, j: (k, 0)),          # best
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_dim, n_dim), jnp.float32),
            jax.ShapeDtypeStruct((k_dim, 1), jnp.int32),
        ],
        scratch_shapes=[
            # VMEM scratch persisting across the sequential TPU grid:
            pltpu.VMEM((K_T, N_T), jnp.float32),   # acc (cost matvec)
            pltpu.VMEM((K_T, 1), jnp.float32),     # running min
            pltpu.VMEM((K_T, 1), jnp.int32),       # running argmin
        ],
        interpret=interpret,
        name=GMSA_SCORE,
    )(q, mu, a, vp, wpue, r)
