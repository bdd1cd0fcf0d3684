"""Pallas TPU kernels: fused GMSA drift-plus-penalty score + argmin.

Two kernels, one program name (``GMSA_SCORE``); ``ops.py`` picks one by the
shapes it is handed.

**Per-run kernel** (``gmsa_score_kernel``): one problem, tiled for fleet
widths. Grid (nk, ni, nj), row-major sequential on TPU (j innermost):

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
(128) and K_T sublane-aligned (8). Vmapped over R Monte-Carlo runs it
becomes an R-step grid, each step padding one run to full tiles.

**Runs-on-lanes kernel** (``gmsa_score_lanes_kernel``): R problems of
N < N_T sites at once, the runs on the lane axis — operands laid out
``(K, N, R)``, R padded to whole run tiles of ``R_T`` lanes, and the grid
walks run tiles (then executor tiles), never single runs. Every product and
sum runs in float32 on the VPU, exact, as the oracle's HIGHEST contraction:

  * cost   — c[k, i, run] = sum_j r[k, i, j] * wpue[j]. Where neither r nor
             wpue differs between runs, c is one (K, N, 1) column, reduced
             over the lanes of r once per launch (first grid step, kept in
             VMEM scratch). Otherwise r arrives ``(N_j, K, N, R | 1)`` and
             wpue ``(N_j, 1, 1, R | 1)`` and each executor j adds its
             (K, N, R_T) product into an accumulator;
  * score  — a * (q - mu + vp * c) on (K, N, R_T) tiles;
  * argmin — over the site (sublane) axis: the lowest index among the
             minima, which is what a running (min, argmin) fold with a
             strict ``<`` keeps, so ties go to the lowest site as above.

Padded run lanes compute on zeros and are sliced away by ``ops.py``.
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

LANES = 128          # run tiles are whole multiples of the lane width
R_T_MAX = 1024       # widest run tile
VMEM_BUDGET = 8 * 2**20  # bytes a lanes launch may hold in VMEM: half of
                         # v5e's 16 MiB scoped default, the rest for Mosaic


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


def lanes_tiles(k: int, n: int, runs: int, r_runs: bool, w_runs: bool):
    """(R_T, J_B) for a runs-on-lanes launch, or None where no run tile fits.

    ``R_T`` is the widest multiple of ``LANES`` (at most ``R_T_MAX``, at
    most the padded run count) whose blocks fit ``VMEM_BUDGET``; ``J_B`` is
    the executors one grid step reads where the cost differs between runs
    (every executor where it does not). Blocks are double-buffered; a
    (..., 1) operand still fills a 128-lane tile, a (1, ...) row 8 sublanes.
    """
    f32, rows = 4, -(-n // 8) * 8
    shared = not (r_runs or w_runs)
    # Per run lane: q, mu, scores (K, N) and a, vp, best (K, 1) blocks, four
    # (K, N) temporaries of the body, and the accumulator where it is per run.
    lane = f32 * (2 * (3 * k * rows + 3 * k * 8) + (4 if shared else 5) * k * rows)
    # The shared cost: r (K, N, N) and wpue (1, 1, N) blocks, the cost column.
    fixed = f32 * (2 * (k * rows * -(-n // LANES) * LANES + 8 * LANES)
                   + k * rows * LANES) if shared else 0
    tile = min(R_T_MAX, -(-runs // LANES) * LANES)
    while True:
        room = VMEM_BUDGET - lane * tile - fixed
        per_j = 2 * f32 * (k * rows * (tile if r_runs else LANES)
                           + 8 * (tile if w_runs else LANES))
        if shared and room >= 0:
            return tile, n
        if not shared and room >= per_j:
            return tile, int(min(n, room // per_j))
        if tile == LANES:
            return None
        tile = max(LANES, tile // 2 // LANES * LANES)


def _lanes_kernel(q_ref, mu_ref, a_ref, vp_ref, w_ref, r_ref,
                  scores_ref, best_ref, cost_ref, *, shared: bool,
                  j_b: int):
    j = pl.program_id(1)

    if shared:
        @pl.when(pl.program_id(0) == 0)
        def _cost_once():
            # (K, N, N) * (1, 1, N), summed over the executor lanes.
            cost_ref[...] = jnp.sum(r_ref[...] * w_ref[...], axis=-1,
                                    keepdims=True)
    else:
        @pl.when(j == 0)
        def _init_acc():
            cost_ref[...] = jnp.zeros_like(cost_ref)

        def add(jj, acc):
            return acc + r_ref[jj] * w_ref[jj]    # (K, N, R_T | 1) x (1, 1, R_T | 1)

        cost_ref[...] = jax.lax.fori_loop(0, j_b, add, cost_ref[...],
                                          unroll=min(j_b, 8))

    @pl.when(j == pl.num_programs(1) - 1)
    def _score():
        score = a_ref[...] * (
            q_ref[...] - mu_ref[...] + vp_ref[...] * cost_ref[...]
        )                                                  # (K, N, R_T)
        scores_ref[...] = score
        low = jnp.min(score, axis=1, keepdims=True)
        site = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        best_ref[...] = jnp.min(jnp.where(score == low, site, score.shape[1]),
                                axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("r_t", "j_b", "interpret"))
def gmsa_score_lanes_kernel(q, mu, a, vp, wpue, r, *, r_t: int, j_b: int,
                            interpret: bool = False):
    """Runs-on-lanes entry point. q/mu: (K, N, Rp); a/vp: (K, 1, Rp), with
    Rp a multiple of ``r_t``. Cost shared by every run: r (K, N, N), wpue
    (1, 1, N). Else r (Nj, K, N, Rp | 1), wpue (Nj, 1, 1, Rp | 1), with Nj
    a multiple of ``j_b``. Returns scores (K, N, Rp), best (K, 1, Rp)."""
    k_dim, n_dim, r_pad = q.shape
    shared = r.ndim == 3
    lanes = lambda x: pl.BlockSpec((k_dim, x, r_t), lambda t, j: (0, 0, t))
    if shared:
        n_j = 1
        r_spec = pl.BlockSpec(r.shape, lambda t, j: (0, 0, 0))
        w_spec = pl.BlockSpec(wpue.shape, lambda t, j: (0, 0, 0))
        cost = pltpu.VMEM((k_dim, n_dim, 1), jnp.float32)
    else:
        n_j = r.shape[0] // j_b

        def executor_spec(x):                 # per run, or one for all runs
            runs = x.shape[-1] > 1
            return pl.BlockSpec((j_b,) + x.shape[1:-1] + (r_t if runs else 1,),
                                lambda t, j: (j, 0, 0, t if runs else 0))

        r_spec, w_spec = executor_spec(r), executor_spec(wpue)
        cost = pltpu.VMEM((k_dim, n_dim, r_t), jnp.float32)

    return pl.pallas_call(
        functools.partial(_lanes_kernel, shared=shared, j_b=j_b),
        grid=(r_pad // r_t, n_j),
        in_specs=[lanes(n_dim), lanes(n_dim), lanes(1), lanes(1),
                  w_spec, r_spec],
        out_specs=[lanes(n_dim), lanes(1)],
        out_shape=[
            jax.ShapeDtypeStruct((k_dim, n_dim, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((k_dim, 1, r_pad), jnp.int32),
        ],
        scratch_shapes=[cost],
        # Sequential: the shared cost is written on the first step only.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=GMSA_SCORE,
    )(q, mu, a, vp, wpue, r)
