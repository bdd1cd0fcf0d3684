"""jit'd public wrapper for the gmsa_score kernels: padding, layout, and the
vmap rule that picks a kernel by shape.

Called on one problem, ``gmsa_score`` runs the per-run tiled kernel. Under
``jax.vmap`` (the Monte-Carlo runs of ``simulate_many``, the placement
controller and the stage scheduler) its own batching rule sees which
operands carry the runs axis and decides by their shapes alone:

* N < N_T (one run fills less than one lane tile) and a run tile fits the
  VMEM budget (``kernel.lanes_tiles``): one runs-on-lanes launch decides
  every run. r and wpue stay shared where no run changes them; q, mu, a
  and vp are laid out per run.
* otherwise (N >= N_T: ``fleet_256`` and larger, where one run already
  fills the lanes): the per-run kernel under a plain vmap, as before.

A vmap around that one (a V sweep around the runs) folds its axis into the
runs axis, so nested vmaps still make one launch.

Padding semantics of the per-run kernel: managers are padded with q=+BIG so
a padded column can never win the argmin; job types pad with zeros (their
rows are discarded on slice-out); the executor axis pads r/wpue with zeros
(no cost contribution). The lanes kernel pads runs and executors with zeros
and slices the padded runs away.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.custom_batching import custom_vmap

from repro.kernels.gmsa_score.kernel import (
    J_T,
    K_T,
    N_T,
    gmsa_score_kernel,
    gmsa_score_lanes_kernel,
    lanes_tiles,
)

_BIG = 3e38


def _pad_to(x: Array, axis: int, mult: int, value: float = 0.0) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _per_run(q, mu, a, vp, r, wpue, interpret):
    k_dim, n_dim = q.shape
    qp = _pad_to(_pad_to(q.astype(jnp.float32), 1, N_T, _BIG), 0, K_T)
    mup = _pad_to(_pad_to(mu.astype(jnp.float32), 1, N_T), 0, K_T)
    ap = _pad_to(a.astype(jnp.float32)[:, None], 0, K_T, 1.0)
    vpp = _pad_to(vp.astype(jnp.float32)[:, None], 0, K_T)
    wp = _pad_to(wpue.astype(jnp.float32)[:, None], 0, J_T)
    rp = _pad_to(_pad_to(_pad_to(r.astype(jnp.float32), 2, J_T), 1, N_T), 0, K_T)

    scores, best = gmsa_score_kernel(qp, mup, ap, vpp, wp, rp, interpret=interpret)
    return scores[:k_dim, :n_dim], best[:k_dim, 0]


def _lanes(q, mu, a, vp, r, wpue, r_runs, w_runs, tiles, interpret):
    """q/mu (R, K, N), a/vp (R, K); r (R, K, N, N) if ``r_runs`` else
    (K, N, N); wpue (R, N) if ``w_runs`` else (N,). One launch."""
    runs, k_dim, n_dim = q.shape
    r_t, j_b = tiles
    f32 = lambda x: x.astype(jnp.float32)
    on_lanes = lambda x: _pad_to(jnp.moveaxis(f32(x), 0, -1), -1, r_t)
    qL, muL = on_lanes(q), on_lanes(mu)                    # (K, N, Rp)
    aL, vpL = on_lanes(a)[:, None], on_lanes(vp)[:, None]  # (K, 1, Rp)
    if not (r_runs or w_runs):
        rL, wL = f32(r), f32(wpue)[None, None]             # (K, N, N), (1, 1, N)
    else:
        # Executor-major: r (Nj, K, N, Rp | 1), wpue (Nj, 1, 1, Rp | 1).
        rL = on_lanes(r) if r_runs else f32(r)[..., None]  # (K, N, Nj, Rp | 1)
        wL = on_lanes(wpue) if w_runs else f32(wpue)[:, None]  # (Nj, Rp | 1)
        rL = _pad_to(rL.transpose(2, 0, 1, 3), 0, j_b)
        wL = _pad_to(wL[:, None, None], 0, j_b)
    scores, best = gmsa_score_lanes_kernel(qL, muL, aL, vpL, wL, rL, r_t=r_t,
                                           j_b=j_b, interpret=interpret)
    return (jnp.moveaxis(scores[..., :runs], -1, 0),       # (R, K, N)
            best[:, 0, :runs].T)                           # (R, K)


@functools.cache
def _lanes_entry(r_runs: bool, w_runs: bool, tiles, interpret: bool):
    """The lanes launch for one (r, wpue) batching, with a vmap rule that
    folds an outer vmap's axis B into the runs axis: (B, R, ...) operands
    become (B*R, ...), an operand the outer vmap alone batches is spread
    over the runs, and the outputs fold back to (B, R, ...)."""

    @custom_vmap
    def entry(q, mu, a, vp, r, wpue):
        return _lanes(q, mu, a, vp, r, wpue, r_runs, w_runs, tiles, interpret)

    @entry.def_vmap
    def fold(outer, batched, q, mu, a, vp, r, wpue):
        runs = q.shape[1] if batched[0] else q.shape[0]

        def merge(x, by_outer, by_runs):
            if not by_outer:
                x = jnp.broadcast_to(x, (outer,) + x.shape)
            if not by_runs:
                x = jnp.broadcast_to(x[:, None], (outer, runs) + x.shape[1:])
            return x.reshape((outer * runs,) + x.shape[2:])

        per_run = [merge(x, b, True) for x, b in zip((q, mu, a, vp), batched)]
        r_b, w_b = r_runs or batched[4], w_runs or batched[5]
        r = merge(r, batched[4], r_runs) if r_b else r
        wpue = merge(wpue, batched[5], w_runs) if w_b else wpue
        k_dim, n_dim = q.shape[-2:]
        inner = _lanes_entry(r_b, w_b, lanes_tiles(k_dim, n_dim, outer * runs,
                                                   r_b, w_b), interpret)
        scores, best = inner(*per_run, r, wpue)
        return ((scores.reshape((outer, runs) + scores.shape[1:]),
                 best.reshape((outer, runs) + best.shape[1:])), (True, True))

    return entry


@functools.cache
def _entry(interpret: bool):
    """``gmsa_score`` for one ``interpret``: the per-run kernel, with the
    vmap rule that picks a kernel by shape (module docstring)."""

    @custom_vmap
    def score(q, mu, a, vp, r, wpue):
        return _per_run(q, mu, a, vp, r, wpue, interpret)

    @score.def_vmap
    def batched_score(runs, batched, q, mu, a, vp, r, wpue):
        k_dim, n_dim = q.shape[-2:]
        r_runs, w_runs = batched[4], batched[5]
        tiles = lanes_tiles(k_dim, n_dim, runs, r_runs, w_runs)
        if n_dim >= N_T or tiles is None:
            axes = tuple(0 if b else None for b in batched)
            out = jax.vmap(functools.partial(_per_run, interpret=interpret),
                           in_axes=axes)(q, mu, a, vp, r, wpue)
            return out, (True, True)
        spread = lambda x, b: x if b else jnp.broadcast_to(x, (runs,) + x.shape)
        q, mu, a, vp = (spread(x, b) for x, b in zip((q, mu, a, vp), batched))
        out = _lanes_entry(r_runs, w_runs, tiles, interpret)(q, mu, a, vp, r, wpue)
        return out, (True, True)

    return score


@functools.partial(jax.jit, static_argnames=("interpret",))
def gmsa_score(
    q: Array,        # (K, N) backlogs (pre-transposed)
    mu: Array,       # (K, N) service rates
    a: Array,        # (K,)   arrivals
    vp: Array,       # (K,)   V * P^k
    r: Array,        # (K, N, N) task-allocation ratios
    wpue: Array,     # (N,)   omega ⊙ PUE
    *,
    interpret: bool | None = None,
) -> tuple[Array, Array]:
    """Fused dispatch scores + argmin. Returns (scores (K, N), best (K,));
    vmapped over R runs, (scores (R, K, N), best (R, K)).

    ``interpret=None`` resolves per backend
    (:func:`repro.kernels.default_interpret`): compiled on TPU, interpret
    elsewhere.
    """
    if interpret is None:
        from repro.kernels import default_interpret

        interpret = default_interpret()
    return _entry(interpret)(q, mu, a, vp, r, wpue)
