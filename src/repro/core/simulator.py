"""Time-slotted trace-driven simulator (paper Sec. V).

One simulation run replays T slots:

    observe (A(t), Q(t), mu(t), omega(t), PUE(t))
      -> policy picks f(t)                       (GMSA / DATA / RANDOM / ...)
      -> Cost(t) accrues                          (repro.core.energy)
      -> queues update by Eq. 1                   (repro.core.queues)

The whole run is a single ``jax.lax.scan`` (jit-compiled); Monte-Carlo
replication is a ``jax.vmap`` over PRNG keys (the paper averages 1000 runs).
Policies are closures with signature
``(key, q, arrivals, mu, e, aux, scalar) -> f`` so GMSA and every baseline
share one engine; ``scalar`` carries a *traced* control parameter (GMSA's V)
so parameter sweeps reuse one compilation.

Perf notes (EXPERIMENTS.md §Perf wall-clock track):
  * the (K,N,N)×(N,) energy matvec is hoisted out of the scan body and
    computed for all T slots in one einsum — and it is *closed over* rather
    than vmapped, so Monte-Carlo runs share it;
  * policies that declare ``state_independent = True`` (DATA, RANDOM) are
    evaluated for all slots in one vectorized pass outside the scan;
  * policies that declare ``consumes_key = False`` (GMSA, JSQ, GREEDY —
    anything that deletes its key) skip the per-slot PRNG split entirely;
  * the per-slot body is then 4 fused elementwise/contraction ops.

Policies that declare ``wants_wpue = True`` receive ``aux = (data_dist,
omega_t * pue_t)`` instead of the bare distribution — the hook the fused
Pallas dispatch path (:func:`repro.core.gmsa.make_kernel_policy`) uses to
see raw per-slot prices; the product is hoisted out of the scan body.
Policies that additionally declare ``wants_r = True`` get the per-slot
ratio tensor appended — ``aux = (data_dist, wpue_t, r_t)`` — so the kernel
dispatch path sees time-varying ``(T, K, N, N)`` ratio traces instead of a
stale static binding; a policy marked ``static_r = True`` fed a
time-varying trace raises instead of silently dispatching on stale ratios.

Monte-Carlo replication shards across devices when ``simulate_many`` is
given a ``mesh`` (:func:`repro.distributed.mesh.runs_mesh`): the runs axis
partitions over the mesh with ``shard_map``, bitwise-identical to the
single-device vmap at every device count.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.energy import HIGHEST
from repro.core.queues import queue_step
from repro.telemetry.config import TelemetryConfig
from repro.telemetry.config import enabled as _tel_enabled
from repro.telemetry.config import histograms as _tel_hist
from repro.telemetry.metrics import hist_series
from repro.telemetry.ring import TelemetryFrame, ring_init
from repro.telemetry.scopes import GMSA_DECIDE, GMSA_SCAN, MC_DRAWS


class SimInputs(NamedTuple):
    """Trace bundle for one simulation run.

    Shapes: T slots, N DCs, K job types.

    ``r`` and ``data_dist`` may carry a leading time axis — (T, K, N, N) and
    (T, K, N) respectively — when the placement layer
    (:mod:`repro.placement`) evolves the dataset layout over the horizon;
    the static (K, N, N) / (K, N) forms remain the common case and are
    broadcast over all slots.
    """

    arrivals: Array   # (T, K)   jobs arriving per slot
    mu: Array         # (T, N, K) service rates per slot
    omega: Array      # (T, N)   energy-price weights
    pue: Array        # (T, N)   PUE traces
    r: Array          # (K, N, N) or (T, K, N, N) task-allocation ratios
    p_it: Array       # (K,)     per-job IT energy
    data_dist: Array  # (K, N) or (T, K, N) dataset distribution (policy aux)


class SimOutputs(NamedTuple):
    cost: Array           # (T,) per-slot energy cost
    energy: Array         # (T,) per-slot energy (PUE-weighted, unpriced)
    backlog_total: Array  # (T,) sum of all queue backlogs
    backlog_avg: Array    # (T,) mean backlog per (DC, type)
    q_final: Array        # (N, K)
    f_trace: Array        # (T, N, K) dispatch decisions


PolicyFn = Callable[..., Array]


def energy_tables(
    r: Array, wpue: Array, pue: Array, p_it: Array
) -> tuple[Array, Array]:
    """(T,K,N) dispatch cost and raw-energy tables in one einsum each.

    The single definition of the per-slot energy accounting, shared by
    ``simulate`` and the placement controller's per-epoch tables (the other
    half of the structural equivalence alongside :func:`slot_step`).
    ``r`` is (K, N, N) broadcast over slots, or (T, K, N, N) time-varying;
    ``wpue`` / ``pue`` are (T, N).
    """
    if r.ndim == 4:
        e_cost = jnp.einsum("tkij,tj->tki", r, wpue, precision=HIGHEST)
        e_raw = jnp.einsum("tkij,tj->tki", r, pue, precision=HIGHEST)
    else:
        e_cost = jnp.einsum("kij,tj->tki", r, wpue, precision=HIGHEST)
        e_raw = jnp.einsum("kij,tj->tki", r, pue, precision=HIGHEST)
    return e_cost * p_it[None, :, None], e_raw * p_it[None, :, None]


def energy_row(
    r: Array, wpue_t: Array, pue_t: Array, p_it: Array
) -> tuple[Array, Array]:
    """(K, N) dispatch cost and raw-energy tables for ONE slot.

    The per-slot form of :func:`energy_tables`, for control loops whose
    ratio tensor changes *inside* an epoch — the placement controller's
    off-schedule recovery epochs invalidate the precomputed epoch tables,
    and re-derive each remaining slot's row from the carried ``r``.
    """
    e_cost = jnp.einsum("kij,j->ki", r, wpue_t, precision=HIGHEST)
    e_raw = jnp.einsum("kij,j->ki", r, pue_t, precision=HIGHEST)
    return e_cost * p_it[:, None], e_raw * p_it[:, None]


def _energy_tables(inputs: SimInputs) -> tuple[Array, Array]:
    """(T,K,N) cost and raw-energy tables for every slot of a trace bundle."""
    return energy_tables(
        inputs.r, inputs.omega * inputs.pue, inputs.pue, inputs.p_it
    )


def slot_step(
    q: Array, f: Array, arrivals: Array, mu: Array, e_cost: Array, e_raw: Array
) -> tuple[Array, tuple]:
    """Advance one slot under dispatch ``f``: accrue cost/energy, step queues.

    The single definition of the per-slot semantics, shared by ``simulate``
    and the placement controller's fast loop (so their W >= T bit-exact
    equivalence is structural, not just test-enforced). Returns
    ``(q_next, (cost, energy, backlog_total, backlog_avg, f))`` — the scan
    output contract behind ``SimOutputs``' per-slot columns.

    Callers feeding this body masked inputs (the controller's fault path)
    must mask with exact identities (``* 1.0``, ``+ 0.0``) or selects —
    see ``drop_site_mask`` — so that bitwise-equal inputs keep producing
    bitwise-equal outputs under XLA's fusion choices.
    """
    fa = f * arrivals[None, :]
    cost = jnp.sum(fa * e_cost.T)
    energy = jnp.sum(fa * e_raw.T)
    q_next = queue_step(q, f, arrivals, mu)
    return q_next, (cost, energy, jnp.sum(q_next), jnp.mean(q_next), f)


@functools.partial(jax.jit, static_argnames=("policy", "telemetry"))
def simulate(
    inputs: SimInputs,
    policy: PolicyFn,
    key: Array,
    scalar: float | Array = 0.0,
    telemetry: TelemetryConfig | None = None,
    health: Array | None = None,
) -> SimOutputs | tuple[SimOutputs, TelemetryFrame]:
    """Run one trace-driven simulation under ``policy``.

    ``telemetry`` is **static**: ``None``/``OFF`` (default) traces to the
    byte-identical jaxpr of the pre-telemetry engine (pinned in tests);
    SUMMARY/TRACE adds a per-slot per-site backlog stream as an extra
    stacked scan output and returns ``(outputs, TelemetryFrame)`` —
    manager-switch events are derived post-scan from ``f_trace`` by
    :func:`repro.telemetry.collect.switch_events`, so this engine records
    nothing inside the scan body beyond the metric stream.

    ``health`` is an optional (T, N) degraded-mode factor
    (:func:`repro.traces.faults.health_trace`): per-slot service rates
    scale as ``mu * health`` — 0 = dead, interior = straggler — applied
    once *before* the scan (hoisted into the trace bundle, zero extra
    ops in the scan body). ``None`` leaves the engine's jaxpr untouched,
    and an all-ones trace is an exact ``* 1.0`` identity, so the
    degraded path is bitwise the nominal path when nothing degrades.
    """
    tel_on = _tel_enabled(telemetry)
    if health is not None:
        inputs = inputs._replace(
            mu=inputs.mu * jnp.asarray(health, inputs.mu.dtype)[:, :, None]
        )
    t_slots, k_types = inputs.arrivals.shape
    n = inputs.mu.shape[1]
    q0 = jnp.zeros((n, k_types), jnp.float32)
    e_cost_all, e_raw_all = _energy_tables(inputs)                 # (T, K, N)
    scalar = jnp.asarray(scalar, jnp.float32)

    dd_varying = inputs.data_dist.ndim == 3                        # (T, K, N)
    r_varying = inputs.r.ndim == 4                              # (T, K, N, N)
    uses_key = getattr(policy, "consumes_key", True)
    wants_wpue = getattr(policy, "wants_wpue", False)
    wants_r = getattr(policy, "wants_r", False)
    if r_varying and getattr(policy, "static_r", False):
        raise ValueError(
            "policy binds a static (K, N, N) ratio tensor but inputs.r is "
            "time-varying (T, K, N, N) — the kernel would silently dispatch "
            "on stale ratios. Build it with make_kernel_policy(r=None) so "
            "the per-slot r reaches the kernel through the policy aux."
        )
    if wants_r and not wants_wpue:
        raise ValueError(
            "wants_r policies must also declare wants_wpue: the aux "
            "contract is (data_dist, wpue_t, r_t)"
        )
    wpue_all = inputs.omega * inputs.pue if wants_wpue else None

    f_all = None
    if getattr(policy, "state_independent", False):
        keys = jax.random.split(key, t_slots)

        def call(kk, a, m, e, d, w, rr):
            aux = d
            if wants_wpue:
                aux = (aux, w)
            if wants_r:
                aux = aux + (rr,)
            with jax.named_scope(GMSA_DECIDE):
                return policy(kk, q0, a, m, e, aux, scalar)

        f_all = jax.vmap(
            call,
            in_axes=(0, 0, 0, 0, 0 if dd_varying else None,
                     0 if wants_wpue else None,
                     0 if r_varying else None),
        )(keys, inputs.arrivals, inputs.mu, e_cost_all,
          inputs.data_dist, wpue_all,
          inputs.r if wants_r else None)                           # (T, N, K)

    # The PRNG key rides in the scan carry ONLY when the policy actually
    # consumes it — for key-ignoring policies the per-slot threefry split
    # (and the whole key chain) disappears from the compiled body.
    keyed = f_all is None and uses_key
    key0 = key   # signature filler for key-ignoring policies (never used)

    def slot(carry, xs):
        q, key = carry if keyed else (carry, None)
        if wants_r and r_varying:
            xs, r_t = xs[:-1], xs[-1]
        if wants_wpue:
            xs, wpue_t = xs[:-1], xs[-1]
        if dd_varying:
            xs, aux = xs[:-1], xs[-1]
        else:
            aux = inputs.data_dist
        if wants_wpue:
            aux = (aux, wpue_t)
        if wants_r:
            aux = aux + ((r_t if r_varying else inputs.r),)
        if f_all is None:
            arrivals, mu, e_cost, e_raw = xs
            if keyed:
                key, sub = jax.random.split(key)
            else:
                sub = key0
            with jax.named_scope(GMSA_DECIDE):
                f = policy(sub, q, arrivals, mu, e_cost, aux, scalar)
        else:
            arrivals, mu, e_cost, e_raw, f = xs
        q_next, out = slot_step(q, f, arrivals, mu, e_cost, e_raw)
        if tel_on:
            out = out + (jnp.sum(q_next, axis=-1),)       # (N,) per-site q
        return ((q_next, key) if keyed else q_next), out

    xs = (inputs.arrivals, inputs.mu, e_cost_all, e_raw_all)
    if f_all is not None:
        xs = xs + (f_all,)
    if dd_varying:
        xs = xs + (inputs.data_dist,)
    if wants_wpue:
        xs = xs + (wpue_all,)
    if wants_r and r_varying:
        xs = xs + (inputs.r,)
    carry0 = (q0, key) if keyed else q0
    with jax.named_scope(GMSA_SCAN):
        final_carry, scan_outs = jax.lax.scan(slot, carry0, xs)
    if tel_on:
        (cost, energy, btot, bavg, f_trace, q_site) = scan_outs
    else:
        (cost, energy, btot, bavg, f_trace) = scan_outs
    q_final = final_carry[0] if keyed else final_carry
    outs = SimOutputs(cost, energy, btot, bavg, q_final, f_trace)
    if tel_on:
        metrics = {"q_site": q_site}
        if _tel_hist(telemetry):
            # Per-site energy-cost distribution, derived post-scan from
            # the stacked dispatch trace (zero ops in the scan body): the
            # per-slot (N,) site bill is sum_k (f·A) * e_cost, the same
            # contraction ``slot_step`` sums globally.
            site_cost = jnp.einsum(
                "tnk,tk,tkn->tn", f_trace, inputs.arrivals, e_cost_all
            )
            metrics["site_cost_hist"] = hist_series(
                telemetry.hist, site_cost, axis=0
            )                                                  # (N, B)
        return outs, TelemetryFrame(ring=ring_init(1), metrics=metrics)
    return outs


@functools.partial(
    jax.jit,
    static_argnames=("policy", "build_inputs", "n_runs", "telemetry", "mesh"),
)
def simulate_many(
    build_inputs: Callable[[Array], SimInputs],
    policy: PolicyFn,
    key: Array,
    n_runs: int,
    scalar: float | Array = 0.0,
    telemetry: TelemetryConfig | None = None,
    health: Array | None = None,
    mesh=None,
) -> SimOutputs:
    """Monte-Carlo replication: fresh traces + fresh policy randomness per run.

    ``build_inputs(key) -> SimInputs`` regenerates the stochastic traces
    (arrivals, service rates) for each run; deterministic traces (prices,
    PUE, ratios — and the degraded-mode ``health`` factor, when given)
    are closed over and shared. Outputs are stacked on a leading
    (n_runs,) axis (telemetry frames too, when enabled).

    ``mesh`` (static) shards the runs axis over a host-device mesh built by
    :func:`repro.distributed.mesh.runs_mesh` — same split keys, same
    per-run streams, bitwise-identical outputs at every device count;
    non-divisible ``n_runs`` is padded and sliced, never truncated.
    ``None`` keeps the single-device vmap.
    """
    keys = jax.random.split(key, n_runs)

    def one(run_key):
        k_build, k_sim = jax.random.split(run_key)
        with jax.named_scope(MC_DRAWS):
            inputs = build_inputs(k_build)
        return simulate(inputs, policy, k_sim, scalar, telemetry, health)

    if mesh is None:
        return jax.vmap(one)(keys)
    from repro.distributed.mesh import sharded_runs

    return sharded_runs(one, keys, mesh)


def summarize(outs: SimOutputs) -> dict:
    """Time-averaged scalars (averaged over runs if a runs axis is present)."""
    cost = jnp.mean(outs.cost)
    backlog = jnp.mean(outs.backlog_avg)
    return {
        "time_avg_cost": float(cost),
        "time_avg_energy": float(jnp.mean(outs.energy)),
        "time_avg_backlog": float(backlog),
        "final_backlog_total": float(jnp.mean(outs.q_final.sum(axis=(-2, -1)))),
    }
