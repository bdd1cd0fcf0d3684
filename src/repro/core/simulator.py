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
so parameter sweeps reuse one compilation. What the engine must know about
a policy beyond that is its :class:`repro.core.policy.PolicySpec`:

  * ``state_independent`` policies (DATA, RANDOM) are decided for all
    slots in one vectorized pass outside the scan;
  * key-free policies (``keyed=False``: GMSA, JSQ, GREEDY) skip the
    per-slot PRNG split entirely;
  * ``reads`` picks the aux operand: the bare data distribution, or
    ``(data_dist, omega_t * pue_t)`` with the product hoisted out of the
    scan body — how the fused Pallas dispatch
    (:func:`repro.core.gmsa.make_kernel_policy`) sees raw per-slot prices —
    or that pair plus the ratio tensor in force this slot, ``r_t`` sliced
    from a time-varying ``(T, K, N, N)`` trace;
  * a policy that ``binds_r`` raises when fed a time-varying trace instead
    of silently dispatching on stale ratios.

The (K,N,N)×(N,) energy matvec is hoisted out of the scan body and computed
for all T slots in one einsum, closed over rather than vmapped so
Monte-Carlo runs share it; the per-slot body is then 4 fused
elementwise/contraction ops.

Monte-Carlo replication shards across devices when ``simulate_many`` is
given a ``mesh`` (:func:`repro.distributed.mesh.runs_mesh`): the runs axis
partitions over the mesh with ``shard_map``, bitwise-identical to the
single-device vmap at every device count.
"""

from __future__ import annotations

import functools
from collections import namedtuple
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.energy import HIGHEST
from repro.core.policy import check_ratios, decide_all_slots, slot_aux, spec_of
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


class ScanCarry(NamedTuple):
    """What the engines' scans carry from step to step; ``None`` if absent."""

    q: Array                 # backlogs
    key: Array | None = None    # PRNG chain (keyed policies only)
    d: Array | None = None      # placement layout (controller)
    r: Array | None = None      # ratio tensor in force (controller faults)
    fired: Array | None = None  # a recovery re-placed this epoch
    ring: Any = None            # telemetry event ring (controller TRACE)


class SlotCols(NamedTuple):
    """One slot's stacked scan outputs: :func:`slot_step`'s five columns,
    then the ones an engine records only under some options."""

    cost: Array
    energy: Array
    backlog_total: Array
    backlog_avg: Array
    f: Array
    recovery_cost: Array | None = None  # controller fault path
    recovery_gb: Array | None = None
    q_site: Array | None = None         # telemetry: (N,) per-site backlog
    site_cost: Array | None = None      # telemetry histograms (controller)


#: ``simulate``'s per-slot scan operands, ``None`` where absent: ``f`` a
#: state-independent policy's decisions, ``dist``/``r`` time-varying traces.
_SlotXs = namedtuple("_SlotXs", "arrivals mu e_cost e_raw f dist wpue r")


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
    spec = spec_of(policy)
    check_ratios(spec, r_varying)
    wpue_all = inputs.omega * inputs.pue if spec.reads != "dist" else None

    f_all = None
    if spec.state_independent:
        f_all = decide_all_slots(policy, spec, key, q0, inputs, e_cost_all,
                                 wpue_all, scalar)                 # (T, N, K)

    # The PRNG key rides in the scan carry ONLY when the policy actually
    # consumes it — for key-ignoring policies the per-slot threefry split
    # (and the whole key chain) disappears from the compiled body.
    keyed = f_all is None and spec.keyed
    key0 = key   # signature filler for key-ignoring policies (never used)

    def slot(carry, xs):
        if f_all is None:
            if keyed:
                key, sub = jax.random.split(carry.key)
            else:
                key, sub = None, key0
            aux = slot_aux(
                spec,
                inputs.data_dist if xs.dist is None else xs.dist,
                xs.wpue,
                inputs.r if xs.r is None else xs.r,
            )
            with jax.named_scope(GMSA_DECIDE):
                f = policy(sub, carry.q, xs.arrivals, xs.mu, xs.e_cost, aux,
                           scalar)
        else:
            key, f = None, xs.f
        q_next, out = slot_step(carry.q, f, xs.arrivals, xs.mu, xs.e_cost,
                                xs.e_raw)
        q_site = jnp.sum(q_next, axis=-1) if tel_on else None  # (N,) per-site q
        return ScanCarry(q_next, key), SlotCols(*out, q_site=q_site)

    xs = _SlotXs(
        inputs.arrivals, inputs.mu, e_cost_all, e_raw_all, f=f_all,
        dist=inputs.data_dist if dd_varying else None,
        wpue=wpue_all,
        r=inputs.r if spec.reads == "wpue_r" and r_varying else None,
    )
    carry0 = ScanCarry(q0, key if keyed else None)
    with jax.named_scope(GMSA_SCAN):
        final_carry, scan_outs = jax.lax.scan(slot, carry0, xs)
    f_trace = scan_outs.f
    outs = SimOutputs(scan_outs.cost, scan_outs.energy,
                      scan_outs.backlog_total, scan_outs.backlog_avg,
                      final_carry.q, f_trace)
    if tel_on:
        metrics = {"q_site": scan_outs.q_site}
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
