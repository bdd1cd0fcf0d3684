"""``simulate_staged`` — trace-driven engine for stage-structured jobs.

Generalizes :func:`repro.core.simulator.simulate` from one queue per
(DC, type) to one per (DC, type, stage): per slot, each stage's inflow is
dispatched by the policy's (N, K, S) decision, Eq. 1 advances every stage
queue (the scan body evaluates :func:`repro.core.simulator.slot_step`'s
own expressions with the stage axis folded into the type axis, so the
single-stage equivalence with ``simulate`` stays bitwise — pinned in
tests), completions flow down the chain within the slot (a tandem of
queues), and the intermediate bytes
each hop ships across the WAN are billed through
:func:`repro.placement.wan.plan_cost` — the fused bilinear form of
``transfer_cost(transfer_plan(...))``, same surplus/deficit coupling
semantics (a stage whose destination mix equals its source mix — a
data-local map, a co-located reduce — moves nothing) but no (K, N, N)
plan is ever materialized in the scan body.

The per-slot semantics, stage by stage (s = 0..S-1, a static unrolled
loop):

    in^{k,s}   = f^{k,s} * F^{k,s}          F^{k,0} = A^k(t), else the
                                            upstream completions
    Q^{k,s}    + Eq. 1 under (in, mu / c^{k,s})
    done^{k,s} = min(Q + in, mu/c)          flows to stage s+1 (or out)
    WAN bill   = plan_cost(src^{k,s}, f^{k,s}, F^{k,s} * G^{k,s})
                 (== transfer_cost(transfer_plan(...)) to ≤ 1e-5 rel.)

With a single-stage dag (compute 1, shuffle 0) every extra term is an
exact float identity and ``simulate_staged`` reproduces ``simulate``'s
cost/backlog/dispatch bit for bit on every policy — the test suite pins
this down. ``r`` and ``data_dist`` may carry a leading time axis exactly
as in ``simulate``, which is how the subsystem composes with
:func:`repro.placement.controller.simulate_placed`: run the slow loop,
repeat its per-epoch ``placements``/``r_trace`` per slot, and feed them
here — re-placement reshapes the map stage's locality (and the whole
chain's shuffle sources) over the horizon.

The whole run is one ``jax.lax.scan`` (jit); Monte-Carlo replication is a
``jax.vmap`` over PRNG keys (``simulate_staged_many``), sharing one
compilation — the same perf structure as the base simulator.
"""

from __future__ import annotations

import functools
from collections import namedtuple
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.core.policy import check_ratios, decide_all_slots, slot_aux, spec_of
from repro.core.simulator import (
    PolicyFn,
    ScanCarry,
    SimInputs,
    _energy_tables,
)
from repro.jobs.dag import StageDag
from repro.jobs.scheduler import stage_oblivious, stage_service_rates_all
from repro.placement.wan import WanModel, degraded_surcharge, plan_cost
from repro.telemetry.config import TelemetryConfig
from repro.telemetry.config import enabled as _tel_enabled
from repro.telemetry.config import histograms as _tel_hist
from repro.telemetry.metrics import hist_series
from repro.telemetry.ring import TelemetryFrame, ring_init

#: Zero-flow guard for the source-mix normalization — the same epsilon
#: :func:`repro.jobs.scheduler.flow_step` uses, so the engine's replayed
#: mixes equal the policy lookahead's exactly.
_EPS = 1e-12


def hedged_mu(f: Array, g: Array, mu_stages: Array) -> Array:
    """First-completion service rates under the clone matrix ``g``.

    The boost the policy's flow walk applied, re-derived from the same
    inputs — ``mu + f · (Σ_n g·mu)`` — so the engine's Eq. 1 drains
    exactly the flow the scheduler exported. Gated behind ``lax.cond``:
    slots where no hedge fired keep the unboosted rates bit-for-bit
    (and pay the branch, not the FMA, in the scan body).
    """

    def boosted(ms):
        boost = jnp.sum(g * ms, axis=0)                        # (K, S)
        return ms + f * boost[None]

    return jax.lax.cond(
        jnp.any(g > 0.0), boosted, lambda ms: ms, mu_stages
    )


def staged_slot_update(
    dag: StageDag,
    q: Array,
    ret,
    arrivals: Array,
    mu_stages: Array,
    returns: str = "f",
) -> tuple[Array, Array, Array, Array]:
    """One slot of the staged engine: tandem flow + Eq. 1 for every stage.

    ``ret`` is the policy's output, of the kind its spec ``returns``
    (:class:`repro.core.policy.PolicySpec`) — ``(f, in_stack)`` for
    ``"flow"`` (the stage-aware scheduler already walked the within-slot flow
    via :func:`repro.jobs.scheduler.flow_step`), bare ``f`` otherwise (the
    recursion is replayed here). ``"flow_hedge"`` policies append the
    (N, K, S) speculative-clone matrix ``g`` — the queues then drain at
    the first-completion boosted rates (:func:`hedged_mu`), cond-gated so
    hedge-free slots stay bit-identical. This is the SINGLE definition of
    the per-slot staged update: :func:`simulate_staged`'s scan body calls
    it, and :class:`repro.serve.engine.FleetEngine`'s serving loop calls
    it on live traffic — which is what makes a dispatch-only serving run
    replay bit-for-bit against the simulator on a shared scenario.

    Returns:
        (q_next, f, acc, in_stack): the advanced (N, K, S) queues, the
        dispatch decision, the landed mass ``q + f·in`` (the inside of
        Eq. 1's max) and the (K, S) per-stage inflows.
    """
    s_max = dag.s_max
    if returns == "flow_hedge":
        f, in_stack, g = ret
        acc = q + f * in_stack[None, :, :]                         # (N, K, S)
        mu_stages = hedged_mu(f, g, mu_stages)
    elif returns == "flow":
        f, in_stack = ret
        acc = q + f * in_stack[None, :, :]                         # (N, K, S)
    else:
        f = ret
        total_in = arrivals                                        # (K,)
        ins, accs = [], []
        for s in range(s_max):
            ins.append(total_in)
            acc_s = q[:, :, s] + f[:, :, s] * total_in[None, :]
            accs.append(acc_s)
            if s + 1 < s_max:
                done_s = jnp.minimum(acc_s, mu_stages[:, :, s])
                total_in = (jnp.sum(done_s, axis=0)
                            * dag.stage_mask[:, s + 1])
        acc = jnp.stack(accs, axis=-1)                             # (N, K, S)
        in_stack = jnp.stack(ins, axis=-1)                         # (K, S)

    # Eq. 1 for ALL stages at once, the stage axis folded into the
    # type axis (one queue per (DC, type·stage)). The expression is
    # ``slot_step``'s own — ``max((q + fa) - mu, 0)`` — and for S = 1
    # every reshape is the identity, keeping the single-stage path
    # bitwise the base engine's.
    q_next = jnp.maximum(acc - mu_stages, 0.0)
    return q_next, f, acc, in_stack


def staged_shuffle_mixes(
    f_trace: Array,
    in_all: Array,
    done_all: Array,
    dd_all: Array,
    dag: StageDag,
) -> tuple[Array, Array, Array]:
    """Source/destination mixes + volumes for every (slot, stage) shuffle.

    Vectorized over the whole horizon from the stacked per-slot outputs:
    stage 0 pulls from ``data_dist``; stage s > 0 pulls from where stage
    s-1's completions actually ran (uniform fallback for zero flow — the
    volume is zero there, so the choice is billing-inert, the same
    ``flow_step`` contract the policy lookahead uses).

    Returns:
        (src, dst, vol): (T, S, K, N), (T, S, K, N), (T, S, K).
    """
    t_slots, n = f_trace.shape[0], f_trace.shape[1]
    s_max = dag.s_max
    td_all = jnp.sum(done_all, axis=1)                             # (T,K,S)
    if s_max == 1:
        src_all = dd_all[:, None]                                  # (T,1,K,N)
    else:
        done_up = done_all[:, :, :, :-1].transpose(0, 3, 2, 1)     # (T,S-1,K,N)
        td_up = td_all[:, :, :-1].transpose(0, 2, 1)[..., None]    # (T,S-1,K,1)
        src_up = jnp.where(
            td_up > _EPS, done_up / jnp.maximum(td_up, _EPS), 1.0 / n
        )                                                          # (T,S-1,K,N)
        src_all = jnp.concatenate([dd_all[:, None], src_up], axis=1)
    dst_all = f_trace.transpose(0, 3, 2, 1)                        # (T,S,K,N)
    vol_all = (in_all * dag.shuffle_gb[None]).transpose(0, 2, 1)   # (T,S,K)
    return src_all, dst_all, vol_all


def _hedge_bill(
    dag: StageDag,
    wan: WanModel,
    g_all: Array,
    acc_all: Array,
    mu_stage_all: Array,
    mu_eff_all: Array,
    ec_stage_all: Array,
    src_all: Array,
    wpue_all: Array,
) -> tuple[Array, Array, Array]:
    """Honest post-scan bill for speculative re-execution.

    In the fluid first-completion model the clone's contribution is the
    boost-attributable completions — ``min(acc, mu_eff) - min(acc, mu)``
    — re-executed at the clone site. Each re-executed job-unit bills the
    clone site's per-stage energy cost (compute) plus the expected WAN
    pull of the stage's input shuffle from the upstream source mix to
    the clone site (the same fused rank-2 expected-pull form the
    scheduler prices dispatch with). All (T,)-vectorized, nothing in the
    scan body.

    Returns:
        (hedge_cost, hedge_gb, hedged_jobs) — (T,) each: total $ billed
        (compute + WAN pull), GB pulled to clone sites, and re-executed
        job-units completed by clones.
    """
    extra = (jnp.minimum(acc_all, mu_eff_all)
             - jnp.minimum(acc_all, mu_stage_all))             # (T,N,K,S)
    extra_ks = jnp.sum(extra, axis=1)                          # (T,K,S)
    ec_clone = jnp.einsum("tnks,tksn->tks", g_all, ec_stage_all)
    compute_bill = jnp.sum(extra_ks * ec_clone, axis=(1, 2))   # (T,)
    g_skn = g_all.transpose(0, 3, 2, 1)                        # (T,S,K,N)
    w = wpue_all[:, None, None, :]                             # (T,1,1,N)
    dot = jnp.sum(src_all * w, axis=-1)                        # (T,S,K)
    pull = 0.5 * (dot[..., None] + w) - src_all * w            # (T,S,K,N)
    price_clone = jnp.sum(pull * g_skn, axis=-1)               # (T,S,K)
    vol = extra_ks.transpose(0, 2, 1) * dag.shuffle_gb.T[None]  # (T,S,K)
    wan_bill = wan.energy_per_gb * jnp.sum(price_clone * vol, axis=(1, 2))
    hedge_gb = jnp.sum(vol, axis=(1, 2))
    hedged_jobs = jnp.sum(extra_ks, axis=(1, 2))
    return compute_bill + wan_bill, hedge_gb, hedged_jobs


#: ``simulate_staged``'s per-slot scan operands and outputs, ``None`` where
#: absent (as in :func:`repro.core.simulator.simulate`; ``g`` is the clone
#: matrix of hedging policies).
_StagedXs = namedtuple("_StagedXs", "arrivals mu e_cost mu_stages wpue f dist r")
_StagedCols = namedtuple("_StagedCols", "f acc in_stack g")


class StagedOutputs(NamedTuple):
    """Per-slot traces of one staged run (leading runs axis under vmap).

    The three hedge columns are all-zero for policies that return no clone
    matrix (any spec but ``returns="flow_hedge"``) and on healthy fleets
    where the hedge never fires, so downstream consumers need no feature
    detection.
    """

    cost: Array           # (T,) per-slot stage-compute energy cost
    energy: Array         # (T,) PUE-weighted compute energy (unpriced)
    backlog_total: Array  # (T,) sum over all (DC, type, stage) backlogs
    backlog_avg: Array    # (T,) mean backlog per (DC, type, stage)
    q_final: Array        # (N, K, S)
    f_trace: Array        # (T, N, K, S) per-stage dispatch decisions
    wan_cost: Array       # (T,) $ billed for intermediate-data movement
    wan_energy: Array     # (T,) WAN energy (job-energy equivalents)
    wan_gb: Array         # (T,) intermediate GB crossing the WAN
    completed: Array      # (T, K) jobs finishing their last stage per slot
    hedge_cost: Array     # (T,) $ billed for speculative re-execution
    hedge_gb: Array       # (T,) GB pulled to clone sites by hedges
    hedged_jobs: Array    # (T,) job-units completed by speculative clones


@functools.partial(jax.jit, static_argnames=("policy", "telemetry"))
def simulate_staged(
    inputs: SimInputs,
    dag: StageDag,
    wan: WanModel,
    policy: PolicyFn,
    key: Array,
    scalar: float | Array = 0.0,
    telemetry: TelemetryConfig | None = None,
    health: Array | None = None,
    link_health: Array | None = None,
) -> StagedOutputs | tuple[StagedOutputs, TelemetryFrame]:
    """Run one stage-structured trace-driven simulation under ``policy``.

    Args:
        inputs: the usual trace bundle; ``r``/``data_dist`` may be static
            or time-varying exactly as in ``simulate``.
        dag: the (K, S) stage chain.
        wan: WAN model pricing the inter-stage shuffle bytes.
        policy: a staged policy (spec ``staged=True``, signature
            ``(key, q(N,K,S), arrivals, mu, e, (data_dist, wpue), scalar)
            -> f(N,K,S)``) or any base simulator policy, which is wrapped
            by :func:`repro.jobs.scheduler.stage_oblivious` automatically.
        key: PRNG key (consumed exactly as ``simulate`` does, on both the
            precomputed and the carried-key policy paths).
        scalar: traced control parameter forwarded to the policy (GMSA's V).
        telemetry: **static** flight-recorder config. ``None``/``OFF``
            (default) keeps the jaxpr byte-identical to the pre-telemetry
            engine. Enabled levels return ``(outputs, TelemetryFrame)``
            whose metrics are per-(slot, stage) streams — backlog and the
            WAN bill split by stage. Everything is derived post-scan from
            the stacked ``(f, acc, ins)`` outputs the fast path already
            produces (the PR-4 structure), so TRACE adds ZERO ops to the
            scan body here; the per-stage billing runs ``plan_cost``
            batched once more over ``(T, S)`` without the type-axis fold.
        health: optional (T, N) degraded-mode factor
            (:func:`repro.traces.faults.health_trace`): per-slot service
            rates scale as ``mu * health``, hoisted into the trace
            bundle before any table is derived — zero extra ops in the
            scan body, and an all-ones trace is an exact ``* 1.0``
            identity (``None`` leaves the jaxpr untouched).
        link_health: optional (T, N, N) link factor
            (:func:`repro.traces.bandwidth.link_fault_trace`): the WAN
            bill gains the post-scan
            :func:`repro.placement.wan.degraded_surcharge` premium —
            degraded links cost more, severed links carrying traffic
            bill ``inf`` — added on top of the untouched fused bill (an
            exact ``+ 0.0`` identity on an all-nominal trace).
    """
    tel_on = _tel_enabled(telemetry)
    if health is not None:
        inputs = inputs._replace(
            mu=inputs.mu * jnp.asarray(health, inputs.mu.dtype)[:, :, None]
        )
    t_slots, k_types = inputs.arrivals.shape
    n = inputs.mu.shape[1]
    s_max = dag.s_max
    if dag.compute.shape[0] != k_types:
        raise ValueError(
            f"dag is for K={dag.compute.shape[0]} types, inputs carry "
            f"K={k_types}"
        )
    q0 = jnp.zeros((n, k_types, s_max), jnp.float32)
    e_cost_all, e_raw_all = _energy_tables(inputs)                 # (T, K, N)
    wpue_all = inputs.omega * inputs.pue                           # (T, N)
    scalar = jnp.asarray(scalar, jnp.float32)

    # Perf (EXPERIMENTS.md §Perf): everything per-slot-invariant is hoisted
    # out of the scan body — per-stage service rates (mu / c), the per-stage
    # energy tables (e * c, already laid out (K, S, N) so the in-body
    # flatten to (K·S, N) is a free reshape) and omega*PUE are computed for
    # all T slots in one pass each. Stage padding uses exact identities
    # (c = 1.0), so the single-stage tables are bitwise the base engine's.
    mu_stage_all = stage_service_rates_all(inputs.mu, dag)         # (T,N,K,S)
    ec_stage_all = e_cost_all[:, :, None, :] * dag.compute[None, :, :, None]
    er_stage_all = e_raw_all[:, :, None, :] * dag.compute[None, :, :, None]

    pol = policy if spec_of(policy).staged else stage_oblivious(policy)
    spec = spec_of(pol)
    hedging = spec.returns == "flow_hedge"
    dd_varying = inputs.data_dist.ndim == 3                        # (T, K, N)
    r_varying = inputs.r.ndim == 4                              # (T, K, N, N)
    check_ratios(spec, r_varying)

    f_all = None
    if spec.state_independent:
        f_all = decide_all_slots(pol, spec, key, q0, inputs, e_cost_all,
                                 wpue_all, scalar)

    keyed = f_all is None and spec.keyed
    key0 = key   # for key-ignoring policies (signature filler, never used)

    def slot(carry, xs):
        if f_all is None:
            if keyed:
                key, sub = jax.random.split(carry.key)
            else:
                key, sub = None, key0   # key-ignoring policy: no split
            aux = slot_aux(
                spec,
                inputs.data_dist if xs.dist is None else xs.dist,
                xs.wpue,
                inputs.r if xs.r is None else xs.r,
            )
            ret = pol(sub, carry.q, xs.arrivals, xs.mu, xs.e_cost, aux, scalar)
        else:
            key, ret = None, xs.f

        # Within-slot tandem flow — the only genuinely sequential part,
        # stripped to its recursion via the shared :func:`staged_slot_update`
        # (acc = Q + f·F is the inside of Eq. 1's max — exactly
        # ``slot_step``'s ``q + fa``; completions min(acc, mu) seed the next
        # stage). Policies that walked this exact chain already
        # (``returns="flow"`` — the stage-aware scheduler's lookahead
        # shares flow_step's definition) export the per-stage inflows and
        # the recursion is skipped entirely. Everything derivable from
        # (f, acc, ins) — cost/energy accrual, backlogs, source mixes,
        # shuffle volumes, completions, the WAN bill — is recomputed
        # vectorized over all T slots AFTER the scan, keeping the per-slot
        # body minimal.
        q_next, f, acc, in_stack = staged_slot_update(
            dag, carry.q, ret, xs.arrivals, xs.mu_stages, spec.returns
        )
        g = ret[2] if hedging else None
        return ScanCarry(q_next, key), _StagedCols(f, acc, in_stack, g)

    xs = _StagedXs(
        inputs.arrivals, inputs.mu, e_cost_all, mu_stage_all, wpue_all,
        f=f_all, dist=inputs.data_dist if dd_varying else None,
        r=inputs.r if spec.reads == "wpue_r" and r_varying else None,
    )
    carry0 = ScanCarry(q0, key if keyed else None)
    final_carry, scan_outs = jax.lax.scan(slot, carry0, xs)
    f_trace, acc_all, in_all, g_all = scan_outs
    q_final = final_carry.q

    # Everything the scan body did NOT compute, recovered vectorized over
    # all T slots from (f, acc, ins) — the expressions are ``slot_step``'s
    # and ``flow_step``'s own, evaluated batched so each is one kernel for
    # the whole horizon instead of T per-slot launches:
    #   * cost/energy: sum(fa * e.T) with fa = f * in;
    #   * backlogs: q_next = max(acc - mu, 0) summed/averaged;
    #   * completions per stage: min(acc, mu) summed over sites;
    #   * source mixes + shuffle volumes + the WAN bill — billed for ALL
    #     (slot, stage) pairs in ONE fused batched plan_cost call, stages
    #     folded into the type axis; no (K, N, N) plan is materialized.
    fa_all = f_trace * in_all[:, None]                             # (T,N,K,S)
    cost = jnp.sum(fa_all * ec_stage_all.transpose(0, 3, 1, 2),
                   axis=(1, 2, 3))                                 # (T,)
    energy = jnp.sum(fa_all * er_stage_all.transpose(0, 3, 1, 2),
                     axis=(1, 2, 3))
    if hedging:
        # The carried queues drained at the first-completion boosted
        # rates (cond-gated in the scan body); the vectorized replay
        # applies the same boost unconditionally — slots without a
        # hedge add an exact ``f * 0.0`` identity, so the stats stay
        # bitwise the scan's.
        boost_all = jnp.sum(g_all * mu_stage_all, axis=1)          # (T,K,S)
        mu_eff_all = mu_stage_all + f_trace * boost_all[:, None]
    else:
        mu_eff_all = mu_stage_all
    q_next_all = jnp.maximum(acc_all - mu_eff_all, 0.0)            # (T,N,K,S)
    btot = jnp.sum(q_next_all, axis=(1, 2, 3))
    bavg = btot / jnp.float32(n * k_types * s_max)
    done_all = jnp.minimum(acc_all, mu_eff_all)                    # (T,N,K,S)
    td_all = jnp.sum(done_all, axis=1)                             # (T,K,S)
    nxt = jnp.concatenate(
        [dag.stage_mask[:, 1:], jnp.zeros((k_types, 1), jnp.float32)], axis=1
    )
    completed = jnp.einsum("tks,ks->tk", td_all, dag.stage_mask - nxt)

    dd_all = (
        inputs.data_dist
        if dd_varying
        else jnp.broadcast_to(inputs.data_dist, (t_slots, k_types, n))
    )                                                              # (T, K, N)
    src_all, dst_all, vol_all = staged_shuffle_mixes(
        f_trace, in_all, done_all, dd_all, dag
    )
    wan_c, wan_e, wan_gb = plan_cost(
        src_all.reshape(t_slots, s_max * k_types, n),
        dst_all.reshape(t_slots, s_max * k_types, n),
        vol_all.reshape(t_slots, s_max * k_types),
        wan, inputs.omega, inputs.pue,
    )                                                              # (T,) each
    if link_health is not None:
        # Degraded-link premium on the shuffle traffic, additive to the
        # untouched fused bill (exact zero on an all-nominal trace).
        sur_c, sur_e = degraded_surcharge(
            src_all.reshape(t_slots, s_max * k_types, n),
            dst_all.reshape(t_slots, s_max * k_types, n),
            vol_all.reshape(t_slots, s_max * k_types),
            wan, inputs.omega, inputs.pue, link_health,
        )
        wan_c = wan_c + sur_c
        wan_e = wan_e + sur_e
    if hedging:
        hedge_cost, hedge_gb, hedged_jobs = _hedge_bill(
            dag, wan, g_all, acc_all, mu_stage_all, mu_eff_all,
            ec_stage_all, src_all, wpue_all,
        )
    else:
        zeros_t = jnp.zeros((t_slots,), jnp.float32)
        hedge_cost = hedge_gb = hedged_jobs = zeros_t
    outs = StagedOutputs(
        cost=cost, energy=energy, backlog_total=btot, backlog_avg=bavg,
        q_final=q_final, f_trace=f_trace,
        wan_cost=wan_c, wan_energy=wan_e, wan_gb=wan_gb,
        completed=completed,
        hedge_cost=hedge_cost, hedge_gb=hedge_gb, hedged_jobs=hedged_jobs,
    )
    if tel_on:
        # Per-stage streams, recovered from the same stacked (f, acc, ins)
        # outputs — the per-(slot, stage) WAN split is a SECOND batched
        # plan_cost call over (T, S) (stages as the leading batch dim, the
        # type axis folded as usual), leaving the fused OFF-path bill and
        # its reduction order untouched.
        stage_backlog = jnp.sum(q_next_all, axis=(1, 2))           # (T, S)
        sw_c, _, sw_gb = plan_cost(
            src_all.transpose(1, 0, 2, 3),                         # (S,T,K,N)
            dst_all.transpose(1, 0, 2, 3),
            vol_all.transpose(1, 0, 2),                            # (S,T,K)
            wan, inputs.omega, inputs.pue,
        )                                                          # (S, T)
        metrics = {
            "q_site": jnp.sum(q_next_all, axis=(2, 3)),            # (T, N)
            "stage_backlog": stage_backlog,                        # (T, S)
            "stage_wan_cost": sw_c.T,                              # (T, S)
            "stage_wan_gb": sw_gb.T,                               # (T, S)
        }
        if _tel_hist(telemetry):
            # Per-(slot, stage) queue delay in slots — the stage's total
            # backlog over its fleet-wide service capacity (the fluid
            # analogue of "how long does work admitted now wait here") —
            # histogrammed per stage over the horizon, post-scan.
            cap_stage = jnp.sum(mu_stage_all, axis=(1, 2))         # (T, S)
            delay = stage_backlog / jnp.maximum(cap_stage, _EPS)
            metrics["queue_delay"] = delay                         # (T, S)
            metrics["queue_delay_hist"] = hist_series(
                telemetry.hist, delay, axis=0
            )                                                      # (S, B)
        return outs, TelemetryFrame(ring=ring_init(1), metrics=metrics)
    return outs


@functools.partial(
    jax.jit,
    static_argnames=("policy", "build_inputs", "n_runs", "telemetry", "mesh"),
)
def simulate_staged_many(
    build_inputs: Callable[[Array], SimInputs],
    dag: StageDag,
    wan: WanModel,
    policy: PolicyFn,
    key: Array,
    n_runs: int,
    scalar: float | Array = 0.0,
    telemetry: TelemetryConfig | None = None,
    health: Array | None = None,
    link_health: Array | None = None,
    mesh=None,
) -> StagedOutputs:
    """Monte-Carlo replication of :func:`simulate_staged` (vmap over keys).

    Mirrors ``simulate_many``: fresh stochastic traces + policy randomness
    per run, deterministic traces (prices, PUE, the dag, the WAN model —
    and the degraded-mode health/link traces, when given) shared. One
    compilation serves every run; telemetry frames (when enabled) stack
    on the leading runs axis like every other output.

    ``mesh`` (static) shards the runs axis over a host-device mesh
    (:func:`repro.distributed.mesh.runs_mesh`) — same split keys, bitwise
    the single-device outputs at every device count.
    """
    keys = jax.random.split(key, n_runs)

    def one(run_key):
        k_build, k_sim = jax.random.split(run_key)
        return simulate_staged(
            build_inputs(k_build), dag, wan, policy, k_sim, scalar,
            telemetry, health, link_health,
        )

    if mesh is None:
        return jax.vmap(one)(keys)
    from repro.distributed.mesh import sharded_runs

    return sharded_runs(one, keys, mesh)


def summarize_staged(outs: StagedOutputs) -> dict:
    """Time-averaged scalars incl. the shuffle WAN bill (any runs axis).

    The total includes the speculative re-execution bill (zero for
    hedge-free runs, so pre-hedging totals are unchanged).
    """
    compute = jnp.mean(outs.cost)
    wan = jnp.mean(outs.wan_cost)
    hedge = jnp.mean(outs.hedge_cost)
    return {
        "time_avg_compute_cost": float(compute),
        "time_avg_wan_cost": float(wan),
        "time_avg_hedge_cost": float(hedge),
        "time_avg_total_cost": float(compute + wan + hedge),
        "time_avg_energy": float(jnp.mean(outs.energy)),
        "time_avg_backlog": float(jnp.mean(outs.backlog_avg)),
        "total_wan_gb": float(jnp.mean(jnp.sum(outs.wan_gb, axis=-1))),
        "jobs_completed": float(jnp.mean(jnp.sum(outs.completed, axis=(-2, -1)))),
        "hedged_jobs": float(jnp.mean(jnp.sum(outs.hedged_jobs, axis=-1))),
        "final_backlog_total": float(
            jnp.mean(outs.q_final.sum(axis=(-3, -2, -1)))
        ),
    }
