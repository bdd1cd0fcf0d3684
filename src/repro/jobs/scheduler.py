"""Joint manager selection & stage placement (per-slot decision rules).

Extends GMSA's LP-vertex trick (:mod:`repro.core.gmsa`) from one decision
per job type to one decision per *stage*: the map stage is pinned to
``data_dist`` locality (the GDA premise — map tasks run where the data
lives, nothing crosses the WAN), and every downstream stage's site is
chosen by a drift-plus-penalty score that now includes the
intermediate-data WAN energy term the base algorithm routes implicitly
but never bills:

    score[k, s, i] = F^{k,s} * ( Q_i^{k,s} - mu_i^{k,s}
                                 + V * [ c^{k,s} e_i^k  +  G^{k,s} w_i^{k,s} ] )

with ``F`` the flow entering the stage this slot, ``c`` the stage compute
intensity, ``G`` the stage's shuffle volume, and
``w_i = sum_{j != i} src_j * price[j, i]`` the expected $-per-GB of
pulling the upstream output mix ``src`` to site i, priced exactly as
:func:`repro.placement.wan.transfer_cost` bills it (half the energy at
each endpoint, local pulls free). For one-hot decisions the score's WAN
term equals the engine's ``transfer_plan`` bill to the byte, so the argmin
vertex remains the exact LP optimum of the per-stage relaxation.

Because downstream shuffle sources depend on upstream completions, the
policy replicates the engine's within-slot flow propagation
(:func:`flow_step` — the single definition shared with
:mod:`repro.jobs.engine`) stage by stage: decide f^{k,0}, advance the
flow, decide f^{k,1} against the realized source mix, and so on. All
closed-form, jit-safe, vmappable over Monte-Carlo runs.

``stage_oblivious`` adapts any base simulator policy (GMSA, DATA, RANDOM,
JSQ, GREEDY-COST) to the staged engine: one manager choice per type from
the aggregate backlog, applied to every stage — the current, shuffle-blind
dispatch the benchmarks compare against.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax import Array

from repro.core.gmsa import drift_plus_penalty_scores
from repro.core.policy import declare, slot_aux, spec_of, split_aux
from repro.jobs.dag import StageDag
from repro.placement.wan import WanModel, expected_pull

_EPS = 1e-12


def stage_service_rates(mu: Array, dag: StageDag) -> Array:
    """(N, K, S) effective per-stage service rates.

    A stage with compute intensity c occupies a server c job-units per
    completion, so the base (N, K) service-rate trace stretches to
    ``mu / c`` per stage. Padded stages carry intensity 1.0 (exact
    identity — the single-stage dag reproduces ``mu`` bit for bit).
    """
    return mu[:, :, None] / dag.compute[None, :, :]


def stage_service_rates_all(mu_all: Array, dag: StageDag) -> Array:
    """(T, N, K, S) per-stage service rates for a whole trace in one op.

    The hoisted form of :func:`stage_service_rates` — the staged engine
    computes it once outside its scan body instead of per slot. Identical
    values (same ``mu / c`` divide, padded stages at the exact-identity
    intensity 1.0).
    """
    return mu_all[..., None] / dag.compute[None, None, :, :]


def flow_step(
    q_s: Array, f_s: Array, total_in: Array, mu_s: Array
) -> tuple[Array, Array]:
    """Within-slot flow through one stage: completions and their locations.

    Stage s receives ``f_s * total_in`` on top of backlog ``q_s`` and
    serves at most ``mu_s`` — completions this slot are
    ``min(q_s + f_s * total_in, mu_s)`` (the served mass of Eq. 1's max).
    The single definition shared by the engine's billing loop and the
    stage-aware policy's lookahead, so the score's source mix is exactly
    the mix the engine bills.

    Returns:
        (total_done, src): (K,) completions leaving the stage and their
        (K, N) site distribution (uniform fallback for zero flow — the
        downstream volume is zero there, so the choice is billing-inert).
    """
    n = q_s.shape[0]
    done = jnp.minimum(q_s + f_s * total_in[None, :], mu_s)        # (N, K)
    total_done = jnp.sum(done, axis=0)                             # (K,)
    src = jnp.where(
        total_done[:, None] > _EPS,
        done.T / jnp.maximum(total_done[:, None], _EPS),
        1.0 / n,
    )                                                              # (K, N)
    return total_done, src


def staged_stage_scores(
    q_s: Array,
    total_in: Array,
    mu_s: Array,
    e: Array,
    compute_s: Array,
    shuffle_gb_s: Array,
    pull: Array,
    v: float | Array,
) -> Array:
    """(K, N) drift-plus-penalty scores for one stage's site choice.

    The base GMSA score (:func:`repro.core.gmsa.drift_plus_penalty_scores`)
    with the per-job penalty extended by the stage's WAN pull term:
    ``e_stage[k, i] = compute_s[k] * e[k, i] + shuffle_gb_s[k] * pull[k, i]``
    where ``pull[k, i] = sum_j src[k, j] * price[j, i]`` — the expected
    $-per-GB of pulling the upstream output mix to site i, computed fused
    by :func:`repro.placement.wan.expected_pull` (no (N, N) price matrix
    materialized per slot).
    """
    e_stage = compute_s[:, None] * e + shuffle_gb_s[:, None] * pull
    return drift_plus_penalty_scores(q_s, total_in, mu_s, e_stage, v)


def hedge_clone_choice(
    f_s: Array, mu_s: Array, stage_mask_s: Array, hedge: float
) -> tuple[Array, Array]:
    """Speculative re-execution decision for one stage: clone site + boost.

    The straggler signal is *relative*: the dispatched sites' effective
    service rate ``mu_p = Σ_n f·mu`` (exact for one-hot downstream
    choices; the f-weighted mean for the fractional pinned map) against
    the best alternative site ``r = argmax_n mu·(1 - f)`` — high spare
    rate, low current share. The hedge fires when ``mu_p < hedge·mu_r``:
    the dispatch target is running at less than ``hedge`` of what the
    runner-up could deliver, so the stage is cloned there.

    First-completion enters the fluid recursion as a service-rate boost
    at the dispatched sites: the clone re-executes the same queued work,
    and whichever copy finishes first completes the job, so the stage's
    drain rate rises by the clone's rate — ``mu_eff = mu + f·boost``
    with ``boost = mu_r`` where the hedge fired, 0 elsewhere (an exact
    ``+ 0.0`` identity when nothing fires). The engine bills the work
    the clone actually completes (the boost-attributable completions) at
    the clone site's energy price plus the WAN pull of its inputs.

    Returns:
        (g_s, boost): the (N, K) one-hot clone matrix (zero columns
        where the hedge did not fire) and the (K,) rate boost.
    """
    n = f_s.shape[0]
    mu_p = jnp.sum(f_s * mu_s, axis=0)                         # (K,)
    alt = mu_s * (1.0 - f_s)                                   # (N, K)
    r_hot = (
        jnp.arange(n)[:, None] == jnp.argmax(alt, axis=0)[None]
    ).astype(f_s.dtype)                                        # (N, K)
    mu_r = jnp.sum(r_hot * mu_s, axis=0)                       # (K,)
    fire = ((mu_p < hedge * mu_r) & (stage_mask_s > 0.0)).astype(f_s.dtype)
    return r_hot * fire[None, :], mu_r * fire


def make_staged_policy(dag: StageDag, wan: WanModel, pin_map: bool = True,
                       hedge: float | None = None):
    """Stage-aware GMSA: per-stage LP-vertex dispatch with WAN pricing.

    Returns a policy with the staged signature
    ``(key, q, arrivals, mu, e, aux, scalar) -> f`` where ``q``/``f`` are
    (N, K, S) and ``aux = (data_dist, wpue)`` — V rides in as the traced
    ``scalar`` exactly like :func:`repro.core.gmsa.gmsa_policy`, so a
    V-sweep reuses one compilation.

    Args:
        dag: the stage chain (closed over; arrays, so the closure stays
            jit-transparent).
        wan: WAN model pricing the shuffle pulls.
        pin_map: pin stage 0 to ``data_dist`` (data-local map). When
            False, stage 0 is score-chosen like any other stage — only
            meaningful when the dag bills a stage-0 input pull.
        hedge: speculative re-execution threshold (``None`` disables —
            the policy keeps its exact pre-hedging contract). When set,
            each stage whose dispatched service rate falls below
            ``hedge`` times the runner-up site's rate is cloned there
            (:func:`hedge_clone_choice`), the within-slot flow walk runs
            on the first-completion boosted rates, and the policy
            additionally returns the (N, K, S) clone matrix
            (``returns="flow_hedge"`` in its spec).
    """

    def policy(key, q, arrivals, mu, e, aux, scalar):
        del key
        data_dist, wpue = aux
        n = q.shape[0]
        mu_stages = stage_service_rates(mu, dag)                   # (N, K, S)
        total_in = arrivals                                        # (K,)
        src = data_dist                                            # (K, N)
        cols, ins, clones = [], [], []
        for s in range(dag.s_max):
            mu_s = mu_stages[:, :, s]
            if s == 0 and pin_map:
                f_s = data_dist.T                                  # (N, K)
            else:
                # Fused expected-pull pricing (link_price_matrix *
                # energy_per_gb semantics, no (N, N) matrix in the
                # per-slot body; src is on the simplex by the flow_step
                # contract).
                pull = (expected_pull(src, wpue, assume_simplex=True)
                        * wan.energy_per_gb)
                scores = staged_stage_scores(
                    q[:, :, s], total_in, mu_s, e,
                    dag.compute[:, s], dag.shuffle_gb[:, s],
                    pull, scalar,
                )                                                  # (K, N)
                f_s = (
                    jnp.arange(n)[:, None] == jnp.argmin(scores, axis=1)[None]
                ).astype(q.dtype)                                  # (N, K)
            cols.append(f_s)
            ins.append(total_in)
            if hedge is not None:
                # Clone stragglers to the runner-up and walk the flow on
                # the boosted (first-completion) rates — the engine
                # re-derives the identical boost from g, so the exported
                # inflows replay bit-for-bit.
                g_s, boost = hedge_clone_choice(
                    f_s, mu_s, dag.stage_mask[:, s], hedge
                )
                clones.append(g_s)
                mu_s = mu_s + f_s * boost[None, :]
            total_done, src = flow_step(q[:, :, s], f_s, total_in, mu_s)
            if s + 1 < dag.s_max:
                total_in = total_done * dag.stage_mask[:, s + 1]
        # The lookahead already walked the exact within-slot flow the
        # engine would re-derive (flow_step is the shared definition), so
        # export the per-stage inflows and let the engine skip its own
        # recursion (``returns="flow"`` in the policy's spec).
        f = jnp.stack(cols, axis=-1)
        in_stack = jnp.stack(ins, axis=-1)                         # (K, S)
        if hedge is not None:
            return f, in_stack, jnp.stack(clones, axis=-1)
        return f, in_stack

    return declare(policy, keyed=False, staged=True, reads="wpue",
                   returns="flow" if hedge is None else "flow_hedge")


def staged_dispatch_fn(dag: StageDag, wan: WanModel, v: float,
                       pin_map: bool = True, hedge: float | None = None):
    """Closure adapter binding a static V (one compilation per V)."""
    base = make_staged_policy(dag, wan, pin_map=pin_map, hedge=hedge)

    def policy(key, q, arrivals, mu, e, aux, scalar):
        del scalar
        return base(key, q, arrivals, mu, e, aux, v)

    return declare(policy, spec_of(base))


def stage_oblivious(policy, pin_map: bool = False):
    """Adapt a base simulator policy to the staged engine, shuffle-blind.

    The base policy sees the aggregate backlog ``sum_s Q`` and the plain
    per-job cost table — exactly what it sees in ``simulate`` — and its
    (N, K) decision applies to *every* stage: the job follows its manager,
    no per-stage queues, no WAN term. This is the "current" dispatch the
    jobs benchmarks compare stage-aware scheduling against; with a
    single-stage dag it reproduces ``simulate`` bit for bit.

    Args:
        policy: any base policy ``(key, q(N,K), arrivals, mu, e, aux,
            scalar) -> f(N,K)``. The adapter reads the staged engines'
            ``(data_dist, wpue)`` aux — plus ``r_t`` when the base policy
            ``reads="wpue_r"`` (the carried-r kernel variant) — and hands
            the base policy the aux its own spec reads, exactly as
            :func:`repro.core.simulator.simulate` would: the fleet-scale
            kernel path of :func:`repro.core.gmsa.make_kernel_policy`
            composes with stage-structured queues unchanged. Key use,
            state independence and a bound ratio tensor carry over.
        pin_map: override stage 0 with data-local map placement (used when
            benchmarking against stage-aware policies under the same
            data-local-map premise; keep False for exact base semantics).
    """
    base = spec_of(policy)
    spec = dataclasses.replace(
        base, staged=True,
        reads="wpue_r" if base.reads == "wpue_r" else "wpue",
    )

    def staged(key, q, arrivals, mu, e, aux, scalar):
        data_dist, wpue, r_t = split_aux(spec, aux)
        base_aux = slot_aux(base, data_dist, wpue, r_t)
        q_total = jnp.sum(q, axis=-1)                              # (N, K)
        f_base = policy(key, q_total, arrivals, mu, e, base_aux, scalar)
        f = jnp.broadcast_to(f_base[:, :, None], q.shape)
        if pin_map:
            f = jnp.concatenate(
                [data_dist.T[:, :, None], f[:, :, 1:]], axis=-1
            )
        return f

    return declare(staged, spec)
