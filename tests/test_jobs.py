"""Stage-structured jobs subsystem tests (repro.jobs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
from repro.configs.facebook_4dc_stages import (
    StagedPaperConfig,
    make_staged_builder,
)
from repro.core.baselines import (
    data_dispatch,
    greedy_cost_dispatch,
    jsq_dispatch,
    random_dispatch,
)
from repro.core.gmsa import dispatch_fn, gmsa_policy
from repro.core.simulator import simulate
from repro.jobs import (
    chain_dag,
    make_staged_policy,
    map_reduce_dag,
    pad_chains,
    shuffle_volumes_from_selectivity,
    simulate_staged,
    simulate_staged_many,
    single_stage_dag,
    stage_oblivious,
    summarize_staged,
    validate_dag,
)
from repro.placement import wan_topology
from repro.placement.wan import transfer_cost, transfer_plan
from repro.traces.bandwidth import bandwidth_draw
from repro.traces.stages import (
    selectivity_trace,
    stage_compute_profile,
    stage_depth_mask,
)


@pytest.fixture(scope="module")
def paper_setup():
    cfg = PaperSimConfig()
    template, build = make_sim_builder(cfg)
    root = jax.random.key(cfg.trace_seed)
    up, down = bandwidth_draw(jax.random.split(root, 6)[2], cfg.n_sites)
    return cfg, template, build, wan_topology(up, down)


@pytest.fixture(scope="module")
def staged_setup():
    cfg = StagedPaperConfig()
    template, dag, wan, build = make_staged_builder(cfg)
    return cfg, template, dag, wan, build


# ---------------------------------------------------------------------------
# DAG representation
# ---------------------------------------------------------------------------

def test_pad_chains_ragged_depths():
    dag = pad_chains(
        [[0.5, 0.3, 0.2], [0.6, 0.4]],
        [[0.0, 20.0, 4.0], [0.0, 8.0]],
    )
    validate_dag(dag)
    assert dag.s_max == 3 and dag.k_types == 2
    np.testing.assert_array_equal(np.asarray(dag.n_stages), [3, 2])
    # Padding is the identity stage: compute 1, shuffle 0, mask 0.
    assert float(dag.compute[1, 2]) == 1.0
    assert float(dag.shuffle_gb[1, 2]) == 0.0
    assert float(dag.stage_mask[1, 2]) == 0.0


def test_validate_dag_rejects_bad_masks():
    bad = chain_dag(
        jnp.ones((1, 3)), jnp.zeros((1, 3)), jnp.array([[1.0, 0.0, 1.0]])
    )
    with pytest.raises(ValueError, match="monotone"):
        validate_dag(bad)
    empty = chain_dag(
        jnp.ones((1, 2)), jnp.zeros((1, 2)), jnp.array([[0.0, 0.0]])
    )
    with pytest.raises(ValueError, match="at least one"):
        validate_dag(empty)


def test_shuffle_volumes_from_selectivity():
    sel = jnp.array([[0.2, 0.5, 1.0]])
    vols = shuffle_volumes_from_selectivity(100.0, sel)
    # Stage 0 free (data-local map); stage 1 sees 100*0.2; stage 2 100*0.2*0.5.
    np.testing.assert_allclose(np.asarray(vols[0]), [0.0, 20.0, 10.0], rtol=1e-6)
    vols_in = shuffle_volumes_from_selectivity(100.0, sel, bill_input=True)
    assert float(vols_in[0, 0]) == pytest.approx(100.0)


def test_stage_trace_generators_shapes():
    key = jax.random.key(0)
    mask = stage_depth_mask(key, 5, 4, min_stages=2)
    assert mask.shape == (5, 4)
    assert bool(jnp.all(mask[:, :-1] >= mask[:, 1:]))          # monotone
    assert bool(jnp.all(jnp.sum(mask, 1) >= 2))
    comp = stage_compute_profile(jax.random.key(1), mask)
    active_sum = np.asarray(jnp.sum(comp * mask, axis=1))
    np.testing.assert_allclose(active_sum, 1.0, atol=1e-5)
    sel = selectivity_trace(jax.random.key(2), 5, 4)
    assert bool(jnp.all((sel >= 0.02) & (sel <= 1.2)))


# ---------------------------------------------------------------------------
# Single-stage equivalence: the staged engine degenerates to `simulate`
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    dispatch_fn(1.0), data_dispatch, random_dispatch, jsq_dispatch,
    greedy_cost_dispatch,
], ids=["gmsa", "data", "random", "jsq", "greedy"])
def test_single_stage_bit_exact(paper_setup, policy):
    """A trivial one-stage dag (selectivity 1, no shuffle) reproduces
    `simulate`'s dispatch bit for bit, on every policy, and its cost and
    backlog series.

    The two engines are differently fused programs; XLA:CPU (jax 0.9)
    rounds their float sums differently, so the series may differ at ULP
    level (1.3e-7 relative was seen) and compare to 2e-6 relative."""
    cfg, template, _, wan = paper_setup
    dag = single_stage_dag(cfg.k_types)
    key = jax.random.key(3)
    o_s = simulate(template, policy, key)
    o_j = simulate_staged(template, dag, wan, policy, key)
    for field in ("cost", "energy", "backlog_total", "backlog_avg"):
        np.testing.assert_allclose(
            np.asarray(getattr(o_s, field)), np.asarray(getattr(o_j, field)),
            rtol=2e-6, atol=1e-6, err_msg=field,
        )
    np.testing.assert_array_equal(
        np.asarray(o_s.f_trace), np.asarray(o_j.f_trace[..., 0])
    )
    np.testing.assert_array_equal(
        np.asarray(o_s.q_final), np.asarray(o_j.q_final[..., 0])
    )
    assert float(o_j.wan_cost.sum()) == 0.0
    assert float(o_j.wan_gb.sum()) == 0.0


# ---------------------------------------------------------------------------
# Multi-stage dynamics
# ---------------------------------------------------------------------------

def test_stage_flow_conservation(staged_setup):
    """Jobs are conserved through the chain: every arrival either finishes
    its last stage or sits in some stage queue at the horizon."""
    cfg, template, dag, wan, _ = staged_setup
    outs = simulate_staged(
        template, dag, wan, make_staged_policy(dag, wan),
        jax.random.key(0), scalar=cfg.v,
    )
    arrived = float(template.arrivals.sum())
    finished = float(outs.completed.sum())
    queued = float(outs.q_final.sum())
    assert finished + queued == pytest.approx(arrived, rel=1e-5)
    assert bool(jnp.all(outs.q_final >= 0.0))
    # Padded stages hold no backlog.
    mask = np.asarray(dag.stage_mask)                    # (K, S)
    qf = np.asarray(outs.q_final)                        # (N, K, S)
    assert float(qf[:, mask < 0.5].sum()) == 0.0


def test_shuffle_billing_matches_transfer_plan(paper_setup):
    """One slot of the engine bills exactly transfer_cost(transfer_plan(...))
    of the realized stage flows — the placement layer's WAN semantics."""
    cfg, template, _, wan = paper_setup
    k_types = cfg.k_types
    dag = map_reduce_dag(k_types, intermediate_gb=20.0, map_share=0.5)
    # Deterministic two-slot trace: all mass arrives in slot 0.
    t = 2
    n = cfg.n_sites
    arrivals = jnp.zeros((t, k_types)).at[0].set(10.0)
    mu = jnp.full((t, n, k_types), 50.0)
    inputs = template._replace(
        arrivals=arrivals, mu=mu,
        omega=template.omega[:t], pue=template.pue[:t],
    )
    pol = stage_oblivious(gmsa_policy, pin_map=True)
    outs = simulate_staged(inputs, dag, wan, pol, jax.random.key(0),
                           scalar=1.0)
    # Slot 0: map completes min(10*d, mu/0.5) = 10*d at the data sites; the
    # whole 10-job batch shuffles 20 GB/job into the reduce site chosen by
    # the policy (columns of f[...,1]).
    f1 = np.asarray(outs.f_trace[0, :, :, 1])            # (N, K)
    src = np.asarray(inputs.data_dist)                   # (K, N)
    vol = 10.0 * np.asarray(dag.shuffle_gb[:, 1])        # (K,)
    plan = transfer_plan(jnp.asarray(src), jnp.asarray(f1.T), jnp.asarray(vol))
    wc, wen, wgb = transfer_cost(plan, wan, inputs.omega[0], inputs.pue[0])
    assert float(outs.wan_cost[0]) == pytest.approx(float(wc), rel=1e-5)
    assert float(outs.wan_gb[0]) == pytest.approx(float(wgb), rel=1e-5)
    assert float(outs.wan_energy[0]) == pytest.approx(float(wen), rel=1e-5)
    assert float(outs.wan_gb[0]) > 0.0


def test_completed_jobs_drain_when_stable(staged_setup):
    """On the canonical (stable) scenario the chain drains: completions
    track arrivals and no stage queue diverges."""
    cfg, template, dag, wan, _ = staged_setup
    outs = simulate_staged(
        template, dag, wan, make_staged_policy(dag, wan),
        jax.random.key(1), scalar=cfg.v,
    )
    arrived = float(template.arrivals.sum())
    assert float(outs.completed.sum()) > 0.98 * arrived
    assert float(outs.backlog_total[-1]) < 0.02 * arrived


def test_stage_aware_beats_oblivious(staged_setup):
    """The benchmark claim at reduced Monte-Carlo scale: on the multi-stage
    mix, pricing the shuffle into the per-stage score beats the one-manager
    dispatch on total (compute + WAN) cost, with WAN GB reported."""
    cfg, template, dag, wan, build = staged_setup
    key = jax.random.key(0)
    res = {}
    for name, pol in [
        ("oblivious", stage_oblivious(gmsa_policy, pin_map=True)),
        ("aware", make_staged_policy(dag, wan)),
    ]:
        outs = simulate_staged_many(build, dag, wan, pol, key, 16,
                                    scalar=cfg.v)
        assert outs.cost.shape == (16, cfg.t_slots)
        res[name] = summarize_staged(outs)
    assert (res["aware"]["time_avg_total_cost"]
            < res["oblivious"]["time_avg_total_cost"]), res
    assert res["aware"]["total_wan_gb"] > 0.0
    assert res["oblivious"]["total_wan_gb"] > 0.0
    # The win is routing, not starvation: the aware arm completes at least
    # as much work.
    assert (res["aware"]["jobs_completed"]
            >= 0.999 * res["oblivious"]["jobs_completed"])


def test_staged_composes_with_simulate_placed(staged_setup):
    """Slow-loop re-placement reshapes map locality: the controller's
    evolving placements/ratios replay through the staged engine as
    time-varying inputs, and moving data off the expensive drift target
    cuts the staged bill."""
    from repro.core.baselines import static_placement_rule
    from repro.placement import (
        PlacementConfig,
        make_adaptive_rule,
        simulate_placed,
    )
    from repro.traces.drift import ingest_drift_trace

    cfg, template, dag, wan, _ = staged_setup
    w = 48
    n_epochs = cfg.t_slots // w
    ingest = ingest_drift_trace(
        jax.random.key(7), n_epochs, cfg.k_types, cfg.n_sites,
        bias=jnp.array([0.05, 0.8, 0.05, 0.10]), bias_strength=0.5,
    )
    pcfg = PlacementConfig(
        epoch_slots=w, growth=0.25, dataset_gb=cfg.input_gb,
        manager_share=cfg.manager_share, map_share=cfg.map_share,
    )
    key = jax.random.key(1)
    pol = dispatch_fn(cfg.v)
    aware = make_staged_policy(dag, wan)
    totals = {}
    for arm, rule in [("static", static_placement_rule),
                      ("adaptive", make_adaptive_rule(wan.up))]:
        placed = simulate_placed(
            template, wan.up, wan.down, pol, rule, key, pcfg, ingest=ingest
        )
        staged_inputs = template._replace(
            data_dist=jnp.repeat(placed.placements, w, axis=0),
            r=jnp.repeat(placed.r_trace, w, axis=0),
        )
        outs = simulate_staged(staged_inputs, dag, wan, aware, key,
                               scalar=cfg.v)
        totals[arm] = summarize_staged(outs)["time_avg_total_cost"]
        # The time-varying path conserves jobs too.
        assert (float(outs.completed.sum()) + float(outs.q_final.sum())
                == pytest.approx(float(template.arrivals.sum()), rel=1e-5))
    assert totals["adaptive"] < totals["static"], totals


def test_returns_flow_export_matches_engine_recursion(staged_setup):
    """A returns_flow policy's exported inflows reproduce the engine's own
    within-slot flow recursion exactly: stripping the export (forcing the
    engine to re-derive the chain) changes nothing."""
    cfg, template, dag, wan, _ = staged_setup
    aware = make_staged_policy(dag, wan)

    def stripped(key, q, arrivals, mu, e, aux, scalar):
        return aware(key, q, arrivals, mu, e, aux, scalar)[0]

    stripped.staged = True
    stripped.consumes_key = False
    key = jax.random.key(4)
    o_exp = simulate_staged(template, dag, wan, aware, key, scalar=cfg.v)
    o_rec = simulate_staged(template, dag, wan, stripped, key, scalar=cfg.v)
    for field in o_exp._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(o_exp, field)),
            np.asarray(getattr(o_rec, field)),
            rtol=1e-6, err_msg=field,
        )


def test_staged_many_shapes_and_determinism(staged_setup):
    cfg, template, dag, wan, build = staged_setup
    pol = make_staged_policy(dag, wan)
    o1 = simulate_staged_many(build, dag, wan, pol, jax.random.key(5), 4,
                              scalar=cfg.v)
    o2 = simulate_staged_many(build, dag, wan, pol, jax.random.key(5), 4,
                              scalar=cfg.v)
    assert o1.f_trace.shape == (4, cfg.t_slots, cfg.n_sites, cfg.k_types,
                                dag.s_max)
    np.testing.assert_array_equal(np.asarray(o1.cost), np.asarray(o2.cost))


# Hypothesis property tests (stage-flow conservation, shuffle billing vs.
# transfer_plan, random single-stage bit-exactness) live in
# tests/test_jobs_properties.py — slow-marked, nightly CI job.
