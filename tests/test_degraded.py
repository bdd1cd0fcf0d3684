"""Degraded-mode fleet: stragglers, speculation, link faults (PR 9).

The load-bearing contracts, in test form:

* **bitwise identity** — all-ones health and all-alive links are bitwise
  identical to the pre-degraded-mode paths on every engine (and a
  ``health=None`` call traces to the byte-identical jaxpr);
* **trace validation** — malformed fault schedules (negative starts,
  empty windows, factors outside [0, 1], self-links, regions without an
  alive mask) raise instead of silently no-opping;
* **conservation properties** (18 hand-driven seeds) — hedging never
  loses or double-counts completed jobs, and the evacuation planner
  conserves GB even when links are severed;
* **the speculation pin** — on the calibrated straggler scenario,
  hedged re-execution cuts serve sojourn p99 by >= 20% at <= 10%
  duplicated-compute overhead, and the hedged run still replays
  ``simulate_staged`` on the shared scenario;
* **flight-recorder pairing** — a revival lands an EV_REPAIR event and
  the recovery event's SLO clock measures from the true revival slot.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
from repro.core.gmsa import dispatch_fn
from repro.core.simulator import SimInputs, simulate
from repro.jobs import (
    make_staged_policy,
    pad_chains,
    simulate_staged,
    summarize_staged,
)
from repro.launch.serve import build_engine
from repro.placement import (
    PlacementConfig,
    make_adaptive_rule,
    simulate_placed,
    wan_topology,
)
from repro.placement.controller import region_averse_weights
from repro.placement.wan import (
    degraded_surcharge,
    evacuation_plan,
    transfer_cost,
)
from repro.serve.engine import FleetConfig
from repro.telemetry import (
    TRACE,
    TelemetryConfig,
    collect_records,
    hedge_events,
    link_down_events,
    ring_events,
    straggler_spans,
)
from repro.telemetry.metrics import fifo_sojourn_replay, weighted_percentile
from repro.traces.bandwidth import (
    bandwidth_draw,
    link_fault_trace,
    scheduled_link_fault_trace,
)
from repro.traces.faults import (
    compose_health,
    failure_edges,
    health_to_alive,
    health_trace,
    region_assignment,
    regional_health_trace,
    repair_edges,
    scheduled_failure_trace,
    scheduled_health_trace,
    site_failure_trace,
)

SEEDS = list(range(18))
# One fixed shape across all seeds so the property loop compiles once.
T, N, K, S = 10, 4, 2, 3


def _random_case(seed):
    """A small random staged scenario (deterministic in seed)."""
    rng = np.random.default_rng(seed)
    arrivals = jnp.asarray(rng.integers(0, 20, (T, K)), jnp.float32)
    mu = jnp.asarray(rng.uniform(1.0, 30.0, (T, N, K)), jnp.float32)
    omega = jnp.asarray(rng.uniform(10.0, 60.0, (T, N)), jnp.float32)
    pue = jnp.asarray(rng.uniform(1.0, 1.3, (T, N)), jnp.float32)
    dd = jnp.asarray(rng.dirichlet(np.ones(N), K), jnp.float32)
    r = jnp.asarray(rng.dirichlet(np.ones(N), (K, N)), jnp.float32)
    p_it = jnp.asarray(rng.uniform(0.5, 2.0, (K,)), jnp.float32)
    inputs = SimInputs(arrivals, mu, omega, pue, r, p_it, dd)
    computes = [list(rng.uniform(0.2, 1.0, S)) for _ in range(K)]
    shuffles = [[0.0] + list(rng.uniform(0.0, 40.0, S - 1)) for _ in range(K)]
    dag = pad_chains(computes, shuffles)
    up = jnp.asarray(rng.uniform(0.2, 2.0, (N,)), jnp.float32)
    down = jnp.asarray(rng.uniform(0.2, 2.0, (N,)), jnp.float32)
    return inputs, dag, wan_topology(up, down, energy_per_gb=0.03)


def _random_health(seed):
    """A (T, N) health trace with stragglers but no full deaths."""
    rng = np.random.default_rng(1000 + seed)
    health = np.ones((T, N), np.float32)
    for site in rng.choice(N, size=2, replace=False):
        start = int(rng.integers(0, T - 2))
        health[start:, site] = rng.uniform(0.05, 0.6)
    return jnp.asarray(health)


@pytest.fixture(scope="module")
def fb_setup():
    cfg = dataclasses.replace(PaperSimConfig(), t_slots=96)
    template, build = make_sim_builder(cfg)
    root = jax.random.key(cfg.trace_seed)
    up, down = bandwidth_draw(jax.random.split(root, 6)[2], cfg.n_sites)
    return cfg, template, up, down


# ---------------------------------------------------------------------------
# The bitwise-identity contract
# ---------------------------------------------------------------------------

def _assert_fields_equal(a, b):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=name)


def test_simulate_ones_health_bitwise(fb_setup):
    cfg, template, _, _ = fb_setup
    key = jax.random.key(0)
    pol = dispatch_fn(1.0)
    bare = simulate(template, pol, key)
    ones = simulate(template, pol, key,
                    health=jnp.ones((cfg.t_slots, cfg.n_sites)))
    _assert_fields_equal(bare, ones)


def test_simulate_staged_ones_bitwise(fb_setup):
    cfg, template, up, down = fb_setup
    inputs, dag, wan = _random_case(0)
    key = jax.random.key(1)
    pol = make_staged_policy(dag, wan)
    bare = simulate_staged(inputs, dag, wan, pol, key, scalar=5.0)
    ones = simulate_staged(
        inputs, dag, wan, pol, key, scalar=5.0,
        health=jnp.ones((T, N)), link_health=jnp.ones((T, N, N)),
    )
    _assert_fields_equal(bare, ones)
    # The hedge columns of a hedge-free run are exactly zero.
    assert float(jnp.sum(bare.hedge_cost)) == 0.0
    assert float(jnp.sum(bare.hedged_jobs)) == 0.0


def test_simulate_staged_health_none_jaxpr_identical():
    inputs, dag, wan = _random_case(0)
    pol = make_staged_policy(dag, wan)

    def bare(i, k):
        return simulate_staged(i, dag, wan, pol, k)

    def none(i, k):
        return simulate_staged(i, dag, wan, pol, k,
                               health=None, link_health=None)

    key = jax.random.key(0)
    assert (str(jax.make_jaxpr(bare)(inputs, key))
            == str(jax.make_jaxpr(none)(inputs, key)))


def test_simulate_placed_ones_bitwise(fb_setup):
    cfg, template, up, down = fb_setup
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    pol, rule = dispatch_fn(1.0), make_adaptive_rule(up)
    key = jax.random.key(3)
    bare = simulate_placed(template, up, down, pol, rule, key, pcfg)
    ones = simulate_placed(
        template, up, down, pol, rule, key, pcfg,
        health=jnp.ones((cfg.t_slots, cfg.n_sites)),
        link_health=jnp.ones((cfg.t_slots, cfg.n_sites, cfg.n_sites)),
    )
    _assert_fields_equal(bare, ones)


def test_simulate_placed_regions_all_alive_bitwise(fb_setup):
    cfg, template, up, down = fb_setup
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    pol, rule = dispatch_fn(1.0), make_adaptive_rule(up)
    key = jax.random.key(3)
    alive = jnp.ones((cfg.t_slots, cfg.n_sites))
    plain = simulate_placed(template, up, down, pol, rule, key, pcfg,
                            alive=alive)
    regional = simulate_placed(
        template, up, down, pol, rule, key, pcfg, alive=alive,
        regions=region_assignment(cfg.n_sites, 2),
    )
    _assert_fields_equal(plain, regional)


def test_fleet_ones_bitwise():
    classes = ["qwen2-0.5b", "mamba2-2.7b"]
    common = dict(slots=12, v=1.0, seed=3, arrival=4.0, admit_max=5.0)
    bare = build_engine(classes, **common).run(execute_real=False)
    ones = build_engine(
        classes, health=np.ones((12, 4), np.float32),
        link_health=np.ones((12, 4, 4), np.float32), **common,
    ).run(execute_real=False)
    for name in ("dispatch", "cost", "wan_cost", "wan_gb", "q_final",
                 "admitted", "completed", "backlog"):
        np.testing.assert_array_equal(bare[name], ones[name], err_msg=name)
    assert bare["total_billed_cost"] == ones["total_billed_cost"]


# ---------------------------------------------------------------------------
# Trace generators: validation and structure
# ---------------------------------------------------------------------------

def test_scheduled_failure_trace_rejects_bad_windows():
    with pytest.raises(ValueError, match="down_at=-1"):
        scheduled_failure_trace(10, 3, [(0, -1, 5)])
    with pytest.raises(ValueError, match="up_at=2"):
        scheduled_failure_trace(10, 3, [(0, 5, 2)])
    with pytest.raises(ValueError, match="up_at=5"):
        scheduled_failure_trace(10, 3, [(0, 5, 5)])
    with pytest.raises(ValueError, match="site 3"):
        scheduled_failure_trace(10, 3, [(3, 0, None)])


def test_scheduled_health_trace_validation_and_min_compose():
    with pytest.raises(ValueError, match="factor=1.5"):
        scheduled_health_trace(10, 3, [(0, 0, 5, 1.5)])
    with pytest.raises(ValueError, match="start=-2"):
        scheduled_health_trace(10, 3, [(0, -2, 5, 0.5)])
    h = scheduled_health_trace(10, 3, [(1, 2, 8, 0.5), (1, 4, 6, 0.2)])
    assert float(h[3, 1]) == 0.5 and float(h[5, 1]) == pytest.approx(0.2)
    assert float(h[9, 1]) == 1.0


def test_scheduled_link_fault_trace_validation():
    with pytest.raises(ValueError, match="self-link"):
        scheduled_link_fault_trace(10, 3, [(1, 1, 0, 5, 0.0)])
    lh = scheduled_link_fault_trace(10, 3, [(0, 2, 2, 6, 0.0)])
    assert float(lh[3, 0, 2]) == 0.0 and float(lh[3, 2, 0]) == 0.0
    asym = scheduled_link_fault_trace(10, 3, [(0, 2, 2, 6, 0.0)],
                                      symmetric=False)
    assert float(asym[3, 2, 0]) == 1.0


def test_markov_generators_seeded_and_bounded():
    key = jax.random.key(7)
    h = health_trace(key, 64, 4, straggle_prob=0.1, death_prob=0.3)
    assert h.shape == (64, 4)
    assert bool(jnp.all((h >= 0.0) & (h <= 1.0)))
    np.testing.assert_array_equal(
        np.asarray(h), np.asarray(health_trace(key, 64, 4,
                                               straggle_prob=0.1,
                                               death_prob=0.3)))
    regions = region_assignment(4, 2)
    np.testing.assert_array_equal(np.asarray(regions), [0, 0, 1, 1])
    rh = regional_health_trace(key, 64, regions, outage_prob=0.1)
    # Shared fate: both sites of a region always carry the same factor.
    np.testing.assert_array_equal(np.asarray(rh[:, 0]), np.asarray(rh[:, 1]))
    composed = compose_health(h, rh)
    assert bool(jnp.all(composed <= h + 1e-9))
    alive = health_to_alive(composed)
    assert set(np.unique(np.asarray(alive))) <= {0.0, 1.0}
    lh = link_fault_trace(key, 32, 4, degrade_prob=0.2)
    assert lh.shape == (32, 4, 4)
    np.testing.assert_array_equal(
        np.asarray(lh[:, np.arange(4), np.arange(4)]), 1.0)


def test_repair_edges_pairs_with_failure_edges():
    alive = scheduled_failure_trace(12, 3, [(1, 3, 7)])
    down = failure_edges(alive)
    up = repair_edges(alive)
    assert float(down[3, 1]) == 1.0 and float(down.sum()) == 1.0
    assert float(up[7, 1]) == 1.0 and float(up.sum()) == 1.0
    # An all-alive fleet has no edges of either kind; a trace can never
    # open with a revival (slot 0 compares against all-alive).
    ones = jnp.ones((12, 3))
    assert float(failure_edges(ones).sum()) == 0.0
    assert float(repair_edges(ones).sum()) == 0.0
    permanent = scheduled_failure_trace(12, 3, [(0, 2, None)])
    assert float(repair_edges(permanent).sum()) == 0.0


def test_engine_rejects_malformed_degraded_inputs(fb_setup):
    cfg, template, up, down = fb_setup
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    pol, rule = dispatch_fn(1.0), make_adaptive_rule(up)
    key = jax.random.key(0)
    with pytest.raises(ValueError):
        simulate_placed(template, up, down, pol, rule, key, pcfg,
                        health=jnp.ones((3, cfg.n_sites)))
    with pytest.raises(ValueError):
        simulate_placed(template, up, down, pol, rule, key, pcfg,
                        regions=region_assignment(cfg.n_sites, 2))
    with pytest.raises(ValueError):
        FleetConfig(n_pods=4, horizon_slots=8, hedge_threshold=0.5,
                    dispatch="kernel")
    with pytest.raises(ValueError):
        FleetConfig(n_pods=4, horizon_slots=8, hedge_threshold=-0.1)


# ---------------------------------------------------------------------------
# Degraded links: pricing, routing, surcharge identity
# ---------------------------------------------------------------------------

def test_degraded_links_price_up_and_severed_price_inf():
    inputs, dag, wan = _random_case(3)
    om, pu = inputs.omega[0], inputs.pue[0]
    rng = np.random.default_rng(3)
    plan = jnp.asarray(rng.uniform(0.0, 5.0, (K, N, N)), jnp.float32)
    plan = plan * (1.0 - jnp.eye(N))
    nominal, _, _ = transfer_cost(plan, wan, om, pu)
    lh = jnp.full((N, N), 0.5).at[jnp.arange(N), jnp.arange(N)].set(1.0)
    degraded, _, _ = transfer_cost(plan, wan, om, pu, link_health=lh)
    assert float(degraded) > float(nominal)
    severed, _, _ = transfer_cost(plan, wan, om, pu,
                                  link_health=jnp.zeros((N, N)))
    assert np.isinf(float(severed))
    # The surcharge form of the same bill is exactly zero at all-ones.
    d_old = jnp.asarray(rng.dirichlet(np.ones(N), K), jnp.float32)
    d_new = jnp.asarray(rng.dirichlet(np.ones(N), K), jnp.float32)
    sizes = jnp.asarray(rng.uniform(1.0, 50.0, K), jnp.float32)
    sur_c, sur_e = degraded_surcharge(d_old, d_new, sizes, wan, om, pu,
                                      jnp.ones((N, N)))
    assert float(sur_c) == 0.0 and float(sur_e) == 0.0


def test_evacuation_plan_routes_around_severed_links():
    d_masked = jnp.asarray([[0.5, 0.0, 0.3, 0.0]])
    d_drop = jnp.asarray([[0.5, 0.0, 0.3, 0.2]])
    sizes = jnp.asarray([10.0])
    lh = jnp.ones((4, 4)).at[0, 3].set(0.0)       # site 0 cannot reach 3
    plan = evacuation_plan(d_masked, d_drop, sizes, link_health=lh)
    assert float(plan[0, 0, 3]) == 0.0            # routed around
    assert float(plan[0, 2, 3]) == pytest.approx(2.0)   # all via site 2
    np.testing.assert_allclose(np.asarray(plan.sum(axis=1)[0]),
                               [0.0, 0.0, 0.0, 2.0], atol=1e-6)


def test_region_averse_weights_discount_shared_fate():
    regions = region_assignment(4, 2)
    alive = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    w = region_averse_weights(alive, regions)
    # Site 0 shares site 1's region: half its region is dead, so its
    # weight halves; dead sites stay at zero; the far region is untouched.
    np.testing.assert_allclose(np.asarray(w), [0.5, 0.0, 1.0, 1.0])
    np.testing.assert_array_equal(
        np.asarray(region_averse_weights(jnp.ones(4), regions)),
        np.ones(4))


def test_stragglers_and_degraded_links_move_placed_bills(fb_setup):
    cfg, template, up, down = fb_setup
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    pol, rule = dispatch_fn(1.0), make_adaptive_rule(up)
    key = jax.random.key(3)
    bare = simulate_placed(template, up, down, pol, rule, key, pcfg)
    # Slow the site that carries the most dispatch: a straggler that GMSA
    # already routes around (site 0 gets no job on this draw) moves nothing.
    busiest = int(np.argmax(np.asarray(bare.f_trace).sum(axis=(0, 2))))
    slow = simulate_placed(
        template, up, down, pol, rule, key, pcfg,
        health=scheduled_health_trace(cfg.t_slots, cfg.n_sites,
                                      [(busiest, 10, None, 0.2)]),
    )
    assert (float(jnp.mean(slow.backlog_avg))
            > float(jnp.mean(bare.backlog_avg)))
    lh = np.full((cfg.t_slots, cfg.n_sites, cfg.n_sites), 0.4, np.float32)
    lh[:, np.arange(cfg.n_sites), np.arange(cfg.n_sites)] = 1.0
    linky = simulate_placed(
        template, up, down, pol, rule, key, pcfg, link_health=jnp.asarray(lh),
    )
    assert float(linky.wan_cost.sum()) > float(bare.wan_cost.sum())
    assert float(linky.wan_latency_s.sum()) > float(bare.wan_latency_s.sum())


# ---------------------------------------------------------------------------
# Conservation properties, 18 hand-driven seeds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_prop_hedging_conserves_jobs(seed):
    """Hedging never loses or double-counts completed jobs: arrivals
    still split exactly into completions + final backlog, and the hedge
    columns stay non-negative with the bill attached to the jobs."""
    inputs, dag, wan = _random_case(seed)
    health = _random_health(seed)
    pol = make_staged_policy(dag, wan, hedge=0.9)
    outs = simulate_staged(inputs, dag, wan, pol, jax.random.key(seed),
                           scalar=5.0, health=health)
    arrived = float(inputs.arrivals.sum())
    got = float(outs.completed.sum()) + float(outs.q_final.sum())
    assert got == pytest.approx(arrived, rel=1e-4, abs=1e-3)
    assert bool(jnp.all(outs.q_final >= 0.0))
    assert bool(jnp.all(outs.hedged_jobs >= 0.0))
    assert bool(jnp.all(outs.hedge_cost >= 0.0))
    # No phantom speculation: a zero-hedge slot bills nothing.
    hj = np.asarray(outs.hedged_jobs)
    hc = np.asarray(outs.hedge_cost)
    assert (hc[hj == 0.0] == 0.0).all()
    s = summarize_staged(outs)
    assert s["time_avg_total_cost"] == pytest.approx(
        s["time_avg_compute_cost"] + s["time_avg_wan_cost"]
        + s["time_avg_hedge_cost"], rel=1e-5)


@pytest.mark.parametrize("seed", SEEDS)
def test_prop_evacuation_gb_conserved_under_severed_links(seed):
    """Severing links reroutes the evacuation burst, never shrinks it:
    each destination receives exactly its deficit, link faults or not."""
    rng = np.random.default_rng(seed)
    d_full = rng.dirichlet(np.ones(N), K).astype(np.float32)
    dead = rng.integers(0, N)
    mask = np.ones(N, np.float32)
    mask[dead] = 0.0
    d_masked = jnp.asarray(d_full * mask[None, :])
    d_drop = jnp.asarray(
        np.asarray(d_masked) / np.maximum(
            np.asarray(d_masked).sum(axis=1, keepdims=True), 1e-9))
    sizes = jnp.asarray(rng.uniform(1.0, 100.0, K), jnp.float32)
    lh = np.ones((N, N), np.float32)
    n_cut = int(rng.integers(0, N))
    for _ in range(n_cut):
        i, j = rng.integers(0, N, 2)
        if i != j:
            lh[i, j] = 0.0
    need = np.maximum(np.asarray(d_drop) - np.asarray(d_masked), 0.0) \
        * np.asarray(sizes)[:, None]
    for link_health in (None, jnp.asarray(lh)):
        plan = evacuation_plan(d_masked, d_drop, sizes,
                               link_health=link_health)
        np.testing.assert_allclose(np.asarray(plan.sum(axis=1)), need,
                                   rtol=1e-4, atol=1e-4)
        assert bool(jnp.all(plan >= 0.0))
        assert float(jnp.sum(plan * jnp.eye(N)[None])) == 0.0


# ---------------------------------------------------------------------------
# The speculation pin: p99 cut on the calibrated straggler scenario
# ---------------------------------------------------------------------------

CHAOS_CLASSES = ["qwen2-0.5b", "mamba2-2.7b"]
CHAOS_COMMON = dict(slots=24, v=1.0, seed=3, arrival=4.0, admit_max=5.0)
CHAOS_HEDGE = 0.30
CHAOS_SEEDS = range(8)      # the p99 pin holds on average over these draws


def _chaos_health():
    health = np.ones((24, 4), np.float32)
    health[4:, 2] = 0.12      # the dominant-capacity pod straggles hard
    return health


def _sojourn_p99(out):
    soj, wgt = fifo_sojourn_replay(out["admitted"], out["completed"])
    return float(weighted_percentile(soj, wgt, [99.0])[0])


@pytest.fixture(scope="module")
def chaos_pair():
    health = _chaos_health()
    base = build_engine(CHAOS_CLASSES, health=health, **CHAOS_COMMON)
    hedged = build_engine(CHAOS_CLASSES, health=health, hedge=CHAOS_HEDGE,
                          **CHAOS_COMMON)
    return (hedged, base.run(execute_real=False),
            hedged.run(execute_real=False))


def test_speculation_cuts_p99_within_overhead_budget():
    """Hedging cuts sojourn p99 by >= 20% at <= 10% duplicated compute, on
    average over eight arrival/capacity draws of the straggler scenario.

    One draw decides little: p99 is a slot count, and across seeds 0-7
    the cut ranges 0-60% and the overhead 0.5-9.4% at θ = 0.30 (at the
    earlier θ = 0.35, calibrated on one draw of jax 0.4's random stream,
    the mean overhead on jax 0.9's stream is 10.05%)."""
    cuts, overheads = [], []
    for seed in CHAOS_SEEDS:
        common = dict(CHAOS_COMMON, seed=seed)
        base = build_engine(CHAOS_CLASSES, health=_chaos_health(), **common)
        hedged = build_engine(CHAOS_CLASSES, health=_chaos_health(),
                              hedge=CHAOS_HEDGE, **common)
        base, hedged = base.run(execute_real=False), hedged.run(
            execute_real=False)
        assert hedged["hedged_jobs"].sum() > 0.0, seed
        p_base = _sojourn_p99(base)
        cuts.append((p_base - _sojourn_p99(hedged)) / p_base)
        overheads.append(float(hedged["hedge_cost"].sum()) / (
            float(hedged["cost"].sum()) + float(hedged["hedge_cost"].sum())))
        # First-completion also clears backlog, not just the tail.
        assert hedged["final_backlog"] < base["final_backlog"], seed
        assert hedged["completed"].sum() > base["completed"].sum(), seed
    assert np.mean(cuts) >= 0.20, cuts
    assert np.mean(overheads) <= 0.10, overheads


def test_hedged_serve_conserves_and_bills_honestly(chaos_pair):
    _, _, hedged = chaos_pair
    np.testing.assert_allclose(
        hedged["admitted"].sum(axis=0),
        hedged["completed"].sum(axis=0) + hedged["q_final"].sum(axis=(0, 2)),
        rtol=1e-5, atol=1e-3,
    )
    assert hedged["total_billed_cost"] == pytest.approx(
        float(hedged["cost"].sum()) + float(hedged["wan_cost"].sum())
        + float(hedged["hedge_cost"].sum()), rel=1e-6)
    # The per-slot history carries the hedge stream.
    hist_hj = np.asarray([h["hedged_jobs"] for h in hedged["history"]])
    np.testing.assert_allclose(hist_hj, hedged["hedged_jobs"], rtol=1e-6)


def test_hedged_fleet_replays_simulate_staged(chaos_pair):
    """Replay parity survives hedging: the engine's dispatch and billed
    totals match ``simulate_staged`` with the hedged policy on the shared
    (health-scaled) scenario."""
    from repro.serve.engine import serve_policy

    engine, _, hedged = chaos_pair
    scn = engine.scenario
    pol = serve_policy(engine.fcfg, scn)
    outs = simulate_staged(scn.inputs, scn.dag, scn.wan, pol,
                           jax.random.key(0), engine.fcfg.v)
    np.testing.assert_array_equal(hedged["dispatch"], np.asarray(outs.f_trace))
    np.testing.assert_allclose(hedged["hedge_cost"],
                               np.asarray(outs.hedge_cost),
                               rtol=1e-5, atol=1e-8)
    sim_total = float(np.asarray(outs.cost).sum()
                      + np.asarray(outs.wan_cost).sum()
                      + np.asarray(outs.hedge_cost).sum())
    assert hedged["total_billed_cost"] == pytest.approx(sim_total, rel=1e-5)


def test_hedge_never_fires_on_a_healthy_fleet():
    # At thresholds below the fleet's natural rate spread the hedge gate
    # stays shut without faults; the chaos threshold is deliberately
    # above it so stragglers (not heterogeneity) trip speculation.
    engine = build_engine(CHAOS_CLASSES, hedge=0.2, **CHAOS_COMMON)
    out = engine.run(execute_real=False)
    assert float(out["hedged_jobs"].sum()) == 0.0
    assert float(out["hedge_cost"].sum()) == 0.0


# ---------------------------------------------------------------------------
# Flight recorder: repair pairing, derived events, straggler spans
# ---------------------------------------------------------------------------

def test_revival_lands_repair_event_and_repairs_the_slo_clock(fb_setup):
    cfg, template, up, down = fb_setup
    mask = scheduled_failure_trace(cfg.t_slots, cfg.n_sites, [(1, 30, 60)])
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    tcfg = TelemetryConfig(level=TRACE)
    traced, frame = simulate_placed(
        template, up, down, dispatch_fn(1.0), make_adaptive_rule(up),
        jax.random.key(3), pcfg, alive=mask, telemetry=tcfg,
    )
    events, dropped = ring_events(frame.ring)
    assert dropped == 0
    records = collect_records(traced, frame, cfg=tcfg)
    evs = [r for r in records if r.get("type") == "event"]
    rep = [e for e in evs if e["code"] == "repair"]
    assert len(rep) == 1 and rep[0]["t"] == 60 and rep[0]["site"] == 1
    rec = next(e for e in evs if e["code"] == "recovery")
    assert rec["t"] == 30 and rec["repair_t"] == 60
    # The SLO clock starts at the revival, so it can never report a
    # negative-latency recovery measured from the death slot.
    assert rec["time_to_slo"] is None or rec["time_to_slo"] >= 0


def test_hedge_and_link_down_event_builders():
    hj = np.array([0.0, 2.5, 0.0, 1.0])
    hc = np.array([0.0, 0.01, 0.0, 0.002])
    he = hedge_events(hj, hc)
    assert [e["t"] for e in he] == [1, 3]
    assert he[0]["hedged_jobs"] == 2.5
    assert he[0]["hedge_cost"] == pytest.approx(0.01)
    lh = np.ones((12, 3, 3), np.float32)
    lh[4:8, 0, 2] = 0.0
    le = link_down_events(lh)
    assert [(e["t"], e["edge"]) for e in le] == [(4, "down"), (8, "up")]
    assert le[0]["src"] == 0 and le[0]["dst"] == 2
    # Degraded-but-usable links are not "down": no event below the cut.
    lh2 = np.full((6, 2, 2), 0.5, np.float32)
    assert link_down_events(lh2) == []


def test_straggler_spans_windows_and_overlay():
    h = np.ones((12, 3), np.float32)
    h[3:7, 1] = 0.25
    h[5:, 2] = 0.0
    lh = np.ones((12, 3, 3), np.float32)
    lh[4:8, 0, 2] = 0.0
    spans = straggler_spans(h, link_health=lh)
    cats = [s["cat"] for s in spans]
    assert cats.count("straggler") == 1 and cats.count("dead") == 1
    assert cats.count("repair") == 1      # only the closing window repairs
    assert cats.count("link") == 2
    strag = next(s for s in spans if s["cat"] == "straggler")
    assert (strag["t0"], strag["t1"]) == (3.0, 7.0)
    assert strag["args"]["factor_min"] == pytest.approx(0.25)
    dead = next(s for s in spans if s["cat"] == "dead")
    assert (dead["t0"], dead["t1"]) == (5.0, 12.0)
    assert straggler_spans(np.ones((8, 2))) == []
