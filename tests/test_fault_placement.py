"""Chaos regression tests: site loss inside the two-timescale controller.

The invariants pinned here are the contract of the controller's fault path
(`simulate_placed(..., alive=mask)`):

* an all-ones mask is bit-exact with the no-fault path, on every policy
  path (state-dependent GMSA, precomputed-key RANDOM/DATA) and both rules;
* once a site dies it receives zero dispatch mass and serves nothing;
* its backlog is conserved — re-injected as an arrival burst, not dropped;
* ``recovery_cost`` fires exactly on death edges (and only bills when
  there is data to evacuate);
* revival hands the site back to the regular slow loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.fault import drop_site, drop_site_mask
from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
from repro.core.baselines import (
    data_dispatch,
    random_dispatch,
    static_placement_rule,
)
from repro.core.gmsa import dispatch_fn
from repro.core.iridium import build_task_allocation
from repro.core.simulator import SimInputs
from repro.placement import (
    PlacementConfig,
    evacuation_plan,
    make_adaptive_rule,
    simulate_placed,
    simulate_placed_many,
)
from repro.traces.bandwidth import bandwidth_draw
from repro.traces.faults import (
    failure_edges,
    scheduled_failure_trace,
    site_failure_trace,
)


@pytest.fixture(scope="module")
def paper_setup():
    cfg = PaperSimConfig()
    template, build = make_sim_builder(cfg)
    root = jax.random.key(cfg.trace_seed)
    up, down = bandwidth_draw(jax.random.split(root, 6)[2], cfg.n_sites)
    return cfg, template, build, up, down


def _pcfg(cfg, **kw):
    return PlacementConfig(
        epoch_slots=kw.pop("epoch_slots", 48),
        manager_share=cfg.manager_share, map_share=cfg.map_share, **kw
    )


# ---------------------------------------------------------------------------
# Bit-exactness of the all-alive fault path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    pytest.param(dispatch_fn(1.0), id="gmsa"),
    pytest.param(random_dispatch, id="random"),
    pytest.param(data_dispatch, id="data"),
])
@pytest.mark.parametrize("rule_name", ["static", "adaptive"])
def test_all_alive_mask_bit_exact(paper_setup, policy, rule_name):
    """alive=ones reproduces the no-fault outputs — every masking op in the
    fault path is an exact identity or an edge select, so dispatch,
    placements and the fault bills match bit for bit.

    The two calls compile to differently fused programs, and XLA:CPU
    (jax 0.9) rounds their float sums differently: backlogs may differ at
    ULP level (at most 5.2e-7 relative was seen), so float series compare
    to 2e-6 relative."""
    cfg, template, _, up, down = paper_setup
    rule = (static_placement_rule if rule_name == "static"
            else make_adaptive_rule(up))
    key = jax.random.key(21)
    pcfg = _pcfg(cfg)
    ones = jnp.ones((cfg.t_slots, cfg.n_sites), jnp.float32)
    o0 = simulate_placed(template, up, down, policy, rule, key, pcfg)
    o1 = simulate_placed(template, up, down, policy, rule, key, pcfg,
                         alive=ones)
    for field in o0._fields:
        a, b = np.asarray(getattr(o0, field)), np.asarray(getattr(o1, field))
        if field in ("f_trace", "placements", "recovery_cost",
                     "recovery_gb"):
            np.testing.assert_array_equal(a, b, err_msg=field)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-6,
                                       err_msg=field)
    assert float(o1.recovery_cost.sum()) == 0.0
    assert float(o1.recovery_gb.sum()) == 0.0


# ---------------------------------------------------------------------------
# Site death mid-epoch
# ---------------------------------------------------------------------------

def test_dead_site_gets_no_dispatch_and_serves_nothing(paper_setup):
    cfg, template, _, up, down = paper_setup
    dead, t_die = 1, 100                                  # mid-epoch (W=48)
    mask = scheduled_failure_trace(
        cfg.t_slots, cfg.n_sites, [(dead, t_die, None)]
    )
    # RANDOM dispatches everywhere while a site is alive, so the zero after
    # the death edge is unambiguously the controller's masking at work.
    outs = simulate_placed(
        template, up, down, random_dispatch, make_adaptive_rule(up),
        jax.random.key(3), _pcfg(cfg), alive=mask,
    )
    f = np.asarray(outs.f_trace)
    assert float(np.abs(f[t_die:, dead, :]).max()) == 0.0
    assert float(np.abs(f[:t_die, dead, :]).max()) > 0.0   # alive before
    # Columns still dispatch all arrival mass (renormalized to survivors).
    np.testing.assert_allclose(f[t_die:].sum(axis=1), 1.0, atol=1e-5)
    # The dead site's queue is wiped and stays empty.
    assert float(np.asarray(outs.q_final)[dead].sum()) == 0.0
    # Later epochs place no data there.
    placements = np.asarray(outs.placements)              # (E, K, N)
    assert float(placements[3:, :, dead].max()) == 0.0


def test_backlog_conserved_through_reinjection():
    """With mu = 0 and arrivals only in the first slots, total backlog is an
    invariant — the dead site's queue must re-enter through the burst, not
    vanish."""
    n, k, t = 3, 2, 12
    up = down = jnp.ones((n,))
    d = jnp.array([[0.5, 0.3, 0.2], [0.2, 0.5, 0.3]], jnp.float32)
    arrivals = jnp.zeros((t, k), jnp.float32).at[0].set(
        jnp.array([4.0, 2.0])).at[1].set(jnp.array([1.0, 3.0]))
    inputs = SimInputs(
        arrivals=arrivals,
        mu=jnp.zeros((t, n, k), jnp.float32),
        omega=jnp.ones((t, n), jnp.float32),
        pue=jnp.ones((t, n), jnp.float32),
        r=build_task_allocation(d, up, down),
        p_it=jnp.ones((k,), jnp.float32),
        data_dist=d,
    )
    dead, t_die = 1, 8                                    # mid-epoch (W=6)
    mask = scheduled_failure_trace(t, n, [(dead, t_die, None)])
    outs = simulate_placed(
        inputs, up, down, data_dispatch, static_placement_rule,
        jax.random.key(0), PlacementConfig(epoch_slots=6), alive=mask,
    )
    btot = np.asarray(outs.backlog_total)
    total = float(arrivals.sum())
    np.testing.assert_allclose(btot[1:], total, rtol=1e-5)
    # Across the death edge in particular: nothing lost, nothing invented.
    np.testing.assert_allclose(btot[t_die], btot[t_die - 1], rtol=1e-5)
    q_final = np.asarray(outs.q_final)
    assert float(q_final[dead].sum()) == 0.0
    np.testing.assert_allclose(q_final.sum(), total, rtol=1e-5)
    # The burst was re-dispatched to survivors in the death slot.
    f = np.asarray(outs.f_trace)
    assert float(np.abs(f[t_die:, dead, :]).max()) == 0.0


def test_recovery_cost_fires_exactly_on_failure(paper_setup):
    """recovery_cost > 0 at the death edge (the initial layout spreads data
    on every site, so there is always something to evacuate) and is zero on
    every other slot; the all-alive run bills nothing."""
    cfg, template, _, up, down = paper_setup
    t_die = 77
    mask = scheduled_failure_trace(cfg.t_slots, cfg.n_sites, [(2, t_die, None)])
    assert float(template.data_dist[:, 2].min()) > 0.01   # data to evacuate
    outs = simulate_placed(
        template, up, down, dispatch_fn(1.0), static_placement_rule,
        jax.random.key(5), _pcfg(cfg), alive=mask,
    )
    rc = np.asarray(outs.recovery_cost)
    rgb = np.asarray(outs.recovery_gb)
    assert rc[t_die] > 0.0 and rgb[t_die] > 0.0
    assert float(np.abs(np.delete(rc, t_die)).max()) == 0.0
    assert float(np.abs(np.delete(rgb, t_die)).max()) == 0.0
    # Static rule: the evacuation is pure re-replication of the lost share.
    lost_gb = float(
        (template.data_dist[:, 2] * jnp.asarray(cfg.k_types * [100.0])).sum()
    )
    assert rgb[t_die] == pytest.approx(lost_gb, rel=0.05)


def test_revived_site_rejoins_the_slow_loop(paper_setup):
    """Death then repair: no dispatch while down, and the adaptive slow loop
    is free to re-place data on the revived site afterwards."""
    cfg, template, _, up, down = paper_setup
    dead, t_die, t_up = 0, 60, 120
    mask = scheduled_failure_trace(
        cfg.t_slots, cfg.n_sites, [(dead, t_die, t_up)]
    )
    outs = simulate_placed(
        template, up, down, dispatch_fn(1.0), make_adaptive_rule(up),
        jax.random.key(9), _pcfg(cfg), alive=mask,
    )
    f = np.asarray(outs.f_trace)
    assert float(np.abs(f[t_die:t_up, dead, :]).max()) == 0.0
    assert float(np.abs(f[t_up:, dead, :]).max()) > 0.0
    rc = np.asarray(outs.recovery_cost)
    assert rc[t_die] > 0.0
    assert float(np.abs(np.delete(rc, t_die)).max()) == 0.0  # revival is free


def test_vmapped_fault_path_runs(paper_setup):
    """simulate_placed_many shares the alive mask across Monte-Carlo runs
    (lax.cond lowers to select under vmap — the fault path must survive it)."""
    cfg, template, build, up, down = paper_setup
    mask = scheduled_failure_trace(cfg.t_slots, cfg.n_sites, [(1, 100, None)])
    outs = simulate_placed_many(
        build, up, down, dispatch_fn(1.0), make_adaptive_rule(up),
        jax.random.key(1), 4, _pcfg(cfg), alive=mask,
    )
    assert outs.cost.shape == (4, cfg.t_slots)
    f = np.asarray(outs.f_trace)
    assert float(np.abs(f[:, 100:, 1, :]).max()) == 0.0
    assert (np.asarray(outs.recovery_cost)[:, 100] > 0.0).all()


# ---------------------------------------------------------------------------
# Fault-layer primitives
# ---------------------------------------------------------------------------

def test_drop_site_mask_matches_drop_site():
    """The static-shape mask variant agrees with the shape-changing
    original on the surviving coordinates."""
    key = jax.random.key(4)
    q = jax.random.uniform(key, (4, 2)) * 10
    d = jax.random.dirichlet(key, jnp.full((4,), 2.0), (2,))
    r = build_task_allocation(d, jnp.ones(4), jnp.ones(4))
    dead = 2
    alive = jnp.ones((4,)).at[dead].set(0.0)
    q_ref, _, d_ref, burst_ref = [
        np.asarray(x) for x in drop_site(q, r, d, dead)
    ]
    q2, d_masked, d_drop, burst = drop_site_mask(q, d, alive)
    keep = [0, 1, 3]
    np.testing.assert_allclose(np.asarray(q2)[keep], q_ref, rtol=1e-6)
    assert float(np.asarray(q2)[dead].sum()) == 0.0
    np.testing.assert_allclose(np.asarray(d_drop)[:, keep], d_ref, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(burst), burst_ref, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(d_masked), np.asarray(d) * np.asarray(alive)[None, :]
    )


def test_evacuation_plan_restores_coverage():
    d = jnp.array([[0.5, 0.3, 0.2]])
    alive = jnp.array([1.0, 0.0, 1.0])
    sizes = jnp.array([100.0])
    _, d_masked, d_drop, _ = drop_site_mask(jnp.zeros((3, 1)), d, alive)
    plan = evacuation_plan(d_masked, d_drop, sizes)              # (K, N, N)
    plan_np = np.asarray(plan)
    # Received bytes close exactly the holding gap; dead site neither sends
    # nor receives; nothing self-transfers.
    np.testing.assert_allclose(
        plan_np.sum(1), np.asarray((d_drop - d_masked) * sizes[:, None]),
        atol=1e-4,
    )
    assert plan_np[:, 1, :].sum() == 0.0 and plan_np[:, :, 1].sum() == 0.0
    assert float(np.trace(plan_np[0])) == 0.0
    assert (plan_np >= 0).all()


def test_site_failure_trace_is_seeded_and_respects_min_alive():
    key = jax.random.key(123)
    a = site_failure_trace(key, 500, 4, failure_prob=0.02, repair_slots=30)
    b = site_failure_trace(key, 500, 4, failure_prob=0.02, repair_slots=30)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(np.unique(np.asarray(a))) <= {0.0, 1.0}
    assert float(np.asarray(a).sum(1).min()) >= 1.0          # min_alive
    c = site_failure_trace(jax.random.key(7), 500, 4,
                           failure_prob=0.05, min_alive=3)
    assert float(np.asarray(c).sum(1).min()) >= 3.0
    # Something actually dies at these rates.
    assert float(np.asarray(a).min()) == 0.0
    # Permanent failures never revive.
    p = np.asarray(site_failure_trace(jax.random.key(9), 500, 4,
                                      failure_prob=0.02, repair_slots=None))
    assert (np.diff(p, axis=0) <= 0.0).all()


def test_failure_edges_mark_deaths_only():
    mask = scheduled_failure_trace(10, 2, [(0, 3, 7)])
    edges = np.asarray(failure_edges(mask))
    expected = np.zeros((10, 2), np.float32)
    expected[3, 0] = 1.0                     # death, not the revival at 7
    np.testing.assert_array_equal(edges, expected)
    # A trace that starts dead fires its edge at t=0.
    m0 = scheduled_failure_trace(4, 2, [(1, 0, None)])
    assert failure_edges(m0)[0, 1] == 1.0


@pytest.mark.slow
@pytest.mark.parametrize("trace_seed", [0, 1, 2, 3, 4])
def test_chaos_sweep_random_outages(paper_setup, trace_seed):
    """Nightly chaos sweep: random seeded outage schedules (with repair)
    must uphold every fault invariant at once — no dispatch to dead sites,
    recovery billed only on death edges, placements on the simplex, queues
    finite and non-negative."""
    cfg, template, _, up, down = paper_setup
    mask = site_failure_trace(
        jax.random.key(trace_seed), cfg.t_slots, cfg.n_sites,
        failure_prob=0.01, repair_slots=60,
    )
    outs = simulate_placed(
        template, up, down, dispatch_fn(1.0), make_adaptive_rule(up),
        jax.random.key(trace_seed + 100), _pcfg(cfg), alive=mask,
    )
    m = np.asarray(mask)
    f = np.asarray(outs.f_trace)
    assert float((f * (1 - m)[:, :, None]).max()) == 0.0
    np.testing.assert_allclose(f.sum(1), 1.0, atol=1e-4)
    rc = np.asarray(outs.recovery_cost)
    edges = np.asarray(failure_edges(mask)).max(axis=1)       # (T,)
    assert (rc >= 0).all()
    assert float(rc[edges == 0].max(initial=0.0)) == 0.0      # only on edges
    if edges.any():
        assert rc[edges == 1].sum() >= 0.0
    placements = np.asarray(outs.placements)
    np.testing.assert_allclose(placements.sum(-1), 1.0, atol=1e-4)
    assert (placements >= -1e-6).all()
    btot = np.asarray(outs.backlog_total)
    assert np.isfinite(btot).all() and (btot >= 0).all()
    assert np.isfinite(np.asarray(outs.cost)).all()


def test_ingest_aimed_at_dead_site_redirects_to_survivors(paper_setup):
    """Fresh data cannot land at a dead site: an ingest trace one-hot on
    the dead site spreads uniformly over the survivors instead of silently
    vanishing (the drifted layout must still absorb cfg.growth mass)."""
    cfg, template, _, up, down = paper_setup
    dead = 1
    n_epochs = cfg.t_slots // 48
    one_hot_dead = jnp.zeros((n_epochs, cfg.k_types, cfg.n_sites),
                             jnp.float32).at[:, :, dead].set(1.0)
    mask = scheduled_failure_trace(cfg.t_slots, cfg.n_sites, [(dead, 10, None)])
    pcfg = _pcfg(cfg, growth=0.4)
    outs = simulate_placed(
        template, up, down, data_dispatch, static_placement_rule,
        jax.random.key(2), pcfg, ingest=one_hot_dead, alive=mask,
    )
    placements = np.asarray(outs.placements)                  # (E, K, N)
    np.testing.assert_allclose(placements.sum(-1), 1.0, atol=1e-4)
    assert float(np.abs(placements[1:, :, dead]).max()) == 0.0
    # The redirected ingest visibly pulls later layouts toward uniform over
    # the survivors (static rule never corrects it back).
    survivors = [i for i in range(cfg.n_sites) if i != dead]
    gap0 = np.abs(placements[1][:, survivors] - 1 / 3).max()
    gap_last = np.abs(placements[-1][:, survivors] - 1 / 3).max()
    assert gap_last < gap0


# ---------------------------------------------------------------------------
# io_coupling across a death edge (the stale-epoch-scale fix)
# ---------------------------------------------------------------------------

def test_all_alive_mask_bit_exact_with_io_coupling(paper_setup):
    """The io_coupling fault path keeps the all-ones identity: the per-slot
    mu re-derivation is cond-gated on the death edge, so alive=ones never
    enters it."""
    cfg, template, _, up, down = paper_setup
    pcfg = _pcfg(cfg, io_coupling=True)
    key = jax.random.key(21)
    ones = jnp.ones((cfg.t_slots, cfg.n_sites), jnp.float32)
    o0 = simulate_placed(template, up, down, dispatch_fn(1.0),
                         make_adaptive_rule(up), key, pcfg)
    o1 = simulate_placed(template, up, down, dispatch_fn(1.0),
                         make_adaptive_rule(up), key, pcfg, alive=ones)
    for field in o0._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(o0, field)), np.asarray(getattr(o1, field)),
            err_msg=field,
        )


def test_io_coupling_rescales_inside_recovery_epoch(paper_setup):
    """A mid-epoch death re-derives the I/O service scale from the recovery
    layout per slot — not the stale epoch value.

    Single epoch (W = T), static rule, move_budget = 0: the post-edge
    layout is exactly the survivor-renormalized initial layout, so the
    coupled faulted run must match an UNcoupled faulted run whose mu trace
    is hand-scaled by that layout's slowdown ratio from the edge onward.
    The epoch-0 scale is exactly 1.0, so pre-edge slots agree bitwise.
    """
    from repro.traces.datasets import io_slowdown_from_bandwidth

    cfg, template, _, up, down = paper_setup
    dead, t_die = 1, 100
    mask = scheduled_failure_trace(cfg.t_slots, cfg.n_sites,
                                   [(dead, t_die, None)])
    pcfg = _pcfg(cfg, epoch_slots=cfg.t_slots, io_coupling=True,
                 move_budget=0.0)
    pcfg_off = _pcfg(cfg, epoch_slots=cfg.t_slots, io_coupling=False,
                     move_budget=0.0)
    pol = dispatch_fn(1.0)
    key = jax.random.key(13)

    coupled = simulate_placed(template, up, down, pol,
                              static_placement_rule, key, pcfg, alive=mask)

    # The recovery layout: survivors renormalized, nothing re-placed.
    alive_v = jnp.asarray(mask[t_die])
    masked = template.data_dist * alive_v[None, :]
    d_drop = masked / jnp.sum(masked, axis=1, keepdims=True)
    slow0 = io_slowdown_from_bandwidth(
        up, down, template.data_dist, pcfg.io_compute_seconds, pcfg.io_job_gb
    )
    scale = io_slowdown_from_bandwidth(
        up, down, d_drop, pcfg.io_compute_seconds, pcfg.io_job_gb
    ) / slow0                                                  # (N,)
    assert not np.allclose(np.asarray(scale), 1.0), (
        "evacuation must change the survivors' I/O slowdown for this "
        "scenario to pin anything"
    )
    mu_hand = template.mu.at[t_die:].set(
        template.mu[t_die:] * scale[None, :, None]
    )
    reference = simulate_placed(
        template._replace(mu=mu_hand), up, down, pol,
        static_placement_rule, key, pcfg_off, alive=mask,
    )
    np.testing.assert_allclose(np.asarray(coupled.cost),
                               np.asarray(reference.cost), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(coupled.backlog_total),
                               np.asarray(reference.backlog_total),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(coupled.q_final),
                               np.asarray(reference.q_final),
                               rtol=1e-5, atol=1e-3)

    # And the fix is live: the stale-scale behaviour (uncoupled mu after
    # the edge) visibly diverges from the coupled run.
    stale = simulate_placed(template, up, down, pol,
                            static_placement_rule, key, pcfg_off, alive=mask)
    assert not np.allclose(np.asarray(coupled.backlog_total)[t_die:],
                           np.asarray(stale.backlog_total)[t_die:],
                           rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(coupled.cost)[:t_die], np.asarray(stale.cost)[:t_die]
    )
