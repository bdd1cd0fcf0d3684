"""Sharded Monte-Carlo + carried-r kernel dispatch (repro.distributed.mesh).

Three contracts pinned here:

* **Device-count invariance** — every ``*_many`` / ``sweep_*`` entry point
  produces the same outputs sharded over a runs mesh as the single-device
  vmap, at every device count. The same split keys are merely laid out
  across devices, so this holds per run, not just in distribution: bitwise
  for every engine but ``simulate_placed_many``, whose per-slot cost and
  energy sums XLA:CPU (jax 0.9) rounds differently at a different per-device
  batch (ULP level, 1.7e-7 relative seen; dispatch and placements stay
  bitwise). In-process tests run on whatever devices the process has
  (1 in tier-1; 8 in the CI multi-device job); the subprocess test forces
  an 8-way CPU pod regardless, including the ``n_runs=1000`` case and a
  non-divisible ``n_runs`` exercising pad-and-mask.
* **Carried-r kernel dispatch** — ``make_kernel_policy(r=None)`` reads the
  per-slot ratio tensor from its aux, matching the e-table path on a
  drifting-r run in all three engines; the static-bound variant raises
  loudly when a time-varying trace reaches it.
* **XLA_FLAGS bootstrap ordering** — ``ensure_host_devices`` installs the
  host-device flag before backend init and raises after it.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
from repro.configs.facebook_4dc_stages import (
    StagedPaperConfig,
    make_staged_builder,
)
from repro.core.gmsa import gmsa_policy, make_kernel_policy
from repro.core.simulator import simulate, simulate_many
from repro.core.sweep import sweep_grid, sweep_placed_budgets
from repro.distributed.mesh import runs_mesh, sharded_runs
from repro.jobs import simulate_staged, simulate_staged_many
from repro.placement import PlacementConfig, make_adaptive_rule
from repro.placement.controller import simulate_placed, simulate_placed_many
from repro.traces.bandwidth import bandwidth_draw
from repro.traces.datasets import io_slowdown_from_bandwidth
from repro.traces.faults import site_failure_trace

V_POINTS = (0.1, 1.0, 10.0)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device_counts():
    have = jax.device_count()
    return [d for d in (1, 2, 4, 8) if d <= have]


def _trees_equal(a, b):
    return all(
        bool(jnp.all(x == y))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


@pytest.fixture(scope="module")
def paper_setup():
    cfg = PaperSimConfig(t_slots=48)
    template, build = make_sim_builder(cfg)
    root = jax.random.key(cfg.trace_seed)
    up, down = bandwidth_draw(jax.random.split(root, 6)[2], cfg.n_sites)
    return cfg, template, build, up, down


@pytest.fixture(scope="module")
def staged_setup():
    cfg = StagedPaperConfig(t_slots=48)
    template, dag, wan, build = make_staged_builder(cfg)
    return cfg, template, dag, wan, build


def drifting_r(template, t_slots):
    """A (T, K, N, N) ratio trace that actually moves over the horizon."""
    drift = jnp.linspace(0.0, 1.0, t_slots)[:, None, None, None]
    r_alt = jnp.roll(template.r, 1, axis=-1)
    r_tv = (1.0 - drift) * template.r[None] + drift * r_alt[None]
    return r_tv / jnp.maximum(r_tv.sum(-1, keepdims=True), 1e-9)


# ---------------------------------------------------------------------------
# device-count invariance (in-process: every count the process has)


@pytest.mark.parametrize("n_dev", _device_counts())
def test_simulate_many_mesh_invariance(paper_setup, n_dev):
    _, _, build, _, _ = paper_setup
    key = jax.random.key(3)
    mesh = runs_mesh(n_dev)
    # 10 is not divisible by 4 or 8: the pad-and-mask path runs in-process
    # whenever the process has the devices.
    ref = simulate_many(build, gmsa_policy, key, 10)
    out = simulate_many(build, gmsa_policy, key, 10, mesh=mesh)
    assert out.cost.shape == ref.cost.shape
    assert _trees_equal(ref, out)


@pytest.mark.parametrize("n_dev", _device_counts())
def test_sweep_grid_mesh_invariance(paper_setup, n_dev):
    cfg, _, build, _, _ = paper_setup
    key = jax.random.key(4)
    mesh = runs_mesh(n_dev)
    ref = sweep_grid(build, gmsa_policy, key, 6, V_POINTS)
    out = sweep_grid(build, gmsa_policy, key, 6, V_POINTS, mesh=mesh)
    assert out.cost.shape == (len(V_POINTS), 6, cfg.t_slots)
    assert _trees_equal(ref, out)


def test_staged_many_mesh_invariance(staged_setup):
    _, _, dag, wan, build = staged_setup
    key = jax.random.key(5)
    mesh = runs_mesh()
    ref = simulate_staged_many(build, dag, wan, gmsa_policy, key, 5)
    out = simulate_staged_many(build, dag, wan, gmsa_policy, key, 5,
                               mesh=mesh)
    assert _trees_equal(ref, out)


def test_placed_many_mesh_invariance_with_faults(paper_setup):
    cfg, _, build, up, down = paper_setup
    key = jax.random.key(6)
    rule = make_adaptive_rule(up)
    pcfg = PlacementConfig(epoch_slots=12, manager_share=cfg.manager_share)
    alive = site_failure_trace(
        jax.random.key(9), cfg.t_slots, cfg.n_sites,
        failure_prob=0.02, repair_slots=10,
    )
    assert bool(jnp.any(alive < 0.5)), "fault trace must actually fire"
    mesh = runs_mesh()
    ref = simulate_placed_many(build, up, down, gmsa_policy, rule, key, 5,
                               pcfg, alive=alive)
    out = simulate_placed_many(build, up, down, gmsa_policy, rule, key, 5,
                               pcfg, alive=alive, mesh=mesh)
    assert _trees_equal(ref, out)


def test_sweep_placed_budgets_mesh_invariance(paper_setup):
    cfg, _, build, up, down = paper_setup
    key = jax.random.key(7)
    rule = make_adaptive_rule(up)
    pcfg = PlacementConfig(epoch_slots=12, manager_share=cfg.manager_share)
    budgets = (0.1, 0.9)
    mesh = runs_mesh()
    ref = sweep_placed_budgets(build, up, down, gmsa_policy, rule, key, 5,
                               pcfg, budgets)
    out = sweep_placed_budgets(build, up, down, gmsa_policy, rule, key, 5,
                               pcfg, budgets, mesh=mesh)
    assert ref.cost.shape == out.cost.shape
    assert _trees_equal(ref, out)


def test_sharded_runs_rejects_foreign_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    keys = jax.random.split(jax.random.key(0), 4)
    with pytest.raises(ValueError, match="runs"):
        sharded_runs(lambda k: k, keys, mesh)


def test_runs_mesh_rejects_overask():
    with pytest.raises(ValueError, match="device"):
        runs_mesh(jax.device_count() + 1)


# ---------------------------------------------------------------------------
# carried-r kernel dispatch (the make_kernel_policy static-binding bugfix)


def test_carried_r_matches_ref_on_drifting_trace(paper_setup):
    cfg, template, _, _, _ = paper_setup
    key = jax.random.key(11)
    inp_tv = template._replace(r=drifting_r(template, cfg.t_slots))
    ref = simulate(inp_tv, gmsa_policy, key)          # e-tables see (T,K,N,N)
    for impl in ("ref", "kernel"):
        out = simulate(
            inp_tv, make_kernel_policy(p_it=template.p_it, impl=impl), key
        )
        np.testing.assert_array_equal(
            np.asarray(ref.f_trace), np.asarray(out.f_trace),
            err_msg=f"impl={impl}",
        )
        np.testing.assert_array_equal(
            np.asarray(ref.cost), np.asarray(out.cost), err_msg=f"impl={impl}"
        )


@pytest.mark.parametrize("carried", [False, True], ids=["static_r", "carried_r"])
def test_simulate_many_kernel_matches_table_on_every_run(paper_setup, carried):
    """Monte-Carlo runs through the kernel policy — one runs-on-lanes launch
    per slot, r shared (static) or drifting differently in every run
    (carried) — make every run's decisions of the table path."""
    cfg, template, build, _, _ = paper_setup
    key = jax.random.key(13)
    if carried:
        r_tv = drifting_r(template, cfg.t_slots)

        def run_build(k):
            mix = jax.random.uniform(jax.random.fold_in(k, 1))
            return build(k)._replace(r=(1 - mix) * r_tv + mix * jnp.roll(r_tv, 2, -1))

        policy = make_kernel_policy(p_it=template.p_it)
    else:
        run_build, policy = build, make_kernel_policy(template.r, template.p_it)
    ref = simulate_many(run_build, gmsa_policy, key, 9)
    out = simulate_many(run_build, policy, key, 9)
    np.testing.assert_array_equal(np.asarray(ref.f_trace), np.asarray(out.f_trace))
    np.testing.assert_array_equal(np.asarray(ref.cost), np.asarray(out.cost))


def test_static_r_policy_raises_on_time_varying_trace(paper_setup):
    cfg, template, _, _, _ = paper_setup
    inp_tv = template._replace(r=drifting_r(template, cfg.t_slots))
    static_pol = make_kernel_policy(template.r, template.p_it, impl="ref")
    with pytest.raises(ValueError, match="stale"):
        simulate(inp_tv, static_pol, jax.random.key(0))


def test_static_r_policy_still_exact_on_static_trace(paper_setup):
    _, template, _, _, _ = paper_setup
    key = jax.random.key(12)
    static_pol = make_kernel_policy(template.r, template.p_it, impl="ref")
    ref = simulate(template, gmsa_policy, key)
    out = simulate(template, static_pol, key)
    np.testing.assert_array_equal(
        np.asarray(ref.f_trace), np.asarray(out.f_trace)
    )


def test_carried_r_through_staged_engine(staged_setup):
    cfg, template, dag, wan, _ = staged_setup
    key = jax.random.key(13)
    inp_tv = template._replace(r=drifting_r(template, cfg.t_slots))
    ref = simulate_staged(inp_tv, dag, wan, gmsa_policy, key)
    out = simulate_staged(
        inp_tv, dag, wan, make_kernel_policy(p_it=template.p_it, impl="ref"),
        key,
    )
    np.testing.assert_array_equal(
        np.asarray(ref.f_trace), np.asarray(out.f_trace)
    )
    static_pol = make_kernel_policy(template.r, template.p_it, impl="ref")
    with pytest.raises(ValueError, match="stale"):
        simulate_staged(inp_tv, dag, wan, static_pol, key)


def test_carried_r_through_controller_with_faults(paper_setup):
    """The controller's carried r_c/r_e reaches the kernel path exactly.

    gmsa_policy consumes the controller's cond-carried energy rows; the
    carried-r kernel policy re-derives the same decision from the raw
    ``(r_c, wpue_t)`` operands — equality across epoch rebuilds AND
    mid-epoch recovery re-placements is the bugfix's acceptance gate.
    """
    cfg, template, _, up, down = paper_setup
    key = jax.random.key(14)
    rule = make_adaptive_rule(up)
    pcfg = PlacementConfig(epoch_slots=12, manager_share=cfg.manager_share)
    alive = site_failure_trace(
        jax.random.key(9), cfg.t_slots, cfg.n_sites,
        failure_prob=0.02, repair_slots=10,
    )
    carried = make_kernel_policy(p_it=template.p_it, impl="ref")
    for kwargs in ({}, {"alive": alive}):
        ref = simulate_placed(template, up, down, gmsa_policy, rule, key,
                              pcfg, **kwargs)
        out = simulate_placed(template, up, down, carried, rule, key,
                              pcfg, **kwargs)
        np.testing.assert_array_equal(
            np.asarray(ref.f_trace), np.asarray(out.f_trace),
            err_msg=f"kwargs={list(kwargs)}",
        )
    static_pol = make_kernel_policy(template.r, template.p_it, impl="ref")
    with pytest.raises(ValueError, match="stale"):
        simulate_placed(template, up, down, static_pol, rule, key, pcfg)


# ---------------------------------------------------------------------------
# per-reader I/O slowdown (carried ROADMAP follow-on)


def test_per_reader_io_slowdown_disagrees_with_average():
    """Averaged and per-reader models must disagree where locality is mixed.

    Two sites, two types: type 0 lives at site 0, type 1 at site 1. The
    averaged model sees 50% locality at both sites and slows every type;
    the per-reader model knows type 0's reader at site 0 holds a local
    replica (not slowed at all) while its reader at site 1 pulls remotely.
    """
    from repro.placement.replica import replica_read_assignment
    from repro.placement.wan import wan_topology as wt

    up = jnp.asarray([1.0, 1.0])
    down = jnp.asarray([0.1, 0.1])      # slow downlinks: visible transfer
    d = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)   # (K, N)
    wan = wt(up, down)
    reads = replica_read_assignment(d, wan, jnp.ones((2,), jnp.float32))

    avg = io_slowdown_from_bandwidth(up, down, d)            # (N,)
    per = io_slowdown_from_bandwidth(up, down, d, reads=reads)  # (N, K)
    assert per.shape == (2, 2)
    # Local type not slowed; remote type slowed more than the average says.
    np.testing.assert_allclose(float(per[0, 0]), 1.0)
    np.testing.assert_allclose(float(per[1, 1]), 1.0)
    assert float(per[0, 1]) < float(avg[0]) < 1.0
    assert float(per[1, 0]) < float(avg[1]) < 1.0


def test_controller_per_reader_io_differs_and_default_unchanged(paper_setup):
    cfg, template, _, up, down = paper_setup
    key = jax.random.key(15)
    rule = make_adaptive_rule(up)
    base = dict(epoch_slots=12, manager_share=cfg.manager_share,
                io_coupling=True)
    ref = simulate_placed(template, up, down, gmsa_policy, rule, key,
                          PlacementConfig(**base))
    per = simulate_placed(template, up, down, gmsa_policy, rule, key,
                          PlacementConfig(**base, io_per_reader=True))
    # The per-reader model is a different (finer) model: it must actually
    # change the realized service scale on a mixed-locality scenario.
    assert not np.array_equal(np.asarray(ref.mu_scale),
                              np.asarray(per.mu_scale))
    # And io_per_reader=False stays bitwise the pre-change model.
    again = simulate_placed(template, up, down, gmsa_policy, rule, key,
                            PlacementConfig(**base))
    assert _trees_equal(ref, again)


# ---------------------------------------------------------------------------
# subprocess: forced 8-way CPU pod — invariance at n_runs=1000 + pad case


_INVARIANCE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
    from repro.configs.facebook_4dc_stages import (
        StagedPaperConfig, make_staged_builder,
    )
    from repro.core.gmsa import gmsa_policy
    from repro.core.simulator import simulate_many
    from repro.core.sweep import sweep_grid
    from repro.distributed.mesh import runs_mesh
    from repro.jobs import simulate_staged_many
    from repro.placement import PlacementConfig, make_adaptive_rule
    from repro.placement.controller import simulate_placed_many
    from repro.traces.bandwidth import bandwidth_draw
    from repro.traces.faults import site_failure_trace

    def eq(a, b):
        return all(bool(jnp.all(x == y))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))

    report = {"devices": jax.device_count()}
    mesh = runs_mesh()
    key = jax.random.key(0)

    cfg = PaperSimConfig(t_slots=48)
    template, build = make_sim_builder(cfg)
    # n_runs=1000 divides 8 ways; 1001 exercises pad-and-mask.
    for n in (1000, 1001):
        ref = simulate_many(build, gmsa_policy, key, n)
        out = simulate_many(build, gmsa_policy, key, n, mesh=mesh)
        report[f"simulate_many_{n}"] = eq(ref, out)
        report[f"rows_{n}"] = int(out.cost.shape[0])

    ga = sweep_grid(build, gmsa_policy, key, 12, (0.1, 1.0, 10.0))
    gb = sweep_grid(build, gmsa_policy, key, 12, (0.1, 1.0, 10.0), mesh=mesh)
    report["sweep_grid"] = eq(ga, gb)

    scfg = StagedPaperConfig(t_slots=48)
    stemplate, dag, wan, sbuild = make_staged_builder(scfg)
    sa = simulate_staged_many(sbuild, dag, wan, gmsa_policy, key, 12)
    sb = simulate_staged_many(sbuild, dag, wan, gmsa_policy, key, 12,
                              mesh=mesh)
    report["simulate_staged_many"] = eq(sa, sb)

    root = jax.random.key(cfg.trace_seed)
    up, down = bandwidth_draw(jax.random.split(root, 6)[2], cfg.n_sites)
    rule = make_adaptive_rule(up)
    pcfg = PlacementConfig(epoch_slots=12, manager_share=cfg.manager_share)
    alive = site_failure_trace(jax.random.key(9), cfg.t_slots, cfg.n_sites,
                               failure_prob=0.02, repair_slots=10)
    report["fault_fired"] = bool(jnp.any(alive < 0.5))
    pa = simulate_placed_many(build, up, down, gmsa_policy, rule, key, 12,
                              pcfg, alive=alive)
    pb = simulate_placed_many(build, up, down, gmsa_policy, rule, key, 12,
                              pcfg, alive=alive, mesh=mesh)
    bills = ("cost", "energy")
    report["simulate_placed_many"] = eq(
        pa._replace(**{f: None for f in bills}),
        pb._replace(**{f: None for f in bills}))
    report["simulate_placed_many_bills"] = all(
        bool(jnp.allclose(getattr(pa, f), getattr(pb, f), rtol=1e-6, atol=0))
        for f in bills)
    print(json.dumps(report))
""")


def test_eight_device_invariance_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _INVARIANCE_PROG],
        capture_output=True, text=True, cwd=_REPO_ROOT, env=env, timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    report = json.loads(res.stdout.strip().splitlines()[-1])
    assert report["devices"] == 8
    assert report["fault_fired"]
    assert report["simulate_many_1000"]
    assert report["simulate_many_1001"]
    assert report["rows_1000"] == 1000   # summaries weight real run count
    assert report["rows_1001"] == 1001   # padded-and-masked, not truncated
    assert report["sweep_grid"]
    assert report["simulate_staged_many"]
    assert report["simulate_placed_many"]
    assert report["simulate_placed_many_bills"]


# ---------------------------------------------------------------------------
# XLA_FLAGS bootstrap ordering


_BOOTSTRAP_OK_PROG = textwrap.dedent("""
    import sys; sys.path.insert(0, "src")
    import json, os
    # Before any jax backend init: the flag must take effect.
    from repro.distributed.mesh import ensure_host_devices
    n = ensure_host_devices(6)
    import jax
    print(json.dumps({
        "requested": n,
        "flag": os.environ.get("XLA_FLAGS", ""),
        "devices": jax.device_count(),
    }))
""")

_BOOTSTRAP_LATE_PROG = textwrap.dedent("""
    import sys; sys.path.insert(0, "src")
    import json
    import jax
    jax.devices()          # backends initialize with 1 CPU device
    from repro.distributed.mesh import ensure_host_devices
    try:
        ensure_host_devices(8)
        print(json.dumps({"raised": False}))
    except RuntimeError as e:
        print(json.dumps({"raised": True, "msg": str(e)[:240]}))
""")


def test_xla_flags_bootstrap_ordering_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    ok = subprocess.run(
        [sys.executable, "-c", _BOOTSTRAP_OK_PROG],
        capture_output=True, text=True, cwd=_REPO_ROOT, env=env, timeout=240,
    )
    assert ok.returncode == 0, ok.stderr[-2000:]
    report = json.loads(ok.stdout.strip().splitlines()[-1])
    assert "--xla_force_host_platform_device_count=6" in report["flag"]
    assert report["devices"] == 6

    late = subprocess.run(
        [sys.executable, "-c", _BOOTSTRAP_LATE_PROG],
        capture_output=True, text=True, cwd=_REPO_ROOT, env=env, timeout=240,
    )
    assert late.returncode == 0, late.stderr[-2000:]
    report = json.loads(late.stdout.strip().splitlines()[-1])
    assert report["raised"]
    assert "before the first" in report["msg"]


def test_ensure_host_devices_noop_when_enough():
    # Backends are initialized in-process; asking for what we already have
    # is a no-op rather than an error.
    assert jax.device_count() >= 1
    from repro.distributed.mesh import ensure_host_devices

    assert ensure_host_devices(1) == jax.device_count()
