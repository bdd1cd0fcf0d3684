"""The unified serving control plane: FleetEngine driven by the sim stack.

The contracts pinned here are the point of the serving refactor:

* **replay parity** — a dispatch-only run replays ``simulate_staged`` on
  the shared scenario: per-slot dispatch choices bit-for-bit, billed cost
  to float tolerance;
* **seed determinism** — same config, same traces, same decisions;
* **request conservation** — raw arrivals split exactly into
  admitted + rejected, and admitted mass ends as completed + backlog;
* **served-priced energy** — ``history["energy_j"]`` bills jobs actually
  served (``min(q + f·A, mu)``, compute-weighted), never more than
  admitted;
* **capacity_shares derivation** — ``n_pods=8`` runs end-to-end instead
  of silently truncating (or crashing in) the shares tuple;
* **exact execution counts** — ``_execute_jobs`` runs exactly ``n_jobs``,
  not the next multiple of ``batch_per_exec``;
* **pod-death recovery** — the drain wipes the dead pod, re-injects its
  backlog at the prefill stage, lands a recovery event in the history and
  the telemetry stream, and an all-ones mask is bit-exact no-fault.
"""

import jax
import numpy as np
import pytest

from repro.jobs.engine import simulate_staged
from repro.launch.serve import build_engine
from repro.serve.engine import (
    FleetConfig,
    FleetEngine,
    build_serve_scenario,
    serve_policy,
)


@pytest.fixture(scope="module")
def engine():
    return build_engine(["qwen2-0.5b", "mamba2-2.7b"], slots=12, v=1.0,
                        seed=3, arrival=4.0, admit_max=5.0)


@pytest.fixture(scope="module")
def out(engine):
    return engine.run(execute_real=False)


# ---------------------------------------------------------------------------
# Replay parity and determinism
# ---------------------------------------------------------------------------

def test_dispatch_replays_simulate_staged(engine, out):
    """The parity pin: FleetEngine.run is simulate_staged on the shared
    scenario — same per-slot dispatch vertices, same bills."""
    scn = engine.scenario
    pol = serve_policy(engine.fcfg, scn)
    outs = simulate_staged(
        scn.inputs, scn.dag, scn.wan, pol, jax.random.key(0), engine.fcfg.v
    )
    np.testing.assert_array_equal(out["dispatch"], np.asarray(outs.f_trace))
    np.testing.assert_allclose(
        out["cost"], np.asarray(outs.cost), rtol=1e-5, atol=1e-12
    )
    # The serving loop and the scan are differently fused programs: the
    # KV-handoff bill may differ at ULP level (1.1e-7 relative was seen).
    np.testing.assert_allclose(
        out["wan_cost"], np.asarray(outs.wan_cost), rtol=1e-6, atol=0.0
    )
    sim_total = float(
        np.asarray(outs.cost).sum() + np.asarray(outs.wan_cost).sum()
    )
    assert out["total_billed_cost"] == pytest.approx(sim_total, rel=1e-6)
    np.testing.assert_allclose(
        out["backlog"], np.asarray(outs.backlog_total), rtol=1e-5, atol=1e-5
    )


def test_seed_determinism(engine, out):
    eng2 = build_engine(["qwen2-0.5b", "mamba2-2.7b"], slots=12, v=1.0,
                        seed=3, arrival=4.0, admit_max=5.0)
    out2 = eng2.run(execute_real=False)
    np.testing.assert_array_equal(out["dispatch"], out2["dispatch"])
    np.testing.assert_array_equal(out["cost"], out2["cost"])
    np.testing.assert_array_equal(out["raw_arrivals"], out2["raw_arrivals"])
    # A different seed draws different traffic.
    eng3 = build_engine(["qwen2-0.5b", "mamba2-2.7b"], slots=12, v=1.0,
                        seed=4, arrival=4.0, admit_max=5.0)
    assert not np.array_equal(
        eng3.scenario.raw_arrivals, out["raw_arrivals"]
    )


# ---------------------------------------------------------------------------
# Conservation and the accounting fixes
# ---------------------------------------------------------------------------

def test_request_conservation(engine, out):
    # Admission split is exact, elementwise.
    np.testing.assert_array_equal(
        out["raw_arrivals"], out["admitted"] + out["rejected"]
    )
    assert out["rejected"].sum() > 0          # the cap actually binds here
    assert (out["admitted"] <= engine.fcfg.admit_max + 1e-6).all()
    # Everything admitted is either completed or still queued.
    np.testing.assert_allclose(
        out["admitted"].sum(axis=0),
        out["completed"].sum(axis=0) + out["q_final"].sum(axis=(0, 2)),
        rtol=1e-5, atol=1e-3,
    )


def test_energy_prices_served_not_dispatched(engine, out):
    e_per_job = np.asarray([rc.energy_per_job_j() for rc in engine.classes])
    hist_e = np.asarray([h["energy_j"] for h in out["history"]])   # (T, K)
    np.testing.assert_allclose(
        hist_e, out["served"] * e_per_job[None, :], rtol=1e-6
    )
    # Never bill more than the admitted mass (the old engine billed every
    # dispatched job even when execution capped far below).
    assert (
        hist_e.sum(axis=0) <= e_per_job * out["admitted"].sum(axis=0) + 1e-6
    ).all()
    # With positive backlog at some slot, served < dispatched mass there.
    assert out["served"].sum() < out["admitted"].sum() + 1e-6


def test_execute_jobs_exact_count(engine):
    rc = engine.classes[0]
    b = engine.fcfg.batch_per_exec
    for n_jobs in (1, b - 1, b, b + 1, 2 * b + 3):
        done, secs = engine._execute_jobs(rc, n_jobs)
        assert done == n_jobs, (n_jobs, done)
    assert engine._execute_jobs(rc, 0) == (0, 0.0)


# ---------------------------------------------------------------------------
# FleetConfig shares derivation
# ---------------------------------------------------------------------------

def test_capacity_shares_derived_for_any_pod_count():
    fc = FleetConfig(n_pods=8)
    assert len(fc.capacity_shares) == 8
    assert fc.capacity_shares[:4] == fc.capacity_shares[4:]   # cycled
    fc3 = FleetConfig(n_pods=3)
    assert fc3.capacity_shares == (0.3, 0.2, 0.9)
    with pytest.raises(ValueError):
        FleetConfig(n_pods=2, capacity_shares=())
    with pytest.raises(ValueError):
        FleetConfig(dispatch="magic")


def test_eight_pods_run_end_to_end():
    eng = build_engine(["qwen2-0.5b"], slots=8, v=1.0, seed=1, arrival=4.0,
                       n_pods=8)
    out = eng.run(execute_real=False)
    assert out["dispatch"].shape == (8, 8, 1, 2)
    np.testing.assert_allclose(out["dispatch"].sum(axis=1), 1.0, atol=1e-5)
    assert np.isfinite(out["cost"]).all()


# ---------------------------------------------------------------------------
# Pod death: drain, re-injection, telemetry
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fault_run():
    base = build_engine(["qwen2-0.5b"], slots=12, v=1.0, seed=3, arrival=6.0)
    # Slow pods down so the dying pod carries backlog at the edge.
    fcfg = FleetConfig(
        n_pods=4, horizon_slots=12, v=1.0, seed=3,
        capacity_shares=(0.1, 0.1, 0.1, 0.1),
    )
    dead, t_die = 1, 6
    alive = np.ones((12, 4), np.float32)
    alive[t_die:, dead] = 0.0
    eng = FleetEngine(fcfg, base.classes, base.omega, base.pue, base.r,
                      alive=alive)
    stream = []
    out = eng.run(execute_real=False, stream=stream.append)
    jax.effects_barrier()
    return eng, out, stream, dead, t_die


def test_pod_death_drains_and_reinjects(fault_run):
    eng, out, _, dead, t_die = fault_run
    f = out["dispatch"]
    assert float(np.abs(f[t_die:, dead]).max()) == 0.0       # no new work
    assert float(np.abs(f[:t_die, dead]).max()) > 0.0        # busy before
    np.testing.assert_allclose(f.sum(axis=1), 1.0, atol=1e-5)
    # The wiped queue re-enters as a prefill burst: nothing admitted is lost.
    np.testing.assert_allclose(
        out["admitted"].sum(axis=0),
        out["completed"].sum(axis=0) + out["q_final"].sum(axis=(0, 2)),
        rtol=1e-4, atol=1e-2,
    )
    assert float(out["q_final"][dead].sum()) == 0.0
    ev = out["events"]
    assert len(ev) == 1 and ev[0]["t"] == t_die and ev[0]["pod"] == dead
    assert ev[0]["drained"] > 0.0                            # real backlog
    assert out["history"][t_die]["recovery"]["code"] == "recovery"


def test_recovery_event_reaches_stream_in_order(fault_run):
    _, out, stream, dead, t_die = fault_run
    kinds = [(r["type"], r["t"]) for r in stream]
    assert ("event", t_die) in kinds
    # The event lands at its slot position within the ordered stream.
    idx = kinds.index(("event", t_die))
    assert kinds[idx - 1] == ("metric", t_die)
    ev = stream[idx]
    assert ev["code"] == "recovery" and ev["pod"] == dead
    metrics = [r for r in stream if r["type"] == "metric"]
    assert [r["t"] for r in metrics] == list(range(12))


def test_all_ones_alive_is_bit_exact(engine, out):
    ones = np.ones((12, 4), np.float32)
    eng = FleetEngine(engine.fcfg, engine.classes, engine.omega, engine.pue,
                      engine.r, alive=ones)
    out1 = eng.run(execute_real=False)
    np.testing.assert_array_equal(out["dispatch"], out1["dispatch"])
    np.testing.assert_array_equal(out["cost"], out1["cost"])
    np.testing.assert_array_equal(out["wan_cost"], out1["wan_cost"])
    assert out1["events"] == []


# ---------------------------------------------------------------------------
# Scenario construction details
# ---------------------------------------------------------------------------

def test_replica_reads_route_prefill(engine):
    scn = engine.scenario
    reads = np.asarray(scn.reads)                            # (K, N, N)
    np.testing.assert_allclose(reads.sum(axis=-1), 1.0, atol=1e-5)
    serve_dist = np.asarray(scn.inputs.data_dist)
    np.testing.assert_allclose(serve_dist, reads.mean(axis=1), atol=1e-6)
    # Prefill dispatch is pinned to the serving distribution every slot.
    out = engine.run(execute_real=False)
    for t in range(12):
        np.testing.assert_allclose(
            out["dispatch"][t][:, :, 0], serve_dist.T, atol=1e-6
        )


def test_kv_handoff_priced_when_decode_moves(engine, out):
    scn = engine.scenario
    kv = np.asarray(scn.dag.shuffle_gb)
    assert (kv[:, 0] == 0.0).all() and (kv[:, 1] > 0.0).all()
    # Decode sometimes lands off the prefill mix, so the KV bill is real.
    assert out["wan_gb"].sum() > 0.0


def test_fleet_records_stream(engine, out):
    from repro.telemetry import fleet_records

    recs = fleet_records(out, meta={"slo_backlog": engine.fcfg.slo_backlog})
    assert recs[0]["type"] == "meta" and recs[0]["kind"] == "serve"
    metrics = [r for r in recs if r["type"] == "metric"]
    assert [r["t"] for r in metrics] == list(range(12))
    assert recs[-1]["type"] == "summary"
    assert recs[-1]["total_billed_cost"] == pytest.approx(
        out["total_billed_cost"]
    )
