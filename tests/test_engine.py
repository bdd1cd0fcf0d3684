"""FleetEngine integration: staged dispatch over real (tiny) models."""

import numpy as np
import pytest

from repro.launch.serve import build_engine


@pytest.fixture(scope="module")
def engine():
    return build_engine(["qwen2-0.5b"], slots=12, v=1.0, seed=3, arrival=4.0)


def test_dispatch_only_run(engine):
    out = engine.run(execute_real=False)
    assert out["cost"].shape == (12,)
    assert np.all(out["cost"] >= 0)
    f = out["dispatch"]                      # (T, N, K, S)
    assert f.shape == (12, 4, 1, 2)
    np.testing.assert_allclose(f.sum(axis=1), 1.0, atol=1e-5)
    # energy pricing uses the FULL architecture (0.49B params), not smoke
    assert engine.p_it[0] > 0


def test_history_records_choice_queue_energy(engine):
    out = engine.run(execute_real=False)
    hist = out["history"]
    assert [h["t"] for h in hist] == list(range(12))
    for t, h in enumerate(hist):
        # Choice is the argmax pod of the decode (final) stage's dispatch.
        np.testing.assert_array_equal(
            h["choice"], out["dispatch"][t][:, :, -1].argmax(axis=0))
        assert len(h["q_pod"]) == engine.fcfg.n_pods
        assert all(d >= 0.0 for d in h["q_pod"])
        assert all(j >= 0.0 for j in h["energy_j"])
    # Per-pod depths re-sum to the recorded total backlog.
    np.testing.assert_allclose(
        [sum(h["q_pod"]) for h in hist], out["backlog"], rtol=1e-5)
    # Energy pricing actually priced something over the horizon.
    assert sum(sum(h["energy_j"]) for h in hist) > 0.0


def test_stream_callback_receives_ordered_slots(engine):
    seen = []
    out = engine.run(execute_real=False, stream=seen.append)
    import jax
    jax.effects_barrier()
    assert [r["t"] for r in seen] == list(range(12))
    for r, c, b in zip(seen, out["cost"], out["backlog"]):
        assert r["type"] == "metric" and r["engine"] == "serve"
        assert r["cost"] == pytest.approx(float(c), rel=1e-4, abs=1e-10)
        assert r["backlog"] == pytest.approx(float(b), rel=1e-5, abs=1e-12)


def test_real_execution_smoke(engine):
    out = engine.run(execute_real=True)
    assert out["exec_seconds"] > 0           # models actually ran
    assert out["exec_jobs"] > 0
    assert out["final_backlog"] < 200        # stable under staged dispatch


def test_high_v_prefers_cheap_pods():
    """High V lowers the bill the dispatcher prices: compute plus the
    KV-handoff WAN transfer. The decode-site score includes the KV pull, so
    high V may trade a little compute for a cheaper handoff; on this draw
    compute alone rises 0.6% while the total falls 0.6%."""
    e1 = build_engine(["qwen2-0.5b"], slots=24, v=0.001, seed=5, arrival=4.0)
    e2 = build_engine(["qwen2-0.5b"], slots=24, v=1000.0, seed=5, arrival=4.0)
    o1 = e1.run(execute_real=False)
    o2 = e2.run(execute_real=False)
    assert o2["total_billed_cost"] <= o1["total_billed_cost"] * 1.001


def test_staged_beats_random_dispatch_on_fleet():
    """Fleet-level quantification: the joint stage scheduler vs RANDOM
    dispatch on the SAME scenario traces (the paper's headline, on the
    LLM fleet instead of Hadoop jobs). Unlike the old hand-rolled replay,
    both arms now run the same engine on the same arrivals/mu draws, so
    the deltas are pure policy. In the serving regime the per-job energy
    is kWh-scale, so most of the dispatchable headroom is queueing: the
    pin is a strict compute-cost saving plus a large backlog reduction.

    Both are averaged over eight scenario draws: per draw (seeds 0-7) the
    saving ranges 1.1-4.7% and the backlog ratio 0.57-1.15, so one seed
    pins a draw, not the policy."""
    import jax

    from repro.core.baselines import random_dispatch
    from repro.jobs.engine import simulate_staged
    from repro.jobs.scheduler import stage_oblivious

    savings, ratios = [], []
    for seed in range(8):
        engine = build_engine(["qwen2-0.5b", "granite-3-2b"], slots=48,
                              v=10.0, seed=seed, arrival=5.0)
        out = engine.run(execute_real=False)

        # RANDOM as the old engine ran it: any pod may serve any job
        # (unpinned), on the identical admitted arrivals / capacity draws.
        scn = engine.scenario
        outs = simulate_staged(
            scn.inputs, scn.dag, scn.wan,
            stage_oblivious(random_dispatch, pin_map=False),
            jax.random.key(123), engine.fcfg.v,
        )
        savings.append(1.0 - out["mean_cost"]
                       / float(np.asarray(outs.cost).mean()))
        ratios.append(out["backlog"].mean()
                      / float(np.asarray(outs.backlog_total).mean()))
    assert np.mean(savings) > 0.03, f"fleet compute savings {savings}"
    assert np.mean(ratios) < 0.8, f"backlog ratios {ratios}"
