"""repro.telemetry observability layer (PR 8): distributions, spans, SLOs.

The load-bearing guarantees, in test form:

* The histogram layer is FREE when off — ``TelemetryConfig(level=OFF,
  hist=...)`` still traces to the byte-identical jaxpr of ``telemetry=None``
  on every engine (metrics enabled-then-disabled), and with metrics ON the
  engine *outputs* stay bitwise.
* The decode is HONEST — histogram percentile estimates sit within their
  own reported error bound of the exact ``np.percentile`` /
  weighted-replay answer, for interior, underflow and overflow mass.
* The serving sojourn clock matches an exact host-side FIFO replay of the
  same admitted/completed flow, faulted or not, and conserves mass.
* Span export emits valid Chrome trace-event JSON for a faulted serve
  run with the recovery visible.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
from repro.core.baselines import data_dispatch
from repro.core.gmsa import dispatch_fn
from repro.core.simulator import _energy_tables, simulate
from repro.jobs import simulate_staged
from repro.jobs.dag import single_stage_dag
from repro.jobs.scheduler import stage_service_rates_all
from repro.launch.serve import build_engine
from repro.placement import (
    PlacementConfig,
    make_adaptive_rule,
    simulate_placed,
    wan_topology,
)
from repro.telemetry import (
    OFF,
    SUMMARY,
    TRACE,
    HistogramSpec,
    SloSpec,
    TelemetryConfig,
    fifo_sojourn_replay,
    fleet_records,
    hist_add,
    hist_init,
    hist_quantiles,
    hist_series,
    read_jsonl,
    render_timeline,
    sojourn_init,
    sojourn_step,
    sparkline,
    to_chrome_trace,
    weighted_percentile,
    write_jsonl,
)
from repro.telemetry.slo import bad_fraction, burn_events, evaluate_slo
from repro.telemetry.spans import (
    controller_spans,
    request_spans,
    spans_from_records,
)
from repro.traces.bandwidth import bandwidth_draw
from repro.traces.faults import scheduled_failure_trace

HSPEC = HistogramSpec(lo=0.5, hi=64.0, n_buckets=20)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(PaperSimConfig(), t_slots=96)
    template, build = make_sim_builder(cfg)
    root = jax.random.key(cfg.trace_seed)
    up, down = bandwidth_draw(jax.random.split(root, 6)[2], cfg.n_sites)
    return cfg, template, up, down


@pytest.fixture(scope="module")
def faulted_serve():
    """One faulted serving run with the histogram layer on, plus its twin
    without telemetry (for bitwise comparison)."""
    alive = np.ones((12, 4), np.float32)
    alive[6:, 2] = 0.0
    kw = dict(slots=12, v=1.0, seed=3, arrival=6.0, alive=alive)
    tcfg = TelemetryConfig(level=SUMMARY, hist=HSPEC)
    eng = build_engine(["qwen2-0.5b", "granite-3-2b"], telemetry=tcfg, **kw)
    bare = build_engine(["qwen2-0.5b", "granite-3-2b"], **kw)
    return eng.run(execute_real=False), bare.run(execute_real=False)


# ---------------------------------------------------------------------------
# The histogram spec and its decode
# ---------------------------------------------------------------------------

def test_histogram_spec_edges_and_bucket_index():
    edges = HSPEC.edges()
    assert edges.shape == (HSPEC.n_buckets + 1,)
    assert edges[0] == 0.0 and edges[1] == HSPEC.lo
    assert edges[-2] == HSPEC.hi and np.isinf(edges[-1])
    assert np.all(np.diff(edges[:-1]) > 0)
    idx = np.asarray(HSPEC.bucket_index(
        jnp.asarray([0.0, 0.49, 0.5, 1.0, 63.9, 64.0, 1e9])
    ))
    assert idx[0] == 0 and idx[1] == 0                  # underflow
    assert idx[2] == 1                                  # first interior
    assert idx[-2] == HSPEC.n_buckets - 1               # hi -> overflow
    assert idx[-1] == HSPEC.n_buckets - 1
    # Every interior value lands in the bucket its edges bound.
    vals = np.asarray([0.7, 2.3, 10.0, 33.3, 60.0])
    b = np.asarray(HSPEC.bucket_index(jnp.asarray(vals)))
    assert np.all(edges[b] <= vals) and np.all(vals < edges[b + 1])


def test_hist_quantiles_within_one_bucket_of_exact():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=1.0, sigma=1.0, size=5000)
    counts = np.asarray(hist_add(HSPEC, hist_init(HSPEC),
                                 jnp.asarray(samples)))
    qs = (50.0, 95.0, 99.0)
    est, err = hist_quantiles(counts, HSPEC, qs)
    exact = np.percentile(samples, qs)
    assert np.all(np.isfinite(est))
    assert np.all(np.abs(est - exact) <= err + 1e-9), (est, exact, err)


def test_hist_quantiles_overflow_and_empty():
    counts = np.asarray(hist_add(HSPEC, hist_init(HSPEC),
                                 jnp.asarray([1e6, 2e6, 3e6])))
    est, err = hist_quantiles(counts, HSPEC, (50.0,))
    assert est[0] == HSPEC.hi and np.isinf(err[0])      # lower bound, ±inf
    est0, err0 = hist_quantiles(np.zeros(HSPEC.n_buckets), HSPEC, (50.0,))
    assert np.isnan(est0[0]) and np.isnan(err0[0])


def test_hist_series_matches_per_row_hist_add():
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.lognormal(1.0, 1.5, size=(3, 40)))
    stacked = np.asarray(hist_series(HSPEC, vals, axis=-1))
    for i in range(3):
        row = np.asarray(hist_add(HSPEC, hist_init(HSPEC), vals[i]))
        np.testing.assert_allclose(stacked[i], row)


# ---------------------------------------------------------------------------
# The sojourn clock: device scan state vs exact host replay
# ---------------------------------------------------------------------------

def test_sojourn_step_matches_fifo_replay():
    rng = np.random.default_rng(2)
    t_slots, k = 24, 2
    admitted = rng.uniform(0.0, 8.0, size=(t_slots, k))
    # Completions lag arrivals: serve ~70% of current backlog per slot.
    completed = np.zeros_like(admitted)
    backlog = np.zeros(k)
    for t in range(t_slots):
        backlog += admitted[t]
        completed[t] = 0.7 * backlog
        backlog -= completed[t]
    age, hist = sojourn_init(HSPEC, k, t_slots)
    for t in range(t_slots):
        age, hist = sojourn_step(HSPEC, age, hist,
                                 jnp.asarray(admitted[t], jnp.float32),
                                 jnp.asarray(completed[t], jnp.float32))
    counts = np.asarray(hist)
    # Conservation: every completed unit landed in exactly one bucket.
    np.testing.assert_allclose(counts.sum(-1), completed.sum(0), rtol=1e-5)
    # Percentiles agree with the exact weighted replay within the bound.
    soj, wgt = fifo_sojourn_replay(admitted, completed)
    qs = (50.0, 95.0, 99.0)
    est, err = hist_quantiles(counts, HSPEC, qs)
    for ki in range(k):
        exact = weighted_percentile(soj[ki], wgt[ki], qs)
        assert np.all(np.abs(est[ki] - exact) <= err[ki] + 1e-6), (
            ki, est[ki], exact, err[ki]
        )


def test_fleet_sojourn_matches_exact_replay_faulted(faulted_serve):
    out, _ = faulted_serve
    spec = HistogramSpec(**out["sojourn_spec"])
    counts = out["sojourn_hist"]
    np.testing.assert_allclose(
        counts.sum(-1), out["completed"].sum(0), atol=1e-3
    )
    soj, wgt = fifo_sojourn_replay(out["admitted"], out["completed"])
    qs = (50.0, 95.0, 99.0)
    est, err = hist_quantiles(counts, spec, qs)
    for ki in range(counts.shape[0]):
        exact = weighted_percentile(soj[ki], wgt[ki], qs)
        assert np.all(np.abs(est[ki] - exact) <= err[ki] + 1e-6)
    # The decoded table carries the same numbers, named per class.
    tab = out["sojourn_percentiles"]
    assert [r["name"] for r in tab] == out["class_names"]
    np.testing.assert_allclose([r["p99"] for r in tab], est[:, 2])


# ---------------------------------------------------------------------------
# Enabled-then-disabled: OFF with a hist spec is still byte-identical
# ---------------------------------------------------------------------------

def test_off_with_hist_spec_jaxpr_identical_sim(setup):
    _, template, _, _ = setup
    pol, key = dispatch_fn(1.0), jax.random.key(0)
    # Trace once with the layer ON (enabled), then pin OFF == None.
    simulate(template, pol, key,
             telemetry=TelemetryConfig(level=SUMMARY, hist=HSPEC))
    j_none = jax.make_jaxpr(lambda i, k: simulate(i, pol, k))(template, key)
    j_off = jax.make_jaxpr(
        lambda i, k: simulate(i, pol, k,
                              telemetry=TelemetryConfig(level=OFF, hist=HSPEC))
    )(template, key)
    assert str(j_none) == str(j_off)


def test_off_with_hist_spec_jaxpr_identical_staged(setup):
    cfg, template, up, down = setup
    dag = single_stage_dag(cfg.k_types)
    wan = wan_topology(up, down)
    key = jax.random.key(0)
    simulate_staged(template, dag, wan, data_dispatch, key,
                    telemetry=TelemetryConfig(level=SUMMARY, hist=HSPEC))
    j_none = jax.make_jaxpr(
        lambda i, k: simulate_staged(i, dag, wan, data_dispatch, k)
    )(template, key)
    j_off = jax.make_jaxpr(
        lambda i, k: simulate_staged(
            i, dag, wan, data_dispatch, k,
            telemetry=TelemetryConfig(level=OFF, hist=HSPEC))
    )(template, key)
    assert str(j_none) == str(j_off)


def test_off_with_hist_spec_jaxpr_identical_placed(setup):
    cfg, template, up, down = setup
    mask = scheduled_failure_trace(cfg.t_slots, cfg.n_sites, [(1, 30, None)])
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    pol, rule = dispatch_fn(1.0), make_adaptive_rule(up)
    key = jax.random.key(3)
    j_none = jax.make_jaxpr(
        lambda i, k: simulate_placed(i, up, down, pol, rule, k, pcfg,
                                     alive=mask)
    )(template, key)
    j_off = jax.make_jaxpr(
        lambda i, k: simulate_placed(
            i, up, down, pol, rule, k, pcfg, alive=mask,
            telemetry=TelemetryConfig(level=OFF, hist=HSPEC))
    )(template, key)
    assert str(j_none) == str(j_off)


def _fleet_step_jaxpr(eng) -> str:
    scn, inputs = eng.scenario, eng.scenario.inputs
    e_cost_all, _ = _energy_tables(inputs)
    mu_stage_all = stage_service_rates_all(inputs.mu, scn.dag)
    wpue = inputs.omega * inputs.pue
    q = jnp.zeros((eng.fcfg.n_pods, len(eng.classes), scn.dag.s_max),
                  jnp.float32)
    args = (q, inputs.arrivals[0], inputs.mu[0], e_cost_all[0],
            mu_stage_all[0], inputs.data_dist, wpue[0],
            jnp.float32(eng.fcfg.v))
    return str(jax.make_jaxpr(eng._step)(*args))


def test_fleet_step_jaxpr_identical_off_with_hist():
    kw = dict(slots=8, v=1.0, seed=3, arrival=4.0)
    none = build_engine(["qwen2-0.5b"], **kw)
    off = build_engine(["qwen2-0.5b"], **kw,
                       telemetry=TelemetryConfig(level=OFF, hist=HSPEC))
    assert _fleet_step_jaxpr(none) == _fleet_step_jaxpr(off)


def test_fleet_outputs_bitwise_with_hist_on(faulted_serve):
    out, bare = faulted_serve
    np.testing.assert_array_equal(out["cost"], bare["cost"])
    np.testing.assert_array_equal(out["backlog"], bare["backlog"])
    np.testing.assert_array_equal(np.asarray(out["dispatch"]),
                                  np.asarray(bare["dispatch"]))
    assert out["total_billed_cost"] == bare["total_billed_cost"]


def test_trace_level_with_hist_outputs_bitwise(setup):
    _, template, _, _ = setup
    pol, key = dispatch_fn(1.0), jax.random.key(7)
    o0 = simulate(template, pol, key)
    o1, frame = simulate(template, pol, key,
                         telemetry=TelemetryConfig(level=TRACE, hist=HSPEC))
    for f in o0._fields:
        np.testing.assert_array_equal(np.asarray(getattr(o0, f)),
                                      np.asarray(getattr(o1, f)), err_msg=f)
    assert "site_cost_hist" in frame.metrics


# ---------------------------------------------------------------------------
# SLO monitor: bad fraction, burn-rate alerts, conservative verdicts
# ---------------------------------------------------------------------------

def test_bad_fraction_hand_example():
    admitted = np.asarray([[4.0], [0.0], [0.0]])
    completed = np.asarray([[1.0], [1.0], [2.0]])
    frac = bad_fraction(admitted, completed, target=1.0)
    # t=0: sojourn 0; t=1: sojourn 1 (not > 1); t=2: sojourn 2 (> 1).
    np.testing.assert_allclose(frac[:, 0], [0.0, 0.0, 1.0])


def test_burn_events_fire_on_overload_only():
    t_slots = 20
    slo = SloSpec(target=1.0, percentile=95.0, windows=((3, 8, 1.0),))
    # Underloaded: everything completes the slot it arrives.
    adm = np.full((t_slots, 1), 4.0)
    assert burn_events(adm, adm.copy(), slo) == []
    # Overloaded: a big backlog drains slowly — late mass is all bad.
    admitted = np.zeros((t_slots, 1))
    admitted[0, 0] = 40.0
    completed = np.full((t_slots, 1), 2.0)
    evs = burn_events(admitted, completed, slo)
    assert evs and all(e["code"] == "slo_burn" for e in evs)
    # Rising-edge dedup: the alert opens once, not every slot.
    assert len(evs) == 1
    assert evs[0]["burn_short"] > 1.0 and evs[0]["burn_long"] > 1.0


def test_evaluate_slo_conservative_on_overflow():
    counts = np.asarray(hist_add(HSPEC, hist_init(HSPEC),
                                 jnp.asarray([1e6] * 10)))
    slo = SloSpec(target=1e9, percentile=99.0)
    (v,) = evaluate_slo(counts, HSPEC, slo)
    assert not v["ok"]                      # ±inf can never certify a pass
    fast = np.asarray(hist_add(HSPEC, hist_init(HSPEC),
                               jnp.asarray([1.0] * 100)))
    (v2,) = evaluate_slo(fast, HSPEC, SloSpec(target=8.0, percentile=99.0))
    assert v2["ok"]


# ---------------------------------------------------------------------------
# Spans and the Chrome trace export
# ---------------------------------------------------------------------------

def test_request_spans_phases_and_unserved():
    out = {
        "admitted": np.asarray([[2.0], [1.0]]),
        "completed": np.asarray([[1.0], [1.0]]),
    }
    spans = request_spans(out, class_names=["c0"])
    names = [s["name"] for s in spans]
    cats = {s["cat"] for s in spans}
    assert "unserved" in cats               # 1 unit still queued at horizon
    for phase in ("admit", "prefill", "kv_shuffle", "decode", "served"):
        assert phase in names
    parents = [s for s in spans if s["cat"] in ("request", "unserved")]
    assert len(parents) == 2 and all(s["track"] == "c0" for s in parents)


def test_controller_spans_from_synthetic_stream():
    records = [
        {"type": "meta", "kind": "placed", "t_slots": 48},
        {"type": "event", "t": 23, "code": "epoch", "epoch": 0,
         "wan_gb": 1.5, "wan_cost": 0.2, "sync_cost": 0.1,
         "churn": 0.3, "budget_use": 0.8},
        {"type": "event", "t": 30, "code": "recovery", "site": 1,
         "n_died": 1, "recovery_gb": 4.0, "time_to_slo": 5,
         "slo_backlog": 3.0},
        {"type": "event", "t": 40, "code": "recovery", "site": 2,
         "n_died": 1, "time_to_slo": None, "slo_backlog": 3.0},
        {"type": "event", "t": 31, "code": "switch", "k": 0,
         "src": 1, "dst": 2},
    ]
    spans = controller_spans(records)
    by_name = {s["name"]: s for s in spans}
    ep = by_name["epoch 0"]
    assert ep["t0"] == 0 and ep["t1"] == 24
    rec = by_name["recovery→SLO"]
    assert rec["t0"] == 30 and rec["t1"] == 35
    unrec = by_name["unrecovered"]
    assert unrec["t1"] == 48                # horizon-capped
    assert "death edge @1" in by_name and "switch k0→2" in by_name


def test_chrome_trace_valid_for_faulted_serve(faulted_serve, tmp_path):
    out, _ = faulted_serve
    records = fleet_records(
        out, meta={"slo_backlog": 50.0},
        slo=SloSpec(target=4.0, percentile=99.0),
    )
    spans = spans_from_records(records)
    trace = to_chrome_trace(spans, slot_ms=2.0)
    # Valid trace-event JSON: serializable, every event well-formed.
    blob = json.dumps(trace)
    parsed = json.loads(blob)
    assert parsed["displayTimeUnit"] == "ms"
    phs = set()
    for ev in parsed["traceEvents"]:
        assert {"ph", "pid", "tid", "name"} <= set(ev)
        phs.add(ev["ph"])
        if ev["ph"] == "X":
            assert ev["dur"] > 0 and ev["ts"] >= 0
    assert {"X", "i", "M"} <= phs
    # The fault is visible: a death-edge instant on its own track.
    names = [ev["name"] for ev in parsed["traceEvents"]]
    assert any("death edge" in n or "died" in n for n in names)
    # Request lifecycles made it in from the metric rows alone.
    assert any(n.startswith("req ") for n in names)


def test_fleet_records_round_trip_with_hist_and_slo(faulted_serve, tmp_path):
    out, _ = faulted_serve
    records = fleet_records(out, meta={"slo_backlog": 50.0},
                            slo=SloSpec(target=8.0, percentile=99.0))
    kinds = {r["type"] for r in records}
    assert {"meta", "event", "metric", "hist", "slo", "summary"} <= kinds
    path = write_jsonl(records, tmp_path / "serve.jsonl")
    assert read_jsonl(path) == json.loads(json.dumps(records))
    text = render_timeline(records, codes={"recovery"})
    assert "death edge" in text
    hist = next(r for r in records if r["type"] == "hist")
    assert hist["name"] == "sojourn" and len(hist["percentiles"]) == 2


# ---------------------------------------------------------------------------
# sparkline: empty-bin carry + constant-series pin
# ---------------------------------------------------------------------------

def test_sparkline_constant_series_pins_lowest_block():
    assert sparkline([5.0] * 100, width=60) == "▁" * 60
    assert sparkline([0.0] * 10) == "▁" * 10
    assert sparkline([]) == ""


def test_sparkline_monotone_series_never_spikes():
    s = sparkline(np.linspace(0.0, 1.0, 97), width=60)
    assert len(s) == 60
    blocks = " ▁▂▃▄▅▆▇█"
    levels = [blocks.index(c) for c in s]
    assert levels == sorted(levels)         # nondecreasing, no invented spike
