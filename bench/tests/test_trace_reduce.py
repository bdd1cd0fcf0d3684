"""The reduction from a device trace to per-layer metrics, on small traces
recorded on a TPU v5e by the harness (``bench/tests/data``)."""

import gzip
import importlib.util
import json
from pathlib import Path

import pytest
from conftest import BENCH

from trace_reduce import Op, _set_nesting, layer_of, reduce_trace

DATA = Path(__file__).resolve().parent / "data"
TRACES = sorted(DATA.glob("*.trace.json.gz"))
LAYERS = {"trace generation", "dispatch", "placement rule", "scan engines",
          "outside the scans"}


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_ctx(path, calls):
    fleet = path.name.startswith("fleet")
    return {"calls": calls, "n_runs": 100 if fleet else 1000, "chips": 1,
            "t_slots": 288, "n_sites": 256 if fleet else 4, "k_types": 8 if fleet else 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_fixtures_are_present():
    assert TRACES, f"no recorded traces under {DATA}"


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_a_recorded_trace_reduces_to_busy_time_inside_its_window(path):
    tr = reduce_trace(path)
    assert tr.platform == "tpu" and tr.n_devices == 1
    assert tr.window_s > 0
    assert 0 < tr.busy_s[0] <= tr.window_s * 1.001


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_self_times_add_up_to_the_busy_time(path):
    tr = reduce_trace(path)
    total = sum(op.self_us for _, op in tr.ops) * 1e-6
    assert total == pytest.approx(tr.busy_s[0], rel=1e-3)


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_every_op_lands_in_a_known_layer_and_trace_generation_leads(path):
    tr = reduce_trace(path)
    layers = {layer_of(op.scope) for _, op in tr.ops}
    assert layers <= LAYERS
    gen = tr.self_seconds(lambda s: layer_of(s) == "trace generation")
    assert gen > 0.5 * tr.busy_s[0]


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_the_trace_counts_the_calls_it_covers(path):
    assert reduce_trace(path).calls == 2          # each trace holds two calls


def _cut(path, out):
    """A copy of ``path`` whose device events stop halfway through the
    second call, as the trace's cap of events cuts a window of many calls."""
    doc = json.load(gzip.open(path, "rt"))
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in doc["traceEvents"]
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    device = [e for e in doc["traceEvents"] if e.get("ph") == "X" and threads.get(
        (e["pid"], e["tid"])) in ("XLA Ops", "XLA Modules")]
    cut = sorted(e["ts"] + 0.5 * e["dur"] for e in device
                 if threads[(e["pid"], e["tid"])] == "XLA Modules")[1]
    dropped = {id(e) for e in device if e["ts"] > cut}
    doc["traceEvents"] = [e for e in doc["traceEvents"] if id(e) not in dropped]
    with gzip.open(out, "wt") as fh:
        json.dump(doc, fh)
    return out


def test_a_cut_trace_reads_per_call_over_the_calls_it_covers(tmp_path):
    path = DATA / "paper_mc.trace.json.gz"
    whole = reduce_trace(path)
    cut = reduce_trace(_cut(path, tmp_path / "cut.trace.json.gz"))
    assert cut.calls == 1
    assert cut.window_s < 0.6 * whole.window_s    # ends with the first call
    assert cut.busy_s[0] < 0.6 * whole.busy_s[0]
    assert 0 < reader("device_idle_pct").read(cut, cell_ctx(path, calls=1)) < 100
    for name in ("tracegen_ms", "scan_body_ms", "dispatch_ms"):
        per_call = reader(name).read(cut, cell_ctx(path, calls=cut.calls))
        assert per_call == pytest.approx(
            reader(name).read(whole, cell_ctx(path, calls=whole.calls)), rel=0.05)


def test_the_kernel_is_counted_in_the_dispatch_layer():
    path = DATA / "fleet256_kernel_mc.trace.json.gz"
    tr = reduce_trace(path)
    kernels = [op for _, op in tr.ops if op.name.startswith("gmsa_score")]
    assert kernels and all(layer_of(op.scope) == "dispatch" for op in kernels)


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_readers_read_what_is_there_and_nothing_else(path):
    tr = reduce_trace(path)
    ctx = cell_ctx(path, calls=2)
    assert reader("placement_rule_ms").read(tr, ctx) is None
    idle = reader("device_idle_pct").read(tr, ctx)
    assert 0 <= idle < 100
    for name in ("tracegen_ms", "scan_body_ms", "dispatch_ms"):
        assert reader(name).read(tr, ctx) > 0
    share = reader("dispatch_roofline").read(tr, ctx)
    assert 0 < share <= 100


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.name)
def test_breakdown_names_at_most_ten_ops_and_gaps(path):
    b = reduce_trace(path).breakdown(layer_of)
    for key in ("device_ops", "idle_gaps"):
        assert len(b[key]) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in b[key])


def test_the_least_dispatch_time_is_bound_by_bytes():
    mod = reader("dispatch_roofline")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    secs, bound = mod.least_seconds(100, 288, 256, 8, peaks)
    assert bound == "bytes"
    per_slot = 100 * 4 * (2 * 2048 + 8 + 8) + 4 * (2048 + 8)
    assert secs == pytest.approx(288 * per_slot / 819e9)
    _, ops = mod.least_work(100, 288, 256, 8)
    assert ops / 197e12 < secs / 100


def test_a_loop_counts_only_its_own_time_and_takes_its_body_scope():
    body = "jit(f)/while/body/"
    ops = [Op(0.0, 10.0, "while.1", "", "while"),
           Op(1.0, 3.0, "fusion.1", body + "bench_dispatch/add:", "loop fusion"),
           Op(5.0, 4.0, "fusion.2", body + "add:", "loop fusion"),
           Op(20.0, 2.0, "copy.1", "jit(f)/copy:", "copy")]
    _set_nesting(ops)
    self_us = {op.name: op.self_us for op in ops}
    assert self_us == {"while.1": 3.0, "fusion.1": 3.0, "fusion.2": 4.0, "copy.1": 2.0}
    assert ops[0].scope == body
    assert layer_of(ops[0].scope) == "scan engines"
