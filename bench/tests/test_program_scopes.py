"""The readers of the program's own layer names (``bench/program_scopes.py``),
on a trace of each cell that runs the controller or the kernel, recorded
on a TPU v5e by the harness with the names in the program
(``bench/tests/data/scoped``), and on the traces of a program without them
(``bench/tests/data``)."""

from pathlib import Path

import pytest

import program_scopes
from trace_reduce import Op, Trace, _set_nesting, reduce_trace

DATA = Path(__file__).resolve().parent / "data"
SCOPED = sorted((DATA / "scoped").glob("*.trace.json.gz"))
UNNAMED = sorted(DATA.glob("*.trace.json.gz"))
NAMED = ["draws_ms", "scan_ms", "decide_ms", "gmsa_score_ms", "epochs_ms", "rule_ms",
         "recovery_ms"]
CALLS = 2                                   # each trace holds two calls


def read(name, tr):
    import plugins

    return plugins.load("metrics", name).read(tr, {"calls": CALLS})


def ids(p):
    return p.name.split(".")[0]


def test_fixtures_are_present():
    assert {ids(p) for p in SCOPED} == {"paper_kernel_mc", "paper_placed_fault"}


@pytest.mark.parametrize("path", SCOPED, ids=ids)
def test_the_names_and_the_rest_add_up_to_the_device_time(path):
    tr = reduce_trace(path)
    total = sum(read(m, tr) or 0.0 for m in NAMED) + read("unscoped_ms", tr)
    self_ms = sum(op.self_us for _, op in tr.ops) * 1e-3 / CALLS
    assert total == pytest.approx(self_ms, rel=1e-9)
    assert total == pytest.approx(tr.busy_s[0] * 1e3 / CALLS, rel=5e-3)
    assert read("unscoped_ms", tr) < 0.05 * total


@pytest.mark.parametrize("path", SCOPED, ids=ids)
def test_the_draws_are_what_the_harness_calls_trace_generation(path):
    tr = reduce_trace(path)
    assert read("draws_ms", tr) == pytest.approx(read("tracegen_ms", tr), rel=1e-9)


@pytest.mark.parametrize("path", SCOPED, ids=ids)
def test_decisions_and_kernel_are_what_the_harness_calls_dispatch(path):
    tr = reduce_trace(path)
    kernel = read("gmsa_score_ms", tr) or 0.0
    assert (read("decide_ms", tr) + kernel
            == pytest.approx(read("dispatch_ms", tr), rel=1e-9))
    assert (kernel > 0) == (ids(path) == "paper_kernel_mc")


def test_the_kernel_op_carries_its_name_in_name_and_scope():
    tr = reduce_trace(DATA / "scoped" / "paper_kernel_mc.trace.json.gz")
    kernels = [op for _, op in tr.ops if op.category == "custom-call" and op.self_us > 0
               and "pallas_call" in op.scope]
    assert kernels
    for op in kernels:
        assert op.name.split(".")[0] == "gmsa_score"
        assert "/gmsa_score/pallas_call" in op.scope
        assert program_scopes.innermost(op.scope) == program_scopes.KERNEL


def test_the_rule_is_what_the_harness_calls_the_placement_rule():
    tr = reduce_trace(DATA / "scoped" / "paper_placed_fault.trace.json.gz")
    assert read("rule_ms", tr) == pytest.approx(read("placement_rule_ms", tr), rel=1e-9)
    for name in ("epochs_ms", "recovery_ms", "scan_ms"):
        assert read(name, tr) > 0


@pytest.mark.parametrize("path", UNNAMED, ids=ids)
def test_a_program_without_names_reads_nothing(path):
    tr = reduce_trace(path)
    for name in NAMED + ["unscoped_ms"]:
        assert read(name, tr) is None


def test_an_op_counts_in_its_innermost_name_only():
    stack = "jit(call)/jit(simulate_placed_many)/vmap(jit(simulate_placed))/placed_epochs/"
    body = stack + "while/body/gmsa_scan/while/body/closed_call/"
    ops = [Op(0.0, 10.0, "while.1", "", "while"),
           Op(1.0, 3.0, "fusion.1", body + "gmsa_decide/bench_dispatch/add:", "loop fusion"),
           Op(5.0, 2.0, "fusion.2", body + "add:", "loop fusion"),
           Op(20.0, 1.0, "copy.1", "", "data formatting")]
    _set_nesting(ops)
    tr = Trace(platform="tpu", n_devices=1, window_s=1.0, busy_s=[14e-6],
               ops=[(0, op) for op in ops], gaps=[])
    got = {name: read(name, tr) for name in NAMED + ["unscoped_ms"]}
    assert got == {"draws_ms": None, "scan_ms": pytest.approx((5 + 2) * 1e-3 / CALLS),
                   "decide_ms": pytest.approx(3e-3 / CALLS), "gmsa_score_ms": None,
                   "epochs_ms": None, "rule_ms": None, "recovery_ms": None,
                   "unscoped_ms": pytest.approx(1e-3 / CALLS)}
