"""The comparison catches a broken timed path.

Each test drives a whole run of a cell (the harness's look for a chip
skipped, a tiny size on the CPU) with the program broken underneath, and
sees ``correct`` come out false: once for each fault the cells can have.
"""

import pytest
from conftest import cells, tiny_spec
from test_rehearsal import FOUR_CHIP, _four_cpu

ONE_CHIP = cells(chips=1)


def _run(name):
    import run

    return run.run_cell(tiny_spec(name), 3_000_000_013, 0.3, False,
                        require_chip=False, log=lambda m: None)


def _entry(name):
    """The cell's entry module: where its engine and slot step live."""
    import plugins

    return plugins.load("entries", tiny_spec(name)["traffic"]["entry"])


def _step_module(name):
    """The module whose ``slot_step`` the cell's engine calls."""
    import importlib

    return importlib.import_module(_entry(name).ENGINE)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_step_that_returns_its_state_unchanged_is_caught(name, monkeypatch):
    mod = _step_module(name)
    step = mod.slot_step

    def frozen(q, *args):
        _, out = step(q, *args)
        return q, out

    monkeypatch.setattr(mod, "slot_step", frozen)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_half_of_the_batch_left_out_is_caught(name, monkeypatch):
    entry = _entry(name)
    mod, at = _step_module(name), entry.N_RUNS_ARG
    whole = getattr(mod, entry.ENTRY_FN)

    def half(*args, **kwargs):
        args = list(args)
        args[at] //= 2                                # n_runs
        return whole(*args, **kwargs)

    monkeypatch.setattr(mod, entry.ENTRY_FN, half)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_decision_altered_where_it_is_produced_is_caught(name, monkeypatch):
    import jax.numpy as jnp

    import repro.core.gmsa as gmsa

    decide = gmsa.gmsa_dispatch

    def altered(q, *args, **kwargs):
        f = decide(q, *args, **kwargs)                # (N, K) one-hot
        return f.at[:, -1].set(jnp.roll(f[:, -1], 1))  # last type: next site

    monkeypatch.setattr(gmsa, "gmsa_dispatch", altered)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_bill_altered_where_it_is_produced_is_caught(name, monkeypatch):
    mod = _step_module(name)
    step = mod.slot_step

    def altered(*args):
        q_next, (cost, energy, *rest) = step(*args)
        return q_next, (cost, energy * (1.0 + 1e-5), *rest)

    monkeypatch.setattr(mod, "slot_step", altered)
    assert not _run(name)["correct"]


FOUR_CPU_FAULTS = """
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax
import repro.distributed.mesh as mesh
import repro.placement.controller as ctl
from conftest import traffic_spec
import run

def one_shard(one, keys, m):
    # The exchange between chips left out: every chip returns the first
    # chip's rows, as if the gather had only seen shard 0.
    n = keys.shape[0] // m.shape["runs"]
    outs = jax.vmap(one)(keys[:n])
    return jax.tree_util.tree_map(lambda x: jax.numpy.concatenate([x] * m.shape["runs"]), outs)

def frozen(q, f, arrivals, mu, e_cost, e_raw):
    q_next, out = step(q, f, arrivals, mu, e_cost, e_raw)
    return q, out

step = ctl.slot_step
res = {{}}
for fault in ("exchange", "state"):
    if fault == "exchange":
        mesh.sharded_runs = one_shard
        ctl.slot_step = step
    else:
        mesh.sharded_runs = real
        ctl.slot_step = frozen
    out = run.run_cell(traffic_spec({name!r}, 4), 3_000_000_017, 0.3, False,
                       require_chip=False, log=lambda m: None)
    res[fault] = out["correct"]
print(json.dumps(res))
""".replace("step = ctl.slot_step", "step = ctl.slot_step\nreal = mesh.sharded_runs")


@pytest.mark.parametrize("name", FOUR_CHIP)
def test_four_chip_faults_are_caught(name):
    res = _four_cpu(name, FOUR_CPU_FAULTS)
    assert res == {"exchange": False, "state": False}
