"""The engines name their layers in the compiled program, and naming them
changes nothing else.

Each engine opens a ``jax.named_scope`` (:mod:`repro.telemetry.scopes`) at
its own call site of a layer: the per-run draws, the slot loop, the
dispatch decision, and in the placement controller the epoch loop, the
placement rule and the recovery epoch. XLA keeps the names in each op's
``op_name``, which a device profile shows, so a profile of any caller can
attribute device time to the layers. These tests read the names and their
nesting from the compiled modules on the CPU, and check that a compiled
module stripped of its metadata, and its outputs, are the same with the
names as without them. The kernels' own names are checked where they are
compiled for a TPU (``tests/test_tpu_compile.py``).
"""

import contextlib
import dataclasses
import functools
import re

import jax
import numpy as np
import pytest

from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
from repro.core.baselines import data_dispatch
from repro.core.gmsa import gmsa_policy, make_kernel_policy
from repro.core.simulator import simulate_many
from repro.placement import PlacementConfig, make_adaptive_rule
from repro.placement.controller import simulate_placed_many
from repro.telemetry import scopes
from repro.traces.bandwidth import bandwidth_draw
from repro.traces.faults import scheduled_failure_trace

RUNS = 4
NAMES = {scopes.MC_DRAWS, scopes.GMSA_SCAN, scopes.GMSA_DECIDE,
         scopes.PLACED_EPOCHS, scopes.PLACED_RULE, scopes.PLACED_RECOVERY}


@pytest.fixture(scope="module")
def paper():
    cfg = dataclasses.replace(PaperSimConfig(), t_slots=48)
    template, build = make_sim_builder(cfg)
    up, down = bandwidth_draw(jax.random.split(jax.random.key(cfg.trace_seed), 6)[2],
                              cfg.n_sites)
    alive = scheduled_failure_trace(cfg.t_slots, cfg.n_sites, [(2, 30, None)])
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share,
                           map_share=cfg.map_share)
    return cfg, template, build, up, down, alive, pcfg


def fresh(policy):
    """A policy that is a new static argument, so the engine traces anew."""

    @functools.wraps(policy)
    def wrapped(*args):
        return policy(*args)

    return wrapped


def many(paper, policy):
    _, _, build, _, _, _, _ = paper
    return lambda key: simulate_many(build, policy, key, RUNS, 1.0)


def placed(paper, policy):
    _, _, build, up, down, alive, pcfg = paper
    rule = make_adaptive_rule(up)
    return lambda key: simulate_placed_many(build, up, down, policy, rule, key,
                                            RUNS, pcfg, 1.0, alive=alive)


def _name(component):
    while (m := re.fullmatch(r"(\w+)\((.*)\)", component)) is not None:
        if m.group(1) == "jit":
            return None
        component = m.group(2)
    return component


def stacks(compiled_text):
    """For each op of a compiled module, the program names in its
    ``op_name``, outermost first. Ops of the small computations an op
    applies (a reduction's adder) carry a stack relative to it, and are
    left out."""
    out = set()
    for op_name in re.findall(r'op_name="(jit\([^"]*)"', compiled_text):
        names = [_name(c) for c in op_name.split("/")]
        out.add(tuple(n for n in names if n in NAMES))
    return out


def compiled_text(fn):
    return jax.jit(fn).lower(jax.random.key(7)).compile().as_text()


def stripped(text):
    """A compiled module without its debug information: the source-location
    tables of its header, each op's ``metadata``, the module's name (its
    function's) and the names of its ops and computations, which the
    lowering derives from the name stacks (each is renamed by its first
    appearance)."""
    text = re.sub(r"^HloModule [^,]*", "HloModule", text)
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
                  "", text, flags=re.M)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m[0], f"%{len(names)}"), text)


@pytest.mark.parametrize("make", [lambda t: gmsa_policy,
                                  lambda t: make_kernel_policy(t.r, t.p_it)],
                         ids=["table", "kernel"])
def test_simulate_many_names_draws_scan_and_decisions(paper, make):
    got = stacks(compiled_text(many(paper, fresh(make(paper[1])))))
    assert (scopes.MC_DRAWS,) in got
    assert (scopes.GMSA_SCAN,) in got
    assert (scopes.GMSA_SCAN, scopes.GMSA_DECIDE) in got
    # Every decision is made inside the slot loop; the draws are outside it.
    assert all(s[:1] == (scopes.GMSA_SCAN,) for s in got if scopes.GMSA_DECIDE in s)
    assert all(s == (scopes.MC_DRAWS,) for s in got if scopes.MC_DRAWS in s)


def test_hoisted_decisions_are_named_outside_the_slot_loop(paper):
    got = stacks(compiled_text(many(paper, fresh(data_dispatch))))
    assert (scopes.GMSA_DECIDE,) in got
    assert not any(scopes.GMSA_DECIDE in s and scopes.GMSA_SCAN in s for s in got)


def test_simulate_placed_many_nests_rule_recovery_and_scan_in_the_epochs(paper):
    got = stacks(compiled_text(placed(paper, fresh(gmsa_policy))))
    e, s, d = scopes.PLACED_EPOCHS, scopes.GMSA_SCAN, scopes.GMSA_DECIDE
    rule, rec = scopes.PLACED_RULE, scopes.PLACED_RECOVERY
    assert (scopes.MC_DRAWS,) in got
    assert {(e,), (e, rule), (e, s), (e, s, d), (e, s, rec), (e, s, rec, rule)} <= got
    inner = {s, d, rule, rec}
    assert all(st[0] == e for st in got if inner & set(st))


def test_an_outer_scope_changes_no_program(paper):
    run = many(paper, fresh(gmsa_policy))

    def scoped(key):
        with jax.named_scope("outer"):
            return run(key)

    plain, named = compiled_text(run), compiled_text(scoped)
    assert "outer/" in named and "outer/" not in plain
    assert stripped(named) == stripped(plain)
    a, b = jax.jit(run)(jax.random.key(7)), jax.jit(scoped)(jax.random.key(7))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@contextlib.contextmanager
def no_scope(name):
    yield


@pytest.mark.parametrize("engine", [many, placed], ids=["simulate_many",
                                                        "simulate_placed_many"])
def test_the_engines_names_change_no_program(paper, engine, monkeypatch):
    named_fn = engine(paper, fresh(gmsa_policy))
    named = compiled_text(named_fn)
    out_named = jax.jit(named_fn)(jax.random.key(7))
    monkeypatch.setattr(jax, "named_scope", no_scope)
    bare_fn = engine(paper, fresh(gmsa_policy))
    bare = compiled_text(bare_fn)
    out_bare = jax.jit(bare_fn)(jax.random.key(7))
    assert stacks(named) - {()} and not stacks(bare) - {()}
    assert stripped(named) == stripped(bare)
    for x, y in zip(jax.tree.leaves(out_named), jax.tree.leaves(out_bare)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
