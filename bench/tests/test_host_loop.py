"""The harness's host loop: it holds only the digests it will compare, picks
them evenly and from the seed alone, and names on standard error the
collector's runs and the calls that stood still.

One tiny CPU run (``run.run_cell(..., require_chip=False)``) of the first
one-chip cell serves the tests that need a window: its call is wrapped to
keep a weak reference to every digest it returns, to run a full collection
in the window's second call and to sleep in its third.
"""

import dataclasses
import gc
import re
import time
import weakref

import numpy as np
import pytest
from conftest import cells, tiny_spec

COLLECT_AT, SLEEP_AT, SLEEP_S = 2, 3, 0.5


@pytest.fixture(scope="module")
def window_run():
    import jax

    import cell as cell_mod
    import run

    build = cell_mod.build_cell
    digests = []                                  # weak references, one list per call
    lines = []
    alive_at_close = []

    def built(*args, **kwargs):
        c = build(*args, **kwargs)

        def call(base, index, rows):
            if int(index) == COLLECT_AT:
                gc.collect()
            if int(index) == SLEEP_AT:
                time.sleep(SLEEP_S)
            ans, dig = c.call(base, index, rows)
            digests.append([weakref.ref(x) for x in jax.tree_util.tree_leaves(dig)])
            return ans, dig

        return dataclasses.replace(c, call=call)

    def log(line):
        lines.append(line)
        if line.startswith("window "):
            alive_at_close.append(sum(any(r() is not None for r in d) for d in digests))

    cell_mod.build_cell = built
    try:
        out = run.run_cell(tiny_spec(cells(chips=1)[0]), 3_000_000_019, 1.5, False,
                           require_chip=False, log=log)
    finally:
        cell_mod.build_cell = build
    return {"out": out, "lines": lines, "alive": alive_at_close[0],
            "calls": len(digests) - 1}            # the warm-up call is not the window's


def test_only_the_compared_digests_are_alive_when_the_window_closes(window_run):
    import run

    assert window_run["out"]["correct"], window_run["out"]["checks"]
    assert window_run["calls"] == window_run["out"]["attempted"] > 10 * run.CHECK_CALLS
    assert window_run["alive"] == run.CHECK_CALLS


def test_a_slow_call_is_named_with_its_clock_deltas(window_run):
    named = [ln for ln in window_run["lines"] if ln.startswith(f"slow call {SLEEP_AT}:")]
    assert len(named) == 1, window_run["lines"]
    m = re.search(r": ([\d.]+) ms; process_time \+([\d.]+) ms, involuntary switches "
                  r"\+(\d+), voluntary switches \+(\d+), major faults \+(\d+)$", named[0])
    assert m, named[0]
    wall, cpu, _, voluntary, _ = (float(g) for g in m.groups())
    assert wall >= SLEEP_S * 1e3
    assert cpu < 0.25 * wall                      # asleep: the process did not work
    assert voluntary >= 1                         # and it gave up the core to sleep


def test_the_collectors_runs_in_the_window_are_counted(window_run):
    (line,) = [ln for ln in window_run["lines"] if ln.startswith("collector in the window")]
    full = int(re.search(r"generation 2 (\d+)", line).group(1))
    total, longest = map(float, re.search(r"([\d.]+) ms in all, longest ([\d.]+) ms",
                                          line).groups())
    assert full >= 1
    assert 0 < longest <= total


def _pick(seed, n, k):
    import run

    pick = run.Reservoir(k, np.random.default_rng(seed))
    for i in range(n):
        pick.offer(i)
    return sorted(pick.held)


@pytest.mark.parametrize("n,k", [(1, 1), (2, 1), (10, 1), (10, 3)])
def test_the_reservoir_pick_is_fixed_by_the_seed_and_spread_evenly(n, k):
    seeds = range(3_000_000_000, 3_000_000_000 + 20_000)
    picks = [_pick(s, n, k) for s in seeds]
    assert picks[:50] == [_pick(s, n, k) for s in seeds[:50]]
    assert all(len(p) == min(n, k) and len(set(p)) == len(p) for p in picks)
    counts = np.bincount(np.concatenate(picks), minlength=n)
    expected = len(seeds) * min(n, k) / n
    chi2 = float(((counts - expected) ** 2 / expected).sum())
    assert chi2 < 3 * n + 10, counts              # far in the tail of n - 1 degrees of freedom
