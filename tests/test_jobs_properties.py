"""Hypothesis properties of the staged-jobs engine (slow / nightly suite).

Pinned invariants, over random dags, traces and WAN topologies:

* stage-flow conservation — every arrival either completes its last stage
  or sits in some stage queue at the horizon;
* shuffle-volume billing — the engine's per-slot WAN bill equals
  re-deriving ``transfer_cost(transfer_plan(...))`` over the realized
  stage flows (the placement layer's semantics, to the byte);
* single-stage degeneration — a trivial one-stage dag is bit-exact with
  ``repro.core.simulator.simulate``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.gmsa import dispatch_fn
from repro.core.simulator import SimInputs, simulate
from repro.jobs import (
    flow_step,
    make_staged_policy,
    pad_chains,
    simulate_staged,
    single_stage_dag,
    stage_service_rates,
)
from repro.placement.wan import transfer_cost, transfer_plan, wan_topology


def _random_case(seed, n, k, s, t):
    """A small random staged scenario (deterministic in seed)."""
    rng = np.random.default_rng(seed)
    arrivals = jnp.asarray(rng.integers(0, 20, (t, k)), jnp.float32)
    mu = jnp.asarray(rng.uniform(1.0, 30.0, (t, n, k)), jnp.float32)
    omega = jnp.asarray(rng.uniform(10.0, 60.0, (t, n)), jnp.float32)
    pue = jnp.asarray(rng.uniform(1.0, 1.3, (t, n)), jnp.float32)
    dd = jnp.asarray(rng.dirichlet(np.ones(n), k), jnp.float32)
    r = jnp.asarray(rng.dirichlet(np.ones(n), (k, n)), jnp.float32)
    p_it = jnp.asarray(rng.uniform(0.5, 2.0, (k,)), jnp.float32)
    inputs = SimInputs(arrivals, mu, omega, pue, r, p_it, dd)
    depths = rng.integers(1, s + 1, k)
    computes = [list(rng.uniform(0.2, 1.0, d)) for d in depths]
    shuffles = [[0.0] + list(rng.uniform(0.0, 40.0, d - 1)) for d in depths]
    dag = pad_chains(computes, shuffles)
    up = jnp.asarray(rng.uniform(0.2, 2.0, (n,)), jnp.float32)
    down = jnp.asarray(rng.uniform(0.2, 2.0, (n,)), jnp.float32)
    return inputs, dag, wan_topology(up, down, energy_per_gb=0.03)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 5),
       k=st.integers(1, 4), s=st.integers(1, 4))
def test_prop_stage_flow_conservation(seed, n, k, s):
    """Arrivals = completions + final backlog, for random dags/traces."""
    inputs, dag, wan = _random_case(seed, n, k, s, t=16)
    outs = simulate_staged(
        inputs, dag, wan, make_staged_policy(dag, wan),
        jax.random.key(seed % 1000), scalar=5.0,
    )
    arrived = float(inputs.arrivals.sum())
    got = float(outs.completed.sum()) + float(outs.q_final.sum())
    assert got == pytest.approx(arrived, rel=1e-4, abs=1e-3)
    assert bool(jnp.all(outs.q_final >= 0.0))
    assert bool(jnp.all(outs.wan_gb >= 0.0))


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 4),
       k=st.integers(1, 3))
def test_prop_single_stage_bit_exact(seed, n, k):
    """Random single-stage scenarios match `simulate` (to 2e-6 relative:
    the engines are differently fused programs, see test_jobs.py)."""
    inputs, _, wan = _random_case(seed, n, k, s=1, t=12)
    dag = single_stage_dag(k)
    key = jax.random.key(seed % 997)
    pol = dispatch_fn(2.0)
    o_s = simulate(inputs, pol, key)
    o_j = simulate_staged(inputs, dag, wan, pol, key)
    np.testing.assert_array_equal(
        np.asarray(o_s.f_trace), np.asarray(o_j.f_trace[..., 0])
    )
    np.testing.assert_allclose(np.asarray(o_s.cost), np.asarray(o_j.cost),
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(o_s.q_final), np.asarray(o_j.q_final[..., 0]),
        rtol=2e-6, atol=1e-6,
    )
    assert float(o_j.wan_cost.sum()) == 0.0


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 4),
       k=st.integers(1, 3), s=st.integers(2, 4))
def test_prop_shuffle_billing_matches_transfer_plan(seed, n, k, s):
    """The engine's per-slot WAN bill equals re-deriving transfer_cost over
    the realized flows, for random multi-stage scenarios."""
    inputs, dag, wan = _random_case(seed, n, k, s, t=6)
    pol = make_staged_policy(dag, wan)
    outs = simulate_staged(inputs, dag, wan, pol, jax.random.key(0),
                           scalar=5.0)
    # Replay slot 0 by hand: stage flows from the recorded dispatch.
    q = jnp.zeros((n, k, dag.s_max))
    f = outs.f_trace[0]
    mu_st = stage_service_rates(inputs.mu[0], dag)
    total_in, src = inputs.arrivals[0], inputs.data_dist
    wan_cost = 0.0
    for stage in range(dag.s_max):
        vol = total_in * dag.shuffle_gb[:, stage]
        plan = transfer_plan(src, f[:, :, stage].T, vol)
        wc, _, _ = transfer_cost(plan, wan, inputs.omega[0], inputs.pue[0])
        wan_cost += float(wc)
        total_done, src = flow_step(
            q[:, :, stage], f[:, :, stage], total_in, mu_st[:, :, stage]
        )
        if stage + 1 < dag.s_max:
            total_in = total_done * dag.stage_mask[:, stage + 1]
    assert float(outs.wan_cost[0]) == pytest.approx(
        wan_cost, rel=1e-4, abs=1e-4
    )
