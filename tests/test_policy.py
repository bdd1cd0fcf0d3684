"""The policy contract: every built-in policy's declaration, the combinations
a declaration refuses, and how a spec reaches the engines."""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.core.baselines import (
    data_dispatch,
    greedy_cost_dispatch,
    jsq_dispatch,
    random_dispatch,
)
from repro.core.gmsa import dispatch_fn, gmsa_policy, make_kernel_policy
from repro.core.policy import (
    PolicySpec,
    check_ratios,
    declare,
    slot_aux,
    spec_of,
    split_aux,
)
from repro.core.simulator import SimInputs, simulate
from repro.jobs.dag import single_stage_dag
from repro.jobs.scheduler import (
    make_staged_policy,
    stage_oblivious,
    staged_dispatch_fn,
)
from repro.placement import wan_topology

T, N, K = 4, 3, 2
R = jnp.full((K, N, N), 1.0 / N, jnp.float32)
P_IT = jnp.ones((K,), jnp.float32)
DAG = single_stage_dag(K)
WAN = wan_topology(jnp.ones((N,)), jnp.ones((N,)))

KEY_FREE = PolicySpec(keyed=False)
STAGED = PolicySpec(keyed=False, staged=True, reads="wpue", returns="flow")

# What each built-in policy declared through its function attributes
# (``consumes_key``, ``state_independent``, ``wants_wpue``, ``wants_r``,
# ``static_r``, ``staged``, ``returns_flow``, ``returns_hedge``) before they
# became one spec. Staged policies were always handed ``(data_dist, wpue)``
# by the staged engines, so they read ``"wpue"``.
BUILT_IN = {
    "gmsa_policy": (lambda: gmsa_policy, KEY_FREE),
    "dispatch_fn": (lambda: dispatch_fn(1.0), KEY_FREE),
    "kernel_static_r": (
        lambda: make_kernel_policy(R, P_IT),
        PolicySpec(keyed=False, reads="wpue", binds_r=True),
    ),
    "kernel_carried_r": (
        lambda: make_kernel_policy(p_it=P_IT),
        PolicySpec(keyed=False, reads="wpue_r"),
    ),
    "data": (
        lambda: data_dispatch,
        PolicySpec(keyed=False, state_independent=True),
    ),
    "random": (lambda: random_dispatch, PolicySpec(state_independent=True)),
    "jsq": (lambda: jsq_dispatch, KEY_FREE),
    "greedy": (
        lambda: greedy_cost_dispatch,
        PolicySpec(keyed=False, state_independent=True),
    ),
    "staged": (lambda: make_staged_policy(DAG, WAN), STAGED),
    "staged_hedge": (
        lambda: make_staged_policy(DAG, WAN, hedge=0.9),
        PolicySpec(keyed=False, staged=True, reads="wpue",
                   returns="flow_hedge"),
    ),
    "staged_dispatch_fn": (lambda: staged_dispatch_fn(DAG, WAN, 1.0), STAGED),
    "oblivious_data": (
        lambda: stage_oblivious(data_dispatch),
        PolicySpec(keyed=False, state_independent=True, staged=True,
                   reads="wpue"),
    ),
    "oblivious_kernel_static_r": (
        lambda: stage_oblivious(make_kernel_policy(R, P_IT)),
        PolicySpec(keyed=False, staged=True, reads="wpue", binds_r=True),
    ),
    "oblivious_kernel_carried_r": (
        lambda: stage_oblivious(make_kernel_policy(p_it=P_IT)),
        PolicySpec(keyed=False, staged=True, reads="wpue_r"),
    ),
}


@pytest.mark.parametrize("name", list(BUILT_IN))
def test_built_in_policy_declares_its_spec(name):
    make, expected = BUILT_IN[name]
    assert spec_of(make()) == expected


@pytest.mark.parametrize(
    "fields, match",
    [
        ({"reads": "wpue_r", "binds_r": True}, "binding its ratio tensor"),
        ({"returns": "flow"}, "only staged"),
        ({"staged": True, "reads": "wpue", "returns": "flow",
          "state_independent": True}, "cannot be state-independent"),
        ({"staged": True}, "staged policies read"),
        ({"reads": "r"}, "unknown reads"),
        ({"returns": "q"}, "unknown returns"),
    ],
    ids=["binds_and_reads_r", "flow_unstaged", "flow_state_independent",
         "staged_reads_dist", "unknown_reads", "unknown_returns"],
)
def test_invalid_declaration_raises(fields, match):
    def policy(key, q, arrivals, mu, e, aux, scalar):
        return q

    with pytest.raises(ValueError, match=match):
        declare(policy, **fields)
    assert spec_of(policy) == PolicySpec()


def test_spec_survives_functools_wraps():
    """The benchmark wraps every policy in a named scope with
    ``functools.wraps``; the engines must still read its declaration."""
    inner = make_kernel_policy(R, P_IT, impl="ref")

    @functools.wraps(inner)
    def wrapped(*args, **kwargs):
        return inner(*args, **kwargs)

    assert spec_of(wrapped) is spec_of(inner)
    ones = jnp.ones((T, N), jnp.float32)
    varying = SimInputs(
        arrivals=jnp.ones((T, K), jnp.float32),
        mu=jnp.ones((T, N, K), jnp.float32),
        omega=ones, pue=ones,
        r=jnp.broadcast_to(R, (T, K, N, N)),
        p_it=P_IT,
        data_dist=jnp.full((K, N), 1.0 / N, jnp.float32),
    )
    with pytest.raises(ValueError, match="stale"):
        simulate(varying, wrapped, jax.random.key(0))


def test_undeclared_callable_gets_the_defaults():
    def plain(key, q, arrivals, mu, e, aux, scalar):
        return q

    spec = spec_of(plain)
    assert spec == PolicySpec()
    assert (spec.keyed, spec.state_independent, spec.reads, spec.binds_r,
            spec.staged, spec.returns) == (True, False, "dist", False,
                                           False, "f")


@pytest.mark.parametrize("reads", ["dist", "wpue", "wpue_r"])
def test_split_aux_inverts_slot_aux(reads):
    spec = PolicySpec(reads=reads)
    d, w, r = jnp.zeros((K, N)), jnp.ones((N,)), R
    aux = slot_aux(spec, d, w, r)
    got = split_aux(spec, aux)
    want = {"dist": (d, None, None), "wpue": (d, w, None),
            "wpue_r": (d, w, r)}[reads]
    assert all(a is b for a, b in zip(got, want))


@pytest.mark.parametrize("binds_r, r_varying", [(False, True), (True, False)])
def test_check_ratios_passes_where_nothing_is_stale(binds_r, r_varying):
    check_ratios(PolicySpec(reads="wpue", binds_r=binds_r), r_varying)
