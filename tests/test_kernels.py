"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode — executes kernel bodies in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# The parametrized equivalence sweeps below run without hypothesis; only the
# @given property tests need it, so they alone are skipped when it's absent.
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.kernels.gmsa_score import gmsa_score, gmsa_score_ref
from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref
from repro.models.ssm import ssd_chunked


# ---------------------------------------------------------------------------
# gmsa_score
# ---------------------------------------------------------------------------

def _gmsa_inputs(key, k, n, dtype):
    ks = jax.random.split(key, 6)
    return (
        (jax.random.uniform(ks[0], (k, n)) * 100).astype(dtype),
        (jax.random.uniform(ks[1], (k, n)) * 50).astype(dtype),
        (jax.random.uniform(ks[2], (k,)) * 40).astype(dtype),
        (jax.random.uniform(ks[3], (k,)) * 10).astype(dtype),
        jax.random.dirichlet(ks[4], jnp.ones(n), (k, n)).astype(dtype),
        (jax.random.uniform(ks[5], (n,)) * 20).astype(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,n", [(1, 4), (4, 17), (8, 128), (8, 256),
                                 (9, 129), (16, 256)])
def test_gmsa_score_matches_ref(k, n, dtype):
    q, mu, a, vp, r, wpue = _gmsa_inputs(jax.random.key(k * 1000 + n), k, n, dtype)
    s_ref, b_ref = gmsa_score_ref(q, mu, a, vp, r, wpue)
    s, b = gmsa_score(q, mu, a, vp, r, wpue, interpret=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(s, s_ref, rtol=tol, atol=tol)
    # argmin is a discrete boundary: equal iff no near-tie at tolerance.
    gap = np.partition(np.asarray(s_ref, np.float64), 1, axis=1)
    near_tie = (gap[:, 1] - gap[:, 0]) < 1e-2 * np.abs(gap[:, 0])
    agree = np.asarray(b) == np.asarray(b_ref)
    assert np.all(agree | near_tie)


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        k=st.integers(1, 24),
        n=st.integers(2, 200),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_gmsa_score_property(k, n, seed):
        """Property: kernel argmin always indexes a true row minimum."""
        q, mu, a, vp, r, wpue = _gmsa_inputs(jax.random.key(seed), k, n, jnp.float32)
        s_ref, _ = gmsa_score_ref(q, mu, a, vp, r, wpue)
        s, b = gmsa_score(q, mu, a, vp, r, wpue, interpret=True)
        picked = np.asarray(s_ref)[np.arange(k), np.asarray(b)]
        best = np.min(np.asarray(s_ref), axis=1)
        np.testing.assert_allclose(picked, best, rtol=1e-5, atol=1e-4)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_gmsa_score_property():
        pass


# ---------------------------------------------------------------------------
# gmsa_dispatch impl="kernel" — the dispatch-path wiring + fleet e2e
# ---------------------------------------------------------------------------

def test_gmsa_dispatch_kernel_impl_matches_ref_path():
    """The kernel dispatch path agrees with the e-table closed form on the
    fleet tile shape (K=8, N=256 — one K-tile, 2x2 N/J tiles)."""
    from repro.core.gmsa import gmsa_dispatch

    k, n = 8, 256
    q, mu, a, _, r, wpue = _gmsa_inputs(jax.random.key(42), k, n, jnp.float32)
    # e-table path (V applied to the precomputed cost table) vs the
    # raw-(r, wpue) kernel/oracle paths at the same V: the score formulas
    # are algebraically identical (p_it = 1).
    v = 3.0
    e_table = jnp.einsum("kij,j->ki", r, wpue)          # p_it = 1
    f_ref = gmsa_dispatch(q.T, a, mu.T, e_table, v)
    f_kernel = gmsa_dispatch(
        q.T, a, mu.T, None, v, impl="kernel", r=r, wpue=wpue, interpret=True
    )
    f_oracle = gmsa_dispatch(
        q.T, a, mu.T, None, v, impl="ref", r=r, wpue=wpue
    )
    # One-hot columns: near-ties may differ by a ULP of score — compare
    # through realized scores instead of argmin indices.
    s_ref, _ = gmsa_score_ref(q, mu, a, v * jnp.ones((k,)), r, wpue)
    picked = lambda f: np.asarray(s_ref)[np.arange(k), np.asarray(f).argmax(0)]
    best = np.min(np.asarray(s_ref), axis=1)
    np.testing.assert_allclose(picked(f_kernel), best, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(picked(f_oracle), best, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(picked(f_ref), best, rtol=1e-4, atol=1e-3)


def test_gmsa_dispatch_kernel_requires_raw_operands():
    from repro.core.gmsa import gmsa_dispatch

    q = jnp.zeros((4, 2))
    with pytest.raises(ValueError, match="raw operands"):
        gmsa_dispatch(q, jnp.ones(2), q, None, 1.0, impl="kernel")
    with pytest.raises(ValueError, match="unknown impl"):
        gmsa_dispatch(q, jnp.ones(2), q, jnp.zeros((2, 4)), 1.0,
                      impl="bogus")


def test_fleet256_end_to_end_kernel_vs_ref():
    """A short N=256 fleet_256 GMSA run completes through
    gmsa_dispatch(..., impl="kernel") (interpret mode) and matches the
    reference engine slot for slot."""
    from repro.configs.fleet_256 import FleetConfig, make_fleet_builder
    from repro.core.gmsa import gmsa_policy, make_kernel_policy
    from repro.core.simulator import simulate

    cfg = FleetConfig(t_slots=8)
    template, _ = make_fleet_builder(cfg)
    key = jax.random.key(0)
    o_ref = simulate(template, gmsa_policy, key, cfg.v)
    o_k = simulate(
        template, make_kernel_policy(template.r, template.p_it), key, cfg.v
    )
    agree = float((o_ref.f_trace == o_k.f_trace).mean())
    assert agree > 0.999, agree
    np.testing.assert_allclose(
        np.asarray(o_k.cost), np.asarray(o_ref.cost), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(o_k.q_final), np.asarray(o_ref.q_final), rtol=1e-4
    )
    # The pure-jnp oracle fallback drives the same run too.
    o_r = simulate(
        template,
        make_kernel_policy(template.r, template.p_it, impl="ref"),
        key, cfg.v,
    )
    np.testing.assert_allclose(
        np.asarray(o_r.cost), np.asarray(o_ref.cost), rtol=1e-4
    )


# ---------------------------------------------------------------------------
# gmsa_score vmapped over Monte-Carlo runs — the runs-on-lanes kernel below
# N = 128, the per-run kernel from there up
# ---------------------------------------------------------------------------

def _runs_inputs(key, runs, k, n, r_runs, w_runs):
    """R runs of ``_gmsa_inputs``; r and wpue shared unless batched."""
    keep = (True,) * 4 + (r_runs, w_runs)
    draw = lambda kk: _gmsa_inputs(kk, k, n, jnp.float32)
    per_run = jax.jit(jax.vmap(lambda kk: [x for x, b in zip(draw(kk), keep) if b]))(
        jax.random.split(key, runs))
    shared = [x for x, b in zip(draw(key), keep) if not b]
    return tuple(per_run.pop(0) if b else shared.pop(0) for b in keep)


def _runs_axes(r_runs, w_runs):
    return (0, 0, 0, 0, 0 if r_runs else None, 0 if w_runs else None)


def _score_scale(q, mu, a, vp, r, wpue):
    """Each score's size before cancellation: a * (|q| + |mu| + vp * cost)."""
    cost = jnp.einsum("kij,j->ki", r, wpue, precision=jax.lax.Precision.HIGHEST)
    return jnp.abs(a)[:, None] * (jnp.abs(q) + jnp.abs(mu)
                                  + jnp.abs(vp)[:, None] * cost)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and the jaxprs it nests."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_calls(inner)
    return found


_RUN_CASES = [
    pytest.param(k, n, runs, r_runs, w_runs,
                 id=f"K{k}-N{n}-R{runs}-" + {(False, False): "shared",
                                             (True, False): "r_runs",
                                             (True, True): "r_wpue_runs"}[r_runs, w_runs])
    for k, n in [(1, 4), (3, 5), (8, 127)]
    for r_runs, w_runs, run_counts in [(False, False, (1, 37, 1000)),
                                       (True, False, (1, 37)),
                                       (True, True, (1, 37))]
    for runs in run_counts
]


@pytest.mark.parametrize("k,n,runs,r_runs,w_runs", _RUN_CASES)
def test_vmapped_gmsa_score_matches_ref_per_run(k, n, runs, r_runs, w_runs):
    args = _runs_inputs(jax.random.key(runs * 1000 + n), runs, k, n, r_runs, w_runs)
    axes = _runs_axes(r_runs, w_runs)
    s, b = jax.vmap(lambda *x: gmsa_score(*x, interpret=True), in_axes=axes)(*args)
    s_ref, b_ref = jax.vmap(gmsa_score_ref, in_axes=axes)(*args)
    scale = np.asarray(jax.vmap(_score_scale, in_axes=axes)(*args))
    assert s.shape == (runs, k, n) and b.shape == (runs, k)
    s, s_ref = np.asarray(s), np.asarray(s_ref)
    assert np.all(np.abs(s - s_ref) <= 1e-5 * scale)
    low2 = np.partition(s_ref.astype(np.float64), 1, axis=-1)
    near_tie = (low2[..., 1] - low2[..., 0]) <= 1e-5 * scale.max(axis=-1)
    assert np.all((np.asarray(b) == np.asarray(b_ref)) | near_tie)


@pytest.mark.parametrize("r_runs", [False, True], ids=["shared", "r_runs"])
def test_vmapped_gmsa_score_ties_go_to_the_lowest_site(r_runs):
    """Bitwise-equal scores: every site equal, or two sites equal at the
    minimum; the lowest index wins, as the per-run kernel's argmin."""
    runs, k, n = 37, 2, 5
    row = jnp.array([0.25, 0.5, 0.125, 0.125])
    r = jnp.broadcast_to(jnp.concatenate([row, jnp.zeros(1)]), (k, n, n))
    wpue = jnp.array([4.0, 8.0, 2.0, 16.0, 1.0])        # same cost at every site
    tied = np.zeros((runs, k, n), np.float32)           # run 0, type 0: all tie
    tied[1:, 0] = 7.0
    lo, hi = np.arange(1, runs) % (n - 1), np.arange(1, runs) % (n - 1) + 1
    tied[np.arange(1, runs), 0, lo] = tied[np.arange(1, runs), 0, hi] = 3.0
    tied[:, 1] = [9.0, 2.0, 2.0, 2.0, 5.0]              # type 1: sites 1-3 tie
    q, mu = jnp.asarray(tied), jnp.zeros((runs, k, n))
    a, vp = jnp.full((runs, k), 3.0), jnp.full((runs, k), 0.5)
    if r_runs:
        r = jnp.broadcast_to(r, (runs,) + r.shape)
    s, b = jax.vmap(lambda *x: gmsa_score(*x, interpret=True),
                    in_axes=_runs_axes(r_runs, False))(q, mu, a, vp, r, wpue)
    want = np.stack([np.concatenate([[0], lo]), np.full(runs, 1)], axis=1)
    np.testing.assert_array_equal(np.asarray(b), want)
    # The ties are exact: the tied sites' scores are bitwise equal.
    s = np.asarray(s)
    assert np.all(s[1:, 0][np.arange(runs - 1), lo] == s[1:, 0][np.arange(runs - 1), hi])


@pytest.mark.parametrize("r_runs", [False, True], ids=["shared", "r_runs"])
def test_nested_vmap_over_v_and_runs_is_one_launch(r_runs):
    """A V sweep around the runs vmap: one launch for every (V, run) pair,
    the same answers as the per-run oracle."""
    runs, k, n = 37, 3, 5
    q, mu, a, _, r, wpue = _runs_inputs(jax.random.key(5), runs, k, n, r_runs, False)
    vs = jnp.array([0.5, 2.0, 8.0])

    def sweep(score):
        def at(v):
            per_run = lambda q_, mu_, a_, r_: score(q_, mu_, a_, v * jnp.ones(k), r_, wpue)
            return jax.vmap(per_run, in_axes=(0, 0, 0, 0 if r_runs else None))(q, mu, a, r)
        return jax.vmap(at)

    kernel = sweep(lambda *x: gmsa_score(*x, interpret=True))
    (launch,) = _pallas_calls(jax.make_jaxpr(kernel)(vs).jaxpr)
    assert launch.params["name"] == "gmsa_score"
    assert launch.params["grid_mapping"].grid[0] == 1     # 111 runs, one tile
    s, b = kernel(vs)
    s_ref, b_ref = sweep(gmsa_score_ref)(vs)
    assert s.shape == (3, runs, k, n) and b.shape == (3, runs, k)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5, atol=1e-3)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_ref))


def test_runs_vmap_engages_one_lanes_launch_below_n_t_and_per_run_grid_above():
    """The path each shape takes, read from the jaxpr: at the Facebook-4DC
    widths (K=1, N=4, R=1000, shared r) one ``gmsa_score`` launch whose grid
    walks run tiles; at the ``fleet_256`` widths (K=8, N=256), and where no
    run tile fits, the per-run kernel, its grid the unbatched call's with
    the runs in front."""
    from repro.kernels.gmsa_score.kernel import lanes_tiles

    sd = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    score = lambda *x: gmsa_score(*x, interpret=False)
    runs_of = lambda k, n, runs: jax.make_jaxpr(
        jax.vmap(score, in_axes=(0, 0, 0, None, None, 0))
    )(sd(runs, k, n), sd(runs, k, n), sd(runs, k), sd(k), sd(k, n, n), sd(runs, n)).jaxpr
    plain_of = lambda k, n: jax.make_jaxpr(score)(
        sd(k, n), sd(k, n), sd(k), sd(k), sd(k, n, n), sd(n)).jaxpr

    (lanes,) = _pallas_calls(runs_of(1, 4, 1000))
    r_t, _ = lanes_tiles(1, 4, 1000, False, False)
    assert lanes.params["name"] == "gmsa_score"
    assert lanes.params["grid_mapping"].grid == (-(-1000 // r_t), 1)
    assert r_t <= 1024 and r_t % 128 == 0

    for k, n in [(1, 4), (8, 256)]:
        (plain,) = _pallas_calls(plain_of(k, n))
        assert "minval_ref" in plain.params["jaxpr"].debug_info.arg_names
    (fleet,) = _pallas_calls(runs_of(8, 256, 16))
    assert fleet.params["name"] == "gmsa_score"
    assert fleet.params["grid_mapping"].grid == (16,) + plain.params["grid_mapping"].grid
    assert fleet.params["jaxpr"].debug_info.arg_names == plain.params["jaxpr"].debug_info.arg_names

    # Below N_T, but no run tile fits the VMEM budget: the per-run kernel.
    assert lanes_tiles(64, 127, 16, False, False) is None
    (wide,) = _pallas_calls(runs_of(64, 127, 16))
    assert wide.params["grid_mapping"].grid[0] == 16
    assert "minval_ref" in wide.params["jaxpr"].debug_info.arg_names


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

def _ssd_inputs(key, b, s, h, p, n, dtype):
    ks = jax.random.split(key, 5)
    return (
        jax.random.normal(ks[0], (b, s, h, p)).astype(dtype),
        jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype),
        -jnp.exp(jax.random.normal(ks[2], (h,))),
        jax.random.normal(ks[3], (b, s, n)).astype(dtype),
        jax.random.normal(ks[4], (b, s, n)).astype(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [(1, 32, 2, 8, 16, 8), (2, 128, 3, 64, 128, 128), (1, 72, 2, 32, 64, 16)],
)
def test_ssd_scan_matches_ref(b, s, h, p, n, chunk, dtype):
    x, dt, a, bm, cm = _ssd_inputs(jax.random.key(b + s), b, s, h, p, n, dtype)
    y_ref, h_ref = ssd_scan_ref(x, dt, a, bm, cm)
    y, hf = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    tol = 3e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(hf, h_ref, rtol=tol, atol=tol)


def test_ssd_scan_matches_model_path():
    """Kernel == the model's chunked pure-JAX path (third formulation)."""
    b, s, h, p, n = 2, 64, 2, 16, 32
    x, dt, a, bm, cm = _ssd_inputs(jax.random.key(7), b, s, h, p, n, jnp.float32)
    y_kernel, h_kernel = ssd_scan(x, dt, a, bm, cm, chunk=16, interpret=True)
    y_model, h_model = ssd_chunked(x, dt, a, bm, cm, 16)
    np.testing.assert_allclose(y_kernel, y_model, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_kernel, h_model, rtol=2e-4, atol=2e-4)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        s=st.integers(4, 96),
        chunk=st.sampled_from([4, 8, 16, 32]),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_ssd_scan_chunk_invariance(s, chunk, seed):
        """Property: the result must not depend on the chunk size."""
        b, h, p, n = 1, 2, 8, 16
        x, dt, a, bm, cm = _ssd_inputs(jax.random.key(seed), b, s, h, p, n, jnp.float32)
        y1, h1 = ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
        y2, h2 = ssd_scan(x, dt, a, bm, cm, chunk=s, interpret=True)
        np.testing.assert_allclose(y1, y2, rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(h1, h2, rtol=3e-4, atol=3e-4)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_ssd_scan_chunk_invariance():
        pass
