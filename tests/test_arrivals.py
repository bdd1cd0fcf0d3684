"""The trace draws' inverse-CDF lookup: the TPU's count equals the search.

``_inverse_cdf`` lowers to a binary search on the CPU and to a fused
compare-and-count on the TPU (``_count_below``). Both must return the same
Poisson counts, bit for bit, on every table the repo draws from. These
tests run the TPU path on the CPU by patching it in as the lookup, and
compare it with the binary search on the same keys and tables.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.traces.arrivals as arrivals


def _facebook_4dc():
    from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder

    _, build = make_sim_builder(PaperSimConfig())
    keys = jax.random.split(jax.random.key(20260), 1000)
    out = jax.jit(jax.vmap(build))(keys)
    assert out.arrivals.shape == (1000, 288, 1)
    return out.arrivals, out.mu


def _fleet_256():
    from repro.configs.fleet_256 import FleetConfig, make_fleet_builder

    cfg = FleetConfig()
    # a_max != mu_max: the narrower tables are padded with trailing 1.0s.
    assert cfg.a_max != cfg.mu_max
    _, build = make_fleet_builder(cfg)
    out = jax.jit(jax.vmap(build))(jax.random.split(jax.random.key(256), 2))
    return out.arrivals, out.mu


def _serve_rate_tables():
    arr_cdf, mu_cdf = arrivals.serve_rate_tables(
        [40.0, 3.5, 250.0], [0.4, 0.3, 0.2, 0.1], mu_headroom=1.2)
    ka, km = jax.random.split(jax.random.key(7))
    return arrivals.poisson_pair_from_tables(
        ka, km, jnp.asarray(arr_cdf), jnp.asarray(mu_cdf), 96)


def _edges():
    below_one = np.nextafter(np.float32(1.0), np.float32(0.0))
    tables = np.stack([
        arrivals.poisson_table(200.0, 400),   # leading 0.0s, tail of 1.0s
        arrivals.poisson_table(0.5, 400),     # almost all of it exact 1.0s
        np.pad(arrivals.poisson_table(40.5, 128), (0, 272),
               constant_values=1.0),          # padded as the fleet pads
    ]).astype(np.float32)
    assert (tables == 1.0).sum(axis=1).min() > 100 and (tables == 0.0).any()
    entries = np.where(tables < 1.0, tables, 0.0)
    u = np.concatenate([
        np.zeros((3, 4), np.float32),
        np.full((3, 4), below_one, np.float32),
        entries,                              # u equal to each table entry
        np.nextafter(entries, np.float32(1.0)),
        np.nextafter(entries, np.float32(0.0)).clip(0.0),
    ], axis=1).astype(np.float32)
    return arrivals._inverse_cdf(jnp.asarray(tables), jnp.asarray(u))


@pytest.mark.parametrize(
    "draw", [_facebook_4dc, _fleet_256, _serve_rate_tables, _edges],
    ids=["facebook_4dc", "fleet_256", "serve_rate_tables", "edges"],
)
def test_tpu_count_equals_binary_search(monkeypatch, draw):
    monkeypatch.setattr(arrivals, "_inverse_cdf", arrivals._binary_search)
    searched = jax.tree.map(np.asarray, draw())
    monkeypatch.setattr(arrivals, "_inverse_cdf", arrivals._count_below)
    counted = jax.tree.map(np.asarray, draw())
    for s, c in zip(jax.tree.leaves(searched), jax.tree.leaves(counted)):
        assert s.dtype == c.dtype and s.shape == c.shape
        np.testing.assert_array_equal(c, s, strict=True)
