"""Draws whose count rounding decides.

A service rate computed in another sound order of float32 operations moves
its CDF table by an ulp or two, and a uniform that falls between the two
tables draws the neighbouring count. The reference marks such draws, and the
comparison accepts either count there and nowhere else.
"""

import numpy as np
import pytest
from conftest import tiny_spec

SEED = 3_000_000_017


@pytest.fixture(scope="module")
def paper():
    import reference

    spec = tiny_spec("paper_mc")
    cfg = spec["config"]
    return spec, reference.scenario(cfg)


def test_a_rate_a_few_ulps_off_draws_only_marked_counts(paper):
    import reference

    spec, scen = paper
    rng = np.random.default_rng(SEED)
    u = rng.random((4096, 96, *scen["mu_cdf"].shape[:2]), dtype=np.float32)
    # The reference's own rate moved by 4 float32 ulps: every count that
    # changes lies in the band, and is one the band allows.
    for scale in (1 + 4 * 2.0 ** -24, 1 - 4 * 2.0 ** -24):
        moved = reference.poisson_cdf(scen["mu_rate"] * scale, scen["mu_cdf"].shape[-1] - 1)
        base, other = _counts(scen["mu_cdf"], u), _counts(moved, u)
        least, most = _counts(scen["mu_band"][1], u), _counts(scen["mu_band"][0], u)
        assert np.any(other != base)
        assert np.all((least <= other) & (other <= most))
        assert np.all((least <= base) & (base <= most))


def _counts(tables, u):
    """Inverse-CDF counts of ``u`` (..., *B) from nondecreasing ``tables`` (*B, M+1)."""
    out = np.empty(u.shape, np.int64)
    for at in np.ndindex(tables.shape[:-1]):
        out[(...,) + at] = np.searchsorted(tables[at], u[(...,) + at], side="left")
    return out


@pytest.mark.parametrize("marked", [True, False])
def test_a_count_off_by_one_passes_only_where_rounding_decides(paper, marked):
    import compare
    import reference

    spec, scen = paper
    cfg, traffic = spec["config"], spec["traffic"]
    f = cfg["fields"]
    limit = float(spec["limits"]["replay_gap"]["limit"])
    arr, mu, _ = reference.draws(scen, reference.call_key(SEED, 2), 8, f["t_slots"])
    t = f["t_slots"] // 2
    moved = arr.copy()
    moved[3, t, 0] += 1                      # the program drew one job more
    program = reference.evaluate(cfg, traffic, scen, moved, mu)
    amb = [[] for _ in range(arr.shape[0])]
    if marked:
        amb[3].append(("arr", (t, 0), (float(moved[3, t, 0]),)))
    got = compare.judge(cfg, traffic, scen, (arr, mu, amb), program)
    assert (got["replay_gap"] <= limit) == marked, got
