"""Replica-selection & placement scoring (slow-timescale counterpart of GMSA).

Where :func:`repro.core.gmsa.gmsa_dispatch` answers "which DC manages this
slot's jobs", this module answers the slow question "which DCs should *hold*
each dataset" — trading the co-location gain of hosting data at cheap,
capacity-rich sites (Kumar et al., data placement & replica selection)
against replication storage/sync cost and per-site storage caps.

Everything is a vectorized closed-form/greedy rule in the style of
``gmsa_dispatch``:

* :func:`hosting_scores` — the per-(type, site) linear objective;
* :func:`target_placement` — softmin over sites (temperature -> 0 recovers
  the LP-vertex one-hot, exactly as GMSA's argmin) projected onto the
  storage-capacity polytope by iterative proportional capping;
* :func:`replica_read_assignment` — the fast replica-*selection* rule: each
  reader site picks its cheapest live replica (an argmin vertex rule);
* :func:`effective_replicas` / :func:`replication_premium` /
  :func:`sync_cost` — the replication premium (the rule's objective term
  and the controller's bill share one definition);
* :func:`expected_read_cost` — spread's benefit under replica selection
  (feeds the sync-aware candidate ladder of :func:`make_adaptive_rule`).

All functions are pure jnp with static iteration counts: jit-safe inside the
controller's epoch scan, vmappable over Monte-Carlo runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array
from jax.nn import one_hot, softmax

from repro.placement.wan import WanModel, link_price_matrix

_EPS = 1e-12

#: A replica below this placement fraction is considered not materialized at
#: the site (it cannot serve reads, it incurs no sync traffic).
REPLICA_THRESHOLD = 0.01

#: Hosting-score penalty added to dead sites (same units as the scores,
#: $/MWh-equivalents): large enough that the softmin underflows to exactly
#: zero preference there at any realistic temperature.
DEAD_SITE_PENALTY = 1e6


def hosting_scores(
    wpue_bar: Array,
    cap_share: Array,
    up: Array,
    colo_weight: float = 0.0,
    net_weight: float = 0.0,
) -> Array:
    """Per-(type, site) cost of hosting one unit of data — lower is better.

        score[k, j] = wpue_bar_j  -  colo_weight * cap_share[k, j]
                      +  net_weight / up_j

    The first term is the epoch-average energy price paid by the data-local
    work that follows the dataset (map tasks + the Iridium-placed reduce
    pull); the second rewards co-locating data with service capacity (more
    jobs complete where the data lives); the third penalizes hosts whose
    uplink throttles shipping the data to remote executors.

    Args:
        wpue_bar: (N,) epoch-average omega * PUE per site.
        cap_share: (K, N) per-type service-capacity shares (rows sum to 1).
        up: (N,) uplink bandwidths, Gb/s.

    Returns:
        (K, N) scores.
    """
    return (
        wpue_bar[None, :]
        - colo_weight * cap_share
        + net_weight / jnp.maximum(up[None, :], _EPS)
    )


def capacity_project(
    target: Array,
    sizes_gb: Array,
    capacity_gb: Array,
    iters: int = 32,
) -> Array:
    """Project row-simplex placements onto per-site storage caps.

    Repeats (static ``iters``, jit-safe): scale down every site that exceeds
    its cap, then redistribute each row's lost mass to sites with headroom,
    proportionally to ``headroom * original preference``. With feasible
    totals (sum of dataset sizes <= sum of caps) this converges to a
    row-stochastic placement with site loads within a fraction of a percent
    of the caps; callers must provision feasible capacity.

    Args:
        target: (K, N) unconstrained placement preference (rows sum to 1).
        sizes_gb: (K,) dataset sizes.
        capacity_gb: (N,) per-site storage caps (``inf`` = uncapped).

    Returns:
        (K, N) row-stochastic placement respecting the caps.
    """
    finite_cap = jnp.isfinite(capacity_gb)

    # A rolled loop: unrolled, the 32 steps made the TPU compile of every
    # engine that holds a placement rule take minutes.
    def step(_, p):
        load = jnp.sum(p * sizes_gb[:, None], axis=0)                  # (N,)
        scale = jnp.where(
            finite_cap, jnp.minimum(1.0, capacity_gb / jnp.maximum(load, _EPS)), 1.0
        )
        p = p * scale[None, :]
        headroom = jnp.where(
            finite_cap,
            jnp.maximum(capacity_gb - jnp.sum(p * sizes_gb[:, None], axis=0), 0.0),
            jnp.float32(1e9),
        )
        w = target * headroom[None, :] + _EPS
        deficit = jnp.maximum(1.0 - jnp.sum(p, axis=1), 0.0)           # (K,)
        return p + deficit[:, None] * w / jnp.sum(w, axis=1, keepdims=True)

    p = jax.lax.fori_loop(0, iters, step, jnp.asarray(target))
    return p / jnp.maximum(jnp.sum(p, axis=1, keepdims=True), _EPS)


def target_placement(
    scores: Array,
    sizes_gb: Array,
    capacity_gb: Array,
    temp: float = 2.0,
    project_iters: int = 32,
) -> Array:
    """Greedy placement target: softmin over sites, capacity-projected.

    ``temp`` is in the same units as the scores ($/MWh-equivalents); as
    ``temp -> 0`` the softmin collapses to the one-hot LP vertex (all of
    dataset k at its single cheapest feasible site), exactly mirroring
    ``gmsa_dispatch``'s argmin. Finite temperature keeps secondary replicas
    alive, which is what replica *selection* then exploits.
    """
    pref = softmax(-scores / jnp.maximum(temp, 1e-6), axis=1)          # (K, N)
    return capacity_project(pref, sizes_gb, capacity_gb, project_iters)


def replica_read_assignment(
    data_dist: Array, wan: WanModel, wpue: Array, latency_weight: float = 0.0
) -> Array:
    """Each reader site's cheapest live replica — an argmin vertex rule.

    read_cost[k, j, i] = energy_per_gb * (wpue_i + wpue_j)/2
                         + latency_weight * 8 / link_bw[i, j]      (i -> j)

    with sites holding less than :data:`REPLICA_THRESHOLD` of dataset k
    masked out. Local reads are free (link_bw diagonal is ``inf`` and the
    energy term is still paid only when i != j — enforced by zeroing the
    diagonal cost), so a reader holding a replica always serves itself.

    Returns:
        (K, N, N) selection s[k, j, i] one-hot over hosts i for each reader j.
    """
    n = wpue.shape[0]
    price = link_price_matrix(wpue) * wan.energy_per_gb                 # (N, N) i,j
    lat = latency_weight * 8.0 / wan.link_bw                            # (N, N)
    cost = price + lat
    cost = jnp.where(jnp.eye(n, dtype=bool), 0.0, cost)                 # local free
    live = data_dist >= REPLICA_THRESHOLD                               # (K, N)
    cost_kji = jnp.where(live[:, None, :], cost.T[None, :, :], jnp.inf) # (K, j, i)
    best = jnp.argmin(cost_kji, axis=2)                                 # (K, N)
    return one_hot(best, n, dtype=data_dist.dtype)                      # (K, N, N)


def effective_replicas(data_dist: Array) -> Array:
    """(K,) inverse-Simpson replica count 1 / sum_j d_kj^2.

    1.0 when a dataset is fully concentrated at one site, N when spread
    uniformly — a smooth, jit-safe proxy for "how many copies must be kept
    in sync".
    """
    return 1.0 / jnp.maximum(jnp.sum(jnp.square(data_dist), axis=1), _EPS)


def sync_cost(
    data_dist: Array,
    sizes_gb: Array,
    wan: WanModel,
    wpue: Array,
    update_fraction: float = 0.01,
) -> Array:
    """Per-epoch replication sync bill (scalar $).

    Every replica beyond the first must absorb ``update_fraction`` of its
    dataset in updates per epoch, shipped over the WAN at the mean link
    price. Shards below :data:`REPLICA_THRESHOLD` are not materialized
    (same rule as :func:`replica_read_assignment`): they hold no copy and
    sync nothing, so the softmin's residue at expensive sites is not
    billed. The billed quantity is exactly :func:`replication_premium` —
    the term the sync-aware hosting rule optimizes — priced in GB.
    """
    gb = jnp.sum(replication_premium(data_dist, update_fraction) * sizes_gb)
    return gb * wan.energy_per_gb * jnp.mean(wpue)


def replication_premium(target: Array, update_fraction: float) -> Array:
    """(K,) per-unit-data sync overhead of a candidate placement.

    ``update_fraction * (effective_replicas - 1)`` over the *materialized*
    shards (the :data:`REPLICA_THRESHOLD` rule). :func:`sync_cost` prices
    exactly this quantity, so the rule's objective and the controller's
    bill agree on what counts as a replica by construction. Units:
    fraction of the dataset re-shipped per epoch — multiplied by a
    $-per-unit weight by the caller.
    """
    live = jnp.where(target >= REPLICA_THRESHOLD, target, 0.0)
    total = jnp.sum(live, axis=1, keepdims=True)
    live = jnp.where(total > _EPS, live / jnp.maximum(total, _EPS), target)
    return update_fraction * jnp.maximum(effective_replicas(live) - 1.0, 0.0)


def expected_read_cost(target: Array, wpue: Array, reader_share: Array) -> Array:
    """(K,) per-unit-data cost of serving reads from a candidate placement.

    Each reader site pulls from its cheapest *materialized* replica —
    the exact selection rule of :func:`replica_read_assignment` (local
    reads free, remote reads at the endpoint-mean price) — weighted by
    ``reader_share`` (where the reading work actually runs). This is the
    spread-favoring half of the replication trade-off: more replicas
    mean cheaper reads, which is what finite placement temperature buys
    and what the sync premium charges for. Units: $/MWh-equivalents per
    unit data (the ``energy_per_gb`` scale is the caller's weight).

    Args:
        target: (K, N) candidate placement (rows on the simplex).
        wpue: (N,) current omega * PUE.
        reader_share: (K, N) per-type read weights (rows sum to 1).
    """
    price = link_price_matrix(wpue)                               # (i, j)
    live = target >= REPLICA_THRESHOLD                            # (K, N)
    cost_kji = jnp.where(live[:, None, :], price.T[None], jnp.inf)
    best = jnp.min(cost_kji, axis=2)                              # (K, j)
    # A candidate with no materialized replica cannot serve reads at all;
    # make it maximally unattractive (finite, so argmin stays valid).
    best = jnp.where(jnp.isfinite(best), best, jnp.max(wpue))
    return jnp.sum(reader_share * best, axis=1)


def make_adaptive_rule(
    up: Array,
    temp: float = 2.0,
    colo_weight: float = 0.0,
    net_weight: float = 0.0,
    project_iters: int = 32,
    sync_weight: float = 0.0,
    update_fraction: float = 0.01,
    read_fraction: float = 0.05,
):
    """Bind scoring weights into the controller's slow-timescale rule.

    Returns ``rule(d, obs) -> d_target`` for
    :func:`repro.placement.controller.simulate_placed`; ``obs`` is a
    :class:`repro.placement.controller.SlowObs`.

    With ``sync_weight > 0`` the rule itself trades replication's benefit
    against its overhead (not just the billing): it evaluates a ladder of
    spread candidates — softmins from 4x warmer than ``temp`` down to the
    one-hot LP vertex — under the replica-*selection* cost surrogate

        min over materialized i of score[k, i]       (primary serving)
        + read_fraction * expected_read_cost(c)      (spread's benefit)
        + sync_weight * wpue_mean
          * replication_premium(c, update_fraction)  (spread's cost)

    all in $/MWh-equivalents per unit data, and keeps the per-type argmin
    before capacity projection. Under replica selection the marginal work
    is served by the best materialized replica (so serving cost is the
    primary's score, shared by every candidate that keeps the best site
    live — NOT the linear ``c . score``, under which the vertex would
    minimize serving and premium simultaneously and no weight could ever
    spread); what extra replicas buy is read locality (every reader
    pulls from its cheapest materialized replica, the
    :func:`replica_read_assignment` rule), and what they cost is exactly
    the premium :func:`sync_cost` bills. ``sync_weight`` dials
    consolidation: 0 preserves the original single-candidate rule
    exactly; small values keep warm, replica-rich placements; large
    values collapse to the vertex.
    """
    up = jnp.asarray(up, jnp.float32)

    def rule(d: Array, obs) -> Array:
        del d  # memoryless target; the controller applies the move budget
        cap_share = (obs.mu_bar / jnp.maximum(
            jnp.sum(obs.mu_bar, axis=0, keepdims=True), _EPS
        )).T                                                            # (K, N)
        scores = hosting_scores(
            obs.wpue_bar, cap_share, up,
            colo_weight=colo_weight, net_weight=net_weight,
        )
        capacity_gb = obs.capacity_gb
        alive = getattr(obs, "alive", None)
        if alive is not None:
            # Survivor-aware: dead sites can neither host (score penalty
            # underflows the softmin to 0 there) nor store (zero cap for
            # the projection). With every site alive both terms are exact
            # no-ops, keeping the no-fault path bit-exact.
            alive = jnp.asarray(alive, jnp.float32)
            scores = scores + DEAD_SITE_PENALTY * (1.0 - alive)[None, :]
            capacity_gb = jnp.where(alive < 0.5, 0.0, capacity_gb)
        if sync_weight == 0.0:
            return target_placement(
                scores, obs.sizes_gb, capacity_gb,
                temp=temp, project_iters=project_iters,
            )
        # Sync-aware candidate ladder: warmer softmins spread replicas
        # (cheap reads, costly sync), colder ones consolidate. Chosen per
        # type under the selection surrogate: primary serving + read
        # benefit + sync premium, jit-safe.
        cands = jnp.stack([
            softmax(-scores / jnp.maximum(t, 1e-6), axis=1)
            for t in (4.0 * temp, temp, 0.25 * temp, 1e-6)
        ])                                                              # (C, K, N)
        live = cands >= REPLICA_THRESHOLD
        big = jnp.max(jnp.abs(scores)) + 1.0
        primary = jnp.min(
            jnp.where(live, scores[None], big), axis=2
        )                                                               # (C, K)
        premium = jnp.stack([
            replication_premium(c, update_fraction) for c in cands
        ])                                                              # (C, K)
        read = jnp.stack([
            expected_read_cost(c, obs.wpue_bar, cap_share) for c in cands
        ])                                                              # (C, K)
        wpue_mean = jnp.mean(obs.wpue_bar)
        total = (primary + read_fraction * read
                 + sync_weight * wpue_mean * premium)
        best = jnp.argmin(total, axis=0)                                # (K,)
        pref = jnp.take_along_axis(
            cands, best[None, :, None], axis=0
        )[0]                                                            # (K, N)
        return capacity_project(
            pref, obs.sizes_gb, capacity_gb, project_iters
        )

    return rule
