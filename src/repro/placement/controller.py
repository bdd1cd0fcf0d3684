"""Two-timescale placement controller: slow re-placement x fast GMSA dispatch.

The fast loop is the paper's per-slot GMSA (or any simulator policy); the
slow loop fires every ``epoch_slots`` (W) slots and may re-place / replicate
the datasets across sites under a WAN transfer-cost model and per-site
storage caps, after which the Iridium ratio tensor ``r`` is re-derived for
the new layout. Structurally this is a ``lax.scan`` over epochs whose body
contains the placement step, the (K, N, N) Iridium rebuild, and an inner
``lax.scan`` over the epoch's W slots — one jit compilation end-to-end,
vmappable over Monte-Carlo keys exactly like ``repro.core.simulator``.

Epoch 0 always runs the *given* placement untouched (no move, no rebuild),
so with ``W >= T`` the controller degenerates to a single epoch and
``simulate_placed`` reproduces plain ``simulate`` bit-for-bit — the
equivalence the test suite pins down.

Exogenous dataset drift (new data ingested at sites the controller does not
choose — the scenario of Zhang et al., where placement must adapt over
time) enters through an optional per-epoch ``ingest`` trace; the controller
observes the drifted layout and corrects it within its per-epoch move
budget, paying for every byte through :mod:`repro.placement.wan`.

Site loss (the chaos scenario class, :mod:`repro.traces.faults`) enters
through an optional per-slot ``alive`` mask. On a death edge the controller
runs an immediate *off-schedule recovery epoch* inside the fast loop —
``drop_site`` semantics via :func:`repro.checkpoint.fault.drop_site_mask`:
the dead sites' backlog re-injects as an arrival burst, their dataset share
re-replicates over the survivors, the slow rule re-places restricted to
survivors, and the emergency WAN burst is billed into
``PlacedOutputs.recovery_cost``. Everything stays one jit'd scan-of-scans
— the recovery epoch is a ``lax.cond`` on the death edge, so the heavy
branch (rule re-place, Iridium rebuild, fused evacuation billing) executes
only on the handful of slots where a site actually dies and the no-edge
slot body stays the base engine's few fused ops — and with an all-ones
mask the fault path is bit-exact with the no-fault path: every masking op
is either an exact float identity (``* 1.0``, ``+ 0.0``), a select, or
behind the never-taken cond branch.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from repro.checkpoint.fault import drop_site_mask
from repro.core.iridium import make_allocation_rebuilder
from repro.core.simulator import (
    PolicyFn,
    SimInputs,
    energy_row,
    energy_tables,
    slot_step,
)
from repro.placement.replica import replica_read_assignment
from repro.placement.replica import sync_cost as replica_sync_cost
from repro.telemetry.config import TelemetryConfig
from repro.telemetry.config import enabled as _tel_enabled
from repro.telemetry.config import histograms as _tel_hist
from repro.telemetry.config import tracing as _tel_tracing
from repro.telemetry.metrics import hist_series
from repro.telemetry.ring import (
    EV_EPOCH,
    EV_INGEST_REDIRECT,
    EV_RECOVERY,
    EV_REPAIR,
    TelemetryFrame,
    ring_init,
    ring_push,
)
from repro.telemetry.scopes import (
    GMSA_DECIDE,
    GMSA_SCAN,
    MC_DRAWS,
    PLACED_EPOCHS,
    PLACED_RECOVERY,
    PLACED_RULE,
)
from repro.traces.datasets import io_slowdown_from_bandwidth
from repro.placement.wan import (
    DEFAULT_ENERGY_PER_GB,
    degraded_surcharge,
    evacuation_cost,
    evacuation_plan,
    plan_cost,
    transfer_cost,
    transfer_latency,
    transfer_plan,
    wan_topology,
)

_EPS = 1e-12


def survivor_renorm(masked: Array, fallback: Array, axis: int = -1) -> Array:
    """Renormalize a survivor-masked distribution back onto the simplex.

    ``masked`` is a distribution with dead sites already zeroed; rows whose
    mass sat entirely on dead sites are degenerate (zero sum) and take
    ``fallback`` instead. The single definition behind every
    mask-then-renormalize site in the fault path — keep the eps and the
    degenerate-row semantics in one place.
    """
    total = jnp.sum(masked, axis=axis, keepdims=True)
    return jnp.where(total > _EPS, masked / jnp.maximum(total, _EPS), fallback)


_survivor_renorm = survivor_renorm   # internal call sites / back-compat


def region_averse_weights(alive: Array, regions: Array) -> Array:
    """Survivor weights that shy away from regions already seeing deaths.

    Correlated outages share fate within a region (one grid feed, one
    fiber bundle — :func:`repro.traces.faults.regional_health_trace`), so
    a survivor in a region where peers just died is a worse re-placement
    target than an equally-capable survivor in an untouched region. Each
    survivor's weight is ``alive * (1 - dead_fraction_of_its_region)`` —
    computed with the O(N^2) same-region mask, so the region count never
    needs to be static. With every site alive the dead fraction is zero
    and the weights are exactly ``alive`` (the ``* 1.0`` identity); a
    survivor's weight stays strictly positive (a region with a survivor
    is never fully dead), so renormalization never degenerates beyond
    what plain ``alive`` weighting allows.
    """
    regions = jnp.asarray(regions)
    same = (regions[:, None] == regions[None, :]).astype(alive.dtype)
    dead_frac = (same @ (1.0 - alive)) / jnp.maximum(
        jnp.sum(same, axis=1), 1.0
    )
    return alive * (1.0 - dead_frac)


@dataclasses.dataclass(frozen=True)
class PlacementConfig:
    """Static knobs of the two-timescale controller (hashable: jit-static).

    Attributes:
        epoch_slots: W — slow-loop period in slots. The horizon T must be a
            multiple of min(W, T).
        move_budget: alpha in [0, 1] — per epoch, the placement moves at
            most this fraction of the way from the current layout to the
            rule's target (bounds the WAN burst per epoch).
        dataset_gb: per-type dataset sizes in GB (scalar broadcasts).
        capacity_gb: per-site storage caps in GB, or ``None`` = uncapped.
        energy_per_gb: WAN energy per GB (job-energy equivalents).
        growth: fraction of each dataset that is fresh ingest per epoch
            (only effective when an ``ingest`` trace is supplied).
        update_fraction: share of each dataset that every replica beyond the
            first must absorb as sync updates per epoch (the replication
            premium of :func:`repro.placement.replica.sync_cost`, charged
            every epoch against the layout in force).
        io_coupling: thread the *evolving* placement into the per-slot
            service rates (latency-aware replica reads): each epoch's mu
            is scaled by the current layout's I/O slowdown
            (:func:`repro.traces.datasets.io_slowdown_from_bandwidth`)
            relative to the epoch-0 layout the mu trace was calibrated
            against — re-placement buys throughput, not just energy
            price. The slow rule observes the drifted layout's scale;
            the fast loop runs under the chosen layout's scale, and a
            recovery re-placement inside an epoch re-derives the scale
            per slot from the carried layout (cond-gated on the death
            edge, like the energy rows — the epoch value would be stale:
            evacuated data raises the survivors' I/O slowdown). Off by
            default: the no-coupling path is untouched.
        io_compute_seconds / io_job_gb: the slowdown model's per-job
            compute time and intermediate pull volume (defaults match
            ``io_slowdown_from_bandwidth``).
        io_per_reader: resolve the I/O slowdown from the *actual*
            per-reader replica choices
            (:func:`repro.placement.replica.replica_read_assignment`)
            instead of the type-averaged locality: a (site, type) pair
            whose reader holds a live local replica is not slowed at all,
            whatever the other types pull remotely — the slowdown becomes
            (N, K) and scales mu per type. Off by default: the averaged
            (N,) model (and its bitwise path) is untouched.
        size / manager_share / map_share: Iridium rebuild parameters.
            Defaults equal ``build_task_allocation``'s, so default-built
            ``SimInputs.r`` and the per-epoch rebuilds agree; when the
            inputs use non-default shares (e.g. ``facebook_4dc``'s
            manager_share=0.62), pass the same values here or the cost
            series jumps at the first rebuild for non-placement reasons.
    """

    epoch_slots: int = 48
    move_budget: float = 0.5
    dataset_gb: float | tuple = 100.0
    capacity_gb: tuple | None = None
    energy_per_gb: float = DEFAULT_ENERGY_PER_GB
    growth: float = 0.0
    update_fraction: float = 0.01
    io_coupling: bool = False
    io_compute_seconds: float = 300.0
    io_job_gb: float = 5.0
    io_per_reader: bool = False
    size: float = 1.0
    manager_share: float = 0.3
    map_share: float = 0.6


class SlowObs(NamedTuple):
    """What the slow-timescale rule sees at an epoch boundary.

    Prices/PUE are the *upcoming* epoch's averages — day-ahead market
    structure and weather forecasts make these available in practice (the
    same assumption Iridium makes for bandwidth).
    """

    wpue_bar: Array     # (N,)   epoch-average omega * PUE
    mu_bar: Array       # (N, K) epoch-average service rates
    q: Array            # (N, K) backlogs at the boundary
    sizes_gb: Array     # (K,)   dataset sizes this epoch
    capacity_gb: Array  # (N,)   storage caps
    alive: Array | None = None  # (N,) {0,1} survivors; None = no fault model


#: rule(d_current, obs) -> d_target, both (K, N) row-stochastic.
PlacementRule = Callable[[Array, SlowObs], Array]


class PlacedOutputs(NamedTuple):
    """Flattened fast-loop outputs plus the slow-loop audit trail."""

    cost: Array            # (T,) per-slot dispatch energy cost
    energy: Array          # (T,)
    backlog_total: Array   # (T,)
    backlog_avg: Array     # (T,)
    q_final: Array         # (N, K)
    f_trace: Array         # (T, N, K)
    placements: Array      # (E, K, N) layout in force during each epoch
    r_trace: Array         # (E, K, N, N) ratio tensor per epoch
    wan_cost: Array        # (E,) $ spent moving data at each boundary
    wan_energy: Array      # (E,) WAN energy (job-equivalents)
    wan_gb: Array          # (E,) GB crossing the WAN
    wan_latency_s: Array   # (E,) bottleneck completion time of each move
    sync_cost: Array       # (E,) $ replication sync premium per epoch
    recovery_cost: Array   # (T,) $ emergency WAN burst on site-loss edges
    recovery_gb: Array     # (T,) GB evacuated/re-replicated on those edges
    mu_scale: Array        # (E, N) I/O service-rate scale per epoch (ones
                           # unless cfg.io_coupling)


@functools.partial(
    jax.jit, static_argnames=("policy", "rule", "cfg", "telemetry")
)
def simulate_placed(
    inputs: SimInputs,
    up: Array,
    down: Array,
    policy: PolicyFn,
    rule: PlacementRule,
    key: Array,
    cfg: PlacementConfig,
    scalar: float | Array = 0.0,
    ingest: Array | None = None,
    sizes_gb: Array | None = None,
    alive: Array | None = None,
    move_budget: Array | None = None,
    telemetry: TelemetryConfig | None = None,
    health: Array | None = None,
    link_health: Array | None = None,
    regions: Array | None = None,
) -> PlacedOutputs | tuple[PlacedOutputs, TelemetryFrame]:
    """Run the two-timescale controller over one trace.

    Args:
        inputs: the usual trace bundle; ``data_dist`` must be the static
            (K, N) form (it becomes the epoch-0 layout) and ``r`` the
            static (K, N, N) form (used verbatim for epoch 0).
        up/down: (N,) site bandwidths — feed both the WAN transfer model
            and the per-epoch Iridium rebuild.
        policy: fast-loop dispatch policy (simulator signature).
        rule: slow-loop placement rule, e.g.
            :func:`repro.placement.replica.make_adaptive_rule` or
            :func:`repro.core.baselines.static_placement_rule`.
        key: PRNG key (split per slot exactly as ``simulate`` does).
        cfg: static controller knobs.
        scalar: traced control parameter forwarded to the policy (GMSA's V).
        ingest: optional (E, K, N) exogenous ingest distributions; mixed in
            with weight ``cfg.growth`` at every boundary after epoch 0.
        sizes_gb: optional (E, K) per-epoch dataset sizes (growth trace);
            defaults to ``cfg.dataset_gb`` for all epochs.
        alive: optional (T, N) per-slot {0,1} site-alive mask
            (:mod:`repro.traces.faults`). On each death edge the controller
            runs an off-schedule recovery epoch: the dead sites' backlog
            re-injects as an arrival burst, their dataset share
            re-replicates over the survivors, the rule re-places restricted
            to survivors, and the emergency WAN burst lands in
            ``recovery_cost``. Dead sites receive no dispatch and serve
            nothing while down; an all-ones mask reproduces the no-fault
            outputs bit for bit.
        move_budget: optional *traced* override of ``cfg.move_budget`` —
            the hook :func:`repro.core.sweep.sweep_placed_budgets` uses to
            vmap a whole move-budget sweep through ONE compilation (the
            epoch structure stays static, the step size becomes data).
            ``None`` (default) uses the static config value, bit-exact
            with the pre-override behavior.
        telemetry: **static** flight-recorder config. ``None``/``OFF``
            (default) keeps the jaxpr byte-identical to the pre-telemetry
            controller. SUMMARY adds a per-slot per-site backlog stream
            (extra stacked scan output); TRACE additionally threads a
            fixed-capacity event ring through both scan levels, recording
            every epoch boundary (WAN GB/$, sync $, churn, move-budget
            use), every off-schedule recovery epoch (evacuated GB, $,
            dead sites — pushed right next to the ``lax.cond`` death
            edge) and every dead-site ingest redirect. Enabled levels
            return ``(outputs, TelemetryFrame)``.
        health: optional (T, N) per-slot site health factor in [0, 1]
            (:func:`repro.traces.faults.health_trace`). Degraded-mode
            generalization of ``alive``: the factor scales the service
            rates (a 0.3-health site is a 3.3x straggler), hoisted into
            the mu trace before the scan so the slot body is untouched.
            All-ones health is the ``* 1.0`` identity — bitwise the
            no-health outputs. Death semantics (queue wipe, burst,
            re-placement) stay with ``alive``; compose the two via
            :func:`repro.traces.faults.health_to_alive` when stragglers
            may also die.
        link_health: optional (T, N, N) per-link WAN health factor
            (:func:`repro.traces.bandwidth.link_fault_trace`). Degraded
            links surcharge every epoch-boundary move by
            ``price * (1/health - 1)`` and stretch the reported move
            latency; severed links price to ``inf`` when crossed. On a
            recovery edge the evacuation routes around severed links
            (:func:`repro.placement.wan.evacuation_plan`) and bills the
            degraded premium of the routed burst. All-alive links
            surcharge exactly ``0.0`` — the ``+ 0.0`` identity keeps
            the bills bitwise.
        regions: optional (N,) int region assignment
            (:func:`repro.traces.faults.region_assignment`); requires
            ``alive``. Survivor renormalization of the placement targets
            becomes shared-fate averse: survivors in regions already
            seeing deaths are downweighted by their region's dead
            fraction, so re-placement and evacuated data prefer
            untouched regions. With every site alive the weights
            collapse to ``alive`` exactly.
    """
    tel_on = _tel_enabled(telemetry)
    tel_trace = _tel_tracing(telemetry)
    tel_hist = _tel_hist(telemetry)
    t_slots, k_types = inputs.arrivals.shape
    n = inputs.mu.shape[1]
    if inputs.data_dist.ndim != 2 or inputs.r.ndim != 3:
        raise ValueError("simulate_placed owns the time axis: pass static "
                         "(K, N) data_dist and (K, N, N) r")
    w = min(cfg.epoch_slots, t_slots)
    if t_slots % w != 0:
        raise ValueError(f"T={t_slots} must be a multiple of W={w}")
    n_epochs = t_slots // w

    if health is not None:
        health = jnp.asarray(health, jnp.float32)
        if health.shape != (t_slots, n):
            raise ValueError(f"health must be (T={t_slots}, N={n}), "
                             f"got {health.shape}")
        # Hoisted: stragglers serve slower everywhere downstream, the
        # slot body never sees the factor. All-ones is * 1.0 exactly.
        inputs = inputs._replace(
            mu=inputs.mu * health[:, :, None].astype(inputs.mu.dtype)
        )
    linky = link_health is not None
    if linky:
        link_health = jnp.asarray(link_health, jnp.float32)
        if link_health.shape != (t_slots, n, n):
            raise ValueError(
                f"link_health must be (T={t_slots}, N={n}, N={n}), "
                f"got {link_health.shape}"
            )
    if regions is not None and alive is None:
        raise ValueError("regions requires an alive mask (shared-fate "
                         "aversion only matters under site loss)")

    faulty = alive is not None
    if faulty:
        alive = jnp.asarray(alive, jnp.float32)
        if alive.shape != (t_slots, n):
            raise ValueError(f"alive mask must be (T={t_slots}, N={n}), "
                             f"got {alive.shape}")
        # Slot 0 compares against an all-alive fleet, so a trace that
        # starts dead fires its death edge (and recovery) at t=0.
        alive_prev = jnp.concatenate(
            [jnp.ones((1, n), jnp.float32), alive[:-1]], axis=0
        )

    wan = wan_topology(up, down, cfg.energy_per_gb)
    rebuild = make_allocation_rebuilder(
        up, down, size=cfg.size,
        manager_share=cfg.manager_share, map_share=cfg.map_share,
    )
    cap = (
        jnp.full((n,), jnp.inf, jnp.float32)
        if cfg.capacity_gb is None
        else jnp.asarray(cfg.capacity_gb, jnp.float32)
    )
    if sizes_gb is None:
        sizes_gb = jnp.broadcast_to(
            jnp.asarray(cfg.dataset_gb, jnp.float32), (n_epochs, k_types)
        )
    scalar = jnp.asarray(scalar, jnp.float32)
    mb = cfg.move_budget if move_budget is None else jnp.asarray(
        move_budget, jnp.float32
    )
    p_it = inputs.p_it

    ep = lambda x: x.reshape((n_epochs, w) + x.shape[1:])
    arr_ep, mu_ep = ep(inputs.arrivals), ep(inputs.mu)
    om_ep, pu_ep = ep(inputs.omega), ep(inputs.pue)
    first = jnp.arange(n_epochs) == 0

    # Match ``simulate``'s PRNG stream exactly on both of its policy paths:
    # state-independent policies consume split(key, T)[t] per slot (the
    # precomputed-vmap path), everything else splits the carried key —
    # except key-ignoring policies (``consumes_key = False``: GMSA, JSQ,
    # GREEDY), whose per-slot threefry split is skipped entirely, exactly
    # as ``simulate`` skips it.
    state_ind = getattr(policy, "state_independent", False)
    uses_key = getattr(policy, "consumes_key", True)
    wants_wpue = getattr(policy, "wants_wpue", False)
    wants_r = getattr(policy, "wants_r", False)
    if getattr(policy, "static_r", False):
        raise ValueError(
            "the controller re-derives r at every epoch boundary (and "
            "recovery edge) — a policy binding a static ratio tensor would "
            "dispatch on stale ratios; build it with "
            "make_kernel_policy(r=None) so the carried r reaches the kernel."
        )
    keys_ep = ep(jax.random.split(key, t_slots)) if state_ind else None

    q0 = jnp.zeros((n, k_types), jnp.float32)
    d0 = jnp.asarray(inputs.data_dist, jnp.float32)
    r0 = inputs.r
    if cfg.io_coupling:
        if cfg.io_per_reader:
            ones_n = jnp.ones((n,), jnp.float32)

            def io_slow(d):
                # The read pattern's diagonal (local vs remote) is price-
                # invariant — local reads are free — so a constant wpue
                # yields the actual per-reader local/remote choices.
                reads = replica_read_assignment(d, wan, ones_n)
                return io_slowdown_from_bandwidth(
                    up, down, d, cfg.io_compute_seconds, cfg.io_job_gb,
                    reads=reads,
                )                                                    # (N, K)
        else:

            def io_slow(d):
                return io_slowdown_from_bandwidth(
                    up, down, d, cfg.io_compute_seconds, cfg.io_job_gb
                )                                                    # (N,)

        # The mu trace is calibrated against the epoch-0 layout; the
        # coupling rescales it by the current layout's I/O slowdown.
        slow0 = io_slow(d0)

    def epoch(carry, xs):
        if tel_trace:
            q, key, d, ring = carry
        else:
            q, key, d = carry
        rest = xs[7:]
        arr_e, mu_e, om_e, pu_e, size_e, ing_e, is_first = xs[:7]
        if state_ind:
            keys_e, rest = rest[0], rest[1:]
        if tel_trace:
            e_idx, t_e = rest[-2], rest[-1]
            rest = rest[:-2]
        if linky:
            lh_e, rest = rest[-1], rest[:-1]
        if faulty:
            alive_e, alive_prev_e = rest
            # Aliveness *entering* the epoch drives the boundary decision;
            # deaths inside the epoch are handled by the slot-level edges.
            alive_b = alive_prev_e[0]                                 # (N,)
            any_dead_b = jnp.any(alive_b < 0.5)

        # -- slow timescale: drift, observe, re-place, pay the WAN bill.
        if ingest is not None:
            g = jnp.float32(cfg.growth)
            ing_used = ing_e
            if faulty:
                # Ingest cannot land at dead sites; it redirects to the
                # survivors (renormalized; a row aimed entirely at dead
                # sites spreads uniformly over the survivors), only when
                # any site is down.
                n_alive_b = jnp.maximum(jnp.sum(alive_b), 1.0)
                unif_b = jnp.broadcast_to(alive_b / n_alive_b, ing_e.shape)
                ing_m = _survivor_renorm(ing_e * alive_b[None, :], unif_b,
                                         axis=1)
                ing_used = jnp.where(any_dead_b, ing_m, ing_e)
            drifted = (1.0 - g) * d + g * ing_used
            drifted = drifted / jnp.maximum(
                jnp.sum(drifted, axis=1, keepdims=True), _EPS
            )
            d_drift = jnp.where(is_first, d, drifted)
        else:
            d_drift = d
        wpue_e = om_e * pu_e                                          # (W, N)
        if cfg.io_coupling:
            # The rule observes service under the *drifted* layout (its
            # decision input); the realized scale below follows its choice.
            scale_obs = io_slow(d_drift) / slow0
            if not cfg.io_per_reader:
                scale_obs = scale_obs[:, None]
            mu_bar = jnp.mean(mu_e, axis=0) * scale_obs
        else:
            mu_bar = jnp.mean(mu_e, axis=0)
        if faulty:
            mu_bar = mu_bar * alive_b[:, None]   # dead sites serve nothing
        obs = SlowObs(
            wpue_bar=jnp.mean(wpue_e, axis=0),
            mu_bar=mu_bar,
            q=q, sizes_gb=size_e, capacity_gb=cap,
            alive=alive_b if faulty else None,
        )
        with jax.named_scope(PLACED_RULE):
            target = rule(d_drift, obs)
        if faulty:
            # The controller enforces survivor-only targets regardless of
            # whether the plugged-in rule is survivor-aware; with regions
            # the weights are additionally shared-fate averse.
            surv_b = (alive_b if regions is None
                      else region_averse_weights(alive_b, regions))
            t_m = _survivor_renorm(target * surv_b[None, :], d_drift, axis=1)
            target = jnp.where(any_dead_b, t_m, target)
        stepped = d_drift + mb * (target - d_drift)
        stepped = stepped / jnp.maximum(jnp.sum(stepped, axis=1, keepdims=True), _EPS)
        d_new = jnp.where(is_first, d, stepped)
        # Fused billing (no (K, N, N) plan for the $ numbers); the plan is
        # still materialized once per epoch boundary for the bottleneck
        # latency, which needs the per-link bytes.
        wan_c, wan_e, wan_gb = plan_cost(d_drift, d_new, size_e, wan,
                                         om_e[0], pu_e[0])
        if linky:
            # Degraded links enter as an additive premium on the fused
            # bill (exactly 0.0 on all-alive links) and stretch the
            # bottleneck latency of the boundary move.
            lh_b = lh_e[0]
            sur_c, sur_e = degraded_surcharge(
                d_drift, d_new, size_e, wan, om_e[0], pu_e[0], lh_b
            )
            wan_c, wan_e = wan_c + sur_c, wan_e + sur_e
            wan_lat = transfer_latency(
                transfer_plan(d_drift, d_new, size_e), wan, link_health=lh_b
            )
        else:
            wan_lat = transfer_latency(
                transfer_plan(d_drift, d_new, size_e), wan
            )
        # Ongoing replication premium: every epoch, each replica beyond the
        # first absorbs update_fraction of its dataset at the epoch-mean price.
        sync_c = replica_sync_cost(
            d_new, size_e, wan, obs.wpue_bar, cfg.update_fraction
        )
        if tel_trace:
            # Epoch-boundary flight record: realized churn vs the rule's
            # asked-for churn (move-budget use), plus the epoch's WAN and
            # sync bills — pushed once per epoch into the carried ring.
            churn = 0.5 * jnp.sum(jnp.abs(d_new - d_drift))
            tgt_churn = 0.5 * jnp.sum(jnp.abs(target - d_drift))
            ring = ring_push(
                ring, jnp.bool_(True), e_idx * w, EV_EPOCH,
                (wan_gb, wan_c, sync_c, churn,
                 churn / jnp.maximum(tgt_churn, _EPS),
                 e_idx.astype(jnp.float32)),
            )
            if ingest is not None and faulty:
                ring = ring_push(
                    ring,
                    jnp.logical_and(any_dead_b, jnp.logical_not(is_first)),
                    e_idx * w, EV_INGEST_REDIRECT,
                    (jnp.sum(ing_e * (1.0 - alive_b)[None, :]),
                     jnp.float32(n) - jnp.sum(alive_b)),
                )
        if cfg.io_coupling:
            scale_full = io_slow(d_new) / slow0             # (N,) or (N, K)
            mu_e_raw = mu_e          # pre-scale rows: the fault path re-
            if cfg.io_per_reader:    # derives from these
                mu_e = mu_e * scale_full[None]
                scale_e = jnp.mean(scale_full, axis=-1)  # (N,) audit column
            else:
                mu_e = mu_e * scale_full[None, :, None]
                scale_e = scale_full
        else:
            scale_e = jnp.ones((n,), jnp.float32)
        r_e = jnp.where(is_first, r0, rebuild(d_new))                 # (K, N, N)
        if faulty:
            r_m = r_e * alive_b[None, None, :]
            r_m = r_m / jnp.maximum(jnp.sum(r_m, axis=-1, keepdims=True), _EPS)
            r_e = jnp.where(any_dead_b, r_m, r_e)

        # -- fast timescale: the simulator's slot body against (d_new, r_e).
        e_cost, e_raw = energy_tables(r_e, wpue_e, pu_e, p_it)

        def slot(carry2, xs2):
            if faulty:
                if tel_trace:
                    q2, key2, d_c, r_c, fired, ring2 = carry2
                else:
                    q2, key2, d_c, r_c, fired = carry2
            else:
                q2, key2 = carry2
            arrivals, mu, ec, er = xs2[:4]
            rest2 = xs2[4:]
            if state_ind:
                sub, rest2 = rest2[0], rest2[1:]
            elif uses_key:
                key2, sub = jax.random.split(key2)
            else:
                sub = key2   # key-ignoring policy: no per-slot split
            if wants_wpue and not faulty:
                wpue_t, rest2 = rest2[0], rest2[1:]
            aux = d_new
            if faulty:
                if tel_trace:
                    t_t, rest2 = rest2[-1], rest2[:-1]
                if cfg.io_coupling:
                    mu_raw_t, rest2 = rest2[-1], rest2[:-1]
                if linky:
                    lh_t, rest2 = rest2[-1], rest2[:-1]
                alive_t, alive_prev_t, om_t, pu_t = rest2
                died = alive_prev_t * (1.0 - alive_t)                 # (N,)
                any_died = jnp.any(died > 0.5)
                any_dead = jnp.any(alive_t < 0.5)
                wpue_t = om_t * pu_t
                # drop_site semantics, static-shape: wipe dead queues, form
                # the re-injection burst, renormalize the survivor layout.
                q2, d_masked, d_drop, burst = drop_site_mask(
                    q2, d_c, alive_t, died
                )
                arrivals = arrivals + burst
                mu = mu * alive_t[:, None]

                # ---- the off-schedule recovery epoch, gated by lax.cond
                # on the death edge: the heavy branch (rule re-place,
                # Iridium rebuild, fused evacuation + move billing) runs
                # ONLY on the handful of slots where a site actually dies
                # — every no-edge slot takes the trivial branch and the
                # slot body stays the base engine's few fused ops. The
                # predicate depends only on the (unbatched) alive trace,
                # so the cond survives the Monte-Carlo vmap as a cond.
                @jax.named_scope(PLACED_RECOVERY)
                def recover(q_r, d_masked_r, d_drop_r, mu_r):
                    obs_r = SlowObs(
                        wpue_bar=wpue_t, mu_bar=mu_r, q=q_r,
                        sizes_gb=size_e, capacity_gb=cap, alive=alive_t,
                    )
                    surv_t = (alive_t if regions is None
                              else region_averse_weights(alive_t, regions))
                    with jax.named_scope(PLACED_RULE):
                        tgt_r = rule(d_drop_r, obs_r)
                    tgt = _survivor_renorm(
                        tgt_r * surv_t[None, :], d_drop_r, axis=1,
                    )
                    d_rec = d_drop_r + mb * (tgt - d_drop_r)
                    d_rec = d_rec / jnp.maximum(
                        jnp.sum(d_rec, axis=1, keepdims=True), _EPS
                    )
                    # Fused billing: cost(evac + move) = cost(evac) +
                    # cost(move) — pricing is linear in the plan, and no
                    # (K, N, N) plan is materialized on the fault path.
                    ev_c, _, ev_g = evacuation_cost(
                        d_masked_r, d_drop_r, size_e, wan, om_t, pu_t
                    )
                    mv_c, _, mv_g = plan_cost(
                        d_drop_r, d_rec, size_e, wan, om_t, pu_t
                    )
                    if linky:
                        # Route the evacuation around severed links and
                        # bill the degraded premium of the routed burst
                        # plus the move's surcharge — all inside the cond's
                        # heavy branch, and every term exactly 0.0 when
                        # the links are all alive (the bills stay bitwise).
                        plan_r = evacuation_plan(
                            d_masked_r, d_drop_r, size_e, link_health=lh_t
                        )
                        deg_c, _, _ = transfer_cost(
                            plan_r, wan, om_t, pu_t, link_health=lh_t
                        )
                        nom_c, _, _ = transfer_cost(plan_r, wan, om_t, pu_t)
                        msur_c, _ = degraded_surcharge(
                            d_drop_r, d_rec, size_e, wan, om_t, pu_t, lh_t
                        )
                        ev_c = ev_c + (deg_c - nom_c) + msur_c
                    r_rec = rebuild(d_rec) * alive_t[None, None, :]
                    r_rec = r_rec / jnp.maximum(
                        jnp.sum(r_rec, axis=-1, keepdims=True), _EPS
                    )
                    return d_rec, r_rec, ev_c + mv_c, ev_g + mv_g

                def no_recover(q_r, d_masked_r, d_drop_r, mu_r):
                    zero = jnp.zeros((), jnp.float32)
                    return d_c, r_c, zero, zero

                d_c, r_c, rec_cost, rec_gb = jax.lax.cond(
                    any_died, recover, no_recover, q2, d_masked, d_drop, mu
                )
                fired = jnp.logical_or(fired, any_died)
                if tel_trace:
                    # The flight record of the recovery epoch the cond just
                    # (maybe) ran: a masked ring write, so the no-edge slot
                    # costs a handful of fused selects and writes nothing.
                    ring2 = ring_push(
                        ring2, any_died, t_t, EV_RECOVERY,
                        (rec_gb, rec_cost, jnp.sum(died),
                         jnp.argmax(died).astype(jnp.float32)),
                    )
                    # Revival edge: the companion event the SLO clock
                    # anchors to (time-to-SLO from the true repair slot,
                    # not the death slot — :mod:`repro.telemetry.collect`
                    # pairs the two). Masked write: an all-ones mask
                    # leaves the ring bitwise untouched.
                    revived = alive_t * (1.0 - alive_prev_t)
                    ring2 = ring_push(
                        ring2, jnp.any(revived > 0.5), t_t, EV_REPAIR,
                        (jnp.sum(revived),
                         jnp.argmax(revived).astype(jnp.float32)),
                    )
                # Epoch tables go stale the moment a recovery re-places
                # mid-epoch; re-derive this slot's row from the carried r
                # (also cond-gated: no fault so far -> no extra einsums).
                ec, er = jax.lax.cond(
                    fired,
                    lambda rr: energy_row(rr, wpue_t, pu_t, p_it),
                    lambda rr: (ec, er),
                    r_c,
                )
                if cfg.io_coupling:
                    # The epoch-granular mu scale is derived from the
                    # boundary layout d_new; the moment a recovery re-
                    # places mid-epoch that scale is STALE — dead sites'
                    # data landed on survivors, whose I/O slowdown rose.
                    # Re-derive this slot's scale from the carried layout
                    # (cond-gated like ec/er: no fault so far, no extra
                    # work; fired=False is the exact identity).
                    def _io_rescale(dc):
                        s = io_slow(dc) / slow0
                        if not cfg.io_per_reader:
                            s = s[:, None]
                        return mu_raw_t * s * alive_t[:, None]

                    mu = jax.lax.cond(
                        fired, _io_rescale, lambda dc: mu, d_c
                    )
                aux = d_c
            if wants_wpue:
                # The kernel-dispatch aux contract: raw per-slot prices,
                # and (wants_r) the ratio tensor actually in force — the
                # carried r_c on the fault path (recovery re-places mid-
                # epoch), the epoch rebuild r_e otherwise.
                aux = (aux, wpue_t)
            if wants_r:
                aux = aux + ((r_c if faulty else r_e),)
            with jax.named_scope(GMSA_DECIDE):
                f = policy(sub, q2, arrivals, mu, ec, aux, scalar)
            if faulty:
                # No dispatch mass to dead sites, whatever the policy says.
                n_alive = jnp.maximum(jnp.sum(alive_t), 1.0)
                f_fb = jnp.broadcast_to((alive_t / n_alive)[:, None], f.shape)
                f_m = _survivor_renorm(f * alive_t[:, None], f_fb, axis=0)
                f = jnp.where(any_dead, f_m, f)
            q_next, out = slot_step(q2, f, arrivals, mu, ec, er)
            if tel_on:
                tel_out = (jnp.sum(q_next, axis=-1),)     # (N,) per-site q
                if tel_hist:
                    # Per-site slice of the bill ``slot_step`` just summed
                    # — recorded in-scan because recovery epochs rewrite
                    # the energy rows mid-epoch (``ec`` is cond-carried,
                    # not reconstructible from the epoch tables post-scan).
                    tel_out = tel_out + (
                        jnp.sum(f * arrivals[None, :] * ec.T, axis=1),
                    )
            else:
                tel_out = ()
            if faulty:
                carry_next = (q_next, key2, d_c, r_c, fired)
                if tel_trace:
                    carry_next = carry_next + (ring2,)
                return carry_next, out + (rec_cost, rec_gb) + tel_out
            return (q_next, key2), out + tel_out

        slot_xs = (arr_e, mu_e, e_cost, e_raw)
        if state_ind:
            slot_xs = slot_xs + (keys_e,)
        if wants_wpue and not faulty:
            slot_xs = slot_xs + (wpue_e,)
        if faulty:
            slot_xs = slot_xs + (alive_e, alive_prev_e, om_e, pu_e)
            if linky:
                slot_xs = slot_xs + (lh_e,)
            if cfg.io_coupling:
                slot_xs = slot_xs + (mu_e_raw,)
            if tel_trace:
                slot_xs = slot_xs + (t_e,)
            carry0 = (q, key, d_new, r_e, jnp.bool_(False))
            if tel_trace:
                carry0 = carry0 + (ring,)
            with jax.named_scope(GMSA_SCAN):
                carry_out, slot_outs = jax.lax.scan(slot, carry0, slot_xs)
            q, key, d_carry = carry_out[:3]
            if tel_trace:
                ring = carry_out[-1]
        else:
            with jax.named_scope(GMSA_SCAN):
                (q, key), slot_outs = jax.lax.scan(slot, (q, key), slot_xs)
            d_carry = d_new
        epoch_out = slot_outs + (d_new, r_e, wan_c, wan_e, wan_gb, wan_lat,
                                 sync_c, scale_e)
        carry_out = (q, key, d_carry)
        if tel_trace:
            carry_out = carry_out + (ring,)
        return carry_out, epoch_out

    xs = (arr_ep, mu_ep, om_ep, pu_ep, sizes_gb,
          ingest if ingest is not None else jnp.zeros((n_epochs, k_types, n)),
          first)
    if state_ind:
        xs = xs + (keys_ep,)
    if faulty:
        xs = xs + (ep(alive), ep(alive_prev))
    if linky:
        xs = xs + (ep(link_health),)
    carry_init = (q0, key, d0)
    if tel_trace:
        xs = xs + (jnp.arange(n_epochs, dtype=jnp.int32),
                   jnp.arange(t_slots, dtype=jnp.int32).reshape(n_epochs, w))
        carry_init = carry_init + (ring_init(telemetry.capacity),)
    with jax.named_scope(PLACED_EPOCHS):
        carry_final, outs = jax.lax.scan(epoch, carry_init, xs)
    q_final = carry_final[0]
    if tel_trace:
        ring_out = carry_final[-1]
    # Per-slot scan columns lead; the epoch-level audit trail follows.
    n_slot_cols = (5 + (2 if faulty else 0) + (1 if tel_on else 0)
                   + (1 if tel_hist else 0))
    slot_cols = outs[:n_slot_cols]
    (d_tr, r_tr, wc, we, wgb, wlat, sc, msc) = outs[n_slot_cols:]
    (cost, energy, btot, bavg, f_trace) = slot_cols[:5]
    if faulty:
        rec_cost, rec_gb = slot_cols[5:7]
    else:
        rec_cost = jnp.zeros((n_epochs, w), jnp.float32)
        rec_gb = jnp.zeros((n_epochs, w), jnp.float32)
    flat = lambda x: x.reshape((t_slots,) + x.shape[2:])
    placed = PlacedOutputs(
        cost=flat(cost), energy=flat(energy),
        backlog_total=flat(btot), backlog_avg=flat(bavg),
        q_final=q_final, f_trace=flat(f_trace),
        placements=d_tr, r_trace=r_tr,
        wan_cost=wc, wan_energy=we, wan_gb=wgb, wan_latency_s=wlat,
        sync_cost=sc,
        recovery_cost=flat(rec_cost), recovery_gb=flat(rec_gb),
        mu_scale=msc,
    )
    if tel_on:
        q_site = slot_cols[-2] if tel_hist else slot_cols[-1]  # (E, W, N)
        metrics = {"q_site": flat(q_site)}
        if tel_hist:
            site_cost = flat(slot_cols[-1])                    # (T, N)
            metrics["site_cost_hist"] = hist_series(
                telemetry.hist, site_cost, axis=0
            )                                                  # (N, B)
        return placed, TelemetryFrame(
            ring=ring_out if tel_trace else ring_init(1),
            metrics=metrics,
        )
    return placed


@functools.partial(
    jax.jit,
    static_argnames=("build_inputs", "policy", "rule", "cfg", "n_runs",
                     "telemetry", "mesh"),
)
def simulate_placed_many(
    build_inputs: Callable[[Array], SimInputs],
    up: Array,
    down: Array,
    policy: PolicyFn,
    rule: PlacementRule,
    key: Array,
    n_runs: int,
    cfg: PlacementConfig,
    scalar: float | Array = 0.0,
    ingest: Array | None = None,
    sizes_gb: Array | None = None,
    alive: Array | None = None,
    move_budget: Array | None = None,
    telemetry: TelemetryConfig | None = None,
    health: Array | None = None,
    link_health: Array | None = None,
    regions: Array | None = None,
    mesh=None,
) -> PlacedOutputs:
    """Monte-Carlo replication of :func:`simulate_placed` (vmap over keys).

    Mirrors ``simulate_many``: fresh stochastic traces + policy randomness
    per run, deterministic traces (prices, PUE, drift, the site-alive mask
    and the health/link-health factors) shared. One compilation serves
    every run. With telemetry enabled the frames stack on the runs axis
    like everything else — decode one run's lane with
    :func:`repro.telemetry.collect.collect_records`.

    ``mesh`` (static) shards the runs axis over a host-device mesh
    (:func:`repro.distributed.mesh.runs_mesh`) — same split keys, bitwise
    the single-device outputs at every device count.
    """
    keys = jax.random.split(key, n_runs)

    def one(run_key):
        k_build, k_sim = jax.random.split(run_key)
        with jax.named_scope(MC_DRAWS):
            inputs = build_inputs(k_build)
        return simulate_placed(
            inputs, up, down, policy, rule, k_sim, cfg,
            scalar=scalar, ingest=ingest, sizes_gb=sizes_gb, alive=alive,
            move_budget=move_budget, telemetry=telemetry, health=health,
            link_health=link_health, regions=regions,
        )

    if mesh is None:
        return jax.vmap(one)(keys)
    from repro.distributed.mesh import sharded_runs

    return sharded_runs(one, keys, mesh)


def summarize_placed(outs: PlacedOutputs) -> dict:
    """Time-averaged scalars incl. WAN/sync/recovery bills (any runs axis)."""
    t_slots = outs.cost.shape[-1]
    dispatch = jnp.mean(outs.cost)
    wan_per_slot = jnp.mean(jnp.sum(outs.wan_cost, axis=-1)) / t_slots
    sync_per_slot = jnp.mean(jnp.sum(outs.sync_cost, axis=-1)) / t_slots
    recovery_per_slot = jnp.mean(outs.recovery_cost)
    return {
        "time_avg_dispatch_cost": float(dispatch),
        "time_avg_wan_cost": float(wan_per_slot),
        "time_avg_sync_cost": float(sync_per_slot),
        "time_avg_recovery_cost": float(recovery_per_slot),
        "time_avg_total_cost": float(
            dispatch + wan_per_slot + sync_per_slot + recovery_per_slot
        ),
        "time_avg_energy": float(jnp.mean(outs.energy)),
        "time_avg_backlog": float(jnp.mean(outs.backlog_avg)),
        "total_wan_gb": float(jnp.mean(jnp.sum(outs.wan_gb, axis=-1))),
        "mean_mu_scale": float(jnp.mean(outs.mu_scale)),
        "total_recovery_gb": float(jnp.mean(jnp.sum(outs.recovery_gb, axis=-1))),
        "max_move_latency_s": float(jnp.max(outs.wan_latency_s)),
        "final_backlog_total": float(jnp.mean(outs.q_final.sum(axis=(-2, -1)))),
    }
