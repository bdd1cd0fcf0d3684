"""FleetEngine — the serving control plane, driven by the simulation stack.

This is the paper's Sec. II framework serving live LLM traffic: the front
end ingests stochastic requests per class (architecture × request shape)
from batched :mod:`repro.traces.arrivals` tables, applies per-class
admission control, and every slot dispatches through the SAME joint
stage scheduler that wins in ``simulate_staged`` — each request class is
a 2-stage prefill → decode :class:`repro.jobs.dag.StageDag` (the KV-cache
handoff is the shuffle volume billed when decode runs on a different pod
than prefill), and prefill traffic routes through a placement layout via
:func:`repro.placement.replica.replica_read_assignment` (replica reads
pick the serving pod). Pods then execute REAL prefill+decode steps for
the jobs they drain.

The per-slot update is :func:`repro.jobs.engine.staged_slot_update` — the
single definition shared with ``simulate_staged``'s scan body — and the
post-run cost/WAN bills evaluate the simulator's own batched expressions,
so a dispatch-only :meth:`FleetEngine.run` replays bit-for-bit against
``simulate_staged`` on the shared :class:`ServeScenario` (test-pinned).

Pod death (an optional ``(T, N)`` alive mask) mirrors the placement
controller's fault path: on a death edge the dead pod's queues are wiped
(a select, never ``* alive`` — the ULP trap), the backlog re-injects as
an arrival burst at the prefill stage (the KV cache died with the pod, so
decode-stage work re-executes from scratch — the re-execution discipline
of the reliable-geo-analytics reference, PAPERS.md 1802.00245), routing
renormalizes over the survivors, and the recovery event lands in the
history/telemetry stream. An all-ones mask is bit-exact with the
no-fault loop.

Energy accounting follows DESIGN.md §7: per-job energy derives from the
model's parameter count and tokens processed (6·N_active·tokens FLOPs at
chip efficiency), weighted by per-pod PUE and price traces — and
``history[t]["energy_j"]`` prices jobs actually SERVED
(``min(q + f·A, mu)`` per stage, compute-weighted), not jobs dispatched:
a saturated pod bills only what it drains.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.configs.base import ModelConfig
from repro.core.gmsa import make_kernel_policy
from repro.core.policy import spec_of
from repro.core.simulator import SimInputs, _energy_tables
from repro.jobs.dag import StageDag, chain_dag
from repro.jobs.engine import (
    _hedge_bill,
    hedged_mu,
    staged_shuffle_mixes,
    staged_slot_update,
)
from repro.jobs.scheduler import (
    make_staged_policy,
    stage_oblivious,
    stage_service_rates_all,
)
from repro.models.lm import init_params
from repro.placement.controller import survivor_renorm
from repro.placement.replica import replica_read_assignment
from repro.placement.wan import (
    WanModel,
    degraded_surcharge,
    plan_cost,
    wan_topology,
)
from repro.serve.step import make_local_exec
from repro.telemetry.config import TelemetryConfig
from repro.telemetry.config import histograms as _tel_hist
from repro.telemetry.metrics import (
    percentile_table,
    sojourn_init,
    sojourn_step,
)
from repro.traces.arrivals import (
    admission_split,
    poisson_pair_from_tables,
    serve_rate_tables,
)

# TPU v5e-class constants (DESIGN.md §7).
CHIP_PEAK_FLOPS = 197e12
CHIP_TDP_W = 200.0
CHIP_EFFICIENCY = 0.45

#: Pod throughput skew cycled to any fleet size (FleetConfig.__post_init__).
DEFAULT_CAPACITY_SHARES = (0.3, 0.2, 0.9, 0.6)


@dataclasses.dataclass(frozen=True)
class RequestClass:
    """One workload class k: an architecture at a request shape.

    ``cfg`` is the model actually executed (smoke-scale on this container);
    ``energy_cfg`` (default: cfg) is the architecture whose parameter count
    prices the job — pass the FULL config so the control plane sees
    production-scale energy while execution stays CPU-sized.
    """

    name: str
    cfg: ModelConfig
    energy_cfg: ModelConfig | None = None
    prompt_len: int = 32
    gen_len: int = 8
    arrival_rate: float = 6.0     # jobs / slot (Poisson)

    def flops_per_job(self) -> float:
        toks = self.prompt_len + self.gen_len
        ecfg = self.energy_cfg or self.cfg
        return 6.0 * ecfg.active_param_count() * toks

    def energy_per_job_j(self) -> float:
        """IT-side energy per job (Joules): chip-seconds × TDP."""
        chip_seconds = self.flops_per_job() / (CHIP_PEAK_FLOPS * CHIP_EFFICIENCY)
        return chip_seconds * CHIP_TDP_W

    def stage_compute(self) -> tuple[float, float]:
        """(prefill, decode) compute shares — token-proportional split."""
        toks = float(self.prompt_len + self.gen_len)
        return self.prompt_len / toks, self.gen_len / toks

    def kv_gb(self) -> float:
        """Prefill → decode handoff volume per job (GB): the KV cache.

        Priced at the production architecture (``energy_cfg``), bf16:
        2 (K and V) × layers × kv_heads × head_dim × prompt tokens.
        Attention-free (SSM) backbones hand off the recurrent state
        snapshot instead.
        """
        ecfg = self.energy_cfg or self.cfg
        if ecfg.has_attention:
            by = (2 * ecfg.num_layers * ecfg.num_kv_heads
                  * ecfg.resolved_head_dim * self.prompt_len * 2)
        else:
            by = ecfg.num_layers * ecfg.d_inner * ecfg.ssm_state * 2
        return by / 1e9


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Static serving-fleet knobs.

    ``capacity_shares`` shorter (or longer) than ``n_pods`` is completed
    deterministically in ``__post_init__`` by cycling the given tuple —
    ``FleetConfig(n_pods=8)`` runs end-to-end instead of crashing in the
    straggler-noise Poisson draw. An empty tuple raises.
    """

    n_pods: int = 4
    horizon_slots: int = 32
    v: float = 1.0
    seed: int = 0
    batch_per_exec: int = 4       # jobs executed per model invocation
    capacity_shares: tuple = DEFAULT_CAPACITY_SHARES   # pod throughput skew
    admit_max: float | None = None    # per-class per-slot admission cap
    slo_backlog: float = 50.0     # per-class backlog SLO threshold
    exec_cap: int | None = 8      # real-execution throttle per (class, slot)
                                  # (smoke-scale containers; None = every
                                  # drained job executes)
    dispatch: str = "staged"      # "staged" (joint stage scheduler) or
                                  # "kernel" (gmsa_dispatch impl="kernel")
    hedge_threshold: float | None = None
                                  # speculative re-execution: clone a
                                  # dispatched stage to the runner-up pod
                                  # when its effective service rate falls
                                  # below this fraction of the runner-up's
                                  # (staged dispatch only; None = off)

    def __post_init__(self):
        shares = tuple(float(s) for s in self.capacity_shares)
        if not shares:
            raise ValueError("capacity_shares must not be empty")
        if len(shares) != self.n_pods:
            shares = tuple(
                itertools.islice(itertools.cycle(shares), self.n_pods)
            )
            object.__setattr__(self, "capacity_shares", shares)
        if self.dispatch not in ("staged", "kernel"):
            raise ValueError(f"unknown dispatch impl {self.dispatch!r}")
        if self.hedge_threshold is not None:
            if self.dispatch != "staged":
                raise ValueError(
                    "hedge_threshold requires the staged dispatcher"
                )
            if not self.hedge_threshold > 0.0:
                raise ValueError(
                    f"hedge_threshold must be > 0, got {self.hedge_threshold}"
                )


class ServeScenario(NamedTuple):
    """The shared scenario a serving run and ``simulate_staged`` agree on.

    ``inputs.arrivals`` is the ADMITTED trace (post admission control) and
    ``inputs.data_dist`` the replica-read serving distribution — feed this
    bundle to ``simulate_staged(inputs, dag, wan, policy, ...)`` and a
    dispatch-only ``FleetEngine.run`` replays it bit for bit.
    """

    inputs: SimInputs       # arrivals (T,K) admitted, mu (T,N,K), ...
    dag: StageDag           # (K, 2) prefill -> decode chain
    wan: WanModel           # KV-handoff pricing
    raw_arrivals: Array     # (T, K) pre-admission request counts
    rejected: Array         # (T, K) load shed at the door
    layout: Array           # (K, N) dataset/KV-prefix placement layout
    reads: Array            # (K, N, N) replica-read assignment (one-hot)


def build_serve_scenario(
    fcfg: FleetConfig,
    classes: list[RequestClass],
    omega: np.ndarray,
    pue: np.ndarray,
    r: np.ndarray,
    *,
    up: Array | None = None,
    down: Array | None = None,
    layout: Array | None = None,
) -> ServeScenario:
    """Build the scenario bundle the engine and the simulator share.

    Arrivals and straggler-noise capacities for the WHOLE horizon come
    from one batched inverse-CDF draw (:mod:`repro.traces.arrivals` —
    the per-slot ``np.random`` loop is gone); admission control splits
    them exactly; prefill routing is the placement layer's cheapest-live-
    replica read assignment averaged over (uniform) reader locations.
    """
    n, k = fcfg.n_pods, len(classes)
    t_slots = fcfg.horizon_slots
    key = jax.random.key(fcfg.seed)

    # Price/PUE traces tiled to the horizon (callers may pass shorter).
    idx = np.arange(t_slots)
    omega_t = jnp.asarray(omega, jnp.float32)[idx % len(omega)]
    pue_t = jnp.asarray(pue, jnp.float32)[idx % len(pue)]

    # Batched arrival ingestion + straggler-noise capacity: one
    # searchsorted for the whole horizon.
    rates = np.asarray([rc.arrival_rate for rc in classes], np.float64)
    arr_cdf, mu_cdf = serve_rate_tables(rates, fcfg.capacity_shares)
    ka, km = jax.random.split(jax.random.fold_in(key, 1))
    raw_arrivals, mu = poisson_pair_from_tables(
        ka, km, jnp.asarray(arr_cdf), jnp.asarray(mu_cdf), t_slots
    )
    admitted, rejected = admission_split(raw_arrivals, fcfg.admit_max)

    if up is None or down is None:
        up = jnp.full((n,), 10.0, jnp.float32)
        down = jnp.full((n,), 10.0, jnp.float32)
    wan = wan_topology(jnp.asarray(up), jnp.asarray(down))
    if layout is None:
        layout = jnp.full((k, n), 1.0 / n, jnp.float32)
    layout = jnp.asarray(layout, jnp.float32)

    # Replica reads pick the serving pod: each (uniformly located) reader
    # pulls from its cheapest live replica at the horizon-mean energy
    # price; the class's prefill serving distribution is the read
    # assignment averaged over readers.
    wpue_bar = jnp.mean(omega_t * pue_t, axis=0)                   # (N,)
    reads = replica_read_assignment(layout, wan, wpue_bar)         # (K,N,N)
    serve_dist = jnp.mean(reads, axis=1)                           # (K, N)

    # Prefill -> decode as a 2-stage chain: compute split token-
    # proportional, the KV cache as the inter-stage shuffle volume.
    comp = jnp.asarray([rc.stage_compute() for rc in classes], jnp.float32)
    shuf = jnp.asarray([[0.0, rc.kv_gb()] for rc in classes], jnp.float32)
    dag = chain_dag(comp, shuf)

    p_it = jnp.asarray(
        [rc.energy_per_job_j() / 3.6e6 for rc in classes], jnp.float32
    )  # kWh/job — priced by omega in $/MWh => dollars×1e-3 scale
    inputs = SimInputs(
        arrivals=admitted, mu=mu, omega=omega_t, pue=pue_t,
        r=jnp.asarray(r, jnp.float32), p_it=p_it, data_dist=serve_dist,
    )
    return ServeScenario(
        inputs=inputs, dag=dag, wan=wan, raw_arrivals=raw_arrivals,
        rejected=rejected, layout=layout, reads=reads,
    )


def serve_policy(fcfg: FleetConfig, scenario: ServeScenario):
    """The dispatch policy of a serving run — the simulator's own.

    ``"staged"`` is the joint stage scheduler (prefill pinned to the
    replica-read layout, decode site scored drift-plus-penalty with the
    KV pull priced); ``"kernel"`` routes the per-slot decision through
    ``gmsa_dispatch(impl="kernel")`` — the fleet-scale Pallas path —
    adapted by ``stage_oblivious`` (prefill stays layout-pinned).
    """
    if fcfg.dispatch == "kernel":
        base = make_kernel_policy(scenario.inputs.r, p_it=scenario.inputs.p_it)
        return stage_oblivious(base, pin_map=True)
    return make_staged_policy(scenario.dag, scenario.wan, pin_map=True,
                              hedge=fcfg.hedge_threshold)


class FleetEngine:
    """Slot-driven serving loop, dispatched by the simulation stack."""

    def __init__(
        self,
        fcfg: FleetConfig,
        classes: list[RequestClass],
        omega: np.ndarray,          # (T, N) price traces
        pue: np.ndarray,            # (T, N)
        r: np.ndarray,              # (K, N, N) task-allocation ratios
        *,
        up: Array | None = None,    # (N,) access bandwidths (KV pricing)
        down: Array | None = None,
        layout: Array | None = None,   # (K, N) placement layout
        alive: np.ndarray | None = None,  # (T, N) pod-alive mask
        telemetry: TelemetryConfig | None = None,
        health: np.ndarray | None = None,  # (T, N) pod health in [0, 1]
        link_health: np.ndarray | None = None,  # (T, N, N) WAN link factor
    ):
        self.fcfg = fcfg
        # The distribution layer (ISSUE 8): a TelemetryConfig with a
        # HistogramSpec threads a per-class FIFO sojourn clock through the
        # jitted step — OFF/None leaves the step's jaxpr untouched.
        self.telemetry = telemetry
        self._hist_on = _tel_hist(telemetry)
        self.classes = classes
        self.omega, self.pue, self.r = omega, pue, r
        self.key = jax.random.key(fcfg.seed)
        self.params = {}
        self._decode_jit = {}
        self._prefill_jit = {}
        for rc in classes:
            self.key, sub = jax.random.split(self.key)
            self.params[rc.name] = init_params(sub, rc.cfg, jnp.float32)
            self._prefill_jit[rc.name], self._decode_jit[rc.name] = (
                make_local_exec(rc.cfg, rc.gen_len)
            )
        self.scenario = build_serve_scenario(
            fcfg, classes, omega, pue, r, up=up, down=down, layout=layout
        )
        self.health = None
        if health is not None:
            health = np.asarray(health, np.float32)
            if health.shape != (fcfg.horizon_slots, fcfg.n_pods):
                raise ValueError(
                    f"health must be (T={fcfg.horizon_slots}, "
                    f"N={fcfg.n_pods}), got {health.shape}"
                )
            self.health = health
            # Hoisted exactly like the scan engines: stragglers serve
            # slower everywhere downstream (dispatch scoring, the hedge
            # trigger, the drain), the per-slot step never sees the
            # factor. All-ones health is the * 1.0 identity — the
            # scenario stays bitwise, and so does every replay pin.
            inputs = self.scenario.inputs
            self.scenario = self.scenario._replace(
                inputs=inputs._replace(
                    mu=inputs.mu * jnp.asarray(health)[:, :, None]
                )
            )
        self.link_health = None
        if link_health is not None:
            link_health = np.asarray(link_health, np.float32)
            if link_health.shape != (
                fcfg.horizon_slots, fcfg.n_pods, fcfg.n_pods
            ):
                raise ValueError(
                    f"link_health must be (T={fcfg.horizon_slots}, "
                    f"N={fcfg.n_pods}, N={fcfg.n_pods}), "
                    f"got {link_health.shape}"
                )
            self.link_health = link_health
        self.p_it = self.scenario.inputs.p_it
        self.policy = serve_policy(fcfg, self.scenario)
        if spec_of(self.policy).keyed:
            raise ValueError(
                "FleetEngine dispatch policies must be key-free "
                "(keyed=False) so the serving loop carries no PRNG "
                "chain — both built-in dispatch impls are"
            )
        self.alive = None
        if alive is not None:
            alive = np.asarray(alive, np.float32)
            if alive.shape != (fcfg.horizon_slots, fcfg.n_pods):
                raise ValueError(
                    f"alive mask must be (T={fcfg.horizon_slots}, "
                    f"N={fcfg.n_pods}), got {alive.shape}"
                )
            self.alive = alive
        self._step = self._make_step(faulty=self.alive is not None)

    # ------------------------------------------------------------------
    # the per-slot control-plane step (jitted once per engine)
    # ------------------------------------------------------------------
    def _make_step(self, faulty: bool):
        pol = self.policy
        dag = self.scenario.dag
        returns = spec_of(pol).returns
        hedging = returns == "flow_hedge"
        key0 = jax.random.key(0)   # signature filler: key-free policies only
        hist_on = self._hist_on
        spec = self.telemetry.hist if hist_on else None

        def core(q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v):
            ret = pol(key0, q, arrivals, mu, e_cost, (dd_t, wpue_t), v)
            q_next, f, acc, in_stack = staged_slot_update(
                dag, q, ret, arrivals, mu_stages, returns
            )
            if hedging:
                # Queues drained at the first-completion boosted rates;
                # ``done`` must drain the same flow, so hand the boosted
                # rates (and the clone matrix, for the honest post-run
                # bill) back to the step. Hedge off keeps ``mu_stages``
                # itself — the non-hedging step's jaxpr is untouched.
                g = ret[2]
                return q_next, f, acc, in_stack, g, hedged_mu(f, g, mu_stages)
            return q_next, f, acc, in_stack, None, mu_stages

        def clock(age, hist, admitted, done):
            # Sojourn inflow is ADMITTED mass only — recovery-burst
            # re-injections keep their original clock, so re-executed
            # work shows up as tail latency rather than restarting at 0.
            completed = jnp.sum(done[:, :, -1], axis=0)            # (K,)
            return sojourn_step(spec, age, hist, admitted, completed)

        if not faulty:
            if not hist_on:
                @jax.jit
                def step(q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v):
                    q_next, f, acc, in_stack, g, mu_eff = core(
                        q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v
                    )
                    done = jnp.minimum(acc, mu_eff)
                    out = (q_next, f, acc, in_stack, done, jnp.float32(0.0))
                    if hedging:
                        out = out + (g,)
                    return out
                return step

            @jax.jit
            def step(q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v,
                     age, hist):
                q_next, f, acc, in_stack, g, mu_eff = core(
                    q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v
                )
                done = jnp.minimum(acc, mu_eff)
                age, hist = clock(age, hist, arrivals, done)
                out = (q_next, f, acc, in_stack, done, jnp.float32(0.0))
                if hedging:
                    out = out + (g,)
                return out + (age, hist)
            return step

        @jax.jit
        def step(q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v,
                 alive_t, died_t, *tel):
            admitted0 = arrivals   # pre-burst: the sojourn inflow
            any_died = jnp.any(died_t > 0.5)
            any_dead = jnp.any(alive_t < 0.5)
            # Recovery drain, mirroring the placement controller's fault
            # path: wipe dead pods' queues (a SELECT — ``* alive`` would
            # leave -0.0 ULP residue), re-inject the drained backlog as a
            # prefill-stage arrival burst (the KV cache died with the pod:
            # in-flight decode work re-executes from scratch), and route
            # around the dead pods by what the policy SEES — zero service,
            # prohibitive energy, survivor-renormalized prefill layout —
            # so its within-slot flow walk (in_stack) stays consistent
            # with the dispatch it returns. Every rewrite is gated on
            # any_dead / exact (* 1.0), so an all-ones mask is bit-exact
            # with the no-fault step.
            q_wiped = jnp.where(alive_t[:, None, None] > 0.5, q, 0.0)
            burst = jnp.sum(q * died_t[:, None, None], axis=(0, 2))   # (K,)
            q = jnp.where(any_dead, q_wiped, q)
            arrivals = arrivals + jnp.where(any_died, burst, 0.0)
            mu = mu * alive_t[:, None]
            mu_stages = mu_stages * alive_t[:, None, None]
            e_cost = jnp.where(
                jnp.logical_and(any_dead, alive_t[None, :] < 0.5),
                1e30, e_cost,
            )
            n_alive = jnp.maximum(jnp.sum(alive_t), 1.0)
            unif = jnp.broadcast_to((alive_t / n_alive)[None, :], dd_t.shape)
            dd_m = survivor_renorm(dd_t * alive_t[None, :], unif, axis=1)
            dd_t = jnp.where(any_dead, dd_m, dd_t)
            q_next, f, acc, in_stack, g, mu_eff = core(
                q, arrivals, mu, e_cost, mu_stages, dd_t, wpue_t, v
            )
            done = jnp.minimum(acc, mu_eff)
            out = (q_next, f, acc, in_stack, done, jnp.sum(burst))
            if hedging:
                out = out + (g,)
            if hist_on:
                age, hist = clock(tel[0], tel[1], admitted0, done)
                out = out + (age, hist)
            return out

        return step

    def _execute_jobs(self, rc: RequestClass, n_jobs: int) -> tuple[int, float]:
        """Run real prefill+decode for EXACTLY n_jobs; returns (done, secs).

        The final batch is sliced to the remainder instead of over-running
        (and over-timing) up to ``batch_per_exec - 1`` phantom jobs.
        """
        if n_jobs <= 0:
            return 0, 0.0
        b = self.fcfg.batch_per_exec
        done = 0
        t0 = time.perf_counter()
        self.key, sub = jax.random.split(self.key)
        tokens = jax.random.randint(
            sub, (b, rc.prompt_len), 0, rc.cfg.vocab_size, dtype=jnp.int32
        )
        while done < n_jobs:
            nb = min(b, n_jobs - done)
            logits, cache = self._prefill_jit[rc.name](
                self.params[rc.name], tokens[:nb]
            )
            tok = jnp.argmax(logits[:, -1:, : rc.cfg.vocab_size], axis=-1).astype(jnp.int32)
            for _ in range(rc.gen_len):
                logits, cache = self._decode_jit[rc.name](
                    self.params[rc.name], cache, tok
                )
                tok = jnp.argmax(logits[:, :, : rc.cfg.vocab_size], axis=-1).astype(jnp.int32)
            tok.block_until_ready()
            done += nb
        return done, time.perf_counter() - t0

    def run(self, execute_real: bool = True, stream=None) -> dict:
        """Run the serving loop. Returns per-slot traces + summary.

        Args:
            execute_real: run real prefill+decode for drained jobs (only
                completed decode drains execute, throttled at
                ``fcfg.exec_cap`` per class per slot).
            stream: optional callable receiving one JSON-ready dict per
                slot as the run progresses (live telemetry), emitted
                through ``jax.experimental.io_callback`` (``ordered=True``)
                — metric records every slot, plus a
                ``{"type": "event", "code": "recovery", ...}`` record on
                every pod-death edge, in slot order.

        The returned dict keeps its original keys (backward-compatible:
        ``cost``/``backlog``/``dispatch``/``exec_seconds``/``mean_cost``/
        ``final_backlog``/``history``) and adds the staged serving
        telemetry: admission splits, per-class served/completed mass,
        the KV-handoff WAN bill, SLO violations and recovery events.
        ``history[t]["energy_j"]`` prices jobs actually served.
        """
        fcfg = self.fcfg
        scn = self.scenario
        inputs = scn.inputs
        dag = scn.dag
        n, k = fcfg.n_pods, len(self.classes)
        s_max = dag.s_max
        t_slots = fcfg.horizon_slots
        v = jnp.float32(fcfg.v)

        # Hoisted tables — the simulator's own (bitwise: the parity pin).
        e_cost_all, _ = _energy_tables(inputs)                     # (T, K, N)
        wpue_all = inputs.omega * inputs.pue                       # (T, N)
        mu_stage_all = stage_service_rates_all(inputs.mu, dag)     # (T,N,K,S)
        ec_stage_all = (
            e_cost_all[:, :, None, :] * dag.compute[None, :, :, None]
        )                                                          # (T,K,S,N)

        e_per_job = np.asarray(
            [rc.energy_per_job_j() for rc in self.classes], np.float64
        )
        compute_np = np.asarray(dag.compute)                       # (K, S)
        admitted_np = np.asarray(inputs.arrivals)
        rejected_np = np.asarray(scn.rejected)
        faulty = self.alive is not None
        if faulty:
            alive_prev = np.concatenate(
                [np.ones((1, n), np.float32), self.alive[:-1]], axis=0
            )
            died_np = alive_prev * (1.0 - self.alive)

        emit = None
        if stream is not None:
            from jax.experimental import io_callback

            def _host_emit(kind_, t_, a, b_, c, d, e_, g):
                if int(kind_) == 0:
                    stream({
                        "type": "metric", "engine": "serve",
                        "t": int(t_), "cost": float(a),
                        "backlog": float(b_), "admitted": float(c),
                        "rejected": float(d), "served": float(e_),
                        "slo_viol": int(g),
                    })
                else:
                    stream({
                        "type": "event", "engine": "serve",
                        "code": "recovery", "t": int(t_),
                        "drained": float(a), "pod": int(b_),
                        "n_died": int(c),
                    })

            @jax.jit
            def emit(kind_, t_, a, b_, c, d, e_, g):
                io_callback(_host_emit, None, kind_, t_, a, b_, c, d, e_, g,
                            ordered=True)

        q = jnp.zeros((n, k, s_max), jnp.float32)
        hist_on = self._hist_on
        hedging = spec_of(self.policy).returns == "flow_hedge"
        if hist_on:
            # Per-class FIFO sojourn clock: the age ring is bounded by the
            # horizon (no request can wait longer than the run).
            age, soj_hist = sojourn_init(self.telemetry.hist, k, t_slots)
        f_slots, in_slots, done_slots = [], [], []
        g_slots, acc_slots = [], []
        history: list[dict] = []
        events: list[dict] = []
        backlogs = []
        exec_secs, exec_jobs = 0.0, 0
        served_np = np.zeros((t_slots, k))
        completed_np = np.zeros((t_slots, k))

        for t in range(t_slots):
            args = (
                q, inputs.arrivals[t], inputs.mu[t], e_cost_all[t],
                mu_stage_all[t], inputs.data_dist, wpue_all[t], v,
            )
            if faulty:
                args = args + (
                    jnp.asarray(self.alive[t]), jnp.asarray(died_np[t]),
                )
            if hist_on:
                args = args + (age, soj_hist)
            res = self._step(*args)
            if hist_on:
                res, (age, soj_hist) = res[:-2], res[-2:]
            q, f, acc, in_stack, done, drained = res[:6]
            if hedging:
                g_slots.append(res[6])
                acc_slots.append(acc)
            f_slots.append(f)
            in_slots.append(in_stack)
            done_slots.append(done)

            done_np = np.asarray(done)                             # (N, K, S)
            served_k = (done_np * compute_np[None]).sum(axis=(0, 2))
            completed_k = done_np[:, :, -1].sum(axis=0)
            served_np[t] = served_k
            completed_np[t] = completed_k
            energy_j = served_k * e_per_job                        # SERVED-priced
            q_np = np.asarray(q)
            q_class = q_np.sum(axis=(0, 2))                        # (K,)
            slo_viol = q_class > fcfg.slo_backlog
            backlogs.append(float(q_np.sum()))

            rec = {
                "t": t,
                # Manager pod per class: where the decode (response) stage
                # landed this slot.
                "choice": np.argmax(np.asarray(f)[:, :, -1], axis=0).tolist(),
                "q_pod": q_np.sum(axis=(1, 2)).tolist(),
                "energy_j": energy_j.tolist(),
                "admitted": admitted_np[t].tolist(),
                "rejected": rejected_np[t].tolist(),
                "served": served_k.tolist(),
                "completed": completed_k.tolist(),
                "slo_viol": slo_viol.tolist(),
            }
            if faulty and died_np[t].sum() > 0.5:
                ev = {
                    "type": "event", "code": "recovery", "t": t,
                    "pod": int(np.argmax(died_np[t])),
                    "n_died": int(died_np[t].sum()),
                    "drained": float(drained),
                }
                events.append(ev)
                rec["recovery"] = ev
            history.append(rec)

            if execute_real:
                for ki, rc in enumerate(self.classes):
                    njobs = int(round(completed_k[ki]))
                    if fcfg.exec_cap is not None:
                        njobs = min(njobs, fcfg.exec_cap)
                    ndone, secs = self._execute_jobs(rc, njobs)
                    exec_secs += secs
                    exec_jobs += ndone
            if emit is not None:
                cost_t = jnp.sum(
                    (f * in_stack[None]) * ec_stage_all[t].transpose(2, 0, 1)
                )
                emit(jnp.int32(0), jnp.int32(t), cost_t,
                     jnp.float32(backlogs[-1]),
                     jnp.float32(admitted_np[t].sum()),
                     jnp.float32(rejected_np[t].sum()),
                     jnp.float32(served_k.sum()),
                     jnp.int32(int(slo_viol.sum())))
                if faulty and died_np[t].sum() > 0.5:
                    emit(jnp.int32(1), jnp.int32(t), drained,
                         jnp.float32(np.argmax(died_np[t])),
                         jnp.float32(died_np[t].sum()),
                         jnp.float32(0), jnp.float32(0), jnp.int32(0))

        # Post-run billing: the simulator's own batched expressions over
        # the stacked per-slot outputs — identical reduction order to
        # simulate_staged's post-scan block, so a dispatch-only run's cost
        # series replays the one simulate_staged reports on this scenario.
        f_trace = jnp.stack(f_slots)                               # (T,N,K,S)
        in_all = jnp.stack(in_slots)                               # (T,K,S)
        done_all = jnp.stack(done_slots)                           # (T,N,K,S)
        fa_all = f_trace * in_all[:, None]
        cost = jnp.sum(fa_all * ec_stage_all.transpose(0, 3, 1, 2),
                       axis=(1, 2, 3))                             # (T,)
        dd_all = jnp.broadcast_to(inputs.data_dist, (t_slots, k, n))
        src_all, dst_all, vol_all = staged_shuffle_mixes(
            f_trace, in_all, done_all, dd_all, dag
        )
        wan_c, wan_e, wan_gb = plan_cost(
            src_all.reshape(t_slots, s_max * k, n),
            dst_all.reshape(t_slots, s_max * k, n),
            vol_all.reshape(t_slots, s_max * k),
            scn.wan, inputs.omega, inputs.pue,
        )
        if self.link_health is not None:
            # Degraded-link premium on the KV-handoff traffic — the same
            # additive surcharge simulate_staged applies (exact zero on
            # an all-nominal trace, so the replay pin survives).
            sur_c, sur_e = degraded_surcharge(
                src_all.reshape(t_slots, s_max * k, n),
                dst_all.reshape(t_slots, s_max * k, n),
                vol_all.reshape(t_slots, s_max * k),
                scn.wan, inputs.omega, inputs.pue,
                jnp.asarray(self.link_health),
            )
            wan_c = wan_c + sur_c
            wan_e = wan_e + sur_e
        if hedging:
            # The honest speculation bill, identical to simulate_staged's
            # post-scan block: boost-attributable completions billed at
            # the clone pod's stage energy plus the expected KV pull.
            g_all = jnp.stack(g_slots)                         # (T,N,K,S)
            acc_all = jnp.stack(acc_slots)                     # (T,N,K,S)
            mu_used = mu_stage_all
            if faulty:
                mu_used = mu_used * jnp.asarray(
                    self.alive
                )[:, :, None, None]
            boost_all = jnp.sum(g_all * mu_used, axis=1)       # (T,K,S)
            mu_eff_all = mu_used + f_trace * boost_all[:, None]
            hedge_c, hedge_gb, hedged_jobs = _hedge_bill(
                dag, scn.wan, g_all, acc_all, mu_used, mu_eff_all,
                ec_stage_all, src_all, wpue_all,
            )
        else:
            hedge_c = hedge_gb = hedged_jobs = jnp.zeros(
                (t_slots,), jnp.float32
            )
        hedge_costs = np.asarray(hedge_c)
        hedged_np = np.asarray(hedged_jobs)
        for t, h in enumerate(history):
            h["hedged_jobs"] = float(hedged_np[t])
        costs = np.asarray(cost)
        wan_costs = np.asarray(wan_c)
        slo_viol_frac = np.mean(
            [h["slo_viol"] for h in history], axis=0
        )

        out_tel = {}
        if hist_on:
            spec = self.telemetry.hist
            counts = np.asarray(soj_hist)                          # (K, B)
            out_tel = {
                "sojourn_hist": counts,
                "sojourn_spec": dataclasses.asdict(spec),
                "class_names": [rc.name for rc in self.classes],
                "sojourn_percentiles": percentile_table(
                    counts, spec, names=[rc.name for rc in self.classes]
                ),
            }

        return {
            **out_tel,
            "cost": costs,
            "backlog": np.asarray(backlogs),
            "dispatch": np.asarray(f_trace),
            "exec_seconds": exec_secs,
            "exec_jobs": exec_jobs,
            "mean_cost": float(np.mean(costs)),
            "final_backlog": backlogs[-1],
            "history": history,
            "q_final": np.asarray(q),
            "wan_cost": wan_costs,
            "wan_gb": np.asarray(wan_gb),
            "wan_energy": np.asarray(wan_e),
            "hedge_cost": hedge_costs,
            "hedge_gb": np.asarray(hedge_gb),
            "hedged_jobs": hedged_np,
            "total_billed_cost": float(
                costs.sum() + wan_costs.sum() + hedge_costs.sum()
            ),
            "raw_arrivals": np.asarray(scn.raw_arrivals),
            "admitted": admitted_np,
            "rejected": rejected_np,
            "served": served_np,
            "completed": completed_np,
            "slo_viol_frac": slo_viol_frac,
            "events": events,
        }
