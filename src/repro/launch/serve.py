"""Serving launcher: the simulation-dispatched fleet engine on real models.

  PYTHONPATH=src python -m repro.launch.serve --slots 24 --v 1.0 \
      [--classes qwen2-0.5b,granite-3-2b] [--no-exec] [--pods 8] \
      [--admit-max 6] [--kill "2:12"] [--dispatch kernel] [--variant full]

Each request class is an architecture (executed at its smoke variant
unless ``--variant full``) modeled as a 2-stage prefill→decode chain; prefill routes through the
placement layer's replica-read assignment over a drawn dataset layout,
every slot dispatches through the joint stage scheduler (or the Pallas
kernel path with ``--dispatch kernel``), and drained jobs actually
execute prefill+decode. ``--kill pod:slot`` injects a pod death — the
recovery drain shows up in the history/telemetry stream.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_arch
from repro.core.iridium import build_task_allocation
from repro.launch.cache import enable_compile_cache
from repro.serve.engine import FleetConfig, FleetEngine, RequestClass
from repro.telemetry.config import TelemetryConfig
from repro.traces.bandwidth import bandwidth_draw
from repro.traces.datasets import dataset_distribution
from repro.traces.price import FACEBOOK_SITES, price_trace
from repro.traces.pue import pue_trace


def build_engine(classes: list[str], slots: int, v: float, seed: int = 0,
                 arrival: float = 6.0, n_pods: int = 4,
                 admit_max: float | None = None, dispatch: str = "staged",
                 alive: np.ndarray | None = None,
                 telemetry: TelemetryConfig | None = None,
                 health: np.ndarray | None = None,
                 link_health: np.ndarray | None = None,
                 hedge: float | None = None,
                 variant: str = "smoke") -> FleetEngine:
    """Build a serving engine; ``variant`` is the executed model size
    (``"smoke"`` or ``"full"``). Energy is always priced at full size."""
    key = jax.random.key(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # Pods beyond the four Facebook DCs reuse their site climates (cycled).
    sites = tuple(FACEBOOK_SITES[i % len(FACEBOOK_SITES)]
                  for i in range(n_pods))
    omega = np.asarray(price_trace(k1, slots, 5.0, sites))
    pue = np.asarray(pue_trace(k2, slots, 5.0, sites))
    rcs = [
        RequestClass(name=a, cfg=get_arch(a, variant),
                     energy_cfg=get_arch(a, "full"), arrival_rate=arrival)
        for a in classes
    ]
    # The dataset layout doubles as the KV-prefix placement the replica-
    # read router serves prefill from; the same draw feeds the task-
    # allocation ratios, so dispatch pricing and routing share one world.
    layout = dataset_distribution(k3, len(rcs), n_pods)
    up, down = bandwidth_draw(k4, n_pods)
    r = np.asarray(build_task_allocation(layout, up, down, manager_share=0.62))
    fcfg = FleetConfig(
        n_pods=n_pods, horizon_slots=slots, v=v, seed=seed,
        admit_max=admit_max, dispatch=dispatch, hedge_threshold=hedge,
    )
    return FleetEngine(
        fcfg, rcs, omega, pue, r,
        up=up, down=down, layout=layout, alive=alive, telemetry=telemetry,
        health=health, link_health=link_health,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--classes", default="qwen2-0.5b,granite-3-2b")
    ap.add_argument("--slots", type=int, default=24)
    ap.add_argument("--v", type=float, default=1.0)
    ap.add_argument("--arrival", type=float, default=6.0)
    ap.add_argument("--pods", type=int, default=4)
    ap.add_argument("--admit-max", type=float, default=None,
                    help="per-class per-slot admission cap (default: admit all)")
    ap.add_argument("--dispatch", choices=["staged", "kernel"],
                    default="staged")
    ap.add_argument("--kill", default=None, metavar="POD:SLOT",
                    help="kill pod POD at slot SLOT (recovery drain demo)")
    ap.add_argument("--straggle", default=None, metavar="POD:SLOT:FACTOR",
                    help="degrade pod POD to FACTOR of its service rate "
                         "from slot SLOT on (straggler demo)")
    ap.add_argument("--hedge", type=float, default=None,
                    help="speculative re-execution threshold (clone a "
                         "stage when its pod's rate falls below this "
                         "fraction of the runner-up's)")
    ap.add_argument("--no-exec", action="store_true",
                    help="skip real model execution (dispatch-only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variant", choices=["smoke", "full"], default="smoke",
                    help="model size the pods execute")
    args = ap.parse_args(argv)
    enable_compile_cache()

    alive = None
    if args.kill:
        pod, slot = (int(x) for x in args.kill.split(":"))
        alive = np.ones((args.slots, args.pods), np.float32)
        alive[slot:, pod] = 0.0
    health = None
    if args.straggle:
        pod, slot, factor = args.straggle.split(":")
        health = np.ones((args.slots, args.pods), np.float32)
        health[int(slot):, int(pod)] = float(factor)

    engine = build_engine(
        args.classes.split(","), args.slots, args.v, args.seed, args.arrival,
        n_pods=args.pods, admit_max=args.admit_max, dispatch=args.dispatch,
        alive=alive, health=health, hedge=args.hedge, variant=args.variant,
    )
    out = engine.run(execute_real=not args.no_exec)
    print(f"slots={args.slots} classes={args.classes} pods={args.pods} "
          f"dispatch={args.dispatch}")
    print(f"mean slot cost      : {out['mean_cost']:.3e} $ "
          f"({out['mean_cost']*1e6:.3f} µ$)")
    print(f"KV-handoff WAN bill : {out['wan_cost'].sum():.3e} $ "
          f"({out['wan_gb'].sum():.2f} GB)")
    if args.hedge is not None:
        print(f"hedge bill          : {out['hedge_cost'].sum():.3e} $ "
              f"({out['hedged_jobs'].sum():.2f} jobs re-executed)")
    print(f"total billed        : {out['total_billed_cost']:.3e} $")
    print(f"final total backlog : {out['final_backlog']:.1f}")
    print(f"admitted/rejected   : {out['admitted'].sum():.0f} / "
          f"{out['rejected'].sum():.0f}")
    print(f"SLO violation frac  : {np.round(out['slo_viol_frac'], 3)}")
    print(f"model-exec seconds  : {out['exec_seconds']:.1f} "
          f"({out['exec_jobs']} jobs)")
    share = out["dispatch"].mean(axis=0).sum(axis=(1, 2))
    print("dispatch share/pod  :", np.round(share / share.sum(), 3))
    for ev in out["events"]:
        print(f"recovery event      : pod {ev['pod']} died at t={ev['t']}, "
              f"drained {ev['drained']:.1f} jobs")
    return out


if __name__ == "__main__":
    main()
