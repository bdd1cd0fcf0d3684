#!/usr/bin/env python3
"""Bring-up check: drive the main paths once on one TPU chip.

    python chip_smoke.py                # one chip, five phases
    python chip_smoke.py --four-chips   # the runs-axis mesh on four chips

Everything runs in this one process, which holds the chip; the reference
runs it compares against use the CPU device of the same process. Each phase
prints one line: what it checked, the numbers it compared, and its first
(compile + run) and steady seconds, which are information only. The first
failed check ends the script with a non-zero exit code. The last line of a
passing run is ``{"ok": true, "device": {...}}``. Without a TPU the script
stops before any phase runs.

Phases (one chip):

1. device and cache — a TPU is device 0; the persistent compile cache is on.
2. kernels — ``gmsa_score`` compiled at the ``fleet_256`` widths (K=8,
   N=256) and ``ssd_scan`` compiled at the mamba2-2.7b layer geometry,
   each against its float32 oracle on the chip.
3. paper — ``simulate_many`` on Facebook-4DC (T=288, 1000 runs) under
   GMSA V=1 and DATA; GMSA must be cheaper; 32 runs agree with the CPU.
4. fleet — ``simulate`` on ``fleet_256`` through the reference policy and
   the compiled-kernel policy (dispatch agreement, cost, kernel in HLO).
5. serve — ``FleetEngine`` executing qwen2-0.5b at full width on 4 pods;
   request conservation, and first-token logits against the CPU.

``--four-chips`` runs only ``simulate_many`` and ``simulate_placed_many``
(with a mid-trace site loss) sharded over a 4-chip runs mesh, at 1000 and
1001 runs, against the single-device vmap on the same keys.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The reference runs need the CPU backend next to the TPU. A platform list
# that names neither (or only the CPU) is left alone, so that a machine
# without a chip still fails the device check below.
_plat = os.environ.get("JAX_PLATFORMS", "")
if "tpu" in _plat.split(",") and "cpu" not in _plat.split(","):
    os.environ["JAX_PLATFORMS"] = _plat + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Gates. Kernel tolerances are those of tests/test_kernels.py (float32).
GMSA_TOL = 1e-4
SSD_TOL = 3e-4
NEAR_TIE = 1e-2            # relative score gap under which argmins may differ
PAPER_RUNS = 1000
PAPER_CPU_RUNS = 32
PAPER_CPU_RTOL = 1e-3      # 32-run mean cost / backlog, chip vs CPU
PAPER_CPU_AGREE = 0.999    # dispatch decisions equal, chip vs CPU
FLEET_AGREE = 0.999        # kernel vs reference policy (kernel_bench gate)
FLEET_COST_RTOL = 1e-3
SERVE_SLOTS = 6
SERVE_LOGIT_RTOL = 5e-2    # max |Δlogit| / max |logit|, chip vs CPU
MESH_RTOL = 1e-5           # per-run means, sharded vs single-device


class CheckFailed(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def timed(fn, *args):
    """(output, first-call seconds, steady seconds) of ``fn(*args)``."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.perf_counter() - t1


def secs(first: float, steady: float) -> str:
    return f"first {first:.2f} s, steady {steady:.3f} s"


def _ratio(x, ref, tol: float) -> float:
    """max |x - ref| / (tol + tol·|ref|): allclose(rtol=atol=tol) iff <= 1."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref) / (tol + tol * np.abs(ref))))


def cpu_device():
    return jax.devices("cpu")[0]


# ---------------------------------------------------------------------------
# 1. device and cache
# ---------------------------------------------------------------------------

def phase_device() -> str:
    from repro.launch.cache import enable_compile_cache

    dev = jax.devices()[0]
    check(dev.platform == "tpu", f"device 0 is {dev.platform}, not a TPU")
    path = enable_compile_cache()
    return (f"platform={dev.platform} kind={dev.device_kind} "
            f"count={len(jax.devices())} compile cache={path}")


# ---------------------------------------------------------------------------
# 2. kernels, compiled
# ---------------------------------------------------------------------------

def phase_kernels() -> str:
    from repro.configs.fleet_256 import FleetConfig, make_score_operands
    from repro.kernels.gmsa_score import gmsa_score, gmsa_score_ref
    from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref

    q, mu, a, vp, r, wpue, _ = make_score_operands(FleetConfig())
    k, n = q.shape
    (s, best), f1, s1 = timed(
        jax.jit(lambda *x: gmsa_score(*x, interpret=False)),
        q, mu, a, vp, r, wpue)
    s_ref, best_ref = jax.jit(gmsa_score_ref)(q, mu, a, vp, r, wpue)
    s, s_ref = np.asarray(s), np.asarray(s_ref)
    np.testing.assert_allclose(s, s_ref, rtol=GMSA_TOL, atol=GMSA_TOL)
    gap = np.partition(s_ref.astype(np.float64), 1, axis=1)
    near_tie = (gap[:, 1] - gap[:, 0]) < NEAR_TIE * np.abs(gap[:, 0])
    agree = np.asarray(best) == np.asarray(best_ref)
    check(bool(np.all(agree | near_tie)), f"gmsa_score argmin differs: "
          f"{agree.tolist()} near ties {near_tie.tolist()}")
    # Ties or not, the kernel's pick must score the true row minimum.
    picked = s_ref[np.arange(k), np.asarray(best)]
    np.testing.assert_allclose(picked, s_ref.min(axis=1), rtol=1e-5,
                               atol=GMSA_TOL)
    line = (f"gmsa_score K={k} N={n}: allclose ratio "
            f"{_ratio(s, s_ref, GMSA_TOL):.3e} (pass <= 1 at rtol=atol="
            f"{GMSA_TOL:g}), argmin equal {int(agree.sum())}/{k} (near ties "
            f"{int(near_tie.sum())}), picked score = row min, {secs(f1, s1)}")

    b, sl, h, p, nn = 1, 1024, 80, 64, 128          # mamba2-2.7b layer
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (b, sl, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, sl, h)))
    a_h = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, sl, nn))
    cm = jax.random.normal(ks[4], (b, sl, nn))
    (y, hf), f2, s2 = timed(
        jax.jit(lambda *x_: ssd_scan(*x_, chunk=128, interpret=False)),
        x, dt, a_h, bm, cm)
    y_ref, h_ref = jax.jit(ssd_scan_ref)(x, dt, a_h, bm, cm)
    # The test tolerance, with atol in units of the output's scale: here
    # |y| reaches hundreds, and float32 cancellation between the chunked
    # and the sequential forms leaves ~1e-6 of that scale even on a CPU.
    ratios = []
    for got, ref in ((y, y_ref), (hf, h_ref)):
        scale = float(jnp.max(jnp.abs(ref)))
        ratios.append(_ratio(np.asarray(got) / scale, np.asarray(ref) / scale,
                             SSD_TOL))
        check(ratios[-1] <= 1.0, f"ssd_scan differs from ssd_scan_ref: "
              f"scaled allclose ratio {ratios[-1]:.3e}")
    return (f"{line}; ssd_scan B={b} S={sl} H={h} P={p} N={nn} chunk=128: "
            f"scaled allclose ratio y {ratios[0]:.3e}, h {ratios[1]:.3e} "
            f"(pass <= 1 at rtol=atol={SSD_TOL:g} of max|ref|), unscaled y "
            f"{_ratio(y, y_ref, SSD_TOL):.3e}, {secs(f2, s2)}")


# ---------------------------------------------------------------------------
# 3. paper scenario
# ---------------------------------------------------------------------------

def _paper_arms():
    from repro.core.baselines import data_dispatch
    from repro.core.gmsa import dispatch_fn

    return [("GMSA V=1", dispatch_fn(1.0)), ("DATA", data_dispatch)]


def phase_paper() -> str:
    from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
    from repro.core.simulator import simulate_many, summarize

    cfg = PaperSimConfig()
    _, build = make_sim_builder(cfg)
    key = jax.random.key(0)
    costs, parts = {}, []
    for name, pol in _paper_arms():
        outs, f1, s1 = timed(
            lambda: simulate_many(build, pol, key, PAPER_RUNS))
        summ = summarize(outs)
        check(all(np.isfinite(v) for v in summ.values()),
              f"{name}: non-finite summary {summ}")
        costs[name] = summ["time_avg_cost"]
        parts.append(f"{name} cost {summ['time_avg_cost']:.4f} backlog "
                     f"{summ['time_avg_backlog']:.4f} ({secs(f1, s1)})")
    check(costs["GMSA V=1"] < costs["DATA"],
          f"GMSA is not cheaper than DATA: {costs}")

    # The same 32-run call on the chip and on the CPU device.
    pol = _paper_arms()[0][1]
    tpu_out = simulate_many(build, pol, key, PAPER_CPU_RUNS)
    with jax.default_device(cpu_device()):
        _, build_c = make_sim_builder(cfg)
        cpu_out = simulate_many(build_c, pol, jax.random.key(0),
                                PAPER_CPU_RUNS)
    agree = float(np.mean(np.asarray(tpu_out.f_trace)
                          == np.asarray(cpu_out.f_trace)))
    rel = {}
    for field in ("cost", "backlog_avg"):
        a_t = float(np.mean(np.asarray(getattr(tpu_out, field), np.float64)))
        a_c = float(np.mean(np.asarray(getattr(cpu_out, field), np.float64)))
        rel[field] = abs(a_t - a_c) / max(abs(a_c), 1e-12)
    line = (f"T={cfg.t_slots} runs={PAPER_RUNS}: " + "; ".join(parts)
            + f"; chip vs CPU ({PAPER_CPU_RUNS} runs, GMSA): dispatch equal "
            f"{agree:.6f} (gate {PAPER_CPU_AGREE}), mean cost rel "
            f"{rel['cost']:.3e}, mean backlog rel {rel['backlog_avg']:.3e} "
            f"(tol {PAPER_CPU_RTOL:g})")
    check(agree >= PAPER_CPU_AGREE and max(rel.values()) <= PAPER_CPU_RTOL,
          f"paper scenario differs from the CPU: {line}")
    return line


# ---------------------------------------------------------------------------
# 4. fleet
# ---------------------------------------------------------------------------

def phase_fleet() -> str:
    from repro.configs.fleet_256 import FleetConfig, make_fleet_builder
    from repro.core.gmsa import gmsa_policy, make_kernel_policy
    from repro.core.simulator import simulate

    cfg = FleetConfig()
    template, _ = make_fleet_builder(cfg)
    key = jax.random.key(0)
    o_ref, f1, s1 = timed(lambda: simulate(template, gmsa_policy, key, cfg.v))
    pol_k = make_kernel_policy(template.r, template.p_it, interpret=False)
    hlo = simulate.lower(template, pol_k, key, cfg.v).compile().as_text()
    check("tpu_custom_call" in hlo,
          "no tpu_custom_call in the kernel policy's compiled simulate")
    o_k, f2, s2 = timed(lambda: simulate(template, pol_k, key, cfg.v))
    agree = float(np.mean(np.asarray(o_k.f_trace) == np.asarray(o_ref.f_trace)))
    c_ref = float(np.mean(np.asarray(o_ref.cost, np.float64)))
    c_k = float(np.mean(np.asarray(o_k.cost, np.float64)))
    cost_rel = abs(c_k - c_ref) / max(abs(c_ref), 1e-12)
    line = (f"N={cfg.n_sites} K={cfg.k_types} T={cfg.t_slots}: "
            f"tpu_custom_call in HLO, dispatch agreement {agree:.6f} "
            f"(gate > {FLEET_AGREE}), cost rel err {cost_rel:.3e} "
            f"(gate < {FLEET_COST_RTOL:g}); reference {secs(f1, s1)}, "
            f"kernel {secs(f2, s2)}")
    check(agree > FLEET_AGREE and cost_rel < FLEET_COST_RTOL,
          f"kernel policy disagrees with the reference: {line}")
    return line


# ---------------------------------------------------------------------------
# 5. serving at full width
# ---------------------------------------------------------------------------

def phase_serve() -> str:
    from repro.launch.serve import build_engine
    from repro.serve.step import make_local_exec

    engine = build_engine(["qwen2-0.5b"], slots=SERVE_SLOTS, v=1.0, seed=0,
                          n_pods=4, variant="full")
    rc = engine.classes[0]
    t0 = time.perf_counter()
    out = engine.run(execute_real=True)
    first = time.perf_counter() - t0
    check(out["exec_jobs"] > 0, "no request was executed")
    adm = out["admitted"].sum(axis=0)
    done = out["completed"].sum(axis=0)
    backlog = out["q_final"].sum(axis=(0, 2))
    gap = float(np.max(np.abs(adm - (done + backlog))))
    check(gap <= 1e-3, f"conservation: admitted {adm} != completed {done} "
          f"+ backlog {backlog}")

    params = engine.params[rc.name]
    prefill, _ = make_local_exec(rc.cfg, rc.gen_len)
    tokens = jax.random.randint(jax.random.key(7), (1, rc.prompt_len), 0,
                                rc.cfg.vocab_size, dtype=jnp.int32)
    vocab = rc.cfg.vocab_size
    chip = np.asarray(prefill(params, tokens)[0][0, -1, :vocab])
    cpu = cpu_device()
    host = np.asarray(prefill(jax.device_put(params, cpu),
                              jax.device_put(tokens, cpu))[0][0, -1, :vocab])
    d_logit = float(np.max(np.abs(chip - host)) / np.max(np.abs(host)))
    line = (f"qwen2-0.5b full width ({rc.cfg.num_layers} layers, d_model "
            f"{rc.cfg.d_model}, vocab {vocab}), 4 pods, {SERVE_SLOTS} slots: "
            f"exec_jobs {out['exec_jobs']}, admitted {float(adm.sum()):.3f} = "
            f"completed {float(done.sum()):.3f} + backlog "
            f"{float(backlog.sum()):.3f} (gap {gap:.2e}); first-token logits "
            f"chip vs CPU max |Δ|/max|logit| {d_logit:.3e} (tol "
            f"{SERVE_LOGIT_RTOL:g}), argmax equal "
            f"{int(np.argmax(chip)) == int(np.argmax(host))}; run "
            f"{first:.2f} s incl. compile, model exec "
            f"{out['exec_seconds']:.3f} s")
    check(d_logit <= SERVE_LOGIT_RTOL, f"logits differ from the CPU: {line}")
    return line


# ---------------------------------------------------------------------------
# --four-chips: the runs axis sharded over a 4-chip mesh
# ---------------------------------------------------------------------------

def _compare_sharded(ref, out, n_runs: int) -> tuple[bool, float]:
    leaves_r = jax.tree_util.tree_leaves(ref)
    leaves_o = jax.tree_util.tree_leaves(out)
    check(all(x.shape[0] == n_runs for x in leaves_o),
          f"sharded outputs do not keep {n_runs} rows")
    bitwise = all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
                  for x, y in zip(leaves_r, leaves_o))
    worst = 0.0
    for x, y in zip(leaves_r, leaves_o):
        x = np.asarray(x, np.float64).reshape(n_runs, -1).mean(axis=1)
        y = np.asarray(y, np.float64).reshape(n_runs, -1).mean(axis=1)
        finite = np.isfinite(x) & np.isfinite(y)
        check(bool(np.array_equal(np.isfinite(x), np.isfinite(y))),
              "sharded and single-device outputs differ in finiteness")
        den = np.maximum(np.abs(x[finite]), 1e-12)
        if finite.any():
            worst = max(worst, float(np.max(np.abs(x - y)[finite] / den)))
    return bitwise, worst


def phase_four_chips() -> str:
    from repro.configs.facebook_4dc import PaperSimConfig, make_sim_builder
    from repro.core.gmsa import gmsa_policy
    from repro.core.simulator import simulate_many
    from repro.distributed.mesh import runs_mesh
    from repro.placement import PlacementConfig, make_adaptive_rule
    from repro.placement.controller import simulate_placed_many
    from repro.traces.bandwidth import bandwidth_draw
    from repro.traces.faults import scheduled_failure_trace

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    mesh = runs_mesh()
    cfg = PaperSimConfig()
    _, build = make_sim_builder(cfg)
    key = jax.random.key(0)
    up, down = bandwidth_draw(
        jax.random.split(jax.random.key(cfg.trace_seed), 6)[2], cfg.n_sites)
    rule = make_adaptive_rule(up)
    pcfg = PlacementConfig(epoch_slots=24, manager_share=cfg.manager_share)
    alive = jnp.asarray(scheduled_failure_trace(
        cfg.t_slots, cfg.n_sites, [(2, cfg.t_slots // 2, None)]))

    parts = []
    for name, call in [
        ("simulate_many", lambda n, m: simulate_many(
            build, gmsa_policy, key, n, 1.0, mesh=m)),
        ("simulate_placed_many", lambda n, m: simulate_placed_many(
            build, up, down, gmsa_policy, rule, key, n, pcfg, 1.0,
            alive=alive, mesh=m)),
    ]:
        for n_runs in (PAPER_RUNS, PAPER_RUNS + 1):
            ref, f1, s1 = timed(lambda: call(n_runs, None))
            out, f2, s2 = timed(lambda: call(n_runs, mesh))
            if name == "simulate_placed_many":
                check(float(out.recovery_gb.sum()) > 0.0,
                      "the site loss fired no recovery")
            bitwise, worst = _compare_sharded(ref, out, n_runs)
            check(bitwise or worst <= MESH_RTOL,
                  f"{name} n_runs={n_runs}: sharded differs from vmap by "
                  f"{worst:.3e} (tol {MESH_RTOL:g})")
            parts.append(f"{name} n_runs={n_runs}: bitwise {bitwise}, "
                         f"max per-run rel {worst:.3e}; vmap {secs(f1, s1)}, "
                         f"mesh {secs(f2, s2)}")
    return (f"runs mesh over {mesh.shape['runs']} chips, site 2 lost at slot "
            f"{cfg.t_slots // 2}: " + "; ".join(parts))


# ---------------------------------------------------------------------------

PHASES = [
    ("device", phase_device),
    ("kernels", phase_kernels),
    ("paper", phase_paper),
    ("fleet", phase_fleet),
    ("serve", phase_serve),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the runs-axis mesh phase on four chips")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: no src/repro next to {__file__}; run it from "
                 "a checkout of the repository")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
                 "nothing was run")
    phases = PHASES
    if args.four_chips:
        phases = [("device", phase_device), ("four-chips", phase_four_chips)]
    for name, fn in phases:
        t0 = time.perf_counter()
        line = fn()
        print(f"[{name}] {line} [{time.perf_counter() - t0:.1f} s]",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
