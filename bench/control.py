#!/usr/bin/env python3
"""Readings that set a cell's limits: the program and the control, per seed.

    python3 bench/control.py --workload <name> --seeds 11 12 13 ... [--calls 1]

For each seed, ``--calls`` call indices are drawn from the seed, as a run
draws the calls it compares. Each call is made once through the program's
timed path (the cell's compiled call) and compared with the plain
reference, which gives the lower readings; the control, the reference with
its contractions at ``high`` (three bfloat16 passes) in the program's
place, is compared with the same reference, which gives the upper
readings; each is judged by the reference's replay of its own decisions.
Everything runs in this one process: the program on the chip, reference
and control on the host. One JSON line per call, then a summary
line with the largest program reading and the smallest control reading of
each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def readings(spec: dict, seeds: list, calls: int, require_chip: bool = True,
             log=print) -> dict:
    import jax
    import numpy as np

    import cell as cell_mod
    import compare
    import reference

    w = spec["workload"]
    devs, _ = run.devices_for(int(w["chips"]), require_chip)
    if str(run.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(run.ROOT / "src"))
    c = cell_mod.build_cell(w["name"], spec["config"], spec["traffic"], chips=len(devs))
    cfg = spec["config"]
    scen = reference.scenario(cfg)
    t_slots = cfg["fields"]["t_slots"]
    prog, ctl = [], []
    for seed in seeds:
        base = jax.device_put(cell_mod.seed_key(seed), c.home)
        rng = np.random.default_rng(seed)
        rows = np.sort(rng.choice(c.n_runs, size=min(run.SLOT_ROWS, c.n_runs),
                                  replace=False))
        rows_d = jax.device_put(rows.astype(np.int32), c.home)
        for idx in sorted(rng.choice(np.arange(1, 201), size=calls, replace=False)):
            t0 = time.perf_counter()
            ans, dig = c.call(base, np.int32(idx), rows_d)
            ans, dig = np.asarray(ans), jax.device_get(dig)
            t1 = time.perf_counter()
            drawn = reference.draws(scen, reference.call_key(seed, int(idx)),
                                    c.n_runs, t_slots, rows=rows)
            p = compare.judge(cfg, spec["traffic"], scen, drawn, dig)
            alt = reference.evaluate(cfg, spec["traffic"], scen, *drawn[:2], "high")
            q = compare.judge(cfg, spec["traffic"], scen, drawn, alt)
            t2 = time.perf_counter()
            prog.append(p)
            ctl.append(q)
            log(json.dumps({"seed": seed, "index": int(idx), "program": p, "control": q,
                            "program_s": t1 - t0, "reference_and_control_s": t2 - t1}))
    summary = {k: {"program_max": max(r[k] for r in prog),
                   "control_min": min(r[k] for r in ctl)} for k in prog[0]}
    log(json.dumps({"workload": w["name"], "seeds": list(seeds), "calls": calls,
                    "summary": summary}))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=int, nargs="+")
    ap.add_argument("--calls", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        spec = run.load_spec(args.workload)
        run.configure_jax()
        readings(spec, args.seeds, args.calls, log=lambda s: print(s, flush=True))
    except run.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
