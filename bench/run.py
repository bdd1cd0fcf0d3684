#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``: a configuration file under
``bench/configs`` and a traffic file under ``bench/traffic``, both found by
name; its comparison limits are ``bench/limits/<cell>.json`` and each
per-layer metric is read by ``bench/metrics/<metric>.py`` (``plugins.py``
lists the pieces found by name).

Traffic is one closed-loop caller with one call in flight. A call runs the
cell's Monte-Carlo entry point once with the key ``fold_in(key(seed), i)``
and fetches the time-average cost and backlog to the host; it is timed from
submit to those scalars on the host. Set-up (process start, building the
scenario, and one warm-up call, which compiles or loads every program from
the persistent cache) is ``setup_s``. Then the window runs calls for
``--seconds``:

* ``--trace 0`` reports the end-to-end metrics: ``runs_per_s`` (runs of all
  calls in the window over its length), ``call_p95_ms`` and ``setup_s``;
* ``--trace 1`` runs the first ``TRACE_SECONDS`` of the window (at least
  two calls) under the profiler and reports the per-layer metrics,
  ``busy_s``/``window_s`` and the ``breakdown``.

The window holds the answer and digest of ``CHECK_CALLS`` of its calls,
picked while it runs by reservoir sampling from the seed, and drops every
other call's once its latency is recorded. The set-up heap is frozen out of
the collector's reach before the window opens. Standard error names the
collector's runs in the window and every call over ten medians, with the
process's CPU time and context switches across it (``host_watch.py``).
After the window the held calls are compared with the plain reference
(``bench/reference.py``); the numbers compared and their limits end standard
error and the result line, which is the last line of standard output.
Without an accelerator, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
#: Runs of each call whose every slot is compared (drawn from the seed).
SLOT_ROWS = 16
#: Calls of the window compared with the reference (drawn from the seed).
CHECK_CALLS = 1
#: Length of the traced part of a ``--trace 1`` window; it holds at least 2 calls.
TRACE_SECONDS = 2.0
sys.path.insert(0, str(BENCH))


class BenchError(RuntimeError):
    pass


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, files and metric lists, found by name."""
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    bj = json.loads(path.read_text())
    cells = {w["name"]: w for w in bj["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    bench = root / "bench"
    return {
        "workload": w,
        "config": json.loads((bench / "configs" / f"{w['config']}.json").read_text()),
        "traffic": json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads((bench / "limits" / f"{workload}.json").read_text())["numbers"],
        "end_to_end": [m for m in bj["end_to_end"] if mine(m)],
        "per_layer": [m for m in bj["per_layer"] if mine(m)],
    }


def configure_jax() -> None:
    """Compile cache and runtime logs inside the checkout, at fixed paths."""
    for sub in ("jax", "tpu_logs"):       # JAX writes no entry into a missing directory
        (CACHE / sub).mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_chip: bool):
    """The devices the cell runs on, and the peak table's row for them."""
    import jax

    devs = jax.devices()
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform == "cpu":
            raise BenchError("JAX found no accelerator (platform cpu); nothing was run")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
        if kind not in peaks:
            raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks.get(kind)


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if not self.active:
            return
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class Reservoir:
    """Holds ``k`` of a stream of unknown length (Algorithm R): once ``n``
    items have been offered, each is in ``held`` with probability ``k / n``.
    The draws come from ``rng`` alone, so the pick is fixed by the seed, and
    the program cannot know which call it is."""

    def __init__(self, k: int, rng):
        self.k, self.rng, self.seen, self.held = k, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.held) < self.k:
            self.held.append(item)
        else:
            j = int(self.rng.random() * self.seen)
            if j < self.k:
                self.held[j] = item


def _quantile(values: list, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, log=None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax
    import numpy as np

    import cell as cell_mod
    import compare
    import host_watch
    import plugins
    import reference
    from trace_reduce import layer_of, reduce_trace

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    w, traffic = spec["workload"], spec["traffic"]
    devs, peaks = devices_for(int(w["chips"]), require_chip)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no src/repro under {ROOT}: run from a checkout of the repository")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))

    counter = CompileCounter()
    c = cell_mod.build_cell(w["name"], spec["config"], traffic, chips=len(devs))
    base = jax.device_put(cell_mod.seed_key(seed), c.home)
    rng = np.random.default_rng(seed)
    rows_np = np.sort(rng.choice(c.n_runs, size=min(SLOT_ROWS, c.n_runs), replace=False))
    rows = jax.device_put(rows_np.astype(np.int32), c.home)
    pick = Reservoir(CHECK_CALLS, rng)          # drawn after rows_np: the rows stay
    span = jax.profiler.TraceAnnotation if trace else (lambda _n: contextlib.nullcontext())
    clock = host_watch.CallClocks()

    def one(i: int):
        with span("bench_key"):
            idx = np.int32(i)
        before = host_watch.clocks()
        t0 = time.perf_counter()
        with span("bench_submit"):
            ans, dig = c.call(base, idx, rows)
        with span("bench_fetch"):
            ans = np.asarray(ans)
        lat = time.perf_counter() - t0
        clock.record(lat, before)
        return ans, dig

    jax.block_until_ready(one(0))
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.6f} s (process start to the end of the warm-up call)")
    clock = host_watch.CallClocks()             # the window's calls alone

    tdir = CACHE / "trace" / w["name"]
    limit = min(TRACE_SECONDS, seconds) if trace else seconds
    # What set-up made (modules, traced and compiled programs) lives to the
    # end: the collector need not rescan it in the window.
    gc.collect()
    gc.freeze()
    try:
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(str(tdir))
        counter.active = True
        with host_watch.Collector() as collector:
            w0 = time.perf_counter()
            with span("bench_window"):
                i = 1
                while True:
                    pick.offer((i, *one(i)))    # (index, answer, digest)
                    i += 1
                    if time.perf_counter() - w0 >= limit and (not trace or i > 2):
                        break
            window = time.perf_counter() - w0
    finally:
        gc.unfreeze()
    counter.active = False
    if trace:
        jax.profiler.stop_trace()
    lats = clock.lat
    log(f"window {window:.6f} s: {len(lats)} calls of {c.n_runs} runs, call median "
        f"{_quantile(lats, 50) * 1e3:.6f} ms, p95 {_quantile(lats, 95) * 1e3:.6f} ms, "
        f"longest {max(lats) * 1e3:.6f} ms, all calls {sum(lats):.6f} s; "
        f"inside the window {counter.compiles} compiles, {counter.traces} traces")
    log(collector.report())
    for line in clock.slow():
        log(line)

    def peak_bytes() -> int | None:
        peaks_b = []
        for d in devs:
            try:
                st = d.memory_stats() or {}
            except Exception:               # backends without memory stats
                st = {}
            if "peak_bytes_in_use" in st:
                peaks_b.append(int(st["peak_bytes_in_use"]))
        return max(peaks_b) if peaks_b else None

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes()}
    metrics = {}
    result = {}
    if trace:
        tr = reduce_trace(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = sum(tr.busy_s) / len(tr.busy_s)
        device["window_s"] = tr.window_s
        log(f"the trace covers {tr.calls} of the window's {len(lats)} calls, "
            f"{tr.window_s:.6f} s")
        ctx = {"calls": tr.calls, "n_runs": c.n_runs, "chips": len(devs),
               "peaks": peaks, **c.shapes}
        for m in spec["per_layer"]:
            v = plugins.load("metrics", m["name"]).read(tr, ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(layer_of)
    else:
        e2e = {
            "runs_per_s": len(lats) * c.n_runs / window,
            "call_p95_ms": _quantile(lats, 95) * 1e3,
            "setup_s": setup_s,
        }
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # The comparison, once the window has closed and the peak is read.
    sample = [(i, a, jax.device_get(d)) for i, a, d in sorted(pick.held, key=lambda h: h[0])]
    del pick
    cfg = spec["config"]
    f = cfg["fields"]
    want = c.entry.shapes(c.n_runs, len(rows_np), f["t_slots"], f["k_types"])
    scen = reference.scenario(cfg)
    readings = []
    for idx, a, d in sample:
        if not compare.shapes_ok(d, want):
            readings.append(compare.failed(d))
            continue
        drawn = reference.draws(scen, reference.call_key(seed, idx), c.n_runs,
                                f["t_slots"], rows=rows_np)
        readings.append(compare.judge(cfg, traffic, scen, drawn, d))
    got = compare.worst(readings)
    correct, checks = compare.verdict(got, spec["limits"])
    log("compared calls " + ", ".join(str(s[0]) for s in sample) + "; not held to a limit: "
        + ", ".join(f"{k} {v!r}" for k, v in got.items() if k not in checks))
    for name, ch in checks.items():
        log(f"check {name} {ch['value']!r} limit {ch['limit']!r}")
    out = {"correct": bool(correct), "attempted": len(lats), "failed": 0,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = checks
    return out


def _finite(x):
    """JSON has no infinity: a reading that is not finite prints as null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True, type=int)
    ap.add_argument("--seconds", required=True, type=float)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        configure_jax()
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(_finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
