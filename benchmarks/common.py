"""Shared helpers for the benchmark suite."""

from __future__ import annotations

import json
import os
import pathlib
import time

ART = pathlib.Path(__file__).resolve().parent / "artifacts"
ART.mkdir(exist_ok=True)

#: Machine-readable perf trajectory (EXPERIMENTS.md §Perf): every bench run
#: appends one entry here so future PRs can diff per-bench ``us_per_call``
#: against history. Lives at the repo root (committed; CI also uploads it
#: as an artifact).
BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sim.json"

#: Paper methodology: 1000 Monte-Carlo runs. Override for quick iterations:
#: REPRO_BENCH_RUNS=100 python -m benchmarks.run
N_RUNS = int(os.environ.get("REPRO_BENCH_RUNS", "1000"))

#: Records accumulated by :func:`emit` in this process, flushed to
#: :data:`BENCH_JSON` by :func:`write_bench_json`.
_RECORDS: list[dict] = []


def timed(fn, *args, warmup: int = 1, iters: int = 3):
    """Wall-time fn (already-jitted callables): returns (result, us_per_call)."""
    import jax

    result = None
    for _ in range(warmup):
        result = fn(*args)
        jax.block_until_ready(result)
    t0 = time.perf_counter()
    for _ in range(iters):
        result = fn(*args)
        jax.block_until_ready(result)
    dt = (time.perf_counter() - t0) / iters
    return result, dt * 1e6


def timed_compile_sweep(thunk, n_runs: int, iters: int = 4,
                        trace_dir: str | None = None):
    """Time a jit-compiled Monte-Carlo sweep, isolating compilation.

    The first call pays compilation plus one full sweep; steady state is
    the MINIMUM of ``iters`` further calls — the timeit-style best-of
    estimator: on shared/noisy CPUs every timing above the minimum is
    scheduler interference, not the program (a single call, which this
    harness used to take, is hostage to that noise). Subtracting isolates
    the one-time compile. Returns ``(outs, us_per_run, compile_us)``.

    ``trace_dir`` wraps the steady-state calls (compilation excluded) in
    ``jax.profiler.trace`` — open the result with TensorBoard's profile
    plugin or Perfetto. Timings taken under the profiler carry its
    overhead; use them for the op-level breakdown, not the trajectory.
    """
    import contextlib

    import jax

    t0 = time.perf_counter()
    outs = thunk()
    jax.block_until_ready(outs)
    first_call_us = (time.perf_counter() - t0) * 1e6

    prof = (jax.profiler.trace(trace_dir) if trace_dir
            else contextlib.nullcontext())
    steady = []
    with prof:
        for _ in range(max(iters, 1)):
            t0 = time.perf_counter()
            outs = thunk()
            jax.block_until_ready(outs)
            steady.append((time.perf_counter() - t0) * 1e6)
    us_per_run = min(steady) / n_runs
    compile_us = max(first_call_us - n_runs * us_per_run, 0.0)
    return outs, us_per_run, compile_us


def _parse_derived(derived: str) -> dict:
    """Best-effort ``k=v;k=v`` -> dict (values floated when possible)."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v.rstrip("%x"))
        except ValueError:
            out[k] = v
    return out


def emit(name: str, us_per_call: float, derived: str):
    """The run.py output contract: ``name,us_per_call,derived`` CSV.

    Also records the row for :func:`write_bench_json`.
    """
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
    _RECORDS.append({
        "name": name,
        "us_per_call": round(us_per_call, 1),
        "derived": _parse_derived(derived),
    })


def _provenance() -> dict:
    """Stamp for a BENCH_sim.json entry: git SHA, jax version, backend."""
    import subprocess

    import jax

    sha = None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=pathlib.Path(__file__).resolve().parent,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        pass
    return {
        "git_sha": sha,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
    }


def read_bench_history(path=None) -> list[dict]:
    """Load the perf-trajectory entries (``[]`` on missing/corrupt file).

    Shared by :func:`write_bench_json` (append + dedup) and callers that
    want to inspect the trajectory.
    """
    path = pathlib.Path(path) if path is not None else BENCH_JSON
    if not path.exists():
        return []
    try:
        return json.loads(path.read_text())
    except json.JSONDecodeError:
        return []


def write_bench_json(label: str | None = None):
    """Append this process's emitted records to :data:`BENCH_JSON`.

    Called by ``benchmarks.run`` after the full suite and by each bench
    module's ``__main__`` guard when run standalone (the CI smoke step),
    so the perf trajectory accrues either way. No-op when nothing was
    emitted.

    Each entry is stamped with provenance (git SHA, jax version, backend)
    so a trajectory diff can tell a regression from an environment change.
    Re-runs that produce a ``derived`` payload identical to the previous
    entry with the same label are SKIPPED — ``us_per_call`` is timing
    noise, so without the dedup every CI retry would grow the file with
    rows that say nothing new.
    """
    if not _RECORDS:
        return
    history = read_bench_history()
    payload = [(r["name"], r["derived"]) for r in _RECORDS]
    for prev in reversed(history):
        if prev.get("label") != label:
            continue
        prev_payload = [
            (b.get("name"), b.get("derived")) for b in prev.get("benches", [])
        ]
        if prev_payload == payload:
            return                      # identical derived results: no news
        break
    history.append({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "label": label,
        "n_runs_env": N_RUNS,
        **_provenance(),
        "benches": list(_RECORDS),
    })
    BENCH_JSON.write_text(json.dumps(history, indent=1) + "\n")
