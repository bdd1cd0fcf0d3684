"""Benchmark orchestrator — one section per paper table/figure + systems
benches. Prints ``name,us_per_call,derived`` CSV lines (stdout contract).

  PYTHONPATH=src python -m benchmarks.run            # full (1000 runs)
  REPRO_BENCH_RUNS=100 PYTHONPATH=src python -m benchmarks.run   # quick
  ... python -m benchmarks.run --trace-dir /tmp/prof  # + profiler trace
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import traceback

from benchmarks.common import write_bench_json


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--trace-dir", default=None, metavar="DIR",
        help="wrap the whole suite in jax.profiler.trace(DIR) — view the "
             "op-level breakdown with TensorBoard's profile plugin",
    )
    args, _ = parser.parse_known_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    failures = []
    print("name,us_per_call,derived")
    if args.trace_dir:
        import jax

        prof = jax.profiler.trace(args.trace_dir)
    else:
        prof = contextlib.nullcontext()
    with prof:
        run_benches(failures)
    # Machine-readable perf trajectory (EXPERIMENTS.md §Perf): append this
    # run's rows to BENCH_sim.json at the repo root.
    write_bench_json(label="full" if not failures else "partial")
    if failures:
        sys.exit(f"benchmark failures: {failures}")


def run_benches(failures: list) -> None:
    for name, modpath in [
        ("fig5", "benchmarks.fig5"),
        ("fig6", "benchmarks.fig6"),
        ("sim_bench", "benchmarks.sim_bench"),
        ("placement_bench", "benchmarks.placement_bench"),
        ("jobs_bench", "benchmarks.jobs_bench"),
        ("kernel_bench", "benchmarks.kernel_bench"),
        ("shard_bench", "benchmarks.shard_bench"),
        ("serve_bench", "benchmarks.serve_bench"),
        ("roofline", "benchmarks.roofline"),
    ]:
        try:
            mod = __import__(modpath, fromlist=["main"])
            mod.main()
        except Exception:  # noqa: BLE001
            failures.append(name)
            print(f"{name},-1,FAILED", flush=True)
            traceback.print_exc()


if __name__ == "__main__":
    main()
