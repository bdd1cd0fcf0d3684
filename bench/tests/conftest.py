"""Shared set-up of the benchmark's own tests (run with
``python -m pytest bench/tests``): the benchmark's modules on the path, and
each cell's spec cut to a size the CPU runs in seconds."""

import json
import sys
from pathlib import Path


BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (BENCH, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

#: Tiny size for CPU runs: few runs.
TINY_RUNS = 8


def cells(chips: int | None = None) -> list:
    """The cells of ``BENCHMARK.json``, or those that ask for ``chips``."""
    return [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
            if chips is None or w["chips"] == chips]


def unlisted_traffic(mesh: bool | None = None) -> list:
    """The traffic mixes that no cell runs yet (PERF.md section 7): all of
    them, or those with (or without) a mesh of chips."""
    used = {w["traffic"] for w in
            json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
    return sorted(p.stem for p in (BENCH / "traffic").glob("*.json")
                  if p.stem not in used
                  and mesh in (None, bool(json.loads(p.read_text()).get("mesh"))))


def traffic_spec(traffic: str, chips: int) -> dict:
    """A tiny spec that drives ``traffic`` on ``chips`` devices with the
    paper configuration and the limits of ``paper_mc``."""
    spec = tiny_spec("paper_mc")
    spec["traffic"] = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    spec["workload"] = dict(spec["workload"], traffic=traffic, chips=chips)
    return spec


def tiny_spec(name: str) -> dict:
    import run

    spec = run.load_spec(name)
    spec["config"]["fields"]["n_runs"] = TINY_RUNS
    return spec
