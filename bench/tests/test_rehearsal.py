"""CPU rehearsal of every cell's call path at a tiny run count, and of the
traffic mixes no cell runs yet: on one device, or split over a mesh of 4
virtual CPU devices.

Kernels run in interpret mode (the program picks it off the TPU). These
runs print no device metric: they check that the path runs end to end and
that the comparison passes; speed is measured on the chip only.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT, cells, tiny_spec, traffic_spec, unlisted_traffic

ONE_CHIP = cells(chips=1)
FOUR_CHIP = unlisted_traffic(mesh=True)


def _quiet(_msg):
    pass


@pytest.mark.parametrize("name", ONE_CHIP)
def test_cell_call_path_passes_on_cpu(name):
    import run

    out = run.run_cell(tiny_spec(name), 3_000_000_007, 0.5, False,
                       require_chip=False, log=_quiet)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("traffic", unlisted_traffic())
def test_unlisted_traffic_passes_on_one_cpu_device(traffic):
    import run

    out = run.run_cell(traffic_spec(traffic, 1), 3_000_000_009, 0.5, False,
                       require_chip=False, log=_quiet)
    assert out["correct"], out["checks"]


FOUR_CPU = """
import json, sys
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
from conftest import traffic_spec
import run
out = run.run_cell(traffic_spec({name!r}, 4), 3_000_000_011, 0.3, False,
                   require_chip=False, log=lambda m: None)
print(json.dumps(run._finite(out)))
"""


def _four_cpu(name: str, code: str = FOUR_CPU) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = code.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                      src=str(ROOT / "src"), name=name)
    res = subprocess.run([sys.executable, "-c", src], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", FOUR_CHIP)
def test_four_chip_call_builds_and_passes_on_four_cpu_devices(name):
    out = _four_cpu(name)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cells()[0], "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_without_a_chip_the_run_fails_and_prints_no_result():
    res = _cli(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no accelerator" in res.stderr


def test_a_checkout_of_the_benchmark_alone_fails_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
