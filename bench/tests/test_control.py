"""The control fails the comparison that the program passes.

At a size the CPU holds, for every cell: the reference with its
contractions at ``high`` (three bfloat16 passes) in the program's place
fails at least one number's limit on every seed, while the program's own
readings stay within every limit. The same readings at the cells' own size
come from ``bench/control.py`` on the chip (PERF.md).
"""

import json

import pytest
from conftest import BENCH, cells, tiny_spec

SEEDS = [5, 3_000_000_001, 2_147_483_659]


@pytest.mark.parametrize("name", cells())
def test_the_control_fails_and_the_program_passes(name):
    import compare
    import control

    spec = tiny_spec(name)
    spec["traffic"]["mesh"] = False                 # one CPU device here
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())["numbers"]
    rows = []
    control.readings(spec, SEEDS, 1, require_chip=False,
                     log=lambda line: rows.append(json.loads(line)))
    per_call = [r for r in rows if "program" in r]
    assert len(per_call) == len(SEEDS)
    for r in per_call:
        ok, checks = compare.verdict(r["program"], limits)
        assert ok, (r["seed"], checks)
        ok, checks = compare.verdict(r["control"], limits)
        assert not ok, (r["seed"], checks)
