"""Find the benchmark's per-name pieces by file.

Everything that belongs to one entry point, one policy, one kind of
configuration or one per-layer metric is a module of its own, found by the
name that ``BENCHMARK.json``, a traffic file or a configuration file gives:

* ``bench/entries/<entry>.py``: the program's entry point, the digest its
  call returns and the plain reference of that entry;
* ``bench/policies/<policy>.py``: the dispatch policy handed to the entry;
* ``bench/scenarios/<scenario>.py``: the site climates and load of a kind
  of configuration, as the reference rebuilds them;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

A new cell adds such files and its entries; it edits none that is there.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once per process."""
    key = f"bench_{kind}_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown {name!r}: there is no bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
