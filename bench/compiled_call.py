#!/usr/bin/env python3
"""The compiled program of a cell's call for a described TPU v5e, without
its debug information, and its digest.

    JAX_PLATFORMS=cpu python3 bench/compiled_call.py --workload <name> [--out FILE]

Builds the cell's call as ``bench/run.py`` does, its kernels compiled and
not interpreted, and compiles it for one chip of a v5e that is described,
not attached (no chip is needed). The
module's text is stripped of what names and source locations alone decide:
the source-location tables of its header, each op's ``metadata``, the
module's name, and the names of its ops and computations (each renamed by
its first appearance). Two checkouts whose calls print the same digest
compile to the same program. Prints the sha256 of the stripped text; with
``--out`` also writes the text.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import re
import sys
from pathlib import Path

import run


def strip(text: str) -> str:
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
                  "", text, flags=re.M)
    text = re.sub(r"^HloModule [^,]*", "HloModule", text)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    names: dict = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m[0], f"%{len(names)}"), text)


def compiled_text(workload: str) -> str:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cell as cell_mod

    jax.config.update("jax_enable_compilation_cache", False)
    spec = run.load_spec(workload)
    if str(run.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(run.ROOT / "src"))
    import repro.kernels

    # Without an attached TPU the program would pick the Pallas
    # interpreter; the module compiled here is the chip's.
    repro.kernels.default_interpret = lambda: False
    c = cell_mod.build_cell(workload, spec["config"], spec["traffic"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    key = jax.ShapeDtypeStruct((), cell_mod.seed_key(0).dtype, sharding=one)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((run.SLOT_ROWS,), jnp.int32, sharding=one)
    return c.call.lower(key, idx, rows).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    text = strip(compiled_text(args.workload))
    if args.out:
        args.out.write_text(text)
    print(hashlib.sha256(text.encode()).hexdigest(), args.workload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
