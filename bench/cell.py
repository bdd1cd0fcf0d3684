"""Build one cell's evaluation call from its configuration and traffic files.

The system under test is reached through its public entry points only:
the configuration's builder (named in its file), the traffic's dispatch
policy (``bench/policies/<policy>.py``) and its entry point
(``bench/entries/<entry>.py``, which also gives the call's digest). The
callables handed to them are wrapped in ``jax.named_scope`` s, so that the
device trace can name the ops of each layer:

* ``bench_tracegen`` around ``build_inputs`` (the per-run trace draws),
* ``bench_dispatch`` around the policy (the GMSA decision),
* ``bench_placement_rule`` around the placement rule (in the entries that
  take one).

``functools.wraps`` copies the policy attributes (``consumes_key``,
``wants_wpue``, ``wants_r``, ``static_r``, ...) that the engines read.

One call is one Monte-Carlo evaluation with the key
``fold_in(key(seed), index)``. It returns the time-average cost and backlog
(the answer the caller fetches) and a digest that stays on the device until
the comparison with the reference reads it (the entry's ``digest``: each
run's time-average cost, and for a few runs drawn from the seed (``rows``)
every slot's decisions, bills and backlog).
"""

from __future__ import annotations

import functools
import importlib
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def scoped(fn: Callable, name: str) -> Callable:
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return wrapped


def seed_key(seed: int):
    """A threefry key holding all 64 bits of ``seed``."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    words = np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def build_config(cfg: dict):
    """The program's configuration object and ``(template, build_inputs)``."""
    spec = cfg["builder"]
    mod = importlib.import_module(spec["module"])
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in cfg["fields"].items()}
    obj = getattr(mod, spec["config"])(**fields)
    template, build_inputs = getattr(mod, spec["make"])(obj)
    return obj, template, build_inputs


@dataclass
class Cell:
    name: str
    n_runs: int
    shapes: dict           # t_slots, n_sites, k_types
    call: Callable         # (base_key, index, rows) -> (answer (2,), digest)
    home: object           # where the call's inputs live: a device or a sharding
    entry: object          # the entry's module: digest shapes and reference


def build_cell(name: str, cfg: dict, traffic: dict, chips: int = 1) -> Cell:
    import plugins

    _, template, build_inputs = build_config(cfg)
    n_runs = int(cfg["fields"]["n_runs"])
    entry = plugins.load("entries", traffic["entry"])
    policy = scoped(plugins.load("policies", traffic["policy"]).make(template),
                    "bench_dispatch")
    build_w = scoped(build_inputs, "bench_tracegen")
    mesh, home = None, jax.devices()[0]
    if traffic.get("mesh"):
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.distributed.mesh import runs_mesh

        mesh = runs_mesh(chips)
        home = NamedSharding(mesh, PartitionSpec())      # replicated
    run = entry.program(cfg, traffic, build_w, policy, n_runs, mesh)

    @jax.jit
    def call(base_key, index, rows):
        outs = run(jax.random.fold_in(base_key, index))
        answer = jnp.stack([jnp.mean(outs.cost), jnp.mean(outs.backlog_avg)])
        return answer, entry.digest(outs, rows)

    f = cfg["fields"]
    return Cell(name=name, n_runs=n_runs,
                shapes={"t_slots": f["t_slots"], "n_sites": f["n_sites"],
                        "k_types": f["k_types"]},
                call=call, home=home, entry=entry)
