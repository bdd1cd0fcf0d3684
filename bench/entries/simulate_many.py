"""Entry ``simulate_many``: n_runs independent GMSA runs of the scenario.

The program's side is the engine itself; the reference is the plain slot
loop of ``reference.simulate`` over the scenario's energy tables.
"""

import jax.numpy as jnp

import reference

#: Where the engine and its slot step live, and which positional argument
#: of the entry is ``n_runs`` (the fault tests break the path there).
ENGINE = "repro.core.simulator"
ENTRY_FN = "simulate_many"
N_RUNS_ARG = 3


def program(cfg, traffic, build_inputs, policy, n_runs, mesh):
    """run(key) -> the engine's outputs of one evaluation."""
    from repro.core.simulator import simulate_many

    v = float(cfg["fields"]["v"])

    def run(key):
        return simulate_many(build_inputs, policy, key, n_runs, v, mesh=mesh)

    return run


def digest(outs, rows) -> dict:
    """Each run's time-average cost, and every slot's decisions, bills and
    backlog of the runs ``rows``."""
    f_rows = outs.f_trace[rows]                           # (S, T, N, K)
    return {
        "runs": jnp.mean(outs.cost, axis=-1),             # (R,)
        "choice": jnp.argmax(f_rows, axis=-2).astype(jnp.int32),
        "fmax": jnp.max(f_rows, axis=-2),
        "slot_cost": outs.cost[rows],
        "slot_energy": outs.energy[rows],
        "slot_backlog": outs.backlog_total[rows],
    }


def shapes(n_runs, n_rows, t_slots, k_types) -> dict:
    """The shape of each digest entry of one whole call."""
    return {"runs": (n_runs,), "choice": (n_rows, t_slots, k_types),
            "fmax": (n_rows, t_slots, k_types), "slot_cost": (n_rows, t_slots),
            "slot_energy": (n_rows, t_slots), "slot_backlog": (n_rows, t_slots)}


def reference_digest(cfg, traffic, scen, arr, mu, precision="highest", forced=None):
    e_cost, e_raw = reference.energy_tables(scen, precision)
    return reference.simulate(arr, mu, e_cost, e_raw, cfg["fields"]["v"], forced)
