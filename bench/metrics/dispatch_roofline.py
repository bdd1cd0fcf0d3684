"""dispatch_roofline: the least time one call's GMSA decisions need on the
chip, as a share (%) of the device time spent under ``bench_dispatch``.

The work is the least that the decisions need, whichever path makes them,
and not any path's own traffic (the kernel re-reads the (K, N, N) ratios
every slot; the table path reads a hoisted cost row). Per slot, with R runs
deciding at once over K types and N sites, in float32:

* reads, per run: the (K, N) backlog, the (K, N) service rates and the
  (K,) arrivals; once for all runs: the slot's (K, N) per-job cost row
  and the (K,) V*P, which no run changes;
* writes, per run: the (K,) argmin;
* operations, per run: for each (K, N) element q - mu, V*e, the add, the
  product with A and the argmin's compare: 5KN.

A call decides T slots. The least time is the larger of bytes over HBM
bandwidth and operations over the peak rate of the peak table. That rate
is the matrix units' bf16 peak, above what vector ops reach, so the
operations' term is a floor; at every size the benchmark runs, the bytes
bound it, by more than a hundredfold.
"""

from trace_reduce import layer_of

OPS_PER_ELEMENT = 5
F32 = 4


def least_work(n_runs, t_slots, n_sites, k_types):
    """(bytes, operations) of one call's decisions."""
    kn = k_types * n_sites
    per_run = F32 * (2 * kn + k_types) + F32 * k_types
    shared = F32 * (kn + k_types)
    nbytes = t_slots * (n_runs * per_run + shared)
    ops = t_slots * n_runs * OPS_PER_ELEMENT * kn
    return nbytes, ops


def least_seconds(n_runs, t_slots, n_sites, k_types, peaks):
    """(seconds, "bytes" or "operations") for one call's decisions."""
    nbytes, ops = least_work(n_runs, t_slots, n_sites, k_types)
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "operations")


def read(trace, cell):
    s = trace.self_seconds(lambda scope: layer_of(scope) == "dispatch")
    if s <= 0 or not cell["calls"] or not cell["peaks"]:
        return None
    least, _ = least_seconds(cell["n_runs"], cell["t_slots"], cell["n_sites"],
                             cell["k_types"], cell["peaks"])
    return 100.0 * least / (s / cell["calls"])
