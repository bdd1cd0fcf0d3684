"""tracegen_ms: device ms per call under the bench_tracegen scope (the per-run trace draws of build_inputs); self time,
averaged over the chips."""

from trace_reduce import layer_of


def read(trace, cell):
    s = trace.self_seconds(lambda scope: layer_of(scope) == "trace generation")
    if s <= 0 or not cell["calls"]:
        return None
    return s * 1e3 / cell["calls"]
