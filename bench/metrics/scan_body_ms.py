"""scan_body_ms: device ms per call inside the engines' scan bodies and outside every bench_* scope; self time,
averaged over the chips."""

from trace_reduce import layer_of


def read(trace, cell):
    s = trace.self_seconds(lambda scope: layer_of(scope) == "scan engines")
    if s <= 0 or not cell["calls"]:
        return None
    return s * 1e3 / cell["calls"]
