"""scan_ms: device ms per call of the slot loop (``gmsa_scan``: its body, the
loop itself and the stacking of its outputs) outside the names nested in it;
self time, averaged over the chips (``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.SCAN)
