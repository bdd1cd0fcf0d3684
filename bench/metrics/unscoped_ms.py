"""unscoped_ms: device ms per call of ops under no program name (the call's key
fold-in and answer, the harness's digest, the energy tables, layout copies);
self time, averaged over the chips (``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.UNSCOPED)
