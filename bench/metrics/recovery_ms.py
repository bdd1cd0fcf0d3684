"""recovery_ms: device ms per call of the off-schedule recovery epoch
(``placed_recovery``) outside the rule it calls; self time, averaged over
the chips (``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.RECOVERY)
