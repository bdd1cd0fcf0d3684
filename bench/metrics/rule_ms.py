"""rule_ms: device ms per call of the slow-timescale placement rule
(``placed_rule``), at the epoch boundaries and in the recovery epoch; self
time, averaged over the chips (``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.RULE)
