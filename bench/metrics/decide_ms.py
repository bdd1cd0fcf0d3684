"""decide_ms: device ms per call of the dispatch decisions (``gmsa_decide``),
the gmsa_score kernel excluded; self time, averaged over the chips
(``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.DECIDE)
