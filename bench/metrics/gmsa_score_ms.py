"""gmsa_score_ms: device ms per call of the gmsa_score Pallas kernel (the op
its ``name=`` names); self time, averaged over the chips
(``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.KERNEL)
