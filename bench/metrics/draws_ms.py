"""draws_ms: device ms per call of the per-run trace draws (``mc_draws``, the
configuration's build_inputs); self time, averaged over the chips
(``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.DRAWS)
