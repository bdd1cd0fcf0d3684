"""epochs_ms: device ms per call of the controller's epoch loop
(``placed_epochs``: epoch bookkeeping, the WAN and sync bills) outside the
names nested in it; self time, averaged over the chips (``program_scopes``)."""

import program_scopes


def read(trace, cell):
    return program_scopes.ms_per_call(trace, cell, program_scopes.EPOCHS)
