"""GMSA dispatched from the hoisted per-job cost table (``gmsa_policy``)."""


def make(template):
    from repro.core.gmsa import gmsa_policy

    del template
    return gmsa_policy
