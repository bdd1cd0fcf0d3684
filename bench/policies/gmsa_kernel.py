"""GMSA with every decision made by the ``gmsa_score`` Pallas kernel on the
raw (r, omega*PUE) operands, the ratios bound statically from the template
(compiled on a TPU, interpreted elsewhere)."""


def make(template):
    from repro.core.gmsa import make_kernel_policy

    return make_kernel_policy(template.r, template.p_it)
