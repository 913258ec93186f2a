"""Roofline share of the grouped QAT matmul of the sparse experts
(``qat_gmm``, ``qat_gmm_dx``, ``qat_gmm_dw`` in ``kernels/fp8_matmul.py``):
the least time of the window's forward, dx and dw work at the expected
routed load (ops and bytes from the layers' logical shapes, ``counts`` of
the configuration's reference module) over the summed device time of
those kernels. A forward that rematerialisation runs twice counts once as
work and twice as time. None where the program has no such kernel."""
from bench.kernels import roofline

FAMILIES = ("qat_gmm",)


def read(ctx):
    return roofline(ctx, "qat_gmm", FAMILIES)
