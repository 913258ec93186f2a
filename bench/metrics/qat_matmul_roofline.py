"""Roofline share of the fused QAT matmul (``kernels/fp8_matmul.py``):
the least time of the window's forward, dx and dw work (ops and bytes
from the layers' logical shapes, ``counts`` of the configuration's
reference module) over the summed device time of those kernels. A
forward that rematerialisation runs twice counts once as work and twice
as time."""
from bench.kernels import roofline

FAMILIES = ("qat_matmul",)


def read(ctx):
    return roofline(ctx, "qat_matmul", FAMILIES)
