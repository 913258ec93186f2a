"""Roofline share of the wire's E4M3 encode (``quant_pack_tiles``,
``kernels/fp8_quant.py``): the least time of every encode of the window
(f32 in, u8 codes out, one f32 clipping value per plane row; memory-bound)
over the summed device time of the encode kernels."""
from bench.kernels import roofline

FAMILIES = ("quant_pack_tiles",)


def read(ctx):
    return roofline(ctx, "quant_pack", FAMILIES)
