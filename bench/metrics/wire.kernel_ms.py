"""Device milliseconds per round of the wire's Pallas kernels: the E4M3
encode (``quant_pack_tiles``, down- and uplink) and the decode
(``unpack_tiles``). The wire's XLA fusions around them are not named yet
and are not counted."""
from bench.kernels import seconds_of

FAMILIES = ("quant_pack_tiles", "unpack_tiles")


def read(ctx):
    s = seconds_of(ctx["trace"], FAMILIES)
    if s is None:
        return None
    return 1e3 * s / ctx["rounds"]
