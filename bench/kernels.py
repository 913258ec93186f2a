"""Finding a kernel's device time in a reduced trace, and its roofline
share, for the per-layer metric readers.

A Pallas kernel's ops in the trace carry the name of the jitted function
that wraps its ``pallas_call`` (``qat_matmul``, ``qat_matmul_dx``,
``quant_pack_tiles``, ...), prefixed by the transformations applied to it
(``jvp_jit_``, ``transpose_jvp_jit_``, ``vmap_jit_``)."""
from __future__ import annotations

from bench import flops


def seconds_of(red, families) -> float | None:
    """Summed device seconds of the ops whose name holds one of
    ``families``; None when the trace holds none."""
    found = [s for name, s in red.op_seconds.items()
             if any(f in name for f in families)]
    return sum(found) if found else None


def roofline(ctx, count_key: str, families) -> float | None:
    """Least time of the window's ``count_key`` work over the device time
    of its kernels, in %; None when either is missing."""
    calls = ctx["counts"].get(count_key) or []
    measured = seconds_of(ctx["trace"], families)
    if not calls or not measured:
        return None
    least = sum(k * flops.least_time(ops, nbytes, ctx["peak"])
                for ops, nbytes, k in calls) * ctx["rounds"]
    return 100.0 * least / measured
