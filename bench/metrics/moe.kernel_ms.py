"""Device milliseconds per round of the sparse experts' grouped QAT
matmul kernels (``qat_gmm``, ``qat_gmm_dx``, ``qat_gmm_dw``). Routing,
the sort, the gathers and the scatter around them are XLA ops and are
not counted. None where the program has no such kernel."""
from bench.kernels import seconds_of

FAMILIES = ("qat_gmm",)


def read(ctx):
    s = seconds_of(ctx["trace"], FAMILIES)
    if s is None:
        return None
    return 1e3 * s / ctx["rounds"]
