"""Operations and bytes of the work a round asks for, from logical shapes.

Every count here comes from the model's layer shapes and the dtypes the
model stores, never from a kernel's block shapes or operand casts, so a
later kernel rewrite is measured against the same work. Model FLOPs count
the forward and backward passes once: recomputation (rematerialisation)
is not work the model asks for.
"""
from __future__ import annotations

BF16, F32, U8 = 2, 4, 1


def least_time(ops: float, nbytes: float, peak) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound (``peak`` is a :class:`bench.peaks.Peak`)."""
    return max(ops / peak.bf16_flops, nbytes / peak.hbm_bytes_per_s)


def qat_matmul_costs(m: int, k: int, n: int) -> dict[str, tuple[int, int]]:
    """(ops, bytes) of the fused QAT matmul's three kernels for an (m, k)
    activation against a (k, n) weight: bf16 activations in and out, the
    f32 master weight, the f32 weight gradient."""
    ops = 2 * m * k * n
    return {
        "fwd": (ops, m * k * BF16 + k * n * F32 + m * n * BF16),
        "dx": (ops, m * n * BF16 + k * n * F32 + m * k * BF16),
        "dw": (ops, m * k * BF16 + m * n * BF16 + k * n * F32),
    }


def quant_pack_cost(n_elems: int, n_rows: int) -> tuple[int, int]:
    """(ops, bytes) of one wire encode: f32 in, one u8 code out per
    element, and one f32 clipping value per row of the (rows, 1024) plane.
    The encode is memory-bound; its handful of VPU operations per element
    are not counted as MXU work."""
    return 0, n_elems * (F32 + U8) + n_rows * F32


# ---------------------------------------------------------------------------
# Decoder LM (GQA attention, gated MLP, untied head)
# ---------------------------------------------------------------------------


def lm_projections(d: int, heads: int, kv_heads: int, head_dim: int,
                   d_ff: int, vocab: int, layers: int) -> list[tuple]:
    """(name, k, n, count per token pass) of every weight matmul."""
    per_layer = [
        ("wq", d, heads * head_dim), ("wk", d, kv_heads * head_dim),
        ("wv", d, kv_heads * head_dim), ("wo", heads * head_dim, d),
        ("w_gate", d, d_ff), ("w_up", d, d_ff), ("w_down", d_ff, d),
    ]
    return [(n, k, m, layers) for n, k, m in per_layer] + \
        [("lm_head", d, vocab, 1)]


def lm_flops_per_token(projections, layers: int, heads: int,
                       head_dim: int, seq: int) -> float:
    """6 N_matmul + 12 L (H hd) S: forward and backward, the PaLM
    convention (attention scores over the whole sequence)."""
    n_matmul = sum(k * n * c for _, k, n, c in projections)
    return 6.0 * n_matmul + 12.0 * layers * heads * head_dim * seq
