"""Mixture-of-Experts FFN: an expert share with dropless routing.

A model holds ``cfg.moe.held`` of the ``n_experts`` routed experts, those
from ``first_expert`` on, as one chip of an expert-parallel deployment
does; with no share given it holds them all. Per layer:

* **route** (``moe.route``): float32 router logits over all ``n_experts``
  from the layer's (unquantized) input, the ``top_k`` largest, and a
  softmax over those ``top_k`` (equal to the softmax over all experts
  renormalised over the chosen ones);
* **dispatch** (``moe.dispatch``): the ``N * top_k`` (token, choice)
  assignments are sorted by local expert, those on experts not held last,
  into one static ``(N * top_k, D)`` buffer whose rows
  ``[offsets[e], offsets[e + 1])`` go to held expert ``e``. There is no
  capacity: no assignment is dropped, whatever the skew;
* **experts** (``moe.experts``): SwiGLU, ``down(silu(gate x) * up x)``, as
  grouped matmuls over the buffer: on a Pallas backend one fused FP8-QAT
  kernel per pass (``kernels.dispatch.qat_gmm``) that visits only the
  held rows, so the work scales with the routed rows; elsewhere the
  operands fake-quantized in float32 through ``kernels.dispatch.gmm``;
* **combine** (``moe.combine``): each held row, scaled by its gate, is
  added to its token.

Dispatch and combine move rows by one permutation of the ``N * top_k``
assignments and its inverse (:func:`_permute_rows`): each direction, and
each one's gradient, is a gather; a token's ``top_k`` rows are summed in
float32.

The result is the held experts' part of the layer's output: what the
experts held elsewhere would add is not computed, and nothing stands in
for it. The shares of all experts add up to the whole layer.

QAT: the router is float32 and not quantized. Expert weights carry one
clipping value per (layer, expert), LSQ-scaled by one expert's size; the
expert input shares the layer's ``mlp_qb`` (LSQ over the ``N x D`` layer
input, as the dense FFN) and the down projection's input ``down_qb``
(LSQ over the ``N * top_k`` routed rows of ``d_ff``, the whole layer's).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.qat import QATConfig, _lsq_grad_scale, aq
from ..kernels import dispatch
from .common import COMPUTE_DTYPE, activation

Array = jax.Array


@jax.custom_vjp
def _permute_rows(x: Array, perm: Array, inv: Array) -> Array:
    """``x[perm]`` for a permutation ``perm`` whose inverse is ``inv``;
    the gradient is the inverse gather, not a scatter-add."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv):
    return x[perm], inv


def _permute_rows_bwd(inv, g):
    return g[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _fused(qcfg: QATConfig) -> bool:
    """Whether the experts take the fused grouped QAT matmul: full
    deterministic QAT on a backend that runs the Pallas kernels."""
    return (qcfg.enabled and qcfg.quantize_weights and qcfg.quantize_acts
            and qcfg.mode == "det" and dispatch.use_kernels())


def _experts(p, name: str, x: Array, site: str, n_act: int, offsets: Array,
             qcfg: QATConfig) -> Array:
    """Rows of ``x`` (sorted by held expert) through their expert's
    ``p[name]`` (E_here, d_in, d_out), the input fake-quantized with
    ``p[site]`` (LSQ over ``n_act`` elements) and each expert's weight with
    its own clip: on the fused kernels, or as float32 quantizers and
    :func:`dispatch.gmm`, the same arithmetic."""
    w = p[name].astype(jnp.float32)
    alpha = _lsq_grad_scale(p[name + "_qa"].reshape(-1).astype(jnp.float32),
                            w[0].size, qcfg.fmt)
    beta = p[site].astype(jnp.float32)
    if _fused(qcfg):
        return dispatch.qat_gmm(x, w, _lsq_grad_scale(beta, n_act, qcfg.fmt),
                                alpha, offsets, qcfg.fmt)
    x = aq(x.astype(jnp.float32), beta, qcfg, n=n_act)
    if qcfg.enabled and qcfg.quantize_weights:
        w = dispatch.quantize_det(w, alpha.reshape(-1, 1, 1), qcfg.fmt)
    return dispatch.gmm(x, w, offsets).astype(COMPUTE_DTYPE)


def moe_ffn(p, x: Array, cfg: ModelConfig, qcfg: QATConfig) -> Array:
    """x: (B, T, D) -> (B, T, D), the held experts' part. ``p`` holds this
    layer's slice."""
    B, T, D = x.shape
    m = cfg.moe
    N, K, G = B * T, m.top_k, m.held
    xf = x.reshape(N, D)

    with jax.named_scope("moe.route"):
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        top, expert = jax.lax.top_k(logits, K)                   # (N, K)
        gates = jax.nn.softmax(top, axis=-1)

    with jax.named_scope("moe.dispatch"):
        local = expert.reshape(-1) - m.first_expert              # (N*K,)
        held = (local >= 0) & (local < G)
        slot = jnp.where(held, local, G)
        order = jnp.argsort(slot, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=order.dtype))
        sizes = jnp.bincount(slot, length=G + 1)[:G]
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes).astype(jnp.int32)])
        weight = jnp.where(held, gates.reshape(-1), 0.0)[order]
        # a token's rows repeated in f32, so their gradients sum in f32
        xs = _permute_rows(jnp.repeat(xf.astype(jnp.float32), K, axis=0),
                           order, inverse).astype(COMPUTE_DTYPE)

    with jax.named_scope("moe.experts"):
        g = _experts(p, "we_gate", xs, "mlp_qb", N * D, offsets, qcfg)
        u = _experts(p, "we_up", xs, "mlp_qb", N * D, offsets, qcfg)
        h = activation(g, cfg.act) * u
        y = _experts(p, "we_down", h, "down_qb", N * K * cfg.d_ff, offsets,
                     qcfg)

    with jax.named_scope("moe.combine"):
        rows = _permute_rows(y.astype(jnp.float32) * weight[:, None],
                             inverse, order)
        out = jnp.sum(rows.reshape(N, K, D), axis=1)
    return out.astype(x.dtype).reshape(B, T, D)
