"""Quantization-aware-training plumbing.

Convention (used by every model in ``repro.models``): learnable clipping
values live *inside* the parameter pytree as siblings of the tensor they
clip —

* weight ``foo`` (ndim >= 2)      -> clipping scalar ``foo_qa`` (alpha)
* activation site ``bar``         -> clipping scalar ``bar_qb`` (beta)

For stacked (scanned-over-layers) parameters of shape ``(L, ...)`` the
clipping value has shape ``(L, 1, ..., 1)`` so it broadcasts per layer —
"per-tensor" in the paper's sense means per (layer, tensor). Stacked
expert weights ``(L, E, d_in, d_out)`` carry one per (layer, expert),
``(L, E, 1, 1)``.

This keeps alphas/betas trainable by the same optimizer as the weights
(the paper treats them as learnable parameters), makes them scan-sliceable,
and lets the communication layer pair weights with their clipping values
by name. Biases, norm parameters and the clip values themselves are never
weight-quantized (paper: "< 2% of parameters", kept FP32).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import fp8
from .fp8 import E4M3, FP8Format

Array = jax.Array
PyTree = Any

QA_SUFFIX = "_qa"
QB_SUFFIX = "_qb"


@dataclasses.dataclass(frozen=True)
class QATConfig:
    """How fake-quantization is applied during local (on-device) training."""

    enabled: bool = True
    quantize_weights: bool = True
    quantize_acts: bool = True
    fmt: FP8Format = E4M3
    # Paper default: deterministic QAT (Remark 4). 'rand' exists for the
    # Table 2 ablation.
    mode: str = "det"
    # Hybrid activation/gradient recipe (TE's fp8_hybrid, opened by the
    # scaling-policy work): when set, activation sites additionally
    # fake-quantize their BACKWARD gradient to this format (typically
    # E5M2 — wider dynamic range for the gradient-like tensor) at a fresh
    # per-tensor amax scale shifted by 2**bwd_margin (TE's fp8_margin;
    # current-scaling semantics — the gradient exists only inside one
    # step, so there is no history to delay against). None keeps the
    # forward-only QAT of the paper bit-for-bit.
    bwd_fmt: FP8Format | None = None
    bwd_margin: int = 0

    def replace(self, **kw) -> "QATConfig":
        return dataclasses.replace(self, **kw)


DISABLED = QATConfig(enabled=False, quantize_weights=False, quantize_acts=False)


def is_clip_key(name: str) -> bool:
    return name.endswith(QA_SUFFIX) or name.endswith(QB_SUFFIX)


def alpha_like(w: Array, stacked: bool = False, lead: int = 1) -> Array:
    """Paper's alpha init: per-tensor max |w|; when stacked, one per index
    of the ``lead`` leading axes (the layer, or the layer and the expert:
    shape ``(L, E, 1, 1)``)."""
    if stacked:
        axes = tuple(range(lead, w.ndim))
        return jnp.max(jnp.abs(w), axis=axes, keepdims=True).astype(jnp.float32)
    return jnp.max(jnp.abs(w)).astype(jnp.float32)


def beta_init(value: float = 4.0, stacked_layers: int | None = None) -> Array:
    """Activation clipping init (refined online by the learnable beta)."""
    if stacked_layers is None:
        return jnp.asarray(value, jnp.float32)
    return jnp.full((stacked_layers,), value, jnp.float32)


# ---------------------------------------------------------------------------
# In-graph fake-quant helpers used by model code
# ---------------------------------------------------------------------------


def _lsq_grad_scale(alpha: Array, n_elements: int, fmt: FP8Format) -> Array:
    """LSQ gradient scaling (Esser et al. 2020) for learnable clip values.

    The raw STE gradient of a range parameter sums contributions over every
    element it clips — ~sqrt(N) too large, which free-falls the clipping
    value within tens of steps (measured: LeNet head alpha 0.55 -> 0.04 in
    20 steps, training collapses to uniform predictions — EXPERIMENTS.md
    §Paper-notes). Forward value is unchanged; the gradient is scaled by
    1/sqrt(N * Q_max), the standard remedy in range-learning QAT.
    """
    import numpy as _np

    g = 1.0 / float(_np.sqrt(max(n_elements, 1) * (2 ** (fmt.mant + 1) - 1)))
    return alpha * g + jax.lax.stop_gradient(alpha * (1.0 - g))


def wq(w: Array, alpha: Array, cfg: QATConfig, key: Array | None = None) -> Array:
    """Fake-quantize a weight tensor for the forward pass (QAT).

    Dispatched through ``kernels.dispatch``: fused Pallas quantizer with the
    STE custom VJP on TPU, the jnp chain elsewhere (same math + autodiff).
    """
    if not (cfg.enabled and cfg.quantize_weights):
        return w
    from ..kernels import dispatch

    alpha = _lsq_grad_scale(alpha, w.size, cfg.fmt)
    if cfg.mode == "rand":
        assert key is not None, "stochastic QAT needs a PRNG key"
        return dispatch.quantize_rand(w, alpha, key, cfg.fmt)
    return dispatch.quantize_det(w, alpha, cfg.fmt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _grad_quant(x: Array, fmt: FP8Format, margin: int) -> Array:
    """Identity forward; the BACKWARD gradient is fake-quantized to ``fmt``.

    The hybrid-recipe bwd leg: the activation gradient is a one-step
    tensor (no cross-round history), so it uses current scaling — a fresh
    per-tensor amax, shifted by the exact power of two ``2**margin``
    (mantissas untouched) and floored like every other clip in the repo.
    """
    return x


def _grad_quant_fwd(x, fmt, margin):
    return x, None


def _grad_quant_bwd(fmt, margin, _res, g):
    a = jnp.maximum(
        jnp.exp2(jnp.float32(margin)) * jnp.max(jnp.abs(g)),
        fp8._ALPHA_FLOOR,
    )
    return (fp8.quantize_det(g, a, fmt),)


_grad_quant.defvjp(_grad_quant_fwd, _grad_quant_bwd)


def aq(x: Array, beta: Array, cfg: QATConfig, n: int | None = None) -> Array:
    """Fake-quantize an activation tensor (always deterministic, sep. clip beta).

    LSQ scales the clip's gradient by the ``n`` elements it clips
    (``x.size`` unless given: a site whose rows repeat counts them once).

    With ``cfg.bwd_fmt`` set, the site becomes the hybrid
    activation/gradient recipe: forward stays ``cfg.fmt`` (E4M3 QAT,
    value-identical to the forward-only path), while the backward
    activation gradient is additionally fake-quantized to ``bwd_fmt``
    (E5M2 by convention) before it reaches the forward quantizer's STE.
    """
    if not (cfg.enabled and cfg.quantize_acts):
        return x
    from ..kernels import dispatch

    # Activations are quantized symmetrically like weights (paper §2).
    beta = _lsq_grad_scale(beta, x.size if n is None else n, cfg.fmt)
    out = dispatch.quantize_det(x, beta, cfg.fmt)
    if cfg.bwd_fmt is not None:
        out = _grad_quant(out, cfg.bwd_fmt, cfg.bwd_margin)
    return out


# ---------------------------------------------------------------------------
# PyTree-level utilities used by the federated/communication layer
# ---------------------------------------------------------------------------


def _walk(params: PyTree) -> list[tuple[tuple, str, Array]]:
    """Flatten to (path, leaf_name, leaf) for dict-based param trees."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = _key_name(path[-1])
        out.append((path, name, leaf))
    return out


def _key_name(entry) -> str:
    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.GetAttrKey):
        return entry.name
    return str(entry)


def quantized_leaf_names(params: PyTree) -> set[str]:
    """Dotted paths of weight leaves that get FP8-quantized for communication."""
    names = set()
    entries = {}
    for path, name, leaf in _walk(params):
        dotted = ".".join(_key_name(p) for p in path)
        entries[dotted] = leaf
    for dotted, leaf in entries.items():
        name = dotted.rsplit(".", 1)[-1]
        if is_clip_key(name):
            continue
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and dotted + QA_SUFFIX in entries:
            names.add(dotted)
    return names


def comm_quantize(
    params: PyTree,
    key: Array,
    fmt: FP8Format = E4M3,
    mode: str = "rand",
) -> PyTree:
    """Quantize a model for transmission (paper: Q_rand on every weight tensor
    that has a paired clipping value; clip values / biases / norms ride along
    in FP32 — they are <2% of bytes, counted exactly by ``metrics``).

    ``mode='det'`` exists for the Table-2 "biased communication" ablation;
    ``mode='none'`` returns the tree unchanged (FP32 baseline).

    Implementation: the flat-buffer wire codec (``core.wire``) — every
    quantizable weight is concatenated into one contiguous buffer and
    quantized+packed/unpacked by a single fused kernel launch, instead of
    the old per-leaf Python loop (O(n_tensors) launches per model copy).
    """
    from . import wire

    return wire.roundtrip(params, key, fmt=fmt, mode=mode)


def clip_value_mask(params: PyTree) -> PyTree:
    """True for learnable clipping values (alpha/beta leaves).

    Used by the optimizers' trust-region guard: clip values get relative
    update clamping (|delta| <= 2% of |alpha| per step). Without it, large
    task gradients (e.g. the classifier head under CE loss) collapse alpha
    within tens of steps — the clip-everything failure mode measured in
    EXPERIMENTS.md §Paper-notes — while the paper's accuracy numbers imply
    stable ranges.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = [is_clip_key(_key_name(path[-1])) for path, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def weight_decay_mask(params: PyTree) -> PyTree:
    """True for leaves that should receive weight decay (>=2-D weights only;
    no biases, no norm scales, no clip values)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        name = _key_name(path[-1])
        out.append((not is_clip_key(name)) and hasattr(leaf, "ndim") and leaf.ndim >= 2)
    return jax.tree_util.tree_unflatten(treedef, out)
