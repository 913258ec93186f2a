"""Gradient parity: kernel-backed custom VJPs vs the jnp STE autodiff oracle.

Acceptance bar (ISSUE 1): the fused Pallas backward kernels must match jnp
autodiff of ``repro.core.fp8`` on weights, activations, and alpha/beta to
<= 1e-5 (relative). Runs the Pallas bodies in interpret mode (bit-exact
with what Mosaic computes, modulo 1-ULP transcendentals) by forcing the
``interpret`` backend around each call.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fp8, wire
from repro.core.fp8 import E4M3, E5M2
from repro.core.qat import alpha_like
from repro.kernels import dispatch, fp8_quant


@pytest.fixture
def interpret_backend(monkeypatch):
    monkeypatch.setenv(dispatch._ENV, "interpret")
    yield
    # monkeypatch restores automatically


def _rel_close(got, want, tol=1e-5):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-6)
    err = np.max(np.abs(got - want)) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol:g}"


def _clip_close(got, want, v, clip, d, tol=1e-5):
    """A clip cotangent is a sum of terms that cancel: ``d * t`` over the
    elements of ``v``, with ``t`` the STE's clip routing beyond the clip and
    rounding residual inside it. Compare relative to the sum of the terms'
    magnitudes, which bounds what per-element round-off can move it by."""
    inside, s, y = fp8_quant._ste_terms(v, clip, E4M3)
    t = jnp.sign(v) * (1.0 - inside) + (jnp.round(y) - y) * s / clip
    scale = float(jnp.sum(jnp.abs(d * t)))
    err = abs(float(got) - float(want)) / scale
    assert err <= tol, f"error {err:.3e} of the terms' L1 > {tol:g}"


@pytest.mark.parametrize("fmt", [E4M3, E5M2])
# (300, 128) / (128, 700) exceed a block dim without dividing it: regression
# for the out-of-bounds-tile padding leaking into the alpha reduction
@pytest.mark.parametrize(
    "shape", [(32, 128), (48, 100), (7, 33), (300, 128), (128, 700)]
)
def test_quant_det_vjp_matches_autodiff(interpret_backend, shape, fmt):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    alpha = jnp.asarray(0.6 * float(jnp.max(jnp.abs(x))), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)

    gx_o, ga_o = jax.grad(
        lambda x, a: jnp.sum(fp8.quantize_det(x, a, fmt) * g), argnums=(0, 1)
    )(x, alpha)
    gx, ga = jax.grad(
        lambda x, a: jnp.sum(dispatch.quantize_det(x, a, fmt) * g),
        argnums=(0, 1),
    )(x, alpha)
    _rel_close(gx, gx_o)
    _rel_close(ga, ga_o)


def test_quant_rand_vjp_matches_autodiff(interpret_backend):
    """Same-bits stochastic STE: build the jnp oracle from the exact bits the
    dispatcher would draw, then compare both cotangents."""
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 256), jnp.float32)
    alpha = jnp.asarray(0.5 * float(jnp.max(jnp.abs(x))), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(3), x.shape, jnp.float32)
    key = jax.random.PRNGKey(7)
    bits = jax.random.bits(key, shape=x.shape, dtype=jnp.uint32)

    def oracle(x, a):
        af = jnp.maximum(a, 1e-12)
        xc = jnp.clip(x, -af, af)
        b = fp8.exponent_bias(af)
        p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
        p = jax.lax.stop_gradient(jnp.where(p > 1.0, p, 1.0))
        s = jnp.exp2(p - b - 3)
        y = xc / s
        fl = jnp.floor(y)
        u = bits.astype(jnp.float32) * (1.0 / 4294967296.0)
        q = fl + (u < (y - fl)).astype(jnp.float32)
        return jnp.sum(s * (y + jax.lax.stop_gradient(q - y)) * g)

    gx_o, ga_o = jax.grad(oracle, argnums=(0, 1))(x, alpha)
    gx, ga = jax.grad(
        lambda x, a: jnp.sum(dispatch.quantize_rand(x, a, key) * g),
        argnums=(0, 1),
    )(x, alpha)
    _rel_close(gx, gx_o)
    _rel_close(ga, ga_o)


# k=784 exceeds the default contraction block without dividing it:
# regression for out-of-bounds K tiles accumulating into real output.
# Alphas are scaled off max|w| so no element sits exactly on the clip
# boundary, where jax.clip autodiff tie-splits the subgradient (0.5) while
# the STE kernels use the closed form (1) — a measure-zero convention
# difference, not an error (see dispatch docstring). The bf16 cases are
# the LMs' path: bf16 activations and cotangents, which the kernels feed
# the MXU as bf16 beside the bf16 FP8 codes; dx comes back in x's dtype,
# so it carries one bf16 rounding (2^-9 relative) more than the f32 oracle.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(96, 160, 64), (128, 128, 128),
                                   (64, 784, 32)])
def test_qat_matmul_vjp_matches_autodiff(interpret_backend, m, k, n, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
         * 0.5).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32) * 0.1
    beta = jnp.asarray(0.8, jnp.float32)
    alpha = jnp.asarray(0.6 * float(jnp.max(jnp.abs(w))), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), (m, n),
                          jnp.float32).astype(dtype)

    def oracle(x, w, beta, alpha):
        return jnp.sum(jnp.dot(
            fp8.quantize_det(x, beta), fp8.quantize_det(w, alpha),
            preferred_element_type=jnp.float32,
        ) * g.astype(jnp.float32))

    gx_o, gw_o, gb_o, ga_o = jax.grad(oracle, argnums=(0, 1, 2, 3))(
        x.astype(jnp.float32), w, beta, alpha
    )
    dispatch.QAT_MXU.clear()
    gx, gw, gb, ga = jax.grad(
        lambda x, w, b, a: jnp.sum(
            dispatch.qat_matmul(x, w, b, a).astype(jnp.float32)
            * g.astype(jnp.float32)),
        argnums=(0, 1, 2, 3),
    )(x, w, beta, alpha)
    mxu = jnp.dtype(dtype).name
    assert dict(dispatch.QAT_MXU) == {
        "qat_matmul:bfloat16": 1, f"qat_matmul_dx:{mxu}": 1,
        f"qat_matmul_dw:{mxu}": 1}
    assert gx.dtype == dtype
    _rel_close(gw, gw_o)
    if dtype == jnp.float32:
        _rel_close(gx, gx_o)
        _rel_close(gb, gb_o)
        _rel_close(ga, ga_o)
        return
    # bf16 activations: dx carries its output rounding, and the clip sums
    # are compared at the scale of their terms (bf16-valued activations
    # clip into larger cancelling sums than the f32 cases' at this width)
    _rel_close(gx, gx_o, 2.0 ** -8)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    _clip_close(gb, gb_o, xf, beta, gf @ fp8.quantize_det(w, alpha).T)
    _clip_close(ga, ga_o, w, alpha, fp8.quantize_det(xf, beta).T @ gf)


def test_fused_dense_equals_the_f32_cast_call_site(interpret_backend):
    """``models.common.dense`` hands the kernels its bf16 activations: the
    output and dx equal those of the f32-cast call site it replaced
    (``x2.astype(float32)`` into ``dispatch.qat_matmul``), after that call
    site's bf16 rounding of the output and of dx."""
    from repro.core.qat import QATConfig
    from repro.models import common

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    x = jax.random.normal(k1, (2, 48, 160), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.normal(k2, (160, 96), jnp.float32) * 0.08
    g = jax.random.normal(k3, (2, 48, 96), jnp.float32).astype(jnp.bfloat16)
    p = {"w": w, "w_qa": jnp.asarray(0.6 * float(jnp.max(jnp.abs(w)))),
         "x_qb": jnp.asarray(1.7, jnp.float32)}
    qcfg = QATConfig()
    assert common._fused_dense_ok(p, "w", x, qcfg, "x_qb")

    def f32_call_site(x):
        beta = common._lsq_grad_scale(p["x_qb"], x.size, qcfg.fmt)
        alpha = common._lsq_grad_scale(p["w_qa"], w.size, qcfg.fmt)
        x2 = x.reshape(-1, x.shape[-1])
        out = dispatch.qat_matmul(x2.astype(jnp.float32), w, beta, alpha,
                                  qcfg.fmt)
        return out.reshape(*x.shape[:-1], w.shape[-1]).astype(jnp.bfloat16)

    out, vjp = jax.vjp(lambda x: common.dense(p, "w", x, qcfg, "x_qb"), x)
    out_o, vjp_o = jax.vjp(f32_call_site, x)
    (gx,), (gx_o,) = vjp(g), vjp_o(g)
    assert out.dtype == gx.dtype == jnp.bfloat16
    _rel_close(out, out_o)
    _rel_close(gx, gx_o)


def test_qat_matmul_forward_matches_composition(interpret_backend):
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 96), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (96, 32), jnp.float32) * 0.2
    beta = jnp.asarray(1.1, jnp.float32)
    alpha = jnp.asarray(float(jnp.max(jnp.abs(w))), jnp.float32)
    got = dispatch.qat_matmul(x, w, beta, alpha)
    want = jnp.dot(fp8.quantize_det(x, beta), fp8.quantize_det(w, alpha),
                   preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_stacked_alpha_falls_back_to_jnp(interpret_backend):
    """Per-layer (L,1,1) clipping values dispatch to jnp — and the jnp path
    must agree with autodiff of the core implementation exactly."""
    w = jax.random.normal(jax.random.PRNGKey(6), (3, 8, 8), jnp.float32)
    alphas = alpha_like(w, stacked=True) * 0.7
    g = jax.random.normal(jax.random.PRNGKey(7), w.shape, jnp.float32)
    gx_o, ga_o = jax.grad(
        lambda w, a: jnp.sum(fp8.quantize_det(w, a) * g), argnums=(0, 1)
    )(w, alphas)
    gx, ga = jax.grad(
        lambda w, a: jnp.sum(dispatch.quantize_det(w, a) * g), argnums=(0, 1)
    )(w, alphas)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_o), atol=1e-6)


# ---------------------------------------------------------------------------
# Flat-buffer wire codec, both backends
# ---------------------------------------------------------------------------


def _model():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w1 = jax.random.normal(k1, (20, 30))
    w2 = jax.random.normal(k2, (3, 8, 8))  # stacked per-layer alphas
    return {
        "l1": {"w": w1, "w_qa": alpha_like(w1), "b": jnp.zeros((30,))},
        "l2": {"w": w2, "w_qa": alpha_like(w2, stacked=True)},
        "norm": jnp.ones((30,)),
    }


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_wire_roundtrip_matches_per_leaf(monkeypatch, backend):
    monkeypatch.setenv(dispatch._ENV, backend)
    params = _model()
    spec = wire.make_wire_spec(params)
    assert spec.q_names == ("l1.w", "l2.w")
    assert spec.total == 20 * 30 + 3 * 8 * 8
    out = wire.roundtrip(params, jax.random.PRNGKey(0), mode="det")
    want1 = fp8.quantize_det(params["l1"]["w"], params["l1"]["w_qa"])
    want2 = fp8.quantize_det(params["l2"]["w"], params["l2"]["w_qa"])
    np.testing.assert_allclose(np.asarray(out["l1"]["w"]), np.asarray(want1),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["l2"]["w"]), np.asarray(want2),
                               rtol=1e-5, atol=1e-6)
    # riders untouched
    np.testing.assert_array_equal(np.asarray(out["norm"]),
                                  np.asarray(params["norm"]))
    np.testing.assert_array_equal(np.asarray(out["l1"]["w_qa"]),
                                  np.asarray(params["l1"]["w_qa"]))


def test_wire_backends_agree():
    """jnp and interpret codec paths compute the same integer hash and the
    same quantization, so codes agree except for rare rounding-boundary
    elements where XLA's exp2/log2 differ by 1 ULP between fusion contexts
    (flips a stochastic-rounding comparison at ~1e-5 of elements)."""
    params = _model()
    spec = wire.make_wire_spec(params)
    key = jax.random.PRNGKey(3)
    payloads = {}
    for be in ("jnp", "interpret"):
        os.environ[dispatch._ENV] = be
        try:
            payloads[be] = wire.encode(params, spec, key, mode="rand")
        finally:
            os.environ.pop(dispatch._ENV, None)
    a = np.asarray(payloads["jnp"]["codes"])
    b = np.asarray(payloads["interpret"]["codes"])
    flip_frac = np.mean(a != b)
    assert flip_frac <= 1e-3, f"code flip fraction {flip_frac:.2e}"
    # det codes carry no stochastic comparison on the boundary-sensitive
    # path for these inputs — they must match exactly
    for be in ("jnp", "interpret"):
        os.environ[dispatch._ENV] = be
        try:
            payloads[be] = wire.encode(params, spec, key, mode="det")
        finally:
            os.environ.pop(dispatch._ENV, None)
    np.testing.assert_array_equal(
        np.asarray(payloads["jnp"]["codes"]),
        np.asarray(payloads["interpret"]["codes"]),
    )


def test_wire_payload_is_one_u8_buffer():
    params = _model()
    spec = wire.make_wire_spec(params)
    payload = wire.encode(params, spec, jax.random.PRNGKey(0), mode="rand")
    assert payload["codes"].dtype == jnp.uint8
    assert payload["codes"].shape == (spec.total,)
    # wire bytes: exactly 1 byte per quantized element + 4 per rider elem
    assert payload["codes"].nbytes == spec.total
    assert wire.payload_nbytes(spec) == spec.total + 4 * spec.n_other_elems
