"""Pallas kernel correctness: sweep shapes/dtypes, allclose vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fp8
from repro.core.fp8 import E4M3, E5M2
from repro.kernels import fp8_matmul, fp8_quant, ops, ref

SHAPES = [(8, 128), (16, 256), (256, 512), (300, 200), (1, 128), (129, 384)]
DTYPES = [jnp.float32, jnp.bfloat16]
FMTS = [E4M3, E5M2]


def assert_quant_close(got, want, fmt, max_flip_frac=3e-4):
    """Quantizer outputs must agree except for boundary flips.

    Compiled (pallas/XLA) exp2/log2 differ from the eager oracle by 1 ULP;
    elements landing exactly on a floor/round boundary may then pick the
    *adjacent* grid point. Low-precision inputs (bf16) sit on round ties
    *systematically*, so for them only the one-grid-step bound applies; f32
    inputs hit ties with ~0 probability, so their flip fraction must be tiny.
    """
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    denom = np.maximum(np.abs(w), 1e-30)
    rel = np.abs(g - w) / denom
    if np.asarray(got).dtype == np.float32:
        flips = rel > 1e-5
        assert flips.mean() <= max_flip_frac, f"flip fraction {flips.mean():.2e}"
    one_step = 2.0 ** (-fmt.mant) * 1.01 + 1e-6
    assert rel.max() <= one_step, f"max rel dev {rel.max():.3e} > one grid step"


def _data(shape, dtype, seed=0, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * scale
    return x.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("fmt", FMTS)
def test_quant_det_matches_oracle(shape, dtype, fmt):
    x = _data(shape, dtype)
    alpha = jnp.max(jnp.abs(x.astype(jnp.float32))) * 0.9
    got = fp8_quant.quant_det(x, alpha, fmt=fmt, interpret=True)
    want = ref.quant_det_ref(x, alpha, fmt)
    assert_quant_close(got, want, fmt)


@pytest.mark.parametrize("shape", SHAPES[:4])
@pytest.mark.parametrize("fmt", FMTS)
def test_quant_det_matches_core(shape, fmt):
    """Kernel vs the production core implementation (independent code path)."""
    x = _data(shape, jnp.float32, seed=3)
    alpha = jnp.max(jnp.abs(x))
    got = fp8_quant.quant_det(x, alpha, fmt=fmt, interpret=True)
    want = fp8.quantize_det(x, alpha, fmt)
    assert_quant_close(got, want, fmt)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("fmt", FMTS)
def test_quant_rand_matches_oracle(shape, fmt):
    x = _data(shape, jnp.float32, seed=1)
    alpha = jnp.max(jnp.abs(x))
    bits = jax.random.bits(jax.random.PRNGKey(7), shape=shape, dtype=jnp.uint32)
    got = fp8_quant.quant_rand(x, alpha, bits, fmt=fmt, interpret=True)
    want = ref.quant_rand_ref(x, alpha, bits, fmt)
    assert_quant_close(got, want, fmt)


def test_quant_rand_unbiased_kernel():
    x = _data((4, 128), jnp.float32, seed=2, scale=0.3)
    alpha = jnp.max(jnp.abs(x))
    acc = np.zeros(x.shape, np.float64)
    n = 600
    for i in range(n):
        acc += np.asarray(
            ops.quantize_rand_kernel(x, alpha, jax.random.PRNGKey(i))
        )
    bias = np.abs(acc / n - np.asarray(x)).max()
    # stderr of the mean ~ s/sqrt(n); grid step near |x|~0.3 is ~0.02
    assert bias < 5e-3, bias


@pytest.mark.parametrize(
    "m,k,n", [(128, 128, 128), (256, 512, 256), (300, 256, 128), (64, 384, 512)]
)
@pytest.mark.parametrize("dtype", DTYPES)
def test_qat_matmul_matches_oracle(m, k, n, dtype):
    x = _data((m, k), dtype, seed=4, scale=0.5)
    w = _data((k, n), dtype, seed=5, scale=0.1)
    beta = jnp.asarray(1.5, jnp.float32)
    alpha = jnp.max(jnp.abs(w.astype(jnp.float32)))
    got = fp8_matmul.qat_matmul(x, w, beta, alpha, interpret=True)
    want = ref.qat_matmul_ref(x, w, beta, alpha)
    # bf16 inputs can sit exactly on FP8 rounding ties; 1-ULP compile/eager
    # differences then flip single grid choices, moving the dot product by
    # one grid step (~0.04 here). f32 inputs are tie-free w.h.p.
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(want, np.float32),
        rtol=5e-2 if dtype == jnp.bfloat16 else 1e-5,
        atol=8e-2 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_qat_matmul_blocking_invariance():
    """Result must not depend on the BlockSpec tiling."""
    x = _data((256, 384), jnp.float32, seed=6, scale=0.4)
    w = _data((384, 256), jnp.float32, seed=7, scale=0.2)
    beta = jnp.asarray(1.2, jnp.float32)
    alpha = jnp.max(jnp.abs(w))
    a = fp8_matmul.qat_matmul(x, w, beta, alpha, bm=64, bk=128, bn=64, interpret=True)
    b = fp8_matmul.qat_matmul(x, w, beta, alpha, bm=256, bk=384, bn=256, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ste_wrapper_gradients():
    """Kernel-backed STE must match jnp autodiff of the core implementation:
    grad wrt x is the clip mask; grad wrt alpha is the signed overflow mass
    PLUS the scale term (q - y) * s / alpha from the differentiable
    exponent bias (see kernels/dispatch.py docstring)."""
    x = _data((32, 128), jnp.float32, seed=8)
    alpha = jnp.asarray(0.5 * float(jnp.max(jnp.abs(x))), jnp.float32)

    gk = jax.grad(lambda xx: jnp.sum(ops.quantize_det_ste(xx, alpha)))(x)
    gx_oracle = jax.grad(lambda xx: jnp.sum(fp8.quantize_det(xx, alpha)))(x)
    mask = (jnp.abs(x) <= alpha).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(mask), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gx_oracle),
                               atol=1e-6)

    ga = jax.grad(lambda a: jnp.sum(ops.quantize_det_ste(x, a)), argnums=0)(alpha)
    ga_oracle = jax.grad(lambda a: jnp.sum(fp8.quantize_det(x, a)))(alpha)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_oracle),
                               rtol=1e-5, atol=1e-5)


def test_uniform_from_bits_equals_direct_cast():
    """The Mosaic-safe u32 -> f32 conversion is the same float as the
    direct cast (one rounding of the exact integer), edge words included."""
    edges = np.array([0, 1, 0xFFFF, 0x10000, 2**24 - 1, 2**24, 2**24 + 1,
                      2**31 - 1, 2**31, 2**32 - 129, 2**32 - 128,
                      2**32 - 1], np.uint32)
    rnd = np.asarray(jax.random.bits(jax.random.PRNGKey(11), (1 << 18,),
                                     jnp.uint32))
    bits = jnp.asarray(np.concatenate([edges, rnd]))
    got = np.asarray(fp8_quant.uniform_from_bits(bits))
    want = np.asarray(bits, np.float64).astype(np.float32) * np.float32(
        1.0 / 4294967296.0)
    np.testing.assert_array_equal(got, want)
    assert got.max() <= 1.0
