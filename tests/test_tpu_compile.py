"""Mosaic compiles of every kernel the chip path dispatches to Pallas.

Each kernel is AOT-compiled at real widths for one chip of a described
``v5e:2x2`` topology (the TPU compiler is installed; no chip is attached)
and must lower to a ``tpu_custom_call``. This catches what interpret mode
cannot: casts, scalar stores and reshapes Mosaic refuses. Widths: a
4096 x 1024 wire/plane tile layout, and TinyLlama's MLP matmul
(2048 x 2048 @ 2048 x 5632).

The topology is described inside a module fixture, never at import: only
one process at a time may hold the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fp8 import E4M3, FP4_E2M1
from repro.kernels import fp8_matmul, fp8_quant

R, LANE = 4096, fp8_quant.WIRE_LANE
M, K, N = 2048, 2048, 5632


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _cases(S):
    f32, u32, u8 = jnp.float32, jnp.uint32, jnp.uint8
    plane, col, key = S((R, LANE), f32), S((R, 1), f32), S((2,), u32)
    mat, scalar = S((M, N), f32), S((), f32)
    x, w = S((M, K), f32), S((K, N), f32)
    kw = dict(interpret=False)
    return {
        "quant_pack_tiles_det": (
            lambda x2, a2: fp8_quant.quant_pack_tiles(x2, a2, None, **kw),
            (plane, col)),
        "quant_pack_tiles_rand": (
            lambda x2, a2, k: fp8_quant.quant_pack_tiles(x2, a2, k, **kw),
            (plane, col, key)),
        "quant_pack_tiles_rand_element_alpha": (
            lambda x2, a2, k: fp8_quant.quant_pack_tiles(x2, a2, k, **kw),
            (plane, plane, key)),
        "quant_pack_tiles_rand_fp4": (
            lambda x2, a2, k: fp8_quant.quant_pack_tiles(
                x2, a2, k, fmt=FP4_E2M1, **kw),
            (plane, col, key)),
        "unpack_tiles": (
            lambda c2, a2: fp8_quant.unpack_tiles(c2, a2, **kw),
            (S((R, LANE), u8), col)),
        "unpack_tiles_fp4": (
            lambda c2, a2: fp8_quant.unpack_tiles(c2, a2, fmt=FP4_E2M1, **kw),
            (S((R, LANE), u8), col)),
        "fake_quant_tiles_det": (
            lambda x2, a2: fp8_quant.fake_quant_tiles(x2, a2, None, **kw),
            (plane, col)),
        "fake_quant_tiles_rand": (
            lambda x2, a2, k: fp8_quant.fake_quant_tiles(x2, a2, k, **kw),
            (plane, col, key)),
        "quant_pack_amax_tiles_det": (
            lambda x2, a2: fp8_quant.quant_pack_amax_tiles(x2, a2, None, **kw),
            (plane, col)),
        "quant_pack_amax_tiles_rand": (
            lambda x2, a2, k: fp8_quant.quant_pack_amax_tiles(x2, a2, k, **kw),
            (plane, col, key)),
        "fake_quant_amax_tiles_det": (
            lambda x2, a2: fp8_quant.fake_quant_amax_tiles(x2, a2, None, **kw),
            (plane, col)),
        "fake_quant_amax_tiles_rand": (
            lambda x2, a2, k: fp8_quant.fake_quant_amax_tiles(x2, a2, k, **kw),
            (plane, col, key)),
        "quant_det_tiles": (
            lambda x2, a2: fp8_quant.quant_det_tiles(x2, a2, **kw),
            (plane, col)),
        "quant_det_tiles_bwd": (
            lambda x2, a2, g: fp8_quant.quant_det_tiles_bwd(x2, a2, g, **kw),
            (plane, col, plane)),
        "quant_det": (
            lambda v, a: fp8_quant.quant_det(v, a, fmt=E4M3, **kw),
            (mat, scalar)),
        "quant_det_bwd": (
            lambda v, a, g: fp8_quant.quant_det_bwd(v, a, g, **kw),
            (mat, scalar, mat)),
        "quant_rand": (
            lambda v, a, u: fp8_quant.quant_rand(v, a, u, **kw),
            (mat, scalar, S((M, N), u32))),
        "quant_rand_bwd": (
            lambda v, a, u, g: fp8_quant.quant_rand_bwd(v, a, u, g, **kw),
            (mat, scalar, S((M, N), u32), mat)),
        "qat_matmul": (
            lambda a, b, be, al: fp8_matmul.qat_matmul(a, b, be, al, **kw),
            (x, w, scalar, scalar)),
        "qat_matmul_dx": (
            lambda g, a, b, be, al: fp8_matmul.qat_matmul_dx(
                g, a, b, be, al, **kw),
            (mat, x, w, scalar, scalar)),
        "qat_matmul_dw": (
            lambda g, a, b, be, al: fp8_matmul.qat_matmul_dw(
                g, a, b, be, al, **kw),
            (mat, x, w, scalar, scalar)),
    }


CASES = sorted(_cases(lambda shape, dtype: None))


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(S)[case]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text, case
