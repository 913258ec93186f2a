"""Mosaic compiles of every kernel the chip path dispatches to Pallas.

Each kernel is AOT-compiled at real widths for one chip of a described
``v5e:2x2`` topology (the TPU compiler is installed; no chip is attached)
and must lower to a ``tpu_custom_call``. This catches what interpret mode
cannot: casts, scalar stores and reshapes Mosaic refuses. Widths: a
4096 x 1024 wire/plane tile layout, and TinyLlama's MLP matmul
(2048 x 2048 @ 2048 x 5632), and the QAT matmul with bf16 activations at
Granite-3-8B's widths, and the grouped QAT matmul at Granite-3.0-3B-A800M's
expert widths (20 experts held, 4096 tokens x 8 routed rows). A small
federated round compiles the same way: its kernels keep the names the
benchmark's readers match (``bench/kernels.py``), and each runs under a
stage scope of the round; so does a small round of the sparse-expert
cell, with nothing routed to jnp.

The topology is described inside a module fixture, never at import: only
one process at a time may hold the TPU library, and every test worker
imports this file.
"""
import collections
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fp8 import E4M3, FP4_E2M1
from repro.kernels import dispatch, fp8_matmul, fp8_quant

R, LANE = 4096, fp8_quant.WIRE_LANE
M, K, N = 2048, 2048, 5632
# The kernel families the benchmark's readers find by substring:
# ``wire.kernel_ms`` (encode, decode) and the two ``*_roofline`` metrics.
FAMILIES = ("qat_matmul", "qat_matmul_dx", "qat_matmul_dw",
            "quant_pack_tiles", "unpack_tiles",
            "qat_gmm", "qat_gmm_dx", "qat_gmm_dw")
KERNEL_CALL = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*"
                         r'custom_call_target="tpu_custom_call"')


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
        **_bf16_matmul_cases(S, kw),
        **_gmm_cases(S, kw),
    }


# Granite-3-8B's projections at 4096 tokens with bf16 activations and
# cotangents, the path whose MXU operands are bf16: attention, MLP, and
# the 6176-wide vocabulary slice of the head (not a multiple of the block).
BF16_WIDTHS = ((4096, 4096), (4096, 12800), (4096, 6176))


def _bf16_matmul_cases(S, kw):
    bf, f32 = jnp.bfloat16, jnp.float32
    scalar = S((), f32)
    cases = {}
    for k, n in BF16_WIDTHS:
        x, w, g = S((4096, k), bf), S((k, n), f32), S((4096, n), bf)
        tag = f"bf16_{k}x{n}"
        cases[f"qat_matmul_{tag}"] = (
            lambda a, b, be, al: fp8_matmul.qat_matmul(a, b, be, al, **kw),
            (x, w, scalar, scalar))
        cases[f"qat_matmul_dx_{tag}"] = (
            lambda g, a, b, be, al: fp8_matmul.qat_matmul_dx(
                g, a, b, be, al, **kw),
            (g, x, w, scalar, scalar))
        cases[f"qat_matmul_dw_{tag}"] = (
            lambda g, a, b, be, al: fp8_matmul.qat_matmul_dw(
                g, a, b, be, al, **kw),
            (g, x, w, scalar, scalar))
    return cases


# Granite-3.0-3B-A800M's experts: 20 held, (1536 -> 512) gate and up,
# (512 -> 1536) down, over the 4096 x 8 routed rows of a 4096-token step.
GMM_WIDTHS, GMM_GROUPS, GMM_ROWS = ((1536, 512), (512, 1536)), 20, 32768


def _gmm_cases(S, kw):
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    beta, alpha = S((), f32), S((GMM_GROUPS,), f32)
    offsets = S((GMM_GROUPS + 1,), i32)
    cases = {}
    for k, n in GMM_WIDTHS:
        x, g = S((GMM_ROWS, k), bf), S((GMM_ROWS, n), bf)
        w = S((GMM_GROUPS, k, n), f32)
        tag = f"bf16_{k}x{n}"
        cases[f"qat_gmm_{tag}"] = (
            lambda a, b, be, al, o: fp8_matmul.qat_gmm(a, b, be, al, o, **kw),
            (x, w, beta, alpha, offsets))
        cases[f"qat_gmm_dx_{tag}"] = (
            lambda g, a, b, be, al, o: fp8_matmul.qat_gmm_dx(
                g, a, b, be, al, o, **kw),
            (g, x, w, beta, alpha, offsets))
        cases[f"qat_gmm_dw_{tag}"] = (
            lambda g, a, b, be, al, o: fp8_matmul.qat_gmm_dw(
                g, a, b, be, al, o, **kw),
            (g, x, w, beta, alpha, offsets))
    return cases


CASES = sorted(_cases(lambda shape, dtype: None))


@pytest.mark.parametrize("case", CASES)
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(S)[case]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text, case
    # a kernel is in a family of the benchmark's readers only if it is
    # that family's kernel
    names = {_family(n) for n, _ in _kernel_calls(text)}
    want = {f for f in FAMILIES if case.startswith(f)}
    assert names == {max(want, key=len) if want else None}, (case, names)


def _kernel_calls(text):
    """(instruction name, op_name) of each Pallas kernel of an HLO text."""
    for line in text.splitlines():
        m = KERNEL_CALL.match(line)
        if m:
            on = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), on.group(1) if on else ""


def _family(instr):
    """The longest family of ``FAMILIES`` in a kernel's instruction name,
    or None."""
    found = [f for f in FAMILIES if f in instr]
    return max(found, key=len) if found else None


@pytest.fixture(scope="module")
def round_kernels(one_chip, no_persistent_cache):
    """The Pallas kernels of a one-layer LM's federated round compiled
    for one v5e chip: AdamW clients, E4M3 QAT, stochastic E4M3 wire."""
    from repro import configs, optim
    from repro.core.engine import FedConfig, RoundEngine
    from repro.core.qat import QATConfig
    from repro.models.registry import get_model

    cfg = configs.get("granite_3_8b").replace(
        n_layers=1, vocab=256, d_model=128, n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=256, ce_chunks=2)
    model = get_model(cfg)

    def loss(params, xb, yb, qat_cfg, key):
        return model.train_loss(params, {"tokens": xb, "labels": yb},
                                qat_cfg)

    fed = FedConfig(n_clients=4, participation=0.5, local_steps=1,
                    batch_size=2, comm_mode="rand", qat=QATConfig())
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_KERNEL_BACKEND", "pallas")
        before = collections.Counter(dispatch.QAT_MXU)
        eng = RoundEngine(loss, optim.adamw(1e-3, weight_decay=0.01), fed)
        state = eng.init(model.init(jax.random.PRNGKey(0)))
        tokens = np.zeros((4, 2, 64), np.int32)
        args = (state, tokens, tokens, np.ones((4,), np.float32),
                np.zeros((2,), np.uint32))
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), jnp.result_type(x), sharding=one_chip), args)
        text = jax.jit(eng.round_fn).lower(*shapes).compile().as_text()
    # the LM's bf16 activations reach all three kernels as bf16
    traced = dispatch.QAT_MXU - before
    assert {key.split(":")[0] for key in traced} == {
        "qat_matmul", "qat_matmul_dx", "qat_matmul_dw"}, traced
    assert {key.split(":")[1] for key in traced} == {"bfloat16"}, traced
    return list(_kernel_calls(text))


def test_round_kernels_keep_their_names(round_kernels):
    names = {re.sub(r"(\.\d+)+$", "", n) for n, _ in round_kernels}
    assert names == {"qat_matmul", "qat_matmul_dx", "qat_matmul_dw",
                     "quant_pack_tiles", "unpack_tiles"}
    for name in names:
        assert {f for f in FAMILIES if f in name} == (
            {"qat_matmul", name} if name.startswith("qat_matmul")
            else {name}), name


def test_round_kernels_run_under_a_stage(round_kernels):
    stage = {n: next((p for p in on.split("/") if p.startswith("fl.")),
                     None) for n, on in round_kernels}
    assert None not in stage.values(), stage
    by_kernel = {}
    for n, st in stage.items():
        by_kernel.setdefault(re.sub(r"(\.\d+)+$", "", n), set()).add(st)
    assert by_kernel == {"qat_matmul": {"fl.train"},
                         "qat_matmul_dx": {"fl.train"},
                         "qat_matmul_dw": {"fl.train"},
                         "quant_pack_tiles": {"fl.down", "fl.up"},
                         "unpack_tiles": {"fl.down", "fl.up"}}


@pytest.fixture(scope="module")
def moe_round(one_chip, no_persistent_cache):
    """A small round of the sparse-expert cell (its build, its expert
    share of half the router's experts) compiled for one v5e chip: the
    HLO text, the ops routed to jnp, and the QAT kernels traced."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import harness

    cell = harness.load_cell(
        "granite-moe-3b-a800m.silo", "granite-moe-3b-a800m",
        cfg=dict(hidden_size=128, intermediate_size=128,
                 num_attention_heads=2, num_key_value_heads=1, head_dim=64,
                 vocab_size=256, num_hidden_layers=1, router_experts=8,
                 num_experts_per_tok=2, num_local_experts=4),
        traffic=dict(rows_per_client=4, seq=64, local_steps=1, batch=2))
    inp = harness.make_inputs(cell, 7)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_KERNEL_BACKEND", "pallas")
        routes = collections.Counter(dispatch.JNP_ROUTES)
        traced = collections.Counter(dispatch.QAT_MXU)
        eng, state = cell.sut.build(cell.cfg, cell.traffic, inp.params(),
                                    inp.data, inp.labels, inp.nk)
        args = (state, inp.data, inp.labels, inp.nk, inp.keys[0])
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), jnp.result_type(x), sharding=one_chip), args)
        text = jax.jit(eng.round_fn).lower(*shapes).compile().as_text()
    return (text, dispatch.JNP_ROUTES - routes, dispatch.QAT_MXU - traced)


def test_moe_round_runs_every_expert_matmul_in_the_kernels(moe_round):
    text, routes, traced = moe_round
    assert not routes, routes
    assert {k for k in traced if k.startswith("qat_gmm")} == {
        "qat_gmm:bfloat16", "qat_gmm_dx:bfloat16", "qat_gmm_dw:bfloat16"}
    gmm = [(n, on) for n, on in _kernel_calls(text) if "qat_gmm" in n]
    assert {_family(n) for n, _ in gmm} == {
        "qat_gmm", "qat_gmm_dx", "qat_gmm_dw"}
    for n, on in gmm:
        parts = on.split("/")
        assert "fl.train" in parts and "moe.experts" in parts, (n, on)
