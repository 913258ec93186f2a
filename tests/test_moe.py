"""Sparse experts: the grouped FP8-QAT matmul kernels, the expert-share
layer, and a tiny Granite-MoE model against the benchmark's plain
reference.

* ``qat_gmm``, ``qat_gmm_dx`` and ``qat_gmm_dw`` (``kernels/fp8_matmul.py``,
  Pallas bodies interpreted) against jnp autodiff of a per-group
  composition of ``core.fp8.quantize_det``: the output, dx, dw, and the
  activation clip's and every group's weight clip's cotangents, on
  ragged groups, an empty group, groups straddling a row tile, and rows
  past the last group's end.
* The expert share: the outputs of the layers holding experts 0-19 and
  20-39 add up to the uncut 40-expert layer, which matches a dense
  reference that applies every expert to every token.
* A tiny ``granite-moe-3b-a800m`` through ``RoundEngine`` against
  ``bench/configs/granite-moe-3b-a800m.ref.py`` on the same seeded weights.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import MoEConfig
from repro.core import fp8
from repro.core.qat import QATConfig
from repro.kernels import dispatch, fp8_matmul
from repro.models import moe

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def interpret_backend(monkeypatch):
    monkeypatch.setenv(dispatch._ENV, "interpret")


def _rel_close(got, want, tol=1e-5):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-6)
    err = np.max(np.abs(got - want)) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol:g}"


def _oracle(x, w, beta, alpha, sizes):
    """Per group, ``Q(x rows; beta) @ Q(w[e]; alpha[e])``; zero rows past
    the groups' end."""
    parts, r = [], 0
    for e, n in enumerate(sizes):
        parts.append(jnp.dot(fp8.quantize_det(x[r:r + n], beta),
                             fp8.quantize_det(w[e], alpha[e]),
                             preferred_element_type=jnp.float32))
        r += n
    parts.append(jnp.zeros((x.shape[0] - r, w.shape[2]), jnp.float32))
    return jnp.concatenate(parts)


# (M, K, N, group sizes, (bm, bk, bn)): rows past the end, K and N not
# multiples of their blocks, groups straddling row tiles; an empty group
# between, first and last; three groups inside one tile; groups that end
# on tile boundaries and fill every row.
GMM_CASES = {
    "ragged": (64, 160, 96, [5, 21, 9, 13], (16, 128, 64)),
    "empty_groups": (40, 48, 24, [0, 9, 0, 17, 6, 0], (8, 16, 8)),
    "one_tile": (32, 64, 64, [3, 2, 4], (16, 32, 32)),
    "aligned_full": (32, 32, 32, [16, 0, 16], (16, 32, 32)),
}


@pytest.mark.parametrize("case", list(GMM_CASES))
def test_qat_gmm_kernels_match_autodiff(case):
    m, k, n, sizes, (bm, bk, bn) = GMM_CASES[case]
    G = len(sizes)
    kx, kw, kg, ka = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(kx, (m, k), jnp.float32) * 0.5
    w = jax.random.normal(kw, (G, k, n), jnp.float32) * 0.1
    beta = jnp.asarray(0.8, jnp.float32)
    # clips below each expert's max |w|, so that some weights clip
    alpha = (jnp.max(jnp.abs(w), axis=(1, 2))
             * jax.random.uniform(ka, (G,), minval=0.5, maxval=0.9))
    end = sum(sizes)
    g = jax.random.normal(kg, (m, n), jnp.float32)
    g = jnp.where(jnp.arange(m)[:, None] < end, g, 0.0)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    blocks = dict(bm=bm, bk=bk, bn=bn, interpret=True)

    # jitted like the interpreted kernels: XLA's CPU log2/exp2 differ
    # between a fused and an op-by-op evaluation, which moves a rounding
    oracle = jax.jit(lambda *a: _oracle(*a, sizes))
    out = fp8_matmul.qat_gmm(x, w, beta, alpha, offsets, **blocks)
    _rel_close(out, oracle(x, w, beta, alpha))
    assert not np.any(np.asarray(out[end:]))

    gx_o, gw_o, gb_o, ga_o = jax.jit(jax.grad(
        lambda *a: jnp.sum(oracle(*a) * g), argnums=(0, 1, 2, 3)))(
        x, w, beta, alpha)
    gx, gb = fp8_matmul.qat_gmm_dx(g, x, w, beta, alpha, offsets, **blocks)
    gw, ga = fp8_matmul.qat_gmm_dw(g, x, w, beta, alpha, offsets, **blocks)
    _rel_close(gx, gx_o)
    _rel_close(gb, gb_o)
    _rel_close(gw, gw_o)
    _rel_close(ga, ga_o)
    assert ga.shape == (G,) and gw.shape == w.shape
    for e, size in enumerate(sizes):
        if size == 0:
            assert float(ga[e]) == 0.0 and not np.any(np.asarray(gw[e]))


def test_qat_gmm_dispatch_counts_bf16_kernels(interpret_backend):
    """The dispatcher's custom VJP runs the three kernels, counted in
    ``QAT_MXU`` with bf16 MXU operands for bf16 activations, and agrees
    with the jnp composition the model takes off the kernels
    (:func:`dispatch.gmm` of the fake-quantized operands)."""
    m, k, n, sizes = 48, 64, 32, [7, 0, 20, 9]
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(5), 3)
    x = (jax.random.normal(kx, (m, k)) * 0.5).astype(jnp.bfloat16)
    w = jax.random.normal(kw, (len(sizes), k, n)) * 0.1
    g = jax.random.normal(kg, (m, n)).astype(jnp.bfloat16)
    beta = jnp.asarray(0.8, jnp.float32)
    alpha = jnp.max(jnp.abs(w), axis=(1, 2)) * 0.7
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)

    def grads():
        return jax.grad(lambda x, w, b, a: jnp.sum(
            dispatch.qat_gmm(x, w, b, a, offsets).astype(jnp.float32)
            * g.astype(jnp.float32)), argnums=(0, 1, 2, 3))(x, w, beta, alpha)

    dispatch.QAT_MXU.clear()
    got = grads()
    assert dict(dispatch.QAT_MXU) == {
        "qat_gmm:bfloat16": 1, "qat_gmm_dx:bfloat16": 1,
        "qat_gmm_dw:bfloat16": 1}
    want = jax.grad(lambda x, w, b, a: jnp.sum(dispatch.gmm(
        fp8.quantize_det(x.astype(jnp.float32), b),
        fp8.quantize_det(w, a.reshape(-1, 1, 1)), offsets).astype(x.dtype)
        .astype(jnp.float32) * g.astype(jnp.float32)),
        argnums=(0, 1, 2, 3))(x, w, beta, alpha)
    assert got[0].dtype == jnp.bfloat16
    # dx carries its bf16 output rounding; the jnp path's bf16 cotangent
    # of a bf16 x rounds once more
    _rel_close(got[0], want[0], 2.0 ** -7)
    _rel_close(got[1], want[1], 1e-5)


@pytest.mark.parametrize("case", list(GMM_CASES))
def test_gmm_matches_per_group_products(case):
    """The jnp grouped matmul, its gradients and its ``vmap`` against the
    per-group products, on the kernels' cases."""
    m, k, n, sizes, _ = GMM_CASES[case]
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (len(sizes), k, n), jnp.float32)
    g = jax.random.normal(kg, (m, n), jnp.float32)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)

    def per_group(x, w):
        parts, r = [], 0
        for e, size in enumerate(sizes):
            parts.append(x[r:r + size] @ w[e])
            r += size
        return jnp.concatenate(parts + [jnp.zeros((m - r, n))])

    _rel_close(dispatch.gmm(x, w, offsets), per_group(x, w))
    got = jax.grad(lambda x, w: jnp.sum(dispatch.gmm(x, w, offsets) * g),
                   argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(per_group(x, w) * g),
                    argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        _rel_close(a, b)
    both = jax.vmap(dispatch.gmm)(jnp.stack([x, -x]), jnp.stack([w, w]),
                                  jnp.stack([offsets, offsets]))
    _rel_close(both[1], -per_group(x, w))


def test_gmm_work_scales_with_the_rows():
    """The jnp grouped matmul's compiled work is at most 1.5 times the
    rows' own products, with 40 groups, plus its masks and scatter (a
    product per group of every row would be 40 times)."""
    m, k, n, groups = 4096, 64, 32, 40
    args = (jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
            jax.ShapeDtypeStruct((groups + 1,), jnp.int32))
    cost = jax.jit(dispatch.gmm).lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    assert 2 * m * k * n <= cost["flops"] <= 1.6 * 2 * m * k * n


# ---------------------------------------------------------------------------
# The expert share
# ---------------------------------------------------------------------------

E, K, D, F, N = 40, 8, 32, 16, 24


def _layer(key):
    ks = jax.random.split(key, 5)
    p = {"router": jax.random.normal(ks[0], (D, E)) * 0.3,
         "mlp_qb": jnp.asarray(3.0), "down_qb": jnp.asarray(2.0)}
    for i, (name, shape) in enumerate((("we_gate", (E, D, F)),
                                       ("we_up", (E, D, F)),
                                       ("we_down", (E, F, D)))):
        p[name] = jax.random.normal(ks[1 + i], shape) / np.sqrt(shape[1])
        p[name + "_qa"] = 0.8 * jnp.max(jnp.abs(p[name]), axis=(1, 2),
                                        keepdims=True)
    x = jax.random.normal(ks[4], (2, N // 2, D)).astype(jnp.bfloat16)
    return p, x


def _share(p, first, held):
    return {k: v[first:first + held] if k.startswith("we_") else v
            for k, v in p.items()}


def _cfg(held=None, first=0):
    return configs.reduced(configs.get("granite_moe_3b_a800m")).replace(
        d_model=D, d_ff=F,
        moe=MoEConfig(n_experts=E, top_k=K, experts_here=held,
                      first_expert=first))


def _dense_reference(p, x):
    """Every expert on every token, weighted by the token's gate for it
    (zero where not chosen), in f32 with the layer's fake-quant."""
    xf = x.reshape(-1, D).astype(jnp.float32)
    top, chosen = jax.lax.top_k(xf @ p["router"], K)
    gate = jnp.sum(jax.nn.softmax(top, -1)[:, :, None]
                   * (chosen[:, :, None] == jnp.arange(E)), axis=1)
    xq = fp8.quantize_det(xf, p["mlp_qb"])

    def w(name):
        return fp8.quantize_det(p[name], p[name + "_qa"])

    g = jnp.einsum("nd,edf->enf", xq, w("we_gate"))
    u = jnp.einsum("nd,edf->enf", xq, w("we_up"))
    h = fp8.quantize_det(jax.nn.silu(g) * u, p["down_qb"])
    y = jnp.einsum("enf,efd->end", h, w("we_down"))
    return jnp.einsum("end,ne->nd", y, gate).reshape(x.shape)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_expert_shares_add_up_to_the_uncut_layer(backend, monkeypatch):
    monkeypatch.setenv(dispatch._ENV, backend)
    p, x = _layer(jax.random.PRNGKey(11))
    q = QATConfig()
    whole = moe.moe_ffn(p, x, _cfg(), q).astype(jnp.float32)
    parts = [moe.moe_ffn(_share(p, first, 20), x, _cfg(20, first), q)
             for first in (0, 20)]
    summed = sum(y.astype(jnp.float32) for y in parts)
    # each share's output and the whole's are rounded to bf16 once
    _rel_close(summed, whole, 2.0 ** -7)
    # the program keeps its activations in bf16, the reference in f32
    _rel_close(whole, _dense_reference(p, x), 2.0 ** -5)
    assert all(float(jnp.max(jnp.abs(y))) > 0 for y in parts)


def test_routing_drops_no_token():
    """Every token routed to a held expert reaches it, however skewed the
    routing: with a router that sends every token to experts 0-7, the
    share holding them computes all of them and the other nothing."""
    p, x = _layer(jax.random.PRNGKey(12))
    p["router"] = jnp.zeros((D, E)).at[:, :K].set(1.0) \
        + jax.random.normal(jax.random.PRNGKey(2), (D, E)) * 1e-3
    x = jnp.abs(x)                 # every token's top 8 are experts 0-7
    q = QATConfig()
    lo = moe.moe_ffn(_share(p, 0, 20), x, _cfg(20, 0), q)
    hi = moe.moe_ffn(_share(p, 20, 20), x, _cfg(20, 20), q)
    assert not np.any(np.asarray(hi))
    _rel_close(lo.astype(jnp.float32), _dense_reference(p, x), 2.0 ** -5)


def test_plane_quantizes_each_expert_with_its_own_clip():
    """The parameter plane takes per-(layer, expert) clips ``(L, E, 1, 1)``
    as one segment per (layer, expert) slab: its one-launch fake-quant
    equals each slab quantized with its own clip, and each clip gets its
    own slab's gradient."""
    from repro.core import plane

    w = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 40, 24))
    a = 0.7 * jnp.max(jnp.abs(w), axis=(2, 3), keepdims=True)
    params = {"we": w, "we_qa": a}
    spec = plane.make_plane_spec(params)
    assert spec.n_seg == 6 and spec.leaf_segs == (6,)
    got = plane.quantize_det(params)["we"]
    want = jnp.stack([jnp.stack([fp8.quantize_det(w[i, e], a[i, e])
                                 for e in range(3)]) for i in range(2)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def loss(q):
        return lambda p: jnp.sum(q(p) ** 2)

    g_plane = jax.grad(loss(lambda p: plane.quantize_det(p)["we"]))(params)
    g_leaf = jax.grad(loss(lambda p: fp8.quantize_det(p["we"], p["we_qa"])))(
        params)
    _rel_close(g_plane["we_qa"], g_leaf["we_qa"])


# ---------------------------------------------------------------------------
# A tiny Granite-MoE against the benchmark's reference
# ---------------------------------------------------------------------------

TINY = dict(hidden_size=128, intermediate_size=128, num_attention_heads=2,
            num_key_value_heads=1, head_dim=64, vocab_size=256,
            num_hidden_layers=2, router_experts=8, num_experts_per_tok=2,
            num_local_experts=4, first_local_expert=2)


@pytest.fixture(scope="module")
def tiny_cell():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness

    return harness, harness.load_cell(
        "granite-moe-3b-a800m.silo", "granite-moe-3b-a800m", cfg=TINY,
        traffic=dict(rows_per_client=4, seq=16, local_steps=2, batch=2))


def _weight_grads(grads):
    """The gradient of every leaf but the clips, as one vector (a clip's
    gradient is a sum of rounding residuals)."""
    from bench import reference

    return jnp.concatenate([
        g.reshape(-1) for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]
        if not reference.dotted(p).endswith(("_qa", "_qb"))])


def test_tiny_model_loss_and_grads_match_reference(tiny_cell):
    """The program's loss and gradient against the reference's at the
    configuration's bfloat16 activations, within twice what rounding alone
    moves the reference: at this size one step's gradient moves by 10-45%
    per leaf between the reference at bfloat16 and at float32, as E4M3
    rounding decisions flip."""
    harness, cell = tiny_cell
    from bench import reference
    from repro.models.registry import get_model

    inp = harness.make_inputs(cell, 2 ** 31 + 99)
    params = inp.params()
    model = get_model(cell.sut.model_config(cell.cfg))
    tokens, labels = inp.data[0, :2], inp.labels[0, :2]
    got, g_got = jax.value_and_grad(model.train_loss)(
        params, {"tokens": tokens, "labels": labels}, QATConfig())
    ref_loss = jax.value_and_grad(cell.ref.make_loss(cell.cfg))
    want, g_want = ref_loss(params, tokens, labels,
                            reference.Quant(jnp.bfloat16))
    _, g_f32 = ref_loss(params, tokens, labels, reference.Quant(jnp.float32))
    assert abs(float(got) - float(want)) <= 5e-3 * abs(float(want))
    g_want = _weight_grads(g_want)

    def gap(g):
        return float(jnp.linalg.norm(_weight_grads(g) - g_want)
                     / jnp.linalg.norm(g_want))

    assert gap(g_got) <= 2 * gap(g_f32), (gap(g_got), gap(g_f32))


def test_tiny_round_engine_matches_reference(tiny_cell):
    """One federated round of the tiny model through ``RoundEngine`` (the
    cell's build, jnp backend) against the reference's round."""
    harness, cell = tiny_cell
    inp = harness.make_inputs(cell, 2 ** 31 + 17)
    engine, state = cell.sut.build(cell.cfg, cell.traffic, inp.params(),
                                   inp.data, inp.labels, inp.nk)
    step, routes = harness.compile_round(engine, state, inp)
    assert not routes
    _, got = harness.program_rounds(step, state, inp, read_at=1, n=1)
    ref = harness.reference_rounds(cell, inp, 1)
    nums = harness.compare(got, ref, 1)
    assert nums["loss_gap.r1"] <= 2e-3, nums
    assert nums["grad_gap.median"] <= 0.01, nums
