"""Static-table rANS entropy coding (``core.entropy`` + ``kernels.rans``).

Property suite (hypothesis where available, with seeded hypothesis-less
twins that always run) for the load-bearing invariants:

* exact roundtrip — ``decode(encode(syms)) == syms`` for ARBITRARY byte
  streams, including ones the Gaussian table model considers improbable
  (the >=1 frequency floor is what guarantees this);
* the two-lane byte contract — the static structural bound
  (``payload_nbytes``) dominates the traced coded size
  (``payload_nbytes_traced``) for every payload, and the engine's traced
  ``wire_bytes`` stays under its static ``round_bytes`` bound;
* table integrity — frequencies sum to exactly ``TAB`` with a >=1 floor
  (which caps the max frequency inside the int32-safe region), cum is
  the exclusive prefix sum, ``slot2sym`` inverts it;
* losslessness at the codec layer — a ``rans:``-wrapped leg decodes to
  the inner codec's values bitwise, and ``fake_quant`` observes exactly
  the inner codec's values.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import codec as codec_lib
from repro.core import fp8, metrics, wire
from repro.core.codec import CodecSchedule, Fp32Codec, get_codec
from repro.core.engine import FedConfig, RoundEngine
from repro.core.entropy import (RansCodec, SIGMA_DELTA, SIGMA_PLAIN,
                                _unpack_np, byte_table, code_probabilities)
from repro.core.fp8 import E4M3, E5M2, FP4_E2M1, FP4_E3M0
from repro.core.qat import QATConfig, clip_value_mask, weight_decay_mask
from repro.data import partition_iid, synthetic_classification
from repro.kernels import rans as rk
from repro.models import small

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # hypothesis-less twins below still cover the core
    HAVE_HYP = False

FMTS = [E4M3, E5M2, FP4_E2M1, FP4_E3M0]


# --------------------------------------------------------------------------
# table integrity
# --------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f"e{f.exp}m{f.mant}")
@pytest.mark.parametrize("sigma", [SIGMA_PLAIN, SIGMA_DELTA, 0.5])
def test_table_integrity(fmt, sigma):
    freq, cum, s2s = byte_table(fmt, sigma)
    assert freq.shape == (256,) and cum.shape == (256,)
    assert s2s.shape == (rk.TAB,)
    assert int(freq.sum()) == rk.TAB
    assert int(freq.min()) >= 1
    # the >=1 floor over 256 symbols is the int32-overflow guard
    assert int(freq.max()) <= rk.TAB - 255
    np.testing.assert_array_equal(
        cum, np.concatenate([[0], np.cumsum(freq)[:-1]]))
    for s in (0, 17, 255):
        assert np.all(s2s[cum[s]:cum[s] + freq[s]] == s)


@pytest.mark.parametrize("fmt", FMTS, ids=lambda f: f"e{f.exp}m{f.mant}")
def test_code_probabilities_normalized(fmt):
    p = code_probabilities(fmt, 0.25)
    assert p.shape == (1 << fmt.bits,)
    assert np.all(p > 0)
    np.testing.assert_allclose(p.sum(), 1.0, rtol=1e-9)


@pytest.mark.parametrize("fmt", [E4M3, E5M2], ids=lambda f: f"e{f.exp}m{f.mant}")
def test_unpack_np_matches_jnp_grid_index(fmt):
    """The pure-numpy decoder twin maps every code to the same grid
    point as the jnp wire decoder (grid-INDEX comparison: the values are
    identical grid points, indexes absorb float-representation noise)."""
    n_codes = 1 << fmt.bits
    codes = np.arange(n_codes)
    v_np = _unpack_np(codes, fmt)
    v_j = np.asarray(
        fp8.unpack_fp8(jnp.asarray(codes, jnp.uint8), jnp.asarray(1.0),
                       fmt=fmt), np.float64)
    grid = np.asarray(fp8.quantization_grid(1.0, fmt), np.float64)
    gi_np = np.abs(grid[None, :] - np.abs(v_np)[:, None]).argmin(1)
    gi_j = np.abs(grid[None, :] - np.abs(v_j)[:, None]).argmin(1)
    np.testing.assert_array_equal(gi_np, gi_j)
    np.testing.assert_array_equal(np.sign(v_np), np.sign(v_j))


# --------------------------------------------------------------------------
# rANS coder: roundtrip + bound + backend identity (hypothesis-less twins)
# --------------------------------------------------------------------------
def _roundtrip(syms_np, fmt=FP4_E2M1, sigma=0.2):
    freq, cum, s2s = (jnp.asarray(a) for a in byte_table(fmt, sigma))
    syms = jnp.asarray(syms_np, jnp.int32)
    buf, state, lens = rk.rans_encode(syms, freq, cum)
    n = len(syms_np)
    assert buf.shape == (rk.LANES, rk.buf_cols(n))
    coded = int(jnp.sum(lens))
    assert coded <= rk.LANES * rk.buf_cols(n)  # static bound dominates
    out = rk.rans_decode(buf, state, lens, n, freq, cum, s2s)
    np.testing.assert_array_equal(np.asarray(out), syms_np)
    return coded


@pytest.mark.parametrize("n", [1, rk.LANES - 1, rk.LANES, rk.LANES + 1,
                               333, 1024])
def test_roundtrip_sizes(n):
    rng = np.random.RandomState(n)
    _roundtrip(rng.randint(0, 256, n))


@pytest.mark.parametrize("stream", ["zeros", "max", "uniform", "skewed"])
def test_roundtrip_distributions(stream):
    rng = np.random.RandomState(7)
    n = 700
    if stream == "zeros":
        syms = np.zeros(n, np.int64)
    elif stream == "max":
        syms = np.full(n, 255)
    elif stream == "uniform":
        syms = rng.randint(0, 256, n)
    else:  # table-skewed: drawn FROM the static table (the matched case)
        _, _, s2s = byte_table(FP4_E2M1, 0.2)
        syms = s2s[rng.randint(0, rk.TAB, n)]
    coded = _roundtrip(syms)
    if stream == "skewed":
        assert coded < n  # matched prior actually compresses


def test_improbable_symbols_decodable():
    """Symbols the Gaussian model gives its floor frequency must still
    code exactly — the invariant that makes a mismatched sigma a
    compression-ratio problem, never a correctness problem."""
    freq, _, _ = byte_table(FP4_E2M1, 0.02)  # extreme prior
    rare = np.argsort(freq)[:8]
    syms = np.repeat(rare, 50)
    _roundtrip(syms, sigma=0.02)


if HAVE_HYP:

    @settings(max_examples=30, deadline=None)
    @given(data=st.data())
    def test_roundtrip_property(data):
        n = data.draw(st.integers(min_value=1, max_value=600))
        fmt = data.draw(st.sampled_from(FMTS))
        sigma = data.draw(st.floats(min_value=0.02, max_value=0.8))
        seed = data.draw(st.integers(min_value=0, max_value=2**31 - 1))
        syms = np.random.RandomState(seed).randint(0, 256, n)
        _roundtrip(syms, fmt=fmt, sigma=sigma)


# --------------------------------------------------------------------------
# codec layer: losslessness, bound >= traced, validation
# --------------------------------------------------------------------------
def _params():
    init, _ = small.REGISTRY["mlp"]
    return init(jax.random.PRNGKey(0), d_in=16, n_classes=4)


@pytest.mark.parametrize("inner", ["fp4_e2m1", "e4m3", "delta:fp4_e2m1"])
def test_rans_codec_lossless(inner):
    p = _params()
    spec = wire.make_wire_spec(p)
    ic = get_codec(inner)
    rc = RansCodec(ic)
    key = jax.random.PRNGKey(3)
    ref = p if inner.startswith("delta:") else None
    want = ic.decode(ic.encode(p, spec, key, ref=ref), spec, ref=ref)
    got = rc.decode(rc.encode(p, spec, key, ref=ref), spec, ref=ref)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fq_w = ic.fake_quant(p, spec, key, ref=ref)
    fq_g = rc.fake_quant(p, spec, key, ref=ref)
    for a, b in zip(jax.tree.leaves(fq_w), jax.tree.leaves(fq_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("inner", ["fp4_e2m1", "e4m3", "delta:fp4_e2m1"])
def test_bound_dominates_traced(inner):
    p = _params()
    spec = wire.make_wire_spec(p)
    rc = get_codec(f"rans:{inner}")
    ref = p if inner.startswith("delta:") else None
    payload = rc.encode(p, spec, jax.random.PRNGKey(5), ref=ref)
    traced = int(rc.payload_nbytes_traced(payload, spec))
    bound = rc.payload_nbytes(spec)
    assert 0 < traced <= bound
    # the bound is what metrics reports for the static lane
    assert codec_lib.leg_nbytes(rc, spec) == bound


def test_rans_validation():
    with pytest.raises(ValueError, match="grid codec"):
        RansCodec(Fp32Codec())
    with pytest.raises(ValueError, match="sigma"):
        RansCodec(get_codec("e4m3"), sigma=-0.1)
    with pytest.raises(ValueError, match="CodecSchedule cannot hold"):
        CodecSchedule((RansCodec(get_codec("e4m3")), "e4m3"), (2,))
    # registry names resolve recursively, incl. bare default
    assert get_codec("rans").tag == "rans:e4m3"
    assert get_codec("rans:delta:fp4_e2m1").tag == "rans:delta:fp4_e2m1"


def _mini_fed(down, up, n_clients=6):
    xall, yall = synthetic_classification(0, 600, d=16, n_classes=4)
    cx, cy, nk = partition_iid(xall, yall, k=n_clients, seed=0)
    init, apply = small.REGISTRY["mlp"]
    params = init(jax.random.PRNGKey(0), d_in=16, n_classes=4)
    loss = small.make_loss(apply)
    opt = optim.sgd(0.05, wd_mask=weight_decay_mask(params),
                    trust_mask=clip_value_mask(params))
    cfg = FedConfig(n_clients=n_clients, participation=0.5, local_steps=2,
                    batch_size=8, qat=QATConfig(), comm_mode="rand",
                    down_codec=down, up_codec=up)
    return (params, loss, opt, cfg,
            (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(nk)))


def test_engine_traced_under_bound():
    """Two jitted rounds of a rans-legged engine: wire_bytes charges the
    true coded size, strictly positive and never above the static
    round_bytes bound; metrics.round_bytes_for agrees with the bound."""
    params, loss, opt, cfg, (cx, cy, nk) = _mini_fed(
        "rans:fp4_e2m1", "rans:delta:fp4_e2m1")
    eng = RoundEngine(loss, opt, cfg)
    assert eng.dynamic
    bound = eng.round_bytes(params)
    assert bound == metrics.round_bytes_for(params, cfg)
    state = eng.init(params)
    rf = jax.jit(eng.round_fn)
    key = jax.random.PRNGKey(11)
    seen = []
    for r in range(2):
        key, k = jax.random.split(key)
        state, m = rf(state, cx, cy, nk, k)
        wb = int(m["wire_bytes"])
        assert 0 < wb <= bound
        seen.append(wb)
    # entropy-coded sizes are data-dependent: consecutive rounds differ
    assert seen[0] != seen[1]


def test_async_engine_rejects_rans():
    from repro.core.async_engine import AsyncConfig, BufferedAsyncEngine

    params, loss, opt, cfg, _ = _mini_fed("rans:fp4_e2m1", "e4m3")
    with pytest.raises(ValueError, match="[Rr]ans"):
        BufferedAsyncEngine(loss, opt, cfg, AsyncConfig(buffer_size=2))


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >=2 devices")
def test_sharded_executor_rejects_rans():
    from repro.launch.mesh import make_client_mesh

    params, loss, opt, cfg, _ = _mini_fed("rans:fp4_e2m1", "e4m3")
    import dataclasses as dc
    cfg = dc.replace(cfg, mesh=make_client_mesh(2))
    with pytest.raises(ValueError, match="ShardedExecutor"):
        RoundEngine(loss, opt, cfg)
