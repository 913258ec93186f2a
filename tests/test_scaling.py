"""ScalingPolicy (ISSUE 8): TE-style delayed/frozen FP8 wire scaling.

The load-bearing contracts, in rough order of importance:

* ``current`` (and the no-knob default) leaves every round builder on the
  ORIGINAL code path — bit-identical states and metrics, local and
  sharded, for every seed tested.
* ``frozen`` downlink decodes bitwise-identically to ``current`` (the
  receiver splices back the alpha values it already holds) while the
  payload drops 4 bytes per quantized leaf — verified against both the
  static accounting and the traced ``wire_bytes``.
* ``delayed`` threads a rolling ``(H, n_q)`` amax history through
  ``ServerState.scales``: the window rotates, the margin is an exact
  power-of-two shift (mantissas untouched), and the effective scale never
  under-estimates any amax the history saw.  The history row is produced
  by the fused quantize+amax launch — no standalone amax reduction in the
  encode path (pinned by the jaxpr launch-count test, which also covers
  the DeltaCodec residual-amax fusion).

The amax-history semantics run twice: hypothesis-generated inputs when
hypothesis is installed, and fixed-vector twins that always run (the
environment ships without hypothesis; the twins carry the coverage).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import codec as codec_lib
from repro.core import fp8, metrics, scaling, wire
from repro.core.engine import (
    FedConfig,
    RoundEngine,
    ServerState,
    ShardedExecutor,
    WireLink,
)
from repro.core.faults import FaultModel
from repro.core.qat import (
    QATConfig,
    alpha_like,
    clip_value_mask,
    weight_decay_mask,
)
from repro.data import partition_iid, synthetic_classification
from repro.models import small

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _mlp_setup(k=6, n=600, d=16, n_classes=4):
    xall, yall = synthetic_classification(0, n + 300, d=d, n_classes=n_classes)
    cx, cy, nk = partition_iid(xall[:n], yall[:n], k=k, seed=0)
    init, apply = small.REGISTRY["mlp"]
    params = init(jax.random.PRNGKey(0), d_in=d, n_classes=n_classes)
    loss = small.make_loss(apply)
    opt = optim.sgd(0.05, wd_mask=weight_decay_mask(params),
                    trust_mask=clip_value_mask(params))
    return params, loss, apply, opt, (jnp.asarray(cx), jnp.asarray(cy),
                                      jnp.asarray(nk))


def _assert_trees_equal(a, b, msg=""):
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb),
                                      err_msg=msg)


_BASE = dict(n_clients=6, participation=0.5, local_steps=2, batch_size=8,
             comm_mode="rand", qat=QATConfig())


# ---------------------------------------------------------------------------
# Policy resolution (the deprecation map: no knob == 'current')
# ---------------------------------------------------------------------------


def test_get_policy_resolution():
    assert scaling.get_policy(None) is scaling.CURRENT
    assert scaling.get_policy("") is scaling.CURRENT
    assert scaling.get_policy("current") is scaling.CURRENT
    assert isinstance(scaling.get_policy("frozen"),
                      scaling.PerRoundFrozenScaling)
    assert isinstance(scaling.get_policy("per_round_frozen"),
                      scaling.PerRoundFrozenScaling)
    d = scaling.get_policy("delayed:4:1")
    assert isinstance(d, scaling.DelayedScaling)
    assert (d.history_len, d.margin) == (4, 1)
    assert scaling.get_policy("delayed:8").history_len == 8
    assert scaling.get_policy("delayed").history_len == 16
    # instance passthrough
    assert scaling.get_policy(d) is d
    with pytest.raises(ValueError, match="unknown scaling policy"):
        scaling.get_policy("amax_ema")
    with pytest.raises(ValueError, match="bad delayed scaling"):
        scaling.get_policy("delayed:4:1:9")
    with pytest.raises(TypeError):
        scaling.get_policy(3.5)
    with pytest.raises(ValueError, match="history_len"):
        scaling.DelayedScaling(history_len=0)


def test_policy_flags():
    assert scaling.CURRENT.is_current and not scaling.CURRENT.stateful
    assert scaling.DelayedScaling().stateful
    assert not scaling.PerRoundFrozenScaling().stateful
    assert not scaling.DelayedScaling().is_current


# ---------------------------------------------------------------------------
# Amax-history semantics — hypothesis-less twins (always run)
# ---------------------------------------------------------------------------


def test_delayed_window_rotation_twin():
    """update() drops the oldest row and appends the new one — the history
    after k updates is exactly the last H rows of [seed rows; appended]."""
    pol = scaling.DelayedScaling(history_len=3)
    hist = pol.init_state(jnp.asarray([1.0, 2.0]))
    assert hist.shape == (3, 2)
    rows = [jnp.asarray([0.5, 4.0]), jnp.asarray([3.0, 0.1]),
            jnp.asarray([0.2, 0.2]), jnp.asarray([9.0, 9.0])]
    seen = [jnp.asarray([1.0, 2.0])] * 3
    for r in rows:
        hist = pol.update(hist, r)
        seen.append(r)
        np.testing.assert_array_equal(
            np.asarray(hist), np.stack([np.asarray(x) for x in seen[-3:]])
        )


def test_delayed_history_one_is_pure_current_amax():
    """H=1 degenerates to last-round amax only (TE's amax_history_len=1)."""
    pol = scaling.DelayedScaling(history_len=1)
    hist = pol.init_state(jnp.asarray([7.0]))
    hist = pol.update(hist, jnp.asarray([0.25]))
    np.testing.assert_array_equal(np.asarray(hist), [[0.25]])
    np.testing.assert_array_equal(np.asarray(pol.effective(hist)), [0.25])


def test_delayed_margin_exact_power_of_two_twin():
    """margin=M multiplies the scale by exactly 2**M: the scaled bits are
    the unscaled bits with the exponent bumped — mantissas untouched."""
    hist = jnp.asarray([[0.7, 3.1e-2], [1.3, 5.5e-3]], jnp.float32)
    base = scaling.DelayedScaling(history_len=2, margin=0).effective(hist)
    for m in (-2, -1, 1, 2, 4):
        got = scaling.DelayedScaling(history_len=2, margin=m).effective(hist)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(base) * np.float32(2.0) ** m
        )


def test_delayed_monotone_underestimation_bound_twin():
    """effective(hist) never under-estimates any amax in the window: every
    value any history round saw stays inside the clip range."""
    pol = scaling.DelayedScaling(history_len=4)
    hist = jnp.asarray(
        [[0.5, 2.0], [4.0, 0.1], [0.25, 0.3], [1.0, 1.0]], jnp.float32
    )
    eff = np.asarray(pol.effective(hist))
    assert (eff[None, :] >= np.asarray(hist)).all()
    np.testing.assert_array_equal(eff, np.max(np.asarray(hist), axis=0))


def test_delayed_effective_floors():
    pol = scaling.DelayedScaling(history_len=2)
    hist = jnp.zeros((2, 3), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(pol.effective(hist)),
        np.full((3,), float(fp8._ALPHA_FLOOR), np.float32),
    )


# ---------------------------------------------------------------------------
# Amax-history semantics — hypothesis suite (skipped w/o hypothesis)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    amaxes = st.floats(min_value=np.float32(1e-6), max_value=1e4, allow_nan=False,
                       allow_infinity=False, width=32)

    @settings(max_examples=30, deadline=None)
    @given(rows=st.lists(st.lists(amaxes, min_size=2, max_size=2),
                         min_size=1, max_size=8),
           h=st.integers(min_value=1, max_value=4))
    def test_hyp_window_rotation(rows, h):
        pol = scaling.DelayedScaling(history_len=h)
        seed = jnp.asarray([1.0, 1.0])
        hist = pol.init_state(seed)
        seen = [np.asarray(seed, np.float32)] * h
        for r in rows:
            hist = pol.update(hist, jnp.asarray(r, jnp.float32))
            seen.append(np.asarray(r, np.float32))
        np.testing.assert_array_equal(np.asarray(hist), np.stack(seen[-h:]))

    @settings(max_examples=30, deadline=None)
    @given(vals=st.lists(amaxes, min_size=2, max_size=8),
           m=st.integers(min_value=-4, max_value=4))
    def test_hyp_margin_exact_pow2(vals, m):
        hist = jnp.asarray(vals, jnp.float32).reshape(-1, 1)
        h = hist.shape[0]
        base = scaling.DelayedScaling(history_len=h, margin=0).effective(hist)
        got = scaling.DelayedScaling(history_len=h, margin=m).effective(hist)
        expect = np.maximum(np.asarray(base) * np.float32(2.0) ** m,
                            np.float32(fp8._ALPHA_FLOOR))
        np.testing.assert_array_equal(np.asarray(got), expect)

    @settings(max_examples=30, deadline=None)
    @given(vals=st.lists(st.lists(amaxes, min_size=3, max_size=3),
                         min_size=1, max_size=6))
    def test_hyp_monotone_underestimation_bound(vals):
        hist = jnp.asarray(vals, jnp.float32)
        pol = scaling.DelayedScaling(history_len=hist.shape[0])
        eff = np.asarray(pol.effective(hist))
        assert (eff[None, :] >= np.asarray(hist) - 0).all()


# ---------------------------------------------------------------------------
# leaf_alphas + payload accounting
# ---------------------------------------------------------------------------


def _params_scalar_clips():
    w1 = jax.random.normal(jax.random.PRNGKey(0), (16, 24))
    w2 = jax.random.normal(jax.random.PRNGKey(1), (24, 8))
    return {"w1": w1, "w1_qa": alpha_like(w1),
            "w2": w2, "w2_qa": alpha_like(w2), "b": jnp.ones((8,))}


def test_leaf_alphas_scalar_clips_bitwise():
    params = _params_scalar_clips()
    spec = wire.make_wire_spec(params)
    assert spec.alpha_cols_ok
    got = np.asarray(scaling.leaf_alphas(params, spec))
    expect = np.asarray([float(params["w1_qa"]), float(params["w2_qa"])],
                        np.float32)
    np.testing.assert_array_equal(np.sort(got), np.sort(expect))


def test_leaf_alphas_stacked_clips_reduce_to_max():
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8))
    params = {"w": w, "w_qa": alpha_like(w, stacked=True)}
    spec = wire.make_wire_spec(params)
    assert not spec.alpha_cols_ok
    got = np.asarray(scaling.leaf_alphas(params, spec))
    np.testing.assert_array_equal(
        got, np.asarray([np.max(np.asarray(params["w_qa"]))], np.float32)
    )
    with pytest.raises(ValueError, match="scalar per-leaf clip"):
        scaling.require_column_alphas(
            spec, scaling.PerRoundFrozenScaling()
        )


def test_policy_payload_deltas_in_leg_nbytes():
    params = _params_scalar_clips()
    spec = wire.make_wire_spec(params)
    c = codec_lib.get_codec("e4m3")
    base = codec_lib.leg_nbytes(c, spec)
    n_q = len(spec.q_slots)
    assert codec_lib.leg_nbytes(c, spec, policy=scaling.CURRENT) == base
    assert codec_lib.leg_nbytes(
        c, spec, policy=scaling.DelayedScaling()
    ) == base + 4 * n_q
    assert codec_lib.leg_nbytes(
        c, spec, policy=scaling.PerRoundFrozenScaling()
    ) == base - 4 * n_q
    # FP32 legs ignore the policy (nothing is scale-quantized)
    f32 = codec_lib.get_codec("fp32")
    assert codec_lib.leg_nbytes(
        f32, spec, policy=scaling.DelayedScaling()
    ) == codec_lib.leg_nbytes(f32, spec)


# ---------------------------------------------------------------------------
# Launch-count pins: the encode hot path has ONE amax reduction total
# ---------------------------------------------------------------------------


def _nleaf_tree(n):
    p = {}
    for i in range(n):
        w = jax.random.normal(jax.random.PRNGKey(i), (8 + i, 12))
        p[f"w{i}"] = w
        p[f"w{i}_qa"] = alpha_like(w)
    return p


def test_delta_codec_residual_amax_single_reduction():
    """DeltaCodec's residual clip derivation is ONE plane-wide reduction
    plus a static segment-max — the reduce_max count in the encode jaxpr
    must not grow with the number of leaves."""
    c = codec_lib.get_codec("delta:e4m3")
    counts = []
    for n in (2, 8):
        p = _nleaf_tree(n)
        spec = wire.make_wire_spec(p)
        ref = jax.tree.map(jnp.zeros_like, p)
        jx = jax.make_jaxpr(
            lambda pp, k, _spec=spec, _ref=ref: c.encode(
                pp, _spec, k, ref=_ref)
        )(p, jax.random.PRNGKey(0))
        counts.append(str(jx).count("reduce_max"))
    assert counts[0] == counts[1] == 1, counts


def test_scaled_encode_amax_is_fused_byproduct():
    """encode_scaled(with_amax=True) must not add a standalone reduction
    over the plane: the amax row count stays one per plane (the fused
    quantize+amax launch), leaf-count independent."""
    c = codec_lib.get_codec("e4m3")
    counts = []
    for n in (2, 8):
        p = _nleaf_tree(n)
        spec = wire.make_wire_spec(p)
        a = scaling.leaf_alphas(p, spec)
        jx = jax.make_jaxpr(
            lambda pp, k, aa, _spec=spec: c.encode_scaled(
                pp, _spec, k, aa, with_amax=True)
        )(p, jax.random.PRNGKey(0), a)
        counts.append(str(jx).count("reduce_max"))
    assert counts[0] == counts[1] == 1, counts


# ---------------------------------------------------------------------------
# WireLink validation: scaled XOR scheduled, grid codecs only
# ---------------------------------------------------------------------------


def test_wirelink_scaling_validation():
    with pytest.raises(ValueError, match="downlink policy"):
        WireLink(up_scaling="frozen")
    WireLink(down_scaling="frozen")  # fine
    with pytest.raises(ValueError, match="FP8-family"):
        WireLink(down_codec="fp32", down_scaling="delayed")
    with pytest.raises(ValueError, match="FP8-family"):
        WireLink(up_codec="delta:e4m3", up_scaling="delayed")
    # sub-byte packed formats are grid codecs — they scale fine
    link = WireLink(down_codec="fp4", down_scaling="delayed:4")
    assert link.scaled and link.down_p.history_len == 4


def test_fedconfig_scaling_validation_is_eager():
    with pytest.raises(ValueError, match="unknown scaling policy"):
        FedConfig(**_BASE, down_scaling="nope")
    cfg = FedConfig(**_BASE, down_scaling="delayed:4:1")
    assert cfg.resolved_down_scaling.margin == 1
    assert cfg.resolved_up_scaling.is_current


# ---------------------------------------------------------------------------
# Engine rounds: current bitwise, frozen bitwise + fewer bytes, delayed
# ---------------------------------------------------------------------------


def _run_round(cfg, seed=7):
    params, loss, apply, opt, data = _mlp_setup()
    eng = RoundEngine(loss, opt, cfg)
    state = eng.init(params)
    key = jax.random.PRNGKey(seed)
    new_state, m = jax.jit(eng.round_fn)(state, *data, key)
    return eng, params, new_state, m


def test_explicit_current_bitwise_no_policy():
    """down_scaling='current'/up_scaling='current' must not change a bit
    (or a byte) vs the knob-free engine — the deprecation map contract."""
    _, _, s_ref, m_ref = _run_round(FedConfig(**_BASE))
    _, _, s_cur, m_cur = _run_round(
        FedConfig(**_BASE, down_scaling="current", up_scaling="current")
    )
    assert s_cur.scales == ()
    _assert_trees_equal(s_ref.params, s_cur.params)
    _assert_trees_equal(m_ref, m_cur)


def test_frozen_downlink_bitwise_and_fewer_bytes():
    """Frozen drops the downlink alpha columns: decoded trees (hence the
    whole round) stay bitwise-identical to current, and both the traced
    and static byte counts shrink by exactly 4 bytes/leaf/copy."""
    eng_ref, params, s_ref, m_ref = _run_round(FedConfig(**_BASE))
    cfg = FedConfig(**_BASE, down_scaling="frozen")
    eng, _, s_frz, m_frz = _run_round(cfg)
    _assert_trees_equal(s_ref.params, s_frz.params)
    np.testing.assert_array_equal(np.asarray(m_ref["local_loss"]),
                                  np.asarray(m_frz["local_loss"]))
    spec = wire.make_wire_spec(params)
    n_q, P = len(spec.q_slots), cfg.clients_per_round
    saved = int(m_ref["wire_bytes"]) - int(m_frz["wire_bytes"])
    assert saved == P * 4 * n_q, (saved, P, n_q)
    # static == traced, both accountings
    assert int(m_frz["wire_bytes"]) == eng.round_bytes(params)
    assert int(m_frz["wire_bytes"]) == metrics.round_bytes_for(params, cfg)


def test_delayed_round_threads_history():
    cfg = FedConfig(**_BASE, down_scaling="delayed:4",
                    up_scaling="delayed:4:1")
    params, loss, apply, opt, data = _mlp_setup()
    eng = RoundEngine(loss, opt, cfg)
    state = eng.init(params)
    spec = wire.make_wire_spec(params)
    n_q = len(spec.q_slots)
    st_down, st_up = state.scales
    assert st_down.shape == (4, n_q) and st_up.shape == (4, n_q)
    a0 = np.asarray(scaling.leaf_alphas(params, spec))
    np.testing.assert_array_equal(np.asarray(st_down),
                                  np.tile(a0, (4, 1)))
    round_fn = jax.jit(eng.round_fn)
    s1, m1 = round_fn(state, *data, jax.random.PRNGKey(0))
    # static == traced including the +4*n_q scale riders per leg copy
    assert int(m1["wire_bytes"]) == metrics.round_bytes_for(params, cfg)
    nd, nu = s1.scales
    assert nd.shape == (4, n_q) and nu.shape == (4, n_q)
    # window rotated: rows 0..2 are the seed, row 3 is this round's amax
    np.testing.assert_array_equal(np.asarray(nd[:3]), np.tile(a0, (3, 1)))
    assert np.all(np.asarray(nd[3]) > 0)
    # a second round consumes the rotated history without retracing
    s2, m2 = round_fn(s1, *data, jax.random.PRNGKey(1))
    assert int(m2["wire_bytes"]) == int(m1["wire_bytes"])
    np.testing.assert_array_equal(np.asarray(s2.scales[0][:2]),
                                  np.tile(a0, (2, 1)))


def test_delayed_with_faults_partial_cohort():
    """Dropped clients must not poison the uplink history: the appended
    row is the max over ACCEPTED uplinks only (amax >= 0, so masked rows
    never win), and an all-dead round holds the history steady."""
    cfg = FedConfig(**_BASE, up_scaling="delayed:4",
                    faults=FaultModel(dropout=0.5))
    params, loss, apply, opt, data = _mlp_setup()
    eng = RoundEngine(loss, opt, cfg)
    round_fn = jax.jit(eng.round_fn)
    state = eng.init(params)
    s1, m1 = round_fn(state, *data, jax.random.PRNGKey(3))
    row = np.asarray(s1.scales[1][-1])
    assert np.all(np.isfinite(row)) and np.all(row > 0)
    # traced bytes match the partial accounting at the realized count
    n_tx = int(m1["n_transmitted"])
    assert int(m1["wire_bytes"]) == metrics.partial_round_bytes(
        params, cfg, n_tx
    )
    # dropout=1.0: nobody reports an amax; the history must carry over
    dead = FedConfig(**_BASE, up_scaling="delayed:4",
                     faults=FaultModel(dropout=1.0), min_quorum=0.0)
    engd = RoundEngine(loss, opt, dead)
    sd = engd.init(params)
    sd1, _ = jax.jit(engd.round_fn)(sd, *data, jax.random.PRNGKey(3))
    np.testing.assert_array_equal(
        np.asarray(sd1.scales[1][-1]),
        np.max(np.asarray(sd.scales[1]), axis=0),
    )


def test_delayed_quorum_skip_reverts_history():
    """A quorum-skipped round must not advance the amax history (the
    failed round's uplinks were discarded with the round)."""
    cfg = FedConfig(**_BASE, up_scaling="delayed:4",
                    faults=FaultModel(dropout=1.0), min_quorum=0.5,
                    quorum_policy="skip")
    params, loss, apply, opt, data = _mlp_setup()
    eng = RoundEngine(loss, opt, cfg)
    state = eng.init(params)
    s1, m1 = jax.jit(eng.round_fn)(state, *data, jax.random.PRNGKey(3))
    _assert_trees_equal(state.params, s1.params)
    _assert_trees_equal(state.scales, s1.scales)


# ---------------------------------------------------------------------------
# Sharded parity (multi-device lane)
# ---------------------------------------------------------------------------


def test_sharded_scaled_round_bitwise_local(virtual_devices):
    """Frozen-down + delayed-up on the client mesh: bit-identical params
    AND history to the schedule-matched local round — the mesh adds zero
    numeric change to the scaled legs too."""
    params, loss, apply, opt, data = _mlp_setup()
    base = dict(**_BASE, down_scaling="frozen", up_scaling="delayed:4")
    key = jax.random.PRNGKey(11)
    local = RoundEngine(loss, opt, FedConfig(**base))
    s_l, m_l = jax.jit(local.round_fn)(local.init(params), *data, key)
    from repro.launch.mesh import make_client_mesh

    sharded = RoundEngine(loss, opt, FedConfig(**base),
                          executor=ShardedExecutor(make_client_mesh(3)))
    s_s, m_s = jax.jit(sharded.round_fn)(sharded.init(params), *data, key)
    _assert_trees_equal(s_l.params, s_s.params)
    _assert_trees_equal(s_l.scales, s_s.scales)
    assert int(m_l["wire_bytes"]) == int(m_s["wire_bytes"])


def test_fed2d_scaled_round_matches_local(virtual_devices):
    """Frozen-down + delayed-up on the 2D clients x fsdp mesh: params to
    the GSPMD tolerance of the unscaled fed2d bar (rtol 2e-5 — FSDP
    reassociates reductions, so bitwise is the 1D contract, not this
    one), amax history rows allclose, wire bytes EXACTLY equal."""
    from repro.launch.mesh import make_fed_mesh
    from repro.core.engine import VmapExecutor

    params, loss, apply, opt, data = _mlp_setup(k=8)
    base = dict(n_clients=8, participation=0.75, local_steps=2,
                batch_size=8, comm_mode="det", qat=QATConfig(),
                down_scaling="frozen", up_scaling="delayed:4")
    key = jax.random.PRNGKey(7)
    full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
    s_full, m_full = jax.jit(full.round_fn)(full.init(params), *data, key)
    eng = RoundEngine(loss, opt, FedConfig(
        mesh=make_fed_mesh(2, 4), model_axis="fsdp", **base))
    s, m = jax.jit(eng.round_fn)(eng.init(params), *data, key)
    rel = 0.0
    for a, b in zip(jax.tree.leaves(s.params), jax.tree.leaves(s_full.params)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        rel = max(rel, float(np.max(np.abs(a - b)))
                  / max(1e-9, float(np.max(np.abs(b)))))
    assert rel < 2e-5, rel
    np.testing.assert_allclose(np.asarray(s.scales[1][-1]),
                               np.asarray(s_full.scales[1][-1]), rtol=2e-5)
    assert int(m["wire_bytes"]) == int(m_full["wire_bytes"])
    assert int(m["wire_bytes"]) == eng.round_bytes(params)


# ---------------------------------------------------------------------------
# Silo boundary (launch.steps): delayed history at the collective boundary
# ---------------------------------------------------------------------------


def test_make_comm_round_delayed_threads_scales():
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.engine import FedAvgM
    from repro.launch import steps

    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params_scalar_clips()
    agg = FedAvgM(lr=1.0, momentum=0.9)
    fn = steps.make_comm_round(mesh, P(), ("pod",), QATConfig(),
                               mode="rand", wire="fp8", aggregator=agg,
                               state_specs=P(), scaling="delayed:4")
    st = steps.comm_round_state(agg, params, scaling="delayed:4")
    spec = wire.make_wire_spec(params)
    assert st["scales"].shape == (4, len(spec.q_slots))
    p1, s1 = jax.jit(fn)(params, st, jax.random.PRNGKey(0))
    assert s1["scales"].shape == st["scales"].shape
    assert np.all(np.asarray(s1["scales"][-1]) > 0)
    # frozen has no silo-boundary story (every silo is both ends)
    with pytest.raises(ValueError, match="delayed"):
        steps.make_comm_round(mesh, P(), ("pod",), QATConfig(),
                              mode="rand", wire="fp8", aggregator=agg,
                              state_specs=P(), scaling="frozen")


# ---------------------------------------------------------------------------
# QAT hybrid recipe (bwd_fmt): forward bitwise, gradient on the grid
# ---------------------------------------------------------------------------


def test_qat_hybrid_forward_is_bitwise_unchanged():
    from repro.core import qat
    from repro.core.fp8 import E5M2

    x = jax.random.normal(jax.random.PRNGKey(0), (64,))
    beta = jnp.asarray(2.0)
    fwd = qat.aq(x, beta, QATConfig())
    hyb = qat.aq(x, beta, QATConfig(bwd_fmt=E5M2))
    np.testing.assert_array_equal(np.asarray(fwd), np.asarray(hyb))


def test_qat_hybrid_gradient_lands_on_fp8_grid():
    from repro.core import qat
    from repro.core.fp8 import E5M2

    x = jax.random.normal(jax.random.PRNGKey(0), (128,))
    beta = jnp.asarray(2.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (128,))

    def f(cfg):
        return jax.grad(lambda xx: jnp.sum(jnp.sin(qat.aq(xx, beta, cfg) * w)))(x)

    g_plain = np.asarray(f(QATConfig()))
    g_hyb = np.asarray(f(QATConfig(bwd_fmt=E5M2)))
    # the hybrid gradient is the plain gradient fake-quantized to E5M2:
    # far fewer distinct magnitudes, and every value on the E5M2 grid
    assert len(np.unique(np.abs(g_hyb))) < len(np.unique(np.abs(g_plain)))
    a = np.maximum(np.float32(2.0) ** 0 * np.max(np.abs(g_plain)),
                   np.float32(fp8._ALPHA_FLOOR))
    regrid = np.asarray(
        fp8.quantize_det(jnp.asarray(g_hyb), jnp.asarray(a), E5M2)
    )
    np.testing.assert_array_equal(g_hyb, regrid)
