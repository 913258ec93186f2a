"""Sharded cohort execution (ISSUE 4): the client mesh must be invisible.

``ShardedExecutor`` spreads the sampled cohort over a named ``clients``
mesh axis with shard_map, trains P/D clients per device (optionally
chunk-scanned) and moves each device's uplink contribution as ONE uint8
payload buffer through ``compression.fp8_wire_allgather_clients``. FP8
wire formats are exactly where silent cross-device numerics bugs hide
(format-dependent rounding — Micikevicius et al.; stochastic-rounding
correctness — Wang et al.), so the contract here is maximal:

* UNCONDITIONAL: ``ShardedExecutor(D)`` is bit-identical to the
  schedule-matched ``ChunkedExecutor(ceil(P/D))`` for any key — the mesh
  (u8 gather, replicated tail, placement) adds ZERO numeric change. The
  engine earns this with three structural pins: an optimization_barrier on
  the executor/uplink boundary (fusion across it would make numerics
  consumer-dependent), a manually-replicated shard_map around the server
  tail (left to GSPMD, the partitioner shards the client axis whenever D
  divides P and the psum reassociates the aggregator's reductions), and
  width-2 padding of degenerate single-client vmaps (XLA collapses a
  batch-1 dot to an unbatched GEMM with a different accumulation order).
* PINNED-KEY: bit-identical to the full-cohort ``VmapExecutor`` under the
  tested keys — including ragged cohort/device and cohort/chunk splits,
  hybrid per-direction formats, and stateful server optimizers. Across
  *different* vmap widths XLA:CPU's collapsed batched GEMM may round the
  last ULP differently for unlucky values (its M-panel tiling spans client
  boundaries), so cross-width parity is strong pinned evidence of
  schedule-invariance rather than a universal float theorem; the
  schedule-matched invariant above is the universal one.
* exact byte accounting: the static estimate, ``metrics.round_bytes_for``
  and the traced ``wire_bytes`` all agree per link variant;
* the cohort-sized collective in the lowering carries u8, not f32.

These tests need >= 8 devices (the session fixture skips otherwise): run
``REPRO_VIRTUAL_DEVICES=8 pytest tests/test_engine_sharded.py`` — the CI
multi-device matrix entry does exactly that. The slow-marked subprocess
test at the bottom proves the same parity dryrun-style from a plain
single-device run, so the full lane exercises it without the env var.
"""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import metrics
from repro.core.engine import (
    FedConfig,
    RoundEngine,
    ShardedExecutor,
    VmapExecutor,
)
from repro.core.fedsim import FedSim
from repro.core.fp8 import E4M3, E5M2
from repro.core.qat import (
    DISABLED,
    QATConfig,
    clip_value_mask,
    weight_decay_mask,
)
from repro.data import partition_iid, synthetic_classification
from repro.models import small


def _mlp_setup(k=6, n=600, d=16, n_classes=4):
    xall, yall = synthetic_classification(0, n + 300, d=d, n_classes=n_classes)
    cx, cy, nk = partition_iid(xall[:n], yall[:n], k=k, seed=0)
    init, apply = small.REGISTRY["mlp"]
    params = init(jax.random.PRNGKey(0), d_in=d, n_classes=n_classes)
    loss = small.make_loss(apply)
    opt = optim.sgd(0.05, wd_mask=weight_decay_mask(params),
                    trust_mask=clip_value_mask(params))
    evald = (jnp.asarray(xall[n:]), jnp.asarray(yall[n:]))
    return (params, loss, apply, opt,
            (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(nk)), evald)


def _client_mesh(devs, n):
    from repro.launch.mesh import make_client_mesh

    return make_client_mesh(n)


def _assert_trees_equal(a, b, msg=""):
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb),
                                      err_msg=msg)


# ---------------------------------------------------------------------------
# Bitwise parity: sharded == vmap, every schedule
# ---------------------------------------------------------------------------


def test_sharded_round_bit_identical_to_vmap(virtual_devices):
    """One compiled vmap reference; every (device count, chunk) schedule —
    including ragged cohort/device (P=3 on D=8: more devices than clients)
    and ragged chunk splits — must reproduce it bitwise."""
    params, loss, apply, opt, data, _ = _mlp_setup()
    base = dict(n_clients=6, participation=0.5, local_steps=2, batch_size=8,
                comm_mode="rand", qat=QATConfig())
    full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
    key = jax.random.PRNGKey(7)
    s_full, m_full = jax.jit(full.round_fn)(full.init(params), *data, key)
    for n_dev, chunk in ((8, None), (8, 2), (2, None), (3, 1)):
        mesh = _client_mesh(virtual_devices, n_dev)
        eng = RoundEngine(loss, opt,
                          FedConfig(mesh=mesh, chunk=chunk, **base))
        assert isinstance(eng.executor, ShardedExecutor)
        s, m = jax.jit(eng.round_fn)(eng.init(params), *data, key)
        _assert_trees_equal(
            s_full.params, s.params,
            f"D={n_dev} chunk={chunk} diverged from full vmap")
        np.testing.assert_array_equal(np.asarray(m_full["local_loss"]),
                                      np.asarray(m["local_loss"]))
        assert int(m_full["wire_bytes"]) == int(m["wire_bytes"])


def test_sharded_matches_schedule_matched_chunked(virtual_devices):
    """The UNCONDITIONAL invariant: ShardedExecutor(D) == ChunkedExecutor
    (ceil(P/D)) bitwise for any key — same group widths, same slots, same
    pad-wrapping, so the only differences are WHERE groups run and HOW the
    payloads travel, and both must be numerically invisible."""
    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    base = dict(n_clients=8, participation=0.5, local_steps=3, batch_size=8,
                comm_mode="rand", qat=QATConfig())
    P = FedConfig(**base).clients_per_round
    for n_dev in (8, 3):
        L = -(-P // n_dev)
        mesh = _client_mesh(virtual_devices, n_dev)
        ch = RoundEngine(loss, opt, FedConfig(chunk=L, **base))
        sh = RoundEngine(loss, opt, FedConfig(mesh=mesh, **base))
        rf_ch, rf_sh = jax.jit(ch.round_fn), jax.jit(sh.round_fn)
        for seed in (0, 1, 2):
            s_ch, s_sh = ch.init(params), sh.init(params)
            key = jax.random.PRNGKey(seed)
            for _ in range(2):
                key, kr = jax.random.split(key)
                s_ch, m_ch = rf_ch(s_ch, *data, kr)
                s_sh, m_sh = rf_sh(s_sh, *data, kr)
            _assert_trees_equal(s_ch.params, s_sh.params,
                                f"D={n_dev} vs chunk={L}, seed {seed}")
            # the MODEL is the bitwise contract; the diagnostic loss mean
            # is lowered in a different context (inside the replicated
            # tail shard_map vs the open jit) and may differ by one ULP
            # (x * (1/P) vs x / P style rewrites)
            np.testing.assert_allclose(np.asarray(m_ch["local_loss"]),
                                       np.asarray(m_sh["local_loss"]),
                                       rtol=2e-7)


def test_sharded_executor_standalone_matches_vmap(virtual_devices):
    """The bare executor protocol (no engine, FP32 gather): stacked client
    params and losses bitwise equal to VmapExecutor, ragged cohort."""
    from repro.core.engine import make_local_update

    params, loss, apply, opt, data, _ = _mlp_setup()
    cfg = FedConfig(n_clients=6, participation=0.5, local_steps=2,
                    batch_size=8)
    lu = make_local_update(loss, opt, cfg)
    d, l, _ = data
    d, l = d[:5], l[:5]  # P=5: ragged on D=8 and D=2
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    ref = jax.jit(lambda d_, l_, k_: VmapExecutor()(lu, params, d_, l_, k_))(
        d, l, keys)
    for n_dev in (8, 2):
        mesh = _client_mesh(virtual_devices, n_dev)
        ex = ShardedExecutor(mesh, "clients")
        got = jax.jit(lambda d_, l_, k_: ex(lu, params, d_, l_, k_))(
            d, l, keys)
        _assert_trees_equal(ref, got, f"standalone executor D={n_dev}")


def test_sharded_hybrid_and_det_links_bit_identical(virtual_devices):
    """Format-dependent rounding is where cross-device bugs hide: E4M3-down
    / E5M2-up and the det-mode ablation must survive the mesh bitwise."""
    params, loss, apply, opt, data, _ = _mlp_setup()
    mesh = _client_mesh(virtual_devices, 8)
    for kwargs in (
        dict(comm_mode="rand", up_fmt=E5M2),          # hybrid formats
        dict(comm_mode="det"),                        # biased ablation
        dict(comm_mode="rand", down_mode="none"),     # FP32 down / FP8 up
    ):
        base = dict(n_clients=6, participation=0.5, local_steps=2,
                    batch_size=8, qat=QATConfig(), **kwargs)
        ref = RoundEngine(loss, opt, FedConfig(**base))
        sh = RoundEngine(loss, opt, FedConfig(mesh=mesh, **base))
        key = jax.random.PRNGKey(9)
        s_ref, m_ref = jax.jit(ref.round_fn)(ref.init(params), *data, key)
        s_sh, m_sh = jax.jit(sh.round_fn)(sh.init(params), *data, key)
        _assert_trees_equal(s_ref.params, s_sh.params, f"link {kwargs}")
        assert int(m_ref["wire_bytes"]) == int(m_sh["wire_bytes"])


# ---------------------------------------------------------------------------
# Byte accounting: static == traced per direction, on the mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,down_q,up_q", [
    (dict(comm_mode="rand", qat=QATConfig()), True, True),
    (dict(comm_mode="none", qat=DISABLED), False, False),
    (dict(comm_mode="rand", qat=QATConfig(), down_mode="none"), False, True),
    (dict(comm_mode="rand", qat=QATConfig(), down_fmt=E4M3, up_fmt=E5M2),
     True, True),
], ids=["rand", "none", "fp32_down_fp8_up", "hybrid"])
def test_sharded_static_and_traced_bytes_agree(virtual_devices, kwargs,
                                               down_q, up_q):
    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    mesh = _client_mesh(virtual_devices, 8)
    cfg = FedConfig(n_clients=8, participation=0.5, mesh=mesh,
                    local_steps=1, batch_size=8, **kwargs)
    eng = RoundEngine(loss, opt, cfg)
    _, m = jax.jit(eng.round_fn)(eng.init(params), *data,
                                 jax.random.PRNGKey(0))
    static = metrics.round_bytes(params, cfg.clients_per_round,
                                 quantized=down_q, up_quantized=up_q)
    assert static == eng.round_bytes(params)
    assert static == metrics.round_bytes_for(params, cfg)
    assert int(m["wire_bytes"]) == static, (int(m["wire_bytes"]), static)


def test_sharded_collective_moves_uint8(virtual_devices):
    """The only cohort-sized collective in the lowered sharded round must
    carry u8 codes (the wire discipline of fp8_wire_allreduce_mean applied
    to the cohort); with the uplink at FP32 there must be no u8 gather."""
    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    mesh = _client_mesh(virtual_devices, 8)

    def gathers(cfg):
        eng = RoundEngine(loss, opt, cfg)
        txt = jax.jit(eng.round_fn).lower(
            eng.init(params), *data, jax.random.PRNGKey(0)
        ).compile().as_text()
        g = [ln for ln in txt.splitlines()
             if re.search(r"=\s*\S*\s*all-gather(-start)?\(", ln)]
        return [ln for ln in g if re.search(r"=\s*u8\[", ln)]

    base = dict(n_clients=8, participation=1.0, mesh=mesh, local_steps=1,
                batch_size=8)
    u8 = gathers(FedConfig(comm_mode="rand", qat=QATConfig(), **base))
    assert len(u8) == 1, f"expected exactly one u8 all-gather: {u8}"
    # 8 clients, 1 per device: each shard contributes its (1, total) codes
    # buffer and the gather output stacks them to u8[8,1,total]
    from repro.core import wire

    total = wire.make_wire_spec(params).total
    assert any(f"u8[8,1,{total}]" in ln for ln in u8), (total, u8)
    assert not gathers(FedConfig(comm_mode="rand", qat=QATConfig(),
                                 up_mode="none", **base)), \
        "FP32 uplink must not emit a u8 gather"


# ---------------------------------------------------------------------------
# Stateful server optimizers on the mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("aggregator,server_lr", [
    ("fedavgm", 1.0),
    ("fedadam", 0.05),
])
def test_sharded_stateful_aggregator_threads_state(virtual_devices,
                                                   aggregator, server_lr):
    """Two rounds of FedAvgM/FedAdam on the mesh: the momentum must thread
    (round 2 differs from a reset-state replay) and both the params AND the
    threaded opt state must match the unsharded engine bitwise."""
    params, loss, apply, opt, data, _ = _mlp_setup()
    mesh = _client_mesh(virtual_devices, 8)
    base = dict(n_clients=6, participation=0.5, local_steps=2, batch_size=8,
                comm_mode="rand", qat=QATConfig(), aggregator=aggregator,
                server_lr=server_lr, server_momentum=0.9)
    ref = RoundEngine(loss, opt, FedConfig(**base))
    sh = RoundEngine(loss, opt, FedConfig(mesh=mesh, **base))
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    r1, _ = jax.jit(ref.round_fn)(ref.init(params), *data, k1)
    s1, _ = jax.jit(sh.round_fn)(sh.init(params), *data, k1)
    r2, _ = jax.jit(ref.round_fn)(r1, *data, k2)
    s2, _ = jax.jit(sh.round_fn)(s1, *data, k2)
    _assert_trees_equal((r2.params, r2.opt), (s2.params, s2.opt),
                        f"{aggregator} state diverged on the mesh")
    assert any(bool(jnp.any(x != 0)) for x in jax.tree.leaves(s2.opt))
    s2_reset, _ = jax.jit(sh.round_fn)(
        s1._replace(opt=sh.init(params).opt), *data, k2)
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(s2.params),
                             jax.tree.leaves(s2_reset.params))]
    assert max(diffs) > 0, "state had no effect on the sharded round"


# ---------------------------------------------------------------------------
# FedSim integration: placement + history parity
# ---------------------------------------------------------------------------


def test_sharded_fedsim_history_and_placement(virtual_devices):
    """FedSim(cfg.mesh) must (a) spread the client dataset stacks over the
    client axis and (b) produce a bit-identical FedHistory AND final model
    to the schedule-matched chunked run under the same key (the
    unconditional invariant — P=4 on D=8 matches chunk=1)."""
    params, loss, apply, opt_a, data, evald = _mlp_setup(k=8)
    _, _, _, opt_b, _, _ = _mlp_setup(k=8)
    mesh = _client_mesh(virtual_devices, 8)
    base = dict(n_clients=8, participation=0.5, local_steps=3, batch_size=8,
                comm_mode="rand", qat=QATConfig())
    sim_ref = FedSim(params, loss, apply, opt_a,
                     FedConfig(chunk=1, **base), *data)
    sim_sh = FedSim(params, loss, apply, opt_b,
                    FedConfig(mesh=mesh, **base), *data)
    ps = sim_sh.client_data.sharding
    assert "clients" in str(ps.spec), f"client data not sharded: {ps}"
    h_ref = sim_ref.run(2, jax.random.PRNGKey(11), eval_data=evald,
                        eval_every=1)
    h_sh = sim_sh.run(2, jax.random.PRNGKey(11), eval_data=evald,
                      eval_every=1)
    assert h_ref.rounds == h_sh.rounds
    assert h_ref.accuracy == h_sh.accuracy      # bitwise float equality
    np.testing.assert_allclose(h_ref.loss, h_sh.loss, rtol=2e-7)  # ULP, see
    # test_sharded_matches_schedule_matched_chunked on the loss metric
    assert h_ref.cumulative_bytes == h_sh.cumulative_bytes
    _assert_trees_equal(sim_ref.params, sim_sh.params)


def test_sharded_executor_rejects_missing_axis(virtual_devices):
    mesh = _client_mesh(virtual_devices, 2)
    with pytest.raises(ValueError, match="no 'silos'"):
        ShardedExecutor(mesh, "silos")


# ---------------------------------------------------------------------------
# Fault layer on the mesh (ISSUE 6)
# ---------------------------------------------------------------------------


def test_sharded_faultmodel_none_bitwise_legacy(virtual_devices):
    """faults=FaultModel.none() must leave the SHARDED engine on its
    legacy trace too: bit-identical states and metrics across seeds."""
    from repro.core.faults import FaultModel

    params, loss, apply, opt, data, _ = _mlp_setup()
    mesh = _client_mesh(virtual_devices, 8)
    base = dict(n_clients=6, participation=0.5, local_steps=2, batch_size=8,
                comm_mode="rand", qat=QATConfig(), mesh=mesh)
    legacy = RoundEngine(loss, opt, FedConfig(**base))
    faulty = RoundEngine(loss, opt,
                         FedConfig(**base, faults=FaultModel.none(),
                                   min_quorum=0.5))
    assert faulty.faults is None, "none() must statically elide"
    f_legacy, f_none = jax.jit(legacy.round_fn), jax.jit(faulty.round_fn)
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        s0, m0 = f_legacy(legacy.init(params), *data, key)
        s1, m1 = f_none(faulty.init(params), *data, key)
        _assert_trees_equal(s0.params, s1.params,
                            f"seed {seed}: none() diverged on the mesh")
        assert set(m0) == set(m1) == {"local_loss", "wire_bytes"}
        np.testing.assert_array_equal(np.asarray(m0["local_loss"]),
                                      np.asarray(m1["local_loss"]))
        assert int(m0["wire_bytes"]) == int(m1["wire_bytes"])


def test_sharded_fault_round_matches_chunked(virtual_devices):
    """Active faults preserve the unconditional schedule invariant: the
    sharded fault round (draw replicated outside the shard_map) must be
    bit-identical to the schedule-matched chunked round — params, fault
    metrics and partial byte accounting alike — across dropout, quorum
    policies and detected corruption."""
    from repro.core.faults import FaultModel

    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    base = dict(n_clients=8, participation=0.5, local_steps=2, batch_size=8,
                comm_mode="rand", qat=QATConfig())
    P = FedConfig(**base).clients_per_round
    n_dev = 8
    L = -(-P // n_dev)
    mesh = _client_mesh(virtual_devices, n_dev)
    for fault_kw in (
        dict(faults=FaultModel(dropout=0.5), min_quorum=2),
        dict(faults=FaultModel(dropout=0.5), quorum_policy="degrade"),
        dict(faults=FaultModel(corrupt=0.7,
                               straggler="lognormal", seed=2)),
    ):
        ch = RoundEngine(loss, opt, FedConfig(chunk=L, **base, **fault_kw))
        sh = RoundEngine(loss, opt, FedConfig(mesh=mesh, **base, **fault_kw))
        rf_ch, rf_sh = jax.jit(ch.round_fn), jax.jit(sh.round_fn)
        for seed in (0, 1):
            key = jax.random.PRNGKey(seed)
            s_ch, m_ch = rf_ch(ch.init(params), *data, key)
            s_sh, m_sh = rf_sh(sh.init(params), *data, key)
            _assert_trees_equal(s_ch.params, s_sh.params,
                                f"{fault_kw} seed {seed} diverged")
            for name in ("n_alive", "n_transmitted", "quorum_met",
                         "round_ok", "wire_bytes"):
                assert int(m_ch[name]) == int(m_sh[name]), (name, fault_kw)
            np.testing.assert_array_equal(np.asarray(m_ch["round_time"]),
                                          np.asarray(m_sh["round_time"]))
            n_tx = int(m_sh["n_transmitted"])
            assert int(m_sh["wire_bytes"]) == sh.partial_round_bytes(
                n_tx, params)


# ---------------------------------------------------------------------------
# 2D federated mesh (clients x fsdp, ISSUE 7): every client's training step
# FSDP-sharded over its mesh row, wire planes per device over local shards.
# Parity bar vs the local (VmapExecutor) round under the SAME key: params
# within rtol 2e-5 (GSPMD reassociates FSDP reductions — bitwise is the 1D
# bar above, not this one), wire bytes EXACTLY equal, fault metrics
# integer-identical. Both row-major shapes of the 8-device pool run.
# ---------------------------------------------------------------------------


def _fed_mesh(shape):
    from repro.launch.mesh import make_fed_mesh

    return make_fed_mesh(*shape)


def _max_rel(got, ref):
    rel = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        rel = max(rel, float(np.max(np.abs(a - b)))
                  / max(1e-9, float(np.max(np.abs(b)))))
    return rel


_FED2D_SHAPES = [(2, 4), (4, 2)]


@pytest.mark.parametrize("shape", _FED2D_SHAPES,
                         ids=[f"{c}x{f}" for c, f in _FED2D_SHAPES])
def test_fed2d_round_matches_local(virtual_devices, shape):
    """The 2D round vs the full local round, same key, across the link
    variants that exercise distinct wire paths (det codec objects, the
    (fmt, mode) shim, FP32, and a stateful server optimizer): params to
    rtol 2e-5, traced == static wire bytes, and the two EXACTLY equal."""
    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    mesh = _fed_mesh(shape)
    for codec_kw in (
        dict(down_codec="e4m3_det", up_codec="e4m3_det"),
        dict(comm_mode="det"),
        dict(comm_mode="none"),
        dict(comm_mode="det", aggregator="fedadam", server_lr=0.05),
    ):
        base = dict(n_clients=8, participation=0.75, local_steps=2,
                    batch_size=8, qat=QATConfig(), **codec_kw)
        full = RoundEngine(loss, opt, FedConfig(**base),
                           executor=VmapExecutor())
        eng = RoundEngine(loss, opt,
                          FedConfig(mesh=mesh, model_axis="fsdp", **base))
        assert eng.executor.model_axis == "fsdp"
        key = jax.random.PRNGKey(7)
        s_full, m_full = jax.jit(full.round_fn)(full.init(params), *data, key)
        s, m = jax.jit(eng.round_fn)(eng.init(params), *data, key)
        rel = _max_rel(s.params, s_full.params)
        assert rel < 2e-5, (codec_kw, shape, rel)
        assert int(m["wire_bytes"]) == int(m_full["wire_bytes"]), codec_kw
        assert int(m["wire_bytes"]) == eng.round_bytes(params), codec_kw
        np.testing.assert_allclose(np.asarray(m["local_loss"]),
                                   np.asarray(m_full["local_loss"]),
                                   rtol=1e-4)


def test_fed2d_stateful_aggregator_threads_state(virtual_devices):
    """Two FedAvgM rounds on the 2D mesh: the sharded server-momentum tail
    must thread state across rounds and track the local engine."""
    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    base = dict(n_clients=8, participation=0.75, local_steps=2, batch_size=8,
                comm_mode="det", qat=QATConfig(), aggregator="fedavgm",
                server_lr=1.0, server_momentum=0.9)
    full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
    eng = RoundEngine(loss, opt, FedConfig(mesh=_fed_mesh((2, 4)),
                                           model_axis="fsdp", **base))
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    r1, _ = jax.jit(full.round_fn)(full.init(params), *data, k1)
    s1, _ = jax.jit(eng.round_fn)(eng.init(params), *data, k1)
    r2, _ = jax.jit(full.round_fn)(r1, *data, k2)
    s2, _ = jax.jit(eng.round_fn)(s1, *data, k2)
    assert _max_rel(s2.params, r2.params) < 2e-5
    assert _max_rel(s2.opt, r2.opt) < 2e-5
    assert any(bool(jnp.any(x != 0)) for x in jax.tree.leaves(s2.opt))


def test_fed2d_scheduled_codec_crosses_phase(virtual_devices):
    """A CodecSchedule on the 2D mesh: per-round bytes exactly match the
    local engine through the FP8 -> FP4 phase boundary (the payload halves)
    and params stay within the parity bar every round."""
    from repro.core.codec import CodecSchedule, get_codec

    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    sched = CodecSchedule((get_codec("e4m3_det"), get_codec("fp4_det")), (2,))
    base = dict(n_clients=8, participation=0.75, local_steps=2, batch_size=8,
                qat=QATConfig(), codec_schedule=sched)
    full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
    eng = RoundEngine(loss, opt, FedConfig(mesh=_fed_mesh((2, 4)),
                                           model_axis="fsdp", **base))
    rf_full, rf_2d = jax.jit(full.round_fn), jax.jit(eng.round_fn)
    sf, sg = full.init(params), eng.init(params)
    bytes_seen = []
    for rnd in range(3):
        k = jax.random.fold_in(jax.random.PRNGKey(7), rnd)
        sf, mf = rf_full(sf, *data, k)
        sg, mg = rf_2d(sg, *data, k)
        assert int(mf["wire_bytes"]) == int(mg["wire_bytes"]), rnd
        assert _max_rel(sg.params, sf.params) < 2e-5, rnd
        bytes_seen.append(int(mg["wire_bytes"]))
    # the schedule actually switched: FP4 rounds move fewer bytes
    assert bytes_seen[0] == bytes_seen[1] > bytes_seen[2], bytes_seen


def test_fed2d_fault_round_matches_local(virtual_devices):
    """Active faults on the 2D mesh: the fault draw is pinned replicated
    (with jax_threefry_partitionable off, GSPMD partitioning would change
    its bits), so every fault metric must be integer-identical to the
    local round and partial byte accounting must hold."""
    from repro.core.faults import FaultModel

    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    base = dict(n_clients=8, participation=0.75, local_steps=2, batch_size=8,
                comm_mode="det", qat=QATConfig(),
                faults=FaultModel(dropout=0.5), min_quorum=2)
    full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
    eng = RoundEngine(loss, opt, FedConfig(mesh=_fed_mesh((2, 4)),
                                           model_axis="fsdp", **base))
    for seed in (0, 7):
        key = jax.random.PRNGKey(seed)
        sf, mf = jax.jit(full.round_fn)(full.init(params), *data, key)
        sg, mg = jax.jit(eng.round_fn)(eng.init(params), *data, key)
        for name in ("n_alive", "n_transmitted", "quorum_met", "round_ok",
                     "wire_bytes"):
            assert int(mf[name]) == int(mg[name]), (name, seed)
        np.testing.assert_array_equal(np.asarray(mf["round_time"]),
                                      np.asarray(mg["round_time"]))
        assert _max_rel(sg.params, sf.params) < 2e-5, seed
        n_tx = int(mg["n_transmitted"])
        assert int(mg["wire_bytes"]) == eng.partial_round_bytes(n_tx, params)


def test_fed2d_server_opt_runs_replicated_tail(virtual_devices):
    """The UQ+ aggregator does cross-element clip-grid searches, so its
    tail runs replicated (not model-sharded) — and must still track the
    local engine within the parity bar with exactly equal bytes."""
    from repro.core.server_opt import ServerOptConfig

    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    base = dict(n_clients=8, participation=0.75, local_steps=2, batch_size=8,
                comm_mode="det", qat=QATConfig(), aggregator="server_opt",
                server_opt=ServerOptConfig(enabled=True, gd_steps=2))
    full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
    eng = RoundEngine(loss, opt, FedConfig(mesh=_fed_mesh((2, 4)),
                                           model_axis="fsdp", **base))
    key = jax.random.PRNGKey(7)
    sf, mf = jax.jit(full.round_fn)(full.init(params), *data, key)
    sg, mg = jax.jit(eng.round_fn)(eng.init(params), *data, key)
    assert _max_rel(sg.params, sf.params) < 2e-5
    assert int(mf["wire_bytes"]) == int(mg["wire_bytes"])


@pytest.mark.parametrize("shape", _FED2D_SHAPES,
                         ids=[f"{c}x{f}" for c, f in _FED2D_SHAPES])
def test_fed2d_collective_moves_uint8_along_clients(virtual_devices, shape):
    """The lowered 2D round has EXACTLY one u8 all-gather and its replica
    groups run along the client axis only: each group holds the C devices
    at one fsdp coordinate (stride-F device ids), so FSDP shards never
    cross the wire."""
    params, loss, apply, opt, data, _ = _mlp_setup(k=8)
    C, F = shape
    eng = RoundEngine(loss, opt, FedConfig(
        n_clients=8, participation=1.0, local_steps=1, batch_size=8,
        comm_mode="rand", qat=QATConfig(), mesh=_fed_mesh(shape),
        model_axis="fsdp"))
    txt = jax.jit(eng.round_fn).lower(
        eng.init(params), *data, jax.random.PRNGKey(0)
    ).compile().as_text()
    g = [ln for ln in txt.splitlines()
         if re.search(r"=\s*\S*\s*all-gather(-start)?\(", ln)]
    u8 = [ln for ln in g if re.search(r"=\s*u8\[", ln)]
    assert len(u8) == 1, f"expected exactly one u8 all-gather: {u8}"
    groups_txt = re.search(r"replica_groups=\{\{(.*?)\}\}", u8[0]).group(1)
    groups = {frozenset(int(d) for d in grp.split(","))
              for grp in groups_txt.split("},{")}
    want = {frozenset(c * F + f for c in range(C)) for f in range(F)}
    assert groups == want, (groups, want)


def test_fed2d_quantize_det_sharded_matches_plane(virtual_devices):
    """quantize_det_sharded under the fed FSDP specs on a real scanned
    tree: values bitwise equal to the replicated plane (Q_det is
    elementwise), STE grads equal to accumulation noise (the shard_map
    transpose psums per-shard alpha cotangents)."""
    from repro import configs
    from repro.core import plane
    from repro.models.registry import get_model
    from repro.sharding.policy import fed_param_shardings

    cfg = configs.reduced(configs.get("tinyllama_1_1b"))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    sh = fed_param_shardings(params, _fed_mesh((2, 4)), axis="fsdp")

    got = jax.jit(lambda p: plane.quantize_det_sharded(p, sh))(params)
    want = jax.jit(plane.quantize_det)(params)
    _assert_trees_equal(got, want, "sharded plane values diverged")

    def sq_loss(quant):
        return lambda p: sum(
            jnp.sum(l.astype(jnp.float32) ** 2)
            for l in jax.tree.leaves(quant(p)))

    g_sh = jax.jit(jax.grad(sq_loss(
        lambda p: plane.quantize_det_sharded(p, sh))))(params)
    g_ref = jax.jit(jax.grad(sq_loss(plane.quantize_det)))(params)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(g_sh)[0],
        jax.tree_util.tree_flatten_with_path(g_ref)[0],
    ):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        scale = max(float(np.max(np.abs(b))), 1e-6)
        assert float(np.max(np.abs(a - b))) / scale <= 1e-5, path


def test_fed2d_quantize_once_sharded_single_launch(virtual_devices,
                                                   monkeypatch):
    """The FSDP quantize-once path stays O(1) kernel launches per device:
    tracing it enters the plane quantizer exactly once (the shard_map body
    traces once), never once per leaf."""
    from repro import configs
    from repro.kernels import dispatch
    from repro.launch.steps import quantize_params_once_sharded
    from repro.models.registry import get_model
    from repro.sharding.policy import fed_param_shardings

    cfg = configs.reduced(configs.get("tinyllama_1_1b"))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    sh = fed_param_shardings(params, _fed_mesh((2, 4)), axis="fsdp")
    calls = []
    orig = dispatch.quant_det_plane
    monkeypatch.setattr(
        dispatch, "quant_det_plane",
        lambda *a, **k: calls.append(1) or orig(*a, **k))
    jax.make_jaxpr(
        lambda p: quantize_params_once_sharded(p, QATConfig(), sh)[0]
    )(params)
    assert len(calls) == 1, f"{len(calls)} plane launches, expected 1"


def test_fed2d_aggregator_state_specs(virtual_devices):
    """State-spec derivation for the sharded server tail: momentum trees
    mirror the param specs, stateless aggregators carry (), and a custom
    stateful aggregator fails loudly instead of silently replicating."""
    from jax.sharding import PartitionSpec as P

    from repro.core.engine import make_aggregator
    from repro.launch.steps import aggregator_state_specs

    specs = {"w": P(None, "fsdp"), "w_qa": P()}
    assert aggregator_state_specs(make_aggregator("mean"), specs) == ()
    assert aggregator_state_specs(make_aggregator("fedavgm"), specs) == specs
    assert aggregator_state_specs(make_aggregator("fedadam"), specs) == {
        "m": specs, "v": specs}

    class Custom:
        def init(self, params):
            return jax.tree.map(jnp.zeros_like, params)

    with pytest.raises(ValueError, match="state_specs"):
        aggregator_state_specs(Custom(), specs)


def test_fed2d_config_validation(virtual_devices):
    """Every invalid 2D wiring dies eagerly in FedConfig with a one-line
    actionable error, not as a shard_map shape mismatch mid-trace."""
    mesh2d = _fed_mesh((4, 2))
    with pytest.raises(ValueError, match="make_fed_mesh"):
        FedConfig(n_clients=8, model_axis="fsdp")
    with pytest.raises(ValueError, match="both"):
        FedConfig(n_clients=8, mesh=mesh2d, model_axis="clients")
    with pytest.raises(ValueError, match="not on the given mesh"):
        FedConfig(n_clients=8, mesh=_client_mesh(8, 8), model_axis="fsdp")
    with pytest.raises(ValueError, match="chunk"):
        FedConfig(n_clients=8, mesh=mesh2d, model_axis="fsdp", chunk=2)
    with pytest.raises(ValueError, match="padding clients"):
        # 4 cohort rows but only 3 clients per round
        FedConfig(n_clients=8, participation=0.375, mesh=mesh2d,
                  model_axis="fsdp")
    with pytest.raises(ValueError, match="model_axis"):
        FedConfig(n_clients=8, mesh=mesh2d)  # 2D mesh, axis never named


def test_fed2d_executor_validation(virtual_devices):
    mesh2d = _fed_mesh((2, 4))
    with pytest.raises(ValueError, match="both"):
        ShardedExecutor(mesh2d, "clients", model_axis="clients")
    with pytest.raises(ValueError, match="'tp'"):
        ShardedExecutor(mesh2d, "clients", model_axis="tp")
    with pytest.raises(ValueError, match="chunk"):
        ShardedExecutor(mesh2d, "clients", chunk=2, model_axis="fsdp")


def test_make_fed_mesh_validation(virtual_devices):
    from repro.launch.mesh import make_fed_mesh

    with pytest.raises(ValueError, match="positive"):
        make_fed_mesh(0, 4)
    with pytest.raises(ValueError, match="device"):
        make_fed_mesh(3, 3)   # needs 9 of 8
    with pytest.raises(ValueError, match="divides"):
        make_fed_mesh(3, 2)   # 6 of 8: idles 2
    mesh = make_fed_mesh(2, 2, client_axis="rows", model_axis="cols")
    assert mesh.axis_names == ("rows", "cols")
    assert dict(mesh.shape) == {"rows": 2, "cols": 2}


# ---------------------------------------------------------------------------
# Dryrun-style subprocess lane: proves parity from a single-device run
# ---------------------------------------------------------------------------

_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro import optim
from repro.core.engine import FedConfig, RoundEngine, VmapExecutor
from repro.core.qat import QATConfig, clip_value_mask, weight_decay_mask
from repro.data import partition_iid, synthetic_classification
from repro.launch.mesh import make_client_mesh
from repro.models import small

xall, yall = synthetic_classification(0, 900, d=16, n_classes=4)
cx, cy, nk = partition_iid(xall[:600], yall[:600], k=6, seed=0)
init, apply = small.REGISTRY["mlp"]
params = init(jax.random.PRNGKey(0), d_in=16, n_classes=4)
loss = small.make_loss(apply)
opt = optim.sgd(0.05, wd_mask=weight_decay_mask(params),
                trust_mask=clip_value_mask(params))
data = (jnp.asarray(cx), jnp.asarray(cy), jnp.asarray(nk))
base = dict(n_clients=6, participation=0.5, local_steps=2, batch_size=8,
            comm_mode="rand", qat=QATConfig())
key = jax.random.PRNGKey(7)
full = RoundEngine(loss, opt, FedConfig(**base), executor=VmapExecutor())
s_full, m_full = jax.jit(full.round_fn)(full.init(params), *data, key)
mesh = make_client_mesh(8)
out = {"devices": len(jax.devices())}
for chunk in (None, 2):
    eng = RoundEngine(loss, opt, FedConfig(mesh=mesh, chunk=chunk, **base))
    s, m = jax.jit(eng.round_fn)(eng.init(params), *data, key)
    identical = all(
        bool(jnp.all(a == b))
        for a, b in zip(jax.tree.leaves(s_full.params),
                        jax.tree.leaves(s.params))
    ) and float(m_full["local_loss"]) == float(m["local_loss"])
    out[f"chunk_{chunk}"] = {
        "identical": identical,
        "wire_bytes": int(m["wire_bytes"]),
        "wire_bytes_ref": int(m_full["wire_bytes"]),
    }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_parity_subprocess_dryrun():
    """Forced 8-virtual-device mesh in a subprocess (jax locks topology at
    first init, dryrun-style) — the full lane proves sharded==vmap bitwise
    even when this pytest process runs on one device."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS], capture_output=True, text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=500,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    for chunk in ("chunk_None", "chunk_2"):
        assert res[chunk]["identical"], f"{chunk}: sharded != vmap"
        assert res[chunk]["wire_bytes"] == res[chunk]["wire_bytes_ref"]
