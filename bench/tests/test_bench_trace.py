"""The trace reduction on a hand-made trace whose answer is known, and on
a small trace recorded on a TPU v5e (``data/v5e_quant_pack.xplane.pb``:
three rounds of a jitted E4M3 encode and decode under the benchmark's
host spans). Runs on the CPU; reading the file starts no backend."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 100, 1000),
        _ev("bench.round.dispatch", 100, 150),
        _ev("bench.round.fetch", 250, 400),
        _ev("bench.round.dispatch", 650, 300),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_round", 0, 2000)]),
        NS(name="XLA Ops", events=[
            _ev("%fusion.1 = f32[8]{0} fusion(...)", 50, 100),  # 100..150
            _ev("%quant_pack_tiles.3 = u8[8,1024] custom-call(...)", 140, 60),
            _ev("%while.2 = (s32[]) while(...)", 480, 140),
            _ev("%fusion.7 = f32[8]{0} fusion(...)", 500, 100),  # in while.2
            _ev("fusion.1", 1050, 200),      # clipped to 1050..1100
        ])])
    return [host, dev, NS(name="/device:TPU:0 SparseCore", lines=[])]


def test_reduce_hand_made_trace():
    red = trace.reduce_planes(_planes())
    assert red.window_s == pytest.approx(1000e-9)
    # busy union: 100..200, 480..620, 1050..1100
    assert red.busy_s == pytest.approx(290e-9)
    assert red.idle_share == pytest.approx(0.71)
    # self times: the while's body op is not counted twice
    assert red.op_seconds == pytest.approx(
        {"fusion": 200e-9, "quant_pack_tiles": 60e-9, "while": 40e-9})
    assert red.gaps == [(pytest.approx(430e-9), "bench.round.dispatch"),
                        (pytest.approx(280e-9), "bench.round.fetch")]
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion"
    assert bd["idle_gaps"][0] == ["bench.round.dispatch",
                                  pytest.approx(430e-9)]


def test_reduce_refuses_a_trace_without_window_or_device():
    planes = _planes()
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(planes[1:])
    with pytest.raises(ValueError, match="device"):
        trace.reduce_planes(planes[:1])


def test_op_family_strips_numeric_suffixes():
    assert trace.op_family("fusion.12") == "fusion"
    assert trace.op_family("%qat_matmul_dw.38 = (f32[2,4096]{1,0}) "
                           "custom-call(%a.1, %b.2)") == "qat_matmul_dw"
    assert trace.op_family("vmap_jit_quant_pack_tiles__.3.1") == \
        "vmap_jit_quant_pack_tiles__"


def test_reduce_recorded_v5e_trace():
    red = trace.reduce_file(str(DATA / "v5e_quant_pack.xplane.pb"))
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(3.92633e-3, rel=1e-5)
    # three encode + decode rounds of a (256, 1024) plane, on a TPU v5e
    assert red.op_seconds["quant_pack_tiles"] == pytest.approx(2.2322e-05)
    assert red.op_seconds["unpack_tiles"] == pytest.approx(3.284e-06)
    # nothing on this trace overlaps: self times add up to the busy union
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s)
    assert 0 < red.busy_s < red.window_s
    assert red.gaps[0] == (pytest.approx(2.398502e-3), "bench.round.fetch")
    assert {label for _, label in red.gaps} <= {
        "bench.round.dispatch", "bench.round.fetch", "none"}


def test_metric_readers_on_a_known_reduction():
    from bench import harness, peaks

    red = trace.Reduced(
        window_s=1.0, busy_s=0.9, gaps=[], n_devices=1,
        op_seconds={"qat_matmul_dw": 0.2, "qat_matmul": 0.1,
                    "quant_pack_tiles": 0.05,
                    "vmap_jit_quant_pack_tiles__": 0.05,
                    "unpack_tiles": 0.02, "fusion": 0.4})
    ctx = dict(trace=red, rounds=10, chips=1, peak=peaks.peak("TPU v5 lite"),
               round_times=[0.1] * 9 + [2.0],
               counts={"model_flops": 1e12,
                       "qat_matmul": [(197e9, 0, 1)],      # 1 ms a round
                       "quant_pack": [(0, 819e6, 1)]})     # 1 ms a round

    def read(name, c=ctx):
        return harness.load_module(harness.BENCH / "metrics"
                                   / f"{name}.py").read(c)

    assert read("device.idle_share") == pytest.approx(10.0)
    assert read("round.mfu") == pytest.approx(100 * 1e13 / 197e12)
    assert read("wire.kernel_ms") == pytest.approx(12.0)
    assert read("qat_matmul_roofline") == pytest.approx(100 / 30)
    assert read("quant_pack_roofline") == pytest.approx(10.0)
    assert read("round.median_s") == pytest.approx(0.1)
    assert read("round.median_s", dict(ctx, round_times=[])) is None
    bare = dict(ctx, trace=trace.Reduced(1.0, 0.9, {"fusion": 0.9}, [], 1))
    for name in ("wire.kernel_ms", "qat_matmul_roofline",
                 "quant_pack_roofline"):
        assert read(name, bare) is None
