"""The stage join (``bench/stages.py``) on a hand-made round whose answer is
known, and on a small scoped round recorded on a TPU v5e
(``data/v5e_round.*.gz``: the tiny fleet cell of ``tiny.py`` on the
Pallas backend, three rounds traced under the benchmark's host spans
after three warm rounds, the window opened by a 5 ms sleep since the
device's clock reads up to ~0.5 ms behind the host's; the trace kept to
the device plane's ``XLA Modules`` and ``XLA Ops`` lines and the host
plane, without stats; the round's optimized HLO text without backend
configs, sharding and source locations). Runs on the CPU; reading a
trace starts no backend."""
import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import stages, trace

DATA = Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_round_fn, entry_computation_layout={(f32[8]{0})->f32[]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(round_fn)/fl.up/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %sub.1 = f32[8]{0} subtract(%param_0.1, %param_0.1)
}

%body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p.1), index=0
  %gte.1 = f32[8]{0} get-tuple-element(%p.1), index=1
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(round_fn)/fl.train/vmap()/while/body/fl.opt/sub"}
  %copy.9 = f32[8]{0} copy(%gte.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.7)
}

%cond.1 (p.2: (s32[], f32[8])) -> pred[] {
  %p.2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %constant.2 = pred[] constant(false)
}

ENTRY %main.1 (x.1: f32[8]) -> f32[] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  %tuple.2 = (s32[], f32[8]{0}) tuple(%constant.3, %fusion.1)
  %while.2 = (s32[], f32[8]{0}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(round_fn)/fl.train/vmap()/while"}
  %copy.3 = f32[8]{0} copy(%x.1), metadata={op_name="x"}
  %quant_pack_tiles.3 = u8[8,1024]{1,0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/fl.down/pallas_call"}
  ROOT %reduce.4 = f32[] reduce(%x.1, %constant.3), dimensions={0}, to_apply=%cond.1, metadata={op_name="jit(round_fn)/reduce_sum"}
}
"""


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[
            _ev("bench.window", 0, 2000),
            _ev("bench.round.dispatch", 0, 20),
            _ev("bench.round.fetch", 1300, 600),
            _ev("$array.py:1 __float__", 1310, 580)]),
        NS(name="pjrt-tpu-tasks/1", events=[
            _ev("tpu::System::TransferFromDevice", 1350, 150)]),
        NS(name="main/2", events=[_ev("PJRT_Execute", 10, 30)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_round_fn(42)", -500, 400),    # begins before the window
            _ev("jit_round_fn(42)", 0, 1000),
            _ev("jit_other(7)", 1000, 300)]),
        NS(name="XLA Ops", events=[
            _ev("%fusion.1 = f32[8]{0} fusion(...)", -450, 100),
            _ev("%fusion.1 = f32[8]{0} fusion(...)", 10, 100),
            _ev("%while.2 = (s32[], f32[8]{0}) while(...)", 120, 500),
            _ev("%fusion.7 = f32[8]{0} fusion(...)", 200, 300),
            _ev("%quant_pack_tiles.3 = u8[8,1024]{1,0} custom-call(...)",
                630, 60),
            _ev("%copy.3 = f32[8]{0} copy(...)", 700, 20),
            _ev("%reduce.4 = f32[] reduce(...)", 730, 10),
            # the same instruction name in another module
            _ev("%fusion.1 = f32[4]{0} fusion(...)", 1010, 100)])])
    return [host, dev]


def test_instruction_stages_of_a_hand_made_round():
    rs = stages.RoundStages(HLO)
    assert rs.module == "jit_round_fn"
    assert rs.of("while.2") == ("fl.train", False)
    assert rs.of("fusion.7") == ("fl.train", True)
    # no metadata: the fused computation's op_name
    assert rs.of("fusion.1") == ("fl.up", False)
    # an argument's layout copy: the stage of the kernel it feeds
    assert rs.of("copy.3") == ("fl.down", False)
    # no metadata, nothing it reads or feeds names a stage: the while's
    assert rs.of("copy.9") == ("fl.train", False)
    assert rs.of("quant_pack_tiles.3") == ("fl.down", False)
    assert rs.of("reduce.4") is None          # the program's, no stage
    assert rs.of("no_such_op.1") is None
    assert stages.stage_of("jit(f)/fl.up/x;jit(f)/fl.train/y") == \
        ("fl.up", False)
    assert stages.stage_of("jit(f)/fl.train/fl.opt/fl.up/y") == \
        ("fl.train", True)
    with pytest.raises(ValueError, match="HloModule"):
        stages.RoundStages("ENTRY %main () -> f32[] {\n}\n")


def test_reduce_stages_of_a_hand_made_trace():
    tr = stages.load(_planes())
    st = stages.reduce_stages(tr, stages.RoundStages(HLO))
    assert st.n_devices == 1
    assert st.module_runs == [pytest.approx(1000e-9)]
    assert st.stage_seconds == pytest.approx({
        "fl.up": 100e-9,
        "fl.train": 500e-9,            # the while's own 200 and its body's
        "fl.opt": 300e-9,
        "fl.down": 80e-9,
        "unscoped": 10e-9})
    assert st.round_self_s == pytest.approx(690e-9)
    assert st.outside_s == pytest.approx(100e-9)
    line = stages.stage_line(st)
    assert "fl.train 0.001" in line and "(fl.opt 0.000)" in line
    assert "unscoped 0.000 (1.45% of the round module's self time)" in line
    want = {"fl.up": {"fusion": 100e-9},
            "fl.train": {"while": 200e-9, "fusion": 300e-9},
            "fl.down": {"quant_pack_tiles": 60e-9, "copy": 20e-9},
            "unscoped": {"reduce": 10e-9}}
    assert st.stage_ops.keys() == want.keys()
    for k, ops in want.items():
        assert st.stage_ops[k] == pytest.approx(ops), k
    assert stages.stage_ops_line(st).startswith(
        "device ms a round of the top ops by stage: fl.down: "
        "quant_pack_tiles 0.000, copy 0.000; fl.train: fusion 0.000")
    # what the existing reduction reads is left as it was
    red = trace.reduce_planes(_planes())
    assert red.op_seconds["fusion"] == pytest.approx(500e-9)


def test_idle_gaps_name_the_host_events():
    tr = stages.load(_planes())
    gaps = stages.idle_gaps(tr)
    assert gaps[0][:3] == (pytest.approx(890e-9), 1110, 2000)
    assert stages.host_events_in(tr, 1110, 2000) == {
        "python3": ("$array.py:1 __float__", 580),   # innermost over half
        "pjrt-tpu-tasks/1": ("tpu::System::TransferFromDevice", 150)}
    assert stages.host_events_in(tr, 50, 60) == {
        "python3": ("bench.window", 10)}
    lines = stages.gap_lines(tr, top=2)
    assert lines[0].startswith("idle 0.001 ms on /device:TPU:0 at +0.000 s:")
    assert "python3: $array.py:1 __float__ (0.001 ms)" in lines[0]
    bare = [NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 0, 100)])]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[])])]
    tr = stages.load(bare)
    tr[0].lines[0][1].clear()      # no host event at all
    tr[0].lines.append(("other", [("bench.window", 0, 100)]))
    assert stages.host_events_in(tr, 200, 300) == {}
    assert stages.gap_lines(tr)[0].endswith(
        ": other: bench.window (0.000 ms)")


def _recorded():
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "v5e_round.xplane.pb.gz").read_bytes())
    text = gzip.decompress((DATA / "v5e_round.hlo.txt.gz").read_bytes())
    return (stages.load(ProfileData.from_serialized_xspace(raw).planes),
            stages.RoundStages(text.decode()))


def test_stages_of_a_recorded_v5e_round():
    tr, rs = _recorded()
    st = stages.reduce_stages(tr, rs)
    assert rs.module == "jit_round_fn" and st.n_devices == 1
    assert st.module_runs == pytest.approx([187.266e-6, 187.522e-6,
                                            187.783e-6])
    assert st.stage_seconds == pytest.approx({
        "fl.sample": 21.392e-6, "fl.down": 39.39e-6, "fl.train": 378.492e-6,
        "fl.opt": 11.64e-6, "fl.up": 67.827e-6, "fl.aggregate": 20.882e-6,
        "unscoped": 0.981e-6})
    # every op of the round's executions lands in a stage or unscoped
    total = sum(v for k, v in st.stage_seconds.items() if k != "fl.opt")
    assert total == pytest.approx(st.round_self_s, rel=1e-9)
    assert st.stage_seconds["unscoped"] <= 0.002 * st.round_self_s
    assert st.outside_s == 0.0
    # nothing else ran: the round's self time is the busy union the
    # existing reduction reads
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "v5e_round.xplane.pb.gz").read_bytes())
    red = trace.reduce_planes(ProfileData.from_serialized_xspace(raw).planes)
    assert red.busy_s == pytest.approx(st.round_self_s, rel=1e-9)
    # the kernels the wire's metric reads are in the wire's stages
    kernels = {n: rs.of(n) for n in rs.instrs
               if trace.op_family(n) in ("quant_pack_tiles", "unpack_tiles")}
    assert {s for s, _ in kernels.values()} == {"fl.down", "fl.up"}
    assert st.stage_ops["fl.down"]["quant_pack_tiles"] > 0
    assert st.stage_ops["fl.up"]["unpack_tiles"] > 0
    gaps = stages.gap_lines(tr, top=2)
    assert gaps[0].startswith("idle 5.257 ms on /device:TPU:0 at +0.000 s:")
    assert "python3: $time sleep" in gaps[0]
    assert "pjrt-tpu-tasks/333:" in gaps[1]
