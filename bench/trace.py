"""Reduce a profiler trace (``.xplane.pb``) of a traced window to the
numbers the per-layer metrics read: device time per operation, the busy
union of the device's operations, and the idle gaps, each gap attributed
to the host span the benchmark's loop was in (``bench.round.dispatch``,
``bench.round.fetch``) or to none.

Reads the file with ``jax.profiler.ProfileData`` alone; loading it starts
no backend.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.round.dispatch", "bench.round.fetch")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_s: float                 # the traced window (host span)
    busy_s: float                   # busy union inside it, mean over chips
    op_seconds: dict                # op family -> self seconds, mean over chips
    gaps: list                      # (seconds, host span) of idle gaps, longest first
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_family(name: str) -> str:
    """An op's instruction name without XLA's numeric suffix. A TPU trace
    names an op by its HLO text (``%qat_matmul_dw.38 = (f32[...]) ...``);
    ``qat_matmul_dw``, so that an op's time sums over its instances."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", name)


def _self_times(events, w0, w1):
    """(family, self ns inside [w0, w1]) of each event of one trace line:
    its time minus the time of the events nested in it (a ``while`` holds
    the ops of its body)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        own = _overlap(s, e, w0, w1)
        if stack and e <= stack[-1][2]:
            stack[-1][3][0] -= own
        rec = [op_family(name), s, e, [own]]
        out.append(rec)
        stack.append(rec)
    return [(fam, own[0]) for fam, _, _, own in out]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce_planes(planes, top_gaps: int = 10) -> Reduced:
    """Reduce the planes of a loaded trace (``ProfileData.planes``)."""
    window = None
    spans = []
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append([[(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
                            for line in plane.lines if line.name == OPS_LINE])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    w0, w1 = window
    busy_total = 0.0
    op_ns: dict = {}
    gaps = []
    for lines in devices:
        for events in lines:
            for fam, ns in _self_times(events, w0, w1):
                if ns > 0:
                    op_ns[fam] = op_ns.get(fam, 0.0) + ns
        merged = _union([(max(s, w0), min(e, w1)) for events in lines
                         for _, s, e in events if e > w0 and s < w1])
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, label = 0.0, "none"
            for name, s, e in spans:
                ov = _overlap(g0, g1, s, e)
                if ov > best:
                    best, label = ov, name
            gaps.append(((g1 - g0) * 1e-9, label))
    n = len(devices)
    gaps.sort(key=lambda g: -g[0])
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total * 1e-9 / n,
        op_seconds={k: v * 1e-9 / n for k, v in op_ns.items()},
        gaps=gaps[:top_gaps],
        n_devices=n,
    )


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    and the longest idle gaps by what the host was doing."""
    ops = sorted(red.op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[label, s] for s, label in red.gaps[:top]]}
