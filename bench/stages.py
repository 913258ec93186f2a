"""Device time of the federated round by stage, and what the host was
doing in the device's longest idle gaps, from a traced run.

The program names the round's stages with ``jax.named_scope``
(``core/engine.py``): ``fl.sample``, ``fl.down``, ``fl.train`` (with the
client optimizer's ``fl.opt`` inside it), ``fl.up`` and ``fl.aggregate``.
A scope lands in the ``metadata={op_name=...}`` of each instruction of
the optimized HLO, not in the instruction's name, and a TPU trace names
an op by its instruction alone (``%fusion.12 = ...``). So an op's stage
is found by joining its instruction name with the compiled round's HLO
text (``Compiled.as_text()``). An instruction belongs to the outermost
``fl.*`` component of its op_name. One that XLA made without metadata (a
concatenate split into a chain of ``dynamic-update-slice`` fusions, a
layout copy of an argument) takes the stage of the instructions that
consume its result, else of those it reads, else of the instruction
that calls its computation.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

makes the cell's traced run (``bench/run.py --trace 1``: the same result
line, last on standard output) and prints, on standard error, the
round's device milliseconds by stage and the host's events in the
longest idle gaps.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys
from pathlib import Path

if __package__ in (None, ""):          # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from bench import trace as trace_lib  # noqa: E402

STAGES = ("fl.sample", "fl.down", "fl.train", "fl.up", "fl.aggregate")
OPT = "fl.opt"                  # nested in fl.train; kept as its own key
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def stage_of(op_name: str):
    """``(stage, in fl.opt)`` of an op_name: its outermost ``fl.*``
    component; ``None`` when it has none. Where XLA merged the names of
    several ops (``a;b``) the first one counts."""
    parts = op_name.split(";", 1)[0].split("/")
    stage = next((p for p in parts if p.startswith("fl.")), None)
    if stage is None:
        return None
    return stage, OPT in parts


@dataclasses.dataclass
class _Instr:
    comp: str
    op_name: str
    refs: list        # the instructions and computations it names


class RoundStages:
    """The stage of each instruction of a compiled round's HLO text.

    An instruction's own stage is that of its op_name. One with no
    op_name, or whose op_name is an argument's name (a layout copy of an
    argument), or a fusion whose fused computation names a stage, is
    given one: the fused computation's; else that of the nearest
    instructions that consume its result; else of those it reads; else
    that of the instruction calling its computation (a ``while``). An
    op_name of the program outside every stage (the metrics' loss mean)
    stays unscoped: :meth:`of` gives None."""

    def __init__(self, text: str):
        self.module = None
        self.instrs, self.comps = {}, {}
        comp = None
        for line in text.splitlines():
            if self.module is None and line.startswith("HloModule "):
                self.module = line.split()[1].rstrip(",")
                continue
            c = _COMP.match(line)
            if c and "=" not in line.split("(", 1)[0]:
                comp = c.group(1)
                self.comps[comp] = []
                continue
            m = _INSTR.match(line)
            if m and comp is not None:
                name, rest = m.groups()
                on = _OP_NAME.search(rest)
                self.instrs[name] = _Instr(comp, on.group(1) if on else "",
                                           _REF.findall(rest))
                self.comps[comp].append(name)
        if self.module is None:
            raise ValueError("not an HLO module's text (no 'HloModule' "
                             "line)")
        self.users = collections.defaultdict(list)
        self.caller = {}
        for name, ins in self.instrs.items():
            for r in ins.refs:
                if r in self.instrs and r != name:
                    self.users[r].append(name)
                elif r in self.comps:
                    self.caller.setdefault(r, name)
        self._memo = {}

    def _own(self, name):
        """(stage or None, whether the instruction's name is final)."""
        ins = self.instrs[name]
        st = stage_of(ins.op_name)
        if st is not None:
            return st, True
        if "/" in ins.op_name:          # a traced op outside every stage
            return None, True
        for c in ins.refs:              # a fusion: its fused computation
            found = [stage_of(self.instrs[i].op_name)
                     for i in self.comps.get(c, ())]
            found = [f for f in found if f]
            if found:
                return found[-1], True
        return None, False

    def _search(self, start, edges):
        seen, todo = {start}, collections.deque(edges(start))
        while todo:
            n = todo.popleft()
            if n in seen or n not in self.instrs:
                continue
            seen.add(n)
            st, final = self._own(n)
            if st is not None:
                return st
            if not final:
                todo.extend(edges(n))
        return None

    def of(self, name: str):
        """``(stage, in fl.opt)`` of an instruction, or None."""
        if name not in self._memo:
            self._memo[name] = self._resolve(name, 0)
        return self._memo[name]

    def _resolve(self, name, depth):
        if name not in self.instrs:
            return None
        st, final = self._own(name)
        if st is not None or final:
            return st
        st = (self._search(name, lambda n: self.users.get(n, ()))
              or self._search(name, lambda n: [
                  r for r in self.instrs[n].refs if r in self.instrs]))
        up = self.caller.get(self.instrs[name].comp)
        if st is None and up is not None and depth < 8:
            st = self._resolve(up, depth + 1)
        return st


def instruction_name(event_name: str) -> str:
    """The instruction an ``XLA Ops`` event ran: ``%fusion.12 = f32[...]
    fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@dataclasses.dataclass
class Plane:
    name: str
    lines: list            # (line name, [(event name, start ns, end ns)])

    def line(self, name: str) -> list:
        return next((evs for n, evs in self.lines if n == name), [])


def load(planes) -> list:
    """The planes of a loaded trace (``ProfileData.planes``, which can be
    read once) as :class:`Plane` s."""
    return [Plane(p.name, [(ln.name, [(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns)
                                      for e in ln.events])
                           for ln in p.lines])
            for p in planes]


def _devices(trace):
    return [p for p in trace if trace_lib.DEVICE_PLANE.match(p.name)]


def _hosts(trace):
    return [p for p in trace if p.name.startswith("/host:")]


def window(trace):
    """(start, end) ns of the ``bench.window`` span, or None."""
    for plane in _hosts(trace):
        for _, events in plane.lines:
            for name, s, e in events:
                if name == trace_lib.WINDOW_SPAN:
                    return s, e
    return None


@dataclasses.dataclass
class Stages:
    stage_seconds: dict    # stage, fl.opt, unscoped -> self s, mean over chips
    stage_ops: dict        # stage or unscoped -> {op family: self s}
    module_runs: list      # device seconds of each execution of the round
    round_self_s: float    # self seconds of the round's ops, mean over chips
    outside_s: float       # self s of other modules' ops, mean over chips
    n_devices: int


def _self_times(events, w0, w1):
    """(instruction, self ns inside [w0, w1], start) of each event of one
    trace line: its time minus that of the events nested in it (a
    ``while`` holds the ops of its body)."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        own = trace_lib._overlap(s, e, w0, w1)
        if stack and e <= stack[-1][2]:
            stack[-1][3][0] -= own
        rec = [instruction_name(name), s, e, [own]]
        out.append(rec)
        stack.append(rec)
    return [(name, own[0], s) for name, s, _, own in out]


def reduce_stages(trace, stages: RoundStages) -> Stages:
    """Self time of each op by the stage of its instruction, over the
    executions of the round's module (its ``XLA Modules`` events) that
    start in the traced window (the ``bench.window`` span, else the whole
    trace; ``trace`` from :func:`load`), each execution whole; the ops
    of the window that no execution of the module holds count as
    outside."""
    w0, w1 = window(trace) or (float("-inf"), float("inf"))
    devices = _devices(trace)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    sec = collections.Counter()
    ops = collections.defaultdict(collections.Counter)
    runs = []
    round_self = outside = 0.0
    for plane in devices:
        mods = sorted((s, e) for name, s, e in plane.line(MODULES_LINE)
                      if name.split("(", 1)[0] == stages.module)
        starts = [s for s, _ in mods]
        runs += [(e - s) * 1e-9 for s, e in mods if w0 <= s < w1]
        for name, ns, s in _self_times(plane.line(trace_lib.OPS_LINE),
                                       float("-inf"), float("inf")):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                outside += ns if w0 <= s < w1 else 0.0
                continue
            if not w0 <= mods[i][0] < w1:
                continue
            round_self += ns
            st = stages.of(name)
            key = st[0] if st else UNSCOPED
            sec[key] += ns
            ops[key][trace_lib.op_family(name)] += ns
            if st and st[1]:
                sec[OPT] += ns
    n = len(devices)
    return Stages(stage_seconds={k: v * 1e-9 / n for k, v in sec.items()},
                  stage_ops={k: {f: v * 1e-9 / n for f, v in c.items()}
                             for k, c in ops.items()},
                  module_runs=runs, round_self_s=round_self * 1e-9 / n,
                  outside_s=outside * 1e-9 / n, n_devices=n)


def _rounds(st: Stages) -> int:
    return max(1, len(st.module_runs) // st.n_devices)


def stage_line(st: Stages) -> str:
    """The operator's view: device ms a round (over the round module's
    executions) of every stage, of fl.opt (inside fl.train), of the
    round's unscoped ops and of the ops of other modules; the median
    execution of the round module."""
    rounds = _rounds(st)

    def ms(s):
        return f"{1e3 * s / rounds:.3f}"

    sec = st.stage_seconds
    parts = [f"{k} {ms(sec.get(k, 0.0))}" for k in STAGES]
    parts.insert(STAGES.index("fl.train") + 1,
                 f"({OPT} {ms(sec.get(OPT, 0.0))})")
    uns = sec.get(UNSCOPED, 0.0)
    share = 100.0 * uns / st.round_self_s if st.round_self_s else 0.0
    runs = sorted(st.module_runs)
    med = (f"{1e3 * runs[len(runs) // 2]:.3f} ms over {rounds} rounds"
           if runs else "no execution")
    return (f"device ms a round by stage: {', '.join(parts)}, "
            f"{UNSCOPED} {ms(uns)} ({share:.2f}% of the round module's "
            f"self time); ops outside the round module {ms(st.outside_s)}; "
            f"round module median {med}")


def stage_ops_line(st: Stages, top: int = 4) -> str:
    """Device ms a round of the ``top`` op families of each stage."""
    rounds = _rounds(st)
    parts = []
    for key in STAGES + (UNSCOPED,):
        fams = sorted(st.stage_ops.get(key, {}).items(), key=lambda kv: -kv[1])
        if fams:
            parts.append(f"{key}: " + ", ".join(
                f"{f} {1e3 * s / rounds:.3f}" for f, s in fams[:top]))
    return "device ms a round of the top ops by stage: " + "; ".join(parts)


# --- what the host was doing in the idle gaps -----------------------------

def idle_gaps(trace, top: int = 10) -> list:
    """``(seconds, start ns, end ns, device plane)`` of the longest
    intervals of the traced window in which no op ran on a device."""
    w = window(trace)
    if w is None:
        raise ValueError(f"the trace holds no {trace_lib.WINDOW_SPAN!r} "
                         "span")
    w0, w1 = w
    gaps = []
    for plane in _devices(trace):
        merged = trace_lib._union([
            (max(s, w0), min(e, w1))
            for _, s, e in plane.line(trace_lib.OPS_LINE)
            if e > w0 and s < w1])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [((g1 - g0) * 1e-9, g0, g1, plane.name)
                 for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]
    gaps.sort(key=lambda g: -g[0])
    return gaps[:top]


def _with_depth(events):
    """(name, start, end, depth) of one host thread's events, depth 0 for
    an event nested in no other."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1] <= s:
            stack.pop()
        out.append((name, s, e, len(stack)))
        stack.append(e)
    return out


def host_events_in(trace, g0: float, g1: float) -> dict:
    """Per host thread that has an event in [g0, g1]: ``(event, overlap
    ns)`` of the innermost of its events that cover at least half of the
    interval, else of the one that overlaps it most."""
    out = {}
    for plane in _hosts(trace):
        for thread, events in plane.lines:
            ov = [(trace_lib._overlap(s, e, g0, g1), d, n)
                  for n, s, e, d in _with_depth(events) if e > g0 and s < g1]
            if not ov:
                continue
            half = [(d, o, n) for o, d, n in ov if 2 * o >= g1 - g0]
            _, o, n = max(half) if half else max((d, o, n) for o, d, n in ov
                                                 if o == max(ov)[0])
            out[thread] = (n, o)
    return out


def gap_lines(trace, top: int = 5) -> list:
    """One line per long idle gap: its length, where it starts in the
    window, and each host thread's event there (``no host event`` when no
    thread has one)."""
    w0 = window(trace)[0]
    lines = []
    for sec, g0, g1, dev in idle_gaps(trace, top):
        ev = host_events_in(trace, g0, g1)
        what = ("; ".join(f"{t}: {n} ({o * 1e-6:.3f} ms)"
                          for t, (n, o) in sorted(ev.items()))
                or "no host event")
        lines.append(f"idle {sec * 1e3:.3f} ms on {dev} at "
                     f"+{(g0 - w0) * 1e-9:.3f} s: {what}")
    return lines


# --- the operator's run ----------------------------------------------------

def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import json

    import jax
    from jax.profiler import ProfileData

    from bench import harness, run

    cache = run.enable_compile_cache(jax)
    cell = harness.find_cell(args.workload)
    try:
        devices, peak = run.check_device(jax, cell.chips)
    except run.Refused as e:
        print(f"bench: refusing to run: {e}", file=sys.stderr)
        return 3
    print(f"bench: {args.workload} on {devices[0].device_kind} x "
          f"{len(devices)}; compile cache {cache}", file=sys.stderr)
    hlo = {}

    def keep_text(step):
        hlo["text"] = step.as_text()
        return step

    # bench/run.py reduces the trace and deletes it inside run_cell: the
    # join reads the same file there
    reduce_file = trace_lib.reduce_file

    def reduce_and_report(path):
        red = reduce_file(path)
        trace = load(ProfileData.from_file(path).planes)
        if not hlo.get("text"):
            print("stages: the compiled round gave no HLO text",
                  file=sys.stderr)
        else:
            st = reduce_stages(trace, RoundStages(hlo["text"]))
            print(f"[{cell.name}] {stage_line(st)}", file=sys.stderr)
            print(f"[{cell.name}] {stage_ops_line(st)}", file=sys.stderr)
        for line in gap_lines(trace):
            print(f"[{cell.name}] {line}", file=sys.stderr)
        return red

    trace_lib.reduce_file = reduce_and_report
    out = run.run_cell(cell, args.seed, args.seconds, True, devices, peak,
                       run.T_START, round_wrap=keep_text)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
