"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; ``bench/harness.py`` says
which files make it. The run:

1. refuses to run (non-zero exit, no result line) unless JAX's first
   device is a TPU, the cell's chips are there, ``device_kind`` is in
   ``bench/peaks.py`` and the kernel backend resolves to ``pallas``;
2. makes the weights and the traffic on the device from ``--seed``,
   compiles the program's round once, and drives it through its first
   rounds (the rounds the comparison follows; they also warm it up);
3. measures for ``--seconds``: a closed loop that dispatches the round and
   fetches its local loss and wire bytes to the host before the next one,
   as ``FedSim.run`` does; with ``--trace 1`` under the profiler;
4. reads the device's peak memory, frees the program's state, runs the
   plain reference through the same first rounds and compares;
5. prints each number compared beside its limit, last on standard error
   and in the result line under ``checks``, which comes last there; the
   result line is the last line of standard output.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (``bench/metrics/<name>.py``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class Refused(RuntimeError):
    """The run cannot measure what the cell asks for here."""


def enable_compile_cache(jax) -> str:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR`` when
    set, else at a fixed directory of the checkout; every program cached,
    so that a cell's second run compiles nothing."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def check_device(jax, chips: int):
    """The devices to run on and the peaks of their kind, or Refused."""
    from bench import peaks
    from repro.kernels import dispatch

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        peak = peaks.peak(devs[0].device_kind)
    except peaks.UnknownDevice as e:
        raise Refused(str(e)) from None
    if dispatch.backend() != "pallas":
        raise Refused(f"kernel backend is {dispatch.backend()!r}, not "
                      "'pallas'")
    return devs[:chips], peak


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peak,
             t_start: float, loss_wrap=None, round_wrap=None) -> dict:
    """One run of ``cell`` (a :class:`bench.harness.Cell`); returns the
    result line's object. ``loss_wrap`` and ``round_wrap`` wrap the
    program's loss and compiled round: tests break the timed path with
    them."""
    import jax

    from bench import harness, trace as trace_lib

    inp = harness.make_inputs(cell, seed)
    engine, state = cell.sut.build(cell.cfg, cell.traffic, inp.params(),
                                   inp.data, inp.labels, inp.nk,
                                   loss_wrap=loss_wrap)
    static_bytes = engine.round_bytes(state.params)
    step, routes = harness.compile_round(engine, state, inp)
    if round_wrap is not None:
        step = round_wrap(step)
    print(f"[{cell.name}] ops the pallas backend traced on jnp: "
          f"{routes or 0}; static wire bytes per round {static_bytes}",
          file=sys.stderr)
    n_ref = cell.checks["reference_rounds"]
    state, got = harness.program_rounds(step, state, inp, read_at=n_ref)

    # --- the window ------------------------------------------------------
    gc.collect()
    gc.freeze()     # the collector has nothing of the set-up to scan
    r = r0 = harness.SETUP_ROUNDS
    failed = 0
    trace_path = TRACE_DIR / f"{cell.name}-{seed}"
    if trace:
        shutil.rmtree(trace_path, ignore_errors=True)
        jax.profiler.start_trace(str(trace_path))
    w0 = time.perf_counter()
    w1, took = w0, []
    with jax.profiler.TraceAnnotation("bench.window"):
        while w1 - w0 < seconds and r < len(inp.keys):
            with jax.profiler.TraceAnnotation("bench.round.dispatch"):
                state, m = step(state, inp.data, inp.labels, inp.nk,
                                inp.keys[r])
            with jax.profiler.TraceAnnotation("bench.round.fetch"):
                loss, wb = float(m["local_loss"]), int(m["wire_bytes"])
            t = time.perf_counter()
            took.append(t - w1)
            w1 = t
            failed += int(not math.isfinite(loss) or wb != static_bytes)
            r += 1
    if trace:
        jax.profiler.stop_trace()
    n = r - r0
    setup_s = w0 - t_start
    # arrays (in use) plus the memory reserved for the compiled programs'
    # temporaries, which peak_bytes_in_use leaves out on a TPU
    mem = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0))
                     + int(s.get("peak_bytes_reserved", 0)) for s in mem)
    print(f"[{cell.name}] memory stats after the window: {mem}",
          file=sys.stderr)
    del state, step, m, engine
    gc.unfreeze()
    gc.collect()

    # --- the comparison with the plain reference -------------------------
    ref = harness.reference_rounds(cell, inp, n_ref)
    nums = harness.compare(got, ref, n_ref)
    ref_bytes = harness.round_bytes(cell, inp.layout)
    nums["wire_bytes_gap"] = max(abs(w - ref_bytes) for w in got.wire) \
        + abs(static_bytes - ref_bytes)
    limits = cell.checks["limits"]
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    spec = harness.benchmark_spec()
    out = {"correct": bool(correct), "attempted": n, "failed": failed}
    if trace:
        red = trace_lib.reduce_file(trace_lib.find_xplane(str(trace_path)))
        shutil.rmtree(trace_path, ignore_errors=True)
        counts = cell.ref.counts(cell.cfg, cell.traffic, inp.layout.n_q,
                                 inp.layout.n_rows)
        ctx = dict(trace=red, rounds=n, chips=len(devices), peak=peak,
                   counts=counts, round_times=took)
        metrics = {}
        for m_spec in spec["per_layer"]:
            if cell.name not in m_spec.get("workloads", [cell.name]):
                continue
            reader = harness.load_module(harness.BENCH / "metrics"
                                         / f"{m_spec['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m_spec["name"]] = {"value": value,
                                           "unit": m_spec["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out.update(metrics=metrics, device=device,
                   breakdown=trace_lib.breakdown(red))
    else:
        e2e = {"round_s": (w1 - w0) / n, "setup_s": setup_s}
        metrics = {}
        for m_spec in spec["end_to_end"]:
            if cell.name in m_spec.get("workloads", [cell.name]):
                metrics[m_spec["name"]] = {"value": e2e[m_spec["name"]],
                                           "unit": m_spec["unit"]}
        out.update(metrics=metrics, device=device)
    print(f"[{cell.name}] seed {seed}: {n} rounds in {w1 - w0!r} s, "
          f"median {sorted(took)[n // 2]!r} s, slowest {sorted(took)[-5:]}; "
          f"set-up {setup_s!r} s; program losses {got.losses}, reference "
          f"{ref.losses}; worst leaves {nums['_leaves']}; left out as "
          f"moved by round-off alone: {nums['_excluded']}", file=sys.stderr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program (src/repro) under {ROOT}", file=sys.stderr)
        return 2
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from bench import harness

    cache = enable_compile_cache(jax)
    cell = harness.find_cell(args.workload)
    try:
        devices, peak = check_device(jax, cell.chips)
    except Refused as e:
        print(f"bench: refusing to run: {e}", file=sys.stderr)
        return 3
    print(f"bench: {args.workload} on {devices[0].device_kind} x "
          f"{len(devices)}; compile cache {cache}", file=sys.stderr)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   peak, T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
