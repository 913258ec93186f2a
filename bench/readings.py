"""Read the numbers the correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out chiprun_out/readings-<cell>.json]

In one process, for every seed: the program's round (compiled once)
through the first rounds against the plain reference (the lower reading);
and for each control seed, the reference computed one precision below the
configuration's (``harness.CONTROL``) and the reference with each planted
fault (``reference.FAULTS``), each against the reference (the upper
readings), with the look at round-off (:func:`look_at_round_off`). A
state left unchanged reads 1 by construction. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _public(nums: dict) -> dict:
    return {k: v for k, v in nums.items() if not k.startswith("_")}


def look_at_round_off(cell, inp, ref) -> dict:
    """What round-off alone does to the reference: its round 1 with its
    matmuls at ``highest`` against its own at the configuration's
    precision (per leaf, as the comparison reads the program), and the
    one-element leaves' gradient at the first client's first step under
    both precisions, beside AdamW's eps."""
    import functools

    import jax

    from bench import harness, reference

    hi = harness.reference_rounds(cell, inp, 1, precision="highest")
    ref1 = harness.Readings(ref.losses[:1], ref.first, ref.first, ref.p1)
    nums = harness.compare(hi, ref1, 1)
    grad = jax.jit(functools.partial(harness.reference_round(cell).first_grad,
                                     lay=inp.layout))
    grads = {}
    for prec in (cell.cfg["matmul_precision"], "highest"):
        with jax.default_matmul_precision(prec):
            g = grad(inp.params(), inp.data, inp.labels, inp.keys[0])
        grads[prec] = {reference.dotted(p): float(x.reshape(()))
                       for p, x in jax.tree_util.tree_flatten_with_path(g)[0]
                       if x.size == 1}
    return {"highest_vs_default": {k: v for k, v in nums.items()
                                   if k.startswith(("grad_", "loss_gap.r1"))},
            "per_leaf": {k: v for k, v in nums["_per_leaf"].items()
                         if k.startswith("grad_")},
            "scalar_first_grad": grads,
            "adam_eps": 1e-8}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from bench import harness, reference
    from bench.run import Refused, check_device, enable_compile_cache

    enable_compile_cache(jax)
    cell = harness.find_cell(args.workload)
    try:
        check_device(jax, cell.chips)
    except Refused as e:
        print(f"readings: refusing to run: {e}", file=sys.stderr)
        return 3
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    n = cell.checks["reference_rounds"]
    control_dtype = harness.CONTROL[cell.cfg["activation_dtype"]]
    step, records = None, []
    for seed in seeds:
        t0 = time.perf_counter()
        inp = harness.make_inputs(cell, seed)
        engine, state = cell.sut.build(cell.cfg, cell.traffic, inp.params(),
                                       inp.data, inp.labels, inp.nk)
        if step is None:
            step, routes = harness.compile_round(engine, state, inp)
            print(f"[{cell.name}] jnp routes {routes or 0}", flush=True)
        state, got = harness.program_rounds(step, state, inp, read_at=n)
        del state, engine
        ref = harness.reference_rounds(cell, inp, n)
        nums = harness.compare(got, ref, n)
        rec = {"seed": seed, "program": _public(nums),
               "per_leaf": nums["_per_leaf"], "excluded": nums["_excluded"],
               "losses": {"program": got.losses, "reference": ref.losses},
               "wire_bytes": got.wire,
               "reference_bytes": harness.round_bytes(cell, inp.layout)}
        if seed in controls:
            ctl = harness.reference_rounds(cell, inp, n, dtype=control_dtype)
            rec["control"] = _public(nums := harness.compare(ctl, ref, n))
            rec["per_leaf_control"] = nums["_per_leaf"]
            rec["losses"]["control"] = ctl.losses
            for fault in reference.FAULTS:
                f = harness.reference_rounds(cell, inp, n, fault=fault)
                rec[fault] = _public(nums := harness.compare(f, ref, n))
                rec[f"per_leaf_{fault}"] = nums["_per_leaf"]
                rec["losses"][fault] = f.losses
            rec["round_off"] = look_at_round_off(cell, inp, ref)
        rec["seconds"] = time.perf_counter() - t0
        records.append(rec)
        print(json.dumps({k: v for k, v in rec.items()
                          if not k.startswith("per_leaf")
                          and k != "round_off"}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
