import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the full-size
model is traced with ShapeDtypeStruct stand-ins (zero allocation), jitted
with the production sharding policy against the 16x16 (single-pod) and
2x16x16 (multi-pod) meshes, and ``.compile()`` must succeed. The compiled
artifact yields ``memory_analysis()`` (fits?) and ``cost_analysis()``
(FLOPs/bytes) plus the HLO collective schedule — the inputs to
EXPERIMENTS.md §Roofline.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama_1_1b \
        --shape train_4k [--multi-pod] [--no-qat] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""


import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..configs.base import SHAPES
from ..core.qat import DISABLED, QATConfig
from ..models import registry
from ..models.common import sharding_rules
from ..sharding.policy import ShardingPolicy
from . import hlo_cost
from .compile_cache import enable_compile_cache
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from .steps import make_comm_round, make_decode_step, make_optimizer, \
    make_prefill_step, make_train_step

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum output-buffer bytes of every collective op in the HLO."""
    out: dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for coll in _COLLECTIVES:
            # match "= <shapes> all-reduce(" and "all-reduce-start("
            if f" {coll}(" in stripped or f" {coll}-start(" in stripped:
                lhs = stripped.split(f" {coll}")[0]
                if "=" not in lhs:
                    continue
                shapes = lhs.split("=", 1)[1]
                total = 0.0
                for dt, dims in shape_re.findall(shapes):
                    if dt not in _DTYPE_BYTES:
                        continue
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    total += n * _DTYPE_BYTES[dt]
                out[coll] += total
                break
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return "quadratic full attention at 500k context (per assignment: skip)"
    return None


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             qat: bool = True, comm_round: bool = False,
             opt_level: int = 1) -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "qat": qat,
    }
    if reason:
        rec["status"] = "skip"
        rec["reason"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    policy = ShardingPolicy(mesh)
    model = registry.get_model(cfg)
    qcfg = QATConfig() if qat else DISABLED

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspec = policy.params(params_shape)
    in_specs = registry.input_specs(cfg, shape)
    bspec = policy.batch(in_specs)
    t0 = time.time()

    with mesh, sharding_rules(
        policy.activation_rules(seq_sharded=shape.kind != "decode")
    ):
        if shape.kind == "train":
            opt = make_optimizer(params_shape)
            opt_state_shape = jax.eval_shape(opt.init, params_shape)
            ospec = policy.params(opt_state_shape)
            # grad-accumulation microbatching: target <=16k tokens per
            # device per microbatch (bounds live activations + scan stacks;
            # MoE halves the target — dispatch buffers scale with tokens x
            # top_k x capacity_factor)
            dp_size = n_chips // mesh.shape.get("model", 1)
            tokens_per_dev = shape.global_batch * shape.seq_len // max(dp_size, 1)
            target = 8192 if cfg.moe else 16384
            accum = max(1, tokens_per_dev // target)
            while shape.global_batch % accum or \
                    (shape.global_batch // accum) % max(dp_size, 1):
                accum -= 1
            rec["accum"] = accum
            rec["opt_level"] = opt_level
            fn = make_train_step(model, opt, qcfg, accum=accum,
                                 opt_level=opt_level,
                                 grad_shardings=pspec if opt_level >= 1 else None)
            step_spec = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(
                fn,
                in_shardings=(pspec, ospec, bspec, NamedSharding(mesh, P())),
                out_shardings=(pspec, ospec, None),
                donate_argnums=(0, 1),
            ).lower(params_shape, opt_state_shape, in_specs, step_spec)
        elif shape.kind == "prefill":
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len)
            )
            cspec = policy.cache(cache_shape, shape.global_batch)
            fn = make_prefill_step(model, qcfg)
            lowered = jax.jit(
                fn,
                in_shardings=(pspec, bspec),
                out_shardings=(None, cspec),
            ).lower(params_shape, in_specs)
        else:  # decode
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len)
            )
            cspec = policy.cache(cache_shape, shape.global_batch)
            fn = make_decode_step(model, qcfg)
            tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(
                fn,
                in_shardings=(pspec, cspec,
                              policy.batch({"token": tok})["token"],
                              NamedSharding(mesh, P())),
                out_shardings=(None, cspec),
                donate_argnums=(1,),
            ).lower(params_shape, cache_shape, tok, pos)

        compiled = lowered.compile()

    rec["lower_compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    # jax>=0.4.30 returns a per-device-program list; older returned a dict
    if isinstance(xla_cost, (list, tuple)):
        xla_cost = xla_cost[0] if xla_cost else {}
    # loop-aware re-analysis (XLA counts while bodies once; ours multiplies
    # by trip count — see hlo_cost.py). All numbers are PER DEVICE: the HLO
    # is the SPMD-partitioned per-device module.
    an = hlo_cost.analyze(compiled.as_text())
    flops, bytes_acc, coll = an["flops"], an["bytes"], an["collective_bytes"]

    rec.update(
        status="ok",
        n_chips=n_chips,
        hlo_flops=flops,
        hlo_bytes=bytes_acc,
        collective_bytes=coll,
        xla_flops_unscaled=float(xla_cost.get("flops", 0.0)),
        memory={
            k: getattr(mem, k, None)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
        },
    )
    # MODEL_FLOPS: 6*N*D train / 2*N*D forward (active params for MoE)
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    rec["model_flops_per_chip"] = model_flops / n_chips
    rec["useful_flops_ratio"] = (
        rec["model_flops_per_chip"] / flops if flops else 0.0
    )
    rec["roofline"] = {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll["total"] / ICI_BW,
    }
    dom = max(
        ("compute_s", "memory_s", "collective_s"),
        key=lambda k: rec["roofline"][k],
    )
    rec["roofline"]["dominant"] = dom

    if comm_round and multi_pod:
        key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        for wire, mode in (("fp8", "rand"), ("f32", "rand"), ("f32", "none")):
            cr = make_comm_round(mesh, pspec_to_pspecs(pspec), ("pod",), qcfg,
                                 mode=mode, wire=wire)
            with mesh:
                compiled_cr = jax.jit(cr).lower(params_shape, key_spec).compile()
            rec[f"comm_round_{wire}_{mode}"] = hlo_cost.analyze(
                compiled_cr.as_text()
            )["collective_bytes"]
    return rec


def pspec_to_pspecs(sharding_tree):
    return jax.tree.map(lambda s: s.spec, sharding_tree,
                        is_leaf=lambda x: isinstance(x, NamedSharding))


def iter_cells():
    for arch in configs.ARCH_IDS:
        for shape in SHAPES:
            yield arch, shape


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--comm-round", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cells = list(iter_cells()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    records = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp, qat=not args.no_qat,
                               comm_round=args.comm_round)
            except Exception as e:  # a failed cell is a bug; surface it loudly
                rec = {
                    "arch": arch, "shape": shape,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:],
                }
            records.append(rec)
            r = rec.get("roofline", {})
            print(
                f"[{rec['status']:4s}] {arch:24s} {shape:12s} {rec['mesh']:8s} "
                f"flops={rec.get('hlo_flops', 0):.3e} "
                f"dom={r.get('dominant', '-')} "
                f"t={rec.get('lower_compile_s', 0)}s",
                flush=True,
            )
            if rec["status"] == "FAIL":
                print(rec["error"], file=sys.stderr, flush=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
    bad = [r for r in records if r["status"] == "FAIL"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
