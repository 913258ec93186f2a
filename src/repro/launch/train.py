"""Production cross-silo federated trainer (DESIGN.md §4).

One *silo* = one group of mesh rows along the federated axes. Within a
silo, training is ordinary DP/FSDP+TP; every ``--local-steps`` steps the
FedAvg round boundary runs as a quantized collective
(``core.compression.quantized_allreduce_mean``) across the silo axes.

Fault tolerance: atomic keep-k checkpoints (params + opt state + round
counter + data cursor); ``--resume`` restores and re-shards onto the
*current* mesh — elastic by construction since checkpoints are
mesh-agnostic. Client/silo dropout: a silo that misses the deadline is
excluded from the quantized all-reduce by its participation weight (the
collective weights by the live-silo count).

On this CPU container the same code path runs with the host mesh
(``--mesh host``) and a reduced config (``--reduced``) — that is what
examples/train_lm100m.py drives. The production mesh is exercised by
``dryrun.py``.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import configs
from ..checkpoint import CheckpointManager
from ..core.qat import DISABLED, QATConfig
from ..data.pipeline import LMBatcher, silo_stream
from ..models import registry
from ..models.common import sharding_rules
from ..sharding.policy import ShardingPolicy
from .mesh import make_host_mesh, make_production_mesh
from .compile_cache import enable_compile_cache
from .steps import make_comm_round, make_optimizer, make_train_step


def build_trainer(cfg, mesh, qat: bool, lr: float, opt_kind: str = "adamw"):
    policy = ShardingPolicy(mesh)
    model = registry.get_model(cfg)
    qcfg = QATConfig() if qat else DISABLED

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspec = policy.params(params_shape)
    opt = make_optimizer(params_shape, kind=opt_kind, lr=lr)
    ospec = policy.params(jax.eval_shape(opt.init, params_shape))

    step_fn = make_train_step(model, opt, qcfg)
    jitted = jax.jit(
        step_fn,
        in_shardings=(pspec, ospec, None, None),
        out_shardings=(pspec, ospec, None),
        donate_argnums=(0, 1),
    )
    return model, opt, jitted, policy, qcfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--local-steps", type=int, default=10,
                    help="U: steps between federated round boundaries")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--no-qat", action="store_true")
    ap.add_argument("--comm-mode", default="rand",
                    choices=["rand", "det", "none"])
    ap.add_argument("--server-opt", default="mean",
                    choices=["mean", "fedavgm", "fedadam"],
                    help="aggregator at the round boundary (core.engine); "
                         "fedavgm/fedadam thread server momentum across "
                         "rounds (and through checkpoints)")
    ap.add_argument("--server-lr", type=float, default=None,
                    help="server step size; default = the aggregator's own "
                         "default (FedAvgM 1.0, FedAdam 0.1)")
    ap.add_argument("--mesh", default="host", choices=["host", "pod", "multipod"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")

    model, opt, jitted, policy, qcfg = build_trainer(
        cfg, mesh, not args.no_qat, args.lr
    )

    stream = silo_stream(cfg.vocab, args.batch * (args.seq + 1) * 64, 0,
                         args.seed)
    batcher = LMBatcher(stream, args.batch, args.seq)

    mgr = CheckpointManager(args.ckpt_dir, keep=3, every=args.ckpt_every)
    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)

    # server-side aggregator at the round boundary (core.engine): mean is
    # the stateless FedAvg tail; fedavgm/fedadam carry momentum that must
    # thread through rounds AND checkpoints
    from ..core import engine as fed_engine

    aggregator = None if args.server_opt == "mean" else \
        fed_engine.make_aggregator(args.server_opt, lr=args.server_lr)
    agg_state = ()
    if aggregator is not None:
        from .steps import comm_round_state
        agg_state = comm_round_state(aggregator, params)

    start = 0
    if args.resume:
        from ..checkpoint.manager import latest_step, load_checkpoint
        if latest_step(args.ckpt_dir) is not None:
            tree, manifest = load_checkpoint(
                args.ckpt_dir, {"params": params, "opt": opt_state}
            )
            params, opt_state = tree["params"], tree["opt"]
            params = jax.device_put(params, policy.params(params))
            opt_state = jax.device_put(opt_state, policy.params(opt_state))
            if aggregator is not None:
                # server state is absent from checkpoints written with
                # --server-opt mean (or pre-engine runs); restart the
                # momentum fresh rather than KeyError deep in np.load
                try:
                    srv, _ = load_checkpoint(args.ckpt_dir,
                                             {"srv": agg_state})
                    agg_state = jax.device_put(
                        jax.tree.map(jnp.asarray, srv["srv"]),
                        policy.params(srv["srv"]),
                    )
                except KeyError:
                    # rebuild from the RESTORED params: the pseudo-gradient
                    # baseline must anchor to the checkpointed model, not
                    # the fresh random init agg_state was first built from
                    agg_state = comm_round_state(aggregator, params)
                    print("checkpoint has no server-optimizer state; "
                          "starting momentum fresh")
            start = manifest["step"]
            print(f"resumed at step {start}")

    fl_axes = tuple(a for a in ("pod",) if a in mesh.axis_names
                    and mesh.shape[a] > 1)
    comm_round = None
    if fl_axes:
        # built + jitted ONCE: the round boundary's quantized collective is
        # the same computation every round, so constructing it inside the
        # loop would retrace (and re-lower) it at every boundary
        from .dryrun import pspec_to_pspecs

        comm_round = jax.jit(make_comm_round(
            mesh, pspec_to_pspecs(policy.params(params)), fl_axes,
            qcfg, mode=args.comm_mode, aggregator=aggregator,
        ))

    with mesh, sharding_rules(policy.activation_rules()):
        t0 = time.time()
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in batcher(step).items()}
            params, opt_state, m = jitted(
                params, opt_state, batch, jnp.asarray(step, jnp.int32)
            )
            if comm_round is not None and (step + 1) % args.local_steps == 0:
                # federated round boundary: quantized collective across silos
                if aggregator is None:
                    params = comm_round(params, jax.random.PRNGKey(step))
                else:
                    params, agg_state = comm_round(
                        params, agg_state, jax.random.PRNGKey(step)
                    )
            if (step + 1) % 10 == 0 or step == start:
                print(
                    f"step {step+1:5d}  loss {float(m['loss']):.4f}  "
                    f"{(step + 1 - start) / (time.time() - t0):.2f} it/s",
                    flush=True,
                )
            tree = {"params": params, "opt": opt_state}
            if aggregator is not None:
                tree["srv"] = agg_state
            mgr.maybe_save(step + 1, tree, extra={"arch": args.arch})
        tree = {"params": params, "opt": opt_state}
        if aggregator is not None:
            tree["srv"] = agg_state
        mgr.maybe_save(args.steps, tree, extra={"arch": args.arch}, force=True)
    print("done")


if __name__ == "__main__":
    main()
