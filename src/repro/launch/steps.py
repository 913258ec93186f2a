"""Step functions lowered by the dry-run / production trainer.

* ``make_train_step``   — one local QAT training step (forward, backward,
  AdamW update). In the cross-silo FL deployment this runs U times between
  round boundaries.
* ``make_comm_round``   — the FedAvg round boundary as a *quantized
  collective*: Q_rand on every weight tensor, then mean over the federated
  mesh axes (paper Algorithm 1 uplink+aggregate+downlink fused).
* ``make_prefill_step`` / ``make_decode_step`` — serving paths.

opt_level >= 1 pre-quantizes the weight tree once per step on the tiled
parameter plane (``core.plane``): one fused Q_det launch for the whole
tree, forward and VJP replay, instead of one per tensor. FSDP-sharded
lowerings (``grad_shardings`` set) use the SHARD-AWARE plane
(:func:`quantize_params_once_sharded`): a ``shard_map`` whose body builds
the per-device plane over the local leaf shards — still one launch per
device, no cross-shard resharding. The old per-leaf loop survives only as
the parity reference (:func:`quantize_params_once_per_leaf`).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core import compression
from ..core.qat import QATConfig, weight_decay_mask
from ..models.registry import Model
from ..optim import adamw, sgd
from ..optim.base import Optimizer, apply_updates

PyTree = Any


def make_optimizer(params_shape: PyTree, kind: str = "adamw",
                   lr: float = 3e-4) -> Optimizer:
    from ..core.qat import clip_value_mask

    mask = weight_decay_mask(params_shape)
    tmask = clip_value_mask(params_shape)
    if kind == "adamw":
        return adamw(lr, weight_decay=0.01, wd_mask=mask, trust_mask=tmask)
    return sgd(lr, momentum=0.9, weight_decay=1e-4, wd_mask=mask,
               trust_mask=tmask)


def quantize_params_once(params: PyTree, qcfg: QATConfig) -> tuple[PyTree, QATConfig]:
    """Beyond-paper §Perf optimization: hoist the deterministic weight
    fake-quant out of the model graph.

    Q_det is a pure function of (w, alpha); inside one optimizer step it is
    evaluated identically at every use (every layer pass, every microbatch,
    every remat recompute). Quantizing the whole parameter tree ONCE is
    mathematically identical (STE gradients flow through this call into w
    and alpha via normal autodiff) and removes O(accum x layers x
    remat-passes) redundant fake-quant chains plus lets downstream consume
    bf16 quantized values instead of f32 master weights. Measured effect:
    see EXPERIMENTS.md §Perf.

    The tree quantizes on the tiled parameter plane (``core.plane``): every
    quantized leaf rides one ``(rows, LANE)`` buffer with a per-row alpha
    column, so the whole-tree fake-quant — forward AND the VJP replay at
    the end of the step — is ONE fused kernel launch instead of
    O(n_tensors). Values and STE gradients match the per-leaf loop
    (:func:`quantize_params_once_per_leaf`) to float accumulation noise.

    Sharding caveat: packing the plane concatenates leaves, which under
    GSPMD reshards FSDP-sharded masters; ``make_train_step`` therefore
    selects :func:`quantize_params_once_sharded` (the shard-aware plane —
    one launch per device over the local shards) whenever it lowers with
    explicit ``grad_shardings``, and this one-launch global plane
    everywhere else (simulator, host meshes, replicated params).
    """
    if not (qcfg.enabled and qcfg.quantize_weights):
        return params, qcfg
    from ..core import plane
    from ..models.common import COMPUTE_DTYPE

    qparams = plane.quantize_det(params, fmt=qcfg.fmt,
                                 out_dtype=COMPUTE_DTYPE)
    return qparams, qcfg.replace(quantize_weights=False)


def quantize_params_once_sharded(
    params: PyTree, qcfg: QATConfig, shardings: PyTree
) -> tuple[PyTree, QATConfig]:
    """Shard-aware variant of :func:`quantize_params_once` for FSDP-sharded
    masters: a ``shard_map`` over the shardings' mesh whose body runs the
    plane quantize on each device's LOCAL shards (``core.plane``'s
    shard-aware layout) — ONE fused launch per device, zero cross-shard
    traffic, and the ``shard_map`` transpose psums per-shard alpha
    cotangents so STE gradients match the replicated plane. This is the
    hot path ``make_train_step`` lowers when ``grad_shardings`` is set;
    the per-leaf loop it retires stays as the parity reference."""
    if not (qcfg.enabled and qcfg.quantize_weights):
        return params, qcfg
    from ..core import plane
    from ..models.common import COMPUTE_DTYPE

    qparams = plane.quantize_det_sharded(params, shardings, fmt=qcfg.fmt,
                                         out_dtype=COMPUTE_DTYPE)
    return qparams, qcfg.replace(quantize_weights=False)


def quantize_params_once_per_leaf(
    params: PyTree, qcfg: QATConfig
) -> tuple[PyTree, QATConfig]:
    """Per-leaf PARITY REFERENCE for :func:`quantize_params_once` /
    :func:`quantize_params_once_sharded` — O(n_tensors) quantize chains,
    purely elementwise per leaf. Retired from the FSDP hot path (the
    shard-aware plane replaced it); kept for grad-parity tests and the
    launch-collapse benchmarks."""
    if not (qcfg.enabled and qcfg.quantize_weights):
        return params, qcfg
    import jax.numpy as _jnp

    from ..core import fp8 as fp8_lib
    from ..core import qat as qat_lib
    from ..models.common import COMPUTE_DTYPE

    qnames = qat_lib.quantized_leaf_names(params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    by_name = {
        ".".join(qat_lib._key_name(p) for p in path): leaf
        for path, leaf in flat
    }
    out = []
    for path, leaf in flat:
        dotted = ".".join(qat_lib._key_name(p) for p in path)
        if dotted in qnames:
            alpha = by_name[dotted + qat_lib.QA_SUFFIX]
            q = fp8_lib.quantize_det(leaf.astype(_jnp.float32), alpha, qcfg.fmt)
            out.append(q.astype(COMPUTE_DTYPE))
        else:
            out.append(leaf)
    return (jax.tree_util.tree_unflatten(treedef, out),
            qcfg.replace(quantize_weights=False))


def make_train_step(model: Model, opt: Optimizer, qcfg: QATConfig,
                    accum: int = 1, opt_level: int = 1,
                    grad_shardings: PyTree | None = None):
    """One optimizer step; ``accum > 1`` splits the global batch into
    microbatches and accumulates grads in a scan — bounds the live
    activation (and scan-residual) memory by 1/accum, the standard
    large-model memory knob.

    opt_level 0 = paper-naive lowering (weights fake-quantized at every
    use); opt_level 1 = quantize-once-per-step + sharded (reduce-scatter)
    gradient accumulation; opt_level 2 = additionally reduce gradients
    across the mesh in bf16 (halves the per-microbatch gradient collective
    payload; accumulation itself stays f32). Each level is lowered by the
    dry-run so §Perf reports before/after.
    """
    reduce_dtype = jnp.bfloat16 if opt_level >= 2 else None

    def constrain(g, cast=False):
        if cast and reduce_dtype is not None:
            # cast BEFORE the sharding constraint so the reduce-scatter XLA
            # inserts at the constraint moves bf16, not f32
            g = jax.tree.map(
                lambda x: x.astype(reduce_dtype)
                if x.dtype == jnp.float32 else x, g,
            )
        if grad_shardings is None:
            return g
        return jax.tree.map(jax.lax.with_sharding_constraint, g,
                            grad_shardings)

    def accumulate(loss_grads_fn, batch, like):
        """Run loss_grads_fn per microbatch, summing grads (f32)."""
        if accum == 1:
            loss, grads = loss_grads_fn(batch)
            return loss, constrain(grads)
        micro = jax.tree.map(
            lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
            batch,
        )

        def body(carry, mb):
            loss_acc, g_acc = carry
            loss, g = loss_grads_fn(mb)
            g = constrain(g, cast=True)  # reduce-scatter (bf16 at opt>=2)
            g_acc = constrain(jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), g_acc, g
            ))
            return (loss_acc + loss, g_acc), None

        g0 = constrain(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), like
        ))
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), g0), micro
        )
        return loss / accum, jax.tree.map(lambda g: g / accum, grads)

    # sharded (FSDP) lowering quantizes on the SHARD-AWARE plane: one
    # launch per device over the local shards — the global plane would
    # reshard the concatenated f32 masters under GSPMD (see
    # quantize_params_once docstring)
    if grad_shardings is not None:
        quantize_once = functools.partial(quantize_params_once_sharded,
                                          shardings=grad_shardings)
    else:
        quantize_once = quantize_params_once

    def train_step(params, opt_state, batch, step):
        if opt_level >= 1:
            # quantize the tree ONCE; vjp replays the STE chain once at the end
            params_q, vjp_quant = jax.vjp(
                lambda p: quantize_once(p, qcfg)[0], params
            )
            q_inner = qcfg.replace(quantize_weights=False)

            def loss_grads(mb):
                return jax.value_and_grad(
                    lambda pq: model.train_loss(pq, mb, q_inner)
                )(params_q)

            loss, g_q = accumulate(loss_grads, batch, params_q)
            # cotangent dtypes must match params_q (bf16 weight leaves)
            g_q = jax.tree.map(lambda g, pq: g.astype(pq.dtype), g_q, params_q)
            grads = vjp_quant(g_q)[0]
        else:
            def loss_grads(mb):
                return jax.value_and_grad(
                    lambda p: model.train_loss(p, mb, qcfg)
                )(params)

            loss, grads = accumulate(loss_grads, batch, params)
        updates, opt_state = opt.update(grads, opt_state, params, step)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    return train_step


def aggregator_state_specs(aggregator, param_specs: PyTree) -> PyTree:
    """Sharding specs for a built-in Aggregator's server state.

    FedAvgM's momentum mirrors the param tree (shard like the params);
    FedAdam carries two mirrored moment trees; stateless aggregators
    carry ``()``. A custom STATEFUL aggregator has a state structure this
    helper cannot know — pass ``state_specs`` to ``make_comm_round``
    explicitly (a silent ``()`` would die as an opaque shard_map pytree
    mismatch instead).
    """
    from ..core import engine as fed_engine

    if isinstance(aggregator, fed_engine.FedAvgM):
        return param_specs
    if isinstance(aggregator, fed_engine.FedAdam):
        return {"m": param_specs, "v": param_specs}
    if not jax.tree_util.tree_leaves(aggregator.init(jnp.zeros(()))):
        return ()   # stateless: opt state is empty
    raise ValueError(
        f"cannot derive state sharding specs for custom stateful "
        f"aggregator {type(aggregator).__name__}; pass state_specs to "
        "make_comm_round explicitly"
    )


def make_comm_round(mesh, param_specs: PyTree, fl_axes: tuple[str, ...],
                    qcfg: QATConfig, mode: str = "rand",
                    wire: str = "fp8", aggregator=None,
                    state_specs: PyTree | None = None,
                    codec=None, partial: bool = False,
                    min_quorum: int = 0, scaling=None):
    """FedAvg round boundary over ``fl_axes`` as a shard_map'd collective.

    ``wire='fp8'`` moves uint8 codes (the paper's 4x compression as actual
    collective bytes); ``wire='f32'`` quantizes values but reduces in f32
    (the conservative variant); ``mode='none'`` + wire='f32' is the FP32
    FedAvg baseline.

    ``aggregator=None`` keeps the fused in-collective mean and the legacy
    ``(params, key) -> params`` signature. Passing a ``core.engine``
    Aggregator instead gathers the per-silo models (still ONE compressed
    payload each on the coded wire — ``compression.fp8_wire_allgather``)
    and applies the aggregator's tail, threading its server state:
    ``(params, comm_state, key) -> (params, comm_state)`` with
    ``comm_state = {"prev": previous_global_model, "opt": agg opt state}``
    (build the initial one with :func:`comm_round_state`). ``prev`` is the
    FedOpt baseline: every silo's LOCAL params have diverged through local
    training, so a pseudo-gradient taken against them would give each silo
    a different "global" update that compounds round over round — the
    previous boundary's output is identical on every silo, so the
    aggregator output is too. That is how FedAvgM/FedAdam momentum lives
    at a production round boundary.

    ``codec`` (aggregator path only): a ``core.codec`` WireCodec or
    registry name replacing the legacy ``(qcfg.fmt, mode)`` wire — e.g.
    ``'fp4'`` for a 2-codes/byte boundary, or ``'delta:e4m3'``, whose
    reference model is exactly ``comm_state["prev"]``: the previous global
    model every silo already holds, so only the round's *update* crosses
    the inter-silo wire.

    ``partial=True`` (aggregator path only) makes the boundary
    fault-tolerant, mirroring the simulator's fault layer
    (``core.faults``): the returned fn takes an extra replicated
    ``alive`` mask ``(n_silos,) bool`` — ``(params, comm_state, key,
    alive) -> (params, comm_state)``. Dead silos' gathered models are
    replaced by the previous global model and their aggregation weight
    zeroed, so survivors renormalize by the surviving count; when fewer
    than ``min_quorum`` (resolved via ``core.faults.quorum_count``; 0 =
    any survivor) are alive, the round is discarded — params AND
    aggregator state pass through unchanged. NOTE: a dead silo still
    participates in the *collective* (SPMD programs cannot drop a
    participant mid-step); what the mask models is its *payload* being
    rejected at the boundary.

    ``scaling`` (aggregator path only): a ``core.scaling`` policy —
    ``'current'``/None keeps today's trained-clip grid bit-for-bit;
    ``'delayed[:H[:M]]'`` derives the boundary's shared grid from a
    rolling amax history threaded in ``comm_state["scales"]`` (seed it
    via ``comm_round_state(..., scaling=...)``), updated each boundary
    from the fused quantize launch's amax byproduct pmax'd across silos
    — no fresh reduction, and no ``sync_alphas`` pmax either (the
    history IS the shared grid). ``'frozen'`` is rejected: the gathered
    models are freshly trained per silo, so there are no already-held
    scales to reuse (the same reason the simulator rejects frozen
    uplinks). Under ``partial=True`` the history row is the
    pre-rejection pmax — a dead silo's amax still rode the collective,
    which is conservative (never under-scales), and a below-quorum
    discarded round leaves the history untouched.
    """
    from jax import shard_map

    from ..core import scaling as scaling_lib

    policy = scaling_lib.get_policy(scaling)
    if not policy.is_current:
        if aggregator is None:
            raise ValueError(
                "scaling= needs the aggregator path (the fused "
                "in-collective mean owns its own grid); pass an Aggregator"
            )
        if not isinstance(policy, scaling_lib.DelayedScaling):
            raise ValueError(
                f"make_comm_round supports scaling='current' or "
                f"'delayed[:H[:M]]' only, got {policy.name!r} (frozen is a "
                "simulator downlink policy — freshly-trained silo models "
                "have no already-held scales to reuse)"
            )
    scaled = not policy.is_current

    def _perturb(params):
        # In the dry-run, params enter pod-replicated; real FL silos hold
        # DISTINCT weights. Make them formally distinct per silo so the
        # partitioner cannot fold the aggregation collectives away —
        # otherwise the lowering (and its measured bytes) is vacuous.
        idx = sum(jax.lax.axis_index(a) for a in fl_axes).astype(jnp.float32)
        eps = jnp.float32(1e-30) * idx  # non-foldable, numerically nil
        return jax.tree.map(
            lambda x: (x + eps.astype(x.dtype)) if jnp.issubdtype(
                x.dtype, jnp.floating) else x,
            params,
        )

    if aggregator is None:
        if codec is not None:
            raise ValueError(
                "codec= needs the aggregator path (the fused in-collective "
                "mean is FP8-wire only); pass an Aggregator"
            )
        if partial:
            raise ValueError(
                "partial=True needs the aggregator path (the fused "
                "in-collective mean cannot mask per-silo payloads); "
                "pass an Aggregator"
            )

        def body(params, key):
            params = _perturb(params)
            if wire == "fp8" and mode != "none":
                return compression.fp8_wire_allreduce_mean(
                    params, key, fl_axes, qcfg.fmt
                )
            return compression.quantized_allreduce_mean(
                params, key, fl_axes, qcfg.fmt, mode=mode
            )

        return shard_map(
            body,
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=param_specs,
            check_vma=False,
        )

    import numpy as np

    if wire != "fp8":
        # the aggregator path gathers stacked per-silo trees through the u8
        # wire codec (values identical to an f32 gather of the quantized
        # tree — the codec is exact); a separate f32-wire variant would be
        # indistinguishable except in bytes, so reject rather than silently
        # substitute
        raise ValueError(
            "make_comm_round(aggregator=...) supports wire='fp8' only; "
            f"got wire={wire!r}"
        )
    n_silos = int(np.prod([mesh.shape[a] for a in fl_axes]))
    if state_specs is None:
        state_specs = aggregator_state_specs(aggregator, param_specs)
    comm_specs = {"prev": param_specs, "opt": state_specs}
    if scaled:
        comm_specs["scales"] = P()

    resolved_codec = None
    if codec is not None:
        from ..core import codec as codec_lib

        resolved_codec = codec_lib.get_codec(codec)
    if scaled:
        from ..core import codec as codec_lib

        boundary_codec = (resolved_codec if resolved_codec is not None
                          else codec_lib.codec_for(qcfg.fmt, mode))
        if not isinstance(boundary_codec, codec_lib.Fp8Codec):
            raise ValueError(
                f"scaling={policy.name!r} needs a plain FP8-family "
                f"boundary codec, got {type(boundary_codec).__name__} "
                "(no FP32 passthrough or DeltaCodec)"
            )

    def body_agg(params, comm_state, key, alive=None):
        params = _perturb(params)
        k_wire, k_srv = jax.random.split(key)
        # mode passes through: 'rand' (unbiased), 'det' (biased ablation),
        # 'none' (f32 gather — the FP32 baseline); codec= overrides with a
        # first-class wire codec, ref = the previous global model (the one
        # tree every silo is guaranteed to share — see docstring)
        if scaled:
            a_eff = policy.effective(comm_state["scales"])
            stacked, amax = compression.fp8_wire_allgather(
                params, k_wire, fl_axes, qcfg.fmt, mode=mode,
                codec=resolved_codec, alpha_override=a_eff,
                collect_amax=True,
            )
            new_scales = policy.update(comm_state["scales"], amax)
        else:
            stacked = compression.fp8_wire_allgather(
                params, k_wire, fl_axes, qcfg.fmt, mode=mode,
                codec=resolved_codec, ref=comm_state["prev"],
            )
        nk = jnp.ones((n_silos,), jnp.float32)
        if alive is not None:
            # the simulator fault layer's contract at the silo boundary:
            # dead silos' payloads are replaced by the previous global
            # model and zero-weighted; survivors renormalize by sum(nk)
            prev = comm_state["prev"]
            stacked = jax.tree.map(
                lambda m, f: jnp.where(
                    alive.reshape((n_silos,) + (1,) * (m.ndim - 1)), m, f
                ),
                stacked, prev,
            )
            n_alive = jnp.sum(alive.astype(jnp.int32))
            nk = alive.astype(jnp.float32)
            nk = jnp.where(n_alive > 0, nk, jnp.ones_like(nk))
        # baseline = the previous GLOBAL model (replicated across silos),
        # never the silo's diverged local params — see docstring
        new_params, new_opt = aggregator(
            comm_state["prev"], stacked, nk, k_srv, comm_state["opt"]
        )
        if alive is not None:
            ok = n_alive >= _quorum
            keep = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), new, old
            )
            new_params = keep(new_params, comm_state["prev"])
            new_opt = keep(new_opt, comm_state["opt"])
            if scaled:
                # a discarded round must not advance the amax history
                new_scales = keep(new_scales, comm_state["scales"])
        out_state = {"prev": new_params, "opt": new_opt}
        if scaled:
            out_state["scales"] = new_scales
        return new_params, out_state

    if partial:
        from ..core.faults import quorum_count

        _quorum = quorum_count(min_quorum, n_silos)
        return shard_map(
            body_agg,
            mesh=mesh,
            in_specs=(param_specs, comm_specs, P(), P()),
            out_specs=(param_specs, comm_specs),
            check_vma=False,
        )

    return shard_map(
        body_agg,
        mesh=mesh,
        in_specs=(param_specs, comm_specs, P()),
        out_specs=(param_specs, comm_specs),
        check_vma=False,
    )


def comm_round_state(aggregator, params: PyTree, scaling=None) -> dict:
    """Initial threaded state for ``make_comm_round(aggregator=...)``: the
    global model every silo starts from + the aggregator's opt state.

    Pass the same ``scaling`` given to :func:`make_comm_round` — a delayed
    policy adds a ``"scales"`` history seeded from the model's trained
    clip alphas (round 0 matches the no-history recipe).

    ``prev`` is a COPY, not an alias: trainers donate their param buffers
    to the jitted step (``donate_argnums``), which would delete an aliased
    ``prev`` out from under the next boundary / checkpoint."""
    state = {"prev": jax.tree.map(lambda x: jnp.array(x), params),
             "opt": aggregator.init(params)}
    from ..core import scaling as scaling_lib

    policy = scaling_lib.get_policy(scaling)
    if not policy.is_current:
        from ..core import wire as wire_lib

        spec = wire_lib.make_wire_spec(params)
        state["scales"] = policy.init_state(
            scaling_lib.leaf_alphas(params, spec)
        )
    return state


def make_prefill_step(model: Model, qcfg: QATConfig):
    def prefill_step(params, batch):
        return model.prefill(params, batch, qcfg)

    return prefill_step


def make_decode_step(model: Model, qcfg: QATConfig):
    def decode_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos, qcfg)

    return decode_step
