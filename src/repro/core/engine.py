"""Composable federated round engine — Algorithm 1 as four pluggable stages.

``fedavg.make_round`` used to hardwire one round shape: uniform sampling,
full-cohort vmap, the same E4M3 wire on both links, and a stateless
weighted-mean tail. This module decomposes the round into stages that can
be swapped independently:

* **ClientSampler** — who participates this round. ``UniformSampler``
  (uniform without replacement — the paper's setting), ``WeightedSampler``
  (nk-proportional without replacement via Gumbel top-k), and
  ``FixedCohortSampler`` (deterministic cohort, e.g. cross-silo).
* **Link** — what crosses the wire, per direction. ``WireLink`` is a pair
  of :mod:`repro.core.codec` ``WireCodec`` objects — FP8 (``Fp8Codec``,
  today's 1-byte wire), sub-byte packed formats (``PackedFpCodec``, FP4
  at 2 codes/byte), residual encoding (``DeltaCodec``, uplink-only:
  the reference is the round's broadcast model), a per-round
  ``CodecSchedule`` resolved in-jit from the round-index operand, or FP32
  passthrough (``Fp32Codec``). The legacy per-direction ``(fmt, mode)``
  knobs survive as deprecation shims that resolve through
  ``codec.codec_for`` — e.g. E4M3 down / E5M2 up, the hybrid recipe of
  Micikevicius et al. (*FP8 Formats for Deep Learning*) — bit-identically
  to the pre-codec wire. Byte accounting is per-direction and delegates
  to each codec: every leg is charged at its real payload size.
* **ClientExecutor** — how the cohort's local updates run. ``VmapExecutor``
  is the original full-cohort vmap; ``ChunkedExecutor(chunk)`` scans over
  chunks-of-vmap so peak live memory (per-client optimizer state,
  activations, scan residuals) is O(chunk) instead of O(P) — this is what
  lets cohort sizes reach the thousands on fixed memory.
  ``ShardedExecutor(mesh, axis)`` spreads the cohort axis across a named
  device mesh axis with ``shard_map`` — each device trains P/D clients
  (optionally chunk-scanned, so per-device live memory is O(chunk)) and
  contributes its shard of the uplink as ONE contiguous uint8 payload to a
  single compressed all-gather (``compression.fp8_wire_allgather_clients``).
  All three are bit-identical under the same key: every client sees the
  same ``(params, data, key)`` triple regardless of the schedule.
* **Aggregator** — the server tail, now allowed to carry *state* across
  rounds. ``MeanAggregator`` (weighted mean), ``ServerOptAggregator``
  (UQ+ ``server_optimize``), and the stateful ``FedAvgM`` / ``FedAdam``
  (Reddi et al., *Adaptive Federated Optimization*) whose momentum /
  second-moment state threads through ``ServerState``.

The round signature is ``(server_state, data, labels, nk, key) ->
(server_state, metrics)`` where ``ServerState = (params, opt, round)``
(``round`` is the schedule's round-index operand and stays ``()`` — no
extra leaf — unless the link carries a ``CodecSchedule``). The
simulator (``core.fedsim``) threads the state; ``fedavg.make_round``
remains as a thin back-compat shim for stateless configurations; the
production collective boundary (``launch.steps.make_comm_round``) applies
the same Aggregator objects after its mesh all-gather.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import codec as codec_lib
from . import ef as ef_lib
from . import scaling as scaling_lib
from . import wire
from .codec import CodecSchedule, DeltaCodec, Fp32Codec, Fp8Codec, WireCodec
from .ef import ErrorFeedbackCodec
from .faults import FaultModel, quorum_count
from .fp8 import E4M3, E5M2, FP8Format
from .qat import QATConfig
from .server_opt import ServerOptConfig, server_optimize, weighted_mean
from ..kernels import dispatch
from ..optim.base import Optimizer, apply_updates

Array = jax.Array
PyTree = Any
LossFn = Callable[..., Array]  # (params, x, y, qat_cfg, key) -> scalar


class ServerState(NamedTuple):
    """What the server carries between rounds: the model + aggregator state.

    ``opt`` is ``()`` for stateless aggregators, so the state is exactly
    the params pytree plus nothing — checkpoints of stateless runs stay
    as small as before. ``round`` is the round-index operand a per-round
    :class:`repro.core.codec.CodecSchedule` resolves against inside the
    jitted round; it stays ``()`` (no extra leaf, unchanged pytree) unless
    the link carries a schedule. ``scales`` threads per-leg
    :class:`repro.core.scaling.ScalingPolicy` state (a ``(down, up)``
    tuple — the rolling amax history of a delayed leg) and likewise stays
    ``()`` unless a leg scales away from ``current``, so every legacy
    checkpoint keeps its exact pytree. ``clients`` is persistent
    PER-CLIENT state — today a :class:`repro.core.ef.ClientState` holding
    the ``(n_clients, spec.total)`` error-feedback residual memory of an
    :class:`~repro.core.ef.ErrorFeedbackCodec` uplink — gathered by
    cohort index each round and scattered back after the uplink; it
    stays ``()`` on every non-EF link (same conditional-leaf discipline
    as ``round``/``scales``), so legacy checkpoints are untouched.
    """

    params: PyTree
    opt: PyTree
    round: PyTree = ()
    scales: PyTree = ()
    clients: PyTree = ()


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """One federated experiment. The original fields keep their exact
    defaults (and semantics) so every pre-engine config reproduces
    bit-for-bit; the engine knobs below default to the legacy round shape.
    """

    n_clients: int = 100          # K
    participation: float = 0.1    # C
    local_steps: int = 50         # U (local gradient updates per round)
    batch_size: int = 50          # B
    comm_mode: str = "rand"       # 'rand' (UQ) | 'det' (biased ablation) | 'none' (FP32)
    qat: QATConfig = QATConfig()
    server_opt: ServerOptConfig = ServerOptConfig(enabled=False)
    fmt: FP8Format = E4M3

    # --- engine knobs (defaults == legacy behavior) ----------------------
    sampler: str = "uniform"      # 'uniform' | 'weighted' | 'fixed'
    chunk: int | None = None      # executor chunk size; None = full vmap
    # legacy per-direction link knobs — DEPRECATION SHIMS: they resolve to
    # codec-registry entries via codec.codec_for(fmt, mode) and are ignored
    # on any leg whose codec knob below is set
    down_fmt: FP8Format | None = None   # None -> fmt
    up_fmt: FP8Format | None = None     # None -> fmt
    down_mode: str | None = None        # None -> comm_mode
    up_mode: str | None = None          # None -> comm_mode
    # first-class wire codecs (core.codec): a WireCodec / CodecSchedule
    # instance or a registry name ('e4m3', 'e5m2_det', 'fp4', 'fp4_e3m0',
    # 'delta:e4m3', 'fp32', ...). `codec_schedule` applies one per-round
    # CodecSchedule to BOTH legs (precision annealing) and wins over the
    # per-leg knobs; per-leg knobs win over the legacy (fmt, mode) pairs.
    down_codec: Any = None
    up_codec: Any = None
    codec_schedule: Any = None
    # per-leg scaling policies (core.scaling): a ScalingPolicy instance or
    # a spec string ('current' | 'delayed[:H[:M]]' | 'frozen'). None is the
    # deprecation map — the historical no-knob behavior IS 'current', so
    # every pre-policy config resolves to the bit-identical default.
    down_scaling: Any = None
    up_scaling: Any = None
    aggregator: str = "auto"      # 'auto'|'mean'|'server_opt'|'fedavgm'|'fedadam'
    # cohort device mesh: shard the sampled-client axis over `client_axis`
    # of this jax.sharding.Mesh (ShardedExecutor; composes with `chunk` —
    # each shard scans chunks). None = legacy single-device execution.
    mesh: Any = None
    client_axis: str = "clients"
    # 2D federated mesh (launch.mesh.make_fed_mesh): name the FSDP axis
    # here and each client's training step is model-sharded over it with
    # the sharding/policy.py rules, while the wire/plane paths build
    # per-device planes over the local shards. None = 1D cohort-only mesh.
    model_axis: str | None = None
    # stateful-aggregator hyperparameters; None = that aggregator's own
    # class default (FedAvgM lr 1.0 / beta 0.9; FedAdam lr 0.1, beta2
    # 0.99, tau 1e-3) — so config and CLI paths agree on the defaults
    server_lr: float | None = None
    server_momentum: float | None = None  # FedAvgM beta / FedAdam beta1
    server_beta2: float | None = None     # FedAdam second-moment decay
    server_eps: float | None = None       # FedAdam tau
    # --- fault tolerance (core.faults) -----------------------------------
    # faults: a FaultModel injecting dropout / straggler-deadline /
    # corruption between executor and uplink. None (or FaultModel.none())
    # keeps the legacy round build — bitwise identical to the pre-fault
    # engine. min_quorum: minimum surviving clients for the round to count
    # (float in (0,1] = cohort fraction, int = absolute; 0 = any survivor).
    # quorum_policy: 'skip' discards a below-quorum round (server state
    # unchanged); 'degrade' proceeds with any nonzero survivor set.
    faults: Any = None
    min_quorum: float = 0.0
    quorum_policy: str = "skip"

    def __post_init__(self):
        """Eager validation: every mistake below used to surface as a deep
        jax trace error (or a silently-degenerate round) far from the
        config that caused it — fail at construction with the fix named."""
        if self.n_clients <= 0:
            raise ValueError(
                f"FedConfig.n_clients must be a positive client-pool size, "
                f"got {self.n_clients}"
            )
        if not 0.0 < self.participation <= 1.0:
            raise ValueError(
                f"FedConfig.participation must be in (0, 1] (the sampled "
                f"cohort fraction C), got {self.participation}"
            )
        if self.clients_per_round > self.n_clients:
            raise ValueError(
                f"cohort of {self.clients_per_round} exceeds the "
                f"{self.n_clients}-client pool; lower participation or "
                "grow n_clients"
            )
        if self.local_steps <= 0 or self.batch_size <= 0:
            raise ValueError(
                f"FedConfig.local_steps/batch_size must be positive, got "
                f"{self.local_steps}/{self.batch_size}"
            )
        if self.chunk is not None and self.chunk <= 0:
            raise ValueError(
                f"FedConfig.chunk must be a positive per-scan client count "
                f"(or None for full vmap), got {self.chunk}"
            )
        if self.sampler not in _SAMPLERS:
            raise ValueError(
                f"unknown sampler {self.sampler!r}; one of "
                f"{sorted(_SAMPLERS)}"
            )
        if self.aggregator not in _AGGREGATOR_NAMES:
            raise ValueError(
                f"unknown aggregator {self.aggregator!r}; one of "
                f"{sorted(_AGGREGATOR_NAMES)}"
            )
        if self.mesh is not None and self.client_axis not in getattr(
            self.mesh, "axis_names", ()
        ):
            raise ValueError(
                f"client_axis {self.client_axis!r} not on the given mesh "
                f"(axes: {tuple(getattr(self.mesh, 'axis_names', ()))}); "
                "build one with launch.mesh.make_client_mesh"
            )
        if self.model_axis is not None:
            if self.mesh is None:
                raise ValueError(
                    f"model_axis {self.model_axis!r} needs a 2D mesh; build "
                    "one with launch.mesh.make_fed_mesh(clients, fsdp)"
                )
            if self.model_axis == self.client_axis:
                raise ValueError(
                    f"model_axis and client_axis are both "
                    f"{self.model_axis!r} — a 2D federated mesh needs two "
                    "distinct axes (cohort x FSDP)"
                )
            if self.model_axis not in self.mesh.axis_names:
                raise ValueError(
                    f"model_axis {self.model_axis!r} not on the given mesh "
                    f"(axes: {tuple(self.mesh.axis_names)}); build one with "
                    "launch.mesh.make_fed_mesh"
                )
            if self.chunk is not None:
                raise ValueError(
                    "FedConfig.chunk (scan-chunked cohort) does not compose "
                    "with model_axis (GSPMD-sharded cohort); drop chunk or "
                    "the model axis"
                )
            if self.mesh.shape[self.client_axis] > self.clients_per_round:
                raise ValueError(
                    f"2D mesh has {self.mesh.shape[self.client_axis]} cohort "
                    f"rows but only {self.clients_per_round} clients per "
                    "round — rows past the cohort would train duplicate "
                    "padding clients; shrink the clients axis or raise "
                    "participation"
                )
        if self.mesh is not None:
            extra = [
                a for a in self.mesh.axis_names
                if a not in (self.client_axis, self.model_axis)
            ]
            if extra:
                raise ValueError(
                    f"mesh axes {extra} are neither client_axis "
                    f"({self.client_axis!r}) nor model_axis "
                    f"({self.model_axis!r}) — set FedConfig.model_axis for "
                    "2D meshes"
                )
        if self.quorum_policy not in ("skip", "degrade"):
            raise ValueError(
                f"quorum_policy {self.quorum_policy!r}: 'skip' (discard a "
                "below-quorum round) or 'degrade' (proceed with survivors)"
            )
        if isinstance(self.min_quorum, float) and not (
            0.0 <= self.min_quorum <= 1.0
        ):
            raise ValueError(
                f"float min_quorum is a cohort fraction in [0, 1], got "
                f"{self.min_quorum} (use an int for an absolute count)"
            )
        if isinstance(self.min_quorum, int) and not (
            0 <= self.min_quorum <= self.clients_per_round
        ):
            raise ValueError(
                f"int min_quorum must be in [0, cohort={self.clients_per_round}], "
                f"got {self.min_quorum}"
            )
        if self.faults is not None and not isinstance(self.faults, FaultModel):
            raise ValueError(
                f"FedConfig.faults takes a core.faults.FaultModel (or "
                f"None), got {type(self.faults).__name__}"
            )
        # eager policy resolution: a typo'd scaling spec fails here with
        # the accepted grammar named, not as a deep trace error
        scaling_lib.get_policy(self.down_scaling)
        scaling_lib.get_policy(self.up_scaling)

    @property
    def clients_per_round(self) -> int:
        return max(1, int(round(self.n_clients * self.participation)))

    @property
    def resolved_faults(self) -> "FaultModel | None":
        """The active FaultModel — None when absent or statically fault-free
        (``FaultModel.none()``), which keeps the legacy round build."""
        if self.faults is None or self.faults.is_none:
            return None
        return self.faults

    # resolved per-direction link settings (legacy (fmt, mode) view)
    @property
    def resolved_down(self) -> tuple[FP8Format, str]:
        return (self.down_fmt or self.fmt, self.down_mode or self.comm_mode)

    @property
    def resolved_up(self) -> tuple[FP8Format, str]:
        return (self.up_fmt or self.fmt, self.up_mode or self.comm_mode)

    def _resolved_codec(self, explicit, legacy: tuple[FP8Format, str]):
        if self.codec_schedule is not None:
            return codec_lib.get_codec(self.codec_schedule)
        if explicit is not None:
            return codec_lib.get_codec(explicit)
        return codec_lib.codec_for(*legacy)

    @property
    def resolved_down_codec(self):
        """The downlink WireCodec (codec knobs win over legacy knobs)."""
        return self._resolved_codec(self.down_codec, self.resolved_down)

    @property
    def resolved_up_codec(self):
        """The uplink WireCodec (codec knobs win over legacy knobs)."""
        return self._resolved_codec(self.up_codec, self.resolved_up)

    @property
    def resolved_down_scaling(self):
        """The downlink ScalingPolicy (None == 'current')."""
        return scaling_lib.get_policy(self.down_scaling)

    @property
    def resolved_up_scaling(self):
        """The uplink ScalingPolicy (None == 'current')."""
        return scaling_lib.get_policy(self.up_scaling)

    @property
    def resolved_aggregator(self) -> str:
        if self.aggregator != "auto":
            return self.aggregator
        if self.server_opt.enabled:
            # legacy knobs keep their exact semantics (comm_mode gates the
            # UQ+ tail); codec knobs gate on the resolved downlink codec
            quantized = (
                self.comm_mode != "none"
                if self.down_codec is None and self.codec_schedule is None
                else self.resolved_down_codec.quantized
            )
            if quantized:
                return "server_opt"
        return "mean"


# ---------------------------------------------------------------------------
# Local update (Algorithm 1's LocalUpdate) — unchanged math, lives here so
# the engine has no import cycle with the fedavg shim.
# ---------------------------------------------------------------------------


def make_local_update(
    loss_fn: LossFn,
    optimizer: Optimizer,
    cfg: FedConfig,
):
    """Build ``LocalUpdate(w_t, Q_det; alpha_t, beta_t, D_k)``.

    Returned fn signature: ``(params0, data, labels, key) -> (params_U, mean_loss)``
    where ``params0`` is the (dequantized) downlink model — the hard master
    reset is implicit in starting from it. Optimizer state is re-initialized
    every round, as is standard for FedAvg local solvers. The optimizer's
    init, update and apply run under the ``fl.opt`` scope.
    """

    def local_update(params0: PyTree, data: Array, labels: Array, key: Array):
        with jax.named_scope("fl.opt"):
            opt_state = optimizer.init(params0)
        n = data.shape[0]

        def step(carry, k):
            params, opt_state, i = carry
            k_batch, k_q = jax.random.split(k)
            idx = jax.random.randint(k_batch, (cfg.batch_size,), 0, n)
            xb, yb = data[idx], labels[idx]
            loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb, cfg.qat, k_q)
            with jax.named_scope("fl.opt"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params, i)
                params = apply_updates(params, updates)
            return (params, opt_state, i + 1), loss

        keys = jax.random.split(key, cfg.local_steps)
        (params, _, _), losses = jax.lax.scan(
            step, (params0, opt_state, jnp.zeros((), jnp.int32)), keys
        )
        return params, jnp.mean(losses)

    return local_update


# ---------------------------------------------------------------------------
# Stage 1: ClientSampler — (nk, key) -> cohort indices (P,)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UniformSampler:
    """Uniform without replacement (the paper's P_t; stragglers simply fall
    out of the cohort — FedAvg's native dropout tolerance)."""

    n_clients: int
    cohort: int

    def __call__(self, nk: Array, key: Array) -> Array:
        return jax.random.permutation(key, self.n_clients)[: self.cohort]


@dataclasses.dataclass(frozen=True)
class WeightedSampler:
    """nk-proportional sampling without replacement via the Gumbel top-k
    trick: argtop-k of ``log nk + Gumbel`` draws exactly a PPSWOR cohort —
    clients holding more data participate more often, matching the
    cross-device production setting where cohort selection is
    traffic-weighted."""

    n_clients: int
    cohort: int

    def __call__(self, nk: Array, key: Array) -> Array:
        g = jax.random.gumbel(key, (self.n_clients,))
        _, idx = jax.lax.top_k(jnp.log(jnp.maximum(nk, 1e-12)) + g, self.cohort)
        return idx


@dataclasses.dataclass(frozen=True)
class FixedCohortSampler:
    """A deterministic cohort every round (cross-silo: the same P silos
    always participate). ``indices=None`` means clients ``0..P-1``."""

    n_clients: int
    cohort: int
    indices: tuple[int, ...] | None = None

    def __post_init__(self):
        # the engine sizes key fan-out / executor / byte accounting from
        # `cohort`; a shorter index list would crash the vmap downstream
        if self.indices is not None and len(self.indices) < self.cohort:
            raise ValueError(
                f"FixedCohortSampler: {len(self.indices)} indices < "
                f"cohort {self.cohort}"
            )

    def __call__(self, nk: Array, key: Array) -> Array:
        if self.indices is not None:
            return jnp.asarray(self.indices, jnp.int32)[: self.cohort]
        return jnp.arange(self.cohort, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Stage 2: Link — per-direction wire format
# ---------------------------------------------------------------------------


def _codec_transit(codec, params: PyTree, spec: wire.WireSpec, key: Array,
                   ref: PyTree | None = None) -> PyTree:
    """One leg through ``codec``: what a receiver of the payload observes
    (encode -> decode). A non-quantized codec (FP32) or a tree with no
    quantized leaves passes through untouched."""
    if not (codec.quantized and spec.q_slots):
        return params
    payload = codec.encode(params, spec, key, ref=ref)
    return codec.decode(payload, spec, ref=ref)


def _sched_switch(schedule: CodecSchedule, r: Array, leg, *operands):
    """Resolve a CodecSchedule inside the jitted round: ``lax.switch`` over
    the phases, each branch the same leg at that phase's codec. ``r`` is
    the round-index operand (``ServerState.round``)."""
    if r is None:
        raise ValueError(
            "a CodecSchedule needs the round-index operand; drive this "
            "link through RoundEngine/FedSim (which thread "
            "ServerState.round), not the stateless shim"
        )
    branches = [
        (lambda *ops, _c=c: leg(_c, *ops)) for c in schedule.codecs
    ]
    return jax.lax.switch(schedule.phase(r), branches, *operands)


@dataclasses.dataclass(frozen=True)
class WireLink:
    """Both legs of the model exchange, each a first-class ``WireCodec``.

    ``down_codec``/``up_codec`` accept a codec object, a registry name
    (``'e4m3'``, ``'fp4'``, ``'delta:e4m3'``, ``'fp32'``, ...) or a
    :class:`~repro.core.codec.CodecSchedule`. The legacy per-direction
    ``(fmt, mode)`` fields are deprecation shims resolving through
    ``codec.codec_for`` — ``mode='rand'`` the paper's unbiased quantizer,
    ``'det'`` the biased Table-2 ablation, ``'none'`` FP32 passthrough —
    and are ignored on a leg whose codec field is set.

    ``down``/``up`` emit the tree a *receiver* of the real payload would
    observe (encode -> decode); byte accounting (:meth:`down_bytes` /
    :meth:`up_bytes`) delegates to each leg's codec, so asymmetric links
    (FP32 down / FP8 up, FP4 up, delta up...) charge each direction at its
    real size. ``ref`` is the round's reference model (the broadcast the
    cohort trained from) — consumed by :class:`DeltaCodec` legs; ``r`` is
    the round-index operand consumed by schedules.

    ``down_scaling``/``up_scaling`` pick each leg's
    :class:`~repro.core.scaling.ScalingPolicy` (instance or spec string;
    None == ``'current'``, the bit-identical no-policy past). A non-current
    policy replaces the trained-alpha clip with policy-derived scales, so
    it requires a plain FP8-family leg codec (no FP32 passthrough, delta,
    or schedule — scaled XOR scheduled); ``'frozen'`` is downlink-only.
    """

    down_fmt: FP8Format = E4M3
    up_fmt: FP8Format = E4M3
    down_mode: str = "rand"
    up_mode: str = "rand"
    down_codec: Any = None
    up_codec: Any = None
    down_scaling: Any = None
    up_scaling: Any = None

    def __post_init__(self):
        down = (codec_lib.get_codec(self.down_codec)
                if self.down_codec is not None
                else codec_lib.codec_for(self.down_fmt, self.down_mode))
        up = (codec_lib.get_codec(self.up_codec)
              if self.up_codec is not None
              else codec_lib.codec_for(self.up_fmt, self.up_mode))
        if isinstance(down, DeltaCodec):
            raise ValueError(
                "DeltaCodec cannot run on the downlink: the receiver "
                "(a client joining the round) holds no reference model. "
                "Use it on the uplink, where the reference is the round's "
                "broadcast."
            )
        if isinstance(down, ErrorFeedbackCodec):
            raise ValueError(
                "ErrorFeedbackCodec cannot run on the downlink: the "
                "receivers are freshly-sampled clients holding no memory "
                "of previous broadcasts, so there is no residual to feed "
                "back. Use it on the uplink, where the engine threads "
                "per-client residual state (ServerState.clients)."
            )
        down_p = scaling_lib.get_policy(self.down_scaling)
        up_p = scaling_lib.get_policy(self.up_scaling)
        for leg, pol, c in (("down", down_p, down), ("up", up_p, up)):
            if not pol.is_current and not isinstance(c, Fp8Codec):
                raise ValueError(
                    f"{leg}_scaling={pol.name!r} needs a plain FP8-family "
                    f"{leg}link codec (Fp8Codec/PackedFpCodec) — got "
                    f"{type(c).__name__}; scaling policies do not compose "
                    "with FP32 passthrough, DeltaCodec, or CodecSchedule"
                )
        if isinstance(up_p, scaling_lib.PerRoundFrozenScaling):
            raise ValueError(
                "up_scaling='frozen' is unsupported: the server has no "
                "prior copy of a client's freshly-trained model, so there "
                "are no already-held scales to reuse. Frozen scaling is a "
                "downlink policy; use 'delayed' on the uplink."
            )
        object.__setattr__(self, "_down_c", down)
        object.__setattr__(self, "_up_c", up)
        object.__setattr__(self, "_down_p", down_p)
        object.__setattr__(self, "_up_p", up_p)

    # resolved codecs (read-only views)
    @property
    def down_c(self):
        return self._down_c

    @property
    def up_c(self):
        return self._up_c

    @property
    def has_schedule(self) -> bool:
        return isinstance(self._down_c, CodecSchedule) or isinstance(
            self._up_c, CodecSchedule
        )

    @property
    def needs_ref(self) -> bool:
        return isinstance(self._up_c, DeltaCodec)

    @property
    def up_is_ef(self) -> bool:
        """True when the uplink is an :class:`ErrorFeedbackCodec` — the
        engine must thread per-client residual state through the round."""
        return isinstance(self._up_c, ErrorFeedbackCodec)

    @property
    def down_dynamic(self) -> bool:
        return bool(getattr(self._down_c, "dynamic", False))

    @property
    def up_dynamic(self) -> bool:
        return bool(getattr(self._up_c, "dynamic", False))

    @property
    def dynamic(self) -> bool:
        """True when any leg's coded size is data-dependent (a RansCodec,
        possibly under EF) — the round builders then charge ``wire_bytes``
        from the traced lane (``payload_nbytes_traced``) while the static
        ``payload_nbytes`` bound keeps sizing buffers and guards."""
        return self.down_dynamic or self.up_dynamic

    # resolved scaling policies (read-only views)
    @property
    def down_p(self):
        return self._down_p

    @property
    def up_p(self):
        return self._up_p

    @property
    def scaled(self) -> bool:
        """True when any leg scales away from ``current`` — only then do
        the round builders thread ``ServerState.scales``."""
        return not (self._down_p.is_current and self._up_p.is_current)

    def scales_init(self, params: PyTree,
                    spec: wire.WireSpec | None = None) -> PyTree:
        """Initial ``ServerState.scales``: a ``(down, up)`` state tuple
        seeded from the model's trained clip alphas (``()`` per stateless
        leg)."""
        if not self.scaled:
            return ()
        if spec is None:
            spec = wire.make_wire_spec(params)
        a0 = scaling_lib.leaf_alphas(params, spec)
        return (self._down_p.init_state(a0), self._up_p.init_state(a0))

    def down_scaled(self, params: PyTree, spec: wire.WireSpec, key: Array,
                    st: PyTree, axis: str | None = None):
        """Scaled server -> cohort broadcast: ``(received_tree, new_st)``.

        Delayed legs encode at the history's effective scales and append
        the per-leaf amax the fused quantize launch emitted (``pmax`` over
        ``axis`` first when the plane is model-sharded, so every shard
        appends the same global row). Frozen legs encode at the trained
        alphas but DROP the alpha columns from the payload — the receiver
        splices the values it already holds back in, bitwise."""
        c, pol = self._down_c, self._down_p
        if not (c.quantized and spec.q_slots):
            return params, st
        if isinstance(pol, scaling_lib.PerRoundFrozenScaling):
            scaling_lib.require_column_alphas(spec, pol)
            alphas = scaling_lib.leaf_alphas(params, spec)
            payload = c.encode_scaled(params, spec, key, alphas,
                                      drop_alphas=True)
            out = c.decode_scaled(payload, spec, alphas=alphas,
                                  dropped=True)
            return out, st
        a_eff = pol.effective(st)
        payload, amax = c.encode_scaled(params, spec, key, a_eff,
                                        with_amax=True)
        out = c.decode_scaled(payload, spec)
        if axis is not None:
            amax = jax.lax.pmax(amax, axis)
        return out, pol.update(st, amax)

    def up_scaled(self, client_params: PyTree, spec: wire.WireSpec,
                  key: Array, cohort: int, st: PyTree):
        """Scaled cohort -> server uplink: ``(msgs, up_amax)``.

        Every client encodes at the SAME effective scales (the server's
        history — both ends can derive them without a fresh reduction);
        ``up_amax`` is the per-client ``(cohort, n_q)`` amax byproduct.
        The caller aggregates it into the history so fault masking can
        drop rejected clients' rows first."""
        c, pol = self._up_c, self._up_p
        if not (c.quantized and spec.q_slots):
            return client_params, jnp.zeros((cohort, 0), jnp.float32)
        a_eff = pol.effective(st)
        up_keys = jax.random.split(key, cohort)
        payloads, amax = jax.vmap(
            lambda p, pk: c.encode_scaled(p, spec, pk, a_eff,
                                          with_amax=True)
        )(client_params, up_keys)
        msgs = jax.vmap(
            lambda pl: c.decode_scaled(pl, spec)
        )(payloads)
        return msgs, amax

    def up_gather_scaled(self, client_params: PyTree, keys: Array,
                         axis: str, n_keep: int, st: PyTree,
                         fold_axes: tuple[str, ...] = ()):
        """Scaled uplink for the sharded executors (inside ``shard_map``):
        same wire as :meth:`up_gather` plus the per-client amax gathered
        alongside the codes — ``(msgs, up_amax)`` with ``up_amax`` of
        shape ``(n_keep, n_q)`` replicated like the decoded stack."""
        from .compression import fp8_wire_allgather_clients

        a_eff = self._up_p.effective(st)
        return fp8_wire_allgather_clients(
            client_params, keys, (axis,), codec=self._up_c, n_keep=n_keep,
            fold_axes=fold_axes, alpha_override=a_eff, collect_amax=True,
        )

    def down(self, params: PyTree, spec: wire.WireSpec, key: Array,
             r: Array | None = None) -> PyTree:
        """Server -> cohort broadcast: ONE fused encode, one decode."""
        c = self._down_c
        if isinstance(c, CodecSchedule):
            return _sched_switch(
                c, r,
                lambda cc, p, k: _codec_transit(cc, p, spec, k),
                params, key,
            )
        return _codec_transit(c, params, spec, key)

    def up(self, client_params: PyTree, spec: wire.WireSpec, key: Array,
           cohort: int, ref: PyTree | None = None,
           r: Array | None = None) -> PyTree:
        """Cohort -> server: per-client independent payloads (vmapped)."""

        def leg(cc, stacked, k):
            if not (cc.quantized and spec.q_slots):
                return stacked
            up_keys = jax.random.split(k, cohort)
            payloads = jax.vmap(
                lambda p, pk: cc.encode(p, spec, pk, ref=ref)
            )(stacked, up_keys)
            return jax.vmap(
                lambda pl: cc.decode(pl, spec, ref=ref)
            )(payloads)

        c = self._up_c
        if isinstance(c, CodecSchedule):
            return _sched_switch(c, r, leg, client_params, key)
        return leg(c, client_params, key)

    def up_gather(self, client_params: PyTree, keys: Array, axis: str,
                  n_keep: int, ref: PyTree | None = None,
                  r: Array | None = None,
                  fold_axes: tuple[str, ...] = ()) -> PyTree:
        """Uplink for the sharded executor (called INSIDE shard_map): this
        device's ``(L, ...)`` client stack encodes with the same per-client
        keys :meth:`up` would use, crosses the wire as a single compressed
        payload buffer in one all-gather, and decodes replicated — the
        global ``(n_keep, ...)`` stack every device then holds is
        bit-identical to what the unsharded :meth:`up` emits for the same
        cohort. On a 2D mesh pass the model axis via ``fold_axes`` so each
        FSDP shard draws decorrelated stochastic-rounding bits; the codes
        all-gather still moves along ``axis`` only (sharded operands stay
        in place)."""
        from .compression import fp8_wire_allgather_clients

        def leg(cc, stacked, k):
            return fp8_wire_allgather_clients(
                stacked, k, (axis,), codec=cc, n_keep=n_keep, ref=ref,
                fold_axes=fold_axes,
            )

        c = self._up_c
        if isinstance(c, CodecSchedule):
            return _sched_switch(c, r, leg, client_params, keys)
        return leg(c, client_params, keys)

    # --- dynamic / error-feedback legs (engine-driven) -------------------

    def down_traced(self, params: PyTree, spec: wire.WireSpec, key: Array):
        """Dynamic downlink: ``(received_tree, traced_bytes)`` of ONE
        model copy — same transit as :meth:`down`, but the payload is
        kept long enough to charge its true coded size."""
        c = self._down_c
        if not (c.quantized and spec.q_slots):
            return params, jnp.int32(
                codec_lib.leg_nbytes(c, spec, policy=self._down_p)
            )
        payload = c.encode(params, spec, key)
        return c.decode(payload, spec), c.payload_nbytes_traced(payload,
                                                               spec)

    def up_traced(self, client_params: PyTree, spec: wire.WireSpec,
                  key: Array, cohort: int, ref: PyTree | None = None):
        """Dynamic uplink: ``(msgs, per_client_bytes)`` — the decoded
        cohort stack plus each client's true coded size, (cohort,) i32."""
        c = self._up_c
        if not (c.quantized and spec.q_slots):
            return client_params, jnp.full(
                (cohort,), codec_lib.leg_nbytes(c, spec,
                                                policy=self._up_p),
                jnp.int32,
            )
        up_keys = jax.random.split(key, cohort)
        payloads = jax.vmap(
            lambda p, pk: c.encode(p, spec, pk, ref=ref)
        )(client_params, up_keys)
        msgs = jax.vmap(lambda pl: c.decode(pl, spec, ref=ref))(payloads)
        per = jax.vmap(
            lambda pl: c.payload_nbytes_traced(pl, spec)
        )(payloads)
        return msgs, per

    def up_ef(self, client_params: PyTree, spec: wire.WireSpec,
              key: Array, cohort: int, e_sel: Array):
        """Error-feedback uplink: ``(msgs, new_e, per_client_bytes)``.

        ``e_sel`` is the cohort's gathered (cohort, spec.total) residual
        rows; ``new_e`` is the updated rows the engine scatters back
        (fault masking — dropped clients keep old rows — is the
        engine's job, since only it sees the draw)."""
        c = self._up_c
        if not (c.quantized and spec.q_slots):
            return client_params, e_sel, jnp.full(
                (cohort,), codec_lib.leg_nbytes(c, spec,
                                                policy=self._up_p),
                jnp.int32,
            )
        up_keys = jax.random.split(key, cohort)
        msgs, new_e, payloads = c.up_transit(client_params, spec,
                                             up_keys, e_sel)
        if getattr(c, "dynamic", False):
            inner = c.inner
            per = jax.vmap(
                lambda pl: inner.payload_nbytes_traced(pl, spec)
            )(payloads)
        else:
            per = jnp.full(
                (cohort,), codec_lib.leg_nbytes(c, spec,
                                                policy=self._up_p),
                jnp.int32,
            )
        return msgs, new_e, per

    def up_gather_ef(self, comp_params: PyTree, keys: Array, axis: str,
                     n_keep: int):
        """Error-feedback uplink for the sharded executor (inside
        ``shard_map``): the caller has already COMPENSATED this shard's
        client stack (``ef.add_resid``); the inner grid codec crosses
        the wire exactly like :meth:`up_gather`."""
        from .compression import fp8_wire_allgather_clients

        return fp8_wire_allgather_clients(
            comp_params, keys, (axis,), codec=self._up_c.inner,
            n_keep=n_keep,
        )

    def down_bytes(self, spec: wire.WireSpec, r: int = 0) -> int:
        """Exact bytes of one downlink model copy (static, per receiver).
        Policy-aware: a frozen leg drops its alpha columns, a delayed leg
        ships one effective-scale scalar per quantized leaf."""
        return codec_lib.leg_nbytes(self._down_c, spec, r,
                                    policy=self._down_p)

    def up_bytes(self, spec: wire.WireSpec, r: int = 0) -> int:
        """Exact bytes of one uplink model copy (static, per client)."""
        return codec_lib.leg_nbytes(self._up_c, spec, r, policy=self._up_p)

    def leg_bytes_traced(self, spec: wire.WireSpec,
                         r: Array | None) -> tuple[Array, Array]:
        """``(down, up)`` bytes of ONE model copy per leg as traced int32:
        a scheduled leg resolves its phase from the round-index operand
        (static per-phase table, one ``take``); a plain leg is a trace-time
        constant. Exact — the fault path multiplies these by traced
        participation counts."""

        def leg_traced(c, p):
            if isinstance(c, CodecSchedule):
                # scaled XOR scheduled (validated): the policy here is
                # always current, zero payload delta
                table = jnp.asarray(
                    [codec_lib.leg_nbytes(cc, spec) for cc in c.codecs],
                    jnp.int32,
                )
                return jnp.take(table, c.phase(r))
            return jnp.asarray(
                codec_lib.leg_nbytes(c, spec, policy=p), jnp.int32
            )

        return (leg_traced(self._down_c, self._down_p),
                leg_traced(self._up_c, self._up_p))

    def traced_round_bytes(self, spec: wire.WireSpec, cohort: int,
                           r: Array) -> Array:
        """Per-round wire bytes under a CodecSchedule, resolved from the
        round-index operand: static per-phase tables, one ``take`` per
        scheduled leg — still exact, still int32."""
        down_b, up_b = self.leg_bytes_traced(spec, r)
        return cohort * (down_b + up_b)


def fp32_link() -> WireLink:
    """FP32 passthrough on both legs (the FedAvg baseline)."""
    return WireLink(down_mode="none", up_mode="none")


def hybrid_link(mode: str = "rand") -> WireLink:
    """The E4M3-down / E5M2-up hybrid (NeMo's ``fp8_hybrid`` recipe shape:
    wider dynamic range on the gradient-like leg)."""
    return WireLink(down_fmt=E4M3, up_fmt=E5M2,
                    down_mode=mode, up_mode=mode)


# ---------------------------------------------------------------------------
# Stage 3: ClientExecutor — run LocalUpdate over the cohort
# ---------------------------------------------------------------------------


def _run_width_two(run, data: Array, labels: Array, keys: Array):
    """Run a width-1 client batch at width 2: duplicate the client, run,
    slice the copy back off. XLA collapses a batch-1 dot to an unbatched
    GEMM whose accumulation order differs from the batched lowering, so a
    degenerate schedule (``chunk=1``, or more devices than clients) would
    silently break the executors' bitwise schedule-invariance contract;
    widths >= 2 lower to the same per-slice GEMM. The ONE owner of this
    workaround — every executor path routes its width-1 case here."""
    dup = lambda x: jnp.concatenate([x, x], axis=0)
    out = run(dup(data), dup(labels), dup(keys))
    return jax.tree.map(lambda x: x[:1], out)


def _client_vmap(local_update, down: PyTree, data: Array, labels: Array,
                 keys: Array, *, min_width_two: bool = False):
    """vmap ``local_update`` over the client axis.

    ``min_width_two`` routes a width-1 batch through :func:`_run_width_two`.
    Callers set it only when the FULL cohort is wider than 1 — a true
    single-client cohort must keep the width-1 lowering to match
    :class:`VmapExecutor` on the same cohort.
    """
    v = jax.vmap(local_update, in_axes=(None, 0, 0, 0))
    run = lambda d, l, k: v(down, d, l, k)
    if min_width_two and data.shape[0] == 1:
        return _run_width_two(run, data, labels, keys)
    return run(data, labels, keys)


class VmapExecutor:
    """Full-cohort vmap (the original path): every client trains
    simultaneously, replicating per-client optimizer state and activations
    P times. Fastest when the cohort fits in memory."""

    def __call__(self, local_update, down: PyTree, data: Array,
                 labels: Array, keys: Array):
        return _client_vmap(local_update, down, data, labels, keys)


@dataclasses.dataclass(frozen=True)
class ChunkedExecutor:
    """scan-over-chunks-of-vmap: peak live memory is O(chunk), not O(P).

    The cohort is split into ``ceil(P / chunk)`` chunks; a ``lax.scan``
    trains one chunk at a time, so per-client optimizer state, activations
    and local-step scan residuals exist for only ``chunk`` clients at once.
    The stacked result is bit-identical to :class:`VmapExecutor` under the
    same key: chunking changes the *schedule*, never a client's
    ``(params, data, key)`` inputs, and clients never mix. A ragged tail is
    padded by wrapping the first cohort rows; padded outputs are sliced off.
    """

    chunk: int

    def __call__(self, local_update, down: PyTree, data: Array,
                 labels: Array, keys: Array):
        P = data.shape[0]
        C = min(self.chunk, P)
        if C <= 0:
            raise ValueError(f"chunk must be positive, got {self.chunk}")
        n_chunks = -(-P // C)
        pad = n_chunks * C - P

        def chunked(x):
            if pad:
                x = jnp.concatenate([x, x[:pad]], axis=0)
            return x.reshape((n_chunks, C) + x.shape[1:])

        def body(_, args):
            d, l, k = args
            out = _client_vmap(local_update, down, d, l, k,
                               min_width_two=P > 1)
            return None, out

        _, (stacked, losses) = jax.lax.scan(
            body, None, (chunked(data), chunked(labels), chunked(keys))
        )
        unstack = lambda x: x.reshape((n_chunks * C,) + x.shape[2:])[:P]
        return jax.tree.map(unstack, stacked), unstack(losses)


@dataclasses.dataclass(frozen=True)
class ShardedExecutor:
    """Shard the cohort axis over a named mesh axis with ``shard_map``.

    Each of the D devices on ``mesh.shape[axis]`` trains ``ceil(P / D)``
    clients — through the *inner* executor: a full local vmap, or a
    :class:`ChunkedExecutor` scan when ``chunk`` is set, so per-device
    live training memory is O(chunk) regardless of both P and D. A ragged
    cohort (P not a multiple of D) is padded by wrapping the first cohort
    rows, exactly like the chunked schedule pads its tail; padded outputs
    are sliced off after the gather, so the result is bit-identical to
    :class:`VmapExecutor` under the same key.

    Called standalone (the plain executor protocol), the per-shard outputs
    are all-gathered in FP32 — that is the benchmarking/measurement path.
    Inside a :class:`RoundEngine` round the engine instead fuses the uplink
    INTO the shard (``WireLink.up_gather``): each device's clients encode
    their wire payloads locally and the only cohort-sized collective moves
    uint8 codes, one contiguous buffer per device — the
    ``compression.fp8_wire_allreduce_mean`` wire discipline applied to the
    simulated cohort.
    """

    mesh: Any                     # jax.sharding.Mesh with `axis` in axis_names
    axis: str = "clients"
    chunk: int | None = None      # inner ChunkedExecutor; None = local vmap
    # 2D federated mesh: FSDP-shard each client's training step over this
    # mesh axis (fed_param_specs rules). The RoundEngine routes a set
    # model_axis to the fed2d round build; standalone __call__ stays 1D.
    model_axis: str | None = None

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh has axes {self.mesh.axis_names}, no {self.axis!r}"
            )
        if self.model_axis is not None:
            if self.model_axis == self.axis:
                raise ValueError(
                    f"model_axis and client axis are both {self.axis!r} — "
                    "a 2D executor needs two distinct mesh axes"
                )
            if self.model_axis not in self.mesh.axis_names:
                raise ValueError(
                    f"mesh has axes {self.mesh.axis_names}, no model axis "
                    f"{self.model_axis!r}"
                )
            if self.chunk is not None:
                raise ValueError(
                    "chunk-scan cohort execution does not compose with a "
                    "model_axis (GSPMD-sharded cohort); drop one"
                )

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def _inner(self):
        return ChunkedExecutor(self.chunk) if self.chunk else VmapExecutor()

    def pad_to_shards(self, cohort: int) -> tuple[int, int]:
        """(clients per shard, padded cohort) for a cohort of P clients."""
        local = -(-cohort // self.n_shards)
        return local, local * self.n_shards

    def run_shard(self, local_update, down: PyTree, d: Array, l: Array,
                  k: Array, cohort: int):
        """The inner executor over ONE shard's clients. A single-client
        shard of a wider cohort runs through :func:`_run_width_two` so the
        vmap keeps the batched-GEMM lowering — more devices than clients
        must stay bitwise equal to the width->=2 schedules."""
        inner = self._inner()
        run = lambda d_, l_, k_: inner(local_update, down, d_, l_, k_)
        if d.shape[0] == 1 and cohort > 1:
            return _run_width_two(run, d, l, k)
        return run(d, l, k)

    def __call__(self, local_update, down: PyTree, data: Array,
                 labels: Array, keys: Array):
        from jax import shard_map
        from jax.sharding import PartitionSpec

        P = data.shape[0]
        _, padded = self.pad_to_shards(P)
        pad_idx = jnp.arange(padded, dtype=jnp.int32) % P
        axis = self.axis

        def shard_fn(dn, d, l, k):
            out = self.run_shard(local_update, dn, d, l, k, P)

            def gather(x):
                # (L, ...) per shard -> (D, L, ...) -> cohort order -> [:P]
                g = jax.lax.all_gather(x, axis)
                return g.reshape((-1,) + x.shape[1:])[:P]

            return jax.tree.map(gather, out)

        sh = PartitionSpec(axis)
        return shard_map(
            shard_fn, mesh=self.mesh,
            in_specs=(PartitionSpec(), sh, sh, sh),
            out_specs=(PartitionSpec(), PartitionSpec()),
            check_vma=False,
        )(down, data[pad_idx], labels[pad_idx], keys[pad_idx])


# ---------------------------------------------------------------------------
# Stage 4: Aggregator — the server tail, optionally stateful
# ---------------------------------------------------------------------------
#
# Protocol: ``init(params) -> opt_state`` and
# ``__call__(server_params, stacked_msgs, nk, key, opt_state)
#   -> (new_params, new_opt_state)``.
# Stateless aggregators use ``()`` so ServerState stays minimal.


@dataclasses.dataclass(frozen=True)
class MeanAggregator:
    """Plain federated average with weights n_k / m_t (Algorithm 1's tail)."""

    def init(self, params: PyTree) -> PyTree:
        return ()

    def __call__(self, server_params, stacked_msgs, nk, key, opt_state):
        return weighted_mean(stacked_msgs, nk), ()


@dataclasses.dataclass(frozen=True)
class ServerOptAggregator:
    """UQ+ ``server_optimize`` (paper Eqs. 4-5): minimize the quantized-domain
    MSE to the client models by alternating STE-SGD on w and per-tensor grid
    search on alpha. Stateless — the alternation restarts each round."""

    cfg: ServerOptConfig

    def init(self, params: PyTree) -> PyTree:
        return ()

    def __call__(self, server_params, stacked_msgs, nk, key, opt_state):
        return server_optimize(stacked_msgs, nk, key, self.cfg), ()


def _pseudo_gradient(server_params, stacked_msgs, nk):
    """FedOpt's Delta_t: server minus the weighted client average — the
    direction a *server optimizer* descends (Reddi et al.)."""
    avg = weighted_mean(stacked_msgs, nk)
    return jax.tree.map(lambda s, a: s.astype(jnp.float32) - a.astype(jnp.float32),
                        server_params, avg)


@dataclasses.dataclass(frozen=True)
class FedAvgM:
    """Server momentum (FedAvgM): ``v <- beta v + Delta; w <- w - lr v``.

    ``lr=1, beta=0`` reduces exactly to the weighted mean. The momentum
    buffer is ServerState.opt and threads through rounds — the first
    aggregator here that is genuinely stateful.
    """

    lr: float = 1.0
    momentum: float = 0.9

    def init(self, params: PyTree) -> PyTree:
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

    def __call__(self, server_params, stacked_msgs, nk, key, opt_state):
        delta = _pseudo_gradient(server_params, stacked_msgs, nk)
        v = jax.tree.map(
            lambda m, d: self.momentum * m + d, opt_state, delta
        )
        new = jax.tree.map(
            lambda p, m: (p.astype(jnp.float32) - self.lr * m).astype(p.dtype),
            server_params, v,
        )
        return new, v


@dataclasses.dataclass(frozen=True)
class FedAdam:
    """FedAdam (Reddi et al., *Adaptive Federated Optimization*): Adam on the
    pseudo-gradient, with ``tau`` (``eps``) at the paper-recommended 1e-3
    scale. Both moment buffers live in ServerState.opt."""

    lr: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.99
    eps: float = 1e-3

    def init(self, params: PyTree) -> PyTree:
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        return {"m": zeros(), "v": zeros()}

    def __call__(self, server_params, stacked_msgs, nk, key, opt_state):
        delta = _pseudo_gradient(server_params, stacked_msgs, nk)
        m = jax.tree.map(
            lambda mi, d: self.beta1 * mi + (1 - self.beta1) * d,
            opt_state["m"], delta,
        )
        v = jax.tree.map(
            lambda vi, d: self.beta2 * vi + (1 - self.beta2) * d * d,
            opt_state["v"], delta,
        )
        new = jax.tree.map(
            lambda p, mi, vi: (
                p.astype(jnp.float32) - self.lr * mi / (jnp.sqrt(vi) + self.eps)
            ).astype(p.dtype),
            server_params, m, v,
        )
        return new, {"m": m, "v": v}


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


_SAMPLERS = {
    "uniform": UniformSampler,
    "weighted": WeightedSampler,
    "fixed": FixedCohortSampler,
}

# every name FedConfig.aggregator accepts ('auto' resolves per-config in
# FedConfig.resolved_aggregator; the rest map through make_aggregator)
_AGGREGATOR_NAMES = ("auto", "mean", "server_opt", "fedavgm", "fedadam")


def _mask_rejected(stacked: PyTree, accepted: Array, fallback: PyTree):
    """Replace rejected clients' rows with the round's broadcast model.

    A zero aggregation weight alone would exclude them from every weighted
    mean, but not from NaN propagation: an undelivered payload is
    *arbitrary* memory as far as the server is concerned, and ``0 * NaN``
    is NaN. Substituting the broadcast (a tree every aggregator tolerates)
    plus the zero weight makes rejection exact."""

    def leaf(m, f):
        c = accepted.reshape((accepted.shape[0],) + (1,) * (m.ndim - 1))
        return jnp.where(c, m, f)

    return jax.tree.map(leaf, stacked, fallback)


def _exact_round_bytes(link: WireLink, spec: wire.WireSpec, cohort: int,
                       r: int = 0) -> int:
    """P x (down leg + up leg), each leg at its real payload size (the
    codec's own accounting) — static at trace time. int32 keeps the count
    EXACT (f32 rounds integers above 2^24 ~ 16.7 MB, well inside the
    simulator's round sizes)."""
    total = cohort * (link.down_bytes(spec, r) + link.up_bytes(spec, r))
    if total >= 2 ** 31:
        raise ValueError(
            f"round moves {total} bytes — exceeds the int32 "
            "wire_bytes metric; this simulator targets sub-GiB rounds"
        )
    return total


def _schedule_probe_rounds(link: WireLink) -> list[int]:
    """One representative round index per schedule phase (both legs),
    for static byte-accounting guards."""
    rounds = {0}
    for c in (link.down_c, link.up_c):
        if isinstance(c, CodecSchedule):
            rounds.update(c.boundaries)
    return sorted(rounds)


def make_aggregator(kind: str, *, lr: float | None = None,
                    momentum: float | None = None,
                    beta2: float | None = None, eps: float | None = None,
                    server_opt_cfg: ServerOptConfig | None = None):
    """Name -> Aggregator — the ONE factory every entry point (FedConfig,
    ``launch/train.py --server-opt``, examples) maps CLI/config names
    through. ``None`` keyword = that aggregator's own class default
    (FedAvgM lr 1.0, FedAdam lr 0.1)."""
    if kind == "mean":
        return MeanAggregator()
    if kind == "server_opt":
        return ServerOptAggregator(
            server_opt_cfg if server_opt_cfg is not None else ServerOptConfig()
        )
    kw = {}
    if lr is not None:
        kw["lr"] = lr
    if kind == "fedavgm":
        if momentum is not None:
            kw["momentum"] = momentum
        return FedAvgM(**kw)
    if kind == "fedadam":
        if momentum is not None:
            kw["beta1"] = momentum
        if beta2 is not None:
            kw["beta2"] = beta2
        if eps is not None:
            kw["eps"] = eps
        return FedAdam(**kw)
    raise ValueError(f"unknown aggregator {kind!r}")


def _stages_from_config(cfg: FedConfig):
    """Map FedConfig knobs to default stage objects."""
    P = cfg.clients_per_round
    sampler = _SAMPLERS[cfg.sampler](cfg.n_clients, P)
    link = WireLink(down_codec=cfg.resolved_down_codec,
                    up_codec=cfg.resolved_up_codec,
                    down_scaling=cfg.resolved_down_scaling,
                    up_scaling=cfg.resolved_up_scaling)
    if cfg.mesh is not None:
        executor = ShardedExecutor(cfg.mesh, cfg.client_axis, chunk=cfg.chunk,
                                   model_axis=cfg.model_axis)
    elif cfg.chunk:
        executor = ChunkedExecutor(cfg.chunk)
    else:
        executor = VmapExecutor()
    aggregator = make_aggregator(
        cfg.resolved_aggregator, lr=cfg.server_lr,
        momentum=cfg.server_momentum, beta2=cfg.server_beta2,
        eps=cfg.server_eps, server_opt_cfg=cfg.server_opt,
    )
    return sampler, link, executor, aggregator


class RoundEngine:
    """One communication round, composed from the four stages.

    Stages default from ``cfg`` (matching the legacy round bit-for-bit on
    legacy configs) and can each be overridden with an explicit object.
    ``round_fn`` is jit-compatible with the signature
    ``(server_state, data, labels, nk, key) -> (server_state, metrics)``.
    """

    def __init__(
        self,
        loss_fn: LossFn,
        optimizer: Optimizer,
        cfg: FedConfig,
        *,
        sampler=None,
        link=None,
        executor=None,
        aggregator=None,
        faults=None,
    ):
        self.cfg = cfg
        d_sampler, d_link, d_executor, d_aggregator = _stages_from_config(cfg)
        self.sampler = sampler if sampler is not None else d_sampler
        self.link = link if link is not None else d_link
        self.executor = executor if executor is not None else d_executor
        self.aggregator = aggregator if aggregator is not None else d_aggregator
        # the cohort size follows the SAMPLER (an override may select a
        # different cohort than cfg.participation implies); key fan-out,
        # the executor, and byte accounting must all agree with it
        self.cohort = getattr(self.sampler, "cohort", cfg.clients_per_round)
        # the fault stage: a statically fault-free model (None or
        # FaultModel.none()) resolves to None and the builders emit the
        # LEGACY round — same trace, hence bitwise identical, not merely
        # numerically close with all-ones masks
        fm = faults if faults is not None else cfg.faults
        self.faults = None if fm is None or fm.is_none else fm
        self.quorum = quorum_count(cfg.min_quorum, self.cohort)
        self.quorum_policy = cfg.quorum_policy
        # a CodecSchedule resolves against the round-index operand in
        # ServerState.round; only scheduled links thread the counter
        self.scheduled = bool(getattr(self.link, "has_schedule", False))
        # likewise, only links with a non-current ScalingPolicy thread
        # scaling state — 'current' rounds keep the legacy trace verbatim
        self.scaled = bool(getattr(self.link, "scaled", False))
        # an ErrorFeedbackCodec uplink threads per-client residual memory
        # (ServerState.clients); a dynamic leg (RansCodec) switches the
        # wire_bytes metric to the traced lane. Both gates are static, so
        # non-EF / non-dynamic links keep the legacy trace verbatim.
        self.ef_up = bool(getattr(self.link, "up_is_ef", False))
        self.dynamic = bool(getattr(self.link, "dynamic", False))
        # residual rows are indexed by GLOBAL client id — follow the
        # sampler's pool, like the cohort follows the sampler
        self.pool = getattr(self.sampler, "n_clients", cfg.n_clients)
        if isinstance(self.executor, ShardedExecutor):
            if self.dynamic:
                raise ValueError(
                    "RansCodec legs do not compose with ShardedExecutor: "
                    "the fused u8 uplink all-gather moves fixed-size code "
                    "buffers and cannot carry the per-lane 'rans' state "
                    "entry. Use VmapExecutor/ChunkedExecutor for "
                    "entropy-coded links, or drop the rans: wrapper on "
                    "the sharded run."
                )
            if self.ef_up and self.executor.model_axis is not None:
                raise ValueError(
                    "ErrorFeedbackCodec does not compose with a 2D "
                    "(clients x fsdp) mesh: the residual memory is laid "
                    "out over the GLOBAL wire spec while the fed2d round "
                    "encodes per-device local planes. Use the 1D sharded "
                    "round (model_axis=None) or an unsharded executor."
                )
        self._local_update = make_local_update(loss_fn, optimizer, cfg)
        self.round_fn = self._build_round()

    def init(self, params: PyTree) -> ServerState:
        return ServerState(
            params=params,
            opt=self.aggregator.init(params),
            round=jnp.zeros((), jnp.int32) if self.scheduled else (),
            scales=self.link.scales_init(params) if self.scaled else (),
            clients=(
                ef_lib.init_client_state(self.pool,
                                         wire.make_wire_spec(params))
                if self.ef_up else ()
            ),
        )

    def stateless(self) -> bool:
        """True when the aggregator threads no state (opt is empty)."""
        return not jax.tree_util.tree_leaves(
            self.aggregator.init(jnp.zeros(()))
        )

    def round_bytes(self, params: PyTree = None, r: int = 0, *,
                    spec: wire.WireSpec | None = None) -> int:
        """Static per-round wire bytes: P x (down leg + up leg), each leg at
        its real payload size (codec accounting). Under a CodecSchedule the
        count is per-round — pass ``r`` for the round you are costing.
        Callers costing many rounds pass a prebuilt ``spec`` so the wire
        layout is derived once, not per round."""
        if spec is None:
            spec = wire.make_wire_spec(params)
        return _exact_round_bytes(self.link, spec, self.cohort, r)

    def partial_round_bytes(self, n_transmitted: int, params: PyTree = None,
                            r: int = 0, *,
                            spec: wire.WireSpec | None = None) -> int:
        """Static wire bytes of a PARTIAL round: all P sampled clients
        receive the broadcast (they were cut off after download), but only
        ``n_transmitted`` deliver an uplink payload — dropped/timed-out
        clients charge 0 uplink bytes, detected-corrupt clients full bytes
        (they DID transmit). Equals the traced ``wire_bytes`` metric of a
        fault round with the same transmit count."""
        if not 0 <= n_transmitted <= self.cohort:
            raise ValueError(
                f"n_transmitted must be in [0, cohort={self.cohort}], "
                f"got {n_transmitted}"
            )
        if spec is None:
            spec = wire.make_wire_spec(params)
        return (self.cohort * self.link.down_bytes(spec, r)
                + n_transmitted * self.link.up_bytes(spec, r))

    def _build_round(self):
        if isinstance(self.executor, ShardedExecutor):
            if self.executor.model_axis is not None:
                return self._build_fed2d_round()
            return self._build_sharded_round()
        return self._build_local_round()

    def _build_local_round(self):
        cfg = self.cfg
        P = self.cohort
        sampler, link, executor, aggregator = (
            self.sampler, self.link, self.executor, self.aggregator
        )
        local_update = self._local_update
        scheduled = self.scheduled
        # per-leg static scaling gates: a 'current' leg takes the ORIGINAL
        # branch below verbatim, so its trace (and bitwise contract) is
        # exactly the pre-policy round's
        scaled = self.scaled
        down_scaled_leg = scaled and not link.down_p.is_current
        up_scaled_leg = scaled and not link.up_p.is_current
        # static EF / dynamic gates: non-EF, non-dynamic links take every
        # ORIGINAL branch below verbatim (legacy trace, bitwise contract)
        ef_up = self.ef_up
        down_dyn = bool(getattr(link, "down_dynamic", False))
        up_dyn = bool(getattr(link, "up_dynamic", False))
        dyn = down_dyn or up_dyn
        faults: FaultModel | None = self.faults
        lat_table = (faults.latencies(cfg.n_clients)
                     if faults is not None else None)
        quorum, policy = self.quorum, self.quorum_policy

        def round_fn(state: ServerState, data: Array, labels: Array,
                     nk: Array, key: Array):
            server_params = state.params
            # the round-index operand: a CodecSchedule resolves its phase
            # from it in-jit (None on unscheduled links — no counter leaf)
            r = state.round if scheduled else None
            st_down, st_up = state.scales if scaled else ((), ())
            # key-splitting order matches the legacy round exactly, so the
            # fedavg shim (and any same-key replay) is bit-identical
            with jax.named_scope("fl.sample"):
                k_sel, k_down, k_up, k_loc, k_srv = jax.random.split(key, 5)

            spec = wire.make_wire_spec(server_params)

            # --- stage 1: cohort selection -------------------------------
            with jax.named_scope("fl.sample"):
                idx = sampler(nk, k_sel)
                nk_sel = nk[idx]

            # --- stage 2a: downlink --------------------------------------
            with jax.named_scope("fl.down"):
                if down_scaled_leg:
                    down, st_down = link.down_scaled(server_params, spec,
                                                     k_down, st_down)
                elif down_dyn:
                    down, down_tb = link.down_traced(server_params, spec,
                                                     k_down)
                else:
                    down = link.down(server_params, spec, k_down, r=r)

            # --- stage 3: local QAT training over the cohort -------------
            with jax.named_scope("fl.train"):
                loc_keys = jax.random.split(k_loc, P)
                client_params, losses = executor(
                    local_update, down, data[idx], labels[idx], loc_keys
                )
                # pin the stage boundary: without the barrier XLA fuses
                # the training tail into the uplink encode and the fused
                # lowering (and hence the last-ULP accumulation order)
                # would depend on the CONSUMER — the executor contract is
                # that every schedule computes the same client params, so
                # materialize them here
                client_params, losses = jax.lax.optimization_barrier(
                    (client_params, losses)
                )

            # --- stage 2b: uplink, with what strikes it (faults) and the
            # client- and server-side state it updates (EF residuals,
            # delayed-scaling history) --------------------------------------
            with jax.named_scope("fl.up"):
                # `down` is the round's reference model: every client started
                # local training from it, so a DeltaCodec uplink quantizes the
                # residual against a tree both ends hold
                if up_scaled_leg:
                    msgs, up_amax = link.up_scaled(client_params, spec, k_up,
                                                   P, st_up)
                elif ef_up:
                    # gather the cohort's residual rows, compensate-encode-
                    # update through the EF codec, scatter back below (after
                    # the fault draw decides who actually transmitted)
                    e_sel = state.clients.resid[idx]
                    msgs, new_e, up_tb = link.up_ef(client_params, spec,
                                                    k_up, P, e_sel)
                elif up_dyn:
                    msgs, up_tb = link.up_traced(client_params, spec, k_up,
                                                 P, ref=down)
                else:
                    msgs = link.up(client_params, spec, k_up, P, ref=down, r=r)

                # --- fault stage (statically elided when fault-free, so the
                # legacy trace — and its bitwise contract — is untouched).
                # Logically the faults strike between executor and uplink: a
                # non-transmitting client's payload never reaches the server,
                # so its row is replaced by the broadcast and its nk zeroed —
                # survivors are renormalized by sum(nk_eff) inside every
                # aggregator's weighted mean.
                if faults is not None:
                    fd = faults.draw(key, idx, lat_table)
                    if faults.flips_values:
                        msgs = faults.corrupt_tree(msgs, fd.corrupted, key)
                    msgs = _mask_rejected(msgs, fd.accepted, down)
                    n_alive = jnp.sum(fd.accepted.astype(jnp.int32))
                    n_tx = jnp.sum(fd.transmitted.astype(jnp.int32))
                    nk_agg = nk_sel * fd.accepted.astype(nk_sel.dtype)
                    # an all-dead round is always discarded below; the ones
                    # only keep the dead trace's nk-normalization finite so
                    # the discarded result is garbage, never NaN
                    nk_agg = jnp.where(n_alive > 0, nk_agg,
                                       jnp.ones_like(nk_agg))
                else:
                    nk_agg = nk_sel

                # --- residual commit (EF): client-side memory. Every client
                # that TRANSMITTED updates its row — including corrupted ones
                # (the client cannot see the server's checksum reject);
                # dropped/timed-out clients never encoded, so they keep their
                # old rows. A quorum-skipped round still commits (the clients
                # did compress) — see the core.ef docstring.
                if ef_up:
                    if faults is not None:
                        new_e = jnp.where(fd.transmitted[:, None], new_e,
                                          e_sel)
                    new_clients = state.clients._replace(
                        resid=state.clients.resid.at[idx].set(new_e)
                    )
                else:
                    new_clients = state.clients

                # --- delayed-uplink history append ---------------------------
                # the server's next-round scales come from what it RECEIVED:
                # rejected clients' amax rows are masked out first (amax >= 0,
                # so a zeroed row never wins the max); an all-dead round
                # appends the running history max — finite, and discarded by
                # the quorum revert below anyway
                if up_scaled_leg:
                    if faults is not None:
                        acc = fd.accepted.astype(jnp.float32)[:, None]
                        row = jnp.max(up_amax * acc, axis=0)
                        row = jnp.where(n_alive > 0, row,
                                        jnp.max(st_up, axis=0))
                    else:
                        row = jnp.max(up_amax, axis=0)
                    st_up = link.up_p.update(st_up, row)
            new_scales = (st_down, st_up) if scaled else ()

            # --- stage 4: server aggregation -----------------------------
            with jax.named_scope("fl.aggregate"):
                new_params, new_opt = aggregator(
                    server_params, msgs, nk_agg, k_srv, state.opt
                )

                if faults is not None:
                    # quorum policy: 'skip' needs `quorum` survivors for
                    # the round to count, 'degrade' proceeds with any
                    # survivor at all. A discarded round leaves params AND
                    # aggregator state (momentum/moments) untouched.
                    ok = n_alive >= (quorum if policy == "skip" else 1)
                    keep = lambda new, old: jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), new, old
                    )
                    new_params = keep(new_params, server_params)
                    new_opt = keep(new_opt, state.opt)
                    if scaled:
                        # a discarded round must not advance scaling
                        # history
                        new_scales = keep(new_scales, state.scales)

            if faults is not None:
                # static sub-GiB guard per phase (at the BOUND for dynamic
                # legs), then the traced count: P downlink copies + only
                # the TRANSMITTED uplink payloads, dynamic legs charged at
                # their true coded size (bound >= traced by construction)
                for pr in (_schedule_probe_rounds(link)
                           if scheduled else [0]):
                    _exact_round_bytes(link, spec, P, pr)
                down_b, up_b = link.leg_bytes_traced(spec, r)
                if down_dyn:
                    down_b = down_tb
                if ef_up or up_dyn:
                    up_total = jnp.sum(
                        up_tb * fd.transmitted.astype(jnp.int32)
                    )
                else:
                    up_total = n_tx * up_b
                wire_b = P * down_b + up_total
            elif dyn:
                # static sub-GiB guard at the bound, then the true coded
                # bytes from the traced lane
                _exact_round_bytes(link, spec, P)
                down_b, up_b = link.leg_bytes_traced(spec, r)
                if down_dyn:
                    down_b = down_tb
                up_total = (jnp.sum(up_tb) if (ef_up or up_dyn)
                            else P * up_b)
                wire_b = P * down_b + up_total
            elif scheduled:
                # per-phase static sub-GiB guard, then the traced per-round
                # count resolved from the round-index operand
                for pr in _schedule_probe_rounds(link):
                    _exact_round_bytes(link, spec, P, pr)
                wire_b = link.traced_round_bytes(spec, P, r)
            else:
                wire_b = jnp.asarray(
                    _exact_round_bytes(link, spec, P), jnp.int32
                )
            metrics = {
                "local_loss": jnp.mean(losses),
                # exact bytes moved this round: P uplink payloads + P
                # downlink copies of the broadcast (Figure 1 accounting),
                # each leg charged at its own payload size
                "wire_bytes": wire_b,
            }
            if faults is not None:
                metrics.update(
                    n_alive=n_alive,
                    n_transmitted=n_tx,
                    quorum_met=(n_alive >= quorum).astype(jnp.int32),
                    round_ok=ok.astype(jnp.int32),
                    round_time=faults.round_time(fd),
                )
            return ServerState(new_params, new_opt,
                               (r + 1) if scheduled else (),
                               new_scales, new_clients), metrics

        return round_fn

    def _build_sharded_round(self):
        """The :class:`ShardedExecutor` round: executor + uplink fused into
        one ``shard_map`` so the cohort-sized collective moves uint8.

        Same key-split order and per-client ``(params, data, key)`` triples
        as the local round — the only changes are WHERE each client trains
        (device ``i * D // P_pad``) and HOW its payload reaches the server
        (one u8 all-gather instead of a local vmap), so the result is
        bit-identical to :class:`VmapExecutor` under the same key. The
        downlink broadcast and the aggregator tail run replicated outside
        the shard: every device holds the same server params and, after the
        gather, the same cohort stack, so those stages are device-count
        invariant by construction.
        """
        from jax import shard_map
        from jax.sharding import PartitionSpec

        P = self.cohort
        ex: ShardedExecutor = self.executor
        mesh, axis = ex.mesh, ex.axis
        local, padded = ex.pad_to_shards(P)
        sampler, link, aggregator = self.sampler, self.link, self.aggregator
        local_update = self._local_update
        scheduled = self.scheduled
        # static per-leg scaling gates — 'current' legs keep the pinned
        # legacy lowering (and its sharded==local bitwise contract)
        scaled = self.scaled
        down_scaled_leg = scaled and not link.down_p.is_current
        up_scaled_leg = scaled and not link.up_p.is_current
        # EF gate (dynamic legs are rejected for this executor, so the
        # inner codec here is always a fixed-size grid codec)
        ef_up = self.ef_up
        cfg = self.cfg
        faults: FaultModel | None = self.faults
        lat_table = (faults.latencies(cfg.n_clients)
                     if faults is not None else None)
        quorum, policy = self.quorum, self.quorum_policy

        def round_fn(state: ServerState, data: Array, labels: Array,
                     nk: Array, key: Array):
            server_params = state.params
            r = state.round if scheduled else None
            st_down, st_up = state.scales if scaled else ((), ())
            with jax.named_scope("fl.sample"):
                k_sel, k_down, k_up, k_loc, k_srv = jax.random.split(key, 5)

            spec = wire.make_wire_spec(server_params)

            # --- stage 1: cohort selection (replicated) ------------------
            with jax.named_scope("fl.sample"):
                idx = sampler(nk, k_sel)
                nk_sel = nk[idx]

            # --- stage 2a: downlink (replicated: ONE encode+decode) ------
            # inside a fully-replicated shard_map, like the aggregation
            # tail below: GSPMD cannot partition the codec's Mosaic
            # kernels, so every device runs the same encode+decode locally
            rep = PartitionSpec()
            with jax.named_scope("fl.down"):
                if down_scaled_leg:
                    down, st_down = shard_map(
                        lambda p, k, st: link.down_scaled(p, spec, k, st),
                        mesh=mesh, in_specs=(rep, rep, rep),
                        out_specs=(rep, rep), check_vma=False,
                    )(server_params, k_down, st_down)
                else:
                    # r is None (an empty pytree) unless the round is
                    # scheduled
                    down = shard_map(
                        lambda p, k, r_op: link.down(p, spec, k, r=r_op),
                        mesh=mesh, in_specs=(rep, rep, rep), out_specs=rep,
                        check_vma=False,
                    )(server_params, k_down, r)

            # same fan-out as the local round; the pad wraps cohort rows
            # (keys included) so padded clients are exact duplicates whose
            # outputs are sliced off inside the shard
            with jax.named_scope("fl.train"):
                loc_keys = jax.random.split(k_loc, P)
                pad_idx = jnp.arange(padded, dtype=jnp.int32) % P
                sel = idx[pad_idx]
                d_sel, l_sel = data[sel], labels[sel]
                lk_sel = loc_keys[pad_idx]
            with jax.named_scope("fl.up"):
                uk_sel = jax.random.split(k_up, P)[pad_idx]

            # --- stages 3 + 2b: per-shard training, u8 uplink gather -----
            def train_shard(dn, d, l, lk):
                with jax.named_scope("fl.train"):
                    client_params, losses = ex.run_shard(
                        local_update, dn, d, l, lk, P
                    )
                    # same stage-boundary pin as the local round: the
                    # per-shard training must not fuse into the encode it
                    # feeds
                    return jax.lax.optimization_barrier(
                        (client_params, losses)
                    )

            def gather_losses(losses):
                g = jax.lax.all_gather(losses, axis)
                return g.reshape(-1)[:P]

            def shard_body(dn, d, l, lk, uk, r_op):
                client_params, losses = train_shard(dn, d, l, lk)
                with jax.named_scope("fl.up"):
                    msgs = link.up_gather(client_params, uk, axis, n_keep=P,
                                          ref=dn, r=r_op)
                    return msgs, gather_losses(losses)

            sh = PartitionSpec(axis)
            rep = PartitionSpec()
            if up_scaled_leg:
                # scaled uplink: the history's effective scales ride into
                # the shard replicated; the per-client amax byproduct is
                # gathered alongside the codes and comes back replicated
                def shard_body_scaled(dn, d, l, lk, uk, st):
                    client_params, losses = train_shard(dn, d, l, lk)
                    with jax.named_scope("fl.up"):
                        msgs, amax = link.up_gather_scaled(
                            client_params, uk, axis, n_keep=P, st=st
                        )
                        return msgs, gather_losses(losses), amax

                msgs, losses, up_amax = shard_map(
                    shard_body_scaled, mesh=mesh,
                    in_specs=(rep, sh, sh, sh, sh, rep),
                    out_specs=(rep, rep, rep),
                    check_vma=False,
                )(down, d_sel, l_sel, lk_sel, uk_sel, st_up)
            elif ef_up:
                # EF uplink fused into the shard: residual rows ride in
                # cohort-sharded, each shard compensates ITS clients, the
                # inner grid codec crosses the wire exactly like the
                # legacy gather, and the new residual rows come back
                # sharded. Padded rows duplicate cohort rows (same keys,
                # same residual), so slicing [:P] outside recovers the
                # exact cohort-order rows the local round computes.
                def shard_body_ef(dn, d, l, lk, uk, e):
                    client_params, losses = train_shard(dn, d, l, lk)
                    with jax.named_scope("fl.up"):
                        comp = jax.vmap(
                            lambda p, ei: ef_lib.add_resid(p, ei, spec)
                        )(client_params, e)
                        msgs = link.up_gather_ef(comp, uk, axis, n_keep=P)
                        # this shard's decoded twins: row j here is cohort
                        # client (start + j) % P of the replicated stack
                        start = jax.lax.axis_index(axis) * local
                        take = (start
                                + jnp.arange(local, dtype=jnp.int32)) % P
                        dec = jax.tree.map(lambda x: x[take], msgs)
                        flat = jax.vmap(lambda t: ef_lib.flatten_q(t, spec))
                        new_e = flat(comp) - flat(dec)
                        return msgs, gather_losses(losses), new_e

                with jax.named_scope("fl.up"):
                    e_sel_pad = state.clients.resid[sel]
                msgs, losses, new_e_pad = shard_map(
                    shard_body_ef, mesh=mesh,
                    in_specs=(rep, sh, sh, sh, sh, sh),
                    out_specs=(rep, rep, sh),
                    check_vma=False,
                )(down, d_sel, l_sel, lk_sel, uk_sel, e_sel_pad)
                with jax.named_scope("fl.up"):
                    new_e = new_e_pad[:P]
                    e_sel = e_sel_pad[:P]
            elif scheduled:
                # the round-index rides replicated into the shard so the
                # scheduled uplink resolves its phase inside shard_map
                msgs, losses = shard_map(
                    shard_body, mesh=mesh,
                    in_specs=(rep, sh, sh, sh, sh, rep),
                    out_specs=(rep, rep),
                    check_vma=False,
                )(down, d_sel, l_sel, lk_sel, uk_sel, r)
            else:
                # no extra operand on unscheduled links: the lowering (and
                # its pinned bitwise-parity contract) is unchanged
                msgs, losses = shard_map(
                    lambda dn, d, l, lk, uk: shard_body(dn, d, l, lk, uk,
                                                        None),
                    mesh=mesh,
                    in_specs=(rep, sh, sh, sh, sh),
                    out_specs=(rep, rep),
                    check_vma=False,
                )(down, d_sel, l_sel, lk_sel, uk_sel)

            with jax.named_scope("fl.up"):
                # --- fault stage (replicated; statically elided when
                # fault-free). The draw is a pure function of the round
                # key, so every device computes the same masks; masking
                # is elementwise (no reduction, nothing to reassociate),
                # so the sharded==local bitwise contract survives under
                # faults too.
                if faults is not None:
                    fd = faults.draw(key, idx, lat_table)
                    if faults.flips_values:
                        msgs = faults.corrupt_tree(msgs, fd.corrupted, key)
                    msgs = _mask_rejected(msgs, fd.accepted, down)
                    n_alive = jnp.sum(fd.accepted.astype(jnp.int32))
                    n_tx = jnp.sum(fd.transmitted.astype(jnp.int32))
                    nk_agg = nk_sel * fd.accepted.astype(nk_sel.dtype)
                    nk_agg = jnp.where(n_alive > 0, nk_agg,
                                       jnp.ones_like(nk_agg))
                else:
                    nk_agg = nk_sel

                # --- residual commit (EF, replicated): same semantics as
                # the local round — transmitters update, dropped clients
                # keep old rows, quorum-skips still commit (core.ef
                # docstring)
                if ef_up:
                    if faults is not None:
                        new_e = jnp.where(fd.transmitted[:, None], new_e,
                                          e_sel)
                    new_clients = state.clients._replace(
                        resid=state.clients.resid.at[idx].set(new_e)
                    )
                else:
                    new_clients = state.clients

                # --- delayed-uplink history append (replicated; identical
                # math to the local round, so the contract holds under
                # scaling too) --------------------------------------------
                if up_scaled_leg:
                    if faults is not None:
                        acc = fd.accepted.astype(jnp.float32)[:, None]
                        row = jnp.max(up_amax * acc, axis=0)
                        row = jnp.where(n_alive > 0, row,
                                        jnp.max(st_up, axis=0))
                    else:
                        row = jnp.max(up_amax, axis=0)
                    st_up = link.up_p.update(st_up, row)
            new_scales = (st_down, st_up) if scaled else ()

            # --- stage 4: server aggregation (replicated) ----------------
            # inside its own fully-replicated shard_map: left to GSPMD, the
            # partitioner shards the (P, ...) client axis whenever D
            # divides P and the cross-device psum REASSOCIATES the
            # aggregator's float reductions (weighted_mean, moments) — a
            # silent mesh-size-dependent drift. Manual mode pins every
            # reduction to the same local, sequential lowering the
            # single-device round uses.
            rep = PartitionSpec()

            def tail_fn(sp, m, w, k, st, ls):
                new_p, new_o = aggregator(sp, m, w, k, st)
                return new_p, new_o, jnp.mean(ls)

            with jax.named_scope("fl.aggregate"):
                new_params, new_opt, mean_loss = shard_map(
                    tail_fn, mesh=mesh,
                    in_specs=(rep, rep, rep, rep, rep, rep),
                    out_specs=(rep, rep, rep),
                    check_vma=False,
                )(server_params, msgs, nk_agg, k_srv, state.opt, losses)

                if faults is not None:
                    # quorum selection outside the tail shard
                    # (elementwise, replicated) — a discarded round leaves
                    # params AND aggregator state untouched, exactly like
                    # the local round
                    ok = n_alive >= (quorum if policy == "skip" else 1)
                    keep = lambda new, old: jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), new, old
                    )
                    new_params = keep(new_params, server_params)
                    new_opt = keep(new_opt, state.opt)
                    if scaled:
                        new_scales = keep(new_scales, state.scales)

            if faults is not None:
                for pr in (_schedule_probe_rounds(link)
                           if scheduled else [0]):
                    _exact_round_bytes(link, spec, P, pr)
                down_b, up_b = link.leg_bytes_traced(spec, r)
                wire_b = P * down_b + n_tx * up_b
            elif scheduled:
                for pr in _schedule_probe_rounds(link):
                    _exact_round_bytes(link, spec, P, pr)
                wire_b = link.traced_round_bytes(spec, P, r)
            else:
                wire_b = jnp.asarray(
                    _exact_round_bytes(link, spec, P), jnp.int32
                )
            metrics = {
                "local_loss": mean_loss,
                # logical round bytes are executor-schedule-invariant: P
                # clients still exchange one model copy per leg (the u8
                # gather IS the uplink payloads, merely batched per device)
                "wire_bytes": wire_b,
            }
            if faults is not None:
                metrics.update(
                    n_alive=n_alive,
                    n_transmitted=n_tx,
                    quorum_met=(n_alive >= quorum).astype(jnp.int32),
                    round_ok=ok.astype(jnp.int32),
                    round_time=faults.round_time(fd),
                )
            return ServerState(new_params, new_opt,
                               (r + 1) if scheduled else (),
                               new_scales, new_clients), metrics

        return round_fn

    def _build_fed2d_round(self):
        """The 2D ``(clients, fsdp)`` round: every stage that touches model
        state is model-sharded over the FSDP axis.

        Placement (``sharding.policy.fed_param_specs``): server params, the
        broadcast, client stacks and aggregator moments are FSDP-sharded on
        their last-two dims and replicated over the client axis; clip
        scalars and small leaves stay replicated everywhere. Inside every
        manual (``shard_map``) region the leaves ARE local shards, so
        ``wire.make_wire_spec`` on the region's tree builds the per-device
        plane at trace time — encode/decode stay ONE fused kernel launch
        per device at any model scale, and the uplink's only cohort-sized
        collective still moves uint8 codes along the client axis (the
        FSDP-sharded operands never cross the model axis).

        RNG discipline: all shards share the round's keys UNFOLDED — a
        quantized leaf that falls back to replicated (``fit_spec``) must
        decode bit-identically on every FSDP row, which same-key encoding
        guarantees (same data + same plane position + same key). Sharded
        leaves reuse draw positions across shards, which biases nothing
        (stochastic rounding is elementwise in the value).

        Parity: det-mode codecs are elementwise in (value, clip) so the 2D
        round matches the local round bitwise on the wire; rand-mode draws
        depend on plane layout, so only det rounds are cross-checked
        against 1D. Params match to GSPMD-reassociation tolerance. A
        ``DeltaCodec`` uplink computes its residual clips ``max|w - ref|``
        over the LOCAL shard — a per-shard grid that is self-consistent
        (the clips ride in the payload) and strictly tighter than the 1D
        per-tensor grid, but not grid-matched to it. Byte accounting stays
        LOGICAL (the global wire spec) — identical to every other round
        build, static == traced.

        The aggregator tail runs model-sharded for the elementwise
        aggregators (mean / FedAvgM / FedAdam operate per element, so
        local-shard math is exact); the UQ+ ``ServerOptAggregator`` does
        per-tensor clip grid searches (cross-element reductions) and runs
        replicated instead — still one plane launch per device, but over
        the gathered tree.
        """
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec

        from ..sharding.policy import fed_param_specs

        P = self.cohort
        ex: ShardedExecutor = self.executor
        mesh, caxis, maxis = ex.mesh, ex.axis, ex.model_axis
        _, padded = ex.pad_to_shards(P)
        sampler, link, aggregator = self.sampler, self.link, self.aggregator
        local_update = self._local_update
        scheduled = self.scheduled
        # static per-leg scaling gates — 'current' legs keep the pinned
        # 2D lowering verbatim. Scaled legs run per-DEVICE over the local
        # plane (the local spec has the same leaf segmentation as the
        # global one), with amax pmax'd over the model axis so every
        # shard appends the same global history row.
        scaled = self.scaled
        down_scaled_leg = scaled and not link.down_p.is_current
        up_scaled_leg = scaled and not link.up_p.is_current
        cfg = self.cfg
        faults: FaultModel | None = self.faults
        lat_table = (faults.latencies(cfg.n_clients)
                     if faults is not None else None)
        quorum, policy = self.quorum, self.quorum_policy
        shard_tail = not isinstance(aggregator, ServerOptAggregator)

        rep = PartitionSpec()

        def _lead(spec_leaves, axis, treedef):
            """Prepend a leading-axis name to every leaf spec."""
            return jax.tree_util.tree_unflatten(
                treedef, [PartitionSpec(axis, *s) for s in spec_leaves]
            )

        def round_fn(state: ServerState, data: Array, labels: Array,
                     nk: Array, key: Array):
            server_params = state.params
            r = state.round if scheduled else None
            st_down, st_up = state.scales if scaled else ((), ())
            with jax.named_scope("fl.sample"):
                k_sel, k_down, k_up, k_loc, k_srv = jax.random.split(key, 5)

            # GLOBAL wire spec: byte accounting only (executor-invariant)
            spec = wire.make_wire_spec(server_params)

            # FSDP placements for everything model-shaped
            pspecs = fed_param_specs(server_params, mesh, axis=maxis)
            treedef = jax.tree_util.tree_structure(server_params)
            spec_leaves = [
                s.spec if hasattr(s, "spec") else s
                for s in treedef.flatten_up_to(pspecs)
            ]
            pspecs = jax.tree_util.tree_unflatten(treedef, spec_leaves)
            shardings = jax.tree_util.tree_unflatten(
                treedef, [NamedSharding(mesh, s) for s in spec_leaves]
            )
            with jax.named_scope("fl.down"):
                server_params = jax.lax.with_sharding_constraint(
                    server_params, shardings
                )

            # --- stage 1: cohort selection (replicated) ------------------
            with jax.named_scope("fl.sample"):
                idx = sampler(nk, k_sel)
                nk_sel = nk[idx]

            # --- stage 2a: downlink (model-sharded: ONE encode+decode per
            # device over its local shards; same key on every shard) ------
            def down_body(p, kd, r_op):
                lspec = wire.make_wire_spec(p)
                return link.down(p, lspec, kd, r=r_op)

            with jax.named_scope("fl.down"):
                if down_scaled_leg:
                    # per-device scaled encode over the LOCAL plane; the
                    # delayed amax is pmax'd over the model axis inside, so
                    # the state update leaves the shard replicated
                    def down_body_scaled(p, kd, st):
                        lspec = wire.make_wire_spec(p)
                        return link.down_scaled(p, lspec, kd, st, axis=maxis)

                    down, st_down = shard_map(
                        down_body_scaled, mesh=mesh,
                        in_specs=(pspecs, rep, rep),
                        out_specs=(pspecs, rep),
                        check_vma=False,
                    )(server_params, k_down, st_down)
                elif scheduled:
                    down = shard_map(
                        down_body, mesh=mesh,
                        in_specs=(pspecs, rep, rep), out_specs=pspecs,
                        check_vma=False,
                    )(server_params, k_down, r)
                else:
                    down = shard_map(
                        lambda p, kd: down_body(p, kd, None), mesh=mesh,
                        in_specs=(pspecs, rep), out_specs=pspecs,
                        check_vma=False,
                    )(server_params, k_down)

            # --- stage 3: GSPMD cohort x FSDP training -------------------
            # clients spread over `caxis` rows (pad wraps cohort rows, so
            # padded clients are duplicates sliced off below); each row's
            # step is partitioned over `maxis` by the sharding constraints
            with jax.named_scope("fl.train"):
                loc_keys = jax.random.split(k_loc, P)
                pad_idx = jnp.arange(padded, dtype=jnp.int32) % P
                sel = idx[pad_idx]

                def cohort_c(x):
                    return jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, PartitionSpec(caxis))
                    )

                with dispatch.gspmd_partitioned():
                    stacked, losses = _client_vmap(
                        local_update, down, cohort_c(data[sel]),
                        cohort_c(labels[sel]), cohort_c(loc_keys[pad_idx]),
                    )
                stk_specs = _lead(spec_leaves, caxis, treedef)
                stacked = jax.lax.with_sharding_constraint(
                    stacked, jax.tree_util.tree_unflatten(
                        treedef,
                        [NamedSharding(mesh, PartitionSpec(caxis, *s))
                         for s in spec_leaves],
                    )
                )
                # same stage-boundary pin as every other round build
                stacked, losses = jax.lax.optimization_barrier(
                    (stacked, losses))

            # --- stage 2b: uplink (u8 codes move along `caxis` only) -----
            def up_body(cp, uk, dn, r_op):
                return link.up_gather(cp, uk, caxis, n_keep=P, ref=dn,
                                      r=r_op)

            out_stk = _lead(spec_leaves, None, treedef)
            with jax.named_scope("fl.up"):
                up_keys = jax.random.split(k_up, P)
                if up_scaled_leg:
                    # scaled uplink: per-device amax over the local shard,
                    # pmax'd over the model axis so the gathered (P, n_q)
                    # row set is globally consistent and fully replicated
                    def up_body_scaled(cp, uk, st):
                        m, amax = link.up_gather_scaled(
                            cp, uk, caxis, n_keep=P, st=st
                        )
                        return m, jax.lax.pmax(amax, maxis)

                    msgs, up_amax = shard_map(
                        up_body_scaled, mesh=mesh,
                        in_specs=(stk_specs, PartitionSpec(caxis), rep),
                        out_specs=(out_stk, rep), check_vma=False,
                    )(stacked, up_keys[pad_idx], st_up)
                elif scheduled:
                    msgs = shard_map(
                        up_body, mesh=mesh,
                        in_specs=(stk_specs, PartitionSpec(caxis), pspecs,
                                  rep),
                        out_specs=out_stk, check_vma=False,
                    )(stacked, up_keys[pad_idx], down, r)
                else:
                    msgs = shard_map(
                        lambda cp, uk, dn: up_body(cp, uk, dn, None),
                        mesh=mesh,
                        in_specs=(stk_specs, PartitionSpec(caxis), pspecs),
                        out_specs=out_stk, check_vma=False,
                    )(stacked, up_keys[pad_idx], down)
                ls = losses[:P]

                # --- fault stage (replicated masks; elementwise over the
                # FSDP-sharded trees, so GSPMD broadcasts them for free).
                # The DRAW is pinned inside a fully-replicated shard_map:
                # left in the open jit, GSPMD would shard the cohort-sized
                # bernoulli masks, and with jax_threefry_partitionable off
                # (the default before JAX 0.5) the bits change under
                # partitioning. Pinned, the realization equals every other
                # round build's for the same key under either setting -----
                if faults is not None:
                    fd = shard_map(
                        lambda k_, i_: faults.draw(k_, i_, lat_table),
                        mesh=mesh, in_specs=(rep, rep), out_specs=rep,
                        check_vma=False,
                    )(key, idx)
                    if faults.flips_values:
                        msgs = faults.corrupt_tree(msgs, fd.corrupted, key)
                    msgs = _mask_rejected(msgs, fd.accepted, down)
                    n_alive = jnp.sum(fd.accepted.astype(jnp.int32))
                    n_tx = jnp.sum(fd.transmitted.astype(jnp.int32))
                    nk_agg = nk_sel * fd.accepted.astype(nk_sel.dtype)
                    nk_agg = jnp.where(n_alive > 0, nk_agg,
                                       jnp.ones_like(nk_agg))
                else:
                    nk_agg = nk_sel

                # --- delayed-uplink history append (replicated; identical
                # math to the local round) --------------------------------
                if up_scaled_leg:
                    if faults is not None:
                        acc = fd.accepted.astype(jnp.float32)[:, None]
                        row = jnp.max(up_amax * acc, axis=0)
                        row = jnp.where(n_alive > 0, row,
                                        jnp.max(st_up, axis=0))
                    else:
                        row = jnp.max(up_amax, axis=0)
                    st_up = link.up_p.update(st_up, row)
            new_scales = (st_down, st_up) if scaled else ()

            # --- stage 4: server aggregation -----------------------------
            def tail_fn(sp, m, w, k, st, l_):
                new_p, new_o = aggregator(sp, m, w, k, st)
                return new_p, new_o, jnp.mean(l_)

            with jax.named_scope("fl.aggregate"):
                if shard_tail:
                    from ..launch.steps import aggregator_state_specs

                    opt_specs = aggregator_state_specs(aggregator, pspecs)
                    new_params, new_opt, mean_loss = shard_map(
                        tail_fn, mesh=mesh,
                        in_specs=(pspecs, out_stk, rep, rep, opt_specs, rep),
                        out_specs=(pspecs, opt_specs, rep),
                        check_vma=False,
                    )(server_params, msgs, nk_agg, k_srv, state.opt, ls)
                else:
                    # UQ+ grid searches reduce across whole tensors — gather
                    # and run the tail replicated (same lowering as the 1D
                    # sharded round's tail)
                    new_params, new_opt, mean_loss = shard_map(
                        tail_fn, mesh=mesh,
                        in_specs=(rep, rep, rep, rep, rep, rep),
                        out_specs=(rep, rep, rep),
                        check_vma=False,
                    )(server_params, msgs, nk_agg, k_srv, state.opt, ls)
                    new_params = jax.lax.with_sharding_constraint(
                        new_params, shardings
                    )

                if faults is not None:
                    ok = n_alive >= (quorum if policy == "skip" else 1)
                    keep = lambda new, old: jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), new, old
                    )
                    new_params = keep(new_params, server_params)
                    new_opt = keep(new_opt, state.opt)
                    if scaled:
                        new_scales = keep(new_scales, state.scales)

            if faults is not None:
                for pr in (_schedule_probe_rounds(link)
                           if scheduled else [0]):
                    _exact_round_bytes(link, spec, P, pr)
                down_b, up_b = link.leg_bytes_traced(spec, r)
                wire_b = P * down_b + n_tx * up_b
            elif scheduled:
                for pr in _schedule_probe_rounds(link):
                    _exact_round_bytes(link, spec, P, pr)
                wire_b = link.traced_round_bytes(spec, P, r)
            else:
                wire_b = jnp.asarray(
                    _exact_round_bytes(link, spec, P), jnp.int32
                )
            metrics = {
                "local_loss": mean_loss,
                # logical accounting: P clients x one model copy per leg,
                # regardless of how the copies are laid out over the mesh
                "wire_bytes": wire_b,
            }
            if faults is not None:
                metrics.update(
                    n_alive=n_alive,
                    n_transmitted=n_tx,
                    quorum_met=(n_alive >= quorum).astype(jnp.int32),
                    round_ok=ok.astype(jnp.int32),
                    round_time=faults.round_time(fd),
                )
            return ServerState(new_params, new_opt,
                               (r + 1) if scheduled else (),
                               new_scales, state.clients), metrics

        return round_fn
