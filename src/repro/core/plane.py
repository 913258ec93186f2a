"""Tiled parameter plane — one ``(rows, LANE)`` view of a param pytree.

PR 1's wire codec collapsed *communication* quantization to one kernel
launch by concatenating every quantized weight into lane-aligned tiles.
This module extracts that tiling machinery into a reusable view so the two
remaining per-leaf hot paths — the opt_level-1 per-step weight fake-quant
(``launch.steps.quantize_params_once``) and the UQ+ server optimizer
(``core.server_opt``) — ride the same O(1)-launch structure.

Layout
======
The plane is built at **alpha-segment** granularity: one segment per
clipping *scalar*, i.e. one per quantized tensor, or one per layer slab for
stacked scanned parameters whose clipping value has shape
``(L, 1, ..., 1)``. Each segment is zero-padded to a whole number of
``(LANE,)`` rows, so every row belongs to exactly one clipping value and
the kernels take alpha as a ``(n_rows, 1)`` per-row *column* — 1/LANE the
operand traffic of a full tile, broadcast in-kernel. (This is where the
plane deliberately differs from ``core.wire``'s payload layout, which packs
each leaf contiguously so codes slice back to exact wire bytes; here the
layout is compute-only and row/alpha alignment is what matters.)

Autodiff
========
``pack_tiles``/``leaf_from_tiles`` are plain pad/reshape/concat/slice ops,
so JAX autodiff flows through the plane view for free. The per-row alpha
column is produced by ``jnp.take(alphas, spec.row_seg)`` — the transpose of
that gather is a scatter-add, i.e. exactly the segment-sum that routes each
row's scale-term cotangent back to its leaf's scalar (or stacked per-layer)
alpha. The fused quantizer in the middle carries its own custom VJP
(``kernels.dispatch.quant_det_plane``), so one forward launch and one
backward launch cover the whole tree.

Shard-aware planes (2D federated mesh / FSDP)
=============================================
Packing the plane concatenates leaves, which under GSPMD would reshard
FSDP-sharded masters through one device. The shard-aware layout instead
builds the plane **per device over the local leaf shards**: inside a
``shard_map`` body the leaves ARE the local shards, so ``make_plane_spec``
on the body's tree is already the per-device plane — same segment/alpha
structure, row math over local shapes. Two structural facts make this
exact:

* the FSDP rules (``sharding.policy.fed_param_specs``) only shard the
  last-two dims, so a stacked scanned weight keeps its leading layer axis
  whole and **alpha-segment granularity is preserved per shard** (every
  local row still maps to exactly one clipping scalar; alphas replicate);
* per-shard zero-padding to whole LANE rows is layout-only — consumers
  slice rows back to exact local element counts, and byte accounting
  charges logical payload bytes (``core.wire`` — built from the same local
  shapes inside the shard), never pad (:func:`plane_pad_elems` exposes the
  pad for the tests that pin this).

``make_local_plane_spec`` builds the same per-device spec OUTSIDE a manual
region (trace-time, from global shapes + PartitionSpecs) for tests and
byte math; :func:`quantize_det_sharded` is the one-launch-per-device
whole-tree fake-quant under explicit shardings — deterministic
quantization is elementwise in ``(x, alpha)``, so its values (and STE
gradients, with alpha cotangents psum-reduced across shards by the
``shard_map`` transpose) match the unsharded plane bitwise. The per-leaf
loop (``launch.steps.quantize_params_once_per_leaf``) survives only as
the parity reference.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import fp8, qat
from .fp8 import E4M3, FP8Format
from ..kernels.fp8_quant import WIRE_LANE as LANE

Array = jax.Array
PyTree = Any


def f32(x: Array) -> Array:
    """Cast to f32 only when needed. A no-op ``convert`` on a buffer feeding
    an interpret-mode pallas_call defeats XLA's operand fusion and costs
    ~7x on the whole encode (measured on the LeNet tree) — skip it."""
    return x if x.dtype == jnp.float32 else x.astype(jnp.float32)


def tiles(pieces: list, fill) -> Array:
    """Stack 1-D pieces into the (rows, LANE) tile layout.

    Each piece is zero-padded to a whole number of 128-lane rows and the
    rows are concatenated. Per-piece row alignment (rather than one flat
    concat reshaped afterwards) matters twice: the lane width is a multiple
    of the TPU native 128, and XLA:CPU pessimizes a flat concat-of-reshapes
    feeding an interpret-mode pallas_call by ~7x (measured). Padding never
    reaches consumers — rows slice back to exact element counts.
    """
    rows = []
    for f in pieces:
        pad = (-f.size) % LANE
        if pad:
            f = jnp.concatenate([f, jnp.full((pad,), fill, f.dtype)])
        rows.append(f.reshape(-1, LANE))
    return jnp.concatenate(rows, axis=0)


def nelem(shape: tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


@dataclasses.dataclass(frozen=True, eq=False)
class PlaneSpec:
    """Static description of a param pytree's tiled parameter plane."""

    treedef: Any
    q_slots: tuple[int, ...]           # flat-leaf index of each quantized leaf
    q_names: tuple[str, ...]           # dotted names (same order as q_slots)
    q_shapes: tuple[tuple[int, ...], ...]
    q_dtypes: tuple[Any, ...]
    alpha_slots: tuple[int, ...]       # flat-leaf index of each leaf's alpha
    alpha_shapes: tuple[tuple[int, ...], ...]
    alpha_dtypes: tuple[Any, ...]
    leaf_segs: tuple[int, ...]         # segments per leaf (1, or L if stacked)
    leaf_seg0: tuple[int, ...]         # first segment id of each leaf
    seg_sizes: tuple[int, ...]         # real elements per segment
    seg_rows: tuple[int, ...]          # rows per segment
    seg_row0: tuple[int, ...]          # first row of each segment
    n_rows: int                        # total rows of the (n_rows, LANE) plane
    n_seg: int                         # total segments == total alpha scalars
    row_seg: np.ndarray                # (n_rows,) int32: row -> segment id

    @property
    def n_leaves(self) -> int:
        return self.treedef.num_leaves


def make_plane_spec(params: PyTree) -> PlaneSpec:
    """Build the static plane layout for a param pytree (trace-time)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    dotted = [".".join(qat._key_name(p) for p in path) for path, _ in flat]
    index = {name: i for i, name in enumerate(dotted)}
    qnames = sorted(qat.quantized_leaf_names(params))

    q_slots, q_shapes, q_dtypes = [], [], []
    alpha_slots, alpha_shapes, alpha_dtypes = [], [], []
    leaf_segs, leaf_seg0 = [], []
    seg_sizes, seg_rows, seg_row0 = [], [], []
    row_seg: list[int] = []
    row0 = seg0 = 0
    for name in qnames:
        leaf = flat[index[name]][1]
        a_leaf = flat[index[name + qat.QA_SUFFIX]][1]
        n_seg_leaf = int(nelem(tuple(a_leaf.shape)))
        if n_seg_leaf > 1:
            # stacked scanned parameter: alpha (L, 1, ..., 1) pairs layer
            # slabs of the (L, ...) weight — one segment per layer
            if leaf.shape[0] != n_seg_leaf:
                raise ValueError(
                    f"{name}: stacked alpha {a_leaf.shape} does not pair "
                    f"leading axis of weight {leaf.shape}"
                )
        size = int(leaf.size) // n_seg_leaf
        q_slots.append(index[name])
        q_shapes.append(tuple(leaf.shape))
        q_dtypes.append(leaf.dtype)
        alpha_slots.append(index[name + qat.QA_SUFFIX])
        alpha_shapes.append(tuple(a_leaf.shape))
        alpha_dtypes.append(a_leaf.dtype)
        leaf_segs.append(n_seg_leaf)
        leaf_seg0.append(seg0)
        for _ in range(n_seg_leaf):
            rows = -(-size // LANE)
            seg_sizes.append(size)
            seg_rows.append(rows)
            seg_row0.append(row0)
            row_seg.extend([seg0] * rows)
            row0 += rows
            seg0 += 1
    return PlaneSpec(
        treedef=treedef,
        q_slots=tuple(q_slots),
        q_names=tuple(qnames),
        q_shapes=tuple(q_shapes),
        q_dtypes=tuple(q_dtypes),
        alpha_slots=tuple(alpha_slots),
        alpha_shapes=tuple(alpha_shapes),
        alpha_dtypes=tuple(alpha_dtypes),
        leaf_segs=tuple(leaf_segs),
        leaf_seg0=tuple(leaf_seg0),
        seg_sizes=tuple(seg_sizes),
        seg_rows=tuple(seg_rows),
        seg_row0=tuple(seg_row0),
        n_rows=row0,
        n_seg=seg0,
        row_seg=np.asarray(row_seg, np.int32),
    )


def pack_tiles(params: PyTree, spec: PlaneSpec) -> tuple[Array, Array]:
    """Params -> ``(x2 (n_rows, LANE) f32, alphas (n_seg,) f32)``.

    Differentiable: pad/reshape/concat only. Alphas are floored at
    ``fp8._ALPHA_FLOOR`` here (the same guard every quantizer applies), so
    downstream consumers can assume strictly positive clipping values.
    """
    leaves = jax.tree_util.tree_leaves(params)
    pieces = []
    for qi, slot in enumerate(spec.q_slots):
        f = f32(leaves[slot].reshape(-1))
        n_seg_leaf = spec.leaf_segs[qi]
        if n_seg_leaf == 1:
            pieces.append(f)
        else:
            per = spec.seg_sizes[spec.leaf_seg0[qi]]
            pieces.extend(
                f[l * per:(l + 1) * per] for l in range(n_seg_leaf)
            )
    x2 = tiles(pieces, 0.0)
    alphas = jnp.concatenate(
        [f32(leaves[s].reshape(-1)) for s in spec.alpha_slots]
    )
    return x2, jnp.maximum(alphas, fp8._ALPHA_FLOOR)


def alpha_column(alphas: Array, spec: PlaneSpec) -> Array:
    """``(n_seg,)`` alphas -> ``(n_rows, 1)`` per-row column.

    The transpose of this gather is a scatter-add over ``row_seg`` — the
    segment-sum that folds per-row alpha cotangents back to each scalar.
    """
    return jnp.take(alphas, jnp.asarray(spec.row_seg))[:, None]


def leaf_from_tiles(vals2: Array, spec: PlaneSpec, qi: int,
                    dtype: Any = None) -> Array:
    """Slice quantized leaf ``qi`` back out of a plane buffer."""
    n_seg_leaf = spec.leaf_segs[qi]
    seg0 = spec.leaf_seg0[qi]
    slabs = []
    for si in range(seg0, seg0 + n_seg_leaf):
        r0, rows, size = spec.seg_row0[si], spec.seg_rows[si], spec.seg_sizes[si]
        slabs.append(vals2[r0:r0 + rows].reshape(-1)[:size])
    flat = slabs[0] if n_seg_leaf == 1 else jnp.concatenate(slabs)
    leaf = flat.reshape(spec.q_shapes[qi])
    dtype = dtype if dtype is not None else spec.q_dtypes[qi]
    return leaf if leaf.dtype == dtype else leaf.astype(dtype)


def plane_pad_elems(spec: PlaneSpec) -> int:
    """Zero-pad elements the tiled layout adds (``n_rows * LANE`` minus the
    real elements). Layout-only: consumers slice rows back to exact counts
    and byte accounting never charges it — the shard-aware tests pin both."""
    return spec.n_rows * LANE - sum(spec.seg_sizes)


def _partition_spec(s):
    """NamedSharding | PartitionSpec -> PartitionSpec."""
    return s.spec if hasattr(s, "spec") else s


def local_shape(shape: tuple[int, ...], spec, mesh,
                name: str = "leaf") -> tuple[int, ...]:
    """The per-device shard shape of a ``shape``-d array under ``spec``."""
    spec = _partition_spec(spec)
    out = list(shape)
    for d, ax in enumerate(spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if out[d] % size:
            raise ValueError(
                f"{name}: dim {d} of {tuple(shape)} is not divisible by "
                f"mesh axes {axes} (size {size}) — fit the spec first "
                "(sharding.policy.fed_param_specs drops non-dividing axes)"
            )
        out[d] //= size
    return tuple(out)


def make_local_plane_spec(params: PyTree, specs: PyTree, mesh) -> PlaneSpec:
    """The per-DEVICE plane a ``shard_map`` body over ``specs`` builds.

    Trace-time twin of calling :func:`make_plane_spec` INSIDE the manual
    region: same segment ordering and alpha pairing, row/byte math over the
    local shard shapes. Used by tests (local-vs-global reconstruction) and
    launch-count/byte accounting outside a shard; the hot paths simply call
    ``make_plane_spec`` on the body's local tree.

    Validates the two invariants the shard-aware layout rests on, with the
    failure named at the offending leaf: a stacked scanned weight's leading
    (layer) axis must stay unsharded (else local rows would straddle alpha
    segments), and every clipping leaf must be replicated (each device
    needs the full alpha vector for its rows).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    spec_leaves = [
        _partition_spec(s) for s in treedef.flatten_up_to(specs)
    ]
    gspec = make_plane_spec(params)
    for qi, slot in enumerate(gspec.q_slots):
        name = gspec.q_names[qi]
        if gspec.leaf_segs[qi] > 1:
            sp = spec_leaves[slot]
            if len(sp) > 0 and sp[0] is not None:
                raise ValueError(
                    f"{name}: stacked scanned weight has its leading layer "
                    f"axis sharded ({sp}) — the plane pairs layer slabs "
                    "with per-layer alphas, so shard the trailing dims "
                    "only (sharding.policy.fed_param_specs does)"
                )
        a_sp = spec_leaves[gspec.alpha_slots[qi]]
        if any(ax is not None for ax in a_sp):
            raise ValueError(
                f"{name}{qat.QA_SUFFIX}: clipping values must be "
                f"replicated, got {a_sp} — every device's plane rows "
                "need the full alpha vector"
            )
    locals_ = [
        jax.ShapeDtypeStruct(
            local_shape(leaf.shape, sp, mesh, name=".".join(
                qat._key_name(p) for p in path)),
            leaf.dtype,
        )
        for (path, leaf), sp in zip(flat, spec_leaves)
    ]
    return make_plane_spec(jax.tree_util.tree_unflatten(treedef, locals_))


def quantize_det_sharded(params: PyTree, shardings: PyTree,
                         fmt: FP8Format = E4M3, out_dtype: Any = None,
                         mesh=None) -> PyTree:
    """:func:`quantize_det` under explicit shardings: ONE launch per device.

    The body runs the plane quantize on each device's LOCAL shards — the
    spec built inside the manual region IS the shard-aware plane, so no
    cross-shard resharding occurs and the launch count stays O(1) per
    device regardless of tree size. Deterministic quantization is
    elementwise in ``(x, alpha)``, so values match the unsharded plane
    bitwise; the ``shard_map`` transpose psums per-shard alpha cotangents
    back to the replicated scalars, so STE gradients match too.

    ``shardings`` is a pytree of ``NamedSharding`` (mesh inferred) or
    ``PartitionSpec`` (pass ``mesh=``) matching ``params``; fully
    replicated trees fall back to the plain plane quantize.
    """
    from jax import shard_map

    treedef = jax.tree_util.tree_structure(params)
    sh_leaves = treedef.flatten_up_to(shardings)
    if mesh is None:
        mesh = next(
            (s.mesh for s in sh_leaves if hasattr(s, "mesh")), None
        )
        if mesh is None:
            raise ValueError(
                "quantize_det_sharded: PartitionSpec shardings need an "
                "explicit mesh="
            )
    spec_leaves = [_partition_spec(s) for s in sh_leaves]
    specs = jax.tree_util.tree_unflatten(treedef, spec_leaves)
    # validates alpha replication / stacked leading axis, with names
    make_local_plane_spec(params, specs, mesh)
    if all(ax is None for sp in spec_leaves for ax in sp):
        # fully replicated: the manual region would only add noise
        return quantize_det(params, fmt=fmt, out_dtype=out_dtype)

    def body(p):
        return quantize_det(p, fmt=fmt, out_dtype=out_dtype)

    return shard_map(
        body, mesh=mesh, in_specs=(specs,), out_specs=specs,
        check_vma=False,
    )(params)


def quantize_det(params: PyTree, fmt: FP8Format = E4M3,
                 spec: PlaneSpec | None = None,
                 out_dtype: Any = None) -> PyTree:
    """Fake-quantize every quantized weight leaf in ONE fused launch.

    Drop-in for the per-leaf ``fp8.quantize_det`` loop: identical values and
    identical STE gradients (clip mask to each weight; clip routing plus the
    ``(q - y) * s / alpha`` scale term segment-summed back to each leaf's
    scalar — or stacked per-layer — alpha), but the kernel launch count is
    O(1) in the number of tensors, forward and VJP replay alike.

    ``out_dtype`` (e.g. the compute dtype for opt_level-1 pre-quantization)
    applies to the quantized leaves only; every other leaf passes through
    untouched.
    """
    from ..kernels import dispatch  # lazy: kernels imports core modules

    if spec is None:
        spec = make_plane_spec(params)
    if not spec.q_slots:
        return params
    leaves = list(jax.tree_util.tree_leaves(params))
    x2, alphas = pack_tiles(params, spec)
    a_col = alpha_column(alphas, spec)
    q2 = dispatch.quant_det_plane(x2, a_col, fmt)
    for qi, slot in enumerate(spec.q_slots):
        leaves[slot] = leaf_from_tiles(q2, spec, qi, dtype=out_dtype)
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)
