"""FP8 kernel microbenchmarks (CPU wall-clock; TPU perf is structural —
see the roofline).

Three families, all recorded to ``BENCH_kernels.json`` for the perf
trajectory:

* fused Pallas quantizer (interpret mode on CPU) vs the unfused jnp chain,
  FORWARD and BACKWARD (the custom-VJP STE kernels vs jnp autodiff);
* the fused QAT matmul + its dx/dw backward kernels vs the jnp composition;
* the flat-buffer wire codec (ONE fused quantize-dequantize launch for a
  whole model pytree, in-kernel counter RNG) vs the per-leaf loop it
  replaced (a quantize+pack+unpack jnp chain and a threefry draw per
  tensor). This is the O(n_tensors) -> O(1) collapse of the comm hot loop
  and must hold >= 3x on a LeNet-sized tree (acceptance criterion);
* the tiled parameter plane (ISSUE 2): whole-tree quantize-params-once
  forward+backward on the plane vs the per-leaf loop, and UQ+
  server_optimize (one launch per GD step / grid point) vs the per-segment
  reference loop;
* the federated client executors (ISSUE 3): chunked scan-over-vmap vs
  full-cohort vmap at K=512 LeNet clients — XLA compiled temp-buffer size
  (the live-memory envelope) and wall-clock. The chunked executor's temps
  must scale with the chunk size, not the cohort size.
* the 16-lane interleaved rANS entropy coder (ISSUE 10): the sender-side
  encode scan and the fused decode kernel vs its bit-identical jnp twin,
  on a matched-prior byte stream;
* the sharded cohort executor (ISSUE 4): the same K=512 round spread over
  a 1- vs 8-virtual-device ``clients`` mesh (this module forces 8 CPU
  host devices when it is the entry point). ``memory_analysis`` of the
  per-shard SPMD executable is the per-DEVICE executor envelope — it must
  shrink ~Dx with device count while the round stays one u8 gather.

Interpret-mode absolute numbers are NOT TPU predictions — the interpreter
executes kernel bodies op-by-op, so true fusion only materializes on a
Mosaic backend. What IS structural and shows on CPU: launch-count
collapse, the removed per-leaf threefry passes, and operand-traffic
reduction (alpha columns, no external random operand).
"""
from __future__ import annotations

import json
import os
import time

# Single-threaded XLA for stable microbenchmark numbers (only effective
# when this module is the entry point — i.e. before jax initializes; the
# aggregate runner may import us after jax is up, which just means noisier
# numbers there). The codec acceptance ratio is measured min-of-interleaved
# to cancel co-tenant load drift either way.
os.environ.setdefault(
    "XLA_FLAGS",
    # 8 virtual host devices for the sharded-cohort rows (dryrun-style);
    # single-device benches still run on device 0, unaffected
    "--xla_force_host_platform_device_count=8 "
    "--xla_cpu_multi_thread_eigen=false",
)

import jax
import jax.numpy as jnp

from repro.core import fp8, wire

from repro.kernels import dispatch, fp8_matmul, fp8_quant
from repro.models import small


def _time(fn, *args, n=20, reps=3) -> float:
    """Best-of-``reps`` mean wall-clock in us (XLA:CPU scheduling is noisy)."""
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / n * 1e6)
    return best


def _row(rows, name, us, derived=""):
    rows.append({"bench": "kernel", "name": name,
                 "us_per_call": round(us, 1), "derived": derived})


def _quantizer_benches(rows):
    shape = (1024, 1024)
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    alpha = jnp.max(jnp.abs(x))
    g = jnp.ones(shape, jnp.float32)
    bits = jax.random.bits(jax.random.PRNGKey(1), shape=shape, dtype=jnp.uint32)

    # --- forward ---------------------------------------------------------
    jnp_det = jax.jit(lambda x, a: fp8.quantize_det(x, a))
    _row(rows, "quant_det_jnp_1Melem", _time(jnp_det, x, alpha), "unfused baseline")
    _row(rows, "quant_det_pallas_interp",
         _time(lambda x, a: fp8_quant.quant_det(x, a, interpret=True), x, alpha),
         "interpret-mode (structural only on CPU)")
    _row(rows, "quant_rand_pallas_interp",
         _time(lambda x, a, b: fp8_quant.quant_rand(x, a, b, interpret=True),
               x, alpha, bits))

    # --- backward --------------------------------------------------------
    jnp_bwd = jax.jit(jax.grad(
        lambda x, a: jnp.sum(fp8.quantize_det(x, a) * g), argnums=(0, 1)
    ))
    _row(rows, "quant_det_bwd_jnp_autodiff", _time(jnp_bwd, x, alpha),
         "unfused STE autodiff baseline")
    _row(rows, "quant_det_bwd_pallas_interp",
         _time(lambda x, a, g: fp8_quant.quant_det_bwd(x, a, g, interpret=True),
               x, alpha, g),
         "fused custom-VJP backward kernel")


def _matmul_benches(rows):
    m = k = n = 256
    x = jax.random.normal(jax.random.PRNGKey(2), (m, k), jnp.float32) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n), jnp.float32) * 0.1
    beta = jnp.asarray(1.0, jnp.float32)
    alpha = jnp.max(jnp.abs(w))
    g = jnp.ones((m, n), jnp.float32)

    jnp_mm = jax.jit(lambda x, w, b, a: jnp.dot(
        fp8.quantize_det(x, b), fp8.quantize_det(w, a),
        preferred_element_type=jnp.float32))
    _row(rows, "qat_matmul_jnp_256", _time(jnp_mm, x, w, beta, alpha),
         "unfused quantize-then-dot baseline")
    _row(rows, "qat_matmul_pallas_interp_256",
         _time(lambda *a: fp8_matmul.qat_matmul(*a, interpret=True),
               x, w, beta, alpha))

    jnp_mm_bwd = jax.jit(jax.grad(
        lambda x, w, b, a: jnp.sum(jnp_mm(x, w, b, a) * g),
        argnums=(0, 1, 2, 3)))
    _row(rows, "qat_matmul_bwd_jnp_256", _time(jnp_mm_bwd, x, w, beta, alpha),
         "unfused autodiff baseline")
    _row(rows, "qat_matmul_bwd_pallas_interp_256",
         _time(lambda *a: (
             fp8_matmul.qat_matmul_dx(g, *a, interpret=True),
             fp8_matmul.qat_matmul_dw(g, *a, interpret=True)),
             x, w, beta, alpha),
         "fused dx+dw backward kernels")


def _codec_benches(rows):
    """Flat-buffer wire codec vs the per-leaf loop it replaced.

    The per-leaf side is the exact structure this codec deleted: one
    ``quantize_rand`` + ``pack_fp8`` + ``unpack_fp8`` jnp chain per weight
    tensor, each with its own ``jax.random`` draw (O(n_tensors) dispatches,
    a threefry pass per leaf). The flat side is the shipped
    ``wire.roundtrip``: ONE fused quantize-dequantize launch for the whole
    model (interpret mode on CPU), randomness from the in-kernel counter
    RNG. Timing is min-of-interleaved so transient machine load (which
    hits whichever side happens to be running) cancels out.
    """
    prior_backend = os.environ.get(dispatch._ENV)
    os.environ[dispatch._ENV] = "interpret"
    try:
        for model in ("lenet", "kwt"):
            init, _ = small.REGISTRY[model]
            params = init(jax.random.PRNGKey(0), n_classes=10)
            spec = wire.make_wire_spec(params)
            key = jax.random.PRNGKey(0)

            @jax.jit
            def per_leaf(params, key):
                leaves = jax.tree_util.tree_leaves(params)
                keys = jax.random.split(key, len(spec.q_slots))
                out = []
                for slot, ai, k in zip(spec.q_slots, spec.alpha_pos, keys):
                    leaf = leaves[slot]
                    a = leaves[spec.other_slots[ai]]
                    q = fp8.quantize_rand(leaf, a, k)
                    codes = fp8.pack_fp8(q, a)
                    out.append(fp8.unpack_fp8(codes, a))
                return out

            flat = jax.jit(lambda p, k: wire.roundtrip(p, k, spec=spec))

            t_flat, t_leaf = _interleaved(flat, per_leaf, params, key,
                                          n=30, outer=16)
            speedup = t_leaf / max(t_flat, 1e-9)
            _row(rows, f"wire_codec_per_leaf_loop_{model}", t_leaf,
                 f"{len(spec.q_slots)} per-leaf quantize+pack+unpack chains")
            _row(rows, f"wire_codec_flat_buffer_{model}", t_flat,
                 f"1 fused launch, {spec.total} elems; "
                 f"{speedup:.1f}x vs per-leaf")
            rows.append({
                "bench": "kernel",
                "name": f"wire_codec_speedup_{model}",
                "us_per_call": round(speedup, 2),
                "derived": "per-leaf/flat wall-clock ratio"
                + (" (>=3x acceptance target)" if model == "lenet" else ""),
            })
    finally:
        if prior_backend is None:
            os.environ.pop(dispatch._ENV, None)
        else:
            os.environ[dispatch._ENV] = prior_backend

    # uint8 pack throughput for the accounting table
    q = fp8.quantize_det(
        jax.random.normal(jax.random.PRNGKey(5), (1024, 1024)), jnp.asarray(3.0))
    pack = jax.jit(lambda q: fp8.pack_fp8(q, jnp.asarray(3.0)))
    t_pack = _time(pack, q)
    mbps = (q.size / (t_pack / 1e6)) / 1e6
    _row(rows, "wire_pack_uint8", t_pack, f"{mbps:.0f} Melem/s")

    # packed sub-byte codec (core.codec.PackedFpCodec): fused FP4
    # encode/decode on the same (R, LANE) plane as the FP8 wire —
    # 2 codes/byte, so the payload (and the u8 collective) halves
    from repro.core.fp8 import FP4_E2M1

    R = 512
    x2 = jax.random.normal(jax.random.PRNGKey(7), (R, fp8_quant.WIRE_LANE),
                           jnp.float32)
    a2 = jnp.full((R, 1), 2.5, jnp.float32)
    key2 = jnp.asarray([1, 2], jnp.uint32)
    t8 = _time(lambda: fp8_quant.quant_pack_tiles(
        x2, a2, key2, interpret=True))
    t4 = _time(lambda: fp8_quant.fold_codes(fp8_quant.quant_pack_tiles(
        x2, a2, key2, fmt=FP4_E2M1, interpret=True), FP4_E2M1))
    n = R * fp8_quant.WIRE_LANE
    _row(rows, "wire_encode_fp8_tiles_0p5M", t8,
         f"fused quantize+pack, {n} B payload")
    _row(rows, "wire_encode_fp4_packed_0p5M", t4,
         f"quantize+pack kernel, then 2 codes/byte fold, {n // 2} B payload "
         "(half the FP8 wire)")
    c8 = fp8_quant.quant_pack_tiles(x2, a2, key2, interpret=True)
    c4 = fp8_quant.fold_codes(fp8_quant.quant_pack_tiles(
        x2, a2, key2, fmt=FP4_E2M1, interpret=True), FP4_E2M1)
    t8d = _time(lambda: fp8_quant.unpack_tiles(c8, a2, interpret=True))
    t4d = _time(lambda: fp8_quant.unpack_tiles(
        fp8_quant.unfold_codes(c4, FP4_E2M1).astype(jnp.uint8), a2,
        fmt=FP4_E2M1, interpret=True))
    _row(rows, "wire_decode_fp8_tiles_0p5M", t8d, "fused unpack-dequantize")
    _row(rows, "wire_decode_fp4_packed_0p5M", t4d,
         "unfold, then unpack-dequantize kernel on the half-size payload")


def _rans_benches(rows):
    """16-lane interleaved rANS coder: encode (reverse ``lax.scan``) and
    decode (forward ``lax.scan``; no kernel form — see ``kernels.rans``),
    with the roundtrip asserted. Stream: a LeNet-scale 32 KiB byte
    payload drawn FROM the static fp4 table itself (the matched-prior
    case the wire sees)."""
    from repro.core.entropy import byte_table
    from repro.core.fp8 import FP4_E2M1
    from repro.kernels import rans as rk

    n = 1 << 15
    freq_np, cum_np, s2s_np = byte_table(FP4_E2M1, 0.28)
    freq, cum, s2s = (jnp.asarray(freq_np), jnp.asarray(cum_np),
                      jnp.asarray(s2s_np))
    # uniform slots through slot2sym == exact table distribution
    slots = jax.random.randint(jax.random.PRNGKey(13), (n,), 0, rk.TAB)
    syms = s2s[slots].astype(jnp.int32)

    enc = jax.jit(lambda s: rk.rans_encode(s, freq, cum))
    t_enc = _time(enc, syms, n=10)
    buf, state, lens = enc(syms)
    coded = float(jnp.sum(lens))
    _row(rows, "rans_encode_32k", t_enc,
         f"reverse lax.scan, {rk.LANES} lanes; {coded:.0f}/{n} coded B "
         f"({8 * coded / n:.2f} bits/byte)")

    dec = jax.jit(lambda b, st, ln: rk.rans_decode(
        b, st, ln, n, freq, cum, s2s))
    assert bool(jnp.all(dec(buf, state, lens) == syms))
    t_d = _time(dec, buf, state, lens, n=10)
    _row(rows, "rans_decode_jnp_32k", t_d, "lax.scan decode")


def _scaling_benches(rows):
    """Encode latency per scaling policy (ISSUE 8): the amax reduction
    leaves the hot path.

    Same 0.5M-element (R, LANE) plane as the wire_encode rows. The
    ``current``-scaling recipe (TE's default before delayed scaling) must
    run a standalone amax reduction whose result GATES the quantize
    launch — two dependent passes over the plane. ``delayed`` quantizes
    at the history's scales and gets the next round's amax as a byproduct
    of the SAME fused launch (``quant_pack_amax_tiles``); ``frozen``
    ships no scales at all, so it is the plain single launch. What is
    structural on CPU: the dependent extra pass disappears — the
    interpret-mode deltas understate a real backend, where the amax
    reduction also serializes against the quantize kernel.
    """
    R = 512
    x2 = jax.random.normal(jax.random.PRNGKey(9), (R, fp8_quant.WIRE_LANE),
                           jnp.float32)
    a2 = jnp.full((R, 1), 2.5, jnp.float32)
    key2 = jnp.asarray([3, 4], jnp.uint32)
    n = R * fp8_quant.WIRE_LANE

    def enc_current(x2, key2):
        # fresh amax: a full pass over the plane BEFORE the quantize
        # launch can start (the scale is its operand)
        a = jnp.maximum(jnp.max(jnp.abs(x2)), fp8._ALPHA_FLOOR)
        return fp8_quant.quant_pack_tiles(
            x2, jnp.full((R, 1), a, jnp.float32), key2, interpret=True)

    def enc_delayed(x2, key2):
        # scales come from the amax history; the NEXT round's amax falls
        # out of the same fused quantize launch
        return fp8_quant.quant_pack_amax_tiles(x2, a2, key2, interpret=True)

    def enc_frozen(x2, key2):
        # receiver already holds the scales: plain quantize, no amax
        return fp8_quant.quant_pack_tiles(x2, a2, key2, interpret=True)

    t_c = _time(enc_current, x2, key2)
    t_d = _time(enc_delayed, x2, key2)
    t_f = _time(enc_frozen, x2, key2)
    _row(rows, "wire_encode_scaling_current_0p5M", t_c,
         f"fresh amax pass + dependent quantize launch, {n} elems")
    _row(rows, "wire_encode_scaling_delayed_0p5M", t_d,
         f"ONE fused quantize+amax launch; {t_c / max(t_d, 1e-9):.2f}x "
         "vs current")
    _row(rows, "wire_encode_scaling_frozen_0p5M", t_f,
         f"plain quantize, no amax, no alpha riders; "
         f"{t_c / max(t_f, 1e-9):.2f}x vs current")
    rows.append({
        "bench": "kernel", "name": "wire_encode_delayed_speedup",
        "us_per_call": round(t_c / max(t_d, 1e-9), 2),
        "derived": "current/delayed encode wall-clock ratio "
                   "(the killed standalone amax reduction)",
    })


def _scaling_fed2d_benches(rows):
    """The same three policies with the plane FSDP-sharded over the 2x4
    federated mesh (clients x fsdp): each device encodes its LOCAL row
    block. ``current`` needs a cross-shard pmax of the fresh amax BEFORE
    any device can quantize (a collective on the critical path);
    ``delayed`` quantizes immediately at the replicated history scales
    and pmaxes only the byproduct amax row — one scalar per segment,
    OFF the critical path; ``frozen`` has no collective at all. jnp
    backend inside shard_map (scheduling is the subject)."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_fed_mesh

    if len(jax.devices()) < 8:
        rows.append({
            "bench": "kernel", "name": "wire_encode_scaling_fed2d_skipped",
            "us_per_call": 0.0,
            "derived": f"needs 8 devices ({len(jax.devices())} present) — "
                       "run this module as the entry point",
        })
        return

    mesh = make_fed_mesh(2, 4)
    R = 512
    x2 = jax.random.normal(jax.random.PRNGKey(9), (R, fp8_quant.WIRE_LANE),
                           jnp.float32)
    x2 = jax.device_put(x2, NamedSharding(mesh, P("fsdp", None)))
    a_loc = jnp.full((R // 4, 1), 2.5, jnp.float32)
    key2 = jnp.asarray([3, 4], jnp.uint32)

    def body_current(xl, k2):
        # fresh GLOBAL amax: local reduce + pmax collective, and only
        # then can the local quantize start
        a = jax.lax.pmax(jnp.max(jnp.abs(xl)), "fsdp")
        a = jnp.maximum(a, fp8._ALPHA_FLOOR)
        return dispatch.quant_pack_tiles(
            xl, jnp.full((xl.shape[0], 1), a, jnp.float32), k2)

    def body_delayed(xl, k2):
        codes, rowmax = dispatch.quant_pack_amax_tiles(xl, a_loc, k2)
        # history row: pmax of the fused byproduct — one scalar, and the
        # codes are already produced when it runs
        amax = jax.lax.pmax(jnp.max(rowmax), "fsdp")
        return codes, amax

    def body_frozen(xl, k2):
        return dispatch.quant_pack_tiles(xl, a_loc, k2)

    def timed(body):
        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("fsdp", None), P()),
            out_specs=(P("fsdp", None), P()) if body is body_delayed
            else P("fsdp", None), check_vma=False,
        ))
        return _time(fn, x2, key2)

    t_c = timed(body_current)
    t_d = timed(body_delayed)
    t_f = timed(body_frozen)
    _row(rows, "wire_encode_scaling_fed2d_current_2x4", t_c,
         "fresh amax: local reduce + pmax gate the sharded quantize")
    _row(rows, "wire_encode_scaling_fed2d_delayed_2x4", t_d,
         f"fused quantize+amax, pmax of one byproduct scalar; "
         f"{t_c / max(t_d, 1e-9):.2f}x vs current")
    _row(rows, "wire_encode_scaling_fed2d_frozen_2x4", t_f,
         f"no collective at all; {t_c / max(t_f, 1e-9):.2f}x vs current")


def _interleaved(fn_a, fn_b, *args, n=20, outer=8):
    """min-of-interleaved wall-clocks (us) so load drift cancels."""
    jax.block_until_ready(fn_a(*args))
    jax.block_until_ready(fn_b(*args))

    def _one(fn):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e6

    t_a = t_b = float("inf")
    for _ in range(outer):
        t_a = min(t_a, _one(fn_a))
        t_b = min(t_b, _one(fn_b))
    return t_a, t_b


def _plane_benches(rows):
    """Tiled parameter plane vs the per-leaf loops it replaced (ISSUE 2).

    quantize-params-once (opt_level 1): forward + backward of the whole-tree
    Q_det — the plane path is ONE fused launch each way (custom-VJP tile
    kernels under ``interpret`` here; jnp fallback elsewhere), the per-leaf
    path is the O(n_tensors) chain the trainer used to trace.
    server_optimize (UQ+): one fused launch per GD step / grid point vs the
    per-segment Python loop (O(n_seg x (gd_steps + n_grid)) launches).
    """
    from repro.core.qat import QATConfig, alpha_like
    from repro.core.server_opt import (ServerOptConfig, server_optimize,
                                       server_optimize_reference)
    from repro.launch.steps import (quantize_params_once,
                                    quantize_params_once_per_leaf)

    params = small.REGISTRY["lenet"][0](jax.random.PRNGKey(0), n_classes=10)
    qcfg = QATConfig()

    def sq_loss(quantize):
        def loss(p):
            q, _ = quantize(p, qcfg)
            return sum(jnp.sum(l.astype(jnp.float32) ** 2)
                       for l in jax.tree.leaves(q))
        return jax.jit(jax.value_and_grad(loss))

    # interpret backend throughout, matching the codec bench: the fused
    # plane paths exercise the kernel bodies; the per-leaf baselines are
    # the jnp chains the old code shipped
    prior_backend = os.environ.get(dispatch._ENV)
    os.environ[dispatch._ENV] = "interpret"
    try:
        t_plane, t_leaf = _interleaved(
            sq_loss(quantize_params_once),
            sq_loss(quantize_params_once_per_leaf), params,
        )
        _row(rows, "quantize_once_per_leaf_lenet_fwdbwd", t_leaf,
             "O(n_tensors) quantize chains + autodiff")
        _row(rows, "quantize_once_plane_lenet_fwdbwd", t_plane,
             "1 fused launch fwd + 1 bwd (interpret); "
             f"{t_leaf / max(t_plane, 1e-9):.1f}x vs per-leaf")
        rows.append({
            "bench": "kernel", "name": "quantize_once_plane_speedup_lenet",
            "us_per_call": round(t_leaf / max(t_plane, 1e-9), 2),
            "derived": "per-leaf/plane fwd+bwd wall-clock ratio",
        })

        # --- server_optimize: plane scan vs per-segment loop -------------
        key = jax.random.PRNGKey(11)
        msgs = []
        for i in range(4):
            t = {}
            for li in range(6):
                w = jax.random.normal(jax.random.fold_in(key, 10 * i + li),
                                      (64, 128)) * 0.3
                t[f"l{li}"] = {"w": w, "w_qa": alpha_like(w) * (1 + 0.05 * i)}
            msgs.append(t)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *msgs)
        nk = jnp.ones((4,))
        cfg = ServerOptConfig(enabled=True, gd_steps=3, lr=0.1, n_grid=10)
        f_plane = jax.jit(lambda s, n, k: server_optimize(s, n, k, cfg))
        f_leaf = jax.jit(
            lambda s, n, k: server_optimize_reference(s, n, k, cfg)
        )
        t_plane, t_leaf = _interleaved(
            f_plane, f_leaf, stacked, nk, jax.random.PRNGKey(3),
            n=5, outer=6,
        )
    finally:
        if prior_backend is None:
            os.environ.pop(dispatch._ENV, None)
        else:
            os.environ[dispatch._ENV] = prior_backend
    _row(rows, "server_opt_per_leaf_6x64x128", t_leaf,
         f"6-leaf loop, {cfg.gd_steps} GD + {cfg.n_grid} grid per leaf")
    _row(rows, "server_opt_plane_6x64x128", t_plane,
         f"scan: 1 fused launch/GD step + 1/grid point (interpret); "
         f"{t_leaf / max(t_plane, 1e-9):.1f}x vs per-leaf")
    rows.append({
        "bench": "kernel", "name": "server_opt_plane_speedup",
        "us_per_call": round(t_leaf / max(t_plane, 1e-9), 2),
        "derived": "per-leaf/plane wall-clock ratio (interpret backend)",
    })


def _fed_executor_benches(rows):
    """Chunked vs full-vmap ClientExecutor at K=512 LeNet clients (ISSUE 3).

    The full-cohort vmap materializes per-client optimizer state,
    activations and local-step scan residuals for ALL 512 clients at once;
    the ChunkedExecutor's lax.scan holds them for one 16-client chunk at a
    time, so XLA's compiled temp-buffer size (reported by
    ``memory_analysis``) is the O(chunk)-vs-O(P) envelope made measurable.
    Both rounds are the SAME computation (bit-identical outputs — asserted
    in tests/test_engine.py); only the schedule differs. QAT/wire are off
    so the numbers isolate the executor. jnp backend: the executor is pure
    scheduling, no kernel bodies involved.
    """
    from repro import optim
    from repro.core.engine import FedConfig, RoundEngine
    from repro.core.qat import DISABLED

    K, CHUNK = 512, 16
    init, _ = small.REGISTRY["lenet"]
    params = init(jax.random.PRNGKey(0), n_classes=10)
    loss = small.make_loss(small.REGISTRY["lenet"][1])
    # momentum so per-client optimizer state is real (mirrors the params)
    opt = optim.sgd(0.05, momentum=0.9)
    base = dict(n_clients=K, participation=1.0, local_steps=1,
                batch_size=4, comm_mode="none", qat=DISABLED)
    data = jax.random.normal(jax.random.PRNGKey(1), (K, 4, 32, 32, 3),
                             jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (K, 4), 0, 10)
    nk = jnp.full((K,), 4.0)
    key = jax.random.PRNGKey(3)

    temps = {}
    for name, cfg in (
        ("full_vmap", FedConfig(**base)),
        (f"chunked_{CHUNK}", FedConfig(chunk=CHUNK, **base)),
    ):
        eng = RoundEngine(loss, opt, cfg)
        # the executor STAGE, jitted standalone: the stacked client params
        # are this jit's *output* buffer (the aggregator's input — an O(P)
        # cost both schedules share), so temp_size_in_bytes isolates the
        # live training memory: per-client optimizer state + activations.
        lu = eng._local_update
        ex = jax.jit(lambda d, l, k: eng.executor(lu, params, d, l, k))
        keys = jax.random.split(key, K)
        ma = ex.lower(data, labels, keys).compile().memory_analysis()
        temp_mb = (ma.temp_size_in_bytes / 1e6) if ma is not None else None
        temps[name] = temp_mb
        # end-to-end round wall-clock (sampling + links + aggregate included)
        rf = jax.jit(eng.round_fn)
        state = eng.init(params)
        t = _time(rf, state, data, labels, nk, key, n=2, reps=2)
        _row(rows, f"fed_round_{name}_K{K}_lenet", t,
             f"one round, U=1, B=4; executor XLA temp "
             f"{temp_mb:.0f} MB" if temp_mb is not None else "temp n/a")
    if all(v is not None for v in temps.values()):
        ratio = temps["full_vmap"] / max(temps[f"chunked_{CHUNK}"], 1e-9)
        rows.append({
            "bench": "fed", "name": f"fed_executor_temp_ratio_K{K}",
            "us_per_call": round(ratio, 2),
            "derived": f"full-vmap/chunked-{CHUNK} executor temp-buffer "
                       f"ratio ({temps['full_vmap']:.0f} MB vs "
                       f"{temps[f'chunked_{CHUNK}']:.0f} MB) — the "
                       "O(P) -> O(chunk) live-memory envelope",
        })


def _fed_sharded_benches(rows):
    """ShardedExecutor at K=512 LeNet over a 1- vs 8-device client mesh
    (ISSUE 4): per-DEVICE executor temp buffers and end-to-end round
    wall-clock. The SPMD executable is per-device, so memory_analysis of
    the jitted executor stage reads each device's live training envelope
    directly — it must shrink ~Dx while outputs (the cohort stack every
    device holds for the server tail) stay O(K) by design. Wall-clock on
    virtual CPU devices is sequential-ish (all shards share the host);
    the structural row is the memory ratio."""
    import jax

    from repro import optim
    from repro.core.engine import FedConfig, RoundEngine, ShardedExecutor
    from repro.core.qat import DISABLED
    from repro.launch.mesh import make_client_mesh

    n_avail = len(jax.devices())
    if n_avail < 2:
        rows.append({
            "bench": "fed", "name": "fed_round_sharded_skipped",
            "us_per_call": 0.0,
            "derived": f"needs multi-device ({n_avail} present) — run this "
                       "module as the entry point to force 8 virtual CPUs",
        })
        return

    K = 512
    init, _ = small.REGISTRY["lenet"]
    params = init(jax.random.PRNGKey(0), n_classes=10)
    loss = small.make_loss(small.REGISTRY["lenet"][1])
    opt = optim.sgd(0.05, momentum=0.9)
    base = dict(n_clients=K, participation=1.0, local_steps=1,
                batch_size=4, comm_mode="none", qat=DISABLED)
    data = jax.random.normal(jax.random.PRNGKey(1), (K, 4, 32, 32, 3),
                             jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (K, 4), 0, 10)
    nk = jnp.full((K,), 4.0)
    key = jax.random.PRNGKey(3)

    temps = {}
    for D in (1, min(8, n_avail)):
        mesh = make_client_mesh(D)
        eng = RoundEngine(loss, opt, FedConfig(mesh=mesh, **base))
        assert isinstance(eng.executor, ShardedExecutor)
        lu = eng._local_update
        ex = jax.jit(lambda d, l, k: eng.executor(lu, params, d, l, k))
        keys = jax.random.split(key, K)
        ma = ex.lower(data, labels, keys).compile().memory_analysis()
        temp_mb = (ma.temp_size_in_bytes / 1e6) if ma is not None else None
        temps[D] = temp_mb
        rf = jax.jit(eng.round_fn)
        state = eng.init(params)
        t = _time(rf, state, data, labels, nk, key, n=2, reps=2)
        _row(rows, f"fed_round_sharded_D{D}_K{K}_lenet", t,
             f"one round over a {D}-device clients mesh, U=1, B=4; "
             + (f"per-device executor XLA temp {temp_mb:.0f} MB"
                if temp_mb is not None else "temp n/a"))
    Ds = sorted(temps)
    if all(temps[d] is not None for d in Ds) and len(Ds) == 2:
        ratio = temps[Ds[0]] / max(temps[Ds[1]], 1e-9)
        rows.append({
            "bench": "fed", "name": f"fed_sharded_temp_ratio_K{K}",
            "us_per_call": round(ratio, 2),
            "derived": f"D={Ds[0]} / D={Ds[1]} per-device executor "
                       f"temp-buffer ratio ({temps[Ds[0]]:.0f} MB vs "
                       f"{temps[Ds[1]]:.0f} MB) — the cohort axis "
                       "spreading across the client mesh",
        })


def _fed2d_plane_benches(rows):
    """Shard-aware plane quantize-once vs the per-leaf loop under FSDP on
    the 2D federated mesh (ISSUE 7): reduced-tinyllama masters sharded by
    ``sharding.policy.fed_param_specs`` over the fsdp axis of a 2x4
    (clients, fsdp) mesh. The sharded plane is a shard_map whose body
    quantizes each device's LOCAL shards — ONE plane-kernel launch per
    device regardless of tree size (trace-time count pinned in
    tests/test_engine_sharded.py) and zero cross-shard resharding; the
    per-leaf loop is the retired FSDP path: O(n_tensors) quantize chains
    that GSPMD reshards around. jnp backend (scheduling is the subject,
    not kernel bodies); fwd+bwd of the same squared loss both sides."""
    from repro import configs
    from repro.core import qat as qat_lib
    from repro.core.qat import QATConfig
    from repro.kernels import dispatch as _dispatch
    from repro.launch.mesh import make_fed_mesh
    from repro.launch.steps import (quantize_params_once_per_leaf,
                                    quantize_params_once_sharded)
    from repro.models.registry import get_model
    from repro.sharding.policy import fed_param_shardings

    if len(jax.devices()) < 8:
        rows.append({
            "bench": "fed", "name": "quantize_once_fsdp_skipped",
            "us_per_call": 0.0,
            "derived": f"needs 8 devices ({len(jax.devices())} present) — "
                       "run this module as the entry point",
        })
        return

    cfg = configs.reduced(configs.get("tinyllama_1_1b"))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    mesh = make_fed_mesh(2, 4)
    sh = fed_param_shardings(params, mesh, axis="fsdp")
    params = jax.device_put(params, sh)
    qcfg = QATConfig()
    n_q = len(qat_lib.quantized_leaf_names(params))

    def sq_loss(quantize):
        def loss(p):
            q, _ = quantize(p, qcfg)
            return sum(jnp.sum(l.astype(jnp.float32) ** 2)
                       for l in jax.tree.leaves(q))
        return jax.jit(jax.value_and_grad(loss))

    f_plane = sq_loss(lambda p, c: quantize_params_once_sharded(p, c, sh))
    f_leaf = sq_loss(quantize_params_once_per_leaf)

    # trace-time launch count of the sharded-plane path (O(1) per device)
    calls = []
    orig = _dispatch.quant_det_plane
    _dispatch.quant_det_plane = (
        lambda *a, **k: calls.append(1) or orig(*a, **k))
    try:
        jax.make_jaxpr(
            lambda p: quantize_params_once_sharded(p, qcfg, sh)[0]
        )(params)
    finally:
        _dispatch.quant_det_plane = orig

    t_plane, t_leaf = _interleaved(f_plane, f_leaf, params, n=10, outer=8)
    _row(rows, "quantize_once_fsdp_per_leaf_tinyllama_fwdbwd", t_leaf,
         f"{n_q} per-leaf quantize chains under GSPMD (retired FSDP path)")
    _row(rows, "quantize_once_fsdp_sharded_plane_tinyllama_fwdbwd", t_plane,
         f"shard_map plane on the 2x4 clients x fsdp mesh: "
         f"{len(calls)} launch/device; "
         f"{t_leaf / max(t_plane, 1e-9):.1f}x vs per-leaf")
    rows.append({
        "bench": "fed", "name": "quantize_once_fsdp_plane_speedup",
        "us_per_call": round(t_leaf / max(t_plane, 1e-9), 2),
        "derived": f"per-leaf/sharded-plane fwd+bwd wall-clock ratio; "
                   f"trace enters the plane kernel {len(calls)}x "
                   f"(O(1)/device) vs {n_q} per-leaf chains",
    })


def run(out_rows=None):
    rows = out_rows if out_rows is not None else []
    _quantizer_benches(rows)
    _matmul_benches(rows)
    _codec_benches(rows)
    _rans_benches(rows)
    _scaling_benches(rows)
    _scaling_fed2d_benches(rows)
    _plane_benches(rows)
    _fed_executor_benches(rows)
    _fed_sharded_benches(rows)
    _fed2d_plane_benches(rows)
    with open("BENCH_kernels.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main():
    rows = run()
    print("bench,name,us_per_call,derived")
    for r in rows:
        print(f"{r['bench']},{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
