"""Bring-up check: the paper's federated FP8 round, end to end, on TPU.

    python3 chip_smoke.py               # one chip: phases fedsim, engine_lm
    python3 chip_smoke.py --four-chips  # four chips: the engine_lm round on
                                        # a 4-device clients mesh and on a
                                        # 2x2 clients x fsdp mesh, against
                                        # the single-device round

One process imports JAX once and starts no children. The script refuses
to run (non-zero exit, no result line) unless JAX's first device is a TPU
and the kernel backend resolves to ``pallas``: it never runs on the CPU in
the chip's place. Any failed check raises, so the exit code is non-zero
and no result line is printed. The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases (one chip):

* ``fedsim`` — ``benchmarks/common.py`` task ``cifar10-lenet`` (LeNet-5 at
  the paper's widths), method ``uq`` (FP8 QAT + stochastic E4M3 wire both
  ways), ``FedSim`` with 16 clients at participation 0.5, 5 rounds and one
  eval. Loss must be finite and fall; the FP8/fp32 byte ratio is printed.
* ``engine_lm`` — the ``RoundEngine`` + ``VmapExecutor`` path of
  ``examples/fed_lm_finetune.py`` on TinyLlama-1.1B at its published
  widths with ``n_layers`` cut to ``LM_LAYERS``: a cohort of 2 clients, 2
  local AdamW steps of 4 x 512 tokens, 2 rounds. Round 1 is compared with
  the same round traced on the ``jnp`` backend under ``highest`` matmul
  precision (tolerances: ``LOSS_RTOL``, ``DOWNLINK_FLIP_SHARE``,
  ``DOWNLINK_VALUE_RTOL``, ``KERNEL_VJP_RTOL``, ``UPDATE_REL_L2``).

The phases are plain functions taking their sizes, so a CPU test can run
them at tiny size with ``REPRO_KERNEL_BACKEND=interpret``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# Tolerances. The LM computes in bf16 (models.common.COMPUTE_DTYPE) with
# f32 accumulation. Another accumulation order (the fused QAT matmul, or
# bf16 partial sums all-reduced under FSDP) moves single activations by up
# to one bf16 ULP, so the two sides of each comparison agree to about one
# bf16 ULP (2^-8 relative) of the loss. A semantic fault (wrong grid, clip,
# key or collective) moves it by far more: one round of training alone
# moves the loss by ~10%. CPU rehearsal at tiny size: 1.1e-3 (jnp vs
# interpreted kernels), 1.1e-3 (2x2 mesh vs one device).
LOSS_RTOL = 2.0 ** -8
# Share of downlink elements allowed on an adjacent FP8 grid point (another
# payload code): both backends draw the same counter-RNG bits on the same
# params, so only ULP-level differences of log2/exp2 between Mosaic and XLA
# can move an element across a rounding threshold (a TPU v5e read 1.65e-5).
DOWNLINK_FLIP_SHARE = 1e-4
# Decoded elements that moved by more than this (relative) count as grid
# flips. Adjacent E4M3 grid points lie at least 2^-4 apart (relative), and
# a decode fault (wrong scale, exponent or alpha) moves an element by at
# least one grid step; the scale exp2(p - b - mant) of the two backends
# differs only in its last ULPs (a TPU v5e put 4.2% of decoded elements
# beyond 2^-20). 2^-12 sits 256x under the finest grid step.
DOWNLINK_VALUE_RTOL = 2.0 ** -12
# The fused kernels' custom VJPs (qat_matmul's dx/dw, quant_det_bwd,
# quant_rand_bwd) at TinyLlama's MLP width vs jnp autodiff of the same math
# in f32 under ``highest`` precision, on clips below max|.| so a share of
# elements clips. Relative L2 per cotangent. Input and weight cotangents:
# the sides differ by f32 summation order (CPU parity tests: <= 1.7e-5)
# and by the kernels' MXU passes, which round f32 operands to bf16 (a TPU
# v5e read 2.5e-3).
KERNEL_VJP_RTOL = 1e-2
# Clip cotangents, each a sum over all elements of rounding residuals
# that cancel: the ULP-level log2/exp2 differences between Mosaic and XLA
# flip the rounding of ~1e-5 of elements (the downlink's code share), and
# each flip moves its term by a whole grid step, so the sound sides differ
# by ~1e-3 at this width (a TPU v5e: 5.7e-4 to 1.4e-2, the latter for the
# activation clip, whose terms also carry the bf16-pass dx). A factor on
# the clip path, a zeroed cotangent, or an SMEM accumulator that keeps one
# tile's partial reads >= 0.5. One lost partial among the many tiles at
# this width is within the noise; the CPU tests, where both sides agree to
# 1e-6, pin that.
CLIP_VJP_RTOL = 5e-2
# Round-1 server params, Pallas vs jnp, per leaf: |p - p_ref| over the
# reference round's own update |p_ref - p0| (relative L2). Bounded on the
# worst leaf other than the clips: AdamW's normalised steps turn the bf16-
# level gradient differences of the two sides into sign flips of small
# gradient elements, 0.14 of its update on ln1 in the CPU rehearsal (tiny
# size); a wrong weight gradient or collective reads >= 1. The clip leaves
# are printed, not bounded: a clip's gradient is a sum of rounding
# residuals, which the fused kernels take on f32 activations and the jnp
# chain on bf16 ones, so their updates differ by design (30x in the CPU
# rehearsal); CLIP_VJP_RTOL checks the clip cotangents themselves.
UPDATE_REL_L2 = 0.5
# Server params after one mesh round vs the single-device round on the
# same QAT path (relative L2, worst leaf). Those bf16-level differences
# reach the client params through two AdamW steps and then flip a share
# of E4M3 wire roundings to the adjacent grid point (<= 1/8 of an
# element's value): rel-L2 ~ 1/8 * sqrt(share),
# 5e-3 in the CPU rehearsal. 2e-2 leaves 4x; a wrong collective gives O(1).
PARAMS_REL_L2 = 2e-2

LM_WIDTHS = dict(clients=2, local_steps=2, batch=4, seq=512, rounds=2)
# TinyLlama has 22 layers. Compiled for a v5e chip, the jnp reference round
# needs 17.7 GB at 2 layers and 16.6 GB at 1 (memory_analysis: arguments +
# outputs + temp); the Pallas round needs 11.0 GB and 10.2 GB.
LM_LAYERS = 1
FEDSIM_SIZES = dict(k=16, c=0.5, rounds=5, local_steps=10, batch=50,
                    n_train=4000, n_test=1000)


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Checks:
    """Collects failed checks, so a phase prints every reading before it
    fails; :meth:`done` raises one :class:`SmokeFailure` naming them all."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, ok: bool, what: str) -> None:
        if not ok:
            print(f"FAILED: {what}", file=sys.stderr)
            self.failed.append(what)

    def done(self) -> None:
        if self.failed:
            raise SmokeFailure("; ".join(self.failed))


@contextlib.contextmanager
def kernel_backend(name: str):
    """Trace under ``REPRO_KERNEL_BACKEND=name`` (read at trace time)."""
    from repro.kernels import dispatch

    old = os.environ.get(dispatch._ENV)
    os.environ[dispatch._ENV] = name
    try:
        yield
    finally:
        if old is None:
            del os.environ[dispatch._ENV]
        else:
            os.environ[dispatch._ENV] = old


def custom_calls(compiled) -> int:
    """Mosaic kernels in a compiled program."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def compile_round(round_fn, *args, donate=()):
    """Compile ``round_fn`` for ``args``; also return the seconds taken and
    the ops its trace routed from Pallas to jnp (``dispatch.JNP_ROUTES``)."""
    from repro.kernels import dispatch

    before = dict(dispatch.JNP_ROUTES)
    t = time.perf_counter()
    compiled = jax.jit(round_fn, donate_argnums=donate).lower(*args).compile()
    secs = time.perf_counter() - t
    routes = {k: n - before.get(k, 0) for k, n in dispatch.JNP_ROUTES.items()
              if n != before.get(k, 0)}
    return compiled, secs, routes


# ---------------------------------------------------------------------------
# fedsim: the paper's workflow at its published widths
# ---------------------------------------------------------------------------


def fedsim_phase(*, k: int, c: float, rounds: int, local_steps: int,
                 batch: int, n_train: int, n_test: int, seed: int = 0,
                 require_kernels: bool = True) -> dict:
    from benchmarks import common

    task = common.TASKS["cifar10-lenet"]
    size = dict(k=k, c=c, local_steps=local_steps, batch=batch,
                n_train=n_train, n_test=n_test, noniid=False, seed=seed)
    sim, eval_data = common.make_sim(task, "uq", **size)
    fp32_bytes = common.make_sim(task, "fp32", **size)[0].bytes_per_round
    key = jax.random.PRNGKey(seed + 99)     # FedSim.run's key schedule
    state = sim.state
    compiled, secs, routes = compile_round(sim.engine.round_fn, state,
                                           sim.client_data,
                                           sim.client_labels, sim.nk, key)
    n_kernels = custom_calls(compiled)
    print(f"[fedsim] round compiled in {secs:.1f} s, {n_kernels} "
          f"tpu_custom_call; ops the pallas backend traced on jnp: "
          f"{routes or 0}")
    losses = []
    for r in range(1, rounds + 1):
        key, k_round = jax.random.split(key)
        state, m = compiled(state, sim.client_data, sim.client_labels,
                            sim.nk, k_round)
        losses.append(float(m["local_loss"]))
        check(int(m["wire_bytes"]) == sim.bytes_per_round,
              f"fedsim traced wire_bytes {int(m['wire_bytes'])} != static "
              f"{sim.bytes_per_round}")
        print(f"[fedsim] round {r}: local_loss {losses[-1]!r}")
    sim.state = state
    acc = sim.evaluate(*eval_data)
    ratio = fp32_bytes / sim.bytes_per_round
    print(f"[fedsim] accuracy {acc!r}; bytes_per_round {sim.bytes_per_round} "
          f"(fp32 {fp32_bytes}, fp32/fp8 {ratio!r})")
    check(bool(np.all(np.isfinite(losses))), f"fedsim loss not finite: "
          f"{losses}")
    check(losses[-1] < losses[0], f"fedsim loss did not fall: {losses}")
    if require_kernels:
        check(n_kernels > 0, "fedsim round holds no tpu_custom_call")
    return dict(losses=losses, accuracy=acc, bytes=sim.bytes_per_round,
                fp32_bytes=fp32_bytes, kernels=n_kernels)


# ---------------------------------------------------------------------------
# engine_lm: the RoundEngine at TinyLlama's published widths
# ---------------------------------------------------------------------------


def lm_config(n_layers: int):
    from repro import configs

    return configs.get("tinyllama_1_1b").replace(n_layers=n_layers)


def lm_round(cfg, *, clients: int, local_steps: int, batch: int, seq: int,
             mesh=None, model_axis=None, comm: str = "rand", seed: int = 0):
    """(engine, server state, (data, labels, nk), key) of the example's
    engine path: per-client Markov token streams, AdamW, E4M3 wire."""
    import jax.numpy as jnp
    from repro import optim
    from repro.core.engine import FedConfig, RoundEngine
    from repro.core.qat import QATConfig
    from repro.data.synthetic import synthetic_lm_tokens
    from repro.models.registry import get_model

    model = get_model(cfg)

    def loss_fn(params, xb, yb, qat_cfg, key):
        return model.train_loss(params, {"tokens": xb, "labels": yb},
                                qat_cfg)

    fed = FedConfig(n_clients=clients, participation=1.0,
                    local_steps=local_steps, batch_size=batch,
                    comm_mode=comm, qat=QATConfig(), mesh=mesh,
                    model_axis=model_axis)
    eng = RoundEngine(loss_fn, optim.adamw(1e-3, weight_decay=0.01), fed)
    rows = local_steps * batch
    streams = np.stack([
        synthetic_lm_tokens(seed + c, rows * (seq + 1), cfg.vocab)
        .reshape(rows, seq + 1) for c in range(clients)])
    data = (jnp.asarray(streams[..., :-1]), jnp.asarray(streams[..., 1:]),
            jnp.ones((clients,), jnp.float32))
    params = model.init(jax.random.PRNGKey(seed))
    return eng, eng.init(params), data, jax.random.PRNGKey(seed + 1)


def round_downlink(eng, params, key):
    """The round's broadcast: its payload codes and what the cohort
    decodes. The engine splits its round key as (sel, down, up, local,
    server)."""
    from repro.core import wire

    k_down = jax.random.split(key, 5)[1]
    codec = eng.link.down_c

    def leg(p, k):
        spec = wire.make_wire_spec(p)
        payload = codec.encode(p, spec, k)
        return payload["codes"], codec.decode(payload, spec)

    return jax.jit(leg)(params, k_down)


def downlink_check(got, ref, fmt) -> dict:
    """Compare two broadcasts of the same params under the same key.

    ``code_flip_share``: payload codes that differ; ``adjacent``: every
    differing code is the neighbouring grid point (same sign, magnitude
    code +-1). ``value_flip_share``: decoded elements that differ by more
    than ``DOWNLINK_VALUE_RTOL`` relative (a grid-point change or a decode
    fault); ``scale_rel``: the largest relative difference of the other
    elements (the scale's last ULPs); ``worst_step``: largest decoded
    difference in units of one top-binade step (max|leaf| / 8).
    """
    ca = np.asarray(got[0]).astype(np.int32).ravel()
    cb = np.asarray(ref[0]).astype(np.int32).ravel()
    nbits = fmt.exp + fmt.mant
    mag_a, mag_b = ca & ((1 << nbits) - 1), cb & ((1 << nbits) - 1)
    diff = (ca != cb) & ((mag_a != 0) | (mag_b != 0))
    adjacent = ((ca >> nbits) == (cb >> nbits)) & (np.abs(mag_a - mag_b) == 1)
    n = n_val = 0
    worst = scale = 0.0
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(ref[1])):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        d = np.abs(a - b)
        flip = d > DOWNLINK_VALUE_RTOL * np.abs(b)
        n += a.size
        n_val += int(np.count_nonzero(flip))
        kept = ~flip & (b != 0)
        if kept.any():
            scale = max(scale, float(np.max(d[kept] / np.abs(b[kept]))))
        top = max(float(np.max(np.abs(b))), 1e-30)
        worst = max(worst, float(np.max(d)) / top * 8.0)
    return dict(code_flip_share=float(diff.mean()),
                adjacent=bool(np.all(adjacent[diff])),
                value_flip_share=n_val / max(n, 1), scale_rel=scale,
                worst_step=worst)


def tree_rel(got: dict, ref: dict) -> dict:
    """Worst leaf's relative L2 and max-abs (over max|ref|) differences,
    over two :func:`host_leaves` dicts."""
    l2 = mx = 0.0
    for n, b in ref.items():
        a = got[n]
        l2 = max(l2, float(np.linalg.norm(a - b))
                 / max(float(np.linalg.norm(b)), 1e-30))
        mx = max(mx, float(np.max(np.abs(a - b)))
                 / max(float(np.max(np.abs(b))), 1e-30))
    return dict(rel_l2=l2, max_rel=mx)


def _leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(k) for k, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _is_clip(name: str) -> bool:
    return name.rstrip("']").endswith(("_qa", "_qb"))


def worst_leaves(num: dict, den: dict) -> dict:
    """Per leaf ||num|| / ||den||: the worst clip leaf (``*_qa``/``*_qb``)
    and the worst other leaf, each as ``(name, value)``."""
    rel = {n: float(np.linalg.norm(num[n])) / max(float(np.linalg.norm(d)),
                                                  1e-30)
           for n, d in den.items()}
    out = {}
    for tag, clip in (("worst", False), ("worst_clip", True)):
        part = [(n, v) for n, v in rel.items() if _is_clip(n) == clip]
        out[tag] = max(part, key=lambda kv: kv[1], default=None)
    return out


def host_leaves(tree) -> dict:
    """{leaf name: float64 host copy}."""
    return {n: np.asarray(x, np.float64)
            for n, x in zip(_leaf_names(tree), jax.tree.leaves(tree))}


def kernel_vjp_check(*, rows: int, d_in: int, d_out: int, seed: int = 0,
                     check=check) -> dict:
    """Relative L2 of each cotangent of the fused QAT kernels, Pallas vs
    jnp autodiff (see ``KERNEL_VJP_RTOL``, ``CLIP_VJP_RTOL``), at x (rows,
    d_in) @ w (d_in, d_out); the QAT matmul's also with bf16 x and g (keys
    ``bf16_matmul_*``), the LMs' path, on which the MXU takes bf16
    operands. Prints the kernels traced by MXU operand dtype
    (``dispatch.QAT_MXU``). Fails ``check`` on any cotangent beyond its
    tolerance."""
    import jax.numpy as jnp

    from repro.core import fp8
    from repro.kernels import dispatch
    from repro.kernels.fp8_quant import uniform_from_bits

    kx, kw, kg, kgw, kr = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(kx, (rows, d_in), jnp.float32)
    w = 0.02 * jax.random.normal(kw, (d_in, d_out), jnp.float32)
    g = jax.random.normal(kg, (rows, d_out), jnp.float32)
    gw = jax.random.normal(kgw, w.shape, jnp.float32)
    beta = 0.5 * jnp.max(jnp.abs(x))
    alpha = 0.5 * jnp.max(jnp.abs(w))
    bits = jax.random.bits(kr, shape=w.shape, dtype=jnp.uint32)

    def rand_ste(v, a, bits):
        # Q_rand on the dispatcher's bits, with the paper's STE
        a = jnp.maximum(a, fp8._ALPHA_FLOOR)
        vc = jnp.clip(v, -a, a)
        b = fp8.exponent_bias(a)
        p = jax.lax.stop_gradient(
            jnp.maximum(jnp.floor(jnp.log2(jnp.abs(vc)) + b), 1.0))
        s = jnp.exp2(p - b - fp8.E4M3.mant)
        y = vc / s
        fl = jnp.floor(y)
        q = fl + (uniform_from_bits(bits) < y - fl).astype(jnp.float32)
        return s * (y + jax.lax.stop_gradient(q - y))

    def vjps(x, w, g, x16, g16, gw, beta, alpha, bits, kernels):
        def rand(v, a):
            if kernels:     # the dispatcher draws ``bits`` from ``kr``
                return dispatch.quantize_rand(v, a, kr)
            return rand_ste(v, a, bits)

        mm = jax.vjp(dispatch.qat_matmul, x, w, beta, alpha)[1](g)
        mm16 = jax.vjp(dispatch.qat_matmul, x16, w, beta, alpha)[1](g16)
        det = jax.vjp(dispatch.quantize_det, w, alpha)[1](gw)
        rnd = jax.vjp(rand, w, alpha)[1](gw)
        mm_names = ("matmul_dx", "matmul_dw", "matmul_dbeta", "matmul_dalpha")
        return dict(zip(mm_names + tuple("bf16_" + n for n in mm_names)
                        + ("det_dx", "det_dalpha", "rand_dx", "rand_dalpha"),
                        (*mm, *mm16, *det, *rnd)))

    # the bf16 operands enter as arrays rounded before either side's jit:
    # cast inside it, XLA on a TPU computed the jnp reference from the
    # unrounded activations
    args = (x, w, g, x.astype(jnp.bfloat16), g.astype(jnp.bfloat16), gw,
            beta, alpha, bits)
    before = collections.Counter(dispatch.QAT_MXU)
    got = jax.jit(vjps, static_argnums=9)(*args, True)
    mxu = dict(dispatch.QAT_MXU - before)
    with kernel_backend("jnp"), jax.default_matmul_precision("highest"):
        ref = jax.jit(vjps, static_argnums=9)(*args, False)
    f32 = {n: (got[n].astype(jnp.float32), r.astype(jnp.float32))
           for n, r in ref.items()}
    rel = {n: float(jnp.linalg.norm(a - r)) / max(float(jnp.linalg.norm(r)),
                                                  1e-30)
           for n, (a, r) in f32.items()}
    print(f"[engine_lm] kernel VJPs at ({rows}, {d_in}) @ ({d_in}, "
          f"{d_out}) vs jnp autodiff (relative L2): {rel}; QAT matmul "
          f"kernels traced by MXU operand dtype: {mxu}")
    for n, v in rel.items():
        tol = CLIP_VJP_RTOL if n.endswith(("alpha", "beta")) else \
            KERNEL_VJP_RTOL
        check(v <= tol, f"kernel VJP {n} off jnp autodiff: {v} > {tol}")
    return rel


def _memory_line(tag: str, compiled) -> None:
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"[engine_lm] {tag} memory_analysis: arguments "
              f"{mem.argument_size_in_bytes} B, outputs "
              f"{mem.output_size_in_bytes} B, aliased "
              f"{mem.alias_size_in_bytes} B, temp {mem.temp_size_in_bytes} B")


def engine_lm_phase(cfg, *, clients: int, local_steps: int, batch: int,
                    seq: int, rounds: int, seed: int = 0,
                    require_kernels: bool = True) -> dict:
    """The Pallas round, compared on round 1 with the jnp reference round.

    The reference runs first, on a state it consumes (donated): its
    autodiff keeps more residuals than the fused kernels' custom VJPs, and
    at these widths it needs nearly all of a 16 GB chip. The round's
    state is then rebuilt from the same seed for the Pallas rounds.
    """
    check = Checks()
    size = dict(clients=clients, local_steps=local_steps, batch=batch,
                seq=seq, seed=seed)
    eng, state, data, key = lm_round(cfg, **size)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"[engine_lm] {cfg.name}: n_layers {cfg.n_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} x "
          f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; {n_params} params")
    compiled, secs, routes = compile_round(eng.round_fn, state, *data, key)
    n_kernels = custom_calls(compiled)
    print(f"[engine_lm] round compiled in {secs:.1f} s, {n_kernels} "
          f"tpu_custom_call; ops the pallas backend traced on jnp: "
          f"{routes or 0}")
    _memory_line("round", compiled)
    if require_kernels:
        check(n_kernels > 0, "engine_lm round holds no tpu_custom_call")

    # reference: the same first round traced on the jnp backend
    with kernel_backend("jnp"), jax.default_matmul_precision("highest"):
        ref_eng = lm_round(cfg, **size)[0]
        ref_compiled, ref_secs, _ = compile_round(ref_eng.round_fn, state,
                                                  *data, key, donate=0)
        ref_down = round_downlink(ref_eng, state.params, key)
    n_ref_kernels = custom_calls(ref_compiled)
    print(f"[engine_lm] jnp reference compiled in {ref_secs:.1f} s, "
          f"{n_ref_kernels} tpu_custom_call")
    _memory_line("jnp reference", ref_compiled)
    check(n_ref_kernels == 0, "the jnp reference round holds Mosaic kernels")
    down = round_downlink(eng, state.params, key)
    dl = downlink_check(down, ref_down, eng.link.down_c.fmt)
    del down, ref_down
    print(f"[engine_lm] round 1 downlink vs jnp: {dl}")
    check(dl["code_flip_share"] <= DOWNLINK_FLIP_SHARE
          and dl["value_flip_share"] <= DOWNLINK_FLIP_SHARE
          and dl["adjacent"] and dl["worst_step"] <= 1.0,
          f"downlink differs from the jnp reference beyond adjacent grid "
          f"points on {DOWNLINK_FLIP_SHARE} of elements (elements moved "
          f"by > {DOWNLINK_VALUE_RTOL} relative count): {dl}")
    p0 = host_leaves(state.params)
    ref_state, ref_m = ref_compiled(state, *data, key)
    ref_loss, ref_bytes = float(ref_m["local_loss"]), int(ref_m["wire_bytes"])
    del ref_m, ref_compiled, state
    ref_p = host_leaves(ref_state.params)
    del ref_state
    vjp = kernel_vjp_check(rows=batch * seq, d_in=cfg.d_model,
                           d_out=cfg.d_ff, seed=seed, check=check)

    state = lm_round(cfg, **size)[1]
    losses, wire = [], []
    for r in range(1, rounds + 1):
        k_round = key if r == 1 else jax.random.fold_in(key, r)
        state, m = compiled(state, *data, k_round)
        losses.append(float(m["local_loss"]))
        wire.append(int(m["wire_bytes"]))
        print(f"[engine_lm] round {r}: local_loss {losses[-1]!r}, "
              f"wire_bytes {wire[-1]}")
        if r == 1:
            got = host_leaves(state.params)
            upd = worst_leaves({n: got[n] - p for n, p in ref_p.items()},
                               {n: p - p0[n] for n, p in ref_p.items()})
            del got, ref_p, p0
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    print(f"[engine_lm] round 1 vs jnp: local_loss {losses[0]!r} vs "
          f"{ref_loss!r} (rel {rel!r}, rtol {LOSS_RTOL}); wire_bytes "
          f"{wire[0]} vs {ref_bytes}")
    print(f"[engine_lm] round 1 server params vs jnp (over the jnp "
          f"round's update, relative L2): {upd}")
    check(bool(np.all(np.isfinite(losses))), f"loss not finite: {losses}")
    check(rel <= LOSS_RTOL, f"local_loss off the jnp reference: rel {rel}")
    check(upd["worst"][1] <= UPDATE_REL_L2,
          f"round-1 server params off the jnp reference beyond "
          f"{UPDATE_REL_L2} of its update: {upd}")
    check(wire[0] == ref_bytes == eng.round_bytes(state.params),
          f"wire_bytes {wire[0]} / jnp {ref_bytes} / static "
          f"{eng.round_bytes(state.params)}")
    check.done()
    return dict(losses=losses, wire_bytes=wire, ref_loss=ref_loss,
                downlink=dl, vjp=vjp, update=upd, kernels=n_kernels,
                routes=routes)


# ---------------------------------------------------------------------------
# four chips: the sharded rounds against the single-device round
# ---------------------------------------------------------------------------


def _device_sets(tree) -> dict:
    """{devices an array spans: how many arrays} over a tree's leaves."""
    import collections

    return dict(collections.Counter(
        len(x.sharding.device_set) for x in jax.tree.leaves(tree)))


def four_chip_phase(cfg, *, clients: int, local_steps: int, batch: int,
                    seq: int, seed: int = 0, n_devices: int = 4,
                    require_kernels: bool = True) -> dict:
    """One engine_lm round on a ``clients`` mesh of all devices and on a
    (devices/2) x 2 clients x fsdp mesh, each against the single-device
    round under the same key. The wire is deterministic E4M3 here, as in
    the CPU parity tests: the 2D round quantizes per-device planes of the
    local shards, so its stochastic-rounding counters are not the
    single-device plane's and a rand wire would differ by design."""
    from repro.kernels import dispatch
    from repro.launch.mesh import make_client_mesh, make_fed_mesh
    from repro.sharding.policy import cohort_sharding

    check = Checks()
    size = dict(clients=clients, local_steps=local_steps, batch=batch,
                seq=seq, comm="det", seed=seed)
    # The 2D round's client training is GSPMD-partitioned, and GSPMD cannot
    # partition a Mosaic kernel: on the pallas backend its one region
    # traces on jnp (engine.py, ``dispatch.gspmd_partitioned``), so it is
    # compared with the single-device round traced on jnp. Any other route
    # to jnp is a fault.
    pallas = dispatch.backend() == "pallas"
    gspmd = {"gspmd_region": 1} if pallas else {}
    refs = {}
    for be in ("pallas", "jnp") if pallas else (dispatch.backend(),):
        with kernel_backend(be):
            eng, state, data, key = lm_round(cfg, **size)
            compiled, secs, _ = compile_round(eng.round_fn, state, *data,
                                              key, donate=0)
        new_state, m = compiled(state, *data, key)
        refs[be] = (host_leaves(new_state.params), float(m["local_loss"]),
                    int(m["wire_bytes"]))
        print(f"[four_chips] single-device round ({be}): compiled in "
              f"{secs:.1f} s, {custom_calls(compiled)} tpu_custom_call, "
              f"local_loss {refs[be][1]!r}, wire_bytes {refs[be][2]}")
        del compiled, new_state, m, state
    out = {}
    half = n_devices // 2
    for name, mesh, axis, want_routes, be in (
            (f"clients{n_devices}", make_client_mesh(n_devices), None, {},
             dispatch.backend()),
            (f"fed{half}x2", make_fed_mesh(half, 2), "fsdp", gspmd,
             "jnp" if pallas else dispatch.backend())):
        ref_p, ref_loss, ref_bytes = refs[be]
        m_eng, m_state, _, _ = lm_round(cfg, mesh=mesh, model_axis=axis,
                                        **size)
        m_data = jax.device_put(data, cohort_sharding(mesh, "clients", data))
        compiled, secs, routes = compile_round(m_eng.round_fn, m_state,
                                               *m_data, key)
        n_kernels = custom_calls(compiled)
        new_state, m = compiled(m_state, *m_data, key)
        loss, nbytes = float(m["local_loss"]), int(m["wire_bytes"])
        diff = tree_rel(host_leaves(new_state.params), ref_p)
        loss_rel = abs(loss - ref_loss) / abs(ref_loss)
        sets = dict(params=_device_sets(new_state.params),
                    data=_device_sets(m_data))
        print(f"[four_chips] {name}: compiled in {secs:.1f} s, {n_kernels} "
              f"tpu_custom_call, jnp routes {routes or 0}; local_loss "
              f"{loss!r} (rel {loss_rel!r}); wire_bytes {nbytes}; params "
              f"vs single device {diff}; arrays by device_set size {sets}")
        if require_kernels:
            check(n_kernels > 0, f"{name} round holds no tpu_custom_call")
        check(routes == want_routes, f"{name} traced {routes} on jnp, "
              f"expected {want_routes}")
        check(nbytes == ref_bytes, f"{name} wire_bytes {nbytes} != "
              f"{ref_bytes}")
        check(loss_rel <= LOSS_RTOL, f"{name} local_loss rel {loss_rel}")
        check(diff["rel_l2"] <= PARAMS_REL_L2, f"{name} params {diff}")
        check(set(sets["params"]) == {n_devices},
              f"{name} server params not spread over {n_devices} devices: "
              f"{sets['params']}")
        out[name] = dict(loss=loss, wire_bytes=nbytes, params=diff,
                         device_sets=sets, kernels=n_kernels)
        del new_state, m, compiled
    check.done()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh rounds and the "
                         "single-device round they are compared with")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    for p in (str(ROOT), str(SRC)):
        if p not in sys.path:
            sys.path.insert(0, p)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    from repro.kernels import dispatch

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: "
              f"{devs[0].platform}); refusing to run on it",
              file=sys.stderr)
        return 3
    if dispatch.backend() != "pallas":
        print(f"chip_smoke: kernel backend is {dispatch.backend()!r}, not "
              f"'pallas'", file=sys.stderr)
        return 3
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devs)}",
              file=sys.stderr)
        return 3
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device {device}; kernel backend {dispatch.backend()}; "
          f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        widths = {k: v for k, v in LM_WIDTHS.items() if k != "rounds"}
        four_chip_phase(lm_config(LM_LAYERS), **widths)
    else:
        fedsim_phase(**FEDSIM_SIZES)
        print(f"[engine_lm] n_layers cut 22 -> {LM_LAYERS}: the jnp "
              f"reference round must fit one 16 GB chip")
        engine_lm_phase(lm_config(LM_LAYERS), **LM_WIDTHS)
    print(f"phases done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
