"""What every run of a cell shares: the cell's files found by name, the
set-up from the seed, the program's first rounds, the plain reference's
rounds, and the comparison that decides ``correct``.

A cell ``<config>.<traffic>`` of ``BENCHMARK.json`` is made of
``bench/configs/<config>.json`` (its sizes), ``bench/configs/<config>.py``
(the system under test, built through the program), beside it
``bench/configs/<config>.ref.py`` (the benchmark's weights and the plain
reference model), ``bench/traffic/<cell>.json`` (the traffic mix) and
``bench/checks/<cell>.json`` (the reference's blocking and the limits of
the comparison, with the readings they were set from).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import statistics
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator, reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
N_KEYS = 1 << 16          # round keys drawn per run; a window ends before
SETUP_ROUNDS = 3          # rounds the comparison follows (set-up drives them)
# A leaf whose reference pseudo-gradient is under this share of the median
# leaf's moves by round-off alone (a clipping value that no forward pass
# reads, or whose gradient is nought next to AdamW's eps): it is left out
# of the norm comparisons.
NOUGHT = 1e-3


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    checks: dict
    sut: object           # bench/configs/<config>.py
    ref: object           # bench/configs/<config>.ref.py

    @property
    def cohort(self) -> int:
        t = self.traffic
        return max(1, int(round(t["clients"] * t["participation"])))


def find_cell(name: str, **overrides) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``. ``overrides`` replaces
    entries of its ``cfg``, ``traffic`` or ``checks`` (tests shrink a cell
    with them)."""
    entry = next((w for w in benchmark_spec()["workloads"]
                  if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return load_cell(name, entry["config"], entry["chips"], **overrides)


def load_cell(name: str, conf: str, chips: int = 1, **overrides) -> Cell:
    """The cell ``name`` of configuration ``conf`` from its files alone."""
    parts = dict(
        cfg=load_json(BENCH / "configs" / f"{conf}.json"),
        traffic=load_json(BENCH / "traffic" / f"{name}.json"),
        checks=load_json(BENCH / "checks" / f"{name}.json"))
    for k, v in overrides.items():
        parts[k] = {**parts[k], **v}
    return Cell(name=name, chips=chips, **parts,
                sut=load_module(BENCH / "configs" / f"{conf}.py"),
                ref=load_module(BENCH / "configs" / f"{conf}.ref.py"))


def seed_key(seed: int):
    """A raw two-word PRNG key holding all 64 bits of ``seed``."""
    s = int(seed) & ((1 << 64) - 1)
    return jnp.asarray([s >> 32, s & 0xFFFFFFFF], jnp.uint32)


@dataclasses.dataclass
class Inputs:
    """A cell's weights maker, data and round keys for one seed."""

    init: object          # jitted: key -> weights (the same on every call)
    k_params: object
    data: object
    labels: object
    nk: object
    keys: np.ndarray      # (N_KEYS, 2) uint32 round keys, on the host
    layout: reference.Layout

    def params(self):
        return self.init(self.k_params)


def make_inputs(cell: Cell, seed: int) -> Inputs:
    k_params, k_data, k_rounds = jax.random.split(seed_key(seed), 3)
    init = jax.jit(functools.partial(cell.ref.init_params, cfg=cell.cfg))
    params = init(k_params)
    data, labels, nk = generator.generate(k_data, cell.traffic, cell.cfg)
    keys = np.asarray(jax.random.split(k_rounds, N_KEYS))
    return Inputs(init, k_params, data, labels, nk, keys,
                  reference.layout(params))


def compile_round(engine, state, inp: Inputs):
    """The program's round, compiled once with its state donated; and the
    ops its Pallas backend traced on jnp (``dispatch.JNP_ROUTES``)."""
    from repro.kernels import dispatch

    before = dict(dispatch.JNP_ROUTES)
    step = jax.jit(engine.round_fn, donate_argnums=0).lower(
        state, inp.data, inp.labels, inp.nk, inp.keys[0]).compile()
    routes = {k: n - before.get(k, 0) for k, n in dispatch.JNP_ROUTES.items()
              if n != before.get(k, 0)}
    return step, routes


@dataclasses.dataclass
class Readings:
    losses: list          # mean local loss of each round
    first: dict           # per leaf ||p1 - p0||: the server's first pseudo-gradient
    change: dict          # per leaf ||pN - p0|| after the last round read
    p1: dict              # per leaf the server's params after round 1, on the host
    wire: list = dataclasses.field(default_factory=list)


_norms = jax.jit(reference.change_norms)


def _on_host(params) -> dict:
    """Per dotted leaf name the leaf as a host array."""
    return {reference.dotted(p): np.asarray(x) for p, x in
            jax.tree_util.tree_flatten_with_path(jax.device_get(params))[0]}


def _change(params, inp: Inputs) -> dict:
    """Per leaf ||params - p0||, on the host."""
    return {k: float(v) for k, v in _norms(params, inp.params()).items()}


def program_rounds(step, state, inp: Inputs, read_at: int,
                   n: int = SETUP_ROUNDS):
    """Drive the compiled round through rounds 1..n from the seed's
    weights; return the state (for the window) and the readings, the
    change read after round ``read_at``."""
    losses, wire, change = [], [], {}
    for r in range(1, n + 1):
        state, m = step(state, inp.data, inp.labels, inp.nk, inp.keys[r - 1])
        losses.append(float(m["local_loss"]))
        wire.append(int(m["wire_bytes"]))
        if r == 1:
            p1 = _on_host(state.params)
        if r in (1, read_at):
            change[r] = _change(state.params, inp)
    return state, Readings(losses, change[1], change[read_at], p1, wire)


# The control: the precision one step below the one a configuration states
# for its activations and their gradients.
CONTROL = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def reference_round(cell: Cell, dtype: str | None = None,
                    fault: str | None = None) -> reference.Round:
    """The plain reference's round of ``cell``, its activations in the
    configuration's ``activation_dtype``; ``dtype`` (``CONTROL``'s) lowers
    them for the control."""
    t = cell.traffic
    return reference.Round(
        loss=cell.ref.make_loss(cell.cfg),
        opt=reference.optimizer(cell.cfg["client_optimizer"]),
        pool=t["clients"], cohort=cell.cohort, local_steps=t["local_steps"],
        batch=t["batch"], block=cell.checks["reference_block"],
        quant=reference.Quant(jnp.dtype(dtype
                                        or cell.cfg["activation_dtype"])),
        fault=fault)


def reference_rounds(cell: Cell, inp: Inputs, n: int,
                     dtype: str | None = None, fault: str | None = None,
                     precision: str | None = None) -> Readings:
    """:func:`reference_round` through rounds 1..n, its matmuls at the
    configuration's ``matmul_precision`` or at ``precision`` (the
    readings' look at round-off)."""
    rnd = jax.jit(functools.partial(reference_round(cell, dtype, fault),
                                    lay=inp.layout))
    with jax.default_matmul_precision(precision
                                      or cell.cfg["matmul_precision"]):
        p, losses, change = inp.params(), [], {}
        for r in range(1, n + 1):
            p, loss = rnd(p, inp.data, inp.labels, inp.nk, inp.keys[r - 1])
            losses.append(float(loss))
            if r == 1:
                p1 = _on_host(p)
            if r in (1, n):
                change[r] = _change(p, inp)
    return Readings(losses, change[1], change[n], p1)


def round_bytes(cell: Cell, lay: reference.Layout) -> int:
    """Wire bytes of one round by the published accounting: every cohort
    client downloads and uploads one encoded copy."""
    return 2 * cell.cohort * lay.copy_bytes()


def _gaps(got: dict, ref: dict, counted: list) -> dict:
    """Per counted leaf: the gap between the two norms over the
    reference's norm of that leaf or of the median leaf, the larger."""
    med = statistics.median(ref[k] for k in counted)
    return {k: abs(got[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in counted}


def _dirs(got: dict, ref: dict, ref_norm: dict, counted: list) -> dict:
    """Per counted leaf: the norm of the difference of the two sides'
    params over the reference's norm of that leaf's change or of the
    median leaf's, the larger."""
    med = statistics.median(ref_norm[k] for k in counted)
    return {k: float(np.linalg.norm(got[k] - ref[k]))
            / max(ref_norm[k], med, 1e-30) for k in counted}


def compare(got: Readings, ref: Readings, n: int) -> dict:
    """The numbers a cell's ``checks`` may hold limits for.

    ``loss_gap``: largest relative gap of a round's mean local loss over
    rounds 1..n; ``loss_gap.r1``: that of round 1. ``grad_gap``: the
    worst leaf's gap (:func:`_gaps`) between the norms of the server's
    first pseudo-gradient p1 - p0; ``change_gap``: the same for the change
    pn - p0. ``grad_dir``: the worst leaf's norm of the difference of the
    two sides' p1 (:func:`_dirs`), which sees a step that points elsewhere
    with the same norm. These take the worst of the leaves of more than one
    element: a one-element leaf (a clipping value) carries the sign of one
    sum a step, a sum that round-off alone flips (PERF.md). ``*.median``: the
    median leaf's gap over all counted leaves. Leaves the reference moves
    by round-off alone (``NOUGHT``) are not counted."""
    med = statistics.median(ref.first.values())
    counted = [k for k, v in ref.first.items() if v >= NOUGHT * med]
    tensors = [k for k in counted if ref.p1[k].size > 1]
    gaps = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
            for a, b in zip(got.losses[:n], ref.losses[:n])]
    per_leaf = {name: {k: math.inf if math.isnan(v) else v
                       for k, v in g.items()} for name, g in (
        ("grad_gap", _gaps(got.first, ref.first, counted)),
        ("change_gap", _gaps(got.change, ref.change, counted)),
        ("grad_dir", _dirs(got.p1, ref.p1, ref.first, counted)))}
    leaves = {name: max(tensors, key=g.get) for name, g in per_leaf.items()}
    out = {"loss_gap": max(gaps), "loss_gap.r1": gaps[0],
           **{name: per_leaf[name][k] for name, k in leaves.items()},
           "grad_gap.median": statistics.median(
               per_leaf["grad_gap"].values()),
           "change_gap.median": statistics.median(
               per_leaf["change_gap"].values())}
    out.update(_leaves=leaves, _per_leaf=per_leaf,
               _excluded=sorted(set(ref.first) - set(counted)))
    return out
