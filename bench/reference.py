"""Plain reference of the federated FP8 round (arXiv 2407.02610, Alg. 1).

Straightforward ``jax.numpy``: float32 master weights, optimizer state
and arithmetic; activations held in the dtype the configuration states
(``Quant``); matmuls at the precision the configuration states (the
caller's ``jax.default_matmul_precision``). It imports nothing of the
program under test and takes nothing it made: the weights and the data come from the benchmark's own
generators, from the run's seed. What it follows of the program is the
published algorithm and the program's documented conventions, which a
sound program keeps:

* the round key splits into (cohort, downlink, uplink, local, server);
  the cohort is the first P of a permutation of the pool;
* the wire quantizes every weight with a learnable clipping value
  (``<name>_qa``) to E4M3 with stochastic rounding; the weights are laid
  out, sorted by their dotted names, in rows of 1024, and each element's
  uniform is the murmur3 finalizer of its global index and the leg key's
  two words; biases, norms and clipping values ride in float32;
* local training is U steps of QAT (deterministic E4M3 fake-quant of
  weights and input activations, LSQ-scaled clip gradients, the
  straight-through estimator), each on a batch drawn with replacement,
  from an optimizer state made fresh every round;
* the server takes the n_k-weighted mean of the decoded uplinks.

The control of the correctness comparison runs this reference with its
activations one precision below the configuration's.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

LANE = 1024
EXP, MANT = 4, 3                      # E4M3
MAX_EXP_CODE = 2 ** EXP - 1
MANT_CONST = float(np.log2(2.0 - 2.0 ** (-MANT)))
ALPHA_FLOOR = 1e-12
QMAX = 2 ** (MANT + 1) - 1            # LSQ's Q_max for E4M3


# ---------------------------------------------------------------------------
# E4M3 quantization
# ---------------------------------------------------------------------------


def exponent_bias(alpha):
    return 2.0 ** EXP - jnp.log2(alpha) + MANT_CONST - 1.0


def lsq(alpha, n_elements: int):
    """LSQ gradient scaling of a clipping value: the value is unchanged,
    its gradient scaled by 1/sqrt(N Q_max)."""
    g = 1.0 / float(np.sqrt(max(n_elements, 1) * QMAX))
    return alpha * g + jax.lax.stop_gradient(alpha * (1.0 - g))


def fake_quant(x, alpha):
    """Deterministic E4M3 fake-quant with the straight-through estimator:
    gradient 1 to ``x`` inside the clip (boundary included), ``sign(x)`` to
    ``alpha`` outside it, plus ``(q - y) s / alpha`` from the exponent bias."""
    alpha = jnp.maximum(alpha, ALPHA_FLOOR)
    xc = jnp.where(jnp.abs(x) <= alpha, x, jnp.sign(x) * alpha)
    b = exponent_bias(alpha)
    p = jax.lax.stop_gradient(
        jnp.maximum(jnp.floor(jnp.log2(jnp.abs(xc)) + b), 1.0))
    s = jnp.exp2(p - b - MANT)
    y = xc / s
    return s * (y + jax.lax.stop_gradient(jnp.round(y) - y))


@dataclasses.dataclass(frozen=True)
class Quant:
    """QAT as a model's forward pass applies it, with activations held in
    ``dtype`` (the configuration's; lower for the control)."""

    dtype: object = jnp.float32

    def cast(self, x):
        """Hold ``x`` at the compute dtype (values, and in the backward
        pass its cotangent)."""
        if jnp.dtype(self.dtype) == jnp.float32:
            return x
        return x.astype(self.dtype).astype(jnp.float32)

    def native(self, f, *xs):
        """``f`` computed on ``xs`` held in the compute dtype, as the
        configuration computes that elementwise op."""
        if jnp.dtype(self.dtype) == jnp.float32:
            return f(*xs)
        return f(*(x.astype(self.dtype) for x in xs)).astype(jnp.float32)

    def act(self, x, beta, n: int | None = None):
        """``n``: the element count LSQ scales by, where the program
        quantizes ``x`` in pieces of that size."""
        return fake_quant(x, lsq(beta, x.size if n is None else n))

    def weight(self, w, alpha):
        return fake_quant(w, lsq(alpha, w.size))


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def _uniform(bits):
    hi = (bits >> jnp.uint32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return (hi * 65536.0 + lo) * (1.0 / 4294967296.0)


def encode(x, a, u):
    """E4M3 codes ``[sign|exp|mant]`` of ``x`` under clip ``a``, rounding
    up with probability equal to the fraction (``u`` uniform)."""
    b = exponent_bias(a)
    xc = jnp.clip(x, -a, a)
    p = jnp.floor(jnp.log2(jnp.abs(xc)) + b)
    p = jnp.minimum(jnp.maximum(p, 1.0), float(MAX_EXP_CODE))
    s = jnp.exp2(p - b - MANT)
    y = xc / s
    fl = jnp.floor(y)
    q = fl + (u < (y - fl)).astype(jnp.float32)
    sign = (q < 0).astype(jnp.int32)
    v = jnp.abs(q).astype(jnp.int32)
    over = v >= 2 ** (MANT + 1)
    at_max = p >= float(MAX_EXP_CODE)
    v = jnp.where(over & at_max, 2 ** (MANT + 1) - 1, jnp.where(over, v // 2, v))
    p = jnp.where(over & ~at_max, p + 1.0, p)
    normal = v >= 2 ** MANT
    f = jnp.where(normal, p.astype(jnp.int32), 0)
    m = jnp.where(normal, v - 2 ** MANT, v)
    return (sign << (EXP + MANT)) | (f << MANT) | m


def decode(code, a):
    b = exponent_bias(a)
    sign = (code >> (EXP + MANT)) & 1
    f = (code >> MANT) & (2 ** EXP - 1)
    m = code & (2 ** MANT - 1)
    normal = f >= 1
    v = jnp.where(normal, m + 2 ** MANT, m)
    p = jnp.where(normal, f, 1).astype(jnp.float32)
    mag = v.astype(jnp.float32) * jnp.exp2(p - b - MANT)
    return jnp.where(sign == 1, -mag, mag)


# ---------------------------------------------------------------------------
# The wire
# ---------------------------------------------------------------------------


def dotted(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


@dataclasses.dataclass(frozen=True)
class Layout:
    """Which leaves cross the wire as E4M3 codes, in wire order, and where
    each starts in the (rows, 1024) plane; every other leaf rides float32."""

    names: tuple          # dotted names of the quantized leaves, sorted
    row0: tuple           # first plane row of each
    n_rows: int           # rows of the plane
    n_q: int              # quantized elements (one code byte each)
    n_other: int          # float32 elements

    def copy_bytes(self) -> int:
        return self.n_q + 4 * self.n_other


def layout(params) -> Layout:
    flat = {dotted(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    names = sorted(n for n, x in flat.items()
                   if x.ndim >= 2 and n + "_qa" in flat
                   and not n.endswith(("_qa", "_qb")))
    row0, r = [], 0
    for n in names:
        row0.append(r)
        r += -(-flat[n].size // LANE)
    n_q = sum(flat[n].size for n in names)
    n_other = sum(x.size for n, x in flat.items() if n not in names)
    return Layout(tuple(names), tuple(row0), r, int(n_q), int(n_other))


def transit(params, key, lay: Layout):
    """What the receiver of one encoded copy decodes (``key``: the leg's
    raw two-word key)."""
    words = jnp.asarray(key, jnp.uint32).reshape(-1)[:2]
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    by_name = {dotted(p): x for p, x in flat}
    out = []
    for path, x in flat:
        name = dotted(path)
        if name not in lay.names:
            out.append(x)
            continue
        r0 = lay.row0[lay.names.index(name)]
        a = jnp.maximum(by_name[name + "_qa"].astype(jnp.float32), ALPHA_FLOOR)
        a = jnp.broadcast_to(a, x.shape).reshape(-1)
        idx = jnp.uint32(r0 * LANE) + jnp.arange(x.size, dtype=jnp.uint32)
        u = _uniform(_fmix32(_fmix32(idx ^ words[0]) ^ words[1]))
        v = decode(encode(x.reshape(-1).astype(jnp.float32), a, u), a)
        out.append(v.reshape(x.shape).astype(x.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Client optimizers (state made fresh every round)
# ---------------------------------------------------------------------------


def adamw(lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0):
    """AdamW with decoupled weight decay on every leaf."""

    def init(params):
        z = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        return (z, z)

    def update(grads, state, params, step):
        mu, nu = state
        eta = jnp.asarray(lr, jnp.float32)
        t = step.astype(jnp.float32) + 1.0
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

        def one(m, v, p):
            st = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if weight_decay:
                st = st + weight_decay * p
            return -eta * st

        return jax.tree.map(one, mu, nu, params), (mu, nu)

    return init, update


def optimizer(spec: dict):
    kind = spec["kind"]
    if kind == "adamw":
        return adamw(spec["lr"], weight_decay=spec.get("weight_decay", 0.0))
    raise ValueError(f"unknown client optimizer {kind!r}")


# ---------------------------------------------------------------------------
# One round
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Round:
    """The round of a cell: ``loss(params, x, y, quant)`` of the model,
    the client optimizer ``(init, update)``, and the cohort, steps and
    batch of its traffic. ``block`` clients train at once; the others wait
    (memory, not arithmetic)."""

    loss: Callable
    opt: tuple
    pool: int
    cohort: int
    local_steps: int
    batch: int
    block: int = 1
    quant: Quant = Quant()
    fault: str | None = None     # one of FAULTS, planted for the readings

    def _batch(self, k, data, labels):
        """A local step's batch, drawn with replacement by its key."""
        k_batch, _ = jax.random.split(k)
        idx = jax.random.randint(k_batch, (self.batch,), 0, data.shape[0])
        return data[idx], labels[idx]

    def local_update(self, params0, data, labels, key):
        init, update = self.opt

        def loss(p, x, y):
            if self.fault == "half_batch":
                x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
            if self.fault == "altered_label":
                y = y.at[0].set(jnp.where(y[0] == 0, 1, 0))
            return self.loss(p, x, y, self.quant)

        def step(carry, k):
            p, st, i = carry
            value, grads = jax.value_and_grad(loss)(
                p, *self._batch(k, data, labels))
            upd, st = update(grads, st, p, i)
            p = jax.tree.map(lambda a, u: (a + u).astype(a.dtype), p, upd)
            return (p, st, i + 1), value

        keys = jax.random.split(key, self.local_steps)
        (p, _, _), losses = jax.lax.scan(
            step, (params0, init(params0), jnp.zeros((), jnp.int32)), keys)
        return p, jnp.mean(losses)

    def __call__(self, params, data, labels, nk, key, lay: Layout):
        """``(new params, mean local loss)`` of one round."""
        k_sel, k_down, k_up, k_loc, _ = jax.random.split(key, 5)
        idx = jax.random.permutation(k_sel, self.pool)[: self.cohort]
        down = transit(params, k_down, lay)
        loc_keys = jax.random.split(k_loc, self.cohort)
        up_keys = jax.random.split(k_up, self.cohort)

        def client(args):
            i, lk, uk = args
            p, loss = self.local_update(down, data[i], labels[i], lk)
            return transit(p, uk, lay), loss

        msgs, losses = jax.lax.map(client, (idx, loc_keys, up_keys),
                                   batch_size=self.block)
        w = nk[idx] / jnp.sum(nk[idx])
        new = jax.tree.map(
            lambda m: jnp.sum(m * w.reshape((-1,) + (1,) * (m.ndim - 1)),
                              axis=0), msgs)
        return new, jnp.mean(losses)

    def first_grad(self, params, data, labels, key, lay: Layout):
        """The gradient the optimizer gets at the first cohort client's
        first local step of the round (the readings' look at round-off)."""
        k_sel, k_down, _, k_loc, _ = jax.random.split(key, 5)
        i = jax.random.permutation(k_sel, self.pool)[0]
        k = jax.random.split(jax.random.split(k_loc, self.cohort)[0],
                             self.local_steps)[0]
        return jax.grad(self.loss)(transit(params, k_down, lay),
                                   *self._batch(k, data[i], labels[i]),
                                   self.quant)


FAULTS = ("half_batch", "altered_label")


def leaf_norms(tree) -> dict:
    return {dotted(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_norms(new, old) -> dict:
    """Per leaf ||new - old||."""
    return leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                   - b.astype(jnp.float32), new, old))

