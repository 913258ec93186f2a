"""The one traffic generator: builds a cell's client data on the device,
in one jitted call, from the parameters of its ``bench/traffic/<cell>.json``
and the run's seed. The same seed gives the same data.

One kind of client data, named by the file's ``kind``:

* ``markov_tokens``: token streams of a sparse first-order Markov chain
  (the generator of ``data/synthetic.py::synthetic_lm_tokens``): a shared
  transition table, ``noise`` of uniformly drawn tokens, and each client's
  vocabulary rotated by its own offset so that clients differ. A row holds
  ``seq + 1`` tokens; inputs are its first ``seq``, labels its last.

Returns ``(data, labels, nk)`` with a leading client axis, as the engine's
round takes them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _markov_tokens(key, t: dict, vocab: int):
    k_tab, k_rot, k_s0, k_steps = jax.random.split(key, 4)
    K, rows, seq = t["clients"], t["rows_per_client"], t["seq"]
    branch = max(2, vocab // t["branch_divisor"])
    n_ctx = min(vocab, t["contexts"])
    nexts = jax.random.randint(k_tab, (n_ctx, branch), 0, vocab)
    rot = jax.random.randint(k_rot, (K, 1), 0, vocab)
    s0 = jax.random.randint(k_s0, (K * rows,), 0, n_ctx)

    def step(state, k):
        k_u, k_b, k_t = jax.random.split(k, 3)
        noisy = jax.random.uniform(k_u, state.shape) < t["noise"]
        pick = jax.random.randint(k_b, state.shape, 0, branch)
        tok = jnp.where(noisy, jax.random.randint(k_t, state.shape, 0, vocab),
                        nexts[state, pick])
        return tok % n_ctx, tok

    _, toks = jax.lax.scan(step, s0, jax.random.split(k_steps, seq + 1))
    toks = toks.T.reshape(K, rows, seq + 1)
    toks = ((toks + rot[:, :, None]) % vocab).astype(jnp.int32)
    return toks[..., :-1], toks[..., 1:]


def generate(key, traffic: dict, model: dict):
    """``(data, labels, nk)`` on the device for ``traffic`` against the
    configuration ``model`` (its vocabulary)."""
    kind = traffic["kind"]
    if kind == "markov_tokens":
        make = functools.partial(_markov_tokens, t=traffic,
                                 vocab=model["vocab_size"])
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")

    @jax.jit
    def build(k):
        data, labels = make(k)
        nk = jnp.full((traffic["clients"],), float(data.shape[1]),
                      jnp.float32)
        return data, labels, nk

    return build(key)
