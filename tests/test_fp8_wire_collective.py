"""FP8-wire federated collective: correctness + actual u8 payload on the wire.

The collective uses the flat-buffer codec (core/wire.py): ONE uint8 payload
per silo for the whole model, ONE all-gather moving u8 — not a per-tensor
collective, and never f32 weights.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import compression, wire
from repro.core.qat import alpha_like


def _params():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    return {"w": w, "w_qa": alpha_like(w), "b": jnp.ones((64,))}


def test_fp8_wire_mean_unbiased_single_device():
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()

    fn = jax.jit(shard_map(
        lambda p, k: compression.fp8_wire_allreduce_mean(p, k, ("pod",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    ))
    acc = np.zeros(params["w"].shape, np.float64)
    n = 400
    for i in range(n):
        acc += np.asarray(fn(params, jax.random.PRNGKey(i))["w"])
    # Monte-Carlo error of the element mean is ~ grid_step / (2 sqrt(n));
    # the max over 2048 elements sits a few sigma out, hence the headroom.
    bias = np.abs(acc / n - np.asarray(params["w"])).max()
    assert bias < 2.5e-2, bias
    out = fn(params, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out["b"]), np.asarray(params["b"]))


def test_fp8_wire_collective_moves_uint8():
    """The lowered collective must carry u8, not f32 — the 4x is real."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()
    fn = shard_map(
        lambda p, k: compression.fp8_wire_allreduce_mean(p, k, ("pod",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )
    txt = jax.jit(fn).lower(params, jax.random.PRNGKey(0)).compile().as_text()
    # collective *op* lines only (consumers referencing the gather as an
    # operand don't count)
    gathers = [ln for ln in txt.splitlines()
               if re.search(r"=\s*\S*\s*all-gather(-start)?\(", ln)]
    assert gathers, "expected an all-gather in the lowering"
    u8 = [ln for ln in gathers if re.search(r"=\s*u8\[", ln)]
    f32 = [ln for ln in gathers if re.search(r"=\s*f32\[", ln)]
    assert u8, "expected a u8 all-gather on the wire"
    assert not f32, f"weights must not cross the wire in f32: {f32}"


def test_fp8_wire_allgather_stacks_silo_trees():
    """The gather variant must return stacked per-silo trees whose mean is
    the allreduce_mean result — same wire, aggregator-shaped output."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()
    key = jax.random.PRNGKey(4)
    gathered = jax.jit(shard_map(
        lambda p, k: compression.fp8_wire_allgather(p, k, ("pod",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    ))(params, key)
    reduced = jax.jit(shard_map(
        lambda p, k: compression.fp8_wire_allreduce_mean(p, k, ("pod",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    ))(params, key)
    assert gathered["w"].shape == (1,) + params["w"].shape
    np.testing.assert_allclose(
        np.asarray(jnp.mean(gathered["w"], axis=0)),
        np.asarray(reduced["w"]), rtol=0, atol=1e-6,
    )


def test_make_comm_round_with_stateful_aggregator():
    """make_comm_round(aggregator=FedAvgM) must thread server momentum
    through the round boundary: state nonzero after one boundary and the
    collective still moves u8."""
    from repro.core.engine import FedAvgM
    from repro.launch.steps import comm_round_state, make_comm_round
    from repro.core.qat import QATConfig

    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()
    agg = FedAvgM(lr=1.0, momentum=0.9)
    comm_state = comm_round_state(agg, params)
    fn = make_comm_round(mesh, P(), ("pod",), QATConfig(),
                         mode="rand", wire="fp8", aggregator=agg,
                         state_specs=P())
    new_params, new_state = jax.jit(fn)(params, comm_state,
                                        jax.random.PRNGKey(0))
    assert jax.tree.structure(new_params) == jax.tree.structure(params)
    assert any(bool(jnp.any(x != 0))
               for x in jax.tree.leaves(new_state["opt"])), \
        "server momentum stayed zero across the boundary"
    # the threaded baseline must be the NEW global model (next round's
    # pseudo-gradient anchor), identical on every silo
    np.testing.assert_array_equal(np.asarray(new_state["prev"]["w"]),
                                  np.asarray(new_params["w"]))
    txt = jax.jit(fn).lower(params, comm_state,
                            jax.random.PRNGKey(0)).compile().as_text()
    u8_gathers = [ln for ln in txt.splitlines()
                  if re.search(r"=\s*u8\[", ln)
                  and re.search(r"all-gather(-start)?\(", ln)]
    assert u8_gathers, "aggregator path lost the u8 wire"


def test_make_comm_round_partial_quorum():
    """make_comm_round(partial=True): the boundary takes a replicated
    alive mask — all-alive matches the non-partial round bitwise, an
    all-dead (below-quorum) round passes params AND aggregator state
    through unchanged, and partial=True without an aggregator raises."""
    from repro.core.engine import FedAvgM
    from repro.core.qat import QATConfig
    from repro.launch.steps import comm_round_state, make_comm_round

    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()

    def build(**kw):
        agg = FedAvgM(lr=1.0, momentum=0.9)
        return make_comm_round(mesh, P(), ("pod",), QATConfig(),
                               mode="rand", wire="fp8", aggregator=agg,
                               state_specs=P(), **kw), \
            comm_round_state(agg, params)

    key = jax.random.PRNGKey(0)
    fn_ref, st_ref = build()
    ref_params, _ = jax.jit(fn_ref)(params, st_ref, key)

    fn, st = build(partial=True, min_quorum=1)
    alive = jnp.ones((1,), bool)
    new_params, new_state = jax.jit(fn)(params, st, key, alive)
    np.testing.assert_array_equal(np.asarray(new_params["w"]),
                                  np.asarray(ref_params["w"]),
                                  err_msg="all-alive partial != full round")

    dead_params, dead_state = jax.jit(fn)(params, st, key,
                                          jnp.zeros((1,), bool))
    np.testing.assert_array_equal(np.asarray(dead_params["w"]),
                                  np.asarray(st["prev"]["w"]),
                                  err_msg="below-quorum round moved params")
    assert all(not bool(jnp.any(x != 0))
               for x in jax.tree.leaves(dead_state["opt"])), \
        "below-quorum round moved aggregator state"
    np.testing.assert_array_equal(np.asarray(dead_state["prev"]["w"]),
                                  np.asarray(st["prev"]["w"]))

    with pytest.raises(ValueError, match="partial"):
        make_comm_round(mesh, P(), ("pod",), QATConfig(), mode="rand",
                        wire="fp8", partial=True)


def test_fp8_wire_single_collective_for_whole_model():
    """Flat codec collapses O(n_tensors) collectives into exactly one."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    w2 = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    params = _params()
    params["w2"], params["w2_qa"] = w2, alpha_like(w2)
    fn = shard_map(
        lambda p, k: compression.fp8_wire_allreduce_mean(p, k, ("pod",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )
    txt = jax.jit(fn).lower(params, jax.random.PRNGKey(0)).compile().as_text()
    u8_gathers = [ln for ln in txt.splitlines()
                  if re.search(r"=\s*u8\[", ln)
                  and re.search(r"all-gather(-start)?\(", ln)]
    assert len(u8_gathers) == 1, u8_gathers
    spec = wire.make_wire_spec(params)
    assert spec.total == 32 * 64 + 16 * 16
    # the gathered buffer is exactly 1 byte per quantized element
    assert any(f"u8[1,{spec.total}]" in ln for ln in u8_gathers), u8_gathers
