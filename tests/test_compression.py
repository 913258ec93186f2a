"""Quantized collectives + error feedback (core/compression.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import compression
from repro.core.qat import alpha_like


def _params(seed=0):
    k = jax.random.PRNGKey(seed)
    w = jax.random.normal(k, (16, 32))
    return {"w": w, "w_qa": alpha_like(w), "b": jnp.zeros((32,))}


def test_ef_biased_compression_residual_shrinks_error():
    """EF21: accumulated biased-quantizer error stays bounded and the
    compressed stream's running mean converges to the true signal."""
    params = _params()
    state = compression.ef_init(params)
    sent_sum = jax.tree.map(jnp.zeros_like, params)
    n = 30
    for i in range(n):
        q, state = compression.ef_compress(
            params, state, jax.random.PRNGKey(i), mode="det"
        )
        sent_sum = jax.tree.map(lambda a, b: a + b, sent_sum, q)
    mean_sent = jax.tree.map(lambda s: s / n, sent_sum)
    # without EF, det quantization has a fixed bias; with EF the time-mean
    # of transmitted values approaches the source
    err = float(jnp.max(jnp.abs(mean_sent["w"] - params["w"])))
    q_plain = jax.tree.map(jnp.asarray, params)
    from repro.core import fp8
    det_err = float(jnp.max(jnp.abs(
        fp8.quantize_det(params["w"], params["w_qa"]) - params["w"]
    )))
    assert err < det_err * 0.6, (err, det_err)


def test_quantized_allreduce_mean_unbiased():
    """Mean over the federated axis of Q_rand'd replicas ~ true mean."""
    n_dev = len(jax.devices())
    if n_dev < 1:
        pytest.skip("no devices")
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()

    def body(p, key):
        return compression.quantized_allreduce_mean(p, key, ("pod",))

    fn = shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   check_vma=False)
    # average many independent quantization draws: should converge to w
    acc = np.zeros(params["w"].shape, np.float64)
    n = 200
    for i in range(n):
        out = jax.jit(fn)(params, jax.random.PRNGKey(i))
        acc += np.asarray(out["w"])
    bias = np.abs(acc / n - np.asarray(params["w"])).max()
    assert bias < 2e-2, bias


def test_sync_alphas_is_pmax():
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    params = _params()

    def body(p):
        return compression.sync_alphas(p, ("pod",))

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                            check_vma=False))(params)
    np.testing.assert_allclose(np.asarray(out["w_qa"]),
                               np.asarray(params["w_qa"]))
