"""Operation and byte counts against hand counts at tiny shapes, and the
model FLOPs of the LM configuration against the HLO cost model of the
program's own compiled computation (``launch/hlo_cost.py``), on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, peaks

TINY_LM = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=256,
               num_hidden_layers=2)


def test_qat_matmul_costs_hand_count():
    c = flops.qat_matmul_costs(2, 3, 5)
    assert c["fwd"] == (60, 2 * 3 * 2 + 3 * 5 * 4 + 2 * 5 * 2)
    assert c["dx"] == (60, 2 * 5 * 2 + 3 * 5 * 4 + 2 * 3 * 2)
    assert c["dw"] == (60, 2 * 3 * 2 + 2 * 5 * 2 + 3 * 5 * 4)


def test_quant_pack_cost_hand_count():
    assert flops.quant_pack_cost(2048, 2) == (0, 2048 * 5 + 8)


def test_least_time_takes_the_binding_bound():
    p = peaks.peak("TPU v5 lite")
    assert flops.least_time(197e12, 0, p) == pytest.approx(1.0)
    assert flops.least_time(0, 819e9, p) == pytest.approx(1.0)
    assert flops.least_time(197e12, 2 * 819e9, p) == pytest.approx(2.0)


def test_peaks_refuse_an_unknown_kind():
    assert peaks.peak("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")


def test_lm_projections_hand_count():
    proj = flops.lm_projections(8, 2, 1, 4, 16, 32, 3)
    n = sum(k * m * c for _, k, m, c in proj)
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert n == 3 * per_layer + 8 * 32
    assert flops.lm_flops_per_token(proj, 3, 2, 4, 10) == \
        6 * n + 12 * 3 * 2 * 4 * 10


def test_lm_round_flops_match_hlo_cost():
    """6 N tokens + 12 L H hd S tokens equals the dot FLOPs of the
    program's forward and backward pass (remat off, so nothing is counted
    twice), as the HLO cost model counts them."""
    from repro.core.qat import QATConfig
    from repro.launch import hlo_cost
    from repro.models.registry import get_model

    B, S = 2, 32
    cell = harness.find_cell("granite-3-8b.silo", cfg=TINY_LM,
                             traffic=dict(seq=S, batch=B))
    model = get_model(cell.sut.model_config(cell.cfg).replace(remat=False))
    params = jax.eval_shape(lambda k: cell.ref.init_params(k, cell.cfg),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    grad = jax.jit(jax.grad(lambda p, x, y: model.train_loss(
        p, {"tokens": x, "labels": y}, QATConfig())))
    counted = hlo_cost.analyze(grad.lower(params, tok, tok).compile()
                               .as_text())["flops"]
    one_step = dict(cell.traffic, clients=1, participation=1.0,
                    local_steps=1)
    ours = cell.ref.counts(cell.cfg, one_step, 0, 0)["model_flops"]
    assert counted == pytest.approx(ours, rel=1e-6)
