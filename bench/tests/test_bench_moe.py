"""The sparse-expert cell ``granite-moe-3b-a800m.silo`` at a CPU size
(registered in ``tiny.TINY`` by ``bench/conftest.py``, with the readings
its limits rest on).

Here: a run of the cell through the harness reads ``correct``; and the
grouped QAT matmul's counted work is 6 x the expected routed expert
parameters x the round's tokens.
"""
import time

import jax
import pytest

from bench import harness
from bench import run as bench_run
from tiny import TINY, tiny_cell

NAME = "granite-moe-3b-a800m.silo"
BIG_SEED = 2 ** 31 + 4242
TINY_MOE = TINY[NAME]["cfg"]


def test_moe_cell_reads_correct(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    out = bench_run.run_cell(tiny_cell(NAME), BIG_SEED, 0.2, False,
                             jax.devices(), None, time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("cfg", [
    TINY_MOE,
    dict(TINY_MOE, router_experts=40, num_experts_per_tok=8,
         num_local_experts=20),
], ids=["tiny", "published_share"])
def test_gmm_ops_are_six_routed_params_per_token(cfg):
    cell = harness.find_cell(NAME, cfg=cfg)
    c, t = cell.cfg, cell.traffic
    counts = cell.ref.counts(c, t, 0, 0)
    tokens = cell.cohort * t["local_steps"] * t["batch"] * t["seq"]
    routed = (c["num_experts_per_tok"] * c["num_local_experts"]
              / c["router_experts"])
    params = routed * 3 * c["hidden_size"] * c["intermediate_size"] \
        * c["num_hidden_layers"]
    ops = sum(o * calls for o, _, calls in counts["qat_gmm"])
    assert ops == pytest.approx(6 * params * tokens, rel=1e-12)
    assert len(counts["qat_gmm"]) == 9


def test_dense_ops_are_six_attention_and_head_params_per_token():
    cell = harness.find_cell(NAME, cfg=TINY_MOE)
    c, t = cell.cfg, cell.traffic
    counts = cell.ref.counts(c, t, 1000, 10)
    tokens = cell.cohort * t["local_steps"] * t["batch"] * t["seq"]
    D, hd = c["hidden_size"], c["head_dim"]
    attn = D * hd * (2 * c["num_attention_heads"]
                     + 2 * c["num_key_value_heads"])
    params = attn * c["num_hidden_layers"] + D * c["vocab_size"]
    ops = sum(o * calls for o, _, calls in counts["qat_matmul"])
    assert ops == pytest.approx(6 * params * tokens, rel=1e-12)
    assert counts["quant_pack"] == [(0, 1000 * 5 + 10 * 4, 1 + cell.cohort)]
