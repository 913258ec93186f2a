"""The system under test for ``granite-moe-3b-a800m``: ``models/transformer.py``
with its expert-share MoE layer (``models/moe.py``) at Granite-3.0-3B-A800M's
widths, holding this chip's share of the experts, through
``core/engine.py::RoundEngine`` with the full-cohort ``VmapExecutor``, as
``granite-3-8b.py`` builds it: AdamW clients, E4M3 QAT (the fused QAT
matmul for attention and the head, the grouped QAT matmul for the experts,
on a Pallas backend), stochastic E4M3 wire on both legs, mean
aggregation."""


def model_config(cfg):
    from repro import configs
    from repro.configs.base import MoEConfig

    moe = MoEConfig(n_experts=cfg["router_experts"],
                    top_k=cfg["num_experts_per_tok"],
                    experts_here=cfg["num_local_experts"],
                    first_expert=cfg["first_local_expert"])
    return configs.get("granite_moe_3b_a800m").replace(
        n_layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], ce_chunks=cfg["ce_chunks"], moe=moe)


def build(cfg, traffic, params, data, labels, nk, loss_wrap=None):
    """``(engine, state)`` of the cell; ``loss_wrap`` wraps the model's
    loss (tests plant faults with it)."""
    from repro import optim
    from repro.core.engine import FedConfig, RoundEngine
    from repro.core.qat import QATConfig
    from repro.models.registry import get_model

    model = get_model(model_config(cfg))

    def loss(params, xb, yb, qat_cfg, key):
        return model.train_loss(params, {"tokens": xb, "labels": yb}, qat_cfg)

    if loss_wrap is not None:
        loss = loss_wrap(loss)
    o = cfg["client_optimizer"]
    fed = FedConfig(n_clients=traffic["clients"],
                    participation=traffic["participation"],
                    local_steps=traffic["local_steps"],
                    batch_size=traffic["batch"], comm_mode="rand",
                    qat=QATConfig())
    eng = RoundEngine(loss, optim.adamw(o["lr"], weight_decay=o["weight_decay"]),
                      fed)
    return eng, eng.init(params)
