"""Registers the tiny CPU size of each cell that ``tests/tiny.py`` does
not list, before any benchmark test file is collected, so that every
file sees every cell whether a run selects it alone or with the others.

``granite-moe-3b-a800m.silo``: limits for its size come from CPU readings
at it (interpreted kernels, seeds 2**31 + 1..5; largest sound reading,
smallest control and fault readings): ``loss_gap`` sound 0.00718, control
0.0597, half batch 0.0935, altered label 0.0698; ``change_gap.median``
sound 0.016, a state left unchanged 1. (``grad_dir`` reads 0.45 sound and
0.91 control here, too near to hold a limit.)
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parent / "src"), str(HERE.parent), str(HERE / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from tiny import TINY  # noqa: E402

TINY.setdefault("granite-moe-3b-a800m.silo", dict(
    cfg=dict(hidden_size=128, intermediate_size=128, num_attention_heads=2,
             num_key_value_heads=1, head_dim=64, vocab_size=256,
             num_hidden_layers=2, router_experts=8, num_experts_per_tok=2,
             num_local_experts=4),
    traffic=dict(rows_per_client=4, seq=16, local_steps=2, batch=2),
    checks=dict(limits={"loss_gap": 0.02, "change_gap.median": 0.03,
                        "wire_bytes_gap": 0})))
