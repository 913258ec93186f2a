"""Each cell at a size a CPU test run holds: the widths of the LM cut,
the pools, steps and batches small; every other part of the cell as
committed.

The LM cells' limits are set here for this size: a one-layer, 128-wide
model on a few 16-token rows learns and drifts at other rates than the
cell does, so the limits committed for the chip's size say nothing here.
They come from CPU readings at this size (interpreted kernels, 3 seeds;
largest sound reading, smallest control and fault readings):

* ``granite-3-8b.silo``: ``loss_gap`` sound 0.00234, control 0.0464, half
  batch 0.131, altered label 0.0722; ``change_gap.median`` sound 0.00875,
  a state left unchanged 1; ``grad_dir`` (5 seeds) sound 0.231, control
  0.695, half batch 0.678, altered label 1.19.
* ``granite-3-8b.fleet``: ``loss_gap`` sound 0.00189, half batch 0.0198,
  altered label 0.0399; ``loss_gap.r1`` sound 0.000315, control 0.00209;
  ``grad_gap.median`` sound 0.000979, control 0.00461;
  ``change_gap.median`` sound 0.00345, a state left unchanged 1;
  ``grad_dir`` (5 seeds) sound 0.318, control 1.19, altered label 1.29,
  half batch 1.09 on the tests' seed (0.028 on one whose two-row batches
  drew one row twice).
"""
from bench import harness

TINY_LM = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=256,
               num_hidden_layers=1)
TINY = {
    "granite-3-8b.silo": dict(
        cfg=TINY_LM, traffic=dict(rows_per_client=4, seq=16, local_steps=2,
                                  batch=2),
        checks=dict(limits={"loss_gap": 0.015, "change_gap.median": 0.05,
                            "grad_dir": 0.45, "wire_bytes_gap": 0})),
    "granite-3-8b.fleet": dict(
        cfg=TINY_LM, traffic=dict(clients=16, rows_per_client=2, seq=64,
                                  participation=0.125, batch=2),
        checks=dict(limits={"loss_gap": 0.007, "loss_gap.r1": 0.0012,
                            "grad_gap.median": 0.0025,
                            "change_gap.median": 0.03, "grad_dir": 0.6,
                            "wire_bytes_gap": 0})),
}


def tiny_cell(name):
    return harness.find_cell(name, **TINY[name])
