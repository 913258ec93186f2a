"""The comparison that decides ``correct`` fails what it must, in every
cell, at a size a test run holds.

* The control: the plain reference, put in the program's place with its
  activations held one precision below the configuration's, fails at least
  one of the cell's limits.
* The harness, its look for a chip skipped, drives a run whose timed path
  is broken underneath and reports ``correct`` false: a round that returns
  its state unchanged, a local step that takes the mean loss over half of
  its batch, and a label altered where the batch is produced. A sound run
  of the same size reads ``correct`` true, so each fault is what flips it.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import run as bench_run
from tiny import tiny_cell

BIG_SEED = 2 ** 31 + 777
CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]]


def _unchanged(step):
    def broken(state, *args):
        _, m = step(jax.tree.map(jnp.copy, state), *args)
        return state, m
    return broken


def _half_batch(loss):
    def broken(p, x, y, *rest):
        h = x.shape[0] // 2
        return loss(p, x[:h], y[:h], *rest)
    return broken


def _altered_label(loss):
    def broken(p, x, y, *rest):
        return loss(p, x, y.at[0].set(jnp.where(y[0] == 0, 1, 0)), *rest)
    return broken


FAULTS = {
    None: {},
    "unchanged": dict(round_wrap=_unchanged),
    "half_batch": dict(loss_wrap=_half_batch),
    "altered_label": dict(loss_wrap=_altered_label),
}


@pytest.mark.parametrize("fault", list(FAULTS), ids=str)
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_reads_incorrect(name, fault, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    out = bench_run.run_cell(tiny_cell(name), BIG_SEED, 0.2, False,
                             jax.devices(), None, time.perf_counter(),
                             **FAULTS[fault])
    assert out["correct"] is (fault is None), out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_limit(name):
    cell = tiny_cell(name)
    inp = harness.make_inputs(cell, BIG_SEED)
    n = cell.checks["reference_rounds"]
    ref = harness.reference_rounds(cell, inp, n)
    ctl = harness.reference_rounds(
        cell, inp, n, dtype=harness.CONTROL[cell.cfg["activation_dtype"]])
    nums = harness.compare(ctl, ref, n)
    limits = cell.checks["limits"]
    assert any(nums[k] > v for k, v in limits.items() if k in nums), nums
