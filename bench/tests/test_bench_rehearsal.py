"""CPU rehearsal of the benchmark: every cell builds from a seed at a tiny
size and runs a few rounds through the harness's loop with the Pallas
kernels interpreted; the same seed gives the same traffic; and
``bench/run.py`` refuses to run without a TPU, on an unknown device kind,
with a kernel backend other than Pallas, and outside a checkout of the
program."""
import json
import os
import shutil
import subprocess
import sys
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench import run as bench_run
from tiny import TINY, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]]
BIG_SEED = 2 ** 31 + 12345


def test_every_cell_has_a_tiny_size():
    assert sorted(TINY) == sorted(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_traffic(name):
    cell = tiny_cell(name)
    a, b = harness.make_inputs(cell, BIG_SEED), harness.make_inputs(cell, BIG_SEED)
    c = harness.make_inputs(cell, BIG_SEED + 1)
    for x, y in ((a.data, b.data), (a.labels, b.labels), (a.keys, b.keys)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert jax.tree.all(jax.tree.map(np.array_equal, a.params(), b.params()))
    assert not np.array_equal(np.asarray(a.data), np.asarray(c.data))
    assert a.data.shape[0] == cell.traffic["clients"]


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_runs_the_cell(name, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    cell = tiny_cell(name)
    out = bench_run.run_cell(cell, BIG_SEED, 0.5, False, jax.devices(), None,
                             time.perf_counter())
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.checks["limits"])
    wanted = {m["name"] for m in harness.benchmark_spec()["end_to_end"]
              if name in m.get("workloads", [name])}
    assert set(out["metrics"]) == wanted
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["wire_bytes_gap"]["value"] == 0
    json.dumps(out)


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("REPRO_KERNEL_BACKEND", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    return p.returncode, p.stdout


def test_run_refuses_without_a_tpu():
    rc, out = _run(ROOT, {})
    assert rc != 0 and out.strip() == ""


def test_run_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out = _run(tmp_path, {})
    assert rc != 0 and out.strip() == ""


def _fake_jax(kind):
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev])


def test_check_device_refuses_an_unknown_kind(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    with pytest.raises(bench_run.Refused, match="peaks table"):
        bench_run.check_device(_fake_jax("TPU v9 imaginary"), 1)
    devs, peak = bench_run.check_device(_fake_jax("TPU v5 lite"), 1)
    assert peak.bf16_flops == 197e12


def test_check_device_refuses_a_backend_other_than_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp")
    with pytest.raises(bench_run.Refused, match="pallas"):
        bench_run.check_device(_fake_jax("TPU v5 lite"), 1)


def test_check_device_refuses_too_few_chips(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    with pytest.raises(bench_run.Refused, match="chips"):
        bench_run.check_device(_fake_jax("TPU v5 lite"), 4)
