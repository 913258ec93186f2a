"""chip_smoke.py's phases at tiny size on the CPU, and its refusals.

The phases run with the Pallas kernel bodies interpreted
(``REPRO_KERNEL_BACKEND=interpret``) so their control flow, checks and
reference comparison are exercised without a chip; the four-chip phase
runs in a subprocess on four virtual CPU devices. The script itself must
exit non-zero, printing no result line, when JAX finds no TPU and when it
stands alone without the repository.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro import configs  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def _tiny_lm():
    return configs.reduced(configs.get("tinyllama_1_1b")).replace(n_layers=1)


@pytest.fixture
def interpret_backend(monkeypatch):
    monkeypatch.setenv(dispatch._ENV, "interpret")


def test_fedsim_phase_tiny(interpret_backend):
    out = chip_smoke.fedsim_phase(k=4, c=0.5, rounds=3, local_steps=2,
                                  batch=8, n_train=128, n_test=64,
                                  require_kernels=False)
    assert out["losses"][-1] < out["losses"][0]
    # the FP8 wire moves ~1/4 of the fp32 bytes (codes + per-leaf alphas)
    assert 3.5 < out["fp32_bytes"] / out["bytes"] < 4.0


def test_engine_lm_phase_tiny(interpret_backend):
    out = chip_smoke.engine_lm_phase(_tiny_lm(), clients=2, local_steps=2,
                                     batch=2, seq=16, rounds=2,
                                     require_kernels=False)
    # same counter bits, same grid: bit-identical broadcast on the CPU
    assert out["downlink"]["code_flip_share"] == 0.0
    assert out["downlink"]["worst_step"] == 0.0
    assert out["wire_bytes"][0] == out["wire_bytes"][1]
    print(out["update"])


@pytest.mark.parametrize("case,flips", [
    ("scale_ulps", 0), ("adjacent_code", 1), ("scale_fault", 512)])
def test_downlink_check_separates_ulps_from_grid_flips(case, flips):
    """ULP-level scale differences pass the value comparison; a moved grid
    point or a wrong decode scale counts as a flip."""
    import jax.numpy as jnp

    from repro.core import fp8

    x = jnp.linspace(-1.0, 1.0, 512, dtype=jnp.float32)
    alpha = jnp.float32(1.0)
    codes = fp8.pack_fp8(fp8.quantize_det(x, alpha), alpha)
    ref = (codes, {"w": fp8.unpack_fp8(codes, alpha)})
    got_codes = codes
    if case == "adjacent_code":     # a magnitude code away from any edge
        got_codes = codes.at[400].set(codes[400] + 1)
    got = fp8.unpack_fp8(got_codes, alpha)
    if case == "scale_ulps":
        got = got * (1 + 2.0 ** -19)
    if case == "scale_fault":
        got = got * (1 + 2.0 ** -4)
    dl = chip_smoke.downlink_check((got_codes, {"w": got}), ref, fp8.E4M3)
    assert dl["code_flip_share"] == (1 / 512 if case == "adjacent_code"
                                     else 0.0)
    assert dl["adjacent"]
    n_zero = int(jnp.sum(ref[1]["w"] == 0))
    assert dl["value_flip_share"] * 512 == flips - (
        n_zero if case == "scale_fault" else 0)
    assert dl["scale_rel"] < chip_smoke.DOWNLINK_VALUE_RTOL
    if case == "scale_ulps":
        assert dl["scale_rel"] > 0.0


def test_checks_collects_every_failure(capsys):
    check = chip_smoke.Checks()
    check(True, "fine")
    check(False, "first")
    check(False, "second")
    assert "FAILED: first" in capsys.readouterr().err
    with pytest.raises(chip_smoke.SmokeFailure, match="first; second"):
        check.done()


def test_kernel_vjp_check_tiny(interpret_backend):
    rel = chip_smoke.kernel_vjp_check(rows=64, d_in=256, d_out=384)
    assert max(rel.values()) <= 1e-4, rel


@pytest.mark.parametrize("kernel,scale", [
    ("qat_matmul_dw", 0.0), ("qat_matmul_dw", 0.5),
    ("qat_matmul_dx", 0.5), ("quant_det_bwd", 0.5),
    ("quant_rand_bwd", 0.5)])
def test_kernel_vjp_check_catches_clip_cotangent_fault(
        interpret_backend, monkeypatch, kernel, scale):
    """A planted fault in a kernel's clip cotangent (lost, or off by a
    constant factor) fails the check; the loss and the AdamW-normalised
    params would barely move."""
    from repro.kernels import fp8_matmul, fp8_quant

    mod = fp8_matmul if kernel.startswith("qat") else fp8_quant
    good = getattr(mod, kernel)

    def faulty(*args, **kw):
        gx, ga = good(*args, **kw)
        return gx, ga * scale

    monkeypatch.setattr(mod, kernel, faulty)
    with pytest.raises(chip_smoke.SmokeFailure, match="VJP"):
        chip_smoke.kernel_vjp_check(rows=64, d_in=256, d_out=384)


def test_four_chip_phase_on_virtual_devices():
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import chip_smoke\n"
        "from repro import configs\n"
        "cfg = configs.reduced(configs.get('tinyllama_1_1b'))"
        ".replace(n_layers=1)\n"
        "out = chip_smoke.four_chip_phase(cfg, clients=2, local_steps=2, "
        "batch=2, seq=16, require_kernels=False)\n"
        "print(sorted(out))\n" % (str(ROOT), str(ROOT / "src"))
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_KERNEL_BACKEND", None)
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    assert run.stdout.strip().splitlines()[-1] == "['clients4', 'fed2x2']"


def _no_result(run):
    lines = run.stdout.strip().splitlines()
    return not lines or '"ok"' not in lines[-1]


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert run.returncode != 0
    assert _no_result(run), run.stdout
    assert "no TPU" in run.stderr


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert run.returncode != 0
    assert _no_result(run), run.stdout


def test_result_line_format(monkeypatch, capsys):
    """The last line is exactly the device JSON when every phase passes."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [Dev()])
    monkeypatch.setenv(dispatch._ENV, "pallas")
    monkeypatch.setattr(chip_smoke, "fedsim_phase", lambda **kw: {})
    monkeypatch.setattr(chip_smoke, "engine_lm_phase", lambda *a, **kw: {})
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "cache")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
