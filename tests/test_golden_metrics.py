"""Golden-metrics regression: one seeded round per engine variant, plus
one short buffered-async run per async variant.

Every stage combination from the engine grid (sampler x link x executor x
aggregator) runs ONE deterministic round and is pinned against the
checked-in goldens in ``tests/goldens/engine_goldens.json``:

* ``wire_bytes`` — exact integer equality (any cohort/link/payload drift
  fails immediately);
* ``local_loss`` and per-leaf ``(mean, l2)`` fingerprints of the new
  server model — tight relative tolerance (2e-5). A semantic regression
  (key-split reorder, stage rewiring, rounding-mode confusion, changed
  sampler) shifts these by orders of magnitude more; last-ULP platform
  noise (different SIMD widths re-tiling XLA:CPU's GEMMs) sits ~100x
  below it. Numeric drift in any stage therefore fails THIS fast unit
  test instead of surfacing as a slow-lane convergence flake.

Regenerating the goldens (after an INTENDED numerics change — review the
diff of the JSON, it is the contract):

    PYTHONPATH=src python tests/test_golden_metrics.py --regen
"""
import json
import os

# honor REPRO_VIRTUAL_DEVICES on DIRECT runs too (--regen of the 2D-mesh
# variants): the flag must reach XLA before jax initializes. Under pytest
# the conftest has already applied it — the guard keeps this idempotent.
_want = os.environ.get("REPRO_VIRTUAL_DEVICES", "")
if _want.isdigit() and int(_want) > 1 and (
        "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_want}"
    ).strip()

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core.async_engine import AsyncConfig, BufferedAsyncEngine
from repro.core.codec import CodecSchedule
from repro.core.engine import FedConfig, RoundEngine
from repro.core.qat import (
    DISABLED,
    QATConfig,
    clip_value_mask,
    weight_decay_mask,
)
from repro.core.fp8 import E5M2
from repro.core.server_opt import ServerOptConfig
from repro.data import partition_iid, synthetic_classification
from repro.models import small

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "goldens",
                           "engine_goldens.json")

_BASE = dict(n_clients=6, participation=0.5, local_steps=2, batch_size=8)

# id -> FedConfig kwargs beyond _BASE; one variant per engine knob value
VARIANTS = {
    "uniform_rand_mean": dict(comm_mode="rand", qat=QATConfig()),
    "weighted_rand_mean": dict(comm_mode="rand", qat=QATConfig(),
                               sampler="weighted"),
    "fixed_det_mean": dict(comm_mode="det", qat=QATConfig(),
                           sampler="fixed"),
    "uniform_fp32_mean": dict(comm_mode="none", qat=DISABLED),
    "hybrid_rand_mean": dict(comm_mode="rand", qat=QATConfig(),
                             up_fmt=E5M2),
    "fp32down_fp8up_mean": dict(comm_mode="rand", qat=QATConfig(),
                                down_mode="none"),
    "chunked_rand_mean": dict(comm_mode="rand", qat=QATConfig(), chunk=2),
    "uniform_rand_fedavgm": dict(comm_mode="rand", qat=QATConfig(),
                                 aggregator="fedavgm", server_lr=1.0,
                                 server_momentum=0.9),
    "uniform_rand_fedadam": dict(comm_mode="rand", qat=QATConfig(),
                                 aggregator="fedadam", server_lr=0.05),
    "uniform_rand_serveropt": dict(
        comm_mode="rand", qat=QATConfig(),
        server_opt=ServerOptConfig(enabled=True, gd_steps=2, lr=0.1,
                                   n_grid=5),
    ),
    # --- codec-API variants (ISSUE 5): sub-byte / delta / schedule ------
    "fp4_rand_mean": dict(comm_mode="rand", qat=QATConfig(),
                          down_codec="fp4", up_codec="fp4"),
    "fp4_e3m0_det_mean": dict(comm_mode="rand", qat=QATConfig(),
                              down_codec="fp4_e3m0_det",
                              up_codec="fp4_e3m0_det"),
    "delta_up_mean": dict(comm_mode="rand", qat=QATConfig(),
                          up_codec="delta:e4m3"),
    "sched_e5m2_fp4_mean": dict(
        comm_mode="rand", qat=QATConfig(),
        codec_schedule=CodecSchedule(("e5m2", "fp4"), (1,)),
    ),
    # --- compression-research variants (ISSUE 10): EF + entropy wire ----
    # wire_bytes of the rans variants pins the TRACED (entropy-coded)
    # ledger — data-dependent but deterministic in the seed
    "ef_fp4_det_mean": dict(comm_mode="rand", qat=QATConfig(),
                            up_codec="ef:fp4_e2m1_det"),
    "rans_delta_fp4_mean": dict(comm_mode="rand", qat=QATConfig(),
                                down_codec="rans:fp4_e2m1",
                                up_codec="rans:delta:fp4_e2m1"),
    "ef_rans_fp4_det_mean": dict(comm_mode="rand", qat=QATConfig(),
                                 down_codec="rans:fp4_e2m1",
                                 up_codec="ef:rans:fp4_e2m1_det"),
    # --- scaling-policy variants (ISSUE 8): delayed / frozen wires ------
    "delayed_wire_mean": dict(comm_mode="rand", qat=QATConfig(),
                              down_scaling="delayed:4",
                              up_scaling="delayed:4:1"),
    "frozen_down_mean": dict(comm_mode="rand", qat=QATConfig(),
                             down_scaling="frozen"),
    # --- 2D federated mesh variants (ISSUE 7): clients x fsdp -----------
    # ``mesh2d`` resolves lazily to make_fed_mesh(C, F) + model_axis so
    # importing this module never touches device state; the test skips
    # when fewer than C*F devices exist (run the multi-device lane:
    # REPRO_VIRTUAL_DEVICES=8). det wires keep the pins independent of
    # the per-device plane layout, which sets a 2D rand wire's counters.
    "fed2d_2x4_det_mean": dict(comm_mode="det", qat=QATConfig(),
                               mesh2d=(2, 4)),
    "fed2d_2x4_fp4_fedavgm": dict(comm_mode="det", qat=QATConfig(),
                                  down_codec="fp4_det", up_codec="fp4_det",
                                  aggregator="fedavgm", server_lr=1.0,
                                  server_momentum=0.9, mesh2d=(2, 4)),
    "fed2d_4x2_det_mean": dict(comm_mode="det", qat=QATConfig(),
                               participation=1.0, mesh2d=(4, 2)),
}


def _variant_devices(variant: str) -> int:
    c, f = VARIANTS[variant].get("mesh2d", (1, 1))
    return c * f


# buffered-async variants (ISSUE 6): buffer size x staleness discount x
# momentum x delta-coded uplink, each pinned as (exact cumulative bytes,
# loss, param fingerprints) of a short deterministic event-loop run
ASYNC_VARIANTS = {
    "k2_plain": dict(acfg=dict(buffer_size=2, staleness_alpha=0.0)),
    "k4_stale1": dict(acfg=dict(buffer_size=4, staleness_alpha=1.0)),
    "k2_momentum": dict(acfg=dict(buffer_size=2, staleness_alpha=0.5,
                                  server_momentum=0.9)),
    "k2_delta_up": dict(acfg=dict(buffer_size=2, staleness_alpha=0.5),
                        cfg=dict(up_codec="delta:e4m3")),
}


def _leaf_fingerprints(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    leaves = {}
    for path, leaf in flat:
        name = ".".join(str(getattr(p, "key", p)) for p in path)
        arr = np.asarray(leaf, np.float64)
        leaves[name] = [float(arr.mean()), float(np.linalg.norm(arr))]
    return leaves


def _async_round_metrics(variant: str) -> dict:
    params, loss, opt, (cx, cy, nk) = _setup()
    spec = ASYNC_VARIANTS[variant]
    cfg = FedConfig(**_BASE, comm_mode="rand", qat=QATConfig(),
                    **spec.get("cfg", {}))
    eng = BufferedAsyncEngine(loss, opt, cfg,
                              AsyncConfig(concurrency=4, **spec["acfg"]))
    state, hist = eng.run(params, cx, cy, jax.random.PRNGKey(42), folds=4,
                          eval_every=4)
    return {
        "wire_bytes": hist.cumulative_bytes[-1],
        "local_loss": hist.loss[-1],
        "mean_staleness": hist.mean_staleness[-1],
        "leaves": _leaf_fingerprints(state.params),
    }


def _setup():
    xall, yall = synthetic_classification(0, 900, d=16, n_classes=4)
    cx, cy, nk = partition_iid(xall[:600], yall[:600], k=6, seed=0)
    init, apply = small.REGISTRY["mlp"]
    params = init(jax.random.PRNGKey(0), d_in=16, n_classes=4)
    loss = small.make_loss(apply)
    opt = optim.sgd(0.05, wd_mask=weight_decay_mask(params),
                    trust_mask=clip_value_mask(params))
    return params, loss, opt, (jnp.asarray(cx), jnp.asarray(cy),
                               jnp.asarray(nk))


def _round_metrics(variant: str) -> dict:
    params, loss, opt, data = _setup()
    kw = {**_BASE, **VARIANTS[variant]}
    mesh2d = kw.pop("mesh2d", None)
    if mesh2d is not None:
        from repro.launch.mesh import make_fed_mesh

        kw["mesh"] = make_fed_mesh(*mesh2d)
        kw["model_axis"] = "fsdp"
    cfg = FedConfig(**kw)
    eng = RoundEngine(loss, opt, cfg)
    state, m = jax.jit(eng.round_fn)(eng.init(params), *data,
                                     jax.random.PRNGKey(42))
    return {
        "wire_bytes": int(m["wire_bytes"]),
        "local_loss": float(m["local_loss"]),
        "leaves": _leaf_fingerprints(state.params),
    }


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_golden_metrics(variant):
    need = _variant_devices(variant)
    if need > len(jax.devices()):
        pytest.skip(f"needs {need} devices (REPRO_VIRTUAL_DEVICES={need})")
    with open(GOLDEN_PATH) as f:
        goldens = json.load(f)
    assert variant in goldens["variants"], (
        f"no golden for {variant!r} — regenerate: "
        "PYTHONPATH=src python tests/test_golden_metrics.py --regen"
    )
    want = goldens["variants"][variant]
    got = _round_metrics(variant)
    assert got["wire_bytes"] == want["wire_bytes"], (
        variant, got["wire_bytes"], want["wire_bytes"])
    np.testing.assert_allclose(
        got["local_loss"], want["local_loss"], rtol=2e-5,
        err_msg=f"{variant}: local_loss drifted")
    assert got["leaves"].keys() == want["leaves"].keys(), variant
    for name, (mean, l2) in got["leaves"].items():
        wm, wl = want["leaves"][name]
        np.testing.assert_allclose(
            [mean, l2], [wm, wl], rtol=2e-5, atol=1e-7,
            err_msg=f"{variant}/{name}: params fingerprint drifted "
                    "(intended? regen via tests/test_golden_metrics.py)")


@pytest.mark.parametrize("variant", sorted(ASYNC_VARIANTS))
def test_golden_async_metrics(variant):
    """The buffered-async event loop's trajectory is deterministic in
    (seed, configuration): exact cumulative wire bytes, tight-rtol loss /
    staleness / param fingerprints after 4 folds."""
    with open(GOLDEN_PATH) as f:
        goldens = json.load(f)
    assert variant in goldens.get("async_variants", {}), (
        f"no async golden for {variant!r} — regenerate: "
        "PYTHONPATH=src python tests/test_golden_metrics.py --regen"
    )
    want = goldens["async_variants"][variant]
    got = _async_round_metrics(variant)
    assert got["wire_bytes"] == want["wire_bytes"], (
        variant, got["wire_bytes"], want["wire_bytes"])
    np.testing.assert_allclose(
        got["local_loss"], want["local_loss"], rtol=2e-5,
        err_msg=f"{variant}: local_loss drifted")
    np.testing.assert_allclose(
        got["mean_staleness"], want["mean_staleness"], rtol=1e-9,
        err_msg=f"{variant}: dispatch/fold order drifted")
    assert got["leaves"].keys() == want["leaves"].keys(), variant
    for name, (mean, l2) in got["leaves"].items():
        wm, wl = want["leaves"][name]
        np.testing.assert_allclose(
            [mean, l2], [wm, wl], rtol=2e-5, atol=1e-7,
            err_msg=f"{variant}/{name}: async params fingerprint drifted "
                    "(intended? regen via tests/test_golden_metrics.py)")


def _regen():
    existing = {}
    if os.path.exists(GOLDEN_PATH):
        with open(GOLDEN_PATH) as f:
            existing = json.load(f).get("variants", {})
    variants = {}
    for v in sorted(VARIANTS):
        need = _variant_devices(v)
        if need > len(jax.devices()):
            # keep the checked-in pin rather than silently dropping it;
            # regenerate 2D-mesh variants under REPRO_VIRTUAL_DEVICES=8
            assert v in existing, (
                f"{v} needs {need} devices to regenerate: rerun with "
                f"REPRO_VIRTUAL_DEVICES={need}")
            print(f"kept existing golden for {v} "
                  f"(needs {need} devices, have {len(jax.devices())})")
            variants[v] = existing[v]
            continue
        variants[v] = _round_metrics(v)
    out = {
        "_regen": "PYTHONPATH=src python tests/test_golden_metrics.py --regen",
        "_seed": 42,
        "_jax": jax.__version__,
        "variants": variants,
        "async_variants": {
            v: _async_round_metrics(v) for v in sorted(ASYNC_VARIANTS)
        },
    }
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(out['variants'])} sync + "
          f"{len(out['async_variants'])} async goldens to {GOLDEN_PATH}")


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        _regen()
    else:
        print(__doc__)
