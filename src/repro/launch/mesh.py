"""Device mesh construction (dry-run spec, DESIGN.md §4).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — smoke tests see 1 CPU device;
only ``dryrun.py`` sets ``xla_force_host_platform_device_count``.

Federated meshes
================
* :func:`make_client_mesh` — 1D: the cohort axis only. Every device trains
  ceil(P / D) whole clients; each client's model step is single-device.
* :func:`make_fed_mesh` — 2D ``(clients, fsdp)``: the cohort axis times a
  model axis. Each row of ``fsdp`` devices holds ONE client shard-wise —
  the client's training step is FSDP-sharded with the logical-axis rules
  in ``sharding/policy.py`` (``fed_param_specs``), and the wire/plane
  paths build *per-device* planes over the local shards
  (``core.plane``'s shard-aware layout) so quantize/encode stay one
  launch per device at any model scale.

On a CPU host, force virtual devices the dryrun way
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — BEFORE jax
initializes; the test suite's conftest translates ``REPRO_VIRTUAL_DEVICES``
into that flag) to exercise the multi-device paths without hardware.
"""
from __future__ import annotations

import os
import warnings

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally, as a (data, model=1) mesh — used by
    examples/integration tests so the same trainer code runs on CPU."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _virtual_devices_hint(available: int) -> str:
    """Actionable suffix for device-count errors: REPRO_VIRTUAL_DEVICES was
    requested but jax already initialized, so the XLA flag never applied."""
    want = os.environ.get("REPRO_VIRTUAL_DEVICES", "")
    if want.isdigit() and available < int(want):
        return (
            f" (REPRO_VIRTUAL_DEVICES={want} is set but jax initialized "
            f"with {available} device(s) — the flag must reach XLA before "
            "jax first touches devices: run under pytest (conftest applies "
            "it) or export XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={want} before starting python)"
        )
    return " (set xla_force_host_platform_device_count?)"


def make_client_mesh(n: int | None = None,
                     axis: str = "clients") -> jax.sharding.Mesh:
    """The first ``n`` local devices (default: all) on ONE named axis — the
    mesh ``repro.core.engine.ShardedExecutor`` spreads the federated cohort
    over. A non-dividing ``n`` used to silently idle the remaining devices;
    now it warns naming the sizes that use them all. For a cohort ×
    model-parallel mesh use :func:`make_fed_mesh`."""
    devs = jax.devices()
    if n is None:
        n = len(devs)
    if n <= 0:
        raise ValueError(f"client mesh needs a positive device count, got {n}")
    if n > len(devs):
        raise ValueError(
            f"requested a {n}-device client mesh but only {len(devs)} "
            f"device(s) exist{_virtual_devices_hint(len(devs))}"
        )
    if len(devs) % n != 0:
        # not fatal (cohort padding keeps a ragged mesh correct) but it
        # silently idles hardware — say so instead of hiding it
        warnings.warn(
            f"client mesh of {n} devices idles {len(devs) - n} of the "
            f"{len(devs)} available — a divisor of {len(devs)} uses them "
            f"all ({[d for d in range(1, len(devs) + 1) if len(devs) % d == 0]})",
            stacklevel=2,
        )
    return jax.sharding.Mesh(np.array(devs[:n]), (axis,))


def make_fed_mesh(clients: int, fsdp: int, *, client_axis: str = "clients",
                  model_axis: str = "fsdp") -> jax.sharding.Mesh:
    """2D federated mesh: ``clients`` rows of ``fsdp`` devices each.

    Row i trains the i-th slice of the cohort with its model state
    FSDP-sharded over the row (``sharding.policy.fed_param_specs``); the
    uplink's u8 codes all-gather moves along ``client_axis`` only, with
    ``model_axis``-sharded operands staying in place. Pass the mesh plus
    ``model_axis`` to ``FedConfig(mesh=..., model_axis=...)``.
    """
    if clients <= 0 or fsdp <= 0:
        raise ValueError(
            f"make_fed_mesh needs positive axis sizes, got "
            f"clients={clients}, fsdp={fsdp}"
        )
    devs = jax.devices()
    need = clients * fsdp
    if need > len(devs):
        raise ValueError(
            f"{clients}x{fsdp} fed mesh needs {need} devices but only "
            f"{len(devs)} exist{_virtual_devices_hint(len(devs))}"
        )
    if len(devs) % need != 0:
        raise ValueError(
            f"{clients}x{fsdp} fed mesh uses {need} of {len(devs)} devices, "
            f"idling {len(devs) - need} — pick axis sizes whose product "
            f"divides {len(devs)}"
        )
    arr = np.array(devs[:need]).reshape(clients, fsdp)
    return jax.sharding.Mesh(arr, (client_axis, model_axis))


# Hardware constants for the roofline model (TPU v5e per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link (~per-chip effective)
