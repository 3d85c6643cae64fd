"""Production mesh construction.

Axes: ``data`` (DP+FSDP / IM vertex partition), ``model`` (TP/EP / IM
sample-space partition), ``pod`` (multi-pod data parallelism / IM ensemble).
Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices, found {len(devices)} — "
            "the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:ndev],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple, axes: tuple) -> Mesh:
    """Arbitrary mesh for tests/benchmarks (uses the first prod(shape) devices)."""
    ndev = math.prod(shape)
    return jax.make_mesh(shape, axes, devices=jax.devices()[:ndev],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_serving_mesh(mu_v: int, *, vertex_axis: str = "data",
                      sim_axis: str = "model") -> Mesh:
    """``(mu_v, 1)`` mesh for device-resident serving: ``mu_v`` plan-order
    row blocks, one per device, sample space kept whole per device (the
    store's column split is *banks*, not mesh columns — docs/service.md,
    "Sharded serving")."""
    return make_mesh((mu_v, 1), (vertex_axis, sim_axis))


def make_im_mesh(devices: int, *, mu_v: int = 0) -> Mesh:
    """(data, model) mesh for the IM drivers: ``mu_v`` vertex shards x
    ``devices/mu_v`` sample-space shards. ``mu_v=0`` picks the historical
    default (2-way vertex split when the device count is even) — raise it
    when the graph outgrows per-device HBM and the partition planner keeps
    the wider vertex split balanced."""
    if mu_v <= 0:
        mu_v = 2 if devices % 2 == 0 else 1
    if devices % mu_v != 0:
        raise ValueError(f"--devices {devices} not divisible by mu_v={mu_v}")
    return make_mesh((mu_v, devices // mu_v), ("data", "model"))
