"""Production mesh construction.

Single pod: 16x16 = 256 v5e chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model").

FL clients live on the (pod, data) axes — 16 clients/pod — and tensor/
expert parallelism on "model".  A function (not a module constant) so
importing never touches jax device state.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """One mesh constructor for every helper here: Auto axis types, so
    GSPMD partitions everything the program does not pin."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return _mesh((data, model), ("data", "model"))


def make_host_pod_mesh(pods: int = 2, data: int = 1, model: int = 1):
    """Multi-pod mesh over the locally available devices, axes
    ``("pod", "data", "model")`` — the test/bench twin of the multi-pod
    production mesh, for exercising the hierarchical two-tier
    aggregation (fl/streaming.py, DESIGN.md §9) without pod hardware.

    Fails with a named error instead of an opaque device-count assert;
    host runs force the device count via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    jax initializes — tests do this in subprocesses)."""
    n = len(jax.devices())
    need = pods * data * model
    if n < need:
        raise ValueError(
            f"host pod mesh ({pods} pods x {data} data x {model} model) "
            f"needs {need} devices but only {n} are available; force host "
            f"devices with XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={need} before jax initializes")
    return _mesh((pods, data, model), ("pod", "data", "model"))


def client_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a != "model")


def n_clients(mesh) -> int:
    out = 1
    for a in client_axes(mesh):
        out *= mesh.shape[a]
    return out
