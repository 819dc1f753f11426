"""Production meshes (assignment spec).

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import to get 512 placeholder CPU devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: sharding propagates from the
    placed inputs (GSPMD), which is what every caller here relies on.
    ``make_mesh`` defaults to Explicit axes, under which ops such as the
    embedding gather refuse operands of differing shardings."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke/bench runs (same axis names)."""
    return _auto_mesh((1, 1), ("data", "model"))


def parse_mesh_spec(spec: str | None) -> tuple[int, int] | None:
    """CLI mesh spec -> (data, model) sizes.

    ``"1x8"`` -> (1, 8); a bare ``"8"`` means model-only TP, i.e. (1, 8);
    None/"" -> None (no mesh: the single-device serving path)."""
    if spec is None or spec == "" or spec.lower() == "none":
        return None
    parts = spec.lower().split("x")
    try:
        if len(parts) == 1:
            dm = 1, int(parts[0])
        elif len(parts) == 2:
            dm = int(parts[0]), int(parts[1])
        else:
            dm = None
    except ValueError:
        dm = None
    if dm is None or dm[0] < 1 or dm[1] < 1:
        raise ValueError(f"mesh spec must be positive sizes like '1x8' or "
                         f"'8', got {spec!r}")
    return dm


def make_serving_mesh(spec: str | None):
    """Build the serving ("data", "model") mesh named by a CLI spec over
    the locally visible devices; None when no mesh is requested."""
    dm = parse_mesh_spec(spec)
    if dm is None:
        return None
    d, m = dm
    n_dev = len(jax.devices())
    if d * m > n_dev:
        raise ValueError(
            f"mesh {d}x{m} needs {d * m} devices but only {n_dev} are "
            f"visible (CPU: set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={d * m} before the first jax import)")
    return _auto_mesh((d, m), ("data", "model"))


def make_replica_meshes(spec: str | None, n_replicas: int) -> list:
    """Per-replica serving meshes over DISJOINT device slices — fleet
    scale-out: replica i's ``spec``-shaped mesh uses devices
    [i*d*m, (i+1)*d*m), so N engine replicas run side by side with no
    device shared (each replica's jitted steps dispatch to its own
    devices).  ``spec`` is the PER-REPLICA mesh; None -> [None]*N (every
    replica on the default single-device path, the CPU smoke case)."""
    import numpy as np
    from jax.sharding import Mesh

    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    devices = jax.devices()
    dm = parse_mesh_spec(spec)
    if dm is None:
        if n_replicas == 1 or len(devices) < n_replicas:
            return [None] * n_replicas
        dm = (1, 1)
    d, m = dm
    per = d * m
    if per * n_replicas > len(devices):
        raise ValueError(
            f"{n_replicas} replicas of a {d}x{m} mesh need "
            f"{per * n_replicas} devices but only {len(devices)} are "
            f"visible (CPU: set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={per * n_replicas} before the first jax import)")
    return [Mesh(np.asarray(devices[i * per:(i + 1) * per]).reshape(d, m),
                 ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            for i in range(n_replicas)]
