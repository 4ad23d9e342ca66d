"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — "pod" is
an additional pure-data-parallel axis across the inter-pod DCN/ICI links.

Defined as functions (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == need:
        return jax.make_mesh(shape, axes)
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for mesh {shape}, have {len(devs)} — "
            "the dry-run entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    # more devices than needed (e.g. 512 host devices, single-pod mesh):
    # build the mesh on a slice.
    grid = np.asarray(devs[:need]).reshape(shape)
    return Mesh(grid, axes)


def make_app_mesh(max_devices: Optional[int] = None, *,
                  devices: Optional[Sequence] = None) -> Mesh:
    """1-D ``("app",)`` mesh for app-sharded sweeps (experiment engine).

    The application axis of a stacked sweep is pure data parallelism:
    lanes never communicate, so any device count works — the engine pads
    the app axis up to it by edge replication. ``devices`` overrides the
    pool (the elastic supervisor passes the surviving subset after a
    simulated host loss); default is every local device.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs) if max_devices is None else max(1, min(max_devices,
                                                         len(devs)))
    return Mesh(np.asarray(devs[:n]), ("app",))


def make_app_trial_mesh(app_devices: int = 1,
                        max_devices: Optional[int] = None, *,
                        devices: Optional[Sequence] = None) -> Mesh:
    """2-D ``("app", "trial")`` mesh for the streaming Monte-Carlo engine.

    ``app_devices`` lanes shard the application axis (pure data
    parallelism, as in ``make_app_mesh``); the remaining devices form the
    trial axis, across which each scan chunk's PRNG blocks split and the
    additive ``TrialStats`` accumulator is ``psum``-merged
    (``repro.distributed.appaxis.make_app_trial_sharded``). Devices that
    do not fill the rectangle are left idle. ``devices`` overrides the
    pool (elastic supervisor's surviving subset).
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs) if max_devices is None else max(1, min(max_devices,
                                                         len(devs)))
    app = max(1, min(app_devices, n))
    trial = n // app
    grid = np.asarray(devs[:app * trial]).reshape(app, trial)
    return Mesh(grid, ("app", "trial"))


def mesh_tag(mesh: Optional[Mesh]) -> str:
    """A mesh's layout as one word, axis names and sizes in order:
    ``"app2xtrial2"`` for a 2 x 2 ``("app", "trial")`` mesh, ``"none"``
    for no mesh (one device)."""
    if mesh is None:
        return "none"
    return "x".join(f"{name}{size}" for name, size in mesh.shape.items())


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over the actually-available devices (tests/examples)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh((n // mp, mp), ("data", "model"))


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """All pure data-parallel axes of a mesh ("pod" folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh: Mesh, names: Sequence[str]) -> int:
    size = 1
    for n in names:
        if n in mesh.axis_names:
            size *= mesh.shape[n]
    return size
