"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benches see the real single CPU device.

``plan_device_count`` / ``plan_mesh`` serve the planning stack's sharded
grid scans (``repro.core.planning_backend``): a 1-D "plan" mesh over the
local devices, over which the config axis of every argmin scan is
partitioned.  ``REPRO_PLAN_DEVICES`` caps how many local devices planning
uses (``1`` disables sharding entirely); simulated CPU devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
first jax import.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import numpy as np

PLAN_DEVICES_ENV = "REPRO_PLAN_DEVICES"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices with Auto
    axis types; tolerates ``len(jax.devices()) > prod(shape)`` and raises
    when there are too few devices."""
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            "sets this automatically)")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def plan_device_count() -> int:
    """Local devices the planning backends shard their scans over.

    ``len(jax.local_devices())`` capped by the ``REPRO_PLAN_DEVICES`` env
    knob; never below 1.  A result of 1 means the sharded code paths are
    bypassed entirely (the backends build their legacy single-device
    programs), so setting ``REPRO_PLAN_DEVICES=1`` is the rollback switch.
    """
    n = len(jax.local_devices())
    cap = os.environ.get(PLAN_DEVICES_ENV, "").strip()
    if cap:
        try:
            n = min(n, int(cap))
        except ValueError:
            pass
    return max(1, n)


def plan_mesh(n_devices: Optional[int] = None):
    """1-D mesh with axis ``"plan"`` over the first ``n_devices`` local
    devices — the mesh every sharded grid scan / stacked flush runs on."""
    n = plan_device_count() if n_devices is None else max(1, int(n_devices))
    return make_mesh((n,), ("plan",))


def mesh_axes(mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def data_parallel_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n
