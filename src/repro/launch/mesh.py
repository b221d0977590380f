"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod = 16x16 v5e (256 chips); multi-pod
adds a leading 'pod' axis (2 pods = 512 chips).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1, *, strict: bool = False):
    """(data, model) mesh over whatever devices exist (tests / smoke runs).

    Degenerate requests clamp to a valid mesh: each axis size is at least 1
    (``data=0`` or ``data > n`` no longer yields a zero/invalid axis) and
    the product never exceeds the device count — the mesh simply uses the
    first ``data * model`` devices.  ``strict=True`` raises instead when
    the requested shape does not fit, with the CPU fan-out hint (the
    sharded-dispatch path wants the exact mesh it planned for, not a
    silently clamped one).
    """
    devs = jax.devices()
    n = len(devs)
    if strict:
        if data < 1 or model < 1:
            raise ValueError(
                f"mesh axis sizes must be >= 1, got (data={data}, "
                f"model={model})")
        if data * model > n:
            raise ValueError(
                f"mesh (data={data}, model={model}) needs {data * model} "
                f"devices but only {n} exist; on CPU set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "(or pass --host-devices to repro.launch.serve)")
    data = max(1, min(data, n))
    model = max(1, min(model, n // data))
    use = np.asarray(devs[:data * model]).reshape(data, model)
    return Mesh(use, ("data", "model"))
