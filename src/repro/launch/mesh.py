"""Production meshes.  Defined as FUNCTIONS so importing this module
never touches jax device state (the dry-run sets the host-device-count
flag before any jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model's
    ``with_sharding_constraint`` calls (``models.sharding.constrain``)
    may only name Auto axes, and ``make_mesh`` defaults to Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips/pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))


def make_serving_mesh(model: int = 1):
    """Pure tensor-parallel ``(1, model)`` mesh for the paged serving
    engine, over the first ``model`` devices.

    Serving keeps the data axis at size 1 on purpose: the engine's slot
    batch is tiny and host-scheduled, so sharding it would only force
    uneven batch splits through the model's internal batch constraints,
    while the weight/KV tensor axes are where the memory and FLOPs
    actually live.  ``model`` must not exceed the device count."""
    import numpy as np
    devs = jax.devices()
    if model < 1 or model > len(devs):
        raise ValueError(
            f"make_serving_mesh(model={model}): have {len(devs)} device(s)")
    return jax.sharding.Mesh(
        np.asarray(devs[:model]).reshape(1, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The FSDP/batch axes of a mesh (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")
