"""Production meshes. Importing this module never touches jax device state —
mesh construction happens only inside the factory functions."""
from __future__ import annotations

from typing import Optional, Tuple


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto mode (sharding propagated
    by the compiler from the specs the model states)."""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi-pod adds a leading DCN "pod" axis
    (2 pods = 512 chips). Parameters never shard over "pod" (DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_slice_mesh(devices_2d, axis_names: Tuple[str, str] = ("data", "model")):
    """Mesh over one StaticPartitioner slice rectangle."""
    from jax.sharding import Mesh
    return Mesh(devices_2d, axis_names)


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over the first ``data * model`` local devices:
    the chips of one host, or CPU devices in tests."""
    import jax
    n = data * model
    avail = len(jax.devices())
    if avail < n:
        raise RuntimeError(
            f"need {n} devices, have {avail}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            f"importing jax")
    return make_mesh((data, model), ("data", "model"))
