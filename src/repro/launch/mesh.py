"""Production meshes: 16×16 single pod, 2×16×16 multi-pod.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — only ``dryrun.py`` (which sets
``--xla_force_host_platform_device_count=512`` before any jax import) should
construct the production shapes in this container.

Every mesh here has Auto axis types: the models place arrays with explicit
``NamedSharding`` annotations and let the compiler propagate the rest, which
``jax.make_mesh``'s default Explicit axes refuse (e.g. the embedding gather).
"""

from __future__ import annotations

import jax


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto (compiler-propagated sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *, n_pod: int = 0):
    """Small mesh for CPU multi-device tests (requires forced host devices)."""
    if n_pod:
        return auto_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return auto_mesh((n_data, n_model), ("data", "model"))


def single_device_mesh():
    return auto_mesh((1, 1), ("data", "model"))
