"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets XLA_FLAGS before any jax
import and then calls these.

Every mesh gets ``Auto`` axis types: the model code places arrays with
``with_sharding_constraint`` and leaves propagation to the compiler, which
``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh():
    """Every device on the host as an (N, 1) data mesh."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def describe(mesh) -> str:
    return " x ".join(f"{a}={s}" for a, s in
                      zip(mesh.axis_names, mesh.devices.shape)) \
        + f" ({mesh.devices.size} chips)"
