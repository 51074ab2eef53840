"""Device meshes for the sharded RTAC path.

``make_mesh`` is a FUNCTION (importing this module never touches jax device
state). Meshes are built from the devices that exist — the process's own, or
a described topology's for an ahead-of-time compile.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh of ``shape`` over ``axes`` (Auto axis types), on ``devices``
    (default: this process's devices)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes), **kw
    )
