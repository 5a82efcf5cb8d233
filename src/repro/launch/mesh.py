"""Production meshes. v5e hardware constants used by the roofline live here
too so benchmarks/ and launch/ agree on them.

make_production_mesh is a FUNCTION (not a module constant) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax

# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Single-host mesh for tests/examples (1x1 on CPU)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
