"""Production mesh + target-hardware constants (TPU v5e).

``make_production_mesh`` is a function (not a module constant) so that
importing this module never touches jax device state — the dry-run
driver must set ``XLA_FLAGS`` before *any* jax initialization.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class Hardware:
    """TPU v5e per-chip numbers used by the roofline analysis."""
    peak_flops: float = 197e12        # bf16 FLOP/s
    hbm_bw: float = 819e9             # bytes/s
    ici_bw: float = 50e9              # bytes/s per link
    dcn_bw: float = 25e9              # bytes/s per pod (inter-pod axis)
    hbm_bytes: float = 16e9


V5E = Hardware()


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # shard_hint constrains only Auto axes; make_mesh defaults to Explicit.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
