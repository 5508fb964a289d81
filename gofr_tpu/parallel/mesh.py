"""Mesh construction.

Axis convention (jax-ml scaling-book style):
- ``dp``   — pure data parallelism (batch split, gradients all-reduced)
- ``fsdp`` — data parallelism with parameter sharding (ZeRO-3 style;
             params/optimizer sharded, all-gathered per layer)
- ``sp``   — sequence/context parallelism (ring attention over ICI)
- ``pp``   — pipeline parallelism (layer stages, GPipe microbatches)
- ``ep``   — expert parallelism (MoE experts, all_to_all token dispatch)
- ``tp``   — tensor parallelism (heads / hidden dim split)

On a physical slice the trailing axes should map to the fastest ICI links;
jax.make_mesh handles device ordering. Single-process multi-device (one host
of a v5e slice) and the CPU-backed virtual mesh used by tests/dryrun are
built the same way.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh

AXES = ("dp", "fsdp", "sp", "pp", "ep", "tp")


def mesh_shape_for(
    n_devices: int,
    tp: int = 1,
    sp: int = 1,
    fsdp: int = 1,
    pp: int = 1,
    ep: int = 1,
) -> dict[str, int]:
    """Fill ``dp`` with whatever remains after the explicit axes."""
    denom = tp * sp * fsdp * pp * ep
    if n_devices % denom != 0:
        raise ValueError(
            f"{n_devices} devices not divisible by tp*sp*fsdp*pp*ep={denom}"
        )
    return {
        "dp": n_devices // denom, "fsdp": fsdp, "sp": sp,
        "pp": pp, "ep": ep, "tp": tp,
    }


def make_mesh(
    shape: Optional[dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a named mesh. Default: all local devices on ``dp``."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = mesh_shape_for(len(devices))
    sizes = tuple(shape.get(a, 1) for a in AXES)
    total = 1
    for s in sizes:
        total *= s
    if total != len(devices):
        raise ValueError(f"mesh shape {shape} needs {total} devices, have {len(devices)}")
    # Auto axes: GSPMD owns propagation and inserts collectives freely
    # (jax 0.9 defaults some paths to explicit sharding-in-types, which
    # rejects mixed-axis contractions instead of resolving them)
    return jax.make_mesh(
        sizes, AXES, (jax.sharding.AxisType.Auto,) * len(AXES), devices=devices
    )


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def mesh_axes(mesh: Optional[Mesh]) -> Optional[dict[str, int]]:
    """The mesh's non-trivial axes as a plain dict ({"tp": 2, "dp": 4})
    — the shape observability carries (``/admin/engine`` ``mesh``,
    ``gofr_tpu_mesh_axis_size{axis}``, FlightRecord ``mesh_axes``).
    None when no mesh (single chip)."""
    if mesh is None:
        return None
    # a mesh whose axes are all size 1 yields {} (a mesh, trivially) —
    # distinct from the None of no mesh at all
    return {a: s for a, s in mesh.shape.items() if s > 1}
