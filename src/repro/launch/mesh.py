"""Production mesh definitions (DESIGN.md §4).

Single pod: TPU v5e-256 as (data=16, model=16).
Multi-pod:  2 pods × 256 chips as (pod=2, data=16, model=16); the pod axis
is the slow DCI link whose traffic the paper's TT compression targets.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state); the dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before importing
jax so the placeholder devices exist.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """Mesh with Auto axes: GSPMD propagates shardings from the rules in
    ``launch/sharding.py`` (no explicit-sharding mode)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = jax.device_count()
    dp = n // model_parallel
    return make_mesh((dp, model_parallel), ("data", "model"))


def data_axis_size(mesh: Mesh | None, axis: str = "data") -> int:
    """Number of devices along ``axis`` (1 when absent/no mesh) — the
    fan-out the batched compression scheduler round-robins buckets over."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return int(mesh.devices.shape[mesh.axis_names.index(axis)])


def batch_axes(mesh: Mesh):
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


# Hardware model for the roofline (TPU v5e per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s per link (~45GB/s usable quoted; 50 per spec)
ICI_LINKS = 4                     # v5e: 4 ICI links per chip (2D torus x2 dirs)
DCI_BW = 25e9                     # inter-pod (data-center) per-host estimate
