"""Shared kernel plumbing: interpret-mode autodetection and tiling helpers.

All kernels in this package target TPU (pl.pallas_call with explicit
BlockSpec VMEM tiling, MXU-aligned tile shapes).  On non-TPU backends —
including this CPU container — the jit'd wrappers in each ``ops.py`` pass
``interpret=True`` so the kernel body executes exactly as written and can be
validated against the ``ref.py`` oracle.
"""

from __future__ import annotations

import jax
import numpy as np

# MXU native tile; VPU lane width.  All kernel tile shapes are multiples.
MXU_DIM = 128
VPU_LANES = 128
# Scoped VMEM limit handed to Mosaic on every pallas_call of the TT
# contraction family, and the bound its dispatch gate admits chains against
# (v5e has 128 MiB of VMEM per core; the rest is left to the compiler).
VMEM_LIMIT = 96 * 1024 * 1024


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_tile(dim: int, target: int = MXU_DIM, cap: int = 512) -> int:
    """Largest hardware-aligned tile <= cap that divides the (padded) dim."""
    if dim <= target:
        return round_up(max(dim, 1), 8)
    t = target
    while t * 2 <= cap and dim % (t * 2) == 0:
        t *= 2
    return t


def vmem_tile_bytes(shape, dtype) -> int:
    """VMEM bytes of one array of ``shape`` laid out in native TPU tiles:
    the minor dim pads to 128 lanes, the second-minor to the dtype's
    sublane tile (8 rows of 32-bit, 16 of bf16, 32 of int8)."""
    itemsize = np.dtype(dtype).itemsize
    dims = (1,) * max(0, 2 - len(shape)) + tuple(int(d) for d in shape)
    lead = int(np.prod(dims[:-2]))
    sub = round_up(dims[-2], 8 * max(1, 4 // itemsize))
    return lead * sub * round_up(dims[-1], VPU_LANES) * itemsize


def pad_to(x, rows: int, cols: int):
    """Zero-pad a 2D array up to (rows, cols)."""
    import jax.numpy as jnp

    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))
