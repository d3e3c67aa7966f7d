"""Dispatch wrapper for the fused TT-contraction kernels.

``tt_contract`` takes the lead-absorbed chain (see ``ref.py`` for the
representation) and picks the execution path:

  * depth 2 (split 1)      → fused ``tt_contract_2``
  * depth 3 (split 1 or 2) → fused ``tt_contract_3``
  * anything else, or chains whose grid step would not fit
    ``common.VMEM_LIMIT``  → the jnp einsum chain (``tt_contract_ref``),
                             still unmaterialized, just unfused

The unfused chain is a dispatch decision, not an error path, so it is
counted: every dispatch bumps ``dispatch_tally()`` under the kernel it
took, or under ``unfused`` with its reason (``depth`` or ``vmem``).
Dispatch happens while a program is traced, so the tally counts leaf calls
per traced program, not per executed step.

All paths return float32 — callers (``core/tt_linear.tt_apply``) cast back
to the activation dtype after the chain, matching how the dense path's
einsums accumulate.
"""

from __future__ import annotations

import collections
import functools
import os
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import common
from repro.kernels.tt_contract import kernel as _kernel
from repro.kernels.tt_contract.ref import (
    tt_contract_batched_ref, tt_contract_ref, tt_dense_ref, tt_dequant_chain,
)


def resolve_tile_cap(b: int, tile: Optional[int] = None):
    """Token-dim tile cap candidates for a (B, N_in) activation extent,
    best first.

    Priority: explicit ``tile`` argument > TT_CONTRACT_TILE env var >
    adaptive default.  An explicit cap (arg or env) is a single candidate —
    tuning intent is never second-guessed; if its footprint fails the VMEM
    gate the chain falls back unfused, not to a different tile.  The
    adaptive default grows past the historical 512 cap when the flattened
    batch×token extent divides cleanly (fewer grid steps per launch), but
    keeps the smaller caps as fallbacks so growing the cap can only ever
    ADD fused coverage: a shape whose big-tile footprint flunks the gate
    retries at the tile it would have used before."""
    if tile is not None:
        return (_validated_cap(tile, "tile="),)
    env = os.environ.get("TT_CONTRACT_TILE")
    if env:
        return (_validated_cap(env, "the TT_CONTRACT_TILE env var"),)
    caps = [cap for cap in (2048, 1024) if b >= cap and b % cap == 0]
    return (*caps, _kernel.DEFAULT_TILE_CAP)


def _validated_cap(value, source: str) -> int:
    """An explicit tile cap must be a positive integer — reject junk with a
    message naming where it came from (a bad TT_CONTRACT_TILE used to
    surface as an opaque int() ValueError deep in the dispatch)."""
    try:
        cap = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"{source} must be a positive integer tile cap, got {value!r}"
        ) from None
    if cap <= 0:
        raise ValueError(
            f"{source} must be a positive integer tile cap, got {value!r}"
        )
    return cap


def _wide(g):
    """The dtype a core has inside the kernel: integer cores ride in their
    storage dtype, float cores are cast to f32 by the kernel entry point."""
    dt = jnp.dtype(g.dtype)
    return dt if jnp.issubdtype(dt, jnp.integer) else jnp.dtype(jnp.float32)


def _vmem_parts(x2, cores, n_out: int, split: int,
                tile_cap: int = _kernel.DEFAULT_TILE_CAP):
    """(block bytes, value bytes) of one grid step at the tile ``_grid_1d``
    will pick.  Blocks are the activation tile in and out, every core at
    its in-kernel dtype and the scale operand; values are the f32 partial
    products the body holds at once and, for integer cores, their widened
    copies.  Every array is counted in native VMEM tiles
    (``common.vmem_tile_bytes``), which pad small rank dims to 128 lanes."""
    f32 = jnp.float32
    tb = common.vmem_tile_bytes
    bb = _kernel._grid_1d(x2.shape[0], tile_cap)
    n_in = x2.shape[1]
    g0 = cores[0]
    n1, r1 = g0.shape
    blocks = tb((n1, r1), f32)
    values = tb((bb, r1), f32)
    if len(cores) == 2:
        g1 = cores[1]
        blocks += tb((r1, n_out), _wide(g1)) + tb((bb, n_in), f32)
        blocks += tb((bb, n_out), f32)
        values += tb((bb, n_out), f32)
        if _wide(g1) != f32:
            values += tb((r1, n_out), f32)
    else:
        g1, g2 = cores[1], cores[2]
        n_mid, r2 = g1.shape[1], g1.shape[2]
        n3 = g2.shape[1]
        blocks += tb((n_mid, r1, r2), _wide(g1)) + tb((r2, n3), _wide(g2))
        if split == 1:
            blocks += tb((bb, n_in), f32) + tb((n_mid, bb, n3), f32)
        else:
            blocks += tb((n_mid, bb, n_in // n_mid), f32) + tb((bb, n3), f32)
        values += tb((bb, r2), f32) + tb((bb, n3), f32)
        if _wide(g1) != f32:
            values += tb((r1, r2), f32) + tb((r2, n3), f32)
    if any(_wide(g) != f32 for g in cores[1:]):         # (1, r) row scales
        blocks += sum(tb((1, g.shape[0]), f32) for g in cores[1:])
    return blocks, values


def _vmem_bytes(x2, cores, n_out: int, split: int,
                tile_cap: int = _kernel.DEFAULT_TILE_CAP) -> int:
    """VMEM bytes of one grid step.  Pallas may double-buffer every block,
    resident cores included, so blocks count twice: an upper bound (on v5e
    the compiler took ``tt_contract_2`` up to about 2.5x the rank this
    admits at the same limit), so a chain the gate admits compiles."""
    blocks, values = _vmem_parts(x2, cores, n_out, split, tile_cap)
    return 2 * blocks + values


def _fits_vmem(x2, cores, n_out: int, split: int,
               tile_cap: int = _kernel.DEFAULT_TILE_CAP) -> bool:
    """The dispatch gate: one grid step fits the limit the kernels hand
    the compiler (``common.VMEM_LIMIT``) — one number for both."""
    return _vmem_bytes(x2, cores, n_out, split, tile_cap) <= common.VMEM_LIMIT


_TALLY: collections.Counter = collections.Counter()
_TALLY_LOCK = threading.Lock()


def _count(route: str, reason: str = "") -> None:
    with _TALLY_LOCK:
        _TALLY[(route, reason)] += 1


def dispatch_tally() -> dict:
    """``{(route, reason): count}`` of the dispatches traced so far: route
    is the fused kernel's name, or ``"unfused"`` with reason ``"depth"``
    (no fused body for this depth/split) or ``"vmem"`` (gate refused)."""
    with _TALLY_LOCK:
        return dict(_TALLY)


def reset_dispatch_tally() -> None:
    with _TALLY_LOCK:
        _TALLY.clear()


def tt_contract(
    x2: jax.Array,                  # (B, N_in)
    cores: Sequence[jax.Array],     # [g0 (n1,r1), g_k (r,n,s)..., last s==1]
    split: int,
    interpret: bool | None = None,
    tile: Optional[int] = None,     # token-dim tile cap override
    scales: Optional[Sequence[Optional[jax.Array]]] = None,
) -> jax.Array:                     # (B, N_out) float32
    """Contract activations straight through TT cores (no dense weight).

    ``scales`` (aligned with ``cores``: a core's (r,) row scales, ``None``
    for the already-wide first core) selects the dequant-fused kernels:
    integer cores ride into the kernel in storage dtype and each core's row
    scales multiply the partial product that meets it.  The unfused
    fallback contracts the explicitly dequantized chain."""
    if interpret is None:
        interpret = common.use_interpret()
    depth = len(cores)
    x2 = x2.astype(jnp.float32)
    quantized = scales is not None
    n_out = 1
    for g in cores[split:]:
        n_out *= g.shape[1]
    fusable = depth == 2 and split == 1 or depth == 3 and split in (1, 2)
    # first candidate cap whose grid-step footprint clears the VMEM gate
    cap = None
    for c in resolve_tile_cap(x2.shape[0], tile) if fusable else ():
        if _fits_vmem(x2, cores, n_out, split, c):
            cap = c
            break
    if cap is None:
        _count("unfused", "vmem" if fusable else "depth")
        if quantized:
            cores = tt_dequant_chain(cores, scales)
        return tt_contract_ref(x2, cores, split)

    # row scales ride in as (1, r) rows that broadcast over a partial product
    rows = ([jnp.asarray(sc, jnp.float32).reshape(1, -1)
             for sc in scales[1:]] if quantized else [])
    if depth == 2:
        g0, g1 = cores
        operands = [x2, g0, g1[:, :, 0] if g1.ndim == 3 else g1, *rows]
        name, kw = "tt_contract_2", {}
    else:
        g0, g1, g2 = cores
        operands = [x2, g0, g1.transpose(1, 0, 2),          # (n_mid, r1, r2)
                    g2[:, :, 0] if g2.ndim == 3 else g2, *rows]
        name, kw = "tt_contract_3", {"split": split}
    name += "q" if quantized else ""
    _count(name)
    call = functools.partial(getattr(_kernel, name), interpret=interpret,
                             tile_cap=cap, **kw)
    return _replicated_on_mesh(call)(*operands)


def _replicated_on_mesh(call):
    """GSPMD cannot partition a Mosaic kernel: under a multi-device mesh
    (``jax.set_mesh``) every device runs the whole chain on replicated
    operands, the activation tile gathered.  This must match the placement
    ``sharding.place_params`` gives TT leaves (replicated whole); when a
    rule there shards a TT chain, the specs come from that rule and this
    wrapper goes."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return call
    return jax.shard_map(call, in_specs=P(), out_specs=P(), check_vma=False)


def tt_contract_batched(
    x3: jax.Array,                  # (E, B, N_in)
    g0b: jax.Array,                 # (E, n1, r1) per-expert lead-absorbed
    cores: Sequence[jax.Array],     # shared tail [(r,n,s), ...], last s==1
    split: int,
    interpret: bool | None = None,
    tile: Optional[int] = None,
    scales: Optional[Sequence[Optional[jax.Array]]] = None,
) -> jax.Array:                     # (E, B, N_out) float32
    """Expert-batched TT chain: the whole bank in one launch.

    Experts share every tail core — only the lead-absorbed first core
    differs — so vmapping the fused dispatch over the expert axis gives the
    Pallas kernels an extra grid dimension (one launch, E×(B/bb) grid steps)
    while oversized chains still take the per-expert einsum fallback.  The
    VMEM gate applies per grid step, which is exactly the per-expert tile.

    ``scales`` aligns with the shared tail ``cores`` (the per-expert lead is
    handed in wide, its scales folded by the caller), so the row scales are
    expert-invariant and close over the vmap unbatched."""
    rest = list(cores)
    chain_scales = None if scales is None else [None] + list(scales)
    return jax.vmap(
        lambda x2, g0: tt_contract(x2, [g0] + rest, split,
                                   interpret=interpret, tile=tile,
                                   scales=chain_scales)
    )(x3, g0b)


__all__ = [
    "dispatch_tally", "reset_dispatch_tally", "resolve_tile_cap",
    "tt_contract", "tt_contract_batched",
    "tt_contract_batched_ref", "tt_contract_ref", "tt_dense_ref",
    "tt_dequant_chain",
]
