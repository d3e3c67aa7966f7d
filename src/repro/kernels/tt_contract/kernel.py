"""Fused TT-contraction Pallas kernels — the TT-native serving hot path.

A TT-compressed layer weight applied to activations is a short chain of
small matmuls (eq. (1)/(2) contractions with the activation folded in).
Unfused, every intermediate ``(B, ·)`` tensor round-trips through HBM and
each hop is a separate dispatch; fused, the whole chain runs out of one
VMEM residency per activation tile — decode-sized batches are latency-bound
on exactly that.

Two bodies cover the shapes the model zoo produces (``tensorize_dims``
keeps ≥3-D stacked layer weights mode-per-axis, so after the layer index is
absorbed a (L,D,F) MLP weight is a 2-core chain and (L,D,H,K)/(L,H,K,D)
attention weights are 3-core chains):

  * ``tt_contract_2`` — y = (x @ g0) @ g1
  * ``tt_contract_3`` — 3-core chain, input/output structure selected by
    ``split`` (1 = one input core, 2 = two input cores)

Cores sit whole in VMEM (they are the *compressed* payload — KBs); the
grid tiles the token dimension.  Deeper chains fall back to the jnp oracle
(``ref.py``) in ``ops.py``.

The 3-core bodies never reshape across the lane axis (Mosaic refuses such
shape casts): the middle core arrives as ``(n_mid, r1, r2)`` and the body
loops over its leading mode, so every in-kernel value is a plain 2-D tile.
The wrappers lay the mode that the loop walks out as the leading axis of an
HBM array — the split-2 input as ``(n_mid, B, n1)``, the split-1 output as
``(n_mid, B, n3)`` — and transpose it back outside the kernel.

Every ``pallas_call`` here is given ``common.VMEM_LIMIT`` as its scoped
VMEM limit: the same number ``ops._fits_vmem`` admits chains against.

Quantized variants (``tt_contract_2q``/``tt_contract_3q``) take the tail
cores in their integer STORAGE dtype — int8 rides HBM→VMEM at one byte per
element, the cast to f32 happens on the VMEM tile inside the kernel body,
and each core's symmetric dequant scales (one per leading-rank row, passed
as a (1, r) f32 row) multiply the f32 partial product just before it
meets that core — the chain is linear in each core, so scaling a core's
rows and the product's matching columns is the same map.  The wide form of
a stored core never exists outside VMEM — that is the whole point: decode
streams int8, the MXU computes f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _params():
    return pltpu.CompilerParams(vmem_limit_bytes=common.VMEM_LIMIT)


def _tt2_kernel(x_ref, g0_ref, g1_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    t = _dot(x, g0_ref[...])                              # (bb, r1)   MXU
    o_ref[...] = _dot(t, g1_ref[...])                     # (bb, n2)   MXU


def _tt3_body(x_ref, g0, g1_at, g2, o_ref, *, split, s1=None, s2=None):
    """3-core chain on one activation tile.  ``g0``/``g2`` are wide f32
    values, ``g1_at(j)`` loads the middle core's mode-j slice (r1, r2) wide;
    ``s1``/``s2`` are the (1, r1)/(1, r2) row scales of g1/g2 (None: wide).

    split=1: x (bb,n1) · g0 (n1,r1) · g1[j] (r1,r2) · g2 (r2,n3)
             → o[j] (bb,n3) for each middle output mode j.
    split=2: x[j] (bb,n1) · g0 (n1,r1) · g1[j] (r1,r2), summed over the
             second input mode j, then · g2 (r2,n3) → o (bb,n3).
    """
    def rows(v, s):
        return v if s is None else v * s

    n_mid = o_ref.shape[0] if split == 1 else x_ref.shape[0]
    if split == 1:
        t = rows(_dot(x_ref[...].astype(jnp.float32), g0), s1)  # (bb, r1)

        def expand(j, carry):
            u = rows(_dot(t, g1_at(j)), s2)                  # (bb, r2)
            o_ref[j] = _dot(u, g2)                          # (bb, n3)
            return carry

        jax.lax.fori_loop(0, n_mid, expand, 0)
        return

    def contract(j, acc):
        u = rows(_dot(x_ref[j].astype(jnp.float32), g0), s1)  # (bb, r1)
        return acc + _dot(u, g1_at(j))                      # (bb, r2)

    acc = jax.lax.fori_loop(
        0, n_mid, contract,
        jnp.zeros((x_ref.shape[1], g2.shape[0]), jnp.float32))
    o_ref[...] = _dot(rows(acc, s2), g2)                    # (bb, n3)


def _tt3_kernel(x_ref, g0_ref, g1_ref, g2_ref, o_ref, *, split):
    _tt3_body(x_ref, g0_ref[...], lambda j: g1_ref[j], g2_ref[...], o_ref,
              split=split)


def _tt2q_kernel(x_ref, g0_ref, g1_ref, s1_ref, o_ref):
    """Dequant-fused 2-core body: g1 arrives in its storage dtype (int8) and
    widens on the VMEM tile; its (1, r1) row scales multiply the partial
    product before it meets g1."""
    x = x_ref[...].astype(jnp.float32)
    t = _dot(x, g0_ref[...].astype(jnp.float32)) * s1_ref[...]   # (bb, r1)
    o_ref[...] = _dot(t, g1_ref[...].astype(jnp.float32))       # (bb, n2)


def _tt3q_kernel(x_ref, g0_ref, g1_ref, g2_ref, s1_ref, s2_ref, o_ref, *,
                 split):
    """Dequant-fused 3-core body: same dataflow as ``_tt3_kernel`` but the
    tail cores (g1, g2) stay in storage dtype until the in-VMEM cast, and
    their row scales multiply the partial products that meet them."""
    _tt3_body(x_ref, g0_ref[...], lambda j: g1_ref[j].astype(jnp.float32),
              g2_ref[...].astype(jnp.float32), o_ref, split=split,
              s1=s1_ref[...], s2=s2_ref[...])


DEFAULT_TILE_CAP = 512


def _grid_1d(b: int, cap: int = DEFAULT_TILE_CAP):
    """Token-dim tile: first of (cap, cap/2, cap/4) that divides b and is a
    whole number of 8-row sublane tiles (Mosaic's block rule), else the
    whole batch in one block.  ops.py gates kernel eligibility on the VMEM
    footprint of the tile THIS returns, so an indivisible huge batch (whole-b
    block) falls back to the unfused chain instead of blowing VMEM.

    ``cap`` is the tunable upper bound (ops.py resolves it from the
    TT_CONTRACT_TILE env var / call argument, growing it when the token
    extent allows) — bigger tiles amortize grid overhead per launch, the
    VMEM gate keeps them honest."""
    for t in (cap, cap // 2, cap // 4):
        if b > t and b % t == 0 and t % 8 == 0:
            return t
    return b


@functools.partial(jax.jit, static_argnames=("interpret", "tile_cap"))
def tt_contract_2(x, g0, g1, interpret: bool = False,
                  tile_cap: int = DEFAULT_TILE_CAP):
    """(B, n1) · (n1, r1) · (r1, n2) → (B, n2), one launch."""
    b, n1 = x.shape
    n2 = g1.shape[1]
    bb = _grid_1d(b, tile_cap)
    return pl.pallas_call(
        _tt2_kernel,
        grid=(b // bb,),
        in_specs=[
            pl.BlockSpec((bb, n1), lambda i: (i, 0)),
            pl.BlockSpec(g0.shape, lambda i: (0, 0)),
            pl.BlockSpec(g1.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, n2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n2), jnp.float32),
        compiler_params=_params(),
        interpret=interpret,
    )(x, g0.astype(jnp.float32), g1.astype(jnp.float32))


def _tt3_call(kern, x, g0, g1, g2, extra, split, interpret, tile_cap):
    """Shared 3-core launch: lays the looped mode out as a leading HBM axis
    (split 2 input / split 1 output) so the body needs no lane reshape."""
    b, n_in = x.shape
    n_mid, n3 = g1.shape[0], g2.shape[1]
    bb = _grid_1d(b, tile_cap)
    if split == 1:
        x_in, x_spec = x, pl.BlockSpec((bb, n_in), lambda i: (i, 0))
        out_shape = (n_mid, b, n3)
        out_spec = pl.BlockSpec((n_mid, bb, n3), lambda i: (0, i, 0))
    else:
        # x[b, i*n_mid + j] → (n_mid, B, n1): mode j leads
        x_in = x.reshape(b, n_in // n_mid, n_mid).transpose(2, 0, 1)
        x_spec = pl.BlockSpec((n_mid, bb, n_in // n_mid),
                              lambda i: (0, i, 0))
        out_shape, out_spec = (b, n3), pl.BlockSpec((bb, n3),
                                                    lambda i: (i, 0))
    y = pl.pallas_call(
        functools.partial(kern, split=split),
        grid=(b // bb,),
        in_specs=[
            x_spec,
            pl.BlockSpec(g0.shape, lambda i: (0, 0)),
            pl.BlockSpec(g1.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(g2.shape, lambda i: (0, 0)),
        ] + [pl.BlockSpec(e.shape, lambda i: (0, 0)) for e in extra],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=_params(),
        interpret=interpret,
    )(x_in, g0, g1, g2, *extra)
    if split == 1:
        y = y.transpose(1, 0, 2).reshape(b, n_mid * n3)
    return y


@functools.partial(
    jax.jit, static_argnames=("split", "interpret", "tile_cap"),
)
def tt_contract_3(x, g0, g1, g2, *, split: int, interpret: bool = False,
                  tile_cap: int = DEFAULT_TILE_CAP):
    """Fused 3-core chain: x (B, N_in) · g0 (n1, r1) · g1 (n_mid, r1, r2)
    · g2 (r2, n3) → (B, N_out); ``g1`` comes mode-major from ops.py."""
    return _tt3_call(
        _tt3_kernel, x, g0.astype(jnp.float32), g1.astype(jnp.float32),
        g2.astype(jnp.float32), (), split, interpret, tile_cap)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_cap"))
def tt_contract_2q(x, g0, g1, s1, interpret: bool = False,
                   tile_cap: int = DEFAULT_TILE_CAP):
    """Quantized 2-core chain: g1 passes through in storage dtype (int8) —
    one byte per element over HBM→VMEM — and ``s1`` (1, r1) holds its row
    scales.  g0 is the lead-absorbed first core, already wide with its
    scales folded host-side."""
    b, n1 = x.shape
    n2 = g1.shape[1]
    bb = _grid_1d(b, tile_cap)
    return pl.pallas_call(
        _tt2q_kernel,
        grid=(b // bb,),
        in_specs=[
            pl.BlockSpec((bb, n1), lambda i: (i, 0)),
            pl.BlockSpec(g0.shape, lambda i: (0, 0)),
            pl.BlockSpec(g1.shape, lambda i: (0, 0)),
            pl.BlockSpec(s1.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, n2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n2), jnp.float32),
        compiler_params=_params(),
        interpret=interpret,
    )(x, g0.astype(jnp.float32), g1, s1.astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("split", "interpret", "tile_cap"),
)
def tt_contract_3q(x, g0, g1, g2, s1, s2, *, split: int,
                   interpret: bool = False,
                   tile_cap: int = DEFAULT_TILE_CAP):
    """Quantized 3-core chain: tail cores (g1, g2) pass through in storage
    dtype; ``s1`` (1, r1) and ``s2`` (1, r2) are their row scales."""
    return _tt3_call(
        _tt3q_kernel, x, g0.astype(jnp.float32), g1, g2,
        (s1.astype(jnp.float32), s2.astype(jnp.float32)), split, interpret,
        tile_cap)
