"""Householder bidiagonalization (paper Algorithm 2), in pure JAX.

This is the paper-faithful implementation of the HBD-ACC datapath:

  * ``house``            — the HOUSE function (eqs. (3)/(5)): given x, produce
                           the Householder vector v and the resulting pivot
                           value q = -sign(x_1) * ||x||.
  * ``house_mm_update``  — the HOUSE_MM_UPDATE procedure: apply the reflector
                           to a trailing submatrix as *two GEMMs* plus a
                           vector-by-scalar division, exactly as the paper
                           formulates it for GEMM-accelerator reuse:
                               beta  = v[0] * q
                               vec1  = v / beta        (order == 0)
                               vec2  = v^T @ SubArray
                               SubArray += vec1 @ vec2
  * ``householder_bidiagonalize`` — the full Algorithm-2 loop
                           (Householder *reduction* followed by Householder
                           *accumulation* of U_B and V_B^T), expressed with
                           ``jax.lax.fori_loop`` and static-shape masking so
                           that it JIT-compiles for any (M, N).

Faithfulness notes
------------------
The paper operates on sub-views ``A[i:M, i:N]`` with shrinking shapes; XLA
requires static shapes, so we implement the identical arithmetic with
*masking*: at step i every vector is full-length with entries < i forced to
zero.  A masked Householder vector produces a reflector that acts as the
identity on the masked prefix, which is exactly the "embed the (M-i)×(M-i)
reflector into the lower-right corner of an M×M identity" construction used
in LAPACK/ScaLAPACK — the arithmetic matches the paper's element-for-element.

The blocked (WY) variant used for MXU efficiency lives in
``repro/core/blocked.py``; THIS file is the recorded paper baseline.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

# f32 products at DEFAULT precision run as one bf16 pass on TPU; the
# reflectors compound over N steps, so every HBD product asks for full f32.
_HI = jax.lax.Precision.HIGHEST


class HouseResult(NamedTuple):
    q: jax.Array  # the pivot value: -sign(x1) * ||x||
    v: jax.Array  # the (masked, unnormalized) Householder vector


def _sign(x: jax.Array) -> jax.Array:
    """sign(x) with sign(0) := 1 (LAPACK convention; avoids zero reflectors)."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def house(x: jax.Array, mask: jax.Array) -> HouseResult:
    """Paper HOUSE (Alg. 2 lines 22-26) on a masked full-length vector.

    x    : (L,) the column/row to reduce; entries where ``mask`` is False are
           ignored (they correspond to the A[:i] prefix the paper never sees).
    mask : (L,) bool, True on the active suffix. mask[i0] marks x_1.

    Returns q = -sign(x1)*||x|| and v with v[i0] = x1 + sign(x1)*||x||.
    """
    x = jnp.where(mask, x, 0.0)
    norm = jnp.linalg.norm(x)
    # x1 = first *active* element. mask is a suffix mask, so argmax finds it.
    i0 = jnp.argmax(mask)
    x1 = x[i0]
    s = _sign(x1)
    q = -s * norm
    v = x.at[i0].add(s * norm)
    v = jnp.where(mask, v, 0.0)
    return HouseResult(q=q, v=v)


def house_mm_update(
    q: jax.Array,
    v: jax.Array,
    sub: jax.Array,
    order: int | jax.Array,
    row_mask: jax.Array,
    col_mask: jax.Array,
) -> jax.Array:
    """Paper HOUSE_MM_UPDATE (Alg. 2 lines 27-32) with static shapes.

    order == 0: left transform,   sub += (v/beta) @ (v^T sub)
    order == 1: right transform,  sub += (sub v^T... ) — the paper writes the
                symmetric form: vec1 = sub @ v (row-space), vec2 = v/beta.

    beta = v[first_active] * q.  For a Householder vector built by HOUSE,
    v^T v = 2 * v1 * (v1 - x1 + x1) ... = -2 * v1 * q, hence
    I - 2 v v^T / (v^T v) = I + v v^T / (v1 q) = I + (v/beta) v^T.
    The update is numerically identical to applying the reflector H.

    row_mask/col_mask confine the update to the active trailing block, which
    is mathematically a no-op (v is already masked) but keeps the untouched
    region bit-exact with the paper's sub-view semantics.
    """
    left = _is_left_static(order)
    v = jnp.where(row_mask if left else col_mask, v, 0.0)
    i0 = jnp.argmax(row_mask) if left else jnp.argmax(col_mask)
    beta = v[i0] * q

    # Guard: if the active column is already zero, beta == 0 and H == I.
    safe = jnp.abs(beta) > 0
    inv_beta = jnp.where(safe, 1.0 / jnp.where(safe, beta, 1.0), 0.0)

    if _is_left_static(order):
        vec1 = v * inv_beta                      # (M,)   — VEC DIVISION stage
        vec2 = jnp.dot(v, sub, precision=_HI)    # (N,)   — GEMM #1
        upd = jnp.outer(vec1, vec2)              # (M, N) — GEMM #2 (rank-1)
    else:
        vec1 = jnp.dot(sub, v, precision=_HI)    # (M,)   — GEMM #1
        vec2 = v * inv_beta                      # (N,)   — VEC DIVISION stage
        upd = jnp.outer(vec1, vec2)              # (M, N) — GEMM #2 (rank-1)
    return sub + upd


def _is_left_static(order) -> bool:
    if isinstance(order, (int, bool)):
        return int(order) == 0
    raise TypeError("order must be a static python int (0=left, 1=right)")


@jax.jit
def hbd_reduce(a: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Algorithm 2's reduction loop (i = 1..N) on a tall f32 A (M×N).

    Returns ``(a_red, diag, super_)``: the Householder vectors retained in
    place in A's zeroed-out wings (column i below the diagonal holds v_L,
    row i right of the superdiagonal holds v_R) — the software analogue of
    the paper's "on-chip retention of Householder vectors" — and B's two
    bands.  Reflector i is ``I + v v^T / (v[i] q)`` with q its band value.
    """
    m, n = a.shape
    rows = jnp.arange(m)
    cols = jnp.arange(n)

    def reduction_step(i, carry):
        a_, diag, super_ = carry
        row_mask = rows >= i          # active rows  i..M
        col_mask = cols >= i + 1      # active cols  i+1..N (for the right xform)

        # ---- left transform: eliminate sub-diagonal of column i ----
        x = a_[:, i]
        q, v_l = house(x, row_mask)
        diag = diag.at[i].set(q)                      # B[i, i]
        sub = jnp.where(row_mask[:, None] & col_mask[None, :], a_, 0.0)
        sub = house_mm_update(q, v_l, sub, 0, row_mask, col_mask)
        a_ = jnp.where(row_mask[:, None] & col_mask[None, :], sub, a_)
        # retain v_L in the reduced column (paper line 7: A[i,i] <- v_L[1])
        a_ = a_.at[:, i].set(jnp.where(row_mask, v_l, a_[:, i]))

        # ---- right transform: eliminate row i beyond the superdiagonal ----
        def right(a_, super_):
            y = a_[i, :]
            qr_, v_r = house(y, col_mask)
            super_ = super_.at[i].set(qr_)            # B[i, i+1]
            rmask2 = rows >= i + 1
            sub2 = jnp.where(rmask2[:, None] & col_mask[None, :], a_, 0.0)
            sub2 = house_mm_update(qr_, v_r, sub2, 1, rmask2, col_mask)
            a_ = jnp.where(rmask2[:, None] & col_mask[None, :], sub2, a_)
            a_ = a_.at[i, :].set(jnp.where(col_mask, v_r, a_[i, :]))
            return a_, super_

        def no_right(a_, super_):
            return a_, super_

        a_, super_ = jax.lax.cond(i < n - 1, right, no_right, a_, super_)
        return a_, diag, super_

    diag0 = jnp.zeros((n,), jnp.float32)
    super0 = jnp.zeros((n,), jnp.float32)
    return jax.lax.fori_loop(0, n, reduction_step, (a, diag0, super0))


@functools.partial(jax.jit, static_argnames=("compute_uv",))
def householder_bidiagonalize(
    a: jax.Array, compute_uv: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paper Algorithm 2: A (M×N, M>=N) -> (U_B, B, V_B^T) with thin U_B.

    A = U_B · B · V_B^T, U_B^T U_B = I_N, V_B orthogonal (N×N).

    B is upper-bidiagonal (returned as a dense N×N matrix whose only nonzeros
    are B[i,i] and B[i,i+1] — the dense form is what downstream phase-2
    diagonalization consumes; rows N..M of the full M×N form are zero).
    U_B is the thin M×N accumulation: the first N columns of the full
    product of left reflectors, which is all the SVD composes (an M×M U_B
    is 4 TB at M = 2^20).

    Implements both loops of Algorithm 2:
      * reduction   (i = 1..N): ``hbd_reduce``.
      * accumulation(i = N..1): HOUSE_MM_UPDATE on U_B and V_B^T using the
        retained vectors.
    """
    m, n = a.shape
    if m < n:
        raise ValueError(f"HBD expects M >= N, got {a.shape}; transpose first")
    orig_dtype = a.dtype
    a_red, diag, super_ = hbd_reduce(a.astype(jnp.float32))

    rows = jnp.arange(m)
    cols = jnp.arange(n)
    # Dense bidiagonal B (N×N): diag + superdiagonal.
    b = jnp.zeros((n, n), jnp.float32)
    b = b.at[cols, cols].set(diag)
    b = b.at[cols[:-1], cols[:-1] + 1].set(super_[:-1])

    if not compute_uv:
        eye_small = jnp.zeros((0, 0), orig_dtype)
        return eye_small, b.astype(orig_dtype), eye_small

    # ---- accumulation loop (Alg. 2 lines 14-18), i = N..1 ----
    u_b0 = jnp.eye(m, n, dtype=jnp.float32)
    v_bt0 = jnp.eye(n, dtype=jnp.float32)

    def accumulation_step(k, carry):
        i = n - 1 - k                     # i walks N-1 .. 0
        u_b, v_bt = carry
        row_mask = rows >= i
        col_mask = cols >= i + 1

        v_l = jnp.where(row_mask, a_red[:, i], 0.0)
        q_l = diag[i]
        # update columns i.. of the thin U_B in the active row block:
        # columns j < i are still e_j, on which H_i acts as the identity.
        ucols = cols >= i
        usub = jnp.where(row_mask[:, None] & ucols[None, :], u_b, 0.0)
        usub = house_mm_update(q_l, v_l, usub, 0, row_mask, ucols)
        u_b = jnp.where(row_mask[:, None] & ucols[None, :], usub, u_b)

        def acc_right(v_bt):
            # Backward accumulation, paper order-1 form: V_B^T <- V_B^T @ H_i^R
            # (vec1 = SubArray @ v, vec2 = v/beta, SubArray += vec1 (x) vec2).
            # Accumulating right-multiplications for i = N..1 yields
            # H_N ... H_1 = V_B^T.  Rows 0..i of V_B^T are still e_j^T at this
            # point (identity block), for which the update is a no-op, so we
            # confine it to the active i+1.. row block.
            v_r = jnp.where(col_mask, a_red[i, :], 0.0)
            q_r = super_[i]
            vsub = jnp.where(col_mask[:, None], v_bt, 0.0)
            vsub = house_mm_update(q_r, v_r, vsub, 1, col_mask, col_mask)
            return jnp.where(col_mask[:, None], vsub, v_bt)

        v_bt = jax.lax.cond(i < n - 1, acc_right, lambda v: v, v_bt)
        return u_b, v_bt

    u_b, v_bt = jax.lax.fori_loop(0, n, accumulation_step, (u_b0, v_bt0))
    return (
        u_b.astype(orig_dtype),
        b.astype(orig_dtype),
        v_bt.astype(orig_dtype),
    )


@functools.partial(jax.jit, static_argnames=("compute_uv",))
def householder_bidiagonalize_batched(
    a: jax.Array, compute_uv: bool = True
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched Algorithm 2 over a (B, M, N) stack: one launch, B HBDs.

    Everything in the unblocked loop is masking arithmetic + fori_loop, so
    ``jax.vmap`` lifts it wholesale; member k equals
    ``householder_bidiagonalize(a[k])`` exactly.  This is the vmap'd entry
    the batched TT-SVD planner feeds whole same-shape buckets through.
    """
    if a.ndim != 3:
        raise ValueError(f"expected (B, M, N), got {a.shape}")
    fn = functools.partial(householder_bidiagonalize, compute_uv=compute_uv)
    return jax.vmap(fn)(a)


def bidiagonal_bands(b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Extract (diag, superdiag) bands from a dense M×N upper-bidiagonal B."""
    n = b.shape[1]
    idx = jnp.arange(n)
    d = b[idx, idx]
    e = b[idx[:-1], idx[:-1] + 1]
    return d, e
