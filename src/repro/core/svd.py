"""Two-phase SVD (paper §II-A2): Householder bidiagonalization + diagonalization.

The paper's central algorithmic move is splitting SVD into:

  phase 1 (HBD)     A = U_B B V_B^T      — the hardware-accelerated phase
  phase 2 (diag)    B = Q  Σ  P^T         — "standard QR-based procedure",
                                            *unchanged* between their baseline
                                            and TT-Edge (Table III)

and composing      A = (U_B Q) Σ (P^T V_B^T) = U Σ V^T.

Phase 2 here defaults to the library path on the *compact* n×n bidiagonal
block (cheap: B is bidiagonal so this is O(n^2) work for the values plus
O(n^3) for the small basis products — tiny next to phase 1's O(M N^2), the
same asymmetry the paper measures as 3.6:1).  A pure-JAX Golub–Kahan QR
sweep lives in ``bidiag_qr.py`` and is selectable with
``diag_method="golub_kahan"``; tests use it as an independent oracle.

Also implements the paper's ``Sorting_Basis`` (Alg. 1 lines 18-25): sort σ
descending, permute the bases with the recorded index vector.  Hardware uses
bubble sort; any comparison sort yields the identical (σ_s, Ind) pair, so the
JAX path uses ``argsort`` (the Pallas bitonic-network kernel in
``kernels/singular_sort`` is the TPU-idiomatic hardware analogue).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.hbd import householder_bidiagonalize
from repro.core import blocked as _blocked


class SVDResult(NamedTuple):
    u: jax.Array
    s: jax.Array
    vt: jax.Array


def sorting_basis(u: jax.Array, s: jax.Array, vt: jax.Array) -> SVDResult:
    """Paper Sorting_Basis: descending sort of σ + basis permutation.

    Returns (U_s, Σ_s, V_s^T) with the same index vector applied to U's
    columns and V^T's rows (Alg. 1 line 22).
    """
    ind = jnp.argsort(-s)  # descending; the paper's bubble-sort index vector
    return SVDResult(u=u[:, ind], s=s[ind], vt=vt[ind, :])


@functools.partial(jax.jit, static_argnames=("method", "hbd_impl", "panel"))
def svd(
    a: jax.Array,
    method: str = "two_phase",
    hbd_impl: str = "unblocked",
    panel: int = 32,
) -> SVDResult:
    """SVD with selectable factorization path.

    method:
      "two_phase" — the paper's HBD + diagonalization split (default).
      "library"   — jnp.linalg.svd reference (the 'cloud' path in Fig. 1).
    hbd_impl:
      "unblocked" — paper-faithful Algorithm 2 (one reflector at a time).
      "blocked"   — WY/compact-blocked variant (MXU-friendly; beyond-paper).
    Always returns thin, descending-sorted factors: u (M,K), s (K,), vt (K,N)
    with K = min(M, N).
    """
    m, n = a.shape
    if method == "library":
        u, s, vt = jnp.linalg.svd(a, full_matrices=False)
        return sorting_basis(u, s, vt)
    if method != "two_phase":
        raise ValueError(f"unknown svd method: {method}")

    if m < n:
        # HBD expects tall matrices; SVD(A) = SVD(A^T) with factors swapped.
        r = svd(a.T, method=method, hbd_impl=hbd_impl, panel=panel)
        return SVDResult(u=r.vt.T, s=r.s, vt=r.u.T)

    orig = a.dtype
    u_b, bn, v_bt = _phase1(a.astype(jnp.float32), hbd_impl, panel)
    # Phase 2 on the compact n×n bidiagonal block.
    with jax.named_scope("hbd.phase2"):
        q, s, pt = jnp.linalg.svd(bn, full_matrices=False)
        res = _compose(u_b, q, s, pt, v_bt)
    return SVDResult(
        u=res.u.astype(orig), s=res.s.astype(orig), vt=res.vt.astype(orig)
    )


@functools.partial(jax.jit, static_argnames=("hbd_impl", "panel"))
def _phase1(a32, hbd_impl, panel):
    """Tall f32 A → (thin U_B, B[:N, :N], V_B^T)."""
    with jax.named_scope("hbd.phase1"):
        if hbd_impl == "blocked":
            u_b, b, v_bt = _blocked.blocked_bidiagonalize(a32, panel=panel)
        elif hbd_impl == "unblocked":
            u_b, b, v_bt = householder_bidiagonalize(a32)
        else:
            raise ValueError(f"unknown hbd_impl: {hbd_impl}")
        n = a32.shape[1]
        return u_b, b[:n, :n], v_bt


@jax.jit
def _compose(u_b, q, s, pt, v_bt) -> SVDResult:
    """A = (U_B Q) Σ (P^T V_B^T), sorted; U_B is thin (M×N).  One bf16
    pass is the TPU default for f32 products: ask for full f32."""
    hi = jax.lax.Precision.HIGHEST
    return sorting_basis(jnp.dot(u_b, q, precision=hi), s,
                         jnp.dot(pt, v_bt, precision=hi))


def svd_host_diag(a: jax.Array, hbd_impl: str = "unblocked",
                  panel: int = 32) -> SVDResult:
    """Two-phase SVD for host-orchestrated callers (the serial TT-SVD):
    phase 1 on the device, phase 2 — the N×N bidiagonal — on the host's
    LAPACK, where the paper's processor runs it too.

    ``svd`` keeps phase 2 in the graph for the batched and in-graph paths.
    Here that would compile ``jnp.linalg.svd`` for the TPU once per
    unfolding shape: for qwen1.5-0.5b on a v5e those compiles took 587 s of
    a 690 s compression, while the HBD of a 24,576×1024 unfolding compiles
    in seconds.
    """
    m, n = a.shape
    if m < n:
        r = svd_host_diag(a.T, hbd_impl=hbd_impl, panel=panel)
        return SVDResult(u=r.vt.T, s=r.s, vt=r.u.T)
    u_b, bn, v_bt = _phase1(jnp.asarray(a, jnp.float32), hbd_impl, panel)
    q, s, pt = np.linalg.svd(np.asarray(bn), full_matrices=False)
    # B down, its three factors up (and A up when it came from the host)
    obs.count("compress.host_transfers", 4 + (not isinstance(a, jax.Array)))
    res = _compose(u_b, jnp.asarray(q), jnp.asarray(s), jnp.asarray(pt),
                   v_bt)
    return SVDResult(
        u=res.u.astype(a.dtype), s=res.s.astype(a.dtype),
        vt=res.vt.astype(a.dtype),
    )


@functools.partial(jax.jit, static_argnames=("method", "hbd_impl", "panel"))
def svd_batched(
    a: jax.Array,
    method: str = "two_phase",
    hbd_impl: str = "unblocked",
    panel: int = 32,
) -> SVDResult:
    """Batched SVD of a (B, M, N) stack — one launch, B factorizations.

    vmaps the selected factorization path (two-phase HBD included), so a
    bucket of same-shape unfoldings costs a single dispatch instead of B.
    Member k of the result equals ``svd(a[k], ...)`` exactly.
    """
    if a.ndim != 3:
        raise ValueError(f"svd_batched expects (B, M, N), got {a.shape}")
    fn = functools.partial(svd, method=method, hbd_impl=hbd_impl, panel=panel)
    return jax.vmap(fn)(a)


def svd_reconstruct(r: SVDResult) -> jax.Array:
    return (r.u * r.s[None, :]) @ r.vt
