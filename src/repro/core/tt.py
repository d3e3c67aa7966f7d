"""Tensor-Train Decomposition (paper Algorithm 1) and TT reconstruction.

Two execution paths, one algorithm:

* ``ttd``        — the offline path: concrete shapes, truly dynamic δ-ranks
                   (NumPy orchestration around JAX SVDs).  This is what the
                   paper's processor runs end-to-end and what the Table-I /
                   Table-III benchmarks measure.
* ``ttd_static`` — the in-graph path: jittable, fixed max-rank cores with
                   zero-masked tails, usable inside a pjit'd train step for
                   TT-compressed cross-pod parameter sync
                   (``core/comm_compress.py``).

Plus ``tt_reconstruct`` (eq. (1)/(2): chained contractions, each one a
matrix multiplication + reshape — this is what the receiving node in Fig. 1
executes) and compression accounting helpers.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.svd import svd as _svd_fn, svd_host_diag
from repro.core import truncation as _trunc


@dataclass
class TTTensor:
    """A tensor in TT format: cores[k] has shape (r_{k-1}, n_k, r_k)."""

    cores: List[jax.Array]
    shape: Tuple[int, ...]           # original tensor shape (n_1..n_N)
    ranks: Tuple[int, ...]           # (r_0=1, r_1, ..., r_N=1) — live ranks
    eps: float = 0.0

    @property
    def num_params(self) -> int:
        return int(sum(int(np.prod(c.shape)) for c in self.cores))

    @property
    def live_params(self) -> int:
        """Parameter count at the live (δ-selected) ranks, even if cores are
        physically padded to max rank (static path)."""
        r = self.ranks
        return int(
            sum(r[k] * n * r[k + 1] for k, n in enumerate(self.shape))
        )

    @property
    def compression_ratio(self) -> float:
        return float(np.prod(self.shape)) / max(self.live_params, 1)


def _as_2d(x, rows):
    return x.reshape(rows, -1)


def ttd(
    w,
    eps: float = 0.05,
    dims: Optional[Sequence[int]] = None,
    svd_method: str = "two_phase",
    hbd_impl: str = "unblocked",
    max_rank: Optional[int] = None,
) -> TTTensor:
    """Paper Algorithm 1 — offline TT-SVD with dynamic δ-ranks.

    w: array-like; ``dims`` optionally re-tensorizes it (prod must match).
    eps: prescribed relative accuracy ε; guarantees
         ||W - W_R||_F <= ε ||W||_F  (Oseledets 2011, the bound the paper's
         δ = ε/√(d-1)·||W||_F per-step budget enforces).
    """
    obs.count("compress.host_transfers", isinstance(w, jax.Array))
    w = np.asarray(jax.device_get(w), dtype=np.float32)
    if dims is not None:
        assert int(np.prod(dims)) == w.size, (dims, w.shape)
        w = w.reshape(tuple(dims))
    shape = w.shape
    d = w.ndim
    if d == 1:
        obs.count("compress.host_transfers")
        core = jnp.asarray(w[None, :, None])
        return TTTensor(cores=[core], shape=shape, ranks=(1, 1), eps=eps)

    frob = float(np.linalg.norm(w))
    delta = float(_trunc.delta_threshold(eps, d, frob))

    cores: List[jax.Array] = []
    ranks = [1]
    w_temp = w
    for k in range(d - 1):
        rows = ranks[-1] * shape[k]
        mat = _as_2d(w_temp, rows)                          # Reshape (line 7)
        if svd_method == "two_phase":                       # SVD+Sorting (8-9)
            res = svd_host_diag(jnp.asarray(mat), hbd_impl=hbd_impl)
        else:
            res = _svd_fn(jnp.asarray(mat), method=svd_method,
                          hbd_impl=hbd_impl)
        u = np.asarray(res.u)
        s = np.asarray(res.s)
        vt = np.asarray(res.vt)
        # the unfolding up, its three factors down, the core up
        obs.count("compress.host_transfers", 5)
        r = _trunc.truncation_rank(s, delta)                # δ-Trunc. (10)
        if max_rank is not None:
            r = min(r, max_rank)
        u, s, vt = u[:, :r], s[:r], vt[:r, :]
        w_temp = (s[:, None] * vt)                          # Σ_t V_t^T (11)
        cores.append(jnp.asarray(u.reshape(ranks[-1], shape[k], r)))
        ranks.append(r)
    cores.append(jnp.asarray(w_temp.reshape(ranks[-1], shape[-1], 1)))
    obs.count("compress.host_transfers")
    ranks.append(1)
    return TTTensor(cores=cores, shape=shape, ranks=tuple(ranks), eps=eps)


def tt_reconstruct(tt: TTTensor, dtype=None):
    """Eq. (1)/(2): W_R = G_1 ×₁ G_2 ×₁ … ×₁ G_N via matmul+reshape chain."""
    cores = tt.cores
    acc = cores[0]                                  # (1, n_1, r_1)
    for g in cores[1:]:
        r = g.shape[0]
        acc = jnp.dot(_as_2d(acc, acc.size // r), _as_2d(g, r),
                      precision=jax.lax.Precision.HIGHEST)  # contraction (2)
    out = acc.reshape(tt.shape)
    return out.astype(dtype) if dtype is not None else out


# ---------------------------------------------------------------------------
# In-graph (static-shape) TT-SVD
# ---------------------------------------------------------------------------

@dataclass
class StaticTT:
    """Jittable TT: stacked cores padded to max ranks, live ranks as array."""

    cores: List[jax.Array]            # cores[k]: (rmax_{k-1}, n_k, rmax_k)
    ranks: jax.Array                  # (N+1,) int32 live ranks (traced)
    shape: Tuple[int, ...]


def tt_max_ranks(shape: Sequence[int], max_rank: int) -> List[int]:
    """Theoretical TT max ranks min(prod-left, prod-right), clipped."""
    d = len(shape)
    out = [1]
    for k in range(1, d):
        left = int(np.prod(shape[:k]))
        right = int(np.prod(shape[k:]))
        out.append(min(left, right, max_rank))
    out.append(1)
    return out


@functools.partial(
    jax.jit, static_argnames=("eps", "max_rank", "svd_method", "hbd_impl")
)
def ttd_static(
    w: jax.Array,
    eps: float = 0.05,
    max_rank: int = 64,
    svd_method: str = "library",
    hbd_impl: str = "unblocked",
) -> StaticTT:
    """Algorithm 1 with static shapes: cores padded to max ranks, δ-rank
    tracked as a traced value and the tails zero-masked.

    The zero-masking makes the padded reconstruction *exactly equal* to the
    dynamic-rank reconstruction, while every shape stays compile-time
    constant — the property the in-graph comm-compression path relies on.
    """
    shape = w.shape
    d = w.ndim
    rmax = tt_max_ranks(shape, max_rank)
    frob = jnp.linalg.norm(w.astype(jnp.float32))
    delta = _trunc.delta_threshold(eps, d, frob)

    cores: List[jax.Array] = []
    ranks = [jnp.asarray(1, jnp.int32)]
    # w_temp lives padded: (rmax_k, prod(shape[k:]))
    w_temp = w.astype(jnp.float32).reshape(1, -1)
    for k in range(d - 1):
        rows = rmax[k] * shape[k]
        tail = int(np.prod(shape[k + 1:]))
        mat = w_temp.reshape(rows, tail)
        kdim = min(rows, tail)
        res = _svd_fn(mat, method=svd_method, hbd_impl=hbd_impl)
        u, s, vt, r = _trunc.truncate_masked(res.u, res.s, res.vt, delta)
        r = jnp.minimum(r, rmax[k + 1])
        keep = jnp.arange(kdim) < r
        u = u * keep[None, :].astype(u.dtype)
        s = s * keep.astype(s.dtype)
        vt = vt * keep[:, None].astype(vt.dtype)
        # pad/crop factor rank-dim to rmax[k+1]
        rk1 = rmax[k + 1]
        if kdim >= rk1:
            u, s, vt = u[:, :rk1], s[:rk1], vt[:rk1, :]
        else:
            u = jnp.pad(u, ((0, 0), (0, rk1 - kdim)))
            s = jnp.pad(s, (0, rk1 - kdim))
            vt = jnp.pad(vt, ((0, rk1 - kdim), (0, 0)))
        cores.append(u.reshape(rmax[k], shape[k], rk1))
        ranks.append(r)
        w_temp = s[:, None] * vt                       # (rmax_{k+1}, tail)
    cores.append(w_temp.reshape(rmax[d - 1], shape[d - 1], 1))
    ranks.append(jnp.asarray(1, jnp.int32))
    return StaticTT(cores=cores, ranks=jnp.stack(ranks), shape=shape)


@functools.partial(
    jax.jit, static_argnames=("eps", "max_rank", "svd_method", "hbd_impl")
)
def ttd_static_batched(
    w: jax.Array,
    eps: float = 0.05,
    max_rank: int = 64,
    svd_method: str = "library",
    hbd_impl: str = "unblocked",
) -> StaticTT:
    """Batched Algorithm 1: one launch decomposes a whole (B, n_1..n_N) stack.

    Every member runs the identical static-shape TT-SVD (``ttd_static``)
    under ``jax.vmap``, so the returned ``StaticTT`` carries batched leaves:
    cores[k] is (B, rmax_{k-1}, n_k, rmax_k) and ``ranks`` is (B, N+1).
    Per-member results are bit-identical to serial ``ttd_static`` calls —
    the equivalence the batched compression planner relies on.
    """
    fn = functools.partial(
        ttd_static, eps=eps, max_rank=max_rank,
        svd_method=svd_method, hbd_impl=hbd_impl,
    )
    return jax.vmap(fn)(w)


def static_tt_member(tt: StaticTT, i: int) -> StaticTT:
    """Member ``i`` of a batched StaticTT (host-side view)."""
    return StaticTT(
        cores=[c[i] for c in tt.cores], ranks=tt.ranks[i], shape=tt.shape
    )


def static_tt_crop(tt: StaticTT, eps: float = 0.0) -> TTTensor:
    """Crop an (unbatched) StaticTT's zero-masked rank padding away.

    The live-rank slices of the padded cores reconstruct exactly the padded
    product (the masked tails contribute nothing), so this converts the
    in-graph result into the compact host-side ``TTTensor`` the offline
    compressor trades in.
    """
    with obs.span("compress.crop"):
        obs.wait("compress.wait", tt.ranks)
        ranks = [int(r) for r in np.asarray(jax.device_get(tt.ranks))]
        cores = [
            jnp.asarray(
                np.asarray(jax.device_get(c))[: ranks[k], :, : ranks[k + 1]])
            for k, c in enumerate(tt.cores)
        ]
        # the ranks down, each core down and back up
        obs.count("compress.host_transfers", 1 + 2 * len(cores))
    return TTTensor(cores=cores, shape=tt.shape, ranks=tuple(ranks), eps=eps)


def static_tt_reconstruct(tt: StaticTT) -> jax.Array:
    acc = tt.cores[0]
    for g in tt.cores[1:]:
        r = g.shape[0]
        acc = acc.reshape(-1, r) @ g.reshape(r, -1)
    return acc.reshape(tt.shape)


jax.tree_util.register_pytree_node(
    StaticTT,
    lambda t: ((t.cores, t.ranks), t.shape),
    lambda shape, kids: StaticTT(cores=kids[0], ranks=kids[1], shape=shape),
)


# ---------------------------------------------------------------------------
# Tensorization helpers
# ---------------------------------------------------------------------------

def auto_factorize(n: int, max_factor: int = 64) -> List[int]:
    """Split n into balanced factors ≤ max_factor (for re-tensorizing
    matrices/vectors into TT-friendly shapes, TT-Rec-style)."""
    if n <= max_factor:
        return [n]
    best = None
    f = int(np.floor(np.sqrt(n)))
    for cand in range(f, 1, -1):
        if n % cand == 0:
            a, b = cand, n // cand
            left = auto_factorize(a, max_factor)
            right = auto_factorize(b, max_factor)
            best = left + right
            break
    if best is None:  # prime > max_factor: keep as-is
        return [n]
    return best


def tensorize_shape(shape: Sequence[int], max_factor: int = 64) -> List[int]:
    dims: List[int] = []
    for n in shape:
        dims.extend(auto_factorize(int(n), max_factor))
    return dims
