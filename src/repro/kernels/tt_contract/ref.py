"""Oracle for the fused TT-contraction kernel: per-core einsum chain.

Operates on the *lead-absorbed* chain representation a ``TTLinear`` hands
down (``core/tt_linear.py``): ``cores[0]`` is 2D ``(n_1, r_1)`` (boundary
rank and any layer-stack modes already contracted away), every later core is
3D ``(r_{k-1}, n_k, r_k)`` and the final core has ``r_N == 1``.  The first
``split`` cores are *input* cores (their mode dims are contracted against
``x``); the rest are *output* cores (their mode dims build the result).

The contraction order matches ``tt_reconstruct`` exactly — left-to-right,
one mode at a time — so fusing it with the activation never changes the
value, only when the work happens (per token instead of one-shot
materialization of the full ``(N_in, N_out)`` matrix).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def tt_contract_ref(
    x2: jax.Array,                  # (B, N_in)
    cores: Sequence[jax.Array],     # [g0 (n1,r1), g_k (r,n,s) ..., last s==1]
    split: int,
) -> jax.Array:                     # (B, N_out) float32
    """y = x · W where W is the TT chain — pure jnp, any depth."""
    assert 1 <= split <= len(cores), (split, len(cores))
    b = x2.shape[0]
    g0 = cores[0]
    assert g0.ndim == 2, "cores[0] must be lead-absorbed (n1, r1)"
    t = x2.astype(jnp.float32).reshape(b, g0.shape[0], -1)
    t = jnp.einsum("bnm,ns->bms", t, g0.astype(jnp.float32))
    for g in cores[1:split]:
        r = g.shape[0]
        t = t.reshape(b, g.shape[1], -1, r)
        t = jnp.einsum("bnmr,rns->bms", t, g.astype(jnp.float32))
    # all input modes consumed: t is (B, 1, r_split)
    t = t.reshape(b, 1, -1)
    for g in cores[split:]:
        t = jnp.einsum("bmr,rns->bmns", t, g.astype(jnp.float32))
        t = t.reshape(b, -1, g.shape[2])
    return t.reshape(b, -1)


def tt_contract_batched_ref(
    x3: jax.Array,                  # (E, B, N_in)
    g0b: jax.Array,                 # (E, n1, r1) per-expert lead-absorbed
    cores: Sequence[jax.Array],     # shared tail [(r,n,s), ...], last s==1
    split: int,
) -> jax.Array:                     # (E, B, N_out) float32
    """Expert-batched chain oracle: y[e] = x[e] · W[e], where the experts
    differ only in their lead-absorbed first core and share every later
    core — written as one einsum chain with a leading expert axis (the
    batched analogue of ``tt_contract_ref``, same left-to-right order)."""
    assert 1 <= split <= 1 + len(cores), (split, len(cores))
    e, b, _ = x3.shape
    assert g0b.ndim == 3 and g0b.shape[0] == e, g0b.shape
    t = x3.astype(jnp.float32).reshape(e, b, g0b.shape[1], -1)
    t = jnp.einsum("ebnm,ens->ebms", t, g0b.astype(jnp.float32))
    for g in cores[: split - 1]:
        r = g.shape[0]
        t = t.reshape(e, b, g.shape[1], -1, r)
        t = jnp.einsum("ebnmr,rns->ebms", t, g.astype(jnp.float32))
    t = t.reshape(e, b, 1, -1)
    for g in cores[split - 1:]:
        t = jnp.einsum("ebmr,rns->ebmns", t, g.astype(jnp.float32))
        t = t.reshape(e, b, -1, g.shape[2])
    return t.reshape(e, b, -1)


def tt_dequant_chain(
    cores: Sequence[jax.Array],
    scales: Sequence[jax.Array | None],
) -> list[jax.Array]:
    """Explicitly dequantize a chain: each core widened to f32 and each of
    its leading-rank rows multiplied by its symmetric scale (``None`` = core
    is already wide — e.g. the lead-absorbed first core whose scales were
    folded host-side).  This is the unfused oracle the scale-folding kernels
    must match at f32 tolerance: the chain is linear in every core, so
    scaling a core's rows and scaling the matching columns of the partial
    product are the same map."""
    assert len(cores) == len(scales), (len(cores), len(scales))
    out = []
    for g, s in zip(cores, scales):
        g = jnp.asarray(g, jnp.float32)
        if s is not None:
            s = jnp.asarray(s, jnp.float32)
            g = g * s.reshape(s.shape + (1,) * (g.ndim - s.ndim))
        out.append(g)
    return out


def tt_dense_ref(cores: Sequence[jax.Array], split: int) -> jax.Array:
    """Materialize the chain into the dense (N_in, N_out) matrix —
    the reconstruct-then-matmul baseline the fused path must match."""
    acc = jnp.asarray(cores[0], jnp.float32)        # (n1, r1)
    n_in = cores[0].shape[0]
    for k, g in enumerate(cores[1:], start=1):
        r = g.shape[0]
        acc = acc.reshape(-1, r) @ jnp.asarray(g, jnp.float32).reshape(r, -1)
        if k < split:
            n_in *= g.shape[1]
    return acc.reshape(n_in, -1)
