"""Cross-pod TT-compressed parameter/gradient synchronization (FedTTD).

Paper Fig. 1, transplanted to the production mesh: within a pod, gradients
are reduced over fast ICI as usual; ACROSS pods — the slow "edge↔edge /
edge↔cloud" link in the paper's setting — parameters are exchanged in TT
format and reconstructed on arrival.

In-graph mechanics (all jittable, shape-static):

  1. ``psum`` the gradient within the pod's (data, model) axes (unchanged).
  2. Every ``sync_every`` steps, each pod TT-compresses the *parameter
     delta* since the last sync (error-feedback residual accumulation keeps
     the compression unbiased over time).
  3. The padded TT cores — a few percent of the raw payload — are
     ``all_gather``-ed over the ``pod`` axis (this is the collective whose
     operand bytes shrink; visible in the dry-run HLO).
  4. Each pod reconstructs the peers' deltas and averages.

This module provides both the shard_map collective path and a pure
single-process simulator used by tests (``fedttd_roundtrip``).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tt as _tt


@dataclass(frozen=True)
class CommCompressionConfig:
    enabled: bool = False
    eps: float = 0.02
    max_rank: int = 32
    min_size: int = 65536           # only compress big tensors' cross-pod sync
    svd_method: str = "library"     # in-graph default; "two_phase" selectable
    sync_every: int = 1


def _flat2d(x: jax.Array) -> jax.Array:
    """Canonical 2D view for in-graph TT of an arbitrary-rank parameter."""
    n = x.size
    rows = int(np.floor(np.sqrt(n)))
    while n % rows != 0:
        rows -= 1
    return x.reshape(rows, n // rows)


def compress_delta(
    delta: jax.Array, cfg: CommCompressionConfig
) -> Tuple[_tt.StaticTT, jax.Array]:
    """TT-compress one tensor in-graph; returns (tt, residual).

    residual = delta - reconstruct(tt): fed back into the error-feedback
    accumulator so repeated syncs converge to the uncompressed average.
    """
    dims = _tt.tensorize_shape(_flat2d(delta).shape, max_factor=64)
    x = delta.astype(jnp.float32).reshape(tuple(dims))
    tt = _tt.ttd_static(
        x, eps=cfg.eps, max_rank=cfg.max_rank, svd_method=cfg.svd_method
    )
    rec = _tt.static_tt_reconstruct(tt).reshape(delta.shape)
    return tt, delta - rec.astype(delta.dtype)


def compress_delta_batched(
    deltas: jax.Array, cfg: CommCompressionConfig
) -> Tuple[_tt.StaticTT, jax.Array]:
    """TT-compress a (P, *shape) stack of same-shape deltas in ONE launch.

    The per-pod serial loop in ``fedttd_roundtrip``/``train.fedttd`` pays a
    dispatch per pod per tensor; pods always sync the *same* parameter
    pytree, so every leaf is a ready-made bucket of P same-shape problems.
    ``jax.vmap`` over ``compress_delta`` keeps per-member results
    bit-identical to the serial path.  Returns (batched StaticTT with
    leading pod axis on every leaf, residuals (P, *shape)).
    """
    return jax.vmap(functools.partial(compress_delta, cfg=cfg))(deltas)


def pod_sync_tt(
    delta: jax.Array,
    cfg: CommCompressionConfig,
    axis_name: str = "pod",
) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map/pmap over ``axis_name``: TT-compress the local delta,
    all-gather the (small) cores across pods, reconstruct+average.

    Returns (averaged_delta, residual).
    """
    tt, resid = compress_delta(delta, cfg)
    gathered: List[jax.Array] = [
        jax.lax.all_gather(c, axis_name=axis_name) for c in tt.cores
    ]  # each: (n_pods, r, n, r')
    n_pods = jax.lax.psum(1, axis_name=axis_name)

    def rec_one(p):
        cores = [g[p] for g in gathered]
        acc = cores[0]
        for g in cores[1:]:
            r = g.shape[0]
            acc = acc.reshape(-1, r) @ g.reshape(r, -1)
        return acc.reshape(delta.shape)

    # the accumulator sums per-pod values: mark it varying over the axis
    init = jax.lax.pvary(jnp.zeros(delta.shape, jnp.float32), (axis_name,))
    total = jax.lax.fori_loop(0, n_pods, lambda p, s: s + rec_one(p), init)
    avg = (total / n_pods).astype(delta.dtype)
    return avg, resid


def pod_sync_dense(delta: jax.Array, axis_name: str = "pod") -> jax.Array:
    """The uncompressed baseline: plain pmean over the pod axis."""
    return jax.lax.pmean(delta, axis_name=axis_name)


def fedttd_roundtrip(
    deltas: List[jax.Array],
    cfg: CommCompressionConfig,
    plan: str = "batched",
) -> Tuple[jax.Array, List[jax.Array], float]:
    """Single-process simulator of one cross-pod sync round (for tests).

    deltas: one tensor per pod.  Returns (average, residuals, payload_ratio)
    where payload_ratio = compressed_bytes / raw_bytes of the exchange.

    plan="batched" compresses all pods' deltas in one vmapped launch (the
    default); plan="serial" is the original per-pod loop, kept as the
    equivalence oracle — both produce identical numerics.
    """
    n_pods = len(deltas)
    if plan == "batched":
        batched, resid_stack = compress_delta_batched(
            jnp.stack(deltas), cfg
        )
        tts = [_tt.static_tt_member(batched, p) for p in range(n_pods)]
        resids = [resid_stack[p] for p in range(n_pods)]
    elif plan == "serial":
        tts, resids = [], []
        for d in deltas:
            tt, r = compress_delta(d, cfg)
            tts.append(tt)
            resids.append(r)
    else:
        raise ValueError(f"unknown plan: {plan!r}")
    avg = sum(
        _tt.static_tt_reconstruct(t).reshape(deltas[0].shape) for t in tts
    ) / n_pods
    raw = int(np.prod(deltas[0].shape)) * n_pods
    comp = sum(
        int(np.prod(c.shape)) for t in tts for c in t.cores
    )
    return avg.astype(deltas[0].dtype), resids, comp / raw
