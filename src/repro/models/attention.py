"""Attention: GQA with qk-norm/bias/window/softcap; XLA-chunked prefill path
(memory-safe at 32k on the dry-run) and log-sum-exp-mergeable decode path
(keeps the KV cache shardable along SEQUENCE on the model axis — the
flash-decode formulation XLA SPMD turns into small partial-softmax
collectives instead of gathering a 500k-token cache).

The Pallas flash kernel (``kernels/flash_attention``) is the TPU execution
target for prefill; ``impl="pallas"`` switches to it.  Dry-run lowering uses
``impl="xla"`` so cost_analysis reflects pure-XLA collectives/FLOPs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common

NEG_INF = -2.0e38


class AttnParams(NamedTuple):
    """Shapes (per layer; stack a leading L axis for scan).

    wq: (D, Hq, Dh)   wk/wv: (D, Hkv, Dh)   wo: (Hq, Dh, D)
    bq: (Hq, Dh) | None  (QKV bias archs)
    q_norm/k_norm: (Dh,) | None (qk-norm archs)
    """
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    bq: Optional[jax.Array] = None
    bk: Optional[jax.Array] = None
    bv: Optional[jax.Array] = None
    q_norm: Optional[jax.Array] = None
    k_norm: Optional[jax.Array] = None


def init_attn(key, cfg, layers: Optional[int] = None) -> AttnParams:
    d, hq, hkv, dh = (
        cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    )
    dt = common.cdtype(cfg)
    ks = jax.random.split(key, 4)

    def mk(shape, k, in_axis=0):
        if layers is None:
            return common.dense_init(k, shape, in_axis, dt)
        return jax.vmap(
            lambda kk: common.dense_init(kk, shape, in_axis, dt)
        )(jax.random.split(k, layers))

    zeros = lambda shape: (
        jnp.zeros(shape, dt) if layers is None
        else jnp.zeros((layers, *shape), dt)
    )
    return AttnParams(
        wq=mk((d, hq, dh), ks[0]),
        wk=mk((d, hkv, dh), ks[1]),
        wv=mk((d, hkv, dh), ks[2]),
        wo=mk((hq, dh, d), ks[3], 0),
        bq=zeros((hq, dh)) if cfg.qkv_bias else None,
        bk=zeros((hkv, dh)) if cfg.qkv_bias else None,
        bv=zeros((hkv, dh)) if cfg.qkv_bias else None,
        q_norm=zeros((dh,)) if cfg.qk_norm else None,
        k_norm=zeros((dh,)) if cfg.qk_norm else None,
    )


def qkv_project(x, p: AttnParams, cfg, positions):
    # dense_apply dispatches raw arrays and TT payloads identically
    q = common.dense_apply(x, p.wq)
    k = common.dense_apply(x, p.wk)
    v = common.dense_apply(x, p.wv)
    if p.bq is not None:
        q = q + p.bq
        k = k + p.bk
        v = v + p.bv
    if p.q_norm is not None:
        q = common.rms_norm(q, p.q_norm, cfg.norm_eps)
        k = common.rms_norm(k, p.k_norm, cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k, bf16_out: bool = False):
    """(B,S,Hq,D) x (B,T,Hkv,D) -> (B,Hkv,G,S,T) without repeating KV."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, s, hkv, g, d)
    if bf16_out:
        # §Perf: the dot itself emits bf16 (f32 MXU accumulation) so the
        # S×T logit buffer on HBM is half-width.
        return jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.bfloat16),
                          k.astype(jnp.bfloat16))
    return jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                      k.astype(jnp.float32))


def _gqa_out(p, v, bf16_probs: bool = False):
    """(B,Hkv,G,S,T) x (B,T,Hkv,D) -> (B,S,Hq,D)."""
    b, hkv, g, s, t = p.shape
    if bf16_probs:
        # §Perf hillclimb 2: probabilities are in [0,1] post-softmax — bf16
        # storage halves the dominant S×T traffic; the PV matmul still
        # accumulates in f32 (preferred_element_type).
        out = jnp.einsum(
            "bhgst,bthd->bshgd", p.astype(jnp.bfloat16),
            v.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
    else:
        out = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, hkv * g, -1)


def causal_attend(
    q: jax.Array,                # (B, S, Hq, Dh)
    k: jax.Array,                # (B, S, Hkv, Dh)
    v: jax.Array,
    cfg,
    window: Optional[int] = None,
    is_global=False,             # traced bool: widens the window to infinity
    chunk: int = 1024,
    impl: str = "xla",
) -> jax.Array:
    """Causal (optionally windowed) self-attention, O(S·chunk) memory.

    ``is_global`` may be a traced boolean (gemma3's scanned layer flag): the
    window constraint is OR-ed away branchlessly so one scan body serves
    both local and global layers without doubling attention FLOPs.
    """
    if impl == "pallas" and not isinstance(is_global, jax.core.Tracer):
        from repro.kernels.flash_attention.ops import mha_flash
        win = None if (window is None or is_global) else window
        return mha_flash(q, k, v, causal=True, window=win).astype(q.dtype)

    b, s, hq, dh = q.shape
    scale = dh ** -0.5
    if s <= chunk:
        return _attend_block(
            q, k, v, jnp.arange(s), cfg, window, is_global, scale
        )

    assert s % chunk == 0
    nq = s // chunk

    def attend(qblk, kk, vv, pos, ig, k_off=0):
        return _attend_block(qblk, kk, vv, pos, cfg, window, ig, scale,
                             k_off=k_off)

    if getattr(cfg, "opt_attn_remat", False):
        # flash-style nested remat: each q-chunk's S×chunk score tensor is
        # recomputed in its own backward instead of being stacked across the
        # scan as an O(S²) residual (§Perf hillclimb 1).
        attend = jax.checkpoint(attend, static_argnums=(5,))

    if getattr(cfg, "opt_causal_unroll", False):
        # §Perf hillclimb 4: unroll the q-chunk loop so chunk i attends to a
        # STATIC K/V slice — the all-masked future blocks (and, for windowed
        # non-global layers, the expired past) are never computed.  Causal
        # savings: 1 - (nq+1)/2nq ≈ ½ of the full-K score FLOPs and bytes.
        static_local = (window is not None
                        and not isinstance(is_global, jax.core.Tracer)
                        and not bool(is_global))
        outs_u = []
        prev = None
        for qi in range(nq):
            lo = 0
            if static_local:
                lo = max(0, qi * chunk - window + 1) // chunk * chunk
            hi = (qi + 1) * chunk
            pos = qi * chunk + jnp.arange(chunk)
            qblk = q[:, qi * chunk:hi]
            if prev is not None:
                # chain chunks so the scheduler cannot keep all nq score
                # buffers live at once (the scan this replaces serialized
                # them anyway); at 32k/1024 = 32 chunks this is the
                # difference between 1× and 32× peak score memory.
                qblk, _ = jax.lax.optimization_barrier((qblk, prev))
            out = attend(qblk, k[:, lo:hi], v[:, lo:hi], pos, is_global, lo)
            prev = out
            outs_u.append(out)
        return jnp.concatenate(outs_u, axis=1)

    def body(carry, qi):
        qblk = jax.lax.dynamic_slice_in_dim(q, qi * chunk, chunk, axis=1)
        pos = qi * chunk + jnp.arange(chunk)
        out = attend(qblk, k, v, pos, is_global)
        return carry, out

    _, outs = jax.lax.scan(body, 0, jnp.arange(nq))      # (nq, B, chunk, H, D)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, hq, dh)


def _attend_block(qblk, k, v, q_pos, cfg, window, is_global, scale, k_off=0):
    t = k.shape[1]
    k_pos = k_off + jnp.arange(t)
    mask = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        in_window = q_pos[:, None] - k_pos[None, :] < window
        mask &= in_window | jnp.asarray(is_global)
    bf16_scores = getattr(cfg, "opt_bf16_scores", False)
    scores = _gqa_scores(qblk, k, bf16_out=bf16_scores) * jnp.asarray(
        scale, jnp.bfloat16 if bf16_scores else jnp.float32)
    if bf16_scores:
        # §Perf hillclimb 3: the S×T logit buffer on HBM is bf16; the
        # max/exp/sum softmax reductions upcast to f32 inside their fusions.
        scores = jnp.where(mask[None, None, None], scores,
                           jnp.asarray(NEG_INF, jnp.bfloat16))
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    else:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(
        p, v, bf16_probs=getattr(cfg, "opt_bf16_probs", False)
    ).astype(qblk.dtype)


def cross_attend(q, k, v, cfg, mem_len=None) -> jax.Array:
    """Full cross attention for the encoder-decoder arch.

    ``mem_len`` — optional () or (B,) count of valid memory rows per batch
    row; rows at or past it are masked out (the continuous-batching slot
    contract: a slot's encoder memory occupies a prefix of the fixed-size
    ``mem_k``/``mem_v`` rows, and padding rows must never attract weight).
    ``mem_len == 0`` degrades gracefully: the finite NEG_INF mask leaves a
    uniform softmax over all-zero V rows, i.e. exactly the zero output a
    token-only slot decoded against before masking existed.  ``None`` keeps
    the legacy fully-unmasked behaviour bit-for-bit (no ``where`` traced).
    """
    scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q, k) * scale
    if mem_len is not None:
        t = k.shape[1]
        valid = jnp.arange(t)[None, :] < jnp.reshape(mem_len, (-1, 1))
        scores = jnp.where(valid[:, None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(
        p, v, bf16_probs=getattr(cfg, "opt_bf16_probs", False)
    ).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode (single new token against a long cache)
# ---------------------------------------------------------------------------

def decode_attend(
    q: jax.Array,                # (B, 1, Hq, Dh)
    k_cache: jax.Array,          # (B, S_max, Hkv, Dh)
    v_cache: jax.Array,
    pos: jax.Array,              # () shared or (B,) per-slot position
    cfg,
    window: Optional[int] = None,
    is_global=False,
) -> jax.Array:
    """LSE-mergeable single-token attention over the full cache.

    Written as (max, sum-exp, weighted-V) reductions over the cache's
    sequence axis so GSPMD can keep the cache sequence-sharded on the model
    axis and merge with tiny collectives (flash-decoding semantics).

    ``pos`` may be per-slot (B,) — the continuous-batching contract where
    every batch row sits at its own sequence position — or a shared scalar;
    the validity mask broadcasts over whichever it gets.
    """
    b, _, hq, dh = q.shape
    t = k_cache.shape[1]
    scale = dh ** -0.5
    k_pos = jnp.arange(t)
    posc = jnp.reshape(pos, (-1, 1))                     # (B,1) or (1,1)
    valid = k_pos[None, :] <= posc                       # (B|1, T) incl. self
    if window is not None:
        valid &= (k_pos[None, :] > posc - window) | jnp.asarray(is_global)
    scores = _gqa_scores(q, k_cache) * scale             # (B,Hkv,G,1,T)
    scores = jnp.where(valid[:, None, None, None], scores, NEG_INF)
    m = scores.max(axis=-1, keepdims=True)
    e = jnp.exp(scores - jax.lax.stop_gradient(m))
    num = _gqa_out(e, v_cache)                           # (B,1,Hq,Dh) fp32
    den = e.sum(axis=-1)                                 # (B,Hkv,G,1)
    den = den.reshape(b, 1, hq, 1)
    return (num / jnp.maximum(den, 1e-30)).astype(q.dtype)


def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Insert the new token's K/V at ``pos`` (dynamic index).

    Scalar ``pos`` writes one shared position; per-slot ``pos`` (B,) writes
    each batch row at its own position (vmapped dynamic-update — the slot
    contract the continuous-batching engine steps under).  Both clamp at the
    cache edge, so a frozen finished slot re-writes its last row instead of
    overflowing."""
    if jnp.ndim(pos) == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1
        )
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1
        )
        return k_cache, v_cache
    upd = jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
    )
    return (
        upd(k_cache, k_new.astype(k_cache.dtype), pos),
        upd(v_cache, v_new.astype(v_cache.dtype), pos),
    )


def cache_write(k_all, v_all, k_new, v_new, layer, pos):
    """Write layer ``layer``'s new K/V into the stacked (L, B, S_max, Hkv,
    Dh) cache in place: one row per slot, at (layer, b, pos[b]).

    The layer-stacked counterpart of ``cache_update`` for a cache carried
    through the layer scan: nothing but the B new rows is read or written.
    ``pos`` is () or (B,), clamped at the cache edge as ``cache_update``
    clamps, so a frozen finished slot re-writes its last row.

    One ``dynamic_update_slice`` per slot in a ``fori_loop``, so the
    module holds one update per array whatever the slot count."""
    b, s_max = k_all.shape[1], k_all.shape[2]
    row = jnp.broadcast_to(jnp.clip(pos, 0, s_max - 1), (b,))
    zero = jnp.zeros((), row.dtype)
    layer = jnp.asarray(layer, row.dtype)
    k_new = k_new.astype(k_all.dtype)
    v_new = v_new.astype(v_all.dtype)

    def put(s, kv):                      # k_new, v_new: (B, 1, Hkv, Dh)
        idx = (layer, s, row[s], zero, zero)
        return (jax.lax.dynamic_update_slice(kv[0], k_new[s][None, None], idx),
                jax.lax.dynamic_update_slice(kv[1], v_new[s][None, None], idx))

    return jax.lax.fori_loop(0, b, put, (k_all, v_all))
