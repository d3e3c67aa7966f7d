"""Decoder-only transformer LM — covers the dense, MoE, local/global, and
VLM-backbone architectures of the zoo (qwen1.5/qwen3/gemma3/olmoe/dbrx/
pixtral).

Layers are scanned (stacked params); per-layer heterogeneity that doesn't
change parameter shapes (gemma3's 5:1 local:global attention) is expressed
as a scanned boolean flag so a single homogeneous scan body serves every
layer.  Extra frontend inputs (pixtral patch embeddings) are prepended as
precomputed embeddings — the frontend itself is a stub per the assignment.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common
from repro.models import mlp as mlp_mod


class LayerParams(NamedTuple):
    attn: attn.AttnParams
    mlp: Optional[mlp_mod.MLPParams]
    moe: Optional[mlp_mod.MoEParams]
    ln1: jax.Array
    ln2: jax.Array


class TransformerParams(NamedTuple):
    embed: jax.Array                      # (V, D)
    layers: LayerParams                   # stacked (L, ...)
    final_norm: jax.Array                 # (D,)
    lm_head: Optional[jax.Array]          # (V, D) when untied


def init(key, cfg) -> TransformerParams:
    l = cfg.num_layers
    ks = jax.random.split(key, 5)
    dt = common.cdtype(cfg)
    layers = LayerParams(
        attn=attn.init_attn(ks[0], cfg, layers=l),
        mlp=(None if cfg.moe else mlp_mod.init_mlp(ks[1], cfg, layers=l)),
        moe=(mlp_mod.init_moe(ks[1], cfg, layers=l) if cfg.moe else None),
        ln1=jnp.zeros((l, cfg.d_model), dt),
        ln2=jnp.zeros((l, cfg.d_model), dt),
    )
    return TransformerParams(
        embed=common.embed_init(ks[2], (cfg.padded_vocab_size, cfg.d_model), dt),
        layers=layers,
        final_norm=jnp.zeros((cfg.d_model,), dt),
        lm_head=(
            None if cfg.tie_embeddings
            else common.embed_init(ks[3], (cfg.padded_vocab_size, cfg.d_model), dt)
        ),
    )


def _layer_flags(cfg) -> jax.Array:
    """Per-layer is_global flag (gemma3 pattern: every Nth layer global,
    counting from the Nth; all-global when no window is configured)."""
    if cfg.window is None or cfg.global_every is None:
        return jnp.ones((cfg.num_layers,), bool)
    # iota, not jnp.asarray(np.arange(...)): converting a concrete numpy
    # array under trace binds a device_put primitive per step (PRG002)
    idx = jnp.arange(cfg.num_layers)
    return (idx + 1) % cfg.global_every == 0


def _block(x, lp: LayerParams, is_global, cfg, positions, impl):
    x = common.pin_batch(x, cfg)
    h = common.rms_norm(x, lp.ln1, cfg.norm_eps)
    q, k, v = attn.qkv_project(h, lp.attn, cfg, positions)
    o = attn.causal_attend(
        q, k, v, cfg, window=cfg.window, is_global=is_global, impl=impl
    )
    x = x + common.dense_apply(o, lp.attn.wo, in_ndim=2)
    h = common.rms_norm(x, lp.ln2, cfg.norm_eps)
    if cfg.moe is not None:
        f = mlp_mod.moe_apply(h, lp.moe, cfg)
    else:
        f = mlp_mod.mlp_apply(h, lp.mlp, cfg.act)
    return (x + f).astype(x.dtype)


def forward(
    params: TransformerParams,
    tokens: jax.Array,                    # (B, S) int32
    cfg,
    prefix_embeds: Optional[jax.Array] = None,   # (B, P, D) frontend stub
    impl: str = "xla",
) -> jax.Array:
    """Returns final hidden states (B, S(+P), D)."""
    x = params.embed[tokens].astype(common.cdtype(cfg))
    if cfg.family == "dense" and cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate(
            [prefix_embeds.astype(x.dtype), x], axis=1
        )
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    flags = _layer_flags(cfg)

    fn = functools.partial(_block, cfg=cfg, positions=positions, impl=impl)
    if cfg.remat:
        fn = jax.checkpoint(fn)

    # TT-aware layer scan (common.tt_scan): TT-native weights scan the
    # layer index and gather lead vectors in-body; cores stay closure
    # constants the scan must not slice.
    x, _ = common.tt_scan(
        lambda h, lp, is_global: (fn(h, lp, is_global), None),
        x, params.layers, xs=(flags,), length=cfg.num_layers,
    )
    return common.rms_norm(x, params.final_norm, cfg.norm_eps)


def logits_fn(params: TransformerParams, hidden: jax.Array, cfg):
    table = params.lm_head if params.lm_head is not None else params.embed
    return common.unembed(hidden, table, cfg.logit_softcap,
                          real_vocab=cfg.vocab_size)


def loss_fn(
    params: TransformerParams,
    batch: Dict[str, jax.Array],
    cfg,
    impl: str = "xla",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    prefix = batch.get("prefix_embeds")
    hidden = forward(params, batch["tokens"], cfg, prefix_embeds=prefix,
                     impl=impl)
    if prefix is not None:
        hidden = hidden[:, prefix.shape[1]:, :]
    logits = logits_fn(params, hidden, cfg)
    loss = common.cross_entropy_loss(
        logits, batch["labels"], batch.get("mask")
    )
    metrics = {"loss": loss}
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with KV cache
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    k: jax.Array                          # (L, B, S_max, Hkv, Dh)
    v: jax.Array
    pos: jax.Array                        # (B,) int32 — per-slot next write


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    shape = (
        cfg.num_layers, batch, max_len, cfg.num_kv_heads,
        cfg.resolved_head_dim,
    )
    # pos is PER-SLOT (B,): every batch row advances independently, the
    # contract the continuous-batching engine admits/retires slots under.
    # decode_step also accepts a scalar pos (legacy lockstep caches).
    return DecodeCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        pos=jnp.zeros((batch,), jnp.int32),
    )


def decode_step(
    params: TransformerParams,
    cache: DecodeCache,
    tokens: jax.Array,                    # (B, 1)
    cfg,
) -> Tuple[jax.Array, DecodeCache]:
    """One token in, logits out; cache updated at cache.pos."""
    x = params.embed[tokens].astype(common.cdtype(cfg))
    if cfg.family == "dense" and cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    b = x.shape[0]
    pos = cache.pos
    positions = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (b, 1))
    flags = _layer_flags(cfg)

    def step(carry, lp, is_global, i):
        h, k_all, v_all = carry
        hh = common.rms_norm(h, lp.ln1, cfg.norm_eps)
        q, k_new, v_new = attn.qkv_project(hh, lp.attn, cfg, positions)
        with jax.named_scope("attention"):
            k_all, v_all = attn.cache_write(k_all, v_all, k_new, v_new, i,
                                            pos)
            o = attn.decode_attend(
                q, k_all[i], v_all[i], pos, cfg, window=cfg.window,
                is_global=is_global,
            )
        h = h + common.dense_apply(o, lp.attn.wo, in_ndim=2)
        hh = common.rms_norm(h, lp.ln2, cfg.norm_eps)
        if cfg.moe is not None:
            f = mlp_mod.moe_apply(hh, lp.moe, cfg)
        else:
            f = mlp_mod.mlp_apply(hh, lp.mlp, cfg.act)
        return ((h + f).astype(h.dtype), k_all, v_all), None

    # The whole cache rides the layer scan's carry so that each layer
    # writes its B new rows in place; scan xs/ys would copy every layer's
    # slice out and stack it back each step.  TT-native decode: weights
    # never leave TT form — common.tt_scan carries only the layer index;
    # cores are closure constants.
    (x, k_all, v_all), _ = common.tt_scan(
        step, (x, cache.k, cache.v), params.layers,
        xs=(flags, jnp.arange(cfg.num_layers)), length=cfg.num_layers,
    )
    hidden = common.rms_norm(x, params.final_norm, cfg.norm_eps)
    logits = logits_fn(params, hidden, cfg)
    return logits[:, 0, :], DecodeCache(k=k_all, v=v_all, pos=pos + 1)


def prefill(
    params: TransformerParams,
    tokens: jax.Array,                    # (B, S)
    cfg,
    prefix_embeds: Optional[jax.Array] = None,
    impl: str = "xla",
) -> jax.Array:
    """Prefill pass: returns last-position logits (cache fill elided in the
    dry-run shape cell — prefill cost is the forward itself)."""
    hidden = forward(params, tokens, cfg, prefix_embeds=prefix_embeds,
                     impl=impl)
    logits = logits_fn(params, hidden[:, -1:, :], cfg)
    return logits[:, 0, :]


# ---------------------------------------------------------------------------
# TT-native serving rules (registered beside the model, per family)
# ---------------------------------------------------------------------------
# MoE expert banks (L, E, D, F) use stack=2/experts=1: both leading axes
# fold into the lead table but the expert mode stays a batch axis, served
# by the expert-batched chain through ``common.expert_apply``.
_TT_RULES = [
    common.TTServeRule(r"^layers\.attn\.w[qkv]$", in_ndim=1),
    common.TTServeRule(r"^layers\.attn\.wo$", in_ndim=2),
    common.TTServeRule(r"^layers\.mlp\.w_(gate|up|down)$", in_ndim=1),
    common.TTServeRule(r"^layers\.moe\.w_(gate|up|down)$", in_ndim=1,
                       stack=2, experts=1),
]
for _fam in ("dense", "moe", "vlm"):
    common.register_tt_serve_rules(_fam, _TT_RULES)
