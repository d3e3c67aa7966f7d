"""MLP blocks: gated (SwiGLU/GeGLU) dense FFN and expert-parallel MoE.

MoE uses gather-based token grouping with static expert capacity (dropless
up to the capacity factor), experts sharded over the ``model`` axis —
dispatch/combine are all-to-all-shaped collectives under GSPMD.  Cost is
linear in tokens (no GShard one-hot dispatch einsum, which would be
quadratic at 32k prefill).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common


class MLPParams(NamedTuple):
    w_gate: jax.Array             # (D, F)
    w_up: jax.Array               # (D, F)
    w_down: jax.Array             # (F, D)


def init_mlp(key, cfg, d_ff: Optional[int] = None,
             layers: Optional[int] = None) -> MLPParams:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = common.cdtype(cfg)
    ks = jax.random.split(key, 3)

    def mk(shape, k, in_axis=0):
        if layers is None:
            return common.dense_init(k, shape, in_axis, dt)
        return jax.vmap(
            lambda kk: common.dense_init(kk, shape, in_axis, dt)
        )(jax.random.split(k, layers))

    return MLPParams(
        w_gate=mk((d, f), ks[0]),
        w_up=mk((d, f), ks[1]),
        w_down=mk((f, d), ks[2]),
    )


def mlp_apply(x: jax.Array, p: MLPParams, act: str) -> jax.Array:
    """Gated FFN; weights may be raw arrays or TT payloads — every matmul
    goes through the ``common.dense_apply`` dispatch point."""
    g = common.activate(common.dense_apply(x, p.w_gate), act)
    u = common.dense_apply(x, p.w_up)
    return common.dense_apply(g * u, p.w_down)


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------

class MoEParams(NamedTuple):
    router: jax.Array             # (D, E)
    w_gate: jax.Array             # (E, D, F)
    w_up: jax.Array               # (E, D, F)
    w_down: jax.Array             # (E, F, D)


def init_moe(key, cfg, layers: Optional[int] = None) -> MoEParams:
    d, e, f = cfg.d_model, cfg.moe.num_experts, cfg.moe.d_ff
    dt = common.cdtype(cfg)
    ks = jax.random.split(key, 4)

    def mk(shape, k, in_axis):
        def one(kk):
            return common.dense_init(kk, shape, in_axis, dt)
        if layers is None:
            return one(k)
        return jax.vmap(one)(jax.random.split(k, layers))

    return MoEParams(
        router=mk((d, e), ks[0], 0),
        w_gate=mk((e, d, f), ks[1], 1),
        w_up=mk((e, d, f), ks[2], 1),
        w_down=mk((e, f, d), ks[3], 1),
    )


def _route_and_fill(xf, router, e, k, cap, dtype):
    """Router + slot assignment + scatter into per-expert buffers.

    xf: (n, d) tokens (global on the GSPMD path, LOCAL inside shard_map).
    Returns buf (e·cap, d), slot (n·k,), keep (n·k,), topk_p (n, k).
    """
    n, d = xf.shape
    logits = common.dense_apply(xf.astype(jnp.float32),
                                router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, k)             # (n, k)
    topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    # slot assignment: for each (token, k) pair, its rank among same-expert
    # assignments (capacity dropping = rank >= cap)
    flat_e = topk_e.reshape(-1)                          # (n*k,)
    order = jnp.argsort(flat_e, stable=True)             # group by expert
    ranks_sorted = jnp.arange(n * k) - jnp.searchsorted(
        flat_e[order], flat_e[order], side="left"
    )
    inv = jnp.argsort(order)
    rank = ranks_sorted[inv]                             # (n*k,) rank in expert
    keep = rank < cap
    slot = flat_e * cap + jnp.minimum(rank, cap - 1)     # (n*k,) target slot

    buf = jnp.zeros((e * cap, d), dtype)
    src = jnp.repeat(xf, k, axis=0)                      # token for each slot
    buf = buf.at[slot].add(jnp.where(keep[:, None], src, 0))
    return buf, slot, keep, topk_p


def _moe_a2a_applicable(cfg, b, s_len):
    from repro.launch import sharding as _shd
    mesh = _shd.current_mesh()
    if mesh is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes.get("model", 1)
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    dp = 1
    for a in batch_axes:
        dp *= sizes[a]
    if m <= 1 or cfg.moe.num_experts % m or b % max(dp, 1):
        return None
    if ((b // dp) * s_len) % m:          # decode-sized token rows: fall back
        return None
    return mesh, m, batch_axes, dp


def moe_apply_a2a(x, p: MoEParams, cfg, capacity_factor: float = 1.25):
    """Expert-parallel MoE with an EXPLICIT all-to-all dispatch (shard_map).

    The GSPMD scatter formulation partial-sums (cap, d_ff) activations over
    the model axis (measured: EXPERIMENTS.md §Perf cell 2).  Here tokens are
    routed LOCALLY per device, exchanged with one all-to-all over the model
    axis into expert-major layout, FFN'd expert-locally, and returned by the
    inverse all-to-all — the textbook EP schedule, stated manually because
    GSPMD cannot infer it through the scatter.
    """
    import functools as _ft
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as _shd

    b, s_len, d = x.shape
    e = cfg.moe.num_experts
    k = cfg.moe.num_experts_per_tok
    app = _moe_a2a_applicable(cfg, b, s_len)
    assert app is not None
    mesh, m, batch_axes, dp = app
    e_loc = e // m
    f_ax = "data" if cfg.fsdp else None

    n_row = (b // dp) * s_len              # tokens per data row (model-repl.)
    assert n_row % m == 0, (n_row, m)
    n_loc = n_row // m                     # distinct tokens per model peer
    cap_loc = max(min(int(np.ceil(n_loc * k / e * capacity_factor)), n_loc), 1)

    def body(xl, router, wg, wu, wd):
        # xl (B_loc, S, D) is REPLICATED across the model axis — each model
        # peer takes its own 1/M token slice so no work is duplicated.
        if cfg.fsdp:
            router = jax.lax.all_gather(router, "data", axis=0, tiled=True)
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        midx = jax.lax.axis_index("model")
        xf = jax.lax.dynamic_slice_in_dim(
            xl.reshape(-1, d), midx * n_loc, n_loc, axis=0)
        buf, slot, keep, topk_p = _route_and_fill(
            xf, router, e, k, cap_loc, xl.dtype)

        # dispatch: (E, cap_loc, D) -> (E_loc, M*cap_loc, D) over 'model'
        sent = jax.lax.all_to_all(
            buf.reshape(e, cap_loc, d), "model", 0, 1, tiled=True
        ).reshape(e_loc, m * cap_loc, d)

        g = common.activate(common.expert_apply(sent, wg), cfg.act)
        u = common.expert_apply(sent, wu)
        out = common.expert_apply(g * u, wd)             # (E_loc, M·cap, D)

        # return: inverse all-to-all back to token-major layout.  out's
        # second axis is peer-major ([peer0 cap | peer1 cap | …]) — put the
        # peer axis first so each peer gets its own experts back, and the
        # receive-concat is expert-major (matching slot = e·cap + rank).
        ret = jax.lax.all_to_all(
            out.reshape(e_loc, m, cap_loc, d)
               .transpose(1, 0, 2, 3).reshape(m * e_loc, cap_loc, d),
            "model", 0, 0, tiled=True
        ).reshape(e * cap_loc, d)

        per_slot = ret[slot]
        w = (topk_p.reshape(-1) * keep).astype(jnp.float32)[:, None]
        combined = (per_slot.astype(jnp.float32) * w).reshape(
            n_loc, k, d).sum(1)
        # restore the model-replicated token layout
        full = jax.lax.all_gather(
            combined.astype(xl.dtype), "model", axis=0, tiled=True)
        return full.reshape(xl.shape)

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0]
                                                    if batch_axes else None)
    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P(bspec, None, None),
                  P(f_ax, None),
                  P("model", f_ax, None),
                  P("model", f_ax, None),
                  P("model", None, f_ax)),
        out_specs=P(bspec, None, None),
    )(x, p.router, p.w_gate, p.w_up, p.w_down)


def moe_apply(
    x: jax.Array,                # (B, S, D)
    p: MoEParams,
    cfg,
    capacity_factor: float = 1.25,
) -> jax.Array:
    """Top-k routing with static per-expert capacity; gather/scatter grouping.

    Returns the combined expert outputs (B, S, D).  Aux-free (loss-side
    z-loss/load-balance handled by the trainer; see train/losses.py).

    NOTE (serving): capacity COUPLES batch rows — a token's slot rank, and
    hence whether it is dropped, depends on the other rows routed with it.
    The fused decode driver is still token-for-token identical to the
    python loop (same batch, same routing), but continuous batching cannot
    promise staggered == isolated for MoE the way it does for every other
    family: a slot's neighbours (including retired slots' frozen lockstep
    tokens) legitimately shift expert capacity.
    """
    b, s, d = x.shape
    e = cfg.moe.num_experts
    k = cfg.moe.num_experts_per_tok
    if getattr(cfg, "opt_moe_a2a", False) and \
            not common.layers_have_tt(p) and \
            _moe_a2a_applicable(cfg, b, s) is not None:
        # a2a shard_maps the raw expert arrays; TT-native banks (serving)
        # take the expert-batched chain below instead
        return moe_apply_a2a(x, p, cfg, capacity_factor)
    n = b * s
    cap = int(np.ceil(n * k / e * capacity_factor))
    cap = max(min(cap, n), 1)

    xf = x.reshape(n, d)
    buf, slot, keep, topk_p = _route_and_fill(xf, p.router, e, k, cap, x.dtype)

    # expert FFN on grouped tokens: (E, cap, D) einsum with expert weights
    if getattr(cfg, "opt_moe_ep", False):
        # pin the pre-dispatch layout too: slots over data, d replicated —
        # the (data→model) reshard into the expert layout below is then a
        # clean all-to-all instead of whatever GSPMD propagates backwards
        # through the scatter.
        from repro.launch import sharding as _shd
        buf = _shd.act_constraint(buf, "data", None)
    h = buf.reshape(e, cap, d)
    if getattr(cfg, "opt_moe_ep", False):
        # §Perf hillclimb (dbrx): pin the expert-parallel layout — dispatch
        # becomes one all-to-all of (E, cap, D) tokens and every FFN matmul
        # is expert-local, instead of GSPMD's partial-sum all-reduce of the
        # (cap, d_ff) intermediate over the model axis.
        from repro.launch import sharding as _shd
        h = _shd.act_constraint(h, "model", "data", None)
    # expert_apply dispatches raw banks and expert-axis TT payloads alike
    g = common.activate(common.expert_apply(h, p.w_gate), cfg.act)
    u = common.expert_apply(h, p.w_up)
    if getattr(cfg, "opt_moe_ep", False):
        from repro.launch import sharding as _shd
        g = _shd.act_constraint(g, "model", "data", None)
        u = _shd.act_constraint(u, "model", "data", None)
    out = common.expert_apply(g * u, p.w_down)           # (E, cap, D)
    if getattr(cfg, "opt_moe_ep", False):
        from repro.launch import sharding as _shd
        out = _shd.act_constraint(out, "model", "data", None)
    out = out.reshape(e * cap, d)

    # gather back + weighted combine
    per_slot = out[slot]                                 # (n*k, d)
    w = (topk_p.reshape(-1) * keep).astype(jnp.float32)[:, None]
    combined = (per_slot.astype(jnp.float32) * w).reshape(n, k, d).sum(1)
    return combined.reshape(b, s, d).astype(x.dtype)


def router_aux_stats(x, p: MoEParams, cfg):
    """(load-balance loss, router z-loss) for the training objective."""
    n = x.shape[0] * x.shape[1]
    logits = common.dense_apply(x.astype(jnp.float32),
                                p.router.astype(jnp.float32)).reshape(n, -1)
    probs = jax.nn.softmax(logits, axis=-1)
    _, topk_e = jax.lax.top_k(probs, cfg.moe.num_experts_per_tok)
    e = cfg.moe.num_experts
    counts = jnp.zeros((e,), jnp.float32).at[topk_e.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = probs.mean(0)
    lb = e * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return lb, z
