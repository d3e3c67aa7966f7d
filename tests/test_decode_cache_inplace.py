"""The transformer decode step carries the KV cache through its layer scan
and writes one row per slot per layer in place.

Two guarantees:

  * parity: the in-place step is bit-identical, in logits and in both cache
    arrays, to the formulation it replaced (kept below as the oracle: each
    layer's cache slice scanned in as xs, updated with ``cache_update`` and
    stacked back as ys), across the dense, windowed, MoE and TT-served
    transformers, at per-slot positions that differ, at the cache edge, and
    under the legacy scalar ``pos``;
  * structure: the layer scan returns no ys of cache size, its carry holds
    both cache arrays, and the engine's donated dispatches still alias.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import programlint
from repro.configs import get_config
from repro.models import attention as attn
from repro.models import common
from repro.models import mlp as mlp_mod
from repro.models import transformer as tfm
from repro.models.registry import build

B, S_MAX = 4, 24


def xs_ys_decode_step(params, cache, tokens, cfg):
    """The decode step as it was before the cache moved into the carry:
    the oracle of the parity test."""
    x = params.embed[tokens].astype(common.cdtype(cfg))
    if cfg.family == "dense" and cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    b = x.shape[0]
    pos = cache.pos
    positions = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (b, 1))

    def step(h, lp, is_global, k_c, v_c):
        hh = common.rms_norm(h, lp.ln1, cfg.norm_eps)
        q, k_new, v_new = attn.qkv_project(hh, lp.attn, cfg, positions)
        k_c, v_c = attn.cache_update(k_c, v_c, k_new, v_new, pos)
        o = attn.decode_attend(
            q, k_c, v_c, pos, cfg, window=cfg.window, is_global=is_global)
        h = h + common.dense_apply(o, lp.attn.wo, in_ndim=2)
        hh = common.rms_norm(h, lp.ln2, cfg.norm_eps)
        if cfg.moe is not None:
            f = mlp_mod.moe_apply(hh, lp.moe, cfg)
        else:
            f = mlp_mod.mlp_apply(hh, lp.mlp, cfg.act)
        return (h + f).astype(h.dtype), (k_c, v_c)

    x, (k_all, v_all) = common.tt_scan(
        step, x, params.layers,
        xs=(tfm._layer_flags(cfg), cache.k, cache.v), length=cfg.num_layers)
    hidden = common.rms_norm(x, params.final_norm, cfg.norm_eps)
    logits = tfm.logits_fn(params, hidden, cfg)
    return logits[:, 0, :], tfm.DecodeCache(k=k_all, v=v_all, pos=pos + 1)


def _tt(model, params, cfg):
    from repro.core import (
        CompressionPolicy, TTCompressor, spectral_decay_pytree,
    )
    payload, _ = TTCompressor(CompressionPolicy(eps=0.2, min_size=8192)
                              ).compress(spectral_decay_pytree(params))
    return common.tt_native_params(payload, family=cfg.family)


# gemma3 with a window shorter than the cache and one global layer of two,
# so both branches of the per-layer flag run in one scan
MODELS = {
    "qwen1.5-0.5b": ("qwen1.5-0.5b", {}, "dense"),
    "qwen1.5-0.5b-tt": ("qwen1.5-0.5b", {}, "tt"),
    "gemma3-1b": ("gemma3-1b", {"window": 8, "global_every": 2}, "dense"),
    "olmoe-1b-7b": ("olmoe-1b-7b", {}, "dense"),
}

POSITIONS = {
    "per-slot": np.array([3, 17, 0, 9], np.int32),
    "edge": np.array([S_MAX - 1, 2, S_MAX - 1, 11], np.int32),
    "scalar": np.int32(5),
}


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            arch, overrides, weights = MODELS[name]
            cfg = get_config(arch).reduced(**overrides)
            model = build(cfg)
            params = model.init(jax.random.PRNGKey(0))
            if weights == "tt":
                params = _tt(model, params, cfg)
            built[name] = (cfg, model, params)
        return built[name]

    return get


def _history(model, pos, seed=0):
    """A cache whose rows hold K/V of earlier tokens (random, bf16)."""
    cache = model.init_cache(B, S_MAX)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return cache._replace(
        k=jax.random.normal(kk, cache.k.shape).astype(cache.k.dtype),
        v=jax.random.normal(kv, cache.v.shape).astype(cache.v.dtype),
        pos=jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("where", list(POSITIONS))
@pytest.mark.parametrize("name", list(MODELS))
def test_inplace_decode_matches_xs_ys_oracle(models, name, where):
    """Two steps from a filled cache: logits, K, V and pos bit-identical;
    a slot at the cache edge re-writes its last row."""
    cfg, model, params = models(name)
    pos = POSITIONS[where]
    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (B, 2), np.int32)
    new = jax.jit(model.decode_step)
    old = jax.jit(lambda p, c, t: xs_ys_decode_step(p, c, t, cfg))
    c_new = c_old = _history(model, pos)
    for i in range(toks.shape[1]):
        t = jnp.asarray(toks[:, i:i + 1])
        l_new, c_new = new(params, c_new, t)
        l_old, c_old = old(params, c_old, t)
        np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_old))
        for a, b in ((c_new.k, c_old.k), (c_new.v, c_old.v),
                     (c_new.pos, c_old.pos)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if where == "edge":
        # the two slots at the edge wrote their last row twice, nothing else
        start = _history(model, pos)
        changed = np.any(np.asarray(c_new.k != start.k), axis=(0, 3, 4))
        assert changed[0].tolist() == [False] * (S_MAX - 1) + [True]
        assert changed[2].tolist() == [False] * (S_MAX - 1) + [True]


def _layer_scan(jaxpr, length):
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == length]
    assert len(scans) == 1, [e.params["length"] for e in scans]
    return scans[0]


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "qwen1.5-0.5b-tt"])
def test_layer_scan_carries_the_cache_and_returns_no_cache_ys(models, name):
    cfg, model, params = models(name)
    cache = model.init_cache(B, S_MAX)
    jaxpr = jax.make_jaxpr(model.decode_step)(
        params, cache, jnp.zeros((B, 1), jnp.int32)).jaxpr
    scan = _layer_scan(jaxpr, cfg.num_layers)
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shape = cache.k.shape

    def cache_sized(vs):
        return [v for v in vs if getattr(v.aval, "shape", None) == shape]

    assert len(cache_sized(scan.outvars[:n_carry])) == 2       # K and V
    assert cache_sized(scan.outvars[n_carry:]) == []            # no ys
    assert cache_sized(scan.invars[n_consts + n_carry:]) == []  # no xs


def test_engine_donation_still_honoured():
    """PRG003 over the fused chunk (dense, compiled; TT, lowered) and the
    python driver's donated decode step."""
    fs = programlint.run(
        fast=True, rules={"PRG003"},
        entries=[f"gen_scan/{programlint.FAST_ARCH}", "decode_step/"])
    assert fs == [], "\n".join(f.render() for f in fs)
