"""qwen1.5-0.5b served from bf16 TT cores: weights from the seed, and the
plain float32 reference of the same weights.

The cores' dims and ranks come from the ``spec`` in the configuration file
(recorded from the repository's compressor, see the file's ``spec_from``).
``make_weights`` draws every array on the device in one jitted call;
``payload`` wraps them in the compressor's output format
(``CompressedParam``) so that serving consumes what compression emits.

``reference_gaps`` is the plain reference: a float32 forward pass
written out here from the architecture's description (RMSNorm, rotary
attention with QKV bias, SiLU-gated MLP, tied unembedding), computed at
``highest`` matmul precision, that imports nothing of the program.  Each
layer's dense weights are rebuilt from the cores inside the layer scan, so
the reference never holds more than the cores and one layer's matrices.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
TT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


# ---------------------------------------------------------------- sizes --

def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": d, "H": h,
        "Hkv": cfg["num_key_value_heads"], "Dh": d // h,
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
    }


def leaf_spec(cfg: dict) -> Dict[str, dict]:
    """Per TT leaf: ``orig_shape`` (stacked over layers), ``dims``, ``ranks``
    and ``in_ndim``, with the layer mode set to this configuration's depth
    (the recorded spec is at the published depth; a shallower test model
    keeps every other dim and rank, and caps the layer rank at its depth)."""
    s = sizes(cfg)
    L, D, H, Hkv, Dh, F = s["L"], s["D"], s["H"], s["Hkv"], s["Dh"], s["F"]
    shapes = {"wq": ((L, D, H, Dh), 1), "wk": ((L, D, Hkv, Dh), 1),
              "wv": ((L, D, Hkv, Dh), 1), "wo": ((L, H, Dh, D), 2),
              "w_gate": ((L, D, F), 1), "w_up": ((L, D, F), 1),
              "w_down": ((L, F, D), 1)}
    out = {}
    for name in TT_LEAVES:
        rec = cfg["weights"]["spec"][name]
        dims, ranks = list(rec["dims"]), list(rec["ranks"])
        dims[0] = L
        ranks[1] = min(ranks[1], L)
        shape, in_ndim = shapes[name]
        if int(np.prod(dims)) != int(np.prod(shape)):
            raise ValueError(f"{name}: spec dims {dims} do not tile {shape}")
        out[name] = {"orig_shape": shape, "dims": dims, "ranks": ranks,
                     "in_ndim": in_ndim,
                     "fan_in": int(np.prod(shape[1:1 + in_ndim]))}
    return out


# -------------------------------------------------------------- weights --

def _draw(cfg: dict, key):
    s, spec = sizes(cfg), leaf_spec(cfg)
    L, D, H, Hkv, Dh = s["L"], s["D"], s["H"], s["Hkv"], s["Dh"]
    keys = iter(jax.random.split(key, 64))
    w = {
        "embed": (0.02 * jax.random.normal(next(keys), (s["V"], D))
                  ).astype(jnp.bfloat16),
        "final_norm": (0.05 * jax.random.normal(next(keys), (D,))
                       ).astype(jnp.bfloat16),
        "ln1": (0.05 * jax.random.normal(next(keys), (L, D))
                ).astype(jnp.bfloat16),
        "ln2": (0.05 * jax.random.normal(next(keys), (L, D))
                ).astype(jnp.bfloat16),
        "bq": (0.02 * jax.random.normal(next(keys), (L, H, Dh))
               ).astype(jnp.bfloat16),
        "bk": (0.02 * jax.random.normal(next(keys), (L, Hkv, Dh))
               ).astype(jnp.bfloat16),
        "bv": (0.02 * jax.random.normal(next(keys), (L, Hkv, Dh))
               ).astype(jnp.bfloat16),
    }
    for name in TT_LEAVES:
        rec = spec[name]
        dims, ranks = rec["dims"], rec["ranks"]
        cores = []
        for k, n in enumerate(dims):
            # element variance of the product is 1/fan_in: every later
            # core is scaled by 1/sqrt(r_{k-1}); the layer core's rows get
            # norm sqrt(r_1 / fan_in), so that every layer has that scale
            g = jax.random.normal(next(keys), (ranks[k], n, ranks[k + 1]),
                                  jnp.float32)
            if k == 0:
                g = g / jnp.linalg.norm(g, axis=-1, keepdims=True) \
                    * (ranks[1] / rec["fan_in"]) ** 0.5
            else:
                g = g * ranks[k] ** -0.5
            cores.append(g)
        w[name] = cores
    return w


@functools.lru_cache(maxsize=None)
def _drawer(cfg_key: str):
    import json
    cfg = json.loads(cfg_key)
    return jax.jit(functools.partial(_draw, cfg))


def make_weights(cfg: dict, seed: int) -> dict:
    """Every array of the model, drawn on the device in one jitted call:
    the embedding, norms and biases dense in bf16, each TT leaf as its f32
    cores (the compressor's core type)."""
    import json
    key = jax.random.key(np.uint32(seed % 2**32))
    key = jax.random.fold_in(key, seed >> 32)
    return jax.block_until_ready(
        _drawer(json.dumps(cfg, sort_keys=True))(key))


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ModelConfig

    s = sizes(cfg)
    return ModelConfig(
        name="qwen1.5-0.5b", family="dense", num_layers=s["L"],
        d_model=s["D"], num_heads=s["H"], num_kv_heads=s["Hkv"],
        d_ff=s["F"], vocab_size=s["V"], qkv_bias=True,
        rope_theta=s["theta"], norm_eps=s["eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]), fsdp=False)


def payload(cfg: dict, w: dict):
    """The weights in the compressor's output format: a ``TransformerParams``
    tree whose leaves are ``CompressedParam`` (``tt`` for the spec's leaves,
    ``raw`` for the rest)."""
    from repro.core.compression import CompressedParam
    from repro.core.tt import TTTensor
    from repro.models import attention as attn
    from repro.models import mlp as mlp_mod
    from repro.models import transformer as tfm

    spec = leaf_spec(cfg)

    def tt(name):
        rec = spec[name]
        t = TTTensor(cores=list(w[name]), shape=tuple(rec["dims"]),
                     ranks=tuple(rec["ranks"]))
        return CompressedParam("tt", t, None, tuple(rec["orig_shape"]),
                               jnp.bfloat16)

    def raw(x):
        return CompressedParam("raw", None, x, tuple(x.shape), x.dtype)

    layers = tfm.LayerParams(
        attn=attn.AttnParams(wq=tt("wq"), wk=tt("wk"), wv=tt("wv"),
                             wo=tt("wo"), bq=raw(w["bq"]), bk=raw(w["bk"]),
                             bv=raw(w["bv"])),
        mlp=mlp_mod.MLPParams(w_gate=tt("w_gate"), w_up=tt("w_up"),
                              w_down=tt("w_down")),
        moe=None, ln1=raw(w["ln1"]), ln2=raw(w["ln2"]))
    return tfm.TransformerParams(embed=raw(w["embed"]), layers=layers,
                                 final_norm=raw(w["final_norm"]),
                                 lm_head=None)


def build(cfg: dict, seed: int):
    """(model, served params, payload) for the program: the payload through
    ``tt_native_params``, as compression output is served."""
    from repro.models.common import tt_native_params
    from repro.models.registry import build as build_model

    mcfg = model_config(cfg)
    pl = payload(cfg, make_weights(cfg, seed))
    return build_model(mcfg), tt_native_params(pl, family=mcfg.family), pl


# ------------------------------------------------------------ reference --

def _layer_dense(cores: Sequence[jax.Array], layer, shape) -> jax.Array:
    """One layer's dense weight from the stacked cores, in f32."""
    acc = cores[0][0, layer, :]                      # (r_1,)
    for g in cores[1:]:
        r = g.shape[0]
        acc = jnp.dot(acc.reshape(-1, r), g.reshape(r, -1), precision=HI)
    return acc.reshape(shape)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv     # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _hidden(cfg: dict, w: dict, tokens: jax.Array) -> jax.Array:
    """Final-norm hidden states (n, S, D) of a causal f32 forward pass."""
    s, spec = sizes(cfg), leaf_spec(cfg)
    D, H, Hkv, Dh, eps = s["D"], s["H"], s["Hkv"], s["Dh"], s["eps"]
    n, S = tokens.shape
    pos = jnp.arange(S)
    mask = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)

    def dense(name, layer):
        rec = spec[name]
        return _layer_dense(w[name], layer, rec["orig_shape"][1:])

    def layer_fn(x, l):
        h = _rms(x, w["ln1"][l], eps)
        q = jnp.einsum("nsd,dhk->nshk", h, dense("wq", l), precision=HI) \
            + w["bq"][l].astype(jnp.float32)
        k = jnp.einsum("nsd,dhk->nshk", h, dense("wk", l), precision=HI) \
            + w["bk"][l].astype(jnp.float32)
        v = jnp.einsum("nsd,dhk->nshk", h, dense("wv", l), precision=HI) \
            + w["bv"][l].astype(jnp.float32)
        q = jax.vmap(lambda t: _rope(t, pos, s["theta"]))(q)
        k = jax.vmap(lambda t: _rope(t, pos, s["theta"]))(k)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        sc = jnp.einsum("nqhk,nthk->nhqt", q, k, precision=HI) * Dh ** -0.5
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("nhqt,nthk->nqhk", p, v, precision=HI)
        x = x + jnp.einsum("nqhk,hkd->nqd", o, dense("wo", l), precision=HI)
        h = _rms(x, w["ln2"][l], eps)
        g = jnp.einsum("nsd,df->nsf", h, dense("w_gate", l), precision=HI)
        u = jnp.einsum("nsd,df->nsf", h, dense("w_up", l), precision=HI)
        f = jnp.einsum("nsf,fd->nsd", jax.nn.silu(g) * u, dense("w_down", l),
                       precision=HI)
        return x + f, None

    x, _ = jax.lax.scan(layer_fn, x, jnp.arange(s["L"]))
    return _rms(x, w["final_norm"], eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _hidden_jit(cfg_key, w, tokens):
    import json
    return _hidden(json.loads(cfg_key), w, tokens)


@jax.jit
def _gaps(h, idx, table, tok_sets):
    """At each (request, position) of ``idx``: the best logit minus the
    logit of each token set's token."""
    logits = jnp.dot(h[idx[:, 0], idx[:, 1]], table.astype(jnp.float32).T,
                     precision=HI)
    best = logits.max(-1)
    return jnp.stack([best - jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
                      for t in tok_sets])


def reference_gaps(cfg: dict, seed: int,
                   seqs: List[Tuple[np.ndarray, List[np.ndarray]]],
                   shape: Tuple[int, int], rows: int = 1024
                   ) -> List[np.ndarray]:
    """Plain f32 reference over ``seqs``: each item is ``(prompt, [tokens
    per candidate])`` where every candidate holds one token per position
    after the prompt (the served answer, or what a control put first).
    Returns, per candidate set, the gaps ``best - logit(token)`` at every
    position, all requests concatenated.  The sequences are padded to
    ``shape`` (requests, positions) and the logits taken ``rows`` at a
    time, so that every run compiles the same two programs."""
    import json
    import sys
    import time

    t = [time.monotonic()]

    def lap(what):
        t.append(time.monotonic())
        print(f"[bench] reference {what}: {t[-1] - t[-2]:.2f} s",
              file=sys.stderr, flush=True)

    w = make_weights(cfg, seed)
    lap("weights")
    key = json.dumps(cfg, sort_keys=True)
    n_cand = len(seqs[0][1])
    if len(seqs) > shape[0] or max(len(p) + len(c[0]) - 1
                                   for p, c in seqs) > shape[1]:
        raise ValueError(f"sequences exceed the reference shape {shape}")
    toks = np.zeros(shape, np.int32)
    for i, (p, cands) in enumerate(seqs):
        full = np.concatenate([p, cands[0][:-1]])
        toks[i, :len(full)] = full
    with jax.default_matmul_precision("highest"):
        h = jax.block_until_ready(_hidden_jit(key, w, jnp.asarray(toks)))
        lap(f"forward {toks.shape}")
        picks, sets = [], [[] for _ in range(n_cand)]
        for i, (p, cands) in enumerate(seqs):
            pos = np.arange(len(p) - 1, len(p) - 1 + len(cands[0]))
            picks.append(np.stack([np.full_like(pos, i), pos], 1))
            for j, c in enumerate(cands):
                sets[j].append(np.asarray(c, np.int32))
        idx = np.concatenate(picks)
        total = len(idx)
        size = -(-shape[0] * shape[1] // rows) * rows   # fixed: one program
        idx = np.pad(idx, ((0, size - total), (0, 0)))
        sets = [np.pad(np.concatenate(s), (0, size - total)) for s in sets]
        out = [np.asarray(_gaps(h, idx[a:a + rows], w["embed"],
                                [s[a:a + rows] for s in sets]))
               for a in range(0, size, rows)]
    lap("logits")
    gaps = np.concatenate(out, axis=1)[:, :total]
    return [gaps[j] for j in range(n_cand)]
