"""ResNet-32 (CIFAR, n = 5) weights for the paper's compression workload,
and the plain reference its TT payloads are held to.

Weights: each conv/fc kernel is ``U diag(s) V^T`` of its ``(out, in*kh*kw)``
matricization with ``s_i = i^-alpha`` (the power-law spectrum of trained
convnets) at He-init scale; BN scales are ones and shifts zeros.  This is
the generator of the repository's ``benchmarks/workload_resnet32.py``,
rewritten to draw every array on the device in one jitted call.

Reference: a TT-SVD written here in float64 NumPy, at fixed ranks or
truncated by the policy's eps under Algorithm 1's rule, and a plain
reconstruction of a payload.  ``tt_svd`` can round every quantity
it computes to fewer mantissa bits, which is how the control computes the
same decomposition in a lower precision.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def conv_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    """Name -> shape of every parameter, in network order."""
    n, widths, k = cfg["blocks_per_stage"], cfg["widths"], cfg["kernel_size"]
    out: Dict[str, Tuple[int, ...]] = {}

    def conv(name, c_out, c_in):
        out[f"{name}.w"] = (c_out, c_in, k, k)
        out[f"{name}.bn.g"] = (c_out,)
        out[f"{name}.bn.b"] = (c_out,)

    conv("conv1", widths[0], cfg["in_channels"])
    for s, w in enumerate(widths):
        w_in = widths[0] if s == 0 else widths[s - 1]
        for b in range(n):
            conv(f"s{s}.b{b}.conv1", w, w_in if b == 0 else w)
            conv(f"s{s}.b{b}.conv2", w, w)
    out["fc.w"] = (cfg["num_classes"], widths[-1])
    out["fc.b"] = (cfg["num_classes"],)
    return out


def _spectral(key, shape, alpha):
    m, n = shape[0], int(np.prod(shape[1:]))
    k = min(m, n)
    ku, kv = jax.random.split(key)
    qu, _ = jnp.linalg.qr(jax.random.normal(ku, (m, k), jnp.float32))
    qv, _ = jnp.linalg.qr(jax.random.normal(kv, (n, k), jnp.float32))
    s = jnp.arange(1, k + 1, dtype=jnp.float32) ** (-alpha)
    w = (qu * s) @ qv.T
    # He-init scale, as trained nets roughly keep their init magnitude
    w = w * (np.sqrt(2.0 / n) * np.sqrt(m * n)) / jnp.linalg.norm(w)
    return w.reshape(shape)


def _draw(cfg: dict, key):
    shapes = conv_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        if name.endswith(".w"):
            out[name] = _spectral(k, shape, cfg["spectrum_alpha"])
        elif name.endswith(".g"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    return out


@functools.lru_cache(maxsize=None)
def _drawer(cfg_key: str):
    return jax.jit(functools.partial(_draw, json.loads(cfg_key)))


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    key = jax.random.fold_in(jax.random.key(np.uint32(seed % 2**32)),
                             seed >> 32)
    return jax.block_until_ready(
        _drawer(json.dumps(cfg, sort_keys=True))(key))


def policy(cfg: dict):
    from repro.core import CompressionPolicy

    return CompressionPolicy(**cfg["policy"])


# ------------------------------------------------------------ reference --

def round_bits(x: np.ndarray, bits: int) -> np.ndarray:
    """``x`` rounded to ``bits`` significant bits (nearest, ties to even):
    8 is bfloat16, 16 the two-term bf16 split of ``high`` precision, 24
    float32; 53 leaves float64 as it is."""
    if bits >= 53:
        return x
    m, e = np.frexp(x)
    return np.ldexp(np.round(np.ldexp(m, bits)), e - bits)


def truncation_rank(s: np.ndarray, delta: float) -> int:
    """The delta-truncation of Algorithm 1: keep the singular values up to
    and including the first index whose tail norm ``||s[i:]||`` falls
    below ``delta``; all of them where none does."""
    tail = np.sqrt(np.cumsum((s ** 2)[::-1]))[::-1]
    hits = np.nonzero(tail < delta)[0]
    return int(min(hits[0] + 1, s.size)) if hits.size else int(s.size)


def tt_svd(w: np.ndarray, dims: Sequence[int], ranks: Optional[Sequence[int]]
           = None, eps: Optional[float] = None, bits: int = 53
           ) -> List[np.ndarray]:
    """Left-to-right TT-SVD of ``w`` over ``dims``: at fixed ``ranks``, or
    delta-truncated with ``delta = eps * ||w|| / sqrt(d - 1)`` at each
    unfolding.  Every matrix, factor and core is rounded to ``bits``
    significant bits."""
    d = len(dims)
    c = round_bits(np.asarray(w, np.float64).reshape(dims), bits)
    delta = (None if eps is None
             else eps * np.linalg.norm(c) / np.sqrt(max(d - 1, 1)))
    cores, r_prev = [], 1
    for k in range(d - 1):
        mat = c.reshape(r_prev * dims[k], -1)
        u, s, vt = np.linalg.svd(mat, full_matrices=False)
        u, s, vt = (round_bits(a, bits) for a in (u, s, vt))
        r = ranks[k + 1] if ranks is not None else truncation_rank(s, delta)
        cores.append(u[:, :r].reshape(r_prev, dims[k], r))
        c = round_bits(s[:r, None] * vt[:r], bits)
        r_prev = r
    cores.append(c.reshape(r_prev, dims[-1], 1))
    return cores


def reconstruct(cores: Sequence[np.ndarray], bits: int = 53) -> np.ndarray:
    acc = np.asarray(cores[0], np.float64)
    for g in cores[1:]:
        r = g.shape[0]
        acc = round_bits(acc.reshape(-1, r) @ np.asarray(g, np.float64)
                         .reshape(r, -1), bits)
    return acc.reshape([g.shape[1] for g in cores])


def payload_leaves(payload) -> Dict[str, dict]:
    """Host copy of each TT leaf of a compressor payload: cores, dims,
    crop and original shape."""
    from repro.core.compression import CompressedParam

    out = {}
    for name, c in payload.items():
        if isinstance(c, CompressedParam) and c.kind == "tt":
            out[name] = {
                "cores": [np.asarray(g, np.float64) for g in c.tt.cores],
                "dims": tuple(c.tt.shape), "crop": c.crop_dims,
                "shape": tuple(c.orig_shape)}
    return out


def leaf_readings(w: np.ndarray, leaf: dict, eps: float) -> Dict[str, float]:
    """A payload leaf against the weight it stands for: ``eps_error``, its
    relative Frobenius error; ``ref_deviation``, the relative distance of
    its reconstruction from the float64 TT-SVD of the weight at the leaf's
    own ranks; ``ranks_differ``, 1 where its ranks are not those of the
    float64 TT-SVD truncated at ``eps`` over the same dims, else 0."""
    dims, crop = leaf["dims"], leaf["crop"]
    cropped = crop is not None and tuple(crop) != tuple(dims)
    full = reconstruct(leaf["cores"])
    x = np.asarray(w, np.float64)
    if cropped:
        full = full[tuple(slice(0, n) for n in crop)]
        xp = np.zeros(dims)
        xp[tuple(slice(0, n) for n in crop)] = x.reshape(crop)
    else:
        xp = x.reshape(dims)
    ranks = [1] + [g.shape[2] for g in leaf["cores"]]
    ref = reconstruct(tt_svd(xp, dims, ranks=ranks))
    if cropped:
        ref = ref[tuple(slice(0, n) for n in crop)]
    ref_ranks = [1] + [g.shape[2] for g in tt_svd(xp, dims, eps=eps)]
    norm = np.linalg.norm(x)
    return {
        "eps_error": float(np.linalg.norm(full.reshape(x.shape) - x) / norm),
        "ref_deviation": float(np.linalg.norm(full.reshape(x.shape)
                                              - ref.reshape(x.shape)) / norm),
        "ranks_differ": float(ranks != ref_ranks)}


def control_payload(cfg: dict, weights: Dict[str, np.ndarray],
                    dims_of: Dict[str, Tuple[int, ...]], bits: int):
    """The reference TT-SVD in the program's place, at ``bits`` mantissa
    bits and the policy's eps, over the dims the program chose."""
    eps = cfg["policy"]["eps"]
    return {name: {"cores": tt_svd(weights[name], dims, eps=eps, bits=bits),
                   "dims": tuple(dims), "crop": None,
                   "shape": tuple(weights[name].shape)}
            for name, dims in dims_of.items()}
