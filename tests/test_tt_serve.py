"""TT-native serving: TTLinear equivalence, decode parity, TT checkpoints."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CompressionPolicy,
    TTCompressor,
    dequantize_array,
    dequantize_tt,
    is_tt_linear,
    quant_dtype,
    quantize_array,
    quantize_tt,
    quantize_tt_tree,
    select_layer,
    spectral_decay_pytree,
    tt_apply,
    tt_apply_experts,
    tt_leaf_bytes,
    tt_linear_from_tt,
    tt_param_bytes,
    tt_reconstruct,
    ttd,
)
from repro.core.tt_linear import dequantize_core, quantize_core
from repro.models import common as model_common


def _decayed(rng, shape, alpha=1.2):
    w = rng.standard_normal(shape).astype(np.float32)
    mat = w.reshape(-1, shape[-1])
    u, s, vt = np.linalg.svd(mat, full_matrices=False)
    target = s[0] * (np.arange(1, s.size + 1.0) ** -alpha)
    return ((u * target) @ vt).reshape(shape)


# ---------------------------------------------------------------------------
# TTLinear: per-layer apply == slice of the dense reconstruction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,in_ndim", [
    ((3, 64, 96), 1),        # mlp-style  (L, D, F)
    ((3, 64, 4, 16), 1),     # wq-style   (L, D, H, K)
    ((3, 4, 16, 64), 2),     # wo-style   (L, H, K, D)
])
def test_tt_linear_matches_reconstruct(rng, shape, in_ndim):
    w = _decayed(rng, shape)
    tt = ttd(w, eps=0.05, dims=shape)
    lin = tt_linear_from_tt(tt, shape, stack=1, in_ndim=in_ndim,
                            dtype=jnp.float32)
    assert lin is not None
    w_rec = np.asarray(tt_reconstruct(tt))
    in_shape = shape[1:1 + in_ndim]
    x = jnp.asarray(rng.standard_normal((5, *in_shape)), jnp.float32)
    for layer in range(shape[0]):
        y = np.asarray(tt_apply(x, select_layer(lin, layer)))
        wl = w_rec[layer].reshape(int(np.prod(in_shape)), -1)
        y_ref = (np.asarray(x).reshape(5, -1) @ wl).reshape(y.shape)
        scale = max(np.abs(y_ref).max(), 1e-6)
        np.testing.assert_allclose(y, y_ref, atol=1e-4 * scale)


def test_tt_linear_traced_layer_select(rng):
    """select_layer under a traced index (the scan path) == concrete."""
    shape = (4, 32, 48)
    w = _decayed(rng, shape)
    lin = tt_linear_from_tt(ttd(w, eps=0.1, dims=shape), shape, 1, 1,
                            dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 32)), jnp.float32)

    def one(idx):
        return tt_apply(x, select_layer(lin, idx))

    ys = jax.lax.map(one, jnp.arange(shape[0]))
    for layer in range(shape[0]):
        np.testing.assert_allclose(
            np.asarray(ys[layer]), np.asarray(one(layer)), atol=1e-6
        )


def test_tt_linear_rejects_padded_dims(rng):
    """Dims that aren't a per-axis concatenation → None (reconstruct)."""
    w = _decayed(rng, (4, 32, 48))
    tt = ttd(w.reshape(2, 2, 32, 48), eps=0.1)      # stack axis split in two
    assert tt_linear_from_tt(tt, (5, 32, 48), stack=1, in_ndim=1) is None


def test_select_layer_out_of_range_clamps(rng):
    """Pinned behavior: an out-of-range layer index — traced or concrete —
    CLAMPS to the last layer (mode="clip"), never NaN-fills.  Covers both
    the TTLinear lead gather and the raw-leaf gather in layer_at."""
    shape = (3, 32, 48)
    w = _decayed(rng, shape)
    lin = tt_linear_from_tt(ttd(w, eps=0.1, dims=shape), shape, 1, 1,
                            dtype=jnp.float32)
    raw = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    tree = {"tt": lin, "raw": raw}

    last = model_common.layer_at(tree, 2)
    for idx in (7, jnp.int32(7)):
        over = model_common.layer_at(tree, idx)
        np.testing.assert_array_equal(
            np.asarray(over["tt"].lead), np.asarray(last["tt"].lead)
        )
        np.testing.assert_array_equal(
            np.asarray(over["raw"]), np.asarray(last["raw"])
        )
    # under jit (the traced-scan path) the same clamp applies
    over_jit = jax.jit(lambda i: model_common.layer_at(tree, i))(99)
    np.testing.assert_array_equal(
        np.asarray(over_jit["tt"].lead), np.asarray(last["tt"].lead)
    )
    assert np.isfinite(np.asarray(over_jit["raw"])).all()


# ---------------------------------------------------------------------------
# Expert-bank TTLinear (MoE): batched apply == per-expert dense slices
# ---------------------------------------------------------------------------

def test_tt_linear_expert_bank_matches_reconstruct(rng):
    shape = (3, 4, 32, 48)                          # (L, E, D, F)
    w = _decayed(rng, shape)
    tt = ttd(w, eps=0.05, dims=shape)
    lin = tt_linear_from_tt(tt, shape, stack=2, in_ndim=1,
                            dtype=jnp.float32, experts=1)
    assert lin is not None
    assert lin.experts == 4
    assert lin.lead.shape == (3, 4, lin.cores[0].shape[0])
    w_rec = np.asarray(tt_reconstruct(tt)).reshape(shape)
    x = jnp.asarray(rng.standard_normal((4, 5, 32)), jnp.float32)
    for layer in range(shape[0]):
        sel = select_layer(lin, layer)
        assert sel.lead.shape == (4, lin.cores[0].shape[0])
        y = np.asarray(tt_apply_experts(x, sel))    # (E, 5, F)
        for e in range(shape[1]):
            y_ref = np.asarray(x[e]) @ w_rec[layer, e]
            scale = max(np.abs(y_ref).max(), 1e-6)
            np.testing.assert_allclose(y[e], y_ref, atol=1e-4 * scale)


# ---------------------------------------------------------------------------
# dense_apply dispatch
# ---------------------------------------------------------------------------

def test_dense_apply_raw_matches_einsum(rng):
    x = jnp.asarray(rng.standard_normal((2, 3, 16)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((16, 4, 8)), jnp.bfloat16)
    out = model_common.dense_apply(x, w, in_ndim=1)
    ref = jnp.einsum("bsd,dhk->bshk", x, w)
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=1e-2
    )
    o = jnp.asarray(rng.standard_normal((2, 3, 4, 8)), jnp.bfloat16)
    wo = jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.bfloat16)
    out2 = model_common.dense_apply(o, wo, in_ndim=2)
    ref2 = jnp.einsum("bshk,hkd->bsd", o, wo)
    np.testing.assert_allclose(
        np.asarray(out2, np.float32), np.asarray(ref2, np.float32), atol=1e-1
    )


# ---------------------------------------------------------------------------
# Accounting + conversion plumbing
# ---------------------------------------------------------------------------

def test_tt_param_bytes_skips_non_array_leaves(rng):
    """Pytrees carrying Python scalars (step counters in checkpoint trees)
    must not crash the byte accounting — non-array leaves are skipped."""
    arr = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    tree = {"w": arr, "step": 7, "lr": 1e-3, "done": False}
    assert tt_param_bytes(tree) == arr.size * 4
    # numpy scalars still count (they carry size/dtype)
    tree["np_step"] = np.int32(7)
    assert tt_param_bytes(tree) == arr.size * 4 + 4


def _payload_one(rng):
    params = {"layers": {"mlp": {"w_gate": jnp.asarray(
        _decayed(rng, (3, 64, 96)), jnp.bfloat16)}}}
    comp = TTCompressor(CompressionPolicy(eps=0.1, min_size=1024))
    payload, _ = comp.compress(params)
    return payload


def test_tt_native_params_core_dtype_sentinel(rng):
    """None is the only "unset" sentinel: explicit dtypes are honored even
    when they'd compare falsy/equal-to-default after normalization, and
    None falls back to each leaf's original dtype."""
    payload = _payload_one(rng)

    def tt_leaf(tree):
        leaves = [leaf for leaf in jax.tree.leaves(tree, is_leaf=is_tt_linear)
                  if is_tt_linear(leaf)]
        assert len(leaves) == 1
        return leaves[0]

    default = tt_leaf(model_common.tt_native_params(payload))
    assert all(c.dtype == jnp.bfloat16 for c in default.cores)  # orig dtype
    explicit = tt_leaf(model_common.tt_native_params(
        payload, core_dtype=jnp.float32))
    assert all(c.dtype == jnp.float32 for c in explicit.cores)
    # an explicit dtype equal to the original must take the same branch as
    # any other explicit dtype (the old `or` collapsed this case)
    same = tt_leaf(model_common.tt_native_params(
        payload, core_dtype=jnp.bfloat16))
    assert all(c.dtype == jnp.bfloat16 for c in same.cores)


def test_tt_serve_rules_registry_covers_every_family():
    """Each family registers its own rule set beside its model module."""
    for fam in ("dense", "moe", "vlm", "encdec", "ssm", "hybrid"):
        assert model_common.tt_serve_rules(fam), fam
    union = model_common.tt_serve_rules(None)
    assert len(union) > len(model_common.tt_serve_rules("ssm"))
    # unknown family: no rules, everything reconstructs (no crash)
    assert model_common.tt_serve_rules("no-such-family") == ()


def test_tt_checkpoint_family_guard(rng, tmp_path):
    """A payload saved with a recorded family refuses to serve a different
    arch family; the matching family (or a legacy manifest without one)
    loads normally."""
    from argparse import Namespace
    from types import SimpleNamespace

    from repro.checkpoint.checkpoint import save_tt_payload
    from repro.launch import serve as serve_mod

    payload = _payload_one(rng)
    like = jax.tree.map(
        lambda c: jnp.zeros(c.orig_shape, c.orig_dtype), payload,
        is_leaf=lambda x: hasattr(x, "kind"),
    )
    path = str(tmp_path / "ttck")
    save_tt_payload(path, payload, extra={"eps": 0.1}, family="ssm")
    args = Namespace(tt_checkpoint=path, tt_eps=0.2, tt_alpha=1.0,
                     save_tt_checkpoint=None)

    with pytest.raises(ValueError, match="family"):
        serve_mod._tt_setup(like, args, SimpleNamespace(family="dense"))
    params_tt, loaded, line = serve_mod._tt_setup(
        like, args, SimpleNamespace(family="ssm"))
    assert "weight bytes" in line


# ---------------------------------------------------------------------------
# Quantized TT cores: round-trip bounds, apply parity, byte accounting
# ---------------------------------------------------------------------------

def _tt_linear_one(rng, shape=(3, 64, 96), experts=0, eps=0.05):
    stack = 1 + (1 if experts else 0)
    w = _decayed(rng, shape)
    tt = ttd(w, eps=eps, dims=shape)
    lin = tt_linear_from_tt(tt, shape, stack=stack, in_ndim=1,
                            dtype=jnp.float32, experts=experts)
    assert lin is not None
    return lin


def test_quantize_roundtrip_error_bound(rng):
    """Symmetric round-to-nearest int8: per-element error <= scale/2 =
    amax/(2*127) — the documented bound the module docstring carries."""
    a = jnp.asarray(rng.standard_normal((64, 48)) * 3.0, jnp.float32)
    q, s = quantize_array(a)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_array(q, s)) - np.asarray(a))
    amax = float(np.abs(np.asarray(a)).max())
    assert err.max() <= amax / (2 * 127) + 1e-7
    # per-row (lead-table) scales bound per ROW by that row's amax
    qr, sr = quantize_array(a, axis=-1)
    err_r = np.abs(np.asarray(dequantize_array(qr, sr, axis=-1))
                   - np.asarray(a))
    row_amax = np.abs(np.asarray(a)).max(axis=-1, keepdims=True)
    assert (err_r <= row_amax / (2 * 127) + 1e-7).all()
    # all-zero groups round-trip exactly (scale pinned to 1)
    qz, sz = quantize_array(jnp.zeros((4, 4)))
    assert float(sz) == 1.0
    np.testing.assert_array_equal(np.asarray(dequantize_array(qz, sz)), 0.0)


def test_dequantize_core_reads_per_core_scale(rng):
    """A core quantized with one scale for the whole core (int8 checkpoints
    written before row scales) dequantizes with that scale."""
    g = jnp.asarray(rng.standard_normal((6, 5, 4)), jnp.float32)
    q, s = quantize_array(g)
    assert np.ndim(s) == 0
    np.testing.assert_array_equal(np.asarray(dequantize_core(q, s)),
                                  np.asarray(dequantize_array(q, s)))
    qr, sr = quantize_core(g)
    assert sr.shape == (6,)
    np.testing.assert_array_equal(
        np.asarray(dequantize_core(qr, sr)),
        np.asarray(qr, np.float32) * np.asarray(sr)[:, None, None])


def test_quantize_tt_roundtrip_and_idempotence(rng):
    """dequantize_tt inverts to the grid; requantizing the dequantized form
    (absmax calibration) is bit-identical — the property the int8
    checkpoint round-trip leans on."""
    lin = _tt_linear_one(rng)
    q = quantize_tt(lin)
    assert q.quantized and not lin.quantized
    assert all(g.dtype == jnp.int8 for g in q.cores)
    assert q.lead.dtype == jnp.int8 and q.lead_scale.shape == (3,)
    wide = dequantize_tt(q)
    assert not wide.quantized
    q2 = quantize_tt(wide)
    for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(q2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # double-quantization is a bug, not a silent no-op
    with pytest.raises(AssertionError):
        quantize_tt(q)


def test_quantized_tt_apply_matches_dequantized(rng):
    """Quantized apply (fused in-kernel dequant) == apply of the explicitly
    dequantized TTLinear — same chain, same order, f32 tolerance."""
    lin = _tt_linear_one(rng)
    q = quantize_tt(lin)
    wide = dequantize_tt(q)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    for layer in range(3):
        y_q = np.asarray(tt_apply(x, select_layer(q, layer)))
        y_w = np.asarray(tt_apply(x, select_layer(wide, layer)))
        scale = max(np.abs(y_w).max(), 1e-6)
        np.testing.assert_allclose(y_q, y_w, atol=1e-4 * scale)
        # and the quantization itself stays small vs the unquantized apply
        y0 = np.asarray(tt_apply(x, select_layer(lin, layer)))
        assert np.abs(y_q - y0).max() <= 0.05 * max(np.abs(y0).max(), 1.0)


def test_quantized_expert_bank_matches_dequantized(rng):
    """Quantized expert-batched chain == per-expert dequantized applies —
    the (layer, expert)-row lead scales must land on the right rows."""
    lin = _tt_linear_one(rng, shape=(3, 4, 32, 48), experts=1)
    q = quantize_tt(lin)
    assert q.lead_scale.shape == (3, 4)
    wide = dequantize_tt(q)
    x = jnp.asarray(rng.standard_normal((4, 5, 32)), jnp.float32)
    for layer in range(3):
        y_q = np.asarray(tt_apply_experts(x, select_layer(q, layer)))
        y_w = np.asarray(tt_apply_experts(x, select_layer(wide, layer)))
        scale = max(np.abs(y_w).max(), 1e-6)
        np.testing.assert_allclose(y_q, y_w, atol=1e-4 * scale)


def test_quant_dtype_registry():
    assert quant_dtype("int8") == jnp.int8
    with pytest.raises(ValueError, match="int8"):
        quant_dtype("int3")
    with pytest.raises(ValueError, match="calibration"):
        quantize_array(jnp.ones((4, 4)), calib="bogus")
    with pytest.raises(ValueError, match="calibration"):
        quantize_array(jnp.ones((4, 4)), calib="p0")   # 0th pct is invalid


def test_tt_param_bytes_matches_tree_walk(rng):
    """The reported bytes must equal an independent jax.tree byte walk over
    every array hanging off the pytree — scales included.  This is the
    regression for the original bug: tt_param_bytes enumerated lead+cores
    by hand, so the quantization scale arrays escaped the accounting."""
    lin = _tt_linear_one(rng)
    q = quantize_tt(lin)
    tree = {"tt": q, "raw": jnp.asarray(rng.standard_normal((16,)),
                                        jnp.float32)}

    def walk_bytes(t):
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(t)
                   if hasattr(a, "size") and hasattr(a, "dtype"))

    # TTLinear is a registered pytree node: jax.tree.leaves reaches lead,
    # cores, scales, and lead_scale without any hand enumeration
    assert tt_param_bytes(tree) == walk_bytes(tree)
    # quantization shrinks the leaf even while scales ride along
    assert tt_param_bytes({"w": q}) < tt_param_bytes({"w": lin})
    # tt_leaf_bytes agrees with the same walk restricted to the TT leaf
    leaf_b, dense_b = tt_leaf_bytes(tree)
    assert leaf_b == walk_bytes({"w": q})
    assert dense_b == 3 * 64 * 96 * 4              # L * in * out * f32


def test_quantize_tt_tree_only_touches_tt_leaves(rng):
    lin = _tt_linear_one(rng)
    raw = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    tree = quantize_tt_tree({"tt": lin, "raw": raw})
    assert tree["tt"].quantized
    assert tree["raw"].dtype == jnp.float32
    # idempotent at the tree level: already-quantized leaves pass through
    tree2 = quantize_tt_tree(tree)
    assert tree2["tt"] is tree["tt"]


def test_tt_native_params_quant(rng):
    """tt_native_params(quant=...) returns int8 TTLinear leaves; junk quant
    names raise the registry's ValueError before any conversion work."""
    payload = _payload_one(rng)
    tree = model_common.tt_native_params(payload, quant="int8")
    leaves = [leaf for leaf in jax.tree.leaves(tree, is_leaf=is_tt_linear)
              if is_tt_linear(leaf)]
    assert leaves and all(
        leaf.quantized and all(g.dtype == jnp.int8 for g in leaf.cores)
        for leaf in leaves
    )
    with pytest.raises(ValueError, match="int8"):
        model_common.tt_native_params(payload, quant="fp97")


def test_tt_payload_checkpoint_quantized_roundtrip(rng, tmp_path):
    """save(quant="int8") → load → requantize is bit-exact: the loaded
    cores sit on the integer grid, so absmax requantization reproduces the
    saved integer values and scales."""
    from repro.checkpoint.checkpoint import load_tt_payload, save_tt_payload

    params = {"w": jnp.asarray(_decayed(rng, (3, 32, 48)))}
    comp = TTCompressor(CompressionPolicy(eps=0.1, min_size=1024))
    payload, _ = comp.compress(params)
    path = str(tmp_path / "ttq")
    save_tt_payload(path, payload, quant="int8")

    loaded, manifest = load_tt_payload(path, like=params)
    assert manifest["quant"] == "int8"
    cp0 = [c for c in jax.tree.leaves(
        payload, is_leaf=lambda x: hasattr(x, "kind")) if c.kind == "tt"]
    cp1 = [c for c in jax.tree.leaves(
        loaded, is_leaf=lambda x: hasattr(x, "kind")) if c.kind == "tt"]
    assert len(cp0) == len(cp1) == 1
    for g0, g1 in zip(cp0[0].tt.cores, cp1[0].tt.cores):
        q0, s0 = quantize_core(jnp.asarray(g0, jnp.float32))
        q1, s1 = quantize_core(jnp.asarray(g1, jnp.float32))
        assert s0.shape == (g0.shape[0],)             # one scale per row
        np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
        # loaded core == dequantized form of the saved one (grid-exact)
        np.testing.assert_array_equal(
            np.asarray(g1), np.asarray(dequantize_core(q0, s0))
        )


def test_tt_setup_quantized_reports_byte_ladder(rng, tmp_path):
    """--weights tt-int8 through _tt_setup: quantized leaves come back and
    the report line carries the dense -> tt -> tt-int8 ladder plus the
    TT-served-leaf reduction the bench lane gates on."""
    from argparse import Namespace
    from types import SimpleNamespace

    from repro.launch import serve as serve_mod

    params = {"layers": {"mlp": {"w_gate": jnp.asarray(
        _decayed(rng, (3, 64, 96)), jnp.bfloat16)}}}
    args = Namespace(weights="tt-int8", quant_calib="absmax",
                     tt_checkpoint=None, tt_eps=0.1, tt_alpha=1.0,
                     save_tt_checkpoint=str(tmp_path / "ck"))
    params_tt, payload, line = serve_mod._tt_setup(
        params, args, SimpleNamespace(family=None, name="test"))
    leaves = [leaf for leaf in jax.tree.leaves(
        params_tt, is_leaf=is_tt_linear) if is_tt_linear(leaf)]
    assert leaves and all(leaf.quantized for leaf in leaves)
    assert "tt-int8" in line and "TT-served leaves" in line
    # the saved checkpoint recorded the quantized form
    import json, os
    with open(os.path.join(str(tmp_path / "ck"), "tt_manifest.json")) as f:
        assert json.load(f)["quant"] == "int8"
    with pytest.raises(ValueError, match="int8"):
        serve_mod._quant_of("tt-fp97")
    assert serve_mod._quant_of("tt") is None


# ---------------------------------------------------------------------------
# End-to-end: TT-native decode == reconstruct-then-serve decode
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tt_native_decode_matches_reconstruct():
    from repro.configs import get_config
    from repro.models.registry import build

    cfg = get_config("gemma3-1b").reduced()
    model = build(cfg)
    params = spectral_decay_pytree(model.init(jax.random.PRNGKey(0)))
    comp = TTCompressor(CompressionPolicy(eps=0.2, min_size=8192))
    payload, report = comp.compress(params)
    assert report.ratio > 1.5

    params_rx = comp.decompress(payload)
    params_tt = model_common.tt_native_params(payload)
    tt_leaves = [
        leaf for leaf in jax.tree.leaves(params_tt, is_leaf=is_tt_linear)
        if is_tt_linear(leaf)
    ]
    assert len(tt_leaves) == 7          # wq wk wv wo w_gate w_up w_down
    assert tt_param_bytes(params_tt) < tt_param_bytes(params_rx)

    rng = np.random.default_rng(0)
    b, plen = 2, 6
    prompts = rng.integers(0, cfg.vocab_size, (b, plen), np.int32)
    decode = jax.jit(model.decode_step)
    c1 = model.init_cache(b, plen)
    c2 = model.init_cache(b, plen)
    for i in range(plen):
        tok = jnp.asarray(prompts[:, i:i + 1])
        l1, c1 = decode(params_rx, c1, tok)
        l2, c2 = decode(params_tt, c2, tok)
    d, scale, _ = model_common.logit_parity(l2, l1)
    # same cores, same contraction order — bf16 rounding only, far inside ε
    assert d <= max(0.05 * scale, 1e-3), (d, scale)

    # prefill/forward path takes the TT-aware scan too
    p1 = model.prefill(params_rx, {"tokens": jnp.asarray(prompts)})
    p2 = model.prefill(params_tt, {"tokens": jnp.asarray(prompts)})
    dp, pscale, _ = model_common.logit_parity(p2, p1)
    assert dp <= max(0.05 * pscale, 1e-3), dp


# ---------------------------------------------------------------------------
# TT payload checkpoint round-trip
# ---------------------------------------------------------------------------

def test_tt_payload_checkpoint_roundtrip(rng, tmp_path):
    from repro.checkpoint.checkpoint import load_tt_payload, save_tt_payload

    params = {
        "w": jnp.asarray(_decayed(rng, (3, 32, 48))),
        "norm": jnp.asarray(rng.standard_normal((8,)), jnp.float32),
        "embed": jnp.asarray(_decayed(rng, (64, 96)), jnp.bfloat16),
    }
    comp = TTCompressor(CompressionPolicy(eps=0.1, min_size=1024))
    payload, _ = comp.compress(params)
    path = str(tmp_path / "ttckpt")
    save_tt_payload(path, payload, extra={"eps": 0.1})

    # overwriting an existing committed payload goes through the .old swap
    save_tt_payload(path, payload, extra={"eps": 0.1})

    loaded, manifest = load_tt_payload(path, like=params)
    assert manifest["extra"]["eps"] == 0.1
    flat0 = jax.tree_util.tree_flatten_with_path(
        payload, is_leaf=lambda x: hasattr(x, "kind"))[0]
    flat1 = jax.tree_util.tree_flatten_with_path(
        loaded, is_leaf=lambda x: hasattr(x, "kind"))[0]
    for (p0, c0), (p1, c1) in zip(flat0, flat1):
        assert p0 == p1
        assert c0.kind == c1.kind
        assert tuple(c0.orig_shape) == tuple(c1.orig_shape)
        assert jnp.dtype(c0.orig_dtype) == jnp.dtype(c1.orig_dtype)
        if c0.kind == "tt":
            assert tuple(c0.tt.ranks) == tuple(c1.tt.ranks)
            assert c0.tt.eps == c1.tt.eps
            for g0, g1 in zip(c0.tt.cores, c1.tt.cores):
                np.testing.assert_array_equal(
                    np.asarray(g0, np.float32), np.asarray(g1, np.float32)
                )
    # reconstruction error is preserved exactly
    rec0 = comp.decompress(payload)
    rec1 = comp.decompress(loaded)
    for a, b in zip(jax.tree.leaves(rec0), jax.tree.leaves(rec1)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )
