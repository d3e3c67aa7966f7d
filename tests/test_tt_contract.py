"""tt_contract kernel-vs-ref equivalence across core depths and dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tt_linear import quantize_core
from repro.kernels.tt_contract.ops import (
    tt_contract, tt_contract_batched, tt_contract_batched_ref,
    tt_contract_ref, tt_dense_ref, tt_dequant_chain,
)


def _mk_chain(rng, mode_dims, ranks):
    """Lead-absorbed chain: cores[0] (n1, r1) 2D, rest (r, n, s), last s=1."""
    cores = [jnp.asarray(
        rng.standard_normal((mode_dims[0], ranks[0])), jnp.float32)]
    rs = list(ranks) + [1]
    for k in range(1, len(mode_dims)):
        cores.append(jnp.asarray(
            rng.standard_normal((rs[k - 1], mode_dims[k], rs[k])),
            jnp.float32,
        ))
    return cores


CASES = [
    # (mode_dims, ranks, split) — depth 2/3 take the fused Pallas kernels,
    # deeper chains the jnp fallback; splits cover (D,F), (D,H,K), (H,K,D)
    ([128, 256], [7], 1),            # mlp-style, 2-core fused
    ([64, 4, 32], [5, 9], 1),        # wq-style, 3-core fused (split 1)
    ([4, 32, 64], [5, 9], 2),        # wo-style, 3-core fused (split 2)
    ([8, 16, 16, 16], [3, 5, 7], 2),     # depth-4 fallback
    ([6, 7, 8, 9, 10], [2, 3, 4, 5], 3),  # depth-5 fallback
]


@pytest.mark.parametrize("mode_dims,ranks,split", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tt_contract_matches_dense(rng, mode_dims, ranks, split, dtype):
    """Fused/fallback contraction == x @ dense-reconstructed matrix."""
    cores = _mk_chain(rng, mode_dims, ranks)
    n_in = int(np.prod(mode_dims[:split]))
    x = jnp.asarray(rng.standard_normal((9, n_in)), dtype)
    w = tt_dense_ref(cores, split)
    y_dense = np.asarray(x, np.float32) @ np.asarray(w)
    y = np.asarray(tt_contract(x, cores, split))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    scale = max(np.abs(y_dense).max(), 1e-6)
    np.testing.assert_allclose(y, y_dense, atol=tol * scale)


@pytest.mark.parametrize("mode_dims,ranks,split", CASES)
def test_tt_contract_kernel_vs_ref(rng, mode_dims, ranks, split):
    """Kernel dispatch output is bitwise-comparable to the einsum chain."""
    cores = _mk_chain(rng, mode_dims, ranks)
    n_in = int(np.prod(mode_dims[:split]))
    x = jnp.asarray(rng.standard_normal((12, n_in)), jnp.float32)
    y_ref = np.asarray(tt_contract_ref(x, cores, split))
    y = np.asarray(tt_contract(x, cores, split))
    scale = max(np.abs(y_ref).max(), 1e-6)
    np.testing.assert_allclose(y, y_ref, atol=1e-5 * scale)


def test_tt_contract_uneven_batch(rng):
    """Token counts that don't tile (prime B) still work — whole-B grid."""
    cores = _mk_chain(rng, [32, 48], [4])
    x = jnp.asarray(rng.standard_normal((13, 32)), jnp.float32)
    y = np.asarray(tt_contract(x, cores, 1))
    w = np.asarray(tt_dense_ref(cores, 1))
    np.testing.assert_allclose(
        y, np.asarray(x) @ w, atol=1e-5 * max(np.abs(w).max(), 1.0)
    )


# ---------------------------------------------------------------------------
# Deep-chain ref fallback: the einsum chain itself vs dense materialization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode_dims,ranks,split", [
    ([8, 16, 16, 16], [3, 5, 7], 1),
    ([8, 16, 16, 16], [3, 5, 7], 3),
    ([4, 6, 8, 10, 12, 6], [2, 3, 4, 3, 2], 3),   # depth-6
])
def test_tt_contract_ref_deep_matches_dense(rng, mode_dims, ranks, split):
    """Depth >= 4 never fuses — pin the fallback oracle itself against the
    reconstruct-then-matmul baseline across split positions."""
    cores = _mk_chain(rng, mode_dims, ranks)
    n_in = int(np.prod(mode_dims[:split]))
    x = jnp.asarray(rng.standard_normal((7, n_in)), jnp.float32)
    y = np.asarray(tt_contract_ref(x, cores, split))
    w = np.asarray(tt_dense_ref(cores, split))
    y_dense = np.asarray(x) @ w
    np.testing.assert_allclose(
        y, y_dense, atol=1e-5 * max(np.abs(y_dense).max(), 1e-6)
    )


# ---------------------------------------------------------------------------
# Expert-batched chain (MoE banks): vmapped kernel vs extended ref oracle
# ---------------------------------------------------------------------------

BATCHED_CASES = [
    ([64, 96], [5], 1),              # 2-core fused per expert
    ([32, 4, 24], [5, 7], 1),        # 3-core fused, split 1
    ([4, 16, 48], [5, 7], 2),        # 3-core fused, split 2
    ([8, 8, 8, 8], [3, 4, 5], 2),    # depth-4 per-expert fallback
]


@pytest.mark.parametrize("mode_dims,ranks,split", BATCHED_CASES)
def test_tt_contract_batched_matches_ref_and_dense(rng, mode_dims, ranks,
                                                   split):
    """Expert-batched dispatch == extended einsum oracle == per-expert
    dense matmuls (experts share tail cores, differ in the lead-absorbed
    first core — exactly what an expert-axis TTLinear hands down)."""
    e, b = 5, 6
    g0b = jnp.asarray(
        rng.standard_normal((e, mode_dims[0], ranks[0])), jnp.float32)
    rest = _mk_chain(rng, mode_dims, ranks)[1:]
    n_in = int(np.prod(mode_dims[:split]))
    x3 = jnp.asarray(rng.standard_normal((e, b, n_in)), jnp.float32)

    y = np.asarray(tt_contract_batched(x3, g0b, rest, split))
    y_ref = np.asarray(tt_contract_batched_ref(x3, g0b, rest, split))
    scale = max(np.abs(y_ref).max(), 1e-6)
    np.testing.assert_allclose(y, y_ref, atol=1e-5 * scale)
    for ei in range(e):
        w = np.asarray(tt_dense_ref([g0b[ei]] + rest, split))
        np.testing.assert_allclose(
            y[ei], np.asarray(x3[ei]) @ w, atol=1e-5 * scale
        )


# ---------------------------------------------------------------------------
# Quantized chains: int8 tail cores, dequantization fused into the kernels
# ---------------------------------------------------------------------------

def _quantize_tail(cores):
    """TTLinear-style quantized chain: wide lead-absorbed first core (its
    scales folded host-side), int8 tail cores + per-row scales."""
    qcores, scales = [cores[0]], [None]
    for g in cores[1:]:
        q, s = quantize_core(g)
        qcores.append(q)
        scales.append(s)
    return qcores, scales


@pytest.mark.parametrize("mode_dims,ranks,split", CASES)
def test_tt_contract_quantized_matches_dequant_ref(rng, mode_dims, ranks,
                                                   split):
    """Fused-dequant dispatch (scale folded into the output tile) == the
    explicit dequantize-then-einsum oracle at f32 tolerance, across the
    fused depths AND the deep-chain fallback (which applies the scale
    product outside the ref chain)."""
    cores = _mk_chain(rng, mode_dims, ranks)
    qcores, scales = _quantize_tail(cores)
    n_in = int(np.prod(mode_dims[:split]))
    x = jnp.asarray(rng.standard_normal((12, n_in)), jnp.float32)
    y = np.asarray(tt_contract(x, qcores, split, scales=scales))
    y_ref = np.asarray(
        tt_contract_ref(x, tt_dequant_chain(qcores, scales), split)
    )
    scale = max(np.abs(y_ref).max(), 1e-6)
    np.testing.assert_allclose(y, y_ref, atol=1e-5 * scale)
    # and the dequantized chain stays close to the unquantized one: int8
    # symmetric rounding moves each core <= scale/2 per element
    y_exact = np.asarray(tt_contract_ref(x, cores, split))
    assert np.abs(y - y_exact).max() <= 0.05 * max(np.abs(y_exact).max(), 1.0)


@pytest.mark.parametrize("mode_dims,ranks,split", BATCHED_CASES)
def test_tt_contract_batched_quantized_vs_per_expert(rng, mode_dims, ranks,
                                                     split):
    """Quantized expert-batched chain == per-expert dequantize-then-contract
    loop: experts share the int8 tail cores and their row scales, so the
    scales are expert-invariant."""
    e, b = 4, 6
    g0b = jnp.asarray(
        rng.standard_normal((e, mode_dims[0], ranks[0])), jnp.float32)
    rest = _mk_chain(rng, mode_dims, ranks)[1:]
    qrest, tail_scales = [], []
    for g in rest:
        q, s = quantize_core(g)
        qrest.append(q)
        tail_scales.append(s)
    n_in = int(np.prod(mode_dims[:split]))
    x3 = jnp.asarray(rng.standard_normal((e, b, n_in)), jnp.float32)

    y = np.asarray(
        tt_contract_batched(x3, g0b, qrest, split, scales=tail_scales)
    )
    for ei in range(e):
        chain = tt_dequant_chain([g0b[ei]] + qrest, [None] + tail_scales)
        y_ref = np.asarray(tt_contract_ref(x3[ei], chain, split))
        np.testing.assert_allclose(
            y[ei], y_ref, atol=1e-5 * max(np.abs(y_ref).max(), 1e-6)
        )


def test_fits_vmem_accounts_core_itemsize(rng, monkeypatch):
    """Regression: the VMEM gate assumed 4 bytes per core element.  An int8
    chain near the limit occupies less than its wide twin — the old
    accounting would bounce it off the fused path it actually fits on.
    Craft a limit between the int8 and the wide footprint of the SAME chain:
    the quantized chain must pass the gate and dispatch fused, the wide
    chain must fail it."""
    from repro.kernels import common as kcommon
    from repro.kernels.tt_contract import kernel as kernel_mod
    from repro.kernels.tt_contract import ops

    cores = _mk_chain(rng, [64, 512], [64])        # tail core 64*512 elements
    qcores, scales = _quantize_tail(cores)
    x = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    n_out = 512
    q_bytes = ops._vmem_bytes(x, qcores, n_out, 1)
    wide_bytes = ops._vmem_bytes(x, cores, n_out, 1)
    # the int8 block is a quarter of the f32 one; its widened copy is a
    # value, counted once, not a double-buffered block; its (1, 64) row
    # scales are one more (double-buffered) block
    tail = 64 * 512
    scale_block = kcommon.vmem_tile_bytes((1, 64), jnp.float32)
    assert wide_bytes - q_bytes == (2 * (4 - 1) * tail - 4 * tail
                                    - 2 * scale_block)
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", (q_bytes + wide_bytes) // 2)

    assert ops._fits_vmem(x, qcores, n_out, split=1)
    assert not ops._fits_vmem(x, cores, n_out, split=1)

    used = {}
    real = kernel_mod.tt_contract_2q

    def spy(*args, **kw):
        used["fused"] = True
        return real(*args, **kw)

    monkeypatch.setattr(kernel_mod, "tt_contract_2q", spy)
    y = np.asarray(ops.tt_contract(x, qcores, 1, scales=scales))
    assert used.get("fused"), "int8 chain fell off the fused path"
    y_ref = np.asarray(
        tt_contract_ref(x, tt_dequant_chain(qcores, scales), 1)
    )
    np.testing.assert_allclose(
        y, y_ref, atol=1e-5 * max(np.abs(y_ref).max(), 1e-6)
    )


# ---------------------------------------------------------------------------
# VMEM dispatch gate: the depth-3 intermediate tile must be accounted
# ---------------------------------------------------------------------------

def test_fits_vmem_counts_depth3_intermediate(rng, monkeypatch):
    """Regression: a chain whose live f32 values (the per-mode partial
    products of the depth-3 body) push the grid step just past the limit
    must fall back to tt_contract_ref — accounting blocks alone would have
    fused it.  Blocks count double-buffered, values once."""
    from repro.kernels import common as kcommon
    from repro.kernels.tt_contract import kernel as kernel_mod
    from repro.kernels.tt_contract import ops

    cores = _mk_chain(rng, [8, 16, 4], [4, 8])
    x = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    n_out = 16 * 4
    blocks, values = ops._vmem_parts(x, cores, n_out, 1)
    bb = kernel_mod._grid_1d(32)
    # (bb, r1) + (bb, r2) + (bb, n3) f32 tiles, each padded to 128 lanes
    assert values == 3 * bb * 128 * 4
    assert ops._vmem_bytes(x, cores, n_out, 1) == 2 * blocks + values
    # limit between blocks-only and the full footprint: refused
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", 2 * blocks + values // 2)
    assert not ops._fits_vmem(x, cores, n_out, split=1)

    def boom(*a, **k):                             # fused path must not run
        raise AssertionError("dispatched past the corrected VMEM budget")
    monkeypatch.setattr(kernel_mod, "tt_contract_3", boom)
    y = np.asarray(ops.tt_contract(x, cores, split=1))
    w = np.asarray(tt_dense_ref(cores, 1))
    y_dense = np.asarray(x) @ w
    np.testing.assert_allclose(
        y, y_dense, atol=1e-5 * max(np.abs(y_dense).max(), 1e-6)
    )

    # control: with the values inside the limit the fused path runs
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", 2 * blocks + values)
    assert ops._fits_vmem(x, cores, n_out, split=1)


def test_fits_vmem_counts_double_buffered_blocks(rng, monkeypatch):
    """Pallas may hold two buffers of every block, resident cores included:
    a limit that fits one copy of the blocks but not two is refused."""
    from repro.kernels import common as kcommon
    from repro.kernels.tt_contract import ops

    cores = _mk_chain(rng, [128, 256], [64])
    x = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
    blocks, values = ops._vmem_parts(x, cores, 256, 1)
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", blocks + values)
    assert not ops._fits_vmem(x, cores, 256, split=1)
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", 2 * blocks + values)
    assert ops._fits_vmem(x, cores, 256, split=1)


def test_dispatch_tally_counts_unfused(rng, monkeypatch):
    """The unfused chain is a counted dispatch decision: a depth-4 chain is
    tallied under ``depth``, a gate-refused depth-3 chain under ``vmem``,
    and a fused call under its kernel's name."""
    from repro.kernels import common as kcommon
    from repro.kernels.tt_contract import ops

    ops.reset_dispatch_tally()
    deep = _mk_chain(rng, [8, 16, 16, 16], [3, 5, 7])
    ops.tt_contract(jnp.ones((8, 8 * 16), jnp.float32), deep, 2)
    wide = _mk_chain(rng, [64, 4, 32], [5, 9])
    x = jnp.ones((8, 64), jnp.float32)
    ops.tt_contract(x, wide, 1)
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", 1024)
    ops.tt_contract(x, wide, 1)
    assert ops.dispatch_tally() == {
        ("unfused", "depth"): 1,
        ("tt_contract_3", ""): 1,
        ("unfused", "vmem"): 1,
    }
    ops.reset_dispatch_tally()
    assert ops.dispatch_tally() == {}


# ---------------------------------------------------------------------------
# Tunable token-dim tile cap (env var / argument; adaptive default)
# ---------------------------------------------------------------------------

def test_resolve_tile_cap(monkeypatch):
    from repro.kernels.tt_contract import kernel as kernel_mod
    from repro.kernels.tt_contract import ops

    default = kernel_mod.DEFAULT_TILE_CAP
    monkeypatch.delenv("TT_CONTRACT_TILE", raising=False)
    # adaptive default: grows when the token extent divides cleanly, but
    # always keeps the historical cap as a VMEM-gate fallback so a bigger
    # default can only ADD fused coverage
    assert ops.resolve_tile_cap(2048) == (2048, 1024, default)
    assert ops.resolve_tile_cap(3 * 1024) == (1024, default)
    assert ops.resolve_tile_cap(384) == (default,)
    assert ops.resolve_tile_cap(100) == (default,)
    # explicit argument beats everything and is never second-guessed
    assert ops.resolve_tile_cap(2048, tile=64) == (64,)
    # env var beats the adaptive default
    monkeypatch.setenv("TT_CONTRACT_TILE", "256")
    assert ops.resolve_tile_cap(2048) == (256,)
    assert ops.resolve_tile_cap(2048, tile=128) == (128,)


def test_tile_cap_rejects_junk_with_clear_message(monkeypatch):
    """Regression: a non-integer or <= 0 TT_CONTRACT_TILE used to crash
    with an opaque int() ValueError deep in dispatch — the error must name
    the env var (or the tile= argument) so the operator knows what to fix."""
    from repro.kernels.tt_contract import ops

    for junk in ("banana", "1.5", " ", "0", "-128"):
        monkeypatch.setenv("TT_CONTRACT_TILE", junk)
        with pytest.raises(ValueError, match="TT_CONTRACT_TILE"):
            ops.resolve_tile_cap(1024)
    # empty string is falsy → the adaptive default, not an error
    monkeypatch.setenv("TT_CONTRACT_TILE", "")
    assert ops.resolve_tile_cap(100)
    monkeypatch.delenv("TT_CONTRACT_TILE", raising=False)
    # the explicit argument gets the same validation, naming tile= instead
    for junk in (0, -64, "pear"):
        with pytest.raises(ValueError, match="tile="):
            ops.resolve_tile_cap(1024, tile=junk)


def test_tile_cap_changes_grid_not_result(rng):
    """Different tile caps pick different grids but identical outputs, and
    _grid_1d honors the cap it is given."""
    from repro.kernels.tt_contract import kernel as kernel_mod

    assert kernel_mod._grid_1d(2048, 1024) == 1024
    assert kernel_mod._grid_1d(1024, 256) == 256
    assert kernel_mod._grid_1d(96, 512) == 96          # whole-batch block

    cores = _mk_chain(rng, [32, 48], [4])
    x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    y_default = np.asarray(tt_contract(x, cores, 1))
    y_small = np.asarray(tt_contract(x, cores, 1, tile=16))
    y_large = np.asarray(tt_contract(x, cores, 1, tile=4096))
    np.testing.assert_allclose(y_small, y_default, atol=1e-6)
    np.testing.assert_allclose(y_large, y_default, atol=1e-6)


def test_bigger_default_cap_never_evicts_fused(rng, monkeypatch):
    """Regression: a shape whose big-tile footprint flunks the VMEM gate
    must retry at the smaller fallback cap and stay fused, not fall back
    to the unfused chain."""
    from repro.kernels import common as kcommon
    from repro.kernels.tt_contract import kernel as kernel_mod
    from repro.kernels.tt_contract import ops

    monkeypatch.delenv("TT_CONTRACT_TILE", raising=False)
    cores = _mk_chain(rng, [64, 128], [4])
    x = jnp.asarray(rng.standard_normal((2048, 64)), jnp.float32)
    n_out = 128
    # limit between the bb=1024 footprint and the bb=512 one
    assert ops._fits_vmem(x, cores, n_out, 1, 512)
    hi = ops._vmem_bytes(x, cores, n_out, 1, 1024)
    lo = ops._vmem_bytes(x, cores, n_out, 1, 512)
    assert lo < hi
    monkeypatch.setattr(kcommon, "VMEM_LIMIT", (hi + lo) // 2)
    assert not ops._fits_vmem(x, cores, n_out, 1, 2048)
    assert not ops._fits_vmem(x, cores, n_out, 1, 1024)
    assert ops._fits_vmem(x, cores, n_out, 1, 512)

    used = {}
    real = kernel_mod.tt_contract_2

    def spy(x2, g0, g1, interpret=False, tile_cap=None):
        used["tile_cap"] = tile_cap
        return real(x2, g0, g1, interpret=interpret, tile_cap=tile_cap)

    monkeypatch.setattr(kernel_mod, "tt_contract_2", spy)
    y = np.asarray(ops.tt_contract(x, cores, 1))
    assert used["tile_cap"] == 512            # retried down, stayed fused
    w = np.asarray(tt_dense_ref(cores, 1))
    np.testing.assert_allclose(
        y, np.asarray(x) @ w, atol=1e-5 * max(np.abs(w).max(), 1.0)
    )
