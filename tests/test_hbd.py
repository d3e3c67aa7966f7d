"""Householder bidiagonalization (paper Algorithm 2) invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hbd import (
    bidiagonal_bands,
    hbd_reduce,
    house,
    house_mm_update,
    householder_bidiagonalize,
)

SHAPES = [(8, 8), (12, 7), (16, 5), (5, 5), (30, 20), (64, 48), (33, 17)]


@pytest.mark.parametrize("m,n", SHAPES)
def test_reconstruction(rng, m, n):
    a = rng.standard_normal((m, n)).astype(np.float32)
    ub, b, vbt = householder_bidiagonalize(jnp.asarray(a))
    assert ub.shape == (m, n) and vbt.shape == (n, n)
    rec = np.asarray(ub) @ np.asarray(b) @ np.asarray(vbt)
    np.testing.assert_allclose(rec, a, atol=5e-5 * np.sqrt(m * n))


@pytest.mark.parametrize("m,n", SHAPES)
def test_orthogonality(rng, m, n):
    a = rng.standard_normal((m, n)).astype(np.float32)
    ub, _, vbt = householder_bidiagonalize(jnp.asarray(a))
    np.testing.assert_allclose(
        np.asarray(ub).T @ np.asarray(ub), np.eye(n), atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(vbt) @ np.asarray(vbt).T, np.eye(n), atol=2e-5
    )


@pytest.mark.parametrize("m,n", SHAPES)
def test_bidiagonal_structure(rng, m, n):
    a = rng.standard_normal((m, n)).astype(np.float32)
    _, b, _ = householder_bidiagonalize(jnp.asarray(a), compute_uv=False)
    bb = np.asarray(b).copy()
    assert bb.shape == (n, n)                       # thin: rows N..M are zero
    for i in range(min(m, n)):
        bb[i, i] = 0.0
        if i + 1 < n:
            bb[i, i + 1] = 0.0
    assert np.abs(bb).max() == 0.0


@pytest.mark.parametrize("m,n", [(12, 7), (33, 17)])
def test_thin_u_is_leading_columns_of_reflector_product(rng, m, n):
    """The thin U_B is the first N columns of H_0 H_1 ... H_{N-1}, each
    reflector rebuilt from the vectors the reduction stores in place."""
    a = rng.standard_normal((m, n)).astype(np.float32)
    a_red, diag, _ = (np.asarray(x, np.float64)
                      for x in hbd_reduce(jnp.asarray(a)))
    full = np.eye(m)
    for i in range(n):
        v = np.where(np.arange(m) >= i, a_red[:, i], 0.0)
        beta = v[i] * diag[i]
        h = np.eye(m) + (np.outer(v, v) / beta if beta != 0 else 0.0)
        full = full @ h
    ub, _, _ = householder_bidiagonalize(jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(ub), full[:, :n], atol=2e-5)


def test_house_matches_paper_eq3(rng):
    """HOUSE output: q = -sign(x1)||x||, v = x + sign(x1)||x|| e1 (masked)."""
    x = rng.standard_normal(10).astype(np.float32)
    mask = np.arange(10) >= 3
    res = house(jnp.asarray(x), jnp.asarray(mask))
    xa = np.where(mask, x, 0.0)
    norm = np.linalg.norm(xa)
    sign = 1.0 if xa[3] >= 0 else -1.0
    assert np.isclose(float(res.q), -sign * norm, rtol=1e-6)
    expected_v = xa.copy()
    expected_v[3] += sign * norm
    np.testing.assert_allclose(np.asarray(res.v), expected_v, rtol=1e-6)


def test_house_mm_update_is_reflection(rng):
    """HOUSE_MM_UPDATE(q, v, A, 0) == H @ A with H = I - 2vv^T/(v^Tv)."""
    m, n = 12, 9
    a = rng.standard_normal((m, n)).astype(np.float32)
    x = rng.standard_normal(m).astype(np.float32)
    mask = np.arange(m) >= 0
    res = house(jnp.asarray(x), jnp.asarray(mask))
    col_mask = np.ones(n, bool)
    out = house_mm_update(
        res.q, res.v, jnp.asarray(a), 0,
        jnp.asarray(mask), jnp.asarray(col_mask),
    )
    v = np.asarray(res.v)
    h = np.eye(m) - 2 * np.outer(v, v) / (v @ v)
    np.testing.assert_allclose(np.asarray(out), h @ a, atol=1e-4)


def test_zero_column_is_identity():
    """HOUSE on a zero vector must produce H = I (beta guard)."""
    m, n = 6, 4
    a = np.ones((m, n), np.float32)
    x = np.zeros(m, np.float32)
    mask = np.ones(m, bool)
    res = house(jnp.asarray(x), jnp.asarray(mask))
    out = house_mm_update(
        res.q, res.v, jnp.asarray(a), 0,
        jnp.asarray(mask), jnp.asarray(np.ones(n, bool)),
    )
    np.testing.assert_allclose(np.asarray(out), a)


def test_bands_roundtrip(rng):
    a = rng.standard_normal((10, 6)).astype(np.float32)
    _, b, _ = householder_bidiagonalize(jnp.asarray(a), compute_uv=False)
    d, e = bidiagonal_bands(b)
    assert d.shape == (6,) and e.shape == (5,)
    bn = np.asarray(b)[:6, :6]
    np.testing.assert_allclose(np.diag(bn), np.asarray(d))
    np.testing.assert_allclose(np.diag(bn, 1), np.asarray(e))
