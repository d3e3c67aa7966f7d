"""Two-phase SVD: values/bases vs library + Jacobi phase-2 oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bidiag_qr import bidiag_svd_values, jacobi_svd_values
from repro.core.hbd import bidiagonal_bands, householder_bidiagonalize
from repro.core.svd import sorting_basis, svd, svd_host_diag, svd_reconstruct

SHAPES = [(24, 24), (40, 16), (16, 40), (50, 30), (7, 13)]
# phase 2 in the graph (batched/in-graph paths) or on the host's LAPACK
# (the serial TT-SVD): same contract
TWO_PHASE = {"in_graph": lambda a: svd(a, method="two_phase"),
             "host_diag": svd_host_diag}


@pytest.mark.parametrize("impl", sorted(TWO_PHASE))
@pytest.mark.parametrize("m,n", SHAPES)
def test_two_phase_values_match_library(rng, m, n, impl):
    a = rng.standard_normal((m, n)).astype(np.float32)
    r = TWO_PHASE[impl](jnp.asarray(a))
    s_ref = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(np.asarray(r.s), s_ref, atol=2e-5 * s_ref[0])


@pytest.mark.parametrize("impl", sorted(TWO_PHASE))
@pytest.mark.parametrize("m,n", SHAPES)
def test_two_phase_reconstructs(rng, m, n, impl):
    a = rng.standard_normal((m, n)).astype(np.float32)
    r = TWO_PHASE[impl](jnp.asarray(a))
    np.testing.assert_allclose(
        np.asarray(svd_reconstruct(r)), a, atol=5e-5 * np.sqrt(m * n)
    )


@pytest.mark.parametrize("m,n", [(64, 32), (96, 48)])
def test_blocked_hbd_svd(rng, m, n):
    a = rng.standard_normal((m, n)).astype(np.float32)
    r = svd(jnp.asarray(a), method="two_phase", hbd_impl="blocked", panel=16)
    s_ref = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(np.asarray(r.s), s_ref, atol=5e-5 * s_ref[0])
    np.testing.assert_allclose(
        np.asarray(svd_reconstruct(r)), a, atol=1e-4 * np.sqrt(m * n)
    )


def test_descending_order(rng):
    a = rng.standard_normal((30, 20)).astype(np.float32)
    r = svd(jnp.asarray(a), method="two_phase")
    s = np.asarray(r.s)
    assert np.all(np.diff(s) <= 1e-6)


def test_sorting_basis_permutes_consistently(rng):
    u = rng.standard_normal((8, 5)).astype(np.float32)
    s = np.array([3.0, 7.0, 1.0, 9.0, 5.0], np.float32)
    vt = rng.standard_normal((5, 6)).astype(np.float32)
    res = sorting_basis(jnp.asarray(u), jnp.asarray(s), jnp.asarray(vt))
    # product invariant under permutation
    before = u @ np.diag(s) @ vt
    after = (
        np.asarray(res.u) @ np.diag(np.asarray(res.s)) @ np.asarray(res.vt)
    )
    np.testing.assert_allclose(after, before, atol=1e-5)
    assert np.all(np.diff(np.asarray(res.s)) <= 0)


def test_jacobi_oracle_matches_numpy(rng):
    a = rng.standard_normal((32, 20)).astype(np.float32)
    s = np.asarray(jacobi_svd_values(jnp.asarray(a)))
    s_ref = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(s, s_ref, atol=2e-5 * s_ref[0])


def test_phase2_on_hbd_bands(rng):
    """Full two-phase pipeline with the library-free diagonalizer."""
    a = rng.standard_normal((32, 20)).astype(np.float32)
    _, b, _ = householder_bidiagonalize(jnp.asarray(a), compute_uv=False)
    d, e = bidiagonal_bands(b)
    s = np.asarray(bidiag_svd_values(d, e))
    s_ref = np.linalg.svd(a, compute_uv=False)
    np.testing.assert_allclose(s, s_ref, atol=2e-5 * s_ref[0])


def test_low_rank_exactness(rng):
    """Rank-3 matrix: two-phase SVD finds exactly 3 nonzero values."""
    u = rng.standard_normal((30, 3)).astype(np.float32)
    v = rng.standard_normal((3, 20)).astype(np.float32)
    a = u @ v
    r = svd(jnp.asarray(a), method="two_phase")
    s = np.asarray(r.s)
    assert s[3:].max() < 1e-4 * s[0]
