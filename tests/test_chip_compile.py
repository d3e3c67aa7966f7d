"""Compile-only guards for a TPU v5e at qwen1.5-0.5b's served shapes.

Nothing here runs on a chip: each test compiles for a described (not
attached) ``v5e:2x2`` topology, which raises what the chip's compiler would
raise — Mosaic's layout rules, the scoped-VMEM limit, the device's 16 GB.
The topology is described inside a fixture, never at import, and every
test of this kind lives in this one file, pinned to one xdist worker
(``--dist loadgroup``), so a single test process loads the TPU compiler
library.  Where it cannot be described, the tests skip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hbd import householder_bidiagonalize
from repro.kernels.tt_contract import kernel as K
from repro.kernels.tt_contract import ops

pytestmark = pytest.mark.xdist_group("tpu_compile")

V5E_HBM = 16e9
I8, F32 = jnp.int8, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, with the persistent compile cache
    off: an entry written from a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding, **statics):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(lambda *a: fn(*a, interpret=False, **statics)).lower(
        *args).compile()


# decode batch 8 (one block) and 1024 rows (512-row tiles)
ROWS = [8, 1024]


@pytest.mark.parametrize("b", ROWS)
@pytest.mark.parametrize("n_in,r,n_out", [(1024, 64, 2816), (2816, 64, 1024)])
def test_tt_contract_2_compiles(one_chip, b, n_in, r, n_out):
    _compile(K.tt_contract_2, ((b, n_in), F32), ((n_in, r), F32),
             ((r, n_out), F32), sharding=one_chip)
    _compile(K.tt_contract_2q, ((b, n_in), F32), ((n_in, r), F32),
             ((r, n_out), I8), ((1, r), F32), sharding=one_chip)


# (L,D,H,K) q/k/v projections (split 1) and the (L,H,K,D) output
# projection (split 2); g1 arrives mode-major (n_mid, r1, r2)
CHAINS3 = {
    1: ((1024, 256), (16, 256, 64), (64, 64)),
    2: ((16, 384), (64, 384, 64), (64, 1024)),
}


@pytest.mark.parametrize("b", ROWS)
@pytest.mark.parametrize("split", [1, 2])
def test_tt_contract_3_compiles(one_chip, b, split):
    g0, g1, g2 = CHAINS3[split]
    _compile(K.tt_contract_3, ((b, 1024), F32), (g0, F32), (g1, F32),
             (g2, F32), sharding=one_chip, split=split)
    _compile(K.tt_contract_3q, ((b, 1024), F32), (g0, F32), (g1, I8),
             (g2, I8), ((1, g1[1]), F32), ((1, g2[0]), F32),
             sharding=one_chip, split=split)


def test_vmem_gate_admits_what_compiles(one_chip):
    """The dispatch gate and the compiler share ``common.VMEM_LIMIT``: a
    chain just inside the gate compiles, one past it is refused."""
    x = jnp.zeros((8, 1024), F32)

    def chain(r):
        return [jnp.zeros((1024, r), F32), jnp.zeros((r, 2816, 1), F32)]

    assert ops._fits_vmem(x, chain(3000), 2816, 1)
    _compile(K.tt_contract_2, ((8, 1024), F32), ((1024, 3000), F32),
             ((3000, 2816), F32), sharding=one_chip)
    assert not ops._fits_vmem(x, chain(4096), 2816, 1)


def test_hbd_unfolding_fits_one_chip(one_chip):
    """The first unfolding of a (24, 1024, 16, 64) attention weight is
    1,048,576 x 24: with a thin U_B the whole HBD fits the chip's HBM (the
    full M x M U_B it used to build is 4 TB)."""
    x = jax.ShapeDtypeStruct((1048576, 24), F32, sharding=one_chip)
    mem = jax.jit(householder_bidiagonalize).lower(x).compile() \
        .memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM, total
