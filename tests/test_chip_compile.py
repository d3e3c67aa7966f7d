"""Compile-only guards for a TPU v5e at qwen1.5-0.5b's served shapes.

Nothing here runs on a chip: each test compiles for a described (not
attached) ``v5e:2x2`` topology, which raises what the chip's compiler would
raise — Mosaic's layout rules, the scoped-VMEM limit, the device's 16 GB.
The topology is described inside a fixture, never at import, and every
test of this kind lives in this one file, pinned to one xdist worker
(``--dist loadgroup``), so a single test process loads the TPU compiler
library.  Where it cannot be described, the tests skip.
"""

import functools
import os
import re

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


# the decode cell's pool: 32 slots of 576 rows, 16 KV heads of 64, at
# qwen1.5-0.5b's widths with two layers
SLOTS, MAX_LEN, CHUNK = 32, 576, 8


def _hlo_computations(text):
    """{computation name: its instruction lines} of a compiled module."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            cur = comps.setdefault(("ENTRY " if m.group(1) else "")
                                   + m.group(2), [])
        elif cur is not None and line.startswith(" "):
            cur.append(line.strip())
    return comps


def _instruction(line):
    """(name, result shape, opcode, called computation) of one line."""
    m = re.match(r"(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
    if m is None:
        return None
    calls = re.search(r"calls=%?([\w.\-]+)", line)
    return m.group(1), m.group(2), m.group(3), calls and calls.group(1)


def test_decode_chunk_updates_the_cache_in_place(one_chip):
    """The engine's fused chunk (``_run_steps`` over the transformer decode
    step), compiled for the chip at the decode cell's pool: inside its
    loops nothing copies, slices out or stacks the cache or a layer of it.
    The only cache-sized instructions there are fusions rooted in a
    dynamic-update-slice, which write in place: a write the compiler could
    not place in the carried buffer would come with a copy."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import common as kcommon
    from repro.launch import engine as engine_mod
    from repro.launch.engine import Engine
    from repro.models import common as mc
    from repro.models.registry import build

    cfg = dataclasses.replace(get_config("qwen1.5-0.5b"), num_layers=2)
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: Engine(
        model, params, slots=SLOTS, max_len=MAX_LEN,
        chunk_steps=CHUNK).state)
    put = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    interpret = kcommon.use_interpret
    kcommon.use_interpret = lambda: False
    try:
        text = engine_mod._run_steps.lower(
            model.decode_step, put(params), put(state), CHUNK,
            mc.GREEDY).compile().as_text()
    finally:
        kcommon.use_interpret = interpret

    layer = f"[{SLOTS},{MAX_LEN},{cfg.num_kv_heads},{cfg.resolved_head_dim}]"
    whole = "[" + f"{cfg.num_layers}," + layer[1:]
    comps = _hlo_computations(text)
    fused = {}
    for lines in comps.values():
        for line in lines:
            ins = _instruction(line)
            if ins and ins[2] == "fusion":
                fused[ins[3]] = None
    for name in fused:
        root = [l for l in comps[name] if l.startswith("ROOT ")]
        fused[name] = _instruction(root[0])[2]
    n_writes = 0
    for name, lines in comps.items():
        if name.startswith("ENTRY") or name in fused:
            continue
        for line in lines:
            ins = _instruction(line)
            if ins is None or ins[1].startswith("("):
                continue
            _, shape, op, calls = ins
            if op in ("parameter", "get-tuple-element", "bitcast"):
                continue
            if shape.startswith(("bf16" + layer, "f32" + layer)):
                raise AssertionError(f"a layer of the cache made in {name}: "
                                     f"{line[:160]}")
            if shape.startswith("bf16" + whole):
                assert op == "fusion" \
                    and fused[calls] == "dynamic-update-slice", \
                    f"the cache copied in {name}: {line[:160]}"
                n_writes += 1
    assert n_writes, "no in-place write of the cache found"
