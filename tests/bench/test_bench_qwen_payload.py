"""The qwen payload builder: the compressor's format, every dims/ranks
entry of the recorded spec at a reduced depth, served from cores, and the
reference's per-layer weights equal to the payload's."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
NAME = "qwen1.5-0.5b-tt"


@pytest.fixture(scope="module")
def qwen():
    spec = importlib.util.spec_from_file_location(
        "bench_qwen_cfg", os.path.join(ROOT, "bench", "configs", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      NAME + ".json")))
    # published widths, two layers, a short vocabulary: the TT leaves are
    # at full size, the embedding stays small
    cfg.update(num_hidden_layers=2, vocab_size=256)
    return mod, cfg


def test_payload_reproduces_the_spec_at_reduced_depth(qwen):
    from repro.core.compression import CompressedParam

    mod, cfg = qwen
    w = mod.make_weights(cfg, 2**31 + 3)
    pl = mod.payload(cfg, w)
    flat = jax.tree_util.tree_flatten_with_path(
        pl, is_leaf=lambda x: isinstance(x, CompressedParam))[0]
    tt = {jax.tree_util.keystr(p).split(".")[-1]: c for p, c in flat
          if c.kind == "tt"}
    assert sorted(tt) == sorted(mod.TT_LEAVES)
    for name, rec in cfg["weights"]["spec"].items():
        c = tt[name]
        want_dims = [2] + rec["dims"][1:]
        want_ranks = [1, min(rec["ranks"][1], 2)] + rec["ranks"][2:]
        assert list(c.tt.shape) == want_dims, name
        assert list(c.tt.ranks) == want_ranks, name
        assert [tuple(g.shape) for g in c.tt.cores] == [
            (want_ranks[k], n, want_ranks[k + 1])
            for k, n in enumerate(want_dims)], name
        assert int(np.prod(c.orig_shape)) == int(np.prod(want_dims))
    raw = [c for _, c in flat if c.kind == "raw"]
    assert {c.orig_dtype for c in raw} == {np.dtype("bfloat16")}


def test_every_tt_leaf_is_served_from_cores(qwen):
    from repro.core import tt_linear as ttl
    from repro.models.common import tt_native_params

    mod, cfg = qwen
    pl = mod.payload(cfg, mod.make_weights(cfg, 5))
    served = tt_native_params(pl, family="dense")
    n = sum(ttl.is_tt_linear(x) for x in
            jax.tree.leaves(served, is_leaf=ttl.is_tt_linear))
    assert n == len(mod.TT_LEAVES)


def test_reference_layer_weights_equal_the_payload(qwen):
    from repro.core import tt_reconstruct

    mod, cfg = qwen
    w = mod.make_weights(cfg, 9)
    spec = mod.leaf_spec(cfg)
    pl = mod.payload(cfg, w)
    full = np.asarray(tt_reconstruct(pl.layers.mlp.w_down.tt))
    rec = spec["w_down"]
    full = full.reshape(rec["orig_shape"])
    got = np.asarray(mod._layer_dense(w["w_down"], 1, rec["orig_shape"][1:]))
    np.testing.assert_allclose(got, full[1], rtol=1e-4, atol=1e-6)
    # element scale of the product is about 1/sqrt(fan_in)
    assert np.std(full) == pytest.approx(rec["fan_in"] ** -0.5, rel=0.2)
