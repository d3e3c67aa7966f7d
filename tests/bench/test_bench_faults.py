"""Whole runs of the cells at a small size on the CPU, the chip check
skipped: a sound run comes out correct, and each fault the cell can have,
planted in the timed path, comes out not correct."""

import json
import os
import time
from pathlib import Path

import numpy as np
import pytest

from bench import run as brun

ROOT = Path(__file__).resolve().parents[2]
QWEN_TINY = json.load(open(os.path.join(os.path.dirname(__file__), "data",
                                        "qwen_tiny.json")))
DECODE_TINY = {"clients": 8, "requests": 64,
               "prompt": {"dist": "uniform", "min": 4, "max": 8},
               "answer": {"dist": "uniform", "min": 8, "max": 16},
               "engine": {"slots": 4, "max_len": 24, "chunk_steps": 4,
                          "queue_depth": 8},
               "ramp_steps": 24, "check_requests": 3}


def run_tiny(workload, seed, seconds, **over):
    ctx = brun.make_context(ROOT, workload, seed, seconds, False,
                            time.monotonic(), **over)
    return brun.run_cell(ctx, require_chip=False, cache=False)


def run_decode(seed=2**31 + 17):
    return run_tiny("qwen05b-tt.decode", seed, 3, config_override=QWEN_TINY,
                    traffic_override=DECODE_TINY)


def test_decode_sound_run_is_correct():
    line = run_decode()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"out_tok_per_s", "tpot_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"


def test_decode_token_altered_where_produced_is_caught(monkeypatch):
    from repro.launch.engine import Completion, Engine

    vocab = QWEN_TINY["vocab_size"]
    step, peek = Engine.step_chunk, Engine.peek_tokens

    def alter(toks):
        toks = np.array(toks)
        if toks.size:
            toks[0] = (toks[0] + 1) % vocab
        return toks

    monkeypatch.setattr(Engine, "step_chunk", lambda self: [
        Completion(c.uid, alter(c.tokens), c.prompt_logits, c.bad)
        for c in step(self)])
    monkeypatch.setattr(Engine, "peek_tokens", lambda self, uid: (
        None if (t := peek(self, uid)) is None else alter(t)))
    line = run_decode()
    assert not line["correct"]
    assert line["checks"]["served_gap_mean"]["value"] > \
        line["checks"]["served_gap_mean"]["limit"]


def test_compress_sound_run_is_correct():
    line = run_tiny("resnet32.compress", 2**31 + 19, 2)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"compress_ms", "setup_s"}


def _perturbed(payload):
    import dataclasses

    from repro.core.compression import CompressedParam

    name = sorted(k for k, c in payload.items() if c.kind == "tt")[0]
    c = payload[name]
    cores = list(c.tt.cores)
    cores[-1] = cores[-1] * 1.01
    out = dict(payload)
    out[name] = CompressedParam(c.kind, dataclasses.replace(c.tt,
                                                            cores=cores),
                                c.raw, c.orig_shape, c.orig_dtype,
                                c.crop_dims)
    return out


def test_compress_answer_altered_where_produced_is_caught(monkeypatch):
    from repro.core import TTCompressor

    orig = TTCompressor.compress

    def altered(self, params, plan=None):
        payload, report = orig(self, params, plan)
        return _perturbed(payload), report

    monkeypatch.setattr(TTCompressor, "compress", altered)
    line = run_tiny("resnet32.compress", 2**31 + 23, 2)
    assert not line["correct"]
    assert line["checks"]["ref_deviation"]["value"] > \
        line["checks"]["ref_deviation"]["limit"]


def test_compress_one_pass_altered_is_caught(monkeypatch):
    from repro.core import TTCompressor

    orig, calls = TTCompressor.compress, []

    def altered(self, params, plan=None):
        payload, report = orig(self, params, plan)
        calls.append(1)
        # two warm-up passes, then the window: every pass after its first
        return (_perturbed(payload) if len(calls) >= 4 else payload), report

    monkeypatch.setattr(TTCompressor, "compress", altered)
    line = run_tiny("resnet32.compress", 2**31 + 29, 8)
    assert line["attempted"] >= 2
    assert not line["correct"]
    assert line["checks"]["passes_differing"]["value"] >= 1


def test_compress_truncation_left_out_is_caught(monkeypatch):
    import dataclasses

    import jax.numpy as jnp

    from repro.core import TTCompressor
    from repro.core.compression import CompressedParam

    mod = brun.load_module(ROOT / "bench" / "configs" / "resnet32-ttd.py",
                           "resnet32_ttd_fault")
    orig = TTCompressor.compress

    def untruncated(self, params, plan=None):
        # every TT leaf exact, at full ranks: no truncation, still TT form
        payload, report = orig(self, params, plan)
        out = dict(payload)
        for name, c in payload.items():
            if c.kind == "tt":
                cores = mod.tt_svd(np.asarray(params[name]), c.tt.shape,
                                   eps=0.0)
                tt = dataclasses.replace(
                    c.tt, cores=[jnp.asarray(g, jnp.float32) for g in cores],
                    ranks=(1,) + tuple(g.shape[2] for g in cores))
                out[name] = CompressedParam(c.kind, tt, c.raw, c.orig_shape,
                                            c.orig_dtype, c.crop_dims)
        return out, report

    monkeypatch.setattr(TTCompressor, "compress", untruncated)
    line = run_tiny("resnet32.compress", 2**31 + 41, 2)
    checks = line["checks"]
    assert not line["correct"]
    assert checks["ranks_differing"]["value"] == checks["tt_leaves"]["value"]
    # the error, the deviation and the leaf count alone would pass it
    assert checks["eps_error"]["value"] < 1e-3
    assert checks["ref_deviation"]["value"] < checks["ref_deviation"]["limit"]
    assert checks["tt_leaves"]["value"] >= checks["tt_leaves"]["limit"]


def test_decode_open_loop_schedule_runs_and_times_first_tokens():
    mix = dict(DECODE_TINY, loop="open", rate=20.0, grace=5)
    ctx = brun.make_context(ROOT, "qwen05b-tt.decode", 2**31 + 37, 3, False,
                            time.monotonic(), config_override=QWEN_TINY,
                            traffic_override=mix)
    line = brun.run_cell(ctx, require_chip=False, cache=False)
    assert line["correct"], line["checks"]
    assert 40 <= line["attempted"] <= 80       # ~60 due in 3 s at 20/s
