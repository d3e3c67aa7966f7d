"""The trace reduction on a small trace: busy union, idle share, kernel
time and idle gaps named by the innermost host span."""

import json
import os

import pytest

from bench.harness import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def small_trace():
    # two overlapping ops, a gap, one op; host spans: an outer step span
    # with a shorter dispatch span inside it
    ops = {"/device:TPU:0": [("fusion.1", 0, 40), ("tt_contract_2", 30, 60),
                             ("fusion.1", 80, 100)]}
    spans = [("step-chunk", 50, 95), ("run-steps", 65, 75)]
    return ops, spans


def test_busy_is_the_union_of_overlapping_ops():
    ops, _ = small_trace()
    assert tr.busy_ns(ops["/device:TPU:0"], 0, 100) == 80
    assert tr.busy_ns(ops["/device:TPU:0"], 10, 90) == 60


def test_reduce_idle_share_kernel_time_and_gap_names():
    ops, spans = small_trace()
    r = tr.reduce(ops, spans, 0, 100)
    assert r["busy_s"] == pytest.approx(80e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["ops_s"]["tt_contract_2"] == pytest.approx(30e-9)
    assert r["ops_s"]["fusion.1"] == pytest.approx(60e-9)
    # the gap 60..80: 60..65 and 75..80 under step-chunk, 65..75 under the
    # innermost run-steps
    assert r["idle_s"]["step-chunk"] == pytest.approx(10e-9)
    assert r["idle_s"]["run-steps"] == pytest.approx(10e-9)
    assert tr.NO_SPAN not in r["idle_s"]
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"


def test_gap_outside_every_span_is_named_as_such():
    ops = {"/device:TPU:0": [("a", 0, 10), ("b", 30, 40)]}
    r = tr.reduce(ops, [], 0, 40)
    assert r["idle_s"] == {tr.NO_SPAN: pytest.approx(20e-9)}


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        tr.reduce({}, [], 0, 10)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace_reduces_consistently():
    rec = json.load(open(DATA))
    ops = {k: [tuple(e) for e in v] for k, v in rec["ops"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    r = tr.reduce(ops, spans, rec["t0"], rec["t1"])
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = r["window_s"] - r["busy_s"]
    assert sum(r["idle_s"].values()) == pytest.approx(idle, rel=1e-6)
    assert sum(r["ops_s"].values()) >= r["busy_s"] * (1 - 1e-9)
    # the device waits while the router reads streamed tokens
    assert r["idle_s"]["peek-tokens"] > 0


def test_op_stem_names_hlo_ops():
    assert tr.op_stem("%tt_contract_3.35 = f32[4,1024]{1,0} custom-call("
                      "f32[64,4,16] %bitcast.371)") == "tt_contract_3"
    assert tr.op_stem("%while.124 = (s32[]) while(%tuple.5)") == "while"
    assert tr.op_stem("%cond.11.clone = (f32[8]) conditional(%p)") == "cond"
    assert tr.op_stem("fusion") == "fusion"
