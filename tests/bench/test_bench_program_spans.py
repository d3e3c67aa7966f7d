"""The readers of the program's own spans and counters on hand-built
records: each reads its number from a traced run's snapshot and nothing
from an untraced run or a program that records nothing."""

from pathlib import Path

import pytest

from bench import run as brun
from bench.harness import program_spans
from bench.harness.run_data import RunData

ROOT = Path(__file__).resolve().parents[2]
TRACE = {"busy_s": 1.0, "window_s": 3.0}


def reader(name):
    return brun.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                            "bench_metric_" + name.replace(".", "_"))


class Spans:
    """Builds span records the way ``repro.obs`` does."""

    def __init__(self):
        self.spans = []

    def add(self, name, start, end, parent=None, **counts):
        seq = len(self.spans)
        self.spans.append({"seq": seq, "name": name, "start_ns": int(start),
                           "end_ns": int(end), "parent": parent,
                           "counts": counts})
        return seq

    def snap(self):
        return {"spans": self.spans, "dropped": 0}


MS = 1_000_000
SLOT = {"engine.slot_steps": 200, "engine.prompt_slot_steps": 18}


def decode_snapshot():
    s = Spans()
    # the edge of the window: a drain and a stream's peek whose chunk and
    # stream began before it, and a stream whose chunk did
    s.add("engine.drain", 0, MS)
    s.add("engine.peek", 2 * MS, 3 * MS, 1000)
    s.add("router.stream", 4 * MS, 5 * MS)
    for c in range(2):
        t = 10 * MS + c * 100 * MS
        chunk = s.add("engine.chunk", t, t + 10 * MS, **SLOT)
        if c == 0:
            s.add("engine.drain", t + 8 * MS, t + 9 * MS, chunk)
        stream = s.add("router.stream", t + 10 * MS, t + 50 * MS)
        for k in range(3):                  # three peeks of 10 ms each
            a = t + 10 * MS + 10 * MS * k
            peek = s.add("engine.peek", a, a + 10 * MS, stream)
            if k == 0:                      # the first waits 6 ms
                s.add("engine.wait", a, a + 6 * MS, peek)
    s.add("engine.wait", 500 * MS, 900 * MS)        # outside any stream
    return s.snap()


def compress_snapshot():
    s = Spans()
    s.add("compress.crop", 0, 90 * MS)      # its pass began before the window
    for p in range(3):
        t = 100 * MS + p * 1000 * MS
        pss = s.add("compress.pass", t, t + 900 * MS,
                    **{"compress.host_transfers": 204})
        s.add("compress.gather", t + 5 * MS, t + 45 * MS, pss)
        s.add("compress.launch", t + 45 * MS, t + 47 * MS, pss)
        crop = s.add("compress.crop", t + 47 * MS, t + 97 * MS, pss)
        s.add("compress.wait", t + 47 * MS, t + 67 * MS, crop)
        s.add("compress.crop", t + 97 * MS, t + 107 * MS, pss)
    return s.snap()


@pytest.fixture
def snapshot(monkeypatch):
    from repro import obs

    def use(snap):
        monkeypatch.setattr(obs, "collected", lambda: snap)
    return use


def data(trace=TRACE):
    return RunData("cell", 1, {}, {}, trace=trace)


@pytest.mark.parametrize("name, want", [
    # six peeks in three streams, one three-read drain in two chunks
    ("host_reads_per_chunk.decode", 6 / 3 + 3 * 1 / 2),
    # two whole streams of 40 ms, 6 of them waiting, and one of 1 ms
    ("stream_read_ms_per_chunk.decode", (2 * (40 - 6) + 1) / 3),
    ("prefill_share.decode", 9.0),
])
def test_decode_readers(snapshot, name, want):
    snapshot(decode_snapshot())
    assert reader(name).read(data()) == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    ("host_transfers_per_pass.compress", 204),
    ("gather_ms_per_pass.compress", 40),
    ("launch_ms_per_pass.compress", 2),
    ("crop_ms_per_pass.compress", 50 - 20 + 10),
])
def test_compress_readers(snapshot, name, want):
    snapshot(compress_snapshot())
    assert reader(name).read(data()) == pytest.approx(want)


NAMES = ["host_reads_per_chunk.decode", "stream_read_ms_per_chunk.decode",
         "prefill_share.decode", "host_transfers_per_pass.compress",
         "gather_ms_per_pass.compress", "launch_ms_per_pass.compress",
         "crop_ms_per_pass.compress"]


@pytest.mark.parametrize("name", NAMES)
def test_untraced_run_reads_nothing(snapshot, name):
    snapshot(decode_snapshot() if "decode" in name else compress_snapshot())
    assert reader(name).read(data(trace=None)) is None


@pytest.mark.parametrize("name", NAMES)
def test_program_that_records_nothing_reads_nothing(snapshot, name):
    snapshot({"spans": [], "dropped": 0})
    assert reader(name).read(data()) is None


def test_each_new_metric_is_declared_for_its_cell():
    import json

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        cell = ("qwen05b-tt.decode" if name.endswith(".decode")
                else "resnet32.compress")
        assert layer[name]["workloads"] == [cell]
        assert (ROOT / "bench" / "metrics" / f"{name}.py").exists()


def test_self_time_subtracts_only_descendants_under_the_span():
    s = Spans()
    outer = s.add("a", 0, 100)
    mid = s.add("b", 10, 60, outer)
    s.add("w", 20, 30, mid)
    s.add("w", 25, 40, mid)                 # overlaps the first: 20 covered
    s.add("w", 200, 300)                    # under no "a"
    snap = s.snap()
    a, b = (program_spans.under(snap, n, n) for n in "ab")
    assert program_spans.self_ns(snap, a, ["w"]) == 100 - 20
    assert program_spans.self_ns(snap, a, []) == 100
    assert program_spans.self_ns(snap, b, ["w"]) == 50 - 20


def test_under_takes_only_spans_inside_a_recorded_unit():
    s = Spans()
    s.add("x", 0, 10, 99)                   # its unit was not recorded
    unit = s.add("u", 20, 80)
    mid = s.add("m", 30, 60, unit)
    inner = s.add("x", 40, 50, mid)
    s.add("x", 90, 95)
    snap = s.snap()
    assert [x["seq"] for x in program_spans.under(snap, "u", "x")] == [inner]
    assert [x["seq"] for x in program_spans.under(snap, "u", "u")] == [unit]
