"""Program spans and counters (``repro.obs``) and where the program takes them.

* With the profiler off nothing is recorded and no annotation is entered.
* Under ``jax.profiler.start_trace`` spans carry their parent and their
  counts, a span open when the session starts or stops is not kept, and
  the spans land in the trace's host plane at a constant offset from their
  in-memory records.
* The engine spans its device reads and counts its prompt slot-steps
  exactly, also when the session starts or stops inside a chunk; the
  compressor counts its host<->device copies exactly.
* The named scopes the device trace is split by are in the lowered HLO.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core import CompressionPolicy, TTCompressor
from repro.core import plan as plan_mod
from repro.launch import engine as engine_mod
from repro.launch.engine import Engine
from repro.launch.router import Router
from repro.models.registry import build

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

from bench.harness import program_spans  # noqa: E402
from bench.harness import trace as bench_trace  # noqa: E402


def start_profiler(path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)


@pytest.fixture
def profiler(tmp_path):
    """A profiler session around the test body; yields its directory."""
    obs.reset()
    start_profiler(tmp_path)
    try:
        yield str(tmp_path)
    finally:
        if obs.recording():
            jax.profiler.stop_trace()
        obs.reset()


def by_name(name):
    return [s for s in obs.collected()["spans"] if s["name"] == name]


def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    entered = []

    class Spy:
        def __init__(self, name):
            entered.append(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    obs.reset()
    assert not obs.recording()
    with obs.span("outer"):
        obs.count("c", 5)
        obs.wait("w", jnp.ones(2))
    assert entered == []
    assert obs.collected() == {"spans": [], "dropped": 0}


def test_on_records_parents_and_counts(profiler):
    obs.count("reads", 9)                   # no span open: not kept
    with obs.span("outer"):
        with obs.span("inner"):
            obs.count("reads", 2)
        obs.count("reads")
        obs.count("steps", 4)
        with obs.span("inner"):
            pass
    (outer,) = by_name("outer")
    inner = by_name("inner")
    assert outer["parent"] is None
    assert [s["parent"] for s in inner] == [outer["seq"]] * 2
    assert all(outer["start_ns"] <= s["start_ns"] <= s["end_ns"]
               <= outer["end_ns"] for s in inner)
    # a span's counts hold those of the spans inside it
    assert [s["counts"] for s in inner] == [{"reads": 2}, {}]
    assert outer["counts"] == {"reads": 3, "steps": 4}
    obs.reset()
    assert obs.collected() == {"spans": [], "dropped": 0}


def test_spans_open_when_the_session_starts_or_stops_are_not_kept(
        tmp_path):
    obs.reset()
    try:
        with obs.span("before"):
            start_profiler(tmp_path)
            with obs.span("inside"):
                obs.count("reads")
            obs.count("reads", 5)           # its span was not recorded
        with obs.span("whole"):
            obs.count("reads", 2)
        with obs.span("cut"):
            with obs.span("cut.inner"):
                obs.count("reads", 7)
            jax.profiler.stop_trace()
    finally:
        if obs.recording():
            jax.profiler.stop_trace()
    spans = obs.collected()["spans"]
    assert [s["name"] for s in spans] == ["inside", "whole", "cut.inner"]
    inside, whole, cut_inner = spans
    assert inside["parent"] is None and inside["counts"] == {"reads": 1}
    assert whole["counts"] == {"reads": 2}
    # the inner span closed while recording; its unit did not
    assert cut_inner["parent"] is not None
    assert cut_inner["parent"] not in {s["seq"] for s in spans}
    snap = obs.collected()
    assert program_spans.under(snap, "cut", "cut.inner") == []
    obs.reset()


def test_spans_on_other_threads_have_their_own_parents(profiler):
    import threading

    def work():
        with obs.span("thread.span"):
            pass

    with obs.span("main.span"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    (child,) = by_name("thread.span")
    assert child["parent"] is None


def test_threads_lose_no_span_or_count(profiler):
    import threading

    threads, each = 2 * (os.cpu_count() or 1) + 1, 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                with obs.span("hot"):
                    obs.count("hits")
                    obs.count("hits", 2)
                obs.count("hits", 100)      # outside a span: not kept

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    got = obs.collected()
    assert sum(s["counts"]["hits"] for s in got["spans"]) == (
        3 * threads * each)
    assert len(got["spans"]) == threads * each
    assert len({s["seq"] for s in got["spans"]}) == threads * each


def test_wait_is_a_span_only_when_the_array_is_not_ready(profiler):
    class Pending:
        def __init__(self, ready):
            self.ready, self.blocked = ready, 0

        def is_ready(self):
            return self.ready

        def block_until_ready(self):
            self.blocked += 1

    busy, done = Pending(False), Pending(True)
    with obs.span("read"):
        obs.wait("read.wait", busy)
        obs.wait("read.wait", done)
    (w,) = by_name("read.wait")
    assert w["parent"] == by_name("read")[0]["seq"]
    assert (busy.blocked, done.blocked) == (1, 0)


def test_overflow_is_counted_not_raised(profiler, monkeypatch):
    monkeypatch.setattr(obs, "CAPACITY", 3)
    for _ in range(5):
        with obs.span("s"):
            pass
    got = obs.collected()
    assert len(got["spans"]) == 3
    assert got["dropped"] == 2


def test_spans_land_in_the_host_plane_at_a_constant_offset(profiler):
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: jnp.tanh(a @ a))
    f(x).block_until_ready()
    names = [f"clock.{i}" for i in range(6)]
    for name in names:
        with obs.span(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    _, spans = bench_trace.load_xplane(profiler, names)
    assert sorted(s[0] for s in spans) == sorted(names)
    mem = {s["name"]: s for s in obs.collected()["spans"]}
    offsets = [mem[n]["start_ns"] - s for n, s, _ in spans]
    assert max(offsets) - min(offsets) < 100_000          # < 100 us
    ends = [mem[n]["end_ns"] - e for n, _, e in spans]
    assert max(ends) - min(ends) < 100_000


# -- the engine ------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


REQUESTS = [(3, 5), (1, 4), (6, 2), (2, 9), (4, 3), (5, 6)]   # (plen, gen)


def tiny_engine(tiny, admission="scan"):
    cfg, model, params = tiny
    eng = Engine(model, params, slots=3, max_len=16, chunk_steps=4,
                 admission=admission)
    rng = np.random.default_rng(1)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, p), g)
            for p, g in REQUESTS]
    return eng, uids


def chunk_counts(name):
    return [s["counts"].get(name, 0) for s in by_name("engine.chunk")]


@pytest.mark.parametrize("admission", ["scan", "boundary"])
def test_engine_counts_reads_and_prompt_slot_steps(tiny, profiler,
                                                   admission):
    eng, uids = tiny_engine(tiny, admission)
    obs.reset()
    steps0, slot_steps0 = eng.steps, eng.slot_steps
    done, peeks, chunks = [], 0, 0
    while eng.queue or eng.busy_slots:
        done.extend(eng.step_chunk())
        chunks += 1
        for uid in uids:
            if eng.peek_tokens(uid) is not None:
                peeks += 1
    assert sorted(c.uid for c in done) == uids
    # every device read sits in a span: a peek, or a drain / harvest of
    # three arrays inside its chunk
    assert len(by_name("engine.peek")) == peeks
    reads = by_name("engine.drain") + by_name("engine.harvest")
    if admission == "boundary":
        assert not by_name("engine.drain")
        assert len(reads) == len(done)
    else:
        assert not by_name("engine.harvest")
        assert 1 <= len(reads) < len(done)
    seqs = {s["seq"]: s for s in by_name("engine.chunk")}
    assert all(r["parent"] in seqs for r in reads)
    assert len(seqs) == chunks
    assert sum(chunk_counts("engine.prompt_slot_steps")) == sum(
        p - 1 for p, _ in REQUESTS)
    assert sum(chunk_counts("engine.slot_steps")) == (
        eng.slot_steps - slot_steps0)
    assert eng.steps > steps0
    for w in by_name("engine.wait"):
        assert w["parent"] is not None


def test_session_started_and_stopped_inside_chunks_keeps_whole_chunks(
        tiny, tmp_path, monkeypatch):
    # the reference: every chunk recorded
    eng, _ = tiny_engine(tiny)
    obs.reset()
    start_profiler(tmp_path / "all")
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    want = [chunk_counts(k) for k in ("engine.slot_steps",
                                      "engine.prompt_slot_steps")]
    assert len(want[0]) >= 5 and all(want[1][:3])

    # the same schedule, the session starting inside the first chunk's
    # launch and stopping inside the fourth's
    eng, _ = tiny_engine(tiny)
    obs.reset()
    launches = []
    run_steps = engine_mod._run_steps

    def launch(*a, **k):
        out = run_steps(*a, **k)
        launches.append(1)
        if len(launches) == 1:
            start_profiler(tmp_path / "cut")
        if len(launches) == 4:
            jax.profiler.stop_trace()
        return out

    monkeypatch.setattr(engine_mod, "_run_steps", launch)
    try:
        eng.run()
    finally:
        if obs.recording():
            jax.profiler.stop_trace()
    got = [chunk_counts(k) for k in ("engine.slot_steps",
                                     "engine.prompt_slot_steps")]
    assert got == [w[1:3] for w in want]
    # the first chunk's drain was recorded without its chunk: no reader
    # takes it
    snap = obs.collected()
    drains = by_name("engine.drain")
    inside = program_spans.under(snap, "engine.chunk", "engine.drain")
    assert [d["parent"] for d in drains if d not in inside] == [None]
    obs.reset()


def test_router_streams_under_its_spans(tiny, profiler):
    cfg, model, params = tiny
    eng = Engine(model, params, slots=2, max_len=16, chunk_steps=2)
    with Router([eng], queue_depth=8) as router:
        tickets = [router.submit(np.arange(1, 1 + p, dtype=np.int32), g,
                                 stream=True) for p, g in REQUESTS[:4]]
        for t in tickets:
            t.result(timeout=120)
    spans = obs.collected()["spans"]
    seqs = {s["seq"]: s for s in spans}
    assert by_name("router.stream")
    peeks = by_name("engine.peek")
    assert peeks and all(seqs[p["parent"]]["name"] == "router.stream"
                         for p in peeks)


# -- the compressor --------------------------------------------------------

def _weights():
    rng = np.random.default_rng(2)
    shapes = {"a": (8, 6, 10), "b": (8, 6, 9), "c": (4, 5, 6, 7),
              "d": (3, 200), "e": (12,)}
    return {k: jnp.asarray(rng.standard_normal(s), jnp.float32)
            for k, s in shapes.items()}


def test_compress_counts_host_transfers_per_pass(profiler):
    weights = _weights()
    policy = CompressionPolicy(eps=0.3, min_size=64, min_dims=3)
    comp = TTCompressor(policy)
    comp.compress(weights)                      # compile outside the count
    obs.reset()
    comp.compress(weights)
    comp.compress(weights)
    plan = plan_mod.build_plan(weights, policy)
    batched = [b for b in plan.buckets if b.execution == "batched"]
    assert batched and plan.raw
    # per bucket: each member read, the stack uploaded; per member: its
    # ranks read, each of its cores read and uploaded again
    per_pass = sum(b.batch + 1 + b.batch * (1 + 2 * len(b.dims))
                   for b in batched)
    passes = by_name("compress.pass")
    assert [p["counts"]["compress.host_transfers"] for p in passes] == [
        per_pass] * 2
    snap = obs.collected()
    for name, n in (("compress.gather", len(batched)),
                    ("compress.launch", len(batched)),
                    ("compress.crop", sum(b.batch for b in batched))):
        assert len(by_name(name)) == 2 * n
        assert len(program_spans.under(snap, "compress.pass", name)) == 2 * n


def test_serial_compress_counts_host_transfers(profiler):
    w = jnp.asarray(np.random.default_rng(3).standard_normal((6, 7, 8)),
                    jnp.float32)
    comp = TTCompressor(CompressionPolicy(eps=0.2, min_size=64,
                                          plan="serial"))
    comp.compress({"w": w})
    obs.reset()
    comp.compress({"w": w})
    d = 3
    # the tensor read; per unfolding: up, three factors down, the core up,
    # and the host phase 2's bidiagonal down and three factors up; the
    # last core up
    (pss,) = by_name("compress.pass")
    assert pss["counts"]["compress.host_transfers"] == (
        1 + (d - 1) * (5 + 4) + 1)


# -- named scopes ----------------------------------------------------------

def has_scope(text: str, scope: str) -> bool:
    """A location of the lowered module named under ``scope`` (a path
    component, so a source file called ``<scope>.py`` does not count)."""
    return re.search(r'loc\("(?:[^"/]*/)*%s(?:/[^"]*)?"' % re.escape(scope),
                     text) is not None


def test_static_ttsvd_lowers_under_the_hbd_phase_scopes():
    from repro.core import tt

    x = jnp.zeros((2, 4, 6, 8), jnp.float32)
    text = tt.ttd_static_batched.lower(
        x, eps=0.1, max_rank=1 << 30, svd_method="two_phase",
        hbd_impl="unblocked").as_text(debug_info=True)
    assert has_scope(text, "hbd.phase1") and has_scope(text, "hbd.phase2")


def test_tt_decode_step_lowers_under_its_scopes(tiny):
    from repro.core import spectral_decay_pytree
    from repro.models import common as model_common

    cfg, model, params = tiny
    payload, _ = TTCompressor(CompressionPolicy(eps=0.2, min_size=8192)
                              ).compress(spectral_decay_pytree(params))
    tt_params = model_common.tt_native_params(payload, family=cfg.family)
    text = jax.jit(model.decode_step).lower(
        tt_params, model.init_cache(2, 16), jnp.zeros((2, 1), jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("attention", "unembed", "tt_chain"):
        assert has_scope(text, scope), scope
