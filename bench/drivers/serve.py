"""Serving cells: the configuration's weights behind ``Engine`` -> ``Router``
-> the HTTP server, driven over HTTP by the load-generator child.

Set-up builds the served weights from the seed, starts the engine and
compiles what the window runs, through the program's public calls only:
each fused-chunk length and slot cancellation, then every count of
requests that can retire in one chunk (that many requests at once), then
streaming requests of the longest answer, one for each prompt length
modulo the chunk, run through the router to their end: they make it read
every length of streamed tokens that the window can read.  Then the load
starts, and the window opens only after a ramp of the cell's own traffic
(``ramp_steps`` engine steps, one longest request), so that it measures
the steady state and not the first wave into empty slots; every request
of the cell's traffic runs after the warm-up, with nothing left to
compile or load.  Compilations inside the window are one of the numbers
checked, with the limit 0.

Afterwards the program's state is freed and the plain reference of the
configuration scores a sample of the finished requests, drawn from the
seed with the longest answer in it: at every served token, how far its
reference logit lies below the reference's best.  With a control set, the
tokens the control puts first stand in the served tokens' place.
"""

from __future__ import annotations

import gc
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np

from bench.harness import env, flops, traffic as traffic_mod
from bench.harness.run_data import RunData
from bench.harness.tracing import TracedWindow, annotate, restore

SPANS = ("step-chunk", "run-steps", "refill", "peek-tokens", "submit")
RAMP_LIMIT_S = 3000.0               # a guard against a stalled engine


def percentile(xs, q: float) -> float:
    """The ``q``-th percentile, linear between closest ranks."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


def warm(engine, mix: dict) -> None:
    """Compile, before the load starts, each fused-chunk length, slot
    cancellation, and the read of every count of requests that can retire
    in one chunk."""
    t0 = time.monotonic()
    for n in range(1, engine.chunk_steps + 1):      # each chunk length
        engine.submit(np.zeros(1, np.int32), n)
        engine.run()
    uid = engine.submit(np.zeros(1, np.int32), engine.chunk_steps + 1)
    engine.step_chunk()
    engine.cancel(uid)                               # slot deactivation
    engine.run()
    shortest = mix["prompt"]["min"] + mix["answer"]["min"] - 1
    most = engine.slots * math.ceil(engine.chunk_steps / shortest)
    for k in range(2, most + 1):                     # k retire in one chunk
        for _ in range(k):
            engine.submit(np.zeros(1, np.int32), 1)
        engine.run()
    env.log(f"set-up engine programs: {time.monotonic() - t0:.2f} s")


def stream_warmers(router, engine, mix: dict) -> list:
    """Streaming requests of the longest answer, admitted together, with
    prompts of 1 to ``chunk_steps`` tokens: between chunks the router
    reads each one's tokens so far, and together they reach every length
    from 1 to the longest answer less one."""
    return [router.submit(np.zeros(p, np.int32), mix["answer"]["max"],
                          stream=True)
            for p in range(1, engine.chunk_steps + 1)]


class LoadGen:
    """The load-generator child; its result is read on a thread so that
    the child never blocks on a full pipe.  With a ramp, ``open_window``
    tells it when the window opens."""

    def __init__(self, ctx, plan: dict):
        self.child = subprocess.Popen(
            [sys.executable,
             str(ctx.root / "bench" / "harness" / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env={k: v for k, v in os.environ.items()
                 if not k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))})
        self._out = []
        self._reader = threading.Thread(
            target=lambda: self._out.append(self.child.stdout.read()),
            daemon=True)
        self._reader.start()
        self.child.stdin.write(json.dumps(plan).encode() + b"\n")
        self.child.stdin.flush()
        if not plan.get("ramp"):
            self.child.stdin.close()

    def open_window(self, t0: float) -> None:
        self.child.stdin.write(json.dumps({"t0": t0}).encode() + b"\n")
        self.child.stdin.close()

    def stop(self) -> None:
        """End the child if it still runs (a run that failed early)."""
        if self.child.poll() is None:
            self.child.kill()
            self.child.wait()

    def result(self, timeout: float) -> list:
        try:
            if not self.child.stdin.closed:
                self.child.stdin.close()
            self.child.wait(timeout=timeout)
        finally:
            if self.child.poll() is None:
                self.child.kill()
                self.child.wait()
        self._reader.join(timeout=30)
        if self.child.returncode != 0 or not self._out:
            raise RuntimeError(
                f"load generator exited {self.child.returncode}")
        return json.loads(self._out[0])["requests"]


def _sleep_until(t: float) -> None:
    while time.monotonic() < t:
        time.sleep(min(0.01, max(t - time.monotonic(), 0)))


def sample_requests(done: list, seed: int, k: int) -> list:
    """``k`` finished requests drawn from the seed, the longest answer
    among them."""
    if not done:
        return []
    longest = max(done, key=lambda r: (r["gen"], -r["id"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def window_metrics(recs: list, t0: float, t1: float, mix: dict) -> dict:
    out = {}
    toks = sum(n for r in recs for t, n in r["deltas"] if t0 <= t <= t1)
    out["out_tok_per_s"] = toks / (t1 - t0)
    tpot = [(r["last"] - r["first"]) / (len(r["tokens"]) - 1) * 1e3
            for r in recs if r["status"] == "done" and r["last"] <= t1
            and len(r["tokens"]) > 1]
    if tpot:
        out["tpot_p95_ms"] = percentile(tpot, 95)
    if mix["loop"] == "open":
        deadline = t1 + mix["grace"]
        ttft = [((r["first"] if r["first"] is not None else deadline)
                 - (t0 + r["due"])) * 1e3 for r in recs]
        if ttft:
            out["ttft_p95_ms"] = percentile(ttft, 95)
        late = [(r["sent"] - (t0 + r["due"])) * 1e3 for r in recs
                if r["sent"] is not None]
        out["_late_ms"] = (max(late) if late else 0.0,
                           percentile(late, 95) if late else 0.0)
    out["_tpot_n"] = len(tpot)
    return out


def int8_argmax(model, params, seqs, max_len: int):
    """For each ``(prompt, [served])``: the token the program's int8 path
    puts first at every served position, teacher-forced through
    ``decode_step`` over the prompt and the served tokens."""
    import jax
    import jax.numpy as jnp

    from repro.core import quantize_tt_tree

    params_q = quantize_tt_tree(params)
    vocab = model.cfg.vocab_size
    toks = np.zeros((len(seqs), max_len), np.int32)
    for i, (p, cands) in enumerate(seqs):
        full = np.concatenate([p, cands[0]])
        toks[i, :len(full)] = full

    @jax.jit
    def forced(params, tokens):
        def body(cache, tok):
            logits, cache = model.decode_step(params, cache, tok[:, None])
            return cache, jnp.argmax(logits[..., :vocab].reshape(
                tok.shape[0], -1), -1).astype(jnp.int32)
        cache = model.init_cache(tokens.shape[0], tokens.shape[1])
        return jax.lax.scan(body, cache, tokens.T)[1].T

    firsts = np.asarray(forced(params_q, jnp.asarray(toks)))
    return [firsts[i, len(p) - 1:len(p) - 1 + len(c[0])]
            for i, (p, c) in enumerate(seqs)]


def tt_chains(params_tt):
    """Per-layer TT leaves as ``(lead-absorbed core shapes, split)``."""
    from repro.core import tt_linear as ttl
    import jax

    chains = []
    for leaf in jax.tree.leaves(params_tt, is_leaf=ttl.is_tt_linear):
        if ttl.is_tt_linear(leaf):
            shapes = [tuple(c.shape) for c in leaf.cores]
            chains.append(([shapes[0][1:]] + shapes[1:], leaf.split))
    return chains


def run(ctx) -> dict:
    from repro.kernels.tt_contract.ops import dispatch_tally
    from repro.launch import engine as engine_mod
    from repro.launch.engine import Engine
    from repro.launch.router import Router
    from repro.launch.server import serve_in_thread

    cfg, mix, mod = ctx.config, ctx.traffic, ctx.config_module
    eng = mix["engine"]
    last = [time.monotonic()]

    def lap(what):
        now = time.monotonic()
        env.log(f"set-up {what}: {now - last[0]:.2f} s")
        last[0] = now

    model, params, payload = mod.build(cfg, ctx.seed)
    lap("weights and payload")
    vocab = model.cfg.vocab_size
    reqs = traffic_mod.schedule(mix, ctx.seed, vocab)
    engine = Engine(model, params, slots=eng["slots"], max_len=eng["max_len"],
                    chunk_steps=eng["chunk_steps"])
    lap("engine")
    warm(engine, mix)
    lap("warm-up")
    router = Router([engine], queue_depth=eng["queue_depth"])
    server, shutdown = serve_in_thread(router)
    data = RunData(ctx.workload, ctx.seed, cfg, mix,
                   device_kind=ctx.devices[0].device_kind)
    data.facts["chains"] = tt_chains(params)
    data.facts["slots"] = eng["slots"]
    data.facts["sizes"] = mod.sizes(cfg)
    data.facts["unfused"] = sum(
        n for (route, _), n in dispatch_tally().items() if route == "unfused")
    undo: list = []
    if ctx.trace:
        for owner, attr, span in (
                (engine_mod, "_run_steps", "run-steps"),
                (engine_mod, "_refill_scan", "refill"),
                (Engine, "step_chunk", "step-chunk"),
                (Engine, "peek_tokens", "peek-tokens"),
                (Router, "submit", "submit")):
            annotate(owner, attr, span, undo)
    ramp = int(mix.get("ramp_steps", 0)) if mix["loop"] == "closed" else 0
    child = None
    try:
        seconds = ctx.seconds
        t_ramp = time.monotonic()
        warmers = stream_warmers(router, engine, mix)
        for ticket in warmers:
            ticket.result(timeout=RAMP_LIMIT_S)
        t_warm = time.monotonic()
        start = t_warm + 0.5
        plan = {"port": server.port, "start": start, "seconds": seconds,
                "ramp": bool(ramp), "loop": mix["loop"],
                "grace": mix.get("grace", 0), "requests": reqs}
        child = LoadGen(ctx, plan)
        _sleep_until(start)
        s0 = engine.steps
        while engine.steps - s0 < ramp:
            if time.monotonic() - start > RAMP_LIMIT_S:
                raise RuntimeError(f"the ramp made {engine.steps - s0} of "
                                   f"{ramp} steps in {RAMP_LIMIT_S} s")
            time.sleep(0.005)
        t0 = start
        if ramp:
            t0 = time.monotonic()
            child.open_window(t0)
        setup_s = t0 - ctx.t_start
        compiles0 = ctx.compiles.count
        ramp_note = (f"set-up {len(warmers)} streaming warm-up requests: "
                     f"{t_warm - t_ramp:.2f} s; ramp: {engine.steps - s0} "
                     f"steps of the cell's traffic, {t0 - start:.2f} s")
        c0 = (engine.steps, engine.slot_steps)
        if ctx.trace:
            start = t0 + mix["trace_at"] * seconds
            _sleep_until(start)
            with TracedWindow(SPANS) as tw:
                a = (engine.steps, engine.slot_steps)
                _sleep_until(start + mix["trace_seconds"])
                b = (engine.steps, engine.slot_steps)
            data.trace = tw.result
            data.counters.update(steps=b[0] - a[0],
                                 slot_steps=b[1] - a[1])
        _sleep_until(t0 + seconds)
        c1 = (engine.steps, engine.slot_steps)
        compiles = ctx.compiles.count - compiles0
        recs = child.result(timeout=seconds + mix.get("grace", 0) + 120)
    finally:
        restore(undo)
        if child is not None:
            child.stop()
        shutdown()
    device = env.device_record(ctx.devices)
    done = [r for r in recs if r["status"] == "done"]
    sample = sample_requests(done, ctx.seed, mix["check_requests"])
    seqs = [(np.asarray(reqs[r["id"]]["prompt"], np.int32),
             [np.asarray(r["tokens"], np.int32)]) for r in sample]
    if getattr(ctx, "control", None) == "int8" and seqs:
        # the control: the program's own int8 path, teacher-forced over the
        # same prompts and served tokens, reads its first-placed token
        for (prompt, cands), toks in zip(seqs, int8_argmax(
                model, params, seqs, eng["max_len"])):
            cands.append(toks)
    del server, shutdown, router, engine, params, payload, model
    gc.collect()

    t1 = t0 + seconds
    m = window_metrics(recs, t0, t1, mix)
    m["setup_s"] = setup_s
    failed = [r for r in recs if r["status"] not in ("done", "abandoned")]
    mismatched = [r for r in done if r["done_tokens"] != r["tokens"]
                  or len(r["tokens"]) != r["gen"]]
    lengths = [r["plen"] + r["gen"] for r in done]
    data.facts["context"] = flops.mean_context(lengths)
    data.counters.setdefault("window_steps", c1[0] - c0[0])
    data.counters.setdefault("window_slot_steps", c1[1] - c0[1])
    notes = [ramp_note,
             f"window: {len(recs)} requests sent, {len(done)} finished, "
             f"{len(failed)} failed, {len(mismatched)} streamed != final; "
             f"tpot_p95_ms over {m['_tpot_n']} requests",
             f"compilations inside the window: {compiles}",
             f"engine over the window: {c1[0] - c0[0]} steps, "
             f"{c1[1] - c0[1]} slot-steps"]
    if "_late_ms" in m:
        notes.append(f"load generator lateness: max {m['_late_ms'][0]:.3f} ms,"
                     f" p95 {m['_late_ms'][1]:.3f} ms")

    # correctness: the plain reference over a sample of finished requests
    t_ref = time.monotonic()
    checks, readings = [], {}
    if seqs:
        gaps = mod.reference_gaps(cfg, ctx.seed, seqs,
                                  (mix["check_requests"], eng["max_len"]))
        gap = float(gaps[0].max())
        readings.update(served_gap=gap,
                        served_gap_mean=float(gaps[0].mean()),
                        served_flips=float(np.mean(gaps[0] > 0)))
        judged = gaps[0]
        if len(gaps) > 1:
            readings.update(control_gap=float(gaps[1].max()),
                            control_gap_mean=float(gaps[1].mean()),
                            control_flips=float(np.mean(gaps[1] > 0)))
            judged = gaps[1]             # the control in the program's place
        checks.append(("served_gap_mean", float(judged.mean()), "<=",
                       cfg["limits"]["served_gap_mean"]))
        notes.append(f"reference: {len(sample)} requests, {gaps[0].size} "
                     f"served tokens, {time.monotonic() - t_ref:.1f} s; "
                     f"widest gap {float(judged.max()):.6g} (not compared), "
                     f"{float(np.mean(judged > 0)):.2%} of tokens not the "
                     f"reference's best"
                     + (" (the control's tokens)" if len(gaps) > 1 else ""))
    checks.append(("finished_requests", len(done), ">=", 1))
    checks.append(("streamed_mismatch", len(mismatched), "<=", 0))
    checks.append(("window_compilations", compiles, "<=", 0))
    return {"e2e": m, "attempted": len(recs), "failed": len(failed),
            "checks": checks, "data": data, "device": device,
            "notes": notes, "readings": readings}
