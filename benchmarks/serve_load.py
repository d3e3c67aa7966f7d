"""Sustained-load benchmark: the wired front door under a request storm.

End-to-end over real HTTP: N engine replicas behind the load-aware router
and the asyncio SSE server, hammered by a seeded closed-loop client pool.
Every request streams (SSE), carries its own sampling params
(greedy/temperature/top-k mix) and its own seed, and is checked
token-for-token against an isolated ``generate`` run — throughput that
breaks staggered == isolated does not count.

Measures, per replica tier (N=1 and N=2):

  * aggregate req/s and tok/s over the full trace (closed loop,
    ``concurrency`` in-flight clients);
  * per-request latency p50/p99 (ms, first-byte-to-done as seen by the
    client);
  * mean slot occupancy across replicas (from ``GET /stats`` — useful
    slot-steps / total slot-steps).

Asserts: full token parity at every tier, nonzero occupancy, and — on
multicore hosts only (``os.cpu_count() >= 2``; replica chunks can't
overlap on one core) — N=2 aggregate req/s >= 1.5x N=1.
"""

from __future__ import annotations

import http.client
import json
import os
import threading
import time

import numpy as np


def _trace(cfg, n, rng):
    """Seeded heterogeneous request trace with a per-request sampling mix:
    a third greedy, a third temperature-only, a third temperature+top-k —
    the per-slot sampling params ride the wire and must round-trip."""
    out = []
    for i in range(n):
        plen = 2 + int(rng.integers(0, 5))
        gen = 3 + int(rng.integers(0, 6))
        req = {"prompt": rng.integers(0, cfg.vocab_size, (plen,))
               .tolist(), "gen": gen, "seed": i, "stream": True}
        if i % 3 == 1:
            req["temperature"] = 0.9
        elif i % 3 == 2:
            req["temperature"] = 1.1
            req["top_k"] = 32
        out.append(req)
    return out


def _isolated(model, params, trace):
    """The parity oracle: every request run alone through the fused
    driver (same seed, same sampling params)."""
    from repro.launch.engine import generate

    expected = []
    for req in trace:
        out = generate(
            model, params, np.asarray(req["prompt"], np.int32)[None],
            req["gen"], driver="fused", seed=req["seed"],
            temperature=req.get("temperature", 0.0),
            top_k=req.get("top_k"),
        )
        expected.append(out["gen"][0].tolist())
    return expected


def _sse_request(port, req, timeout=600):
    """POST one streaming request; returns (tokens, latency_s).  The SSE
    deltas are reassembled and cross-checked against the ``done`` event's
    full token list — a streaming front door that drops or reorders
    chunks fails here, not silently."""
    t0 = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(req),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if resp.status != 200:
        body = resp.read()
        conn.close()
        raise RuntimeError(f"HTTP {resp.status}: {body[:200]!r}")
    raw = resp.read().decode()
    conn.close()
    latency = time.monotonic() - t0
    deltas, done = [], None
    for block in raw.strip().split("\n\n"):
        lines = block.split("\n")
        event = [ln[7:] for ln in lines if ln.startswith("event: ")]
        data = [ln[6:] for ln in lines if ln.startswith("data: ")]
        if not data:
            continue
        payload = json.loads(data[0])
        if event and event[0] == "done":
            done = payload
        elif event and event[0] == "error":
            raise RuntimeError(f"stream error: {payload}")
        else:
            deltas.extend(payload["tokens"])
    if done is None or deltas != done["tokens"]:
        raise RuntimeError(
            f"SSE deltas {deltas} != done tokens "
            f"{None if done is None else done['tokens']}")
    return done["tokens"], latency


def _fire(port, trace, concurrency):
    """Closed-loop client pool: ``concurrency`` threads drain the trace.
    Returns (wall_s, results[i] -> tokens, latencies)."""
    results = [None] * len(trace)
    latencies = [0.0] * len(trace)
    errors = []
    it = iter(range(len(trace)))
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = next(it, None)
            if i is None:
                return
            try:
                results[i], latencies[i] = _sse_request(port, trace[i])
            except Exception as e:
                errors.append((i, repr(e)))
                return

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    if errors:
        raise RuntimeError(f"client errors: {errors[:3]}")
    return wall, results, latencies


def _stats(port):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/stats")
    out = json.loads(conn.getresponse().read())
    conn.close()
    return out


def _run_tier(model, params, trace, expected, replicas, slots, chunk_steps,
              concurrency):
    from repro.launch.engine import Engine
    from repro.launch.router import Router
    from repro.launch.server import serve_in_thread

    engines = [Engine(model, params, slots=slots, max_len=32,
                      chunk_steps=chunk_steps)
               for _ in range(replicas)]
    router = Router(engines, queue_depth=max(concurrency, 2 * slots))
    server, shutdown = serve_in_thread(router)
    try:
        # warmup pass: compiles every chunk length / admission shape the
        # trace will hit, untimed (results discarded); then best-of-2
        # timed passes (closed-loop client jitter, not engine speed, is
        # the noise source on shared CI hosts)
        _fire(server.port, trace, concurrency)
        wall, results, lats = _fire(server.port, trace, concurrency)
        wall2, results2, lats2 = _fire(server.port, trace, concurrency)
        if wall2 < wall:
            wall, results, lats = wall2, results2, lats2
        stats = _stats(server.port)
    finally:
        shutdown()
    parity = all(r == e for r, e in zip(results, expected))
    occ = [r["occupancy"] for r in stats["replicas"]]
    lat_ms = np.asarray(lats) * 1e3
    total_toks = sum(len(r) for r in results)
    row = {
        "replicas": replicas,
        "requests": len(trace),
        "wall_s": round(wall, 3),
        "req_per_s": round(len(trace) / max(wall, 1e-9), 3),
        "tok_per_s": round(total_toks / max(wall, 1e-9), 1),
        "latency_p50_ms": round(float(np.percentile(lat_ms, 50)), 1),
        "latency_p99_ms": round(float(np.percentile(lat_ms, 99)), 1),
        "occupancy": [round(o, 4) for o in occ],
        "token_parity": bool(parity),
    }
    print(f"  N={replicas}: {row['req_per_s']:.2f} req/s  "
          f"{row['tok_per_s']:.0f} tok/s  p50 {row['latency_p50_ms']:.0f}ms  "
          f"p99 {row['latency_p99_ms']:.0f}ms  occupancy "
          f"{[f'{o:.0%}' for o in occ]}  parity={parity}")
    return row


def run(fast: bool = False, arch: str = "qwen1.5-0.5b"):
    import jax

    from repro.configs import get_config
    from repro.models.registry import build

    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    n_req = 10 if fast else 24
    slots, chunk_steps = 2, 4
    concurrency = 6
    trace = _trace(cfg, n_req, rng)
    print(f"\nsustained load ({arch} reduced): {n_req} streaming requests, "
          f"concurrency={concurrency}, {slots} slots x chunk={chunk_steps}, "
          f"per-request sampling mix")
    expected = _isolated(model, params, trace)

    tiers = [_run_tier(model, params, trace, expected, n, slots,
                       chunk_steps, concurrency)
             for n in (1, 2)]
    speedup = tiers[1]["req_per_s"] / max(tiers[0]["req_per_s"], 1e-9)
    cores = os.cpu_count() or 1
    print(f"  N=2 vs N=1: {speedup:.2f}x aggregate req/s "
          f"({cores} host cores)")
    results = {
        "arch": arch,
        "requests": n_req,
        "concurrency": concurrency,
        "slots": slots,
        "chunk_steps": chunk_steps,
        "host_cores": cores,
        "tiers": tiers,
        "replica_speedup": round(speedup, 3),
    }
    for row in tiers:
        assert row["token_parity"], (
            f"N={row['replicas']}: routed tokens diverged from isolated "
            f"runs — throughput without parity does not count")
        assert max(row["occupancy"]) > 0.0, row
    if cores >= 2:
        # replica chunks only overlap when there are cores to overlap on;
        # a single-core host interleaves them (correctness holds, wall
        # clock cannot improve), so the scaling gate is multicore-only
        assert speedup >= 1.5, (
            f"2 replicas gave {speedup:.2f}x aggregate req/s on "
            f"{cores} cores (expected >= 1.5x)")
    return results


if __name__ == "__main__":
    import sys
    run(fast="--fast" in sys.argv)
