"""Chaos lane: deterministic fault injection against the serving plane.

The fault-tolerance layer (router supervision + failover, numeric
quarantine, checkpoint integrity) is only real if it survives actual
faults — so this lane injects them, at seeded deterministic points
(``runtime/fault_tolerance.FaultPlan``), and holds the plane to three
invariants:

  * **nothing hangs** — every request reaches a terminal state within the
    drain timeout: tokens, or a TYPED error (``ReplicaLost`` /
    ``NumericFault`` / ``DeadlineExpired`` / ...);
  * **survivor parity** — every request that completes returns tokens
    bit-exact vs an isolated ``generate`` run (failover re-decodes only
    never-admitted requests, so parity must hold through a crash);
  * **full recovery** — after the injected crash the router restarts the
    replica and returns to full live capacity, and a retry pass over the
    ``replica_lost`` requests then succeeds (except the NaN-poisoned one,
    which must fail ``NumericFault`` again — poison is not retryable).

Injected per run: one replica crash mid-trace (worker raises inside the
chunk loop), one slow-chunk straggler (trips the watchdog into
``suspect`` and recovers), one NaN-poisoned request (magic poison token
in the prompt → non-finite logits → quarantine), and a corrupt/truncated
checkpoint leg (sha256 verification must name the bad leaf; stale tmp
dirs must be cleaned).

It prints injected/fired fault counts, the outcome histogram, recovery
time, survivor parity, retry outcomes, and the checkpoint-integrity
checklist.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import threading
import time

import numpy as np


# ---------------------------------------------------------------------------
# NaN injection: a model whose decode emits non-finite logits for any slot
# whose current input token is the magic poison token.  Everything else —
# engine, router, guard — is the production path.
# ---------------------------------------------------------------------------

def poison_token(cfg) -> int:
    return cfg.vocab_size - 1


def poisoned_model(model):
    import jax.numpy as jnp

    tok = poison_token(model.cfg)
    base = model.decode_step

    def decode(p, c, t):
        logits, cache = base(p, c, t)
        hit = jnp.any(t == tok, axis=-1)
        return jnp.where(hit[:, None], jnp.asarray(np.nan, logits.dtype),
                         logits), cache

    return dataclasses.replace(model, decode_step=decode)


def _trace(cfg, n, rng, poison):
    out = []
    for i in range(n):
        plen = 2 + int(rng.integers(0, 4))
        prompt = rng.integers(0, cfg.vocab_size - 1,
                              (plen,)).astype(np.int32)
        if i in poison:
            prompt[-1] = poison_token(cfg)
        out.append({"prompt": prompt, "gen": 4 + int(rng.integers(0, 4)),
                    "seed": i})
    return out


def _isolated(model, params, trace, poison):
    """Parity oracle for non-poisoned requests (poisoned ones have no
    meaningful tokens — they must be quarantined, not compared)."""
    from repro.launch.engine import generate

    expected = {}
    for i, req in enumerate(trace):
        if i in poison:
            continue
        out = generate(model, params, req["prompt"][None], req["gen"],
                       driver="fused", seed=req["seed"])
        expected[i] = out["gen"][0].tolist()
    return expected


class _HealthSampler(threading.Thread):
    """Poll replica states during the storm: records when capacity first
    degrades, when it comes back, and whether the straggler was caught
    in ``suspect``."""

    def __init__(self, router):
        super().__init__(name="health-sampler", daemon=True)
        self.router = router
        self.total = len(router.replicas)
        self.stop = threading.Event()
        self.t_degraded = None
        self.t_recovered = None
        self.min_live = self.total
        self.suspect_seen = False

    def run(self):
        from repro.launch.router import SUSPECT

        while not self.stop.wait(0.005):
            st = self.router.stats()
            live = st["live_replicas"]
            self.min_live = min(self.min_live, live)
            if any(r["state"] == SUSPECT for r in st["replicas"]):
                self.suspect_seen = True
            now = time.monotonic()
            if live < self.total and self.t_degraded is None:
                self.t_degraded = now
            if (self.t_degraded is not None and live == self.total
                    and self.t_recovered is None):
                self.t_recovered = now


def _drain(router, tickets, timeout_s):
    """Resolve every ticket to (kind, payload); a ticket that does not
    terminate within the budget is a HANG — the one thing this lane
    exists to rule out."""
    from repro.launch.router import (DeadlineExpired, NumericFault,
                                     ReplicaLost, RequestCancelled)

    outcomes = {}
    hung = []
    deadline = time.monotonic() + timeout_s
    for i, t in tickets.items():
        left = max(0.5, deadline - time.monotonic())
        try:
            outcomes[i] = ("done", t.result(timeout=left))
        except ReplicaLost as e:
            outcomes[i] = ("replica_lost", str(e))
        except NumericFault as e:
            outcomes[i] = ("poisoned", str(e))
        except DeadlineExpired as e:
            outcomes[i] = ("expired", str(e))
        except RequestCancelled as e:
            outcomes[i] = ("cancelled", str(e))
        except Exception as e:
            if e.__class__.__name__ == "Empty":      # queue.Empty: no event
                hung.append(i)
                outcomes[i] = ("HUNG", None)
            else:
                outcomes[i] = ("error", f"{type(e).__name__}: {e}")
    return outcomes, hung


# ---------------------------------------------------------------------------
# checkpoint-integrity leg
# ---------------------------------------------------------------------------

def _checkpoint_leg() -> dict:
    from repro.checkpoint.checkpoint import (CheckpointCorrupt,
                                             CheckpointManager)

    rng = np.random.default_rng(0)
    state = {"w": rng.standard_normal((16, 16)).astype(np.float32),
             "b": rng.standard_normal((8,)).astype(np.float32)}
    out = {}
    root = tempfile.mkdtemp(prefix="chaos_ckpt_")
    try:
        cdir = os.path.join(root, "ckpt")
        mgr = CheckpointManager(cdir, async_save=False)
        mgr.save(3, state)
        restored, _ = mgr.restore(state)          # verify=True default
        out["clean_restore"] = bool(
            np.array_equal(np.asarray(restored["w"]), state["w"]))

        # bit-flip: rewrite the shard with one array zeroed — a VALID zip
        # with wrong content, so only the sha256 can catch it
        shard = os.path.join(cdir, "step_000003", "shard_0.npz")
        data = dict(np.load(shard))
        data["w"] = np.zeros_like(data["w"])
        np.savez(shard, **data)
        try:
            mgr.restore(state)
            out["bitflip_caught"] = False
        except CheckpointCorrupt as e:
            out["bitflip_caught"] = "'w'" in str(e)
        # opt-out still loads the (corrupt) shard
        try:
            mgr.restore(state, verify=False)
            out["verify_opt_out"] = True
        except Exception:
            out["verify_opt_out"] = False

        # truncation: chop the archive mid-file
        mgr.save(4, state)
        shard4 = os.path.join(cdir, "step_000004", "shard_0.npz")
        with open(shard4, "rb") as f:
            raw = f.read()
        with open(shard4, "wb") as f:
            f.write(raw[: len(raw) // 2])
        try:
            mgr.restore(state, step=4)
            out["truncation_caught"] = False
        except CheckpointCorrupt:
            out["truncation_caught"] = True

        # stale tmp dir from a crashed save is cleaned on open
        stale = os.path.join(cdir, "step_000009.tmp")
        os.makedirs(stale)
        mgr2 = CheckpointManager(cdir)
        out["stale_tmp_cleaned"] = (stale in mgr2.cleaned_tmp
                                    and not os.path.exists(stale))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


# ---------------------------------------------------------------------------
# the lane
# ---------------------------------------------------------------------------

def run(fast: bool = False, arch: str = "qwen1.5-0.5b", seed: int = 0):
    import jax

    from repro.configs import get_config
    from repro.launch.engine import Engine
    from repro.launch.router import Router
    from repro.models.registry import build
    from repro.runtime.fault_tolerance import FaultPlan, RestartPolicy

    cfg = get_config(arch).reduced()
    model = poisoned_model(build(cfg))
    params = model.init(jax.random.PRNGKey(0))

    replicas = 3
    n_req = 8 if fast else 20
    plan = FaultPlan.seeded(seed, replicas=replicas, requests=n_req,
                            crashes=1, stalls=1, poisons=1, stall_s=1.0,
                            span=3 if fast else 5)
    print(f"\nchaos ({arch} reduced): {n_req} requests over {replicas} "
          f"replicas; plan: crash={plan.crash_at} stall={plan.stall_at} "
          f"poison={plan.poison}")

    rng = np.random.default_rng(seed)
    trace = _trace(cfg, n_req, rng, set(plan.poison))
    expected = _isolated(model, params, trace, set(plan.poison))

    def mk_engine(_old=None):
        return Engine(model, params, slots=2, max_len=32, chunk_steps=3)

    router = Router(
        [mk_engine() for _ in range(replicas)], queue_depth=12,
        watchdog_s=0.4,
        restart_policy=RestartPolicy(max_restarts=3, backoff_s=0.05,
                                     max_backoff_s=0.5),
        engine_factory=mk_engine, supervise_interval=0.02,
    )
    for i, rep in enumerate(router.replicas):
        rep.fault_hook = plan.hook_for(i)

    sampler = _HealthSampler(router)
    router.start()
    sampler.start()
    t0 = time.monotonic()
    tickets = {i: router.submit(req["prompt"], req["gen"], seed=req["seed"])
               for i, req in enumerate(trace)}
    outcomes, hung = _drain(router, tickets, timeout_s=300)
    # recovery: wait for full live capacity (restart backoff is tiny)
    t_full = time.monotonic() + 30
    while router.live_replicas() < replicas and time.monotonic() < t_full:
        time.sleep(0.02)
    storm_s = time.monotonic() - t0
    sampler.stop.set()
    sampler.join(timeout=5)

    # retry pass: replica_lost is RETRYABLE (at-most-once delivery means
    # nothing was re-decoded) — after recovery a retry must succeed, and
    # the poisoned request must be rejected AGAIN (poison is permanent)
    retry = {}
    for i, (kind, _) in outcomes.items():
        if kind != "replica_lost":
            continue
        req = trace[i]
        t = router.submit(req["prompt"], req["gen"], seed=req["seed"])
        retry.update({i: r for i, r in
                      _drain(router, {i: t}, timeout_s=120)[0].items()})
    stats = router.stats()
    router.close()

    hist = {}
    for kind, _ in outcomes.values():
        hist[kind] = hist.get(kind, 0) + 1
    parity_fail = [i for i, (k, c) in outcomes.items()
                   if k == "done" and c.tokens.tolist() != expected[i]]
    retry_parity_fail = [i for i, (k, c) in retry.items()
                         if k == "done" and c.tokens.tolist() != expected[i]]
    recovery_s = (None if sampler.t_recovered is None or
                  sampler.t_degraded is None
                  else sampler.t_recovered - sampler.t_degraded)
    ckpt = _checkpoint_leg() if plan.corrupt_checkpoint else {}

    results = {
        "arch": arch,
        "seed": seed,
        "requests": n_req,
        "replicas": replicas,
        "injected": plan.counts(),
        "fired": plan.fired(),
        "outcomes": hist,
        "hung": len(hung),
        "survivor_parity": not parity_fail,
        "retry_outcomes": {str(i): k for i, (k, _) in retry.items()},
        "retry_parity": not retry_parity_fail,
        "recovery_s": None if recovery_s is None else round(recovery_s, 3),
        "min_live_replicas": sampler.min_live,
        "suspect_seen": sampler.suspect_seen,
        "live_replicas_final": stats["live_replicas"],
        "restarts": [r["restarts"] for r in stats["replicas"]],
        "storm_s": round(storm_s, 3),
        "checkpoint": ckpt,
    }
    print(f"  outcomes: {hist}  hung={len(hung)}  "
          f"recovery={results['recovery_s']}s  "
          f"suspect_seen={sampler.suspect_seen}  "
          f"restarts={results['restarts']}")
    print(f"  retry: {results['retry_outcomes']}  checkpoint: {ckpt}")

    # -- the gates -----------------------------------------------------------
    assert not hung, f"HUNG tickets: {hung} — fault tolerance failed"
    allowed = {"done", "replica_lost", "poisoned"}
    assert set(hist) <= allowed, f"untyped outcomes: {hist}"
    assert not parity_fail, (
        f"survivors diverged from isolated runs: {parity_fail}")
    assert plan.fired()["crashes"] == len(plan.crash_at), (
        "planned crash never fired — the lane tested nothing")
    assert stats["live_replicas"] == replicas, (
        f"router did not recover: {stats['live_replicas']}/{replicas} live")
    assert sampler.min_live < replicas, (
        "capacity never degraded — crash path untested")
    assert sampler.suspect_seen, (
        "straggler never tripped the watchdog into suspect")
    # retries: every replica_lost request succeeds on retry, except a
    # poisoned one which must be quarantined again
    for i, (kind, _) in retry.items():
        want = "poisoned" if i in plan.poison else "done"
        assert kind == want, f"retry of request {i}: {kind} != {want}"
    assert not retry_parity_fail, retry_parity_fail
    if ckpt:
        assert all(ckpt.values()), f"checkpoint integrity leg failed: {ckpt}"

    return results


if __name__ == "__main__":
    import sys
    run(fast="--fast" in sys.argv)
