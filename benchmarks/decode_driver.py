"""Decode-driver benchmark: python loop vs fused scan vs continuous batching.

Three serving strategies over the SAME decode_step, dense and TT-native:

  * ``python``     — one jitted decode_step per token, driven from Python
                     (a dispatch round-trip + sample/argmax host sync per
                     token).
  * ``fused``      — the whole generation as one scanned computation per
                     phase (``launch/engine.generate(driver="fused")``).
  * ``continuous`` — slot-based continuous batching over the fused driver
                     on a heterogeneous request mix, against the padded
                     lockstep baseline (same request mix, same fused
                     stepper, prompts/gens padded to the batch max).

Asserts (the CI smoke lane gate):
  * fused and python produce token-for-token identical generations —
    greedy AND under temperature/top-k sampling (fixed seed);
  * fused decode tok/s >= python decode tok/s (dense AND tt weights);
  * continuous batching beats padded lockstep on aggregate tok/s;
  * encdec requests under continuous batching (encoder memory computed at
    admission) match isolated runs token-for-token.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def _tt_params(model, cfg, eps: float = 0.2):
    from repro.core import (
        CompressionPolicy, TTCompressor, spectral_decay_pytree,
    )
    from repro.models import common as model_common

    params = spectral_decay_pytree(model.init(jax.random.PRNGKey(0)))
    comp = TTCompressor(CompressionPolicy(eps=eps, min_size=8192))
    payload, _ = comp.compress(params)
    return model_common.tt_native_params(payload, family=cfg.family)


def _timed_generate(model, params, prompts, gen, driver, repeats=2):
    """Best-of-``repeats`` decode timing (first call per driver compiles —
    every timing below is from a warm cache)."""
    from repro.launch.engine import generate

    # share one jitted step across the python-driver repeats so only the
    # first (warmup) call pays the trace+compile
    decode = (jax.jit(model.decode_step, donate_argnums=(1,))
              if driver == "python" else None)
    best = None
    for _ in range(repeats + 1):        # +1 warmup
        out = generate(model, params, prompts, int(gen), driver=driver,
                       decode=decode)
        if best is None or out["decode_t"] < best["decode_t"]:
            best = out
    return best


def _driver_faceoff(model, cfg, params, b, plen, gen, label):
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (b, plen), np.int32)
    py = _timed_generate(model, params, prompts, gen, "python")
    fu = _timed_generate(model, params, prompts, gen, "fused")
    parity = bool(np.array_equal(py["gen"], fu["gen"]))
    tps = lambda o: b * (gen - 1) / max(o["decode_t"], 1e-9)  # noqa: E731
    row = {
        "python_tps": tps(py),
        "fused_tps": tps(fu),
        "speedup": tps(fu) / max(tps(py), 1e-9),
        "token_parity": parity,
    }
    print(f"{label:<10}{row['python_tps']:>14.1f}{row['fused_tps']:>12.1f}"
          f"{row['speedup']:>9.2f}x   parity={parity}")
    assert parity, f"{label}: fused generation diverged from python loop"
    return row


def _sampled_faceoff(model, cfg, params, b, plen, gen, label,
                     temperature=0.8, top_k=50, seed=7):
    """Stochastic-sampling lane: both drivers under the same fixed seed
    must emit identical tokens (the PRNG-carrying scan contract)."""
    from repro.launch.engine import generate

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (b, plen), np.int32)
    kw = dict(temperature=temperature, top_k=top_k, seed=seed)
    py = generate(model, params, prompts, int(gen), driver="python", **kw)
    fu = generate(model, params, prompts, int(gen), driver="fused", **kw)
    fu2 = generate(model, params, prompts, int(gen), driver="fused", **kw)
    parity = bool(np.array_equal(py["gen"], fu["gen"])
                  and np.array_equal(fu["gen"], fu2["gen"]))
    tps = b * (gen - 1) / max(min(fu["decode_t"], fu2["decode_t"]), 1e-9)
    print(f"{label:<10}{'':>14}{tps:>12.1f}{'':>10}   parity={parity} "
          f"(T={temperature}, top_k={top_k}, seed={seed})")
    assert parity, f"{label}: sampled fused generation diverged from python"
    return {"fused_tps": tps, "token_parity": parity,
            "temperature": temperature, "top_k": top_k, "seed": seed}


def _encdec_continuous(fast: bool, arch="seamless-m4t-large-v2"):
    """Encdec under continuous batching: requests carry encoder input,
    admission runs the encode, and every staggered completion must match
    its isolated run token-for-token (the PR 4 hole this lane now gates)."""
    from repro.configs import get_config
    from repro.launch.engine import Engine, generate
    from repro.models.registry import build

    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    n_req = 4 if fast else 6
    reqs = []
    for _ in range(n_req):
        plen = 2 + int(rng.integers(0, 3))
        slen = 3 + int(rng.integers(0, cfg.frontend_len - 3))
        reqs.append((
            rng.integers(0, cfg.vocab_size, (plen,), np.int32),
            4 + int(rng.integers(0, 3)),
            rng.integers(0, cfg.vocab_size, (slen,), np.int32),
        ))
    eng = Engine(model, params, slots=2, max_len=16, chunk_steps=3)
    uids = [eng.submit(p, g, src_tokens=s) for p, g, s in reqs]
    done = {c.uid: c for c in eng.run()}
    parity = True
    for uid, (p, g, s) in zip(uids, reqs):
        iso = generate(model, params, p[None], g, driver="fused",
                       src_tokens=s[None])
        parity &= bool(np.array_equal(done[uid].tokens, iso["gen"][0]))
    occ = eng.slot_steps / max(eng.steps * eng.slots, 1)
    print(f"\nencdec continuous batching ({n_req} requests w/ encoder "
          f"input): staggered==isolated parity={parity}, "
          f"occupancy {occ:.0%}")
    assert parity, "encdec continuous batching diverged from isolated runs"
    return {"requests": n_req, "token_parity": parity, "occupancy": occ}


def _request_mix(cfg, n_small, n_big, rng):
    """Heterogeneous arrival stream: each long request arrives followed by
    a run of short ones.  Padded lockstep groups in arrival order, so every
    group containing a long request stalls its short neighbours for the
    long one's full length; the continuous engine instead parks the longs
    on their own slots and streams the shorts through the rest."""
    reqs = []
    per_big = max(n_small // max(n_big, 1), 1)
    for b in range(n_big):
        plen, gen = 6 + int(rng.integers(0, 4)), 32
        reqs.append((rng.integers(0, cfg.vocab_size, (plen,), np.int32),
                     gen))
        take = per_big if b < n_big - 1 else n_small - per_big * (n_big - 1)
        for _ in range(take):
            plen, gen = 2 + int(rng.integers(0, 2)), 3
            reqs.append((rng.integers(0, cfg.vocab_size, (plen,), np.int32),
                         gen))
    return reqs


def _continuous_vs_lockstep(model, cfg, params, reqs, slots, chunk_steps):
    from repro.launch.engine import Engine, generate

    useful = sum(gen for _, gen in reqs)

    def lockstep():
        total_t = 0.0
        for lo in range(0, len(reqs), slots):
            group = reqs[lo:lo + slots]
            maxp = max(len(p) for p, _ in group)
            maxg = max(g for _, g in group)
            padded = np.zeros((len(group), maxp), np.int32)
            for i, (p, _) in enumerate(group):
                padded[i, :len(p)] = p
            t0 = time.time()
            generate(model, params, padded, maxg, driver="fused")
            total_t += time.time() - t0
        return total_t

    def continuous():
        max_len = max(len(p) + g for p, g in reqs) + 1
        eng = Engine(model, params, slots=slots, max_len=max_len,
                     chunk_steps=chunk_steps)
        for p, g in reqs:
            eng.submit(p, g)
        t0 = time.time()
        done = eng.run()
        dt = time.time() - t0
        assert len(done) == len(reqs), (len(done), len(reqs))
        return dt, eng

    lockstep()                           # compile both paths before timing
    dt_cont, _ = continuous()            # (this one also pays the compiles)
    dt_lock = min(lockstep(), lockstep())
    dt_cont2, eng = continuous()
    dt_cont3, _ = continuous()
    dt_cont = min(dt_cont, dt_cont2, dt_cont3)
    row = {
        "requests": len(reqs),
        "useful_tokens": useful,
        "lockstep_tps": useful / max(dt_lock, 1e-9),
        "continuous_tps": useful / max(dt_cont, 1e-9),
        "speedup": dt_lock / max(dt_cont, 1e-9),
        "fused_steps": eng.steps,
        "occupancy": eng.slot_steps / max(eng.steps * eng.slots, 1),
    }
    print(f"\ncontinuous batching ({len(reqs)} heterogeneous requests, "
          f"{slots} slots, chunk={chunk_steps}):")
    print(f"  lockstep padded {row['lockstep_tps']:>8.1f} tok/s   "
          f"continuous {row['continuous_tps']:>8.1f} tok/s   "
          f"({row['speedup']:.2f}x, occupancy {row['occupancy']:.0%})")
    return row


def run(fast: bool = False, arch: str = "gemma3-1b"):
    from repro.configs import get_config
    from repro.models.registry import build

    b, plen, gen = (2, 6, 16) if fast else (4, 16, 48)
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    print(f"\ndecode drivers ({arch} reduced, batch={b}, prompt={plen}, "
          f"gen={gen})")
    print(f"{'weights':<10}{'python tok/s':>14}{'fused tok/s':>12}"
          f"{'speedup':>10}")
    results = {"arch": arch, "batch": b, "prompt_len": plen, "gen": gen}
    results["dense"] = _driver_faceoff(model, cfg, params, b, plen, gen,
                                       "dense")
    params_tt = _tt_params(model, cfg)
    results["tt"] = _driver_faceoff(model, cfg, params_tt, b, plen, gen,
                                    "tt-native")
    results["sampled"] = _sampled_faceoff(model, cfg, params, b, plen, gen,
                                          "sampled")

    rng = np.random.default_rng(1)
    n_small, n_big = (7, 2) if fast else (9, 3)
    reqs = _request_mix(cfg, n_small, n_big, rng)
    results["continuous"] = _continuous_vs_lockstep(
        model, cfg, params, reqs, slots=3 if fast else 4,
        chunk_steps=4,
    )
    results["encdec_continuous"] = _encdec_continuous(fast)

    assert results["dense"]["speedup"] >= 1.0, results["dense"]
    assert results["tt"]["speedup"] >= 1.0, results["tt"]
    assert results["continuous"]["speedup"] > 1.0, results["continuous"]
    assert results["sampled"]["token_parity"], results["sampled"]
    assert results["encdec_continuous"]["token_parity"], (
        results["encdec_continuous"])
    return results


if __name__ == "__main__":
    import sys
    run(fast="--fast" in sys.argv)
