"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,table3,comm,roofline]
    python -m benchmarks.run --fast            # CI smoke lane (small sweeps)

  table1    Paper Table I   — TD-method comparison on ResNet-32 params
  table3    Paper Table III — TTD phase breakdown, baseline vs TT-Edge
  batched   Batched planner — bucketed one-launch compression vs serial
  comm      Paper Fig. 1    — cross-pod TT-compressed sync payload
  roofline  §Roofline       — per-cell roofline table from the dry-run
  kernels   Pallas kernel block-shape sweeps vs ref oracles (quick)
  tt_serve  TT-native serving — reconstruct-then-serve vs decode straight
            from TT cores (tok/s + resident weight bytes)
  tt_families  TT-native coverage sweep — logit parity + byte reduction on
            one reduced config per family (transformer/encdec/mamba2/
            rglru/MoE); a family regressing to reconstruct fails the lane.
            Also runs the quantized gate (see tt_quant) on reduced gemma3.
  tt_quant  Quantized TT serving — int8 cores + fused in-kernel dequant on
            reduced gemma3; gates ≥99% next-token agreement vs the dense
            oracle and ≥1.8x TT-served-leaf byte reduction vs bf16 cores
  decode_driver  Serving-runtime lane — python-loop vs fused-scan decode
            driver (token parity + tok/s, dense and TT weights) and
            continuous batching vs padded lockstep on a heterogeneous
            request mix
  serve_load  Front-door lane — N router replicas + asyncio SSE server
            under a seeded closed-loop request storm (req/s, p50/p99
            latency, slot occupancy; token parity vs isolated runs)
  chaos     Fault-tolerance lane — seeded FaultPlan injects a replica
            crash, a slow-chunk straggler, a NaN-poisoned request, and a
            corrupt checkpoint; gates zero hung tickets, typed errors,
            survivor parity vs isolated runs, and full live-replica
            recovery

``--fast`` propagates to every benchmark that accepts a ``fast=`` kwarg
(smaller sweeps, single method) — the CI smoke lane that catches
benchmark-script rot without paying full benchmark wall-clock.

Headline numbers additionally persist as ``BENCH_<lane>.json`` at the repo
root (``benchmarks/record.py``) so the perf trajectory is tracked across
PRs, not just printed: ``decode_driver`` → BENCH_decode.json, ``tt_serve``/
``tt_families`` → BENCH_tt_serve.json, ``tt_quant`` (and the quantized leg
of ``tt_families``) → BENCH_tt_quant.json, ``serve_load`` →
BENCH_serve_load.json, ``chaos`` → BENCH_chaos.json.
"""

from __future__ import annotations

import argparse
import inspect
import os
import sys
import time
import traceback

try:                                   # installed package (pip install -e .)
    import repro                       # noqa: F401
except ModuleNotFoundError:            # bare checkout: bootstrap src/
    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "src")
    )


def bench_table1(fast: bool = False):
    from benchmarks import table1_compression
    table1_compression.run(fast=fast)


def bench_table3(fast: bool = False):
    from benchmarks import table3_phases
    table3_phases.run(max_tensors=4 if fast else 12)


def bench_batched(fast: bool = False):
    from benchmarks import batched_compression
    batched_compression.run(fast=fast)


def bench_comm(fast: bool = False):
    from benchmarks import table_comm
    table_comm.run(n_pods=2 if fast else 4)


def bench_roofline():
    from benchmarks import roofline_bench
    roofline_bench.run()


def bench_kernels(fast: bool = False):
    from benchmarks import kernel_bench
    kernel_bench.run(fast=fast)


def bench_tt_serve(fast: bool = False):
    from benchmarks import tt_serve
    tt_serve.run(fast=fast)


def bench_tt_families(fast: bool = False):
    from benchmarks import tt_serve
    tt_serve.run_families(fast=fast)
    # the quantized family rides the coverage lane: one reduced config
    # through int8 cores, gating agreement + leaf-byte reduction
    tt_serve.run_quant(fast=fast)


def bench_tt_quant(fast: bool = False):
    from benchmarks import tt_serve
    tt_serve.run_quant(fast=fast)


def bench_decode_driver(fast: bool = False):
    from benchmarks import decode_driver
    decode_driver.run(fast=fast)


def bench_serve_load(fast: bool = False):
    from benchmarks import serve_load
    serve_load.run(fast=fast)


def bench_chaos(fast: bool = False):
    from benchmarks import chaos_serve
    chaos_serve.run(fast=fast)


ALL = {
    "table1": bench_table1,
    "table3": bench_table3,
    "batched": bench_batched,
    "comm": bench_comm,
    "roofline": bench_roofline,
    "kernels": bench_kernels,
    "tt_serve": bench_tt_serve,
    "tt_families": bench_tt_families,
    "tt_quant": bench_tt_quant,
    "decode_driver": bench_decode_driver,
    "serve_load": bench_serve_load,
    "chaos": bench_chaos,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke mode: shrunken sweeps, same code paths")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        raise SystemExit(
            f"unknown benchmark(s): {','.join(unknown)} "
            f"(choose from: {','.join(ALL)})"
        )

    failures = []
    for name in names:
        print(f"\n{'=' * 72}\n== benchmark: {name}"
              + (" (fast)" if args.fast else "")
              + f"\n{'=' * 72}", flush=True)
        t0 = time.time()
        fn = ALL[name]
        try:
            if "fast" in inspect.signature(fn).parameters:
                fn(fast=args.fast)
            else:
                fn()
            print(f"== {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
