"""Serving driver: batched prefill + decode with a KV/state cache.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch gemma3-1b --reduced --batch 4 --prompt-len 32 --gen 16

Two modes share one model-setup path (``--weights``/``--tt-*`` work in
both):

* **batch mode** (default) — one uniform batch through
  ``launch/engine.generate``, timing + optional TT-vs-dense verify.
* **server mode** (``--serve``) — the production front door: N Engine
  replicas (``--replicas``/``--slots``/``--chunk-steps``) behind the
  load-aware router, fronted by the asyncio HTTP server
  (``--host``/``--port``; per-request deadlines via ``--deadline-ms``,
  backpressure via ``--queue-depth``).  See docs/SERVING.md for the
  operator's handbook.

Decode runs through ``launch/engine.py``: the default ``--driver fused``
executes the whole generation (prefill-by-stepping → sample → append →
step) as one jitted ``lax.scan`` per phase — no host→device dispatch
round-trip per token; ``--driver python`` keeps the legacy
one-jitted-step-per-token loop as the oracle.  Both main run and
``--verify`` oracle go through the same driver.

TT-native serving (``--weights tt``): the driver takes a TTCompressor
payload (compressed in-process from spectrally-decayed init weights, or
loaded from a ``--tt-checkpoint`` directory written by
``checkpoint.save_tt_payload``) and serves decode WITHOUT reconstructing
the dense matrices — layer matmuls contract activations straight against
the TT cores (``models.common.tt_native_params`` → ``core/tt_linear`` →
``kernels/tt_contract``).  ``--verify`` cross-checks the TT-native logits
against the reconstruct-then-serve path and reports resident weight bytes
for both modes.

Quantized TT serving (``--weights tt-int8``): same payload and serving
contract, but every TTLinear leaf stores int8 cores + symmetric scales
(``--quant-calib`` picks absmax or pXX percentile calibration) and the
fused kernels dequantize in-VMEM.  Logits move within the quantization
error, so ``--verify`` reports the measured next-token agreement — the
quantized gate is ≥99% agreement, not exact parity.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import engine as engine_mod
from repro.launch import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build


def _dense_bytes(payload) -> int:
    """Dense resident bytes the payload WOULD occupy if reconstructed —
    from leaf metadata alone, so TT-native serve never materializes it."""
    from repro.core.compression import CompressedParam

    def is_cp(x):
        return isinstance(x, CompressedParam)

    return sum(
        int(np.prod(c.orig_shape)) * jnp.dtype(c.orig_dtype).itemsize
        for c in jax.tree.leaves(payload, is_leaf=is_cp)
    )


def _quant_of(weights: str):
    """``--weights tt-<fmt>`` → fmt (validated), plain ``tt``/``dense`` → None."""
    if weights.startswith("tt-"):
        from repro.core import quant_dtype
        fmt = weights[3:]
        quant_dtype(fmt)          # raise early on junk
        return fmt
    return None


def _tt_setup(params, args, cfg):
    """Compress (or load) the TT payload and build the TT-native params.

    Returns (params_tt, payload, report_line).  The dense oracle is NOT
    reconstructed here — only the verify pass pays for it (on by default;
    ``--no-verify`` serves with just cores + raw leaves resident).  Every
    family in the zoo carries TT-native leaves — the family's registered
    serving rules (``models.common.register_tt_serve_rules``) pick which
    weights serve from cores; the rest reconstruct as before.

    ``--weights tt-int8`` quantizes the built TT leaves in place
    (``quantize_tt_tree``) and the report line shows the byte ladder both
    ways: total resident bytes AND the TT-served-leaf bytes the contraction
    kernels actually stream (raw leaves — embeddings, norms — are identical
    across modes and dilute the total ratio).
    """
    from repro.core import (
        CompressionPolicy, TTCompressor, quant_dtype, quantize_tt_tree,
        spectral_decay_pytree, tt_leaf_bytes, tt_param_bytes,
    )
    from repro.models import common as model_common

    quant = _quant_of(getattr(args, "weights", "tt"))
    calib = getattr(args, "quant_calib", "absmax")
    comp = TTCompressor(CompressionPolicy(eps=args.tt_eps, min_size=8192))
    if args.tt_checkpoint:
        from repro.checkpoint.checkpoint import load_tt_payload
        payload, manifest = load_tt_payload(args.tt_checkpoint, like=params)
        ck_family = manifest.get("family")
        if ck_family is not None and ck_family != cfg.family:
            raise ValueError(
                f"TT checkpoint was compressed from family {ck_family!r}, "
                f"cannot serve arch family {cfg.family!r}"
            )
        ratio = None
    else:
        # random init has a flat spectrum (incompressible — the policy
        # correctly refuses); impose trained-like decay so the TT path
        # actually engages on a synthetic-weights driver run
        params = spectral_decay_pytree(params, alpha=args.tt_alpha)
        payload, report = comp.compress(params)
        ratio = report.ratio
        if getattr(args, "save_tt_checkpoint", None):
            from repro.checkpoint.checkpoint import save_tt_payload
            save_tt_payload(
                args.save_tt_checkpoint, payload,
                extra={"eps": args.tt_eps, "arch": cfg.name},
                family=cfg.family,
                quant=quant, quant_calib=calib,
            )
            print(f"[serve] TT payload saved to {args.save_tt_checkpoint}"
                  + (f" ({quant} cores)" if quant else ""))
    params_tt = model_common.tt_native_params(payload, family=cfg.family)
    dense_b = _dense_bytes(payload)
    tt_b = tt_param_bytes(params_tt)
    if quant is None:
        line = (f"weight bytes: dense {dense_b:,} -> tt-native {tt_b:,} "
                f"({dense_b / max(tt_b, 1):.2f}x resident reduction"
                + (f"; payload ratio {ratio:.2f}x params" if ratio else "")
                + ")")
        return params_tt, payload, line
    wide_leaf_b, dense_leaf_b = tt_leaf_bytes(params_tt)
    params_tt = quantize_tt_tree(
        params_tt, dtype=quant_dtype(quant), calib=calib
    )
    ttq_b = tt_param_bytes(params_tt)
    q_leaf_b, _ = tt_leaf_bytes(params_tt)
    line = (f"weight bytes: dense {dense_b:,} -> tt {tt_b:,} -> "
            f"tt-{quant} {ttq_b:,} ({dense_b / max(ttq_b, 1):.2f}x total); "
            f"TT-served leaves {wide_leaf_b:,} -> {q_leaf_b:,} "
            f"({wide_leaf_b / max(q_leaf_b, 1):.2f}x vs wide cores, "
            f"{dense_leaf_b / max(q_leaf_b, 1):.2f}x vs dense form)")
    return params_tt, payload, line


def serve(args) -> dict:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    mesh = make_host_mesh(model_parallel=args.model_parallel)
    shd.set_mesh_axis_sizes(mesh)

    rng = np.random.default_rng(args.seed)
    b = args.batch
    max_len = args.prompt_len + args.gen

    with mesh:
        params = model.init(jax.random.PRNGKey(args.seed))
        payload = None
        if args.weights != "dense":
            params, payload, byte_line = _tt_setup(params, args, cfg)
            print(f"[serve] TT-native mode: {byte_line}")

        prompts = rng.integers(
            0, cfg.vocab_size, size=(b, args.prompt_len), dtype=np.int32
        )
        # encdec archs carry encoder input: each request/row gets a source
        # stream, encoded into the cross-attn memory before decode
        src = None
        if model.populate_memory is not None:
            src = rng.integers(
                0, cfg.vocab_size, size=(b, cfg.frontend_len),
                dtype=np.int32,
            )
        sample_kw = dict(
            src_tokens=src, temperature=args.temperature, top_k=args.top_k,
            seed=args.seed,
        )
        # main run and verify oracle share ONE driver implementation
        # (launch/engine.generate) — --driver picks fused vs python
        run = engine_mod.generate(
            model, params, prompts, args.gen, max_len=max_len,
            driver=args.driver, **sample_kw,
        )

        if args.weights != "dense" and args.verify:
            # reconstruct-then-serve oracle: same payload, dense weights.
            # Materialized HERE only — use --no-verify for the pure-TT
            # resident footprint (verify is on by default as the demo of
            # the logit-parity guarantee)
            from repro.core import TTCompressor as _TTC
            from repro.models.common import logit_parity
            params_rx = _TTC().decompress(payload)
            oracle = engine_mod.generate(
                model, params_rx, prompts, args.gen, max_len=max_len,
                driver=args.driver, **sample_kw,
            )
            d, scale, agree = logit_parity(
                run["prompt_logits"], oracle["prompt_logits"]
            )
            tps_rx = b * (args.gen - 1) / max(oracle["decode_t"], 1e-9)
            agree_line = f"next-token agreement {agree:.2%}"
            if _quant_of(args.weights) is not None:
                # quantization moves logits, and on synthetic spectral-decay
                # weights the distribution is near-flat (argmax ties flip on
                # any perturbation) — report the GATED metric instead:
                # teacher-forced tie-tolerant agreement over every prompt
                # position (see benchmarks/tt_serve.run_quant)
                from repro.models.common import (
                    teacher_forced_logits, tie_tolerant_agreement,
                )
                tf_q = teacher_forced_logits(model, params, prompts)
                tf_rx = teacher_forced_logits(model, params_rx, prompts)
                agree_line = (
                    f"tie-tolerant next-token agreement "
                    f"{tie_tolerant_agreement(tf_q, tf_rx):.2%} over "
                    f"{tf_rx.shape[0] * tf_rx.shape[1]} teacher-forced "
                    f"positions")
            print(f"[serve] verify vs reconstruct-then-serve: "
                  f"max|Δlogits| {d:.2e} (scale {scale:.2e}), "
                  f"{agree_line}, "
                  f"reconstruct decode {tps_rx:.1f} tok/s")

    gen = run["gen"]
    tps = b * (args.gen - 1) / max(run["decode_t"], 1e-9)
    mode = "dense" if args.weights == "dense" else f"{args.weights}-native"
    print(f"[serve] ({mode}, driver={args.driver}) prefill "
          f"{args.prompt_len} toks in "
          f"{run['prefill_t']*1e3:.0f}ms; decode {args.gen-1} steps @ "
          f"{tps:.1f} tok/s (batch={b})")
    print(f"[serve] sample generation: {gen[0][:16].tolist()}")
    return {"tok_per_s": tps, "generated": gen}


def serve_http(args) -> None:
    """Server mode: N engine replicas behind the router + HTTP front door.

    Replicas share one params pytree (host memory is shared; each replica
    owns only its cache pool), so N replicas cost N cache pools, not N
    copies of the weights.
    """
    from repro.launch.router import Router
    from repro.launch.server import run_server

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    mesh = make_host_mesh(model_parallel=args.model_parallel)
    shd.set_mesh_axis_sizes(mesh)
    with mesh:
        params = model.init(jax.random.PRNGKey(args.seed))
        if args.weights != "dense":
            params, _, byte_line = _tt_setup(params, args, cfg)
            print(f"[serve] TT-native mode: {byte_line}")
        max_len = args.prompt_len + args.gen
        engines = [
            engine_mod.Engine(
                model, params, slots=args.slots, max_len=max_len,
                chunk_steps=args.chunk_steps,
                temperature=args.temperature, top_k=args.top_k,
                seed=args.seed, admission=args.admission,
            )
            for _ in range(args.replicas)
        ]
        watchdog = (None if args.watchdog_ms is None
                    else args.watchdog_ms / 1e3)
        router = Router(engines, queue_depth=args.queue_depth,
                        watchdog_s=watchdog)
        deadline = (None if args.deadline_ms is None
                    else args.deadline_ms / 1e3)
        print(f"[serve] {args.replicas} replica(s) x {args.slots} slots, "
              f"admission={engines[0].admission}, "
              f"queue_depth={args.queue_depth}"
              + (f", watchdog={watchdog:g}s" if watchdog else ""))
        run_server(router, host=args.host, port=args.port,
                   default_deadline=deadline)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0,
                    help="weights/prompts RNG seed AND the sampling seed "
                         "(row r samples under fold_in(PRNGKey(seed), r))")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 (default) is greedy "
                         "argmax, bit-identical to the pre-sampling driver")
    ap.add_argument("--top-k", type=int, default=None,
                    help="keep only the k highest logits before sampling "
                         "(requires --temperature > 0 to matter)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--driver", choices=engine_mod.DRIVERS, default="fused",
                    help="decode driver: 'fused' runs the whole generation "
                         "as one scanned computation per phase (no per-token "
                         "dispatch); 'python' is the legacy per-token oracle")
    ap.add_argument("--weights", choices=("dense", "tt", "tt-int8"),
                    default="dense",
                    help="tt = serve straight from TT cores (no dense "
                         "weight materialization for eligible layers); "
                         "tt-int8 = same, with int8 cores + symmetric "
                         "scales dequantized inside the fused kernels")
    ap.add_argument("--quant-calib", type=str, default="absmax",
                    help="quantization calibration for --weights tt-int8: "
                         "'absmax' (exact round-trip grid) or 'pXX' "
                         "(XX-th percentile of |w|, clips tail outliers)")
    ap.add_argument("--tt-eps", type=float, default=0.2,
                    help="compression ε for the in-process TT payload")
    ap.add_argument("--tt-alpha", type=float, default=1.0,
                    help="spectral decay of the synthetic trained weights")
    ap.add_argument("--tt-checkpoint", type=str, default=None,
                    help="load the TT payload from this directory "
                         "(checkpoint.save_tt_payload layout); the "
                         "manifest's recorded family must match --arch")
    ap.add_argument("--save-tt-checkpoint", type=str, default=None,
                    help="after in-process compression, save the TT "
                         "payload here (records the model family in the "
                         "manifest for the load-time cross-check)")
    ap.add_argument("--verify", action="store_true", default=True,
                    help="cross-check TT-native logits against the "
                         "reconstruct-then-serve oracle (default ON; this "
                         "materializes the dense weights for the oracle "
                         "pass — use --no-verify for the pure-TT resident "
                         "footprint)")
    ap.add_argument("--no-verify", dest="verify", action="store_false")
    ap.add_argument("--serve", action="store_true",
                    help="server mode: run the HTTP front door instead of "
                         "one batch (POST /v1/generate; see docs/SERVING.md)")
    ap.add_argument("--host", type=str, default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="TCP port (0 = ephemeral, printed at startup)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the router (weights are "
                         "shared; each replica adds one cache pool + worker)")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent requests per replica (cache pool rows)")
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="fused decode steps per scheduling chunk")
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="max outstanding requests per replica before "
                         "submissions get 429 (bounded admission queue)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="server-wide default per-request deadline; an "
                         "expired request is cancelled (504) and its slot "
                         "freed.  Requests can override via 'deadline_ms'")
    ap.add_argument("--watchdog-ms", type=float, default=None,
                    help="per-chunk heartbeat watchdog: a replica whose "
                         "worker goes stale longer than this while holding "
                         "work is marked suspect (no new placements) until "
                         "it recovers; worker DEATH is always supervised "
                         "(failover + bounded-backoff restart) regardless")
    ap.add_argument("--admission", choices=engine_mod.ADMISSION_MODES,
                    default="auto",
                    help="slot admission: 'scan' = in-scan device-resident "
                         "queue (token-only families), 'boundary' = one "
                         "dispatch per admission between chunks (encdec); "
                         "'auto' picks per family")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.serve:
        serve_http(args)
    else:
        serve(args)


if __name__ == "__main__":
    main()
