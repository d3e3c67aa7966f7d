"""TT-serve benchmark — reconstruct-then-serve vs TT-native decode.

Compares the two receiving-node strategies for a TT-shipped model on the
serving workload that matters (memory-bound batched decode):

  * ``reconstruct``  — Fig. 1 baseline: materialize every dense weight via
                       eq. (1)/(2), then serve with dense matmuls.
  * ``tt-native``    — contract activations straight against the cores
                       (``core/tt_linear`` + fused ``kernels/tt_contract``);
                       dense matrices for eligible layers never exist.

Reports tokens/s and resident weight bytes for both, and asserts the two
produce the same logits (same cores, same contraction order — only
rounding differs).  ``fast=True`` is the CI smoke lane; ``run_families``
sweeps one reduced config per family (transformer, encdec, mamba2, rglru,
MoE) so TT-native coverage regressions fail the build.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def _decode(model, params, prompts, gen, max_len, driver="fused"):
    """One serving run via the engine (single source of truth for
    prefill-by-stepping + greedy decode + timing boundaries)."""
    from repro.launch.engine import generate
    out = generate(model, params, prompts, gen, max_len=max_len,
                   driver=driver)
    return out["decode_t"], out["prompt_logits"]


def run(fast: bool = False, arch: str = "gemma3-1b", eps: float = 0.2,
        write_json: bool = True):
    from repro.configs import get_config
    from repro.core import (
        CompressionPolicy, TTCompressor, spectral_decay_pytree,
        tt_param_bytes,
    )
    from repro.models import common as model_common
    from repro.models.registry import build

    b, prompt_len, gen = (2, 8, 8) if fast else (4, 32, 32)
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = spectral_decay_pytree(model.init(jax.random.PRNGKey(0)))
    comp = TTCompressor(CompressionPolicy(eps=eps, min_size=8192))
    payload, report = comp.compress(params)

    t0 = time.time()
    params_rx = comp.decompress(payload)
    reconstruct_t = time.time() - t0
    t0 = time.time()
    params_tt = model_common.tt_native_params(payload, family=cfg.family)
    convert_t = time.time() - t0

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (b, prompt_len), np.int32)
    max_len = prompt_len + gen

    rows = []
    logits = {}
    for mode, p in (("reconstruct", params_rx), ("tt-native", params_tt)):
        dt, prompt_logits = _decode(model, p, prompts, gen, max_len)
        logits[mode] = prompt_logits
        rows.append((
            mode,
            b * (gen - 1) / max(dt, 1e-9),
            tt_param_bytes(p),
            reconstruct_t if mode == "reconstruct" else convert_t,
        ))

    print(f"\nTT-serve ({arch} reduced, ε={eps}, batch={b}, gen={gen}; "
          f"payload {report.ratio:.2f}x params)")
    print(f"{'mode':<14}{'tok/s':>10}{'weight bytes':>16}{'setup s':>10}")
    for mode, tps, bytes_, setup in rows:
        print(f"{mode:<14}{tps:>10.1f}{bytes_:>16,}{setup:>10.2f}")

    d, scale, agree = model_common.logit_parity(
        logits["tt-native"], logits["reconstruct"]
    )
    print(f"logit check: max|Δ| {d:.2e} (scale {scale:.2e}), "
          f"agreement {agree:.2%}")
    assert d <= max(0.05 * scale, 1e-3), (d, scale)
    dense_b = rows[0][2]
    tt_b = rows[1][2]
    assert tt_b < dense_b, (tt_b, dense_b)
    print(f"resident-weight reduction: {dense_b / tt_b:.2f}x")
    result = {"arch": arch, "max_diff": d, "agreement": agree,
              "dense_bytes": dense_b, "tt_bytes": tt_b,
              "reconstruct_tps": rows[0][1], "tt_native_tps": rows[1][1]}
    if write_json:
        from benchmarks.record import write_bench
        write_bench("tt_serve", {"archs": {arch: result}})
    return result


def run_quant(fast: bool = False, arch: str = "gemma3-1b", eps: float = 0.2,
              write_json: bool = True, min_agreement: float = 0.99,
              agree_tol: float = 0.05,
              min_vs_tt: float = 1.8, min_vs_dense: float = 3.9):
    """Quantized TT serving gate: int8 cores + fused in-kernel dequant.

    Serves the same payload three ways — reconstruct-then-serve (dense),
    TT-native (wide cores), TT-native int8 — and gates on the quantized
    contract:

      * teacher-forced next-token agreement vs the dense oracle ≥ 99%,
        measured over every prompt position (b×(S-1) positions via
        ``teacher_forced_logits``, so the bound is measured, not vacuous).
        Agreement is TIE-TOLERANT: a position agrees when the quantized
        model's argmax matches the oracle token, or scores it within
        ``agree_tol``·(logit scale) of its own argmax.  On synthetic
        spectral-decay weights the predictive distribution is near-flat
        (top-1/top-2 gaps ~1% of logit scale — random weights have nothing
        to be confident about), so raw argmax between ANY two
        numerically-differing implementations is tie-breaking noise there;
        the tolerance is the same 5%-of-scale bound the wide-TT parity
        gate uses, and a real quantization bug (wrong scale, overflow,
        missing dequant) blows through it at once.  Raw argmax agreement
        is recorded alongside.
      * TT-served-leaf resident bytes (what the ``tt_contract`` kernels
        stream — ``tt_leaf_bytes``) shrink ≥1.8x vs the wide (bf16) cores
        and ≥3.9x vs the dense form of those same leaves.  Raw leaves
        (embeddings, norms) are identical across all three modes; total
        resident bytes are recorded alongside but not gated, since the raw
        remainder dilutes the ratio without saying anything about the
        quantization.
    """
    from repro.configs import get_config
    from repro.core import (
        CompressionPolicy, TTCompressor, spectral_decay_pytree,
        tt_leaf_bytes, tt_param_bytes,
    )
    from repro.models import common as model_common
    from repro.models.registry import build

    # the agreement gate needs position count: b×(prompt_len-1) ≥ 252 keeps
    # the measurement granularity (1/positions) well under the 1% bound
    b, prompt_len, gen = (4, 64, 8) if fast else (4, 64, 32)
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = spectral_decay_pytree(model.init(jax.random.PRNGKey(0)))
    comp = TTCompressor(CompressionPolicy(eps=eps, min_size=8192))
    payload, report = comp.compress(params)

    params_rx = comp.decompress(payload)
    params_tt = model_common.tt_native_params(payload, family=cfg.family)
    params_q = model_common.tt_native_params(
        payload, family=cfg.family, quant="int8"
    )

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (b, prompt_len), np.int32)
    max_len = prompt_len + gen

    rows, tf = [], {}
    for mode, p in (("dense", params_rx), ("tt", params_tt),
                    ("tt-int8", params_q)):
        dt, _ = _decode(model, p, prompts, gen, max_len)
        tf[mode] = model_common.teacher_forced_logits(model, p, prompts)
        rows.append((mode, b * (gen - 1) / max(dt, 1e-9), tt_param_bytes(p)))

    lrx, lq = tf["dense"], tf["tt-int8"]
    positions = lrx.shape[0] * lrx.shape[1]
    raw_agree = float(np.mean(np.argmax(lq, -1) == np.argmax(lrx, -1)))
    agree = model_common.tie_tolerant_agreement(lq, lrx, tol=agree_tol)
    # the wide cores are held to a 5x tighter bound — only quantization is
    # allowed to move logits materially (exact argmax would flip on
    # rounding noise at the near-tied positions)
    tt_agree = model_common.tie_tolerant_agreement(
        tf["tt"], lrx, tol=0.2 * agree_tol)
    wide_leaf, dense_leaf = tt_leaf_bytes(params_tt)
    q_leaf, _ = tt_leaf_bytes(params_q)

    print(f"\nTT-quant ({arch} reduced, ε={eps}, int8 cores, batch={b}, "
          f"gen={gen})")
    print(f"{'mode':<12}{'tok/s':>10}{'total bytes':>14}")
    for mode, tps, bytes_ in rows:
        print(f"{mode:<12}{tps:>10.1f}{bytes_:>14,}")
    print(f"TT-served leaves: bf16-TT {wide_leaf:,} -> int8 {q_leaf:,} "
          f"({wide_leaf / q_leaf:.2f}x; vs dense form "
          f"{dense_leaf / q_leaf:.2f}x)")
    print(f"next-token agreement vs dense oracle: {agree:.2%} "
          f"(tie-tolerant, tol {agree_tol:.0%} of logit scale; raw argmax "
          f"{raw_agree:.2%}) over {positions} teacher-forced positions")

    assert tt_agree == 1.0, ("wide-TT logits drifted from dense", tt_agree)
    assert agree >= min_agreement, (agree, min_agreement)
    assert wide_leaf / q_leaf >= min_vs_tt, (wide_leaf, q_leaf, min_vs_tt)
    assert dense_leaf / q_leaf >= min_vs_dense, (
        dense_leaf, q_leaf, min_vs_dense)

    result = {
        "arch": arch, "agreement": agree, "raw_argmax_agreement": raw_agree,
        "agree_tol_frac": agree_tol,
        "positions": positions,
        "tt_leaf_bytes": wide_leaf, "tt_int8_leaf_bytes": q_leaf,
        "dense_leaf_bytes": dense_leaf,
        "leaf_reduction_vs_tt": wide_leaf / q_leaf,
        "leaf_reduction_vs_dense": dense_leaf / q_leaf,
        "modes": {
            mode: {"tok_per_s": tps, "total_bytes": bytes_}
            for mode, tps, bytes_ in rows
        },
    }
    if write_json:
        from benchmarks.record import write_bench
        write_bench("tt_quant", result)
    return result


# one reduced config per architecture family: transformer (dense), encdec,
# ssm (mamba2), hybrid (rglru), and MoE expert banks
FAMILY_ARCHS = (
    "gemma3-1b",
    "seamless-m4t-large-v2",
    "mamba2-1.3b",
    "recurrentgemma-2b",
    "olmoe-1b-7b",
)


def run_families(fast: bool = False, eps: float = 0.2):
    """Coverage lane: TT-native serving across EVERY family in the zoo.

    Each family must (a) pass the shared logit-parity bound against
    reconstruct-then-serve and (b) shrink resident weight bytes vs dense —
    the two asserts inside ``run`` — so a family regressing to
    reconstruct-on-load fails the build, not just a benchmark number."""
    results = [run(fast=fast, arch=arch, eps=eps, write_json=False)
               for arch in FAMILY_ARCHS]
    print("\nTT-native coverage (family sweep)")
    print(f"{'arch':<24}{'max|Δ|':>10}{'agree':>8}{'byte reduction':>16}")
    for r in results:
        print(f"{r['arch']:<24}{r['max_diff']:>10.2e}"
              f"{r['agreement']:>8.0%}"
              f"{r['dense_bytes'] / r['tt_bytes']:>15.2f}x")
    from benchmarks.record import write_bench
    write_bench("tt_serve", {"archs": {r["arch"]: r for r in results}})
    return results


if __name__ == "__main__":
    import sys
    if "--families" in sys.argv:
        run_families(fast="--fast" in sys.argv)
    elif "--quant" in sys.argv:
        run_quant(fast="--fast" in sys.argv)
    else:
        run(fast="--fast" in sys.argv)
