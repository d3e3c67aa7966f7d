"""Arithmetic shared by the per-layer metric readers in ``bench/metrics``.
Each returns None when the run holds nothing to read."""

from __future__ import annotations

from bench.harness import flops
from bench.harness.peaks import peaks_for

# device operations that are the fused TT-contraction kernels
TT_KERNEL_MARKERS = ("tt_contract", "_tt2_kernel", "_tt3_kernel")


def idle_pct(data):
    tr = data.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def occupancy_pct(data):
    steps = data.counters.get("steps")
    if not steps:
        return None
    return 100.0 * data.counters["slot_steps"] / (steps * data.facts["slots"])


def step_ms(data):
    tr, steps = data.trace, data.counters.get("steps")
    if not tr or not steps:
        return None
    return 1e3 * tr["busy_s"] / steps


def decode_mfu_pct(data):
    tr, tokens = data.trace, data.counters.get("slot_steps")
    ctx = data.facts.get("context")
    if not tr or not tokens or not ctx:
        return None
    s = data.facts["sizes"]
    per_tok = flops.decode_flops_per_token(
        data.facts["chains"], s["L"], s["H"], s["Dh"], s["D"], s["V"], ctx)
    rate = tokens / tr["window_s"] * per_tok
    return 100.0 * rate / peaks_for(data.device_kind).bf16_flops


def is_tt_kernel(name: str) -> bool:
    return any(m in name for m in TT_KERNEL_MARKERS)


def tt_roofline(data):
    """(percent, bound) of the fused TT kernels over the traced window."""
    tr, steps = data.trace, data.counters.get("steps")
    if not tr or not steps or data.facts.get("unfused"):
        return None
    kernel_s = sum(s for n, s in tr["ops_s"].items() if is_tt_kernel(n))
    if kernel_s <= 0:
        return None
    pk = peaks_for(data.device_kind)
    least, bound = 0.0, {}
    for shapes, split in data.facts["chains"]:
        cost = flops.tt_chain_cost(data.facts["slots"], shapes, split)
        t, b = flops.least_time(cost, pk.bf16_flops, pk.hbm_bytes)
        least += t
        bound[b] = bound.get(b, 0.0) + t
    least *= steps * data.facts["sizes"]["L"]
    return 100.0 * least / kernel_s, max(bound, key=bound.get)


def launches_per_pass(data):
    passes = data.counters.get("passes")
    if not passes:
        return None
    return data.counters["launches"] / passes
