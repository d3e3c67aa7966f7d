"""Compression cells: whole-weight-set ``TTCompressor.compress`` passes back
to back on weights resident on the device.

Set-up draws the weights on the device and runs warm-up passes, which
compile every bucket program and every slice the crop takes.  Each timed
pass starts from the device-resident weights and ends with
``block_until_ready`` on every core.  Afterwards every pass's payload must
equal, bit for bit, the payload of one pass drawn from the seed, and that
payload is held to the policy's guarantee (relative Frobenius error at most
eps per leaf), to the ranks the float64 TT-SVD truncated at eps gives, and
to the float64 TT-SVD of the same weights at the same ranks.  With a
control set, the reference TT-SVD in a lower precision stands in the
payload's place.
"""

from __future__ import annotations

import time

import numpy as np

from bench.harness import env
from bench.harness.run_data import RunData
from bench.harness.tracing import TracedWindow, annotate, restore

SPANS = ("pass", "plan", "bucket", "crop")


def _arrays(payload):
    """Every device array of a payload, leaf by leaf."""
    out = []
    for name in sorted(payload):
        c = payload[name]
        out.append((name, list(c.tt.cores) if c.kind == "tt" else [c.raw]))
    return out


def run(ctx) -> dict:
    import jax

    from repro.core import TTCompressor
    from repro.core import batch_exec, plan as plan_mod, tt as tt_mod

    cfg, mix, mod = ctx.config, ctx.traffic, ctx.config_module
    weights = mod.make_weights(cfg, ctx.seed)
    comp = TTCompressor(mod.policy(cfg))

    def one_pass():
        with jax.profiler.TraceAnnotation("pass"):
            payload, report = comp.compress(weights)
            jax.block_until_ready([a for _, arrs in _arrays(payload)
                                   for a in arrs])
        st = report.exec_stats
        return payload, st.bucket_launches + st.serial_dispatches

    for _ in range(mix["warm_passes"]):
        one_pass()
    data = RunData(ctx.workload, ctx.seed, cfg, mix,
                   device_kind=ctx.devices[0].device_kind)
    undo: list = []
    if ctx.trace:
        annotate(plan_mod, "build_plan", "plan", undo)
        annotate(batch_exec.BucketExecutor, "run_bucket", "bucket", undo)
        annotate(tt_mod, "static_tt_crop", "crop", undo)
    payloads, launches = [], []
    try:
        t0 = time.monotonic()
        setup_s = t0 - ctx.t_start
        compiles0 = ctx.compiles.count
        t_end = t0 + ctx.seconds
        traced = False
        while True:
            now = time.monotonic()
            if now >= t_end:
                break
            if (ctx.trace and not traced
                    and now >= t0 + mix["trace_at"] * ctx.seconds):
                traced = True
                n0 = len(payloads)
                with TracedWindow(SPANS) as tw:
                    t_tr = time.monotonic() + mix["trace_seconds"]
                    while time.monotonic() < t_tr:
                        p, n = one_pass()
                        payloads.append(p)
                        launches.append(n)
                data.trace = tw.result
                data.counters["passes"] = len(payloads) - n0
                data.counters["launches"] = sum(launches[n0:])
                continue
            p, n = one_pass()
            payloads.append(p)
            launches.append(n)
        elapsed = time.monotonic() - t0
        compiles = ctx.compiles.count - compiles0
    finally:
        restore(undo)
    device = env.device_record(ctx.devices)

    # correctness: every pass equals the sampled one; that one against the
    # reference
    rng = np.random.default_rng(ctx.seed)
    pick = int(rng.integers(len(payloads)))
    ref_arrays = [(name, [np.asarray(a) for a in arrs])
                  for name, arrs in _arrays(payloads[pick])]
    differing = 0
    for p in payloads:
        got = _arrays(p)
        same = len(got) == len(ref_arrays) and all(
            n1 == n2 and len(a1) == len(a2)
            and all(np.array_equal(np.asarray(x), y) for x, y in zip(a1, a2))
            for (n1, a1), (n2, a2) in zip(got, ref_arrays))
        differing += not same
    host_w = {k: np.asarray(v) for k, v in weights.items()}
    eps = cfg["policy"]["eps"]
    leaves = mod.payload_leaves(payloads[pick])
    raw_diff = sum(
        not np.array_equal(np.asarray(payloads[pick][k].raw), host_w[k])
        for k in payloads[pick] if payloads[pick][k].kind == "raw")
    per_leaf = {name: mod.leaf_readings(host_w[name], leaf, eps)
                for name, leaf in leaves.items()}

    def worst(rows, key):
        return max((r[key] for r in rows.values()), default=0.0)

    def ranks_off(rows):
        return int(sum(r["ranks_differ"] for r in rows.values()))

    readings = {"eps_error": worst(per_leaf, "eps_error"),
                "ref_deviation": worst(per_leaf, "ref_deviation"),
                "ranks_differing": ranks_off(per_leaf)}
    control = getattr(ctx, "control", None)
    if control:
        # the control in the program's place: the reference TT-SVD at
        # ``control`` significant bits, over the dims the program chose
        cp = mod.control_payload(
            cfg, host_w, {k: v["dims"] for k, v in leaves.items()},
            int(control))
        per_leaf = {name: mod.leaf_readings(host_w[name], leaf, eps)
                    for name, leaf in cp.items()}
        readings.update(control_eps_error=worst(per_leaf, "eps_error"),
                        control_deviation=worst(per_leaf, "ref_deviation"),
                        control_ranks_differing=ranks_off(per_leaf))
    lim = cfg["limits"]
    checks = [
        ("eps_error", worst(per_leaf, "eps_error"), "<=", eps),
        ("ref_deviation", worst(per_leaf, "ref_deviation"), "<=",
         lim["ref_deviation"]),
        ("ranks_differing", ranks_off(per_leaf), "<=", 0),
        ("passes_differing", differing, "<=", 0),
        ("raw_leaves_changed", raw_diff, "<=", 0),
        ("tt_leaves", len(per_leaf), ">=", lim["tt_leaves_min"]),
        ("window_compilations", compiles, "<=", 0),
    ]
    data.counters.setdefault("passes", len(payloads))
    data.counters.setdefault("launches", sum(launches))
    notes = [f"window: {len(payloads)} passes in {elapsed:.3f} s, "
             f"{launches[-1] if launches else 0} launches per pass",
             f"compilations inside the window: {compiles}",
             f"TT leaves: {len(per_leaf)}"
             + (f" (the control's, at {control} bits)" if control else "")
             + ", worst eps error " + max(
                 per_leaf, key=lambda k: per_leaf[k]["eps_error"],
                 default="-")
             + ", worst deviation " + max(
                 per_leaf, key=lambda k: per_leaf[k]["ref_deviation"],
                 default="-")]
    e2e = {"setup_s": setup_s}
    if payloads:
        e2e["compress_ms"] = elapsed / len(payloads) * 1e3
    return {"e2e": e2e, "attempted": len(payloads), "failed": differing,
            "checks": checks, "data": data, "device": device,
            "notes": notes, "readings": readings}
