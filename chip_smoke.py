"""Bring-up check of the main path on a TPU, at qwen1.5-0.5b's published widths.

    python chip_smoke.py [--seed 0]        # one chip: compress, then serve
    python chip_smoke.py --chips 4         # model=4 serving mesh vs one chip

One chip, in one process that holds it for the whole run:

  0. weights  ``model.init`` from ``--seed``, then ``spectral_decay_pytree``
              (a host SVD per large leaf: random init is incompressible).
  1. compress ``TTCompressor(CompressionPolicy(eps=0.2, min_size=8192))`` —
              the policy ``launch/serve.py`` uses — batched and serial
              two-phase HBD TT-SVD on the device; every TT leaf's relative
              Frobenius error, computed on the chip, must be <= eps.
  2. TT       ``tt_native_params`` behind ``Engine`` (4 slots) -> ``Router``
              -> HTTP; 8 greedy ``POST /v1/generate`` requests must equal an
              isolated ``generate`` of the same prompt at the engine's batch
              shape token for token (batch 1 is printed beside it, not
              checked), and teacher-forced TT logits must meet
              ``logit_parity``'s bound against reconstruct-then-serve.
  3. TT-int8  the same payload through ``quantize_tt_tree``, the same
              requests, and the >= 99% tie-tolerant agreement gate.
  4. kernels  how many TT leaf dispatches took each fused kernel, and which
              took the unfused chain and why.

``--chips 4`` runs only the model-parallel serving path (``make_host_mesh
(model_parallel=4)`` + ``sharding.place_params``) at the same full size:
the dense weights first, then the TT form of the same weights, compressed
on device 0 (the per-leaf check is phase 1's, not repeated), each run on
the mesh and on one chip with the same prompts.  Greedy tokens must be
equal, and teacher-forced logits over the one-chip greedy sequences must
meet ``logit_parity``'s bound; each device's bytes in use are printed.

Every phase prints one line with its wall and compile seconds; those are
set-up facts of this run, not measurements.  The last line of stdout is
``{"ok": true, "device": {...}}`` only when every check passed; any failed
check or exception exits non-zero.  Without a TPU it exits 2 at once.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen1.5-0.5b"
EPS = 0.2
SLOTS = 4
CHUNK = 8
# (prompt_len, gen) per request: three prompt lengths and two budgets keep
# the isolated references at five compiled programs per weight form
REQUESTS = [(16, 32), (128, 16), (64, 32), (16, 16),
            (128, 32), (64, 16), (16, 32), (128, 16)]
MAX_LEN = max(p + g for p, g in REQUESTS)
TF_SHAPE = (8, 33)          # teacher-forced batch: 8 x 32 = 256 positions
MESH_PROMPT, MESH_GEN = 16, 16


class Smoke:
    """Phase timing, compile-time accounting and the failure list."""

    def __init__(self):
        import jax

        self.failures = []
        self._compile_s = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._compile_s += secs

    def run(self, name, fn, *args):
        """Run one phase; returns its result, or None after an exception."""
        t0, c0 = time.perf_counter(), self._compile_s
        try:
            out = fn(*args)
        except Exception:                    # reported, and fails the run
            traceback.print_exc()
            self.failures.append(f"{name}: exception")
            out = None
        print(f"[{name}] {time.perf_counter() - t0:.1f} s wall, "
              f"{self._compile_s - c0:.1f} s compile", flush=True)
        return out

    def check(self, ok: bool, what: str) -> None:
        print(f"  {'PASS' if ok else 'FAIL'}: {what}", flush=True)
        if not ok:
            self.failures.append(what)


def make_weights(model, seed):
    import jax

    from repro.core import spectral_decay_pytree

    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return jax.block_until_ready(spectral_decay_pytree(params))


def leaf_error(c, x) -> float:
    """||W - W_R||_F / ||W||_F of one TT leaf, on the device, in f32."""
    import jax.numpy as jnp

    from repro.core import tt_reconstruct

    w = jnp.asarray(x, jnp.float32)
    r = tt_reconstruct(c.tt)
    if c.crop_dims is not None:
        r = r[tuple(slice(0, d) for d in c.crop_dims)]
    r = r.reshape(w.shape)
    norm = float(jnp.linalg.norm(w))
    diff = float(jnp.linalg.norm(w - r))
    return diff / norm if norm > 0 else diff     # all-zero leaf: absolute


def compress(smoke, params, check=True):
    import jax

    from repro.core import CompressionPolicy, TTCompressor
    from repro.core.compression import CompressedParam
    from repro.core.plan import build_plan

    policy = CompressionPolicy(eps=EPS, min_size=8192)
    payload, report = TTCompressor(policy).compress(params)
    for line in build_plan(params, policy).describe().splitlines():
        print(f"  {line}")
    st = report.exec_stats
    print(f"  launches: {st.bucket_launches} batched ({st.batched_params} "
          f"leaves), {st.serial_dispatches} serial SVD dispatches "
          f"({st.serial_params} leaves); payload ratio {report.ratio:.2f}x "
          f"({report.total_params:,} -> {report.payload_params:,} params)")
    if not check:
        return payload
    flat_c = jax.tree_util.tree_flatten_with_path(
        payload, is_leaf=lambda x: isinstance(x, CompressedParam))[0]
    flat_x = jax.tree.leaves(params)
    errs = []
    for (path, c), x in zip(flat_c, flat_x):
        if c.kind != "tt":
            continue
        err = leaf_error(c, x)
        errs.append(err)
        print(f"  {jax.tree_util.keystr(path)} dims={tuple(c.tt.shape)} "
              f"ranks={tuple(c.tt.ranks)} rel_err={err:.4f}")
    smoke.check(all(e <= EPS for e in errs),          # NaN fails too
                f"{len(errs)} TT leaves, rel. Frobenius error <= {EPS} each "
                f"(worst {max(errs, default=0.0):.4f})")
    return payload


def make_requests(cfg, seed):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, (p,), np.int32), g)
            for p, g in REQUESTS]


def isolated(model, params, reqs, batch=SLOTS):
    """Each request alone through ``engine.generate`` at the engine's cache
    length, the prompt in each of ``batch`` rows.  At ``batch=SLOTS`` the
    program has the engine's shapes, so only scheduling can differ."""
    from repro.launch.engine import generate

    return [generate(model, params, np.repeat(p[None], batch, 0), g,
                     max_len=MAX_LEN)["gen"][0] for p, g in reqs]


def _n_equal(a, b) -> int:
    return sum(np.array_equal(x, y) for x, y in zip(a, b))


def _post(port, prompt, gen):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    conn.request("POST", "/v1/generate",
                 json.dumps({"prompt": prompt.tolist(), "gen": gen}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    if resp.status != 200:
        raise RuntimeError(f"HTTP {resp.status}: {body}")
    return np.asarray(body["tokens"], np.int32)


def serve_http(model, params, reqs):
    """All requests at once through Engine -> Router -> HTTP, so they
    stagger over the slots; returns their tokens in request order."""
    from repro.launch.engine import Engine
    from repro.launch.router import Router
    from repro.launch.server import serve_in_thread

    engine = Engine(model, params, slots=SLOTS, max_len=MAX_LEN,
                    chunk_steps=CHUNK)
    server, shutdown = serve_in_thread(Router([engine], queue_depth=16))
    out, errors = [None] * len(reqs), []

    def one(i):
        try:
            out[i] = _post(server.port, *reqs[i])
        except Exception as e:               # collected, fails the phase
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(reqs))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        shutdown()
    if errors:
        raise RuntimeError(f"requests failed: {errors}")
    print(f"  engine: {engine.steps} fused steps, occupancy "
          f"{engine.occupancy:.2%}")
    return out


def check_tokens(smoke, got, want, label):
    same = [np.array_equal(a, b) for a, b in zip(got, want)]
    for i, ((p, g), ok) in enumerate(zip(REQUESTS, same)):
        if not ok:
            print(f"  request {i} (prompt {p}, gen {g}): http {got[i][:8]}"
                  f"... vs isolated {want[i][:8]}...")
    smoke.check(all(same), f"{label}: {sum(same)}/{len(same)} HTTP requests "
                f"token-equal to isolated runs")


def serve_tt(smoke, model, params_tt, params_rx, reqs, tf_prompts):
    from repro.models.common import logit_parity, teacher_forced_logits

    got = serve_http(model, params_tt, reqs)
    ref = isolated(model, params_tt, reqs)
    check_tokens(smoke, got, ref, "TT")
    # not a check: whether batch 1 computes each row as batch SLOTS does
    ref1 = isolated(model, params_tt, reqs, batch=1)
    print(f"  TT isolated at batch 1 vs batch {SLOTS}: {_n_equal(ref1, ref)}"
          f"/{len(reqs)} requests token-equal; HTTP vs batch 1: "
          f"{_n_equal(got, ref1)}/{len(reqs)}")
    tf_tt = teacher_forced_logits(model, params_tt, tf_prompts)
    tf_rx = teacher_forced_logits(model, params_rx, tf_prompts)
    d, scale, agree = logit_parity(tf_tt, tf_rx)
    bound = max(0.05 * scale, 1e-3)
    smoke.check(d <= bound, f"TT vs reconstruct-then-serve teacher-forced "
                f"logits: max|d| {d:.3e} <= {bound:.3e} (argmax agreement "
                f"{agree:.2%} over {tf_rx.shape[0] * tf_rx.shape[1]} "
                f"positions)")
    return tf_rx


def serve_int8(smoke, model, params_tt, reqs, tf_prompts, tf_rx):
    from repro.core import quantize_tt_tree
    from repro.models.common import teacher_forced_logits, tie_tolerant_agreement

    params_q = quantize_tt_tree(params_tt)
    got = serve_http(model, params_q, reqs)
    check_tokens(smoke, got, isolated(model, params_q, reqs), "TT-int8")
    agree = tie_tolerant_agreement(
        teacher_forced_logits(model, params_q, tf_prompts), tf_rx)
    smoke.check(agree >= 0.99, f"TT-int8 tie-tolerant teacher-forced "
                f"agreement {agree:.2%} >= 99% over "
                f"{tf_rx.shape[0] * tf_rx.shape[1]} positions")


def report_kernels(smoke):
    from repro.kernels.tt_contract.ops import dispatch_tally

    tally = dispatch_tally()
    fused = sum(n for (route, _), n in tally.items() if route != "unfused")
    unfused = {reason: n for (route, reason), n in tally.items()
               if route == "unfused"}
    for (route, reason), n in sorted(tally.items()):
        print(f"  {route}{f' ({reason})' if reason else ''}: {n}")
    print(f"  TT leaf dispatches: {fused} fused, "
          f"{sum(unfused.values())} unfused {unfused or ''}")
    smoke.check(fused > 0, "the fused tt_contract kernels served TT leaves")


def device_bytes(params):
    """Bytes of ``params`` resident on each device."""
    import jax

    out = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def mesh_phase(smoke, model, cfg, name, p, prompts):
    """One weight form on the model=4 serving mesh vs one chip."""
    import jax

    from repro.launch import sharding as shd
    from repro.launch.engine import generate
    from repro.launch.mesh import make_host_mesh
    from repro.models.common import logit_parity, teacher_forced_logits

    mesh = make_host_mesh(model_parallel=4)
    shd.set_mesh_axis_sizes(mesh)
    one = generate(model, p, prompts, MESH_GEN)["gen"]
    # teacher-force the one-chip greedy sequences on both: logits at
    # every step of the trajectory the token check follows
    seqs = np.concatenate([prompts, one], 1)
    tf_one = teacher_forced_logits(model, p, seqs)
    with jax.set_mesh(mesh):
        placed = shd.place_params(p, cfg, mesh)
        per_dev = device_bytes(placed)
        four = generate(model, placed, prompts, MESH_GEN)["gen"]
        tf_four = teacher_forced_logits(model, placed, seqs)
    print(f"  {name}: param bytes per device "
          f"{dict(sorted(per_dev.items()))}")
    print(f"  {name}: bytes_in_use per device "
          f"{[(d.memory_stats() or {}).get('bytes_in_use') for d in jax.devices()]}")
    del placed
    d, scale, agree = logit_parity(tf_four, tf_one)
    bound = max(0.05 * scale, 1e-3)
    smoke.check(d <= bound, f"{name}: model=4 vs one chip teacher-forced "
                f"logits: max|d| {d:.3e} <= {bound:.3e} (argmax agreement "
                f"{agree:.2%} over {tf_one.shape[0] * tf_one.shape[1]} "
                f"positions)")
    first = np.argwhere(one != four)
    where = ""
    if first.size:
        row, step = first[0]
        pos = tf_one[row, MESH_PROMPT - 1 + step]
        top2 = np.sort(pos)[-2:]
        dpos = np.abs(tf_four[row, MESH_PROMPT - 1 + step] - pos).max()
        where = (f"; first difference at (row, step) ({row}, {step}), "
                 f"where one chip's top-2 logit gap is "
                 f"{top2[1] - top2[0]:.3e} and max|d| {dpos:.3e}")
    smoke.check(bool(np.array_equal(one, four)),
                f"{name}: model=4 greedy tokens equal one chip "
                f"({float(np.mean(one == four)):.2%} of {one.size}{where})")
    smoke.check(len(per_dev) == 4 and min(per_dev.values()) > 0,
                f"{name}: weights spread over 4 devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import get_config

    return run(args, get_config(ARCH))


def run(args, cfg) -> int:
    """All phases for ``cfg`` on the devices JAX sees; returns the exit
    code and prints the result line on success."""
    import jax

    from repro.core import TTCompressor
    from repro.kernels.tt_contract.ops import reset_dispatch_tally
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.common import tt_native_params
    from repro.models.registry import build

    cache = enable_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    print(f"chip_smoke: {cfg.name} (d_model {cfg.d_model}, vocab "
          f"{cfg.vocab_size}, {cfg.num_layers} layers) on {len(devices)} x "
          f"{d0.device_kind}; compile cache {cache}", flush=True)
    smoke = Smoke()
    model = build(cfg)
    with jax.default_device(d0):
        params = smoke.run("weights", make_weights, model, args.seed)
        reqs = make_requests(cfg, args.seed)
        if params is not None and args.chips == 4:
            prompts = np.stack([p[:MESH_PROMPT] for p, _ in reqs])
            smoke.run("mesh-dense", mesh_phase, smoke, model, cfg, "dense",
                      params, prompts)
        payload = (None if params is None
                   else smoke.run("compress", compress, smoke, params,
                                  args.chips == 1))
        if payload is not None and args.chips == 4:
            smoke.run("mesh-tt", mesh_phase, smoke, model, cfg, "tt",
                      tt_native_params(payload, family=cfg.family), prompts)
        elif payload is not None:
            reset_dispatch_tally()
            params_tt = tt_native_params(payload, family=cfg.family)
            params_rx = TTCompressor().decompress(payload)
            tf_prompts = np.random.default_rng(args.seed + 1).integers(
                0, cfg.vocab_size, TF_SHAPE, np.int32)
            tf_rx = smoke.run("serve-tt", serve_tt, smoke, model, params_tt,
                              params_rx, reqs, tf_prompts)
            if tf_rx is not None:
                smoke.run("serve-tt-int8", serve_int8, smoke, model,
                          params_tt, reqs, tf_prompts, tf_rx)
            smoke.run("kernels", report_kernels, smoke)
    if smoke.failures:
        print(f"chip_smoke: FAILED {smoke.failures}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
