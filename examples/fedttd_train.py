"""End-to-end driver — the paper's Fig. 1 distributed-learning workflow.

Multiple "pods" (edge nodes) train local replicas of an LM; every
``--sync-every`` steps they exchange parameter deltas in TT format (the
paper's compression direction) with error feedback, and every pod applies
the average.  Demonstrates, end to end:

  * training substrate: model zoo config, synthetic data pipeline, AdamW,
    grad-accumulated sharded train step,
  * the paper's contribution: TT-compressed parameter exchange
    (core.comm_compress / train.fedttd) with payload accounting,
  * fault tolerance: checkpoint every sync round, then a simulated node
    failure + restart that resumes bit-exact from the manifest.

Run (CPU, ~2 min):
  PYTHONPATH=src python examples/fedttd_train.py
Bigger (~100M params — the full-scale single-host variant):
  PYTHONPATH=src python examples/fedttd_train.py --preset 100m --steps 200
"""

from __future__ import annotations

import argparse
import dataclasses
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.comm_compress import CommCompressionConfig
from repro.data import pipeline as data_pipeline
from repro.launch.mesh import batch_axes, make_host_mesh
from repro.models.registry import build
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train import fedttd
from repro.train.steps import TrainState, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eps", type=float, default=0.08)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if args.preset == "100m":
        cfg = dataclasses.replace(
            cfg, num_layers=8, d_model=768, num_heads=12, num_kv_heads=12,
            d_ff=3072, vocab_size=32768, head_dim=None)
    model = build(cfg)
    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree.leaves(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))))
    print(f"[fedttd] arch={args.arch} preset={args.preset} "
          f"params={n_params/1e6:.1f}M pods={args.pods}")

    mesh = make_host_mesh()
    jax.set_mesh(mesh)
    shape = ShapeConfig("fedttd", args.seq, args.batch, "train")
    optimizer = AdamW(learning_rate=cosine_schedule(3e-4, 10, args.steps))
    step_fn = jax.jit(
        make_train_step(model, optimizer, batch_axes=batch_axes(mesh)),
        donate_argnums=(0,))
    comm_cfg = CommCompressionConfig(eps=args.eps, max_rank=32)

    # one independent island per pod: own data shard, own optimizer state
    states, datas = [], []
    for p in range(args.pods):
        params = model.init(jax.random.PRNGKey(args.seed))   # same init
        states.append(TrainState(params=params, opt=optimizer.init(params)))
        datas.append(data_pipeline.for_model(cfg, shape, seed=100 + p))
    fstate = fedttd.init_state([s.params for s in states])

    ckpt_dir = tempfile.mkdtemp(prefix="fedttd_ckpt_")
    ckpt = CheckpointManager(ckpt_dir, keep=2)

    losses = {p: [] for p in range(args.pods)}
    t0 = time.time()
    for step in range(args.steps):
        for p in range(args.pods):
            batch = {k: jnp.asarray(v)
                     for k, v in datas[p].batch_at(step).items()}
            states[p], metrics = step_fn(states[p], batch)
            losses[p].append(float(metrics["loss"]))
        if (step + 1) % args.sync_every == 0:
            synced, fstate = fedttd.sync(
                [s.params for s in states], fstate, comm_cfg)
            states = [s._replace(params=pp)
                      for s, pp in zip(states, synced)]
            ckpt.save(step, states[0])
            print(f"[fedttd] step {step + 1}: synced "
                  f"(payload {fstate.sent_bytes / max(fstate.raw_bytes, 1):.3f}"
                  f"x of dense, losses "
                  + ",".join(f"{losses[p][-1]:.3f}"
                             for p in range(args.pods)) + ")", flush=True)
    wall = time.time() - t0

    # ---- fault tolerance: kill pod 0, restore from checkpoint ------------
    ckpt.wait()
    latest = ckpt.latest_step()
    dead = TrainState(
        params=model.init(jax.random.PRNGKey(99)),     # "rebooted" node
        opt=optimizer.init(model.init(jax.random.PRNGKey(99))))
    restored, manifest = ckpt.restore(dead)
    same = all(
        bool(jnp.allclose(a, b))
        for a, b in zip(jax.tree.leaves(restored.params),
                        jax.tree.leaves(states[0].params)))
    print(f"[fedttd] node-failure drill: restored step {manifest['step']} "
          f"from {ckpt_dir} — params match latest sync: {same}")

    dci = 1 / max(fstate.sent_bytes / max(fstate.raw_bytes, 1), 1e-9)
    print(f"[fedttd] done in {wall:.1f}s: "
          f"loss pod0 {losses[0][0]:.3f} -> {losses[0][-1]:.3f}; "
          f"{fstate.syncs} syncs, DCI payload reduced {dci:.1f}x vs dense")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    assert losses[0][-1] < losses[0][0], "training should reduce loss"


if __name__ == "__main__":
    main()
