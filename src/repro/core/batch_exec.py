"""Bucket execution: one (mesh-sharded) batched TT-SVD launch per bucket.

Consumes the :class:`~repro.core.plan.CompressionPlan` produced by the
planning pass and runs each bucket through ``ttd_static_batched`` — the
vmapped static-shape Algorithm 1 whose per-member results are bit-identical
to serial ``ttd_static`` calls.  The padded cores come back to the host and
are cropped to their live δ-ranks, yielding the same compact ``TTTensor``
payloads the serial loop produces.

Scheduling
----------
* **Round-robin device sharding** — when a ``launch/mesh.py`` mesh is
  supplied, bucket members are assigned to devices round-robin over the
  ``data`` axis: member lists are chunked per device, each device's chunk is
  stacked contiguously, and the stacked batch axis is block-sharded with a
  ``NamedSharding`` — block-of-round-robin-chunks ≡ the round-robin
  assignment.  Results are gathered back to host for cropping.
* **Executable cache** — compiled bucket executables are cached by
  (batch, dims, ε, max-rank, svd method, hbd impl); recurring bucket shapes
  (the common case across checkpoints of the same model) pay JIT once.
* **Serial fallback** — buckets the planner marked ``execution="serial"``
  (padded-rank work estimate too high) run the classic per-param dynamic
  path unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tt as _tt
from repro.core.plan import Bucket, CompressionPlan

# Rank cap standing in for "uncapped" on the static path: tt_max_ranks takes
# the min with the theoretical ranks, so any large value means "exact".
_UNCAPPED = 1 << 30


@dataclass
class ExecStats:
    """Dispatch accounting for the batched vs serial execution paths."""

    bucket_launches: int = 0          # batched executables actually launched
    serial_params: int = 0            # params routed through the serial loop
    serial_dispatches: int = 0        # SVD dispatches those serial params cost
    batched_params: int = 0           # params decomposed inside bucket launches
    serial_equiv_dispatches: int = 0  # what the all-serial loop would have cost
    cache_hits: int = 0
    compiles: int = 0
    per_bucket: List[Dict] = field(default_factory=list)

    @property
    def total_dispatches(self) -> int:
        return self.bucket_launches + self.serial_dispatches

    @property
    def dispatch_reduction(self) -> float:
        return self.serial_equiv_dispatches / max(self.total_dispatches, 1)


def round_robin_chunks(n: int, ndev: int) -> List[List[int]]:
    """Member indices per device under round-robin assignment.

    Deterministic: member i goes to device ``i % ndev``.  Chunks are padded
    (with -1 sentinels) to equal length so the concatenated batch axis can
    be block-sharded — block-of-chunks realizes exactly this assignment.
    """
    ndev = max(1, ndev)
    chunks = [[i for i in range(n) if i % ndev == d] for d in range(ndev)]
    chunk_len = max((len(c) for c in chunks), default=0)
    for c in chunks:
        c.extend([-1] * (chunk_len - len(c)))
    return chunks


# module-level so repeated compressor instances share compiled executables
_EXEC_CACHE: Dict[Tuple, object] = {}


class BucketExecutor:
    """Runs a CompressionPlan's buckets; returns per-leaf TTTensors."""

    def __init__(self, mesh=None, data_axis: str = "data"):
        self.mesh = mesh
        self.data_axis = data_axis
        self.stats = ExecStats()

    # -- executable cache -------------------------------------------------
    def _compiled(self, stacked: jax.Array, policy):
        """AOT-compiled bucket executable, cached by (batch shape, policy).

        ``ttd_static_batched.lower(...).compile()`` bakes the static policy
        args and the input aval (including its sharding) into an XLA
        executable; recurring bucket shapes — the common case across
        checkpoints of the same model — skip lower+compile entirely on
        later launches.
        """
        statics = dict(
            eps=float(policy.eps),
            max_rank=(policy.max_rank if policy.max_rank is not None
                      else _UNCAPPED),
            svd_method=policy.svd_method,
            hbd_impl=policy.hbd_impl,
        )
        key = (
            stacked.shape, str(stacked.sharding), self._ndev(),
            tuple(sorted(statics.items())),
        )
        fn = _EXEC_CACHE.get(key)
        if fn is None:
            fn = _tt.ttd_static_batched.lower(stacked, **statics).compile()
            _EXEC_CACHE[key] = fn
            self.stats.compiles += 1
        else:
            self.stats.cache_hits += 1
        return fn

    # -- device placement -------------------------------------------------
    def _ndev(self) -> int:
        if self.mesh is None:
            return 1
        from repro.launch.mesh import data_axis_size  # lazy: launch imports core
        return data_axis_size(self.mesh, self.data_axis)

    def _place(self, stacked: jax.Array) -> jax.Array:
        """Block-shard the batch axis over the data axis (no-op off-mesh)."""
        ndev = self._ndev()
        if ndev <= 1 or stacked.shape[0] % ndev != 0:
            return stacked
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(self.data_axis, *([None] * (stacked.ndim - 1)))
        return jax.device_put(stacked, NamedSharding(self.mesh, spec))

    # -- bucket execution --------------------------------------------------
    def run_bucket(
        self, bucket: Bucket, leaves: List, policy
    ) -> List[Tuple[int, _tt.TTTensor, Tuple[int, ...]]]:
        """Decompose one bucket; returns (leaf_index, tt, pre_pad_dims)."""
        d = len(bucket.dims)
        if bucket.execution == "serial" or d < 2:
            out = []
            for m in bucket.members:
                tt = _tt.ttd(
                    leaves[m.index], eps=policy.eps, dims=list(m.dims),
                    svd_method=policy.svd_method, hbd_impl=policy.hbd_impl,
                    max_rank=policy.max_rank,
                )
                out.append((m.index, tt, m.dims))
            self.stats.serial_params += len(bucket.members)
            self.stats.serial_dispatches += len(bucket.members) * max(d - 1, 1)
            return out

        # round-robin member→device chunks, zero-padding ragged tails
        chunks = round_robin_chunks(bucket.batch, self._ndev())
        order = [i for chunk in chunks for i in chunk]
        mats = []
        for i in order:
            if i < 0:
                mats.append(np.zeros(bucket.dims, np.float32))
                continue
            m = bucket.members[i]
            x = np.asarray(
                jax.device_get(leaves[m.index]), np.float32
            ).reshape(m.dims)
            if m.dims != bucket.dims:
                x = np.pad(x, [(0, t - c) for c, t in zip(m.dims, bucket.dims)])
            mats.append(x)
        stacked = self._place(jnp.asarray(np.stack(mats)))

        fn = self._compiled(stacked, policy)
        batched = fn(stacked)                       # ONE launch per bucket
        self.stats.bucket_launches += 1
        self.stats.batched_params += bucket.batch
        self.stats.per_bucket.append({
            "dims": bucket.dims, "batch": bucket.batch,
            "launch_batch": len(order), "devices": self._ndev(),
        })

        out = []
        for pos, i in enumerate(order):
            if i < 0:
                continue
            m = bucket.members[i]
            member = _tt.static_tt_member(batched, pos)
            tt = _tt.static_tt_crop(member, eps=policy.eps)
            out.append((m.index, tt, m.dims))
        return out

    def run(self, plan: CompressionPlan, leaves: List, policy):
        """Execute every bucket; returns {leaf_index: (tt, pre_pad_dims)}."""
        results: Dict[int, Tuple[_tt.TTTensor, Tuple[int, ...]]] = {}
        for bucket in plan.buckets:
            for idx, tt, pre_pad in self.run_bucket(bucket, leaves, policy):
                results[idx] = (tt, pre_pad)
            self.stats.serial_equiv_dispatches += (
                bucket.batch * max(len(bucket.dims) - 1, 1)
            )
        return results
