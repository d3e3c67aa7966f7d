"""TTCompressor — the public model-compression API (paper Fig. 1 workflow).

Compresses a pytree of model parameters into TT format for transmission
(the "edge → cloud" direction) and reconstructs on arrival.  This is the
framework-level face of the paper's contribution: a compression policy
decides, per parameter, whether/how to tensorize, and the TT-SVD engine
(two-phase HBD SVD) does the factorization.

Policy defaults follow DESIGN.md §5:
  * params with fewer than ``min_size`` elements are sent raw (routers,
    norms, biases — TT overhead would exceed the payload);
  * matrices/embeddings are re-tensorized with balanced factors
    (TT-Rec-style) to depth >= ``min_dims``;
  * conv kernels (4D) keep their natural dims;
  * a parameter is only kept in TT form if it actually compresses
    (ratio > 1), otherwise raw — same accept/reject the paper's δ-rule
    effectively applies.

Execution plans
---------------
``plan="batched"`` (default) routes compression through the planning pass
(``core/plan.py``): parameters are bucketed by (padded) tensorized shape
and each bucket is decomposed by ONE batched TT-SVD launch
(``core/batch_exec.py``), optionally sharded over a ``launch/mesh.py``
device mesh.  ``plan="serial"`` is the original per-parameter loop — kept
as the escape hatch and as the equivalence oracle the batched path is
tested against: same ε guarantee, and for exact-shape bucket members the
same accept/reject decision and live ranks.  The one intentional
divergence is *padded* members (shapes merged into a larger bucket under
``pad_tolerance``): their cores carry the padded mode dims, so payload
accounting is up to ``pad_tolerance`` larger than serial and the ratio>1
accept/reject is correspondingly more conservative — a padded member near
the break-even point may be sent raw where serial would keep TT.  Set
``pad_tolerance=0`` to disable padding merges and recover strict parity.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import tt as _tt
from repro.core import plan as _plan
from repro.core import batch_exec as _exec


@dataclass
class CompressionPolicy:
    eps: float = 0.05
    min_size: int = 4096            # below this, send raw
    max_factor: int = 64            # balanced tensorization factor cap
    min_dims: int = 3               # tensorize to at least this many dims
    max_rank: Optional[int] = None
    svd_method: str = "two_phase"
    hbd_impl: str = "unblocked"
    plan: str = "batched"           # "batched" | "serial" execution plan
    pad_tolerance: float = 0.25     # max element overhead to join a bucket
    serial_cutoff_elems: int = 1 << 24   # padded-work bound for batching


@dataclass
class CompressedParam:
    kind: str                        # "tt" | "raw"
    tt: Optional[_tt.TTTensor]
    raw: Optional[jax.Array]
    orig_shape: Tuple[int, ...]
    orig_dtype: Any
    # set when the param was zero-padded into a larger bucket: the pre-pad
    # tensorized dims the reconstruction must be cropped back to
    crop_dims: Optional[Tuple[int, ...]] = None

    @property
    def payload_params(self) -> int:
        if self.kind == "tt":
            return self.tt.num_params
        return int(np.prod(self.orig_shape))


@dataclass
class CompressionReport:
    total_params: int
    payload_params: int
    per_param: Dict[str, Tuple[str, int, int]] = field(default_factory=dict)
    plan_fingerprint: Optional[str] = None
    exec_stats: Optional[_exec.ExecStats] = None

    @property
    def ratio(self) -> float:
        return self.total_params / max(self.payload_params, 1)


# single source of truth for raw/TT dim routing, shared with the planner
_tensorize_dims = _plan.tensorize_dims


def compress_param(x: jax.Array, policy: CompressionPolicy) -> CompressedParam:
    shape = tuple(x.shape)
    size = int(np.prod(shape))
    if size < policy.min_size or min(shape or (1,)) == 0:
        return CompressedParam("raw", None, x, shape, x.dtype)
    dims = _tensorize_dims(shape, policy)
    if len(dims) < 2:
        return CompressedParam("raw", None, x, shape, x.dtype)
    tt = _tt.ttd(
        x,
        eps=policy.eps,
        dims=dims,
        svd_method=policy.svd_method,
        hbd_impl=policy.hbd_impl,
        max_rank=policy.max_rank,
    )
    if tt.num_params >= size:                     # reject non-compressions
        return CompressedParam("raw", None, x, shape, x.dtype)
    return CompressedParam("tt", tt, None, shape, x.dtype)


def decompress_param(c: CompressedParam) -> jax.Array:
    if c.kind == "raw":
        return c.raw
    w = _tt.tt_reconstruct(c.tt)
    if c.crop_dims is not None and tuple(c.crop_dims) != tuple(c.tt.shape):
        w = w[tuple(slice(0, d) for d in c.crop_dims)]
    return w.reshape(c.orig_shape).astype(c.orig_dtype)


def _default_mesh():
    from repro.launch.sharding import current_mesh  # lazy: launch imports core
    return current_mesh()


class TTCompressor:
    """Compress/decompress pytrees of parameters for transmission.

    mesh: optional ``launch/mesh.py`` mesh the batched executor shards
    bucket batches over (round-robin on the ``data`` axis); defaults to the
    mesh registered with ``launch.sharding.set_mesh_axis_sizes``, if any.
    """

    def __init__(self, policy: Optional[CompressionPolicy] = None, mesh=None):
        self.policy = policy or CompressionPolicy()
        self.mesh = mesh

    def compress(self, params, plan: Optional[str] = None
                 ) -> Tuple[Any, CompressionReport]:
        mode = plan or self.policy.plan
        if mode not in ("serial", "batched"):
            raise ValueError(f"unknown compression plan: {mode!r}")
        with obs.span("compress.pass"):
            if mode == "serial":
                return self._compress_serial(params)
            return self._compress_batched(params)

    # ---- the original per-param loop: fallback + equivalence oracle ----
    def _compress_serial(self, params) -> Tuple[Any, CompressionReport]:
        leaves, treedef = jax.tree.flatten(params)
        paths = [
            "/".join(str(k) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        ]
        out = []
        report = CompressionReport(total_params=0, payload_params=0)
        for name, leaf in zip(paths, leaves):
            c = compress_param(jnp.asarray(leaf), self.policy)
            out.append(c)
            size = int(np.prod(c.orig_shape))
            report.total_params += size
            report.payload_params += c.payload_params
            report.per_param[name] = (c.kind, size, c.payload_params)
        return jax.tree.unflatten(treedef, out), report

    # ---- the batched planner/executor path ----
    def _compress_batched(self, params) -> Tuple[Any, CompressionReport]:
        leaves, treedef = jax.tree.flatten(params)
        paths = [
            "/".join(str(k) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        ]
        cplan = _plan.build_plan(
            params, self.policy,
            pad_tolerance=self.policy.pad_tolerance,
            serial_cutoff_elems=self.policy.serial_cutoff_elems,
        )
        executor = _exec.BucketExecutor(mesh=self.mesh or _default_mesh())
        results = executor.run(cplan, leaves, self.policy)

        out = [None] * len(leaves)
        for e in cplan.raw:
            x = jnp.asarray(leaves[e.index])
            out[e.index] = CompressedParam("raw", None, x, e.shape, x.dtype)
        for idx, (tt, pre_pad_dims) in results.items():
            x = jnp.asarray(leaves[idx])
            shape = tuple(x.shape)
            size = int(np.prod(shape))
            if tt.num_params >= size:             # reject non-compressions
                out[idx] = CompressedParam("raw", None, x, shape, x.dtype)
            else:
                crop = (tuple(pre_pad_dims)
                        if tuple(pre_pad_dims) != tuple(tt.shape) else None)
                out[idx] = CompressedParam(
                    "tt", tt, None, shape, x.dtype, crop_dims=crop
                )

        report = CompressionReport(
            total_params=0, payload_params=0,
            plan_fingerprint=cplan.fingerprint,
            exec_stats=executor.stats,
        )
        for name, c in zip(paths, out):
            size = int(np.prod(c.orig_shape))
            report.total_params += size
            report.payload_params += c.payload_params
            report.per_param[name] = (c.kind, size, c.payload_params)
        return jax.tree.unflatten(treedef, out), report

    def decompress(self, compressed) -> Any:
        return jax.tree.map(
            decompress_param,
            compressed,
            is_leaf=lambda x: isinstance(x, CompressedParam),
        )
