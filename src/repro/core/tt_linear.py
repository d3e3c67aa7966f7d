"""TTLinear — apply a dense layer straight from its TT cores.

The paper's Fig. 1 receiving node reconstructs TT-shipped weights (eq.
(1)/(2) chained contractions) and then serves.  But those contractions ARE a
factored matmul: instead of materializing W = G_1 ×₁ … ×₁ G_N once
(O(∏ n_k) bytes resident for the model's lifetime), the forward pass can
contract the activation through the cores per token — the TT-layer
formulation of Novikov et al. (surveyed in Liu & Parhi, arXiv 2304.13539)
and the storage/bandwidth-bound serving mode of the TT-LLM accelerator work
(arXiv 2501.19135).  On memory-bound decode, weight bytes *are* the decode
latency, so shipping cores instead of dense weights is both the memory and
the speed win.

Quantized storage (int8, int4-ready)
------------------------------------
Decode is memory-bound, so the cores' *storage* bytes are the decode
latency.  ``quantize_tt`` rounds every core to a symmetric integer grid —
one scale per leading-rank row of each core (absmax calibration), one scale
per lead ROW (per layer, and per (layer, expert) for expert banks) — and
the fused kernels dequantize *inside* the contraction: HBM streams int8,
the MXU computes f32, and each core's row scales multiply the f32 partial
product that meets that core (``kernels/tt_contract`` q-variants).  Row
scales, not one scale per core: TT-SVD leaves a decaying singular spectrum
along the rank rows, and a single absmax grid rounds the small rows away
(tie-tolerant agreement fell to 84% at qwen1.5-0.5b's widths on a v5e).
The wide form of a stored core never exists outside a VMEM tile; the only
wide intermediate is the per-layer lead-absorbed first core, which is
transient activation-sized traffic (and ``r_s``× smaller than the core it
absorbs).  With round-to-nearest the absolute error per element is at most
``scale/2 = absmax/(2·qmax)`` of its row — ``≲ 0.2%`` of the row's dynamic
range for int8.

Representation
--------------
A ``TTLinear`` wraps one (optionally layer-stacked) weight:

  * ``lead``  — ``(L, r_s)`` per-layer boundary vectors: the layer-stack
                modes of the joint TT contracted at every concrete layer
                index (host-side, at conversion).  ``None`` for unstacked
                weights.  Inside a ``lax.scan`` over layers, selecting
                ``lead[l]`` is a tiny gather — the *shared* in/out cores
                stay closure constants, so HLO size remains depth-
                independent and cores are never duplicated per layer.
  * ``cores`` — the remaining input/output cores, shared by every layer.
  * ``split`` — how many of ``cores`` are input cores (contracted against
                the activation); the rest expand the output modes.
  * ``experts`` — MoE expert banks keep one extra lead mode: the stacked
                lead table is ``(L, E, r_s)`` and ``select_layer`` yields
                ``(E, r_s)`` — a per-expert family of chains over the SAME
                shared cores, applied by ``tt_apply_experts`` through the
                expert-batched kernel path (``tt_contract_batched``).

``tt_apply`` runs the lead-absorbed chain through the fused Pallas kernels
(``kernels/tt_contract``), falling back to the einsum chain for deep TTs.
Because the contraction order matches ``tt_reconstruct`` exactly, TT-native
logits equal reconstruct-then-serve logits to numerical precision — well
inside the compression ε bound.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tt as _tt


@dataclass
class TTLinear:
    lead: Optional[jax.Array]        # (L[, E], r_s) stacked | ([E,] r_s) | None
    cores: List[jax.Array]           # [g (r,n,s), ...]; cores[0] r == r_s
    split: int                       # number of input cores
    in_shape: Tuple[int, ...]        # dense-weight input dims, e.g. (D,)
    out_shape: Tuple[int, ...]       # dense-weight output dims, e.g. (H, K)
    dtype: Any = jnp.bfloat16        # activation dtype of the dense original
    experts: Optional[int] = None    # expert-bank size E (extra lead mode
                                     # kept as a batch axis at apply time)
    scales: Optional[List[jax.Array]] = None   # per-core (r,) f32 dequant
                                     # scales, one per leading-rank row
                                     # (None = wide storage)
    lead_scale: Optional[jax.Array] = None     # per-lead-row f32 scales:
                                     # (L,) stacked / (L, E) experts / ()

    @property
    def quantized(self) -> bool:
        """True when the cores are stored on an integer grid."""
        return self.scales is not None

    @property
    def stacked(self) -> bool:
        """True while the per-layer lead table still carries its L axis."""
        if self.lead is None:
            return False
        return self.lead.ndim == (3 if self.experts else 2)

    @property
    def num_layers(self) -> Optional[int]:
        if self.stacked:
            return int(self.lead.shape[0])
        return None

    @property
    def payload_params(self) -> int:
        n = sum(int(np.prod(c.shape)) for c in self.cores)
        if self.lead is not None:
            n += int(np.prod(self.lead.shape))
        return n


def _ttl_flatten(t: TTLinear):
    return (
        (t.lead, t.cores, t.scales, t.lead_scale),
        (t.split, t.in_shape, t.out_shape, jnp.dtype(t.dtype).name,
         t.experts),
    )


def _ttl_unflatten(aux, kids):
    split, in_shape, out_shape, dtype, experts = aux
    return TTLinear(
        lead=kids[0], cores=kids[1], split=split,
        in_shape=in_shape, out_shape=out_shape, dtype=jnp.dtype(dtype),
        experts=experts, scales=kids[2], lead_scale=kids[3],
    )


jax.tree_util.register_pytree_node(TTLinear, _ttl_flatten, _ttl_unflatten)


def is_tt_linear(x) -> bool:
    return isinstance(x, TTLinear)


def select_layer(t: TTLinear, idx) -> TTLinear:
    """Layer ``idx``'s view of a stacked TTLinear: gather its lead vector
    (``idx`` may be traced — this is what runs inside the layer scan);
    cores are shared and pass through untouched.

    Out-of-range ``idx`` is pinned to CLAMP (``mode="clip"``): a traced
    index beyond the stack returns the last layer's lead instead of jnp's
    default fill-with-NaN — deterministic, and identical between traced and
    concrete indices."""
    if not t.stacked:
        return t
    return TTLinear(
        lead=jnp.take(t.lead, idx, axis=0, mode="clip"), cores=t.cores,
        split=t.split, in_shape=t.in_shape, out_shape=t.out_shape,
        dtype=t.dtype, experts=t.experts, scales=t.scales,
        lead_scale=(None if t.lead_scale is None
                    else jnp.take(t.lead_scale, idx, axis=0, mode="clip")),
    )


# ---------------------------------------------------------------------------
# Quantization: symmetric integer cores, per-rank-row / per-lead-row scales
# ---------------------------------------------------------------------------

# storage formats the serving stack accepts; int4 rides the same machinery
# (qmax from jnp.iinfo) once a packed container lands in the checkpoint path
QUANT_DTYPES = {"int8": jnp.int8}


def quant_dtype(name: str):
    """Resolve a ``--weights tt-<name>`` / ``quant=<name>`` storage format."""
    try:
        return QUANT_DTYPES[name]
    except KeyError:
        raise ValueError(
            f"unknown quantized core format {name!r} "
            f"(supported: {sorted(QUANT_DTYPES)})"
        ) from None


def _calib_amax(a: jax.Array, calib: str, axis=None) -> jax.Array:
    """Calibration point of |a|: ``absmax`` (default) or ``pXX[.X]`` — the
    XX-th percentile of |a|, clipping the tail outliers into saturation in
    exchange for a finer grid on the bulk."""
    mag = jnp.abs(a.astype(jnp.float32))
    if calib == "absmax":
        return mag.max(axis=axis)
    if calib.startswith("p"):
        try:
            pct = float(calib[1:])
        except ValueError:
            pct = -1.0
        if 0.0 < pct <= 100.0:
            return jnp.percentile(mag, pct, axis=axis)
    raise ValueError(
        f"quant calibration must be 'absmax' or 'pXX' (percentile of |w|, "
        f"0 < XX <= 100), got {calib!r}"
    )


def quantize_array(a: jax.Array, dtype=jnp.int8, calib: str = "absmax",
                   axis=None) -> Tuple[jax.Array, jax.Array]:
    """(values, scale) of a symmetric integer quantization of ``a``.

    scale = amax/qmax per reduction group (whole array when ``axis`` is
    None, else per row over ``axis``); values = clip(round(a/scale)).
    All-zero groups pin scale to 1 so the round-trip stays exact.  With
    absmax calibration the max-|a| element lands exactly on ±qmax, so
    dequantize→requantize is idempotent (bit-identical values and scales) —
    the property the int8 checkpoint round-trip leans on."""
    qmax = jnp.iinfo(dtype).max
    amax = _calib_amax(a, calib, axis=axis)
    scale = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    s = scale if axis is None else jnp.expand_dims(scale, axis)
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / s), -qmax, qmax)
    return q.astype(dtype), scale


def dequantize_array(q: jax.Array, scale: jax.Array, axis=None) -> jax.Array:
    """Inverse of ``quantize_array`` (f32 values; exact for the grid)."""
    s = scale if axis is None else jnp.expand_dims(scale, axis)
    return q.astype(jnp.float32) * s


def _row_axes(g) -> Tuple[int, ...]:
    """Axes a core's row scale reduces over: all but the leading rank."""
    return tuple(range(1, g.ndim))


def quantize_core(g: jax.Array, dtype=jnp.int8,
                  calib: str = "absmax") -> Tuple[jax.Array, jax.Array]:
    """(values, (r,) scales) of a core (r, ...): one scale per leading-rank
    row.  The chain is linear in each core, so a row scale commutes onto
    the partial product's matching column."""
    return quantize_array(g, dtype=dtype, calib=calib, axis=_row_axes(g))


def dequantize_core(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_core`` (f32 values; exact for the grid).  A 0-d
    ``scale`` is one scale for the whole core: int8 checkpoints written
    before row scales stored that form, and still load."""
    scale = jnp.asarray(scale)
    if scale.ndim == 0:
        return dequantize_array(q, scale)
    return dequantize_array(q, scale, axis=_row_axes(q))


def quantize_tt(t: TTLinear, dtype=jnp.int8,
                calib: str = "absmax") -> TTLinear:
    """Quantize a TTLinear's resident payload to symmetric integers.

    Each core gets one scale per leading-rank row (``quantize_core``); the
    lead table one scale PER ROW over its rank axis (per layer, and per
    (layer, expert) for expert banks) because row magnitudes vary with
    layer depth.  Max absolute error per element is scale/2 =
    amax/(2·qmax) of its row: <= amax/254 for int8.  Apply-time
    dequantization stays inside the fused kernels (``tt_apply`` hands int8
    cores + scales down to ``kernels/tt_contract``)."""
    assert not t.quantized, "TTLinear is already quantized"
    cores, scales = [], []
    for g in t.cores:
        q, s = quantize_core(g, dtype=dtype, calib=calib)
        cores.append(q)
        scales.append(s)
    lead, lead_scale = t.lead, None
    if lead is not None:
        lead, lead_scale = quantize_array(lead, dtype=dtype, calib=calib,
                                          axis=-1)
    return TTLinear(
        lead=lead, cores=cores, split=t.split, in_shape=t.in_shape,
        out_shape=t.out_shape, dtype=t.dtype, experts=t.experts,
        scales=scales, lead_scale=lead_scale,
    )


def dequantize_tt(t: TTLinear) -> TTLinear:
    """Back to wide (f32) storage — the parity oracle for the fused path."""
    assert t.quantized, "TTLinear is not quantized"
    cores = [dequantize_core(g, s) for g, s in zip(t.cores, t.scales)]
    lead = t.lead
    if lead is not None:
        lead = dequantize_array(lead, t.lead_scale, axis=-1)
    return TTLinear(
        lead=lead, cores=cores, split=t.split, in_shape=t.in_shape,
        out_shape=t.out_shape, dtype=t.dtype, experts=t.experts,
    )


def quantize_tt_tree(params, dtype=jnp.int8, calib: str = "absmax"):
    """Quantize every TTLinear leaf of a params pytree (raw leaves pass
    through untouched) — the one-call seam serve.py and the benchmarks use
    to turn a bf16-TT serving tree into the int8 one."""
    def one(leaf):
        if is_tt_linear(leaf) and not leaf.quantized:
            return quantize_tt(leaf, dtype=dtype, calib=calib)
        return leaf
    return jax.tree.map(one, params, is_leaf=is_tt_linear)


def tt_apply(x: jax.Array, t: TTLinear) -> jax.Array:
    """y = x · W from cores alone; x (..., *in_shape) → (..., *out_shape)."""
    assert not t.experts, "expert-bank TTLinear: use tt_apply_experts"
    assert t.lead is None or t.lead.ndim == 1, (
        "stacked TTLinear: select_layer() before apply"
    )
    nin = len(t.in_shape)
    assert x.shape[x.ndim - nin:] == tuple(t.in_shape), (x.shape, t.in_shape)
    batch = x.shape[: x.ndim - nin]
    x2 = x.reshape(int(np.prod(batch or (1,))), -1)

    g0 = t.cores[0]                                   # (r_s, n_1, r_1)
    lead = t.lead
    chain_scales = None
    if t.quantized:
        # the lead row and the first core fold into the (transient) wide
        # absorbed core, so the tail cores are the only dequant work left
        # for the kernel
        g0 = dequantize_core(g0, t.scales[0])
        if lead is not None:
            lead = dequantize_array(lead, t.lead_scale)
        chain_scales = [None] + list(t.scales[1:])    # tail stays int8
    if lead is not None:
        g0 = jnp.einsum(
            "r,rns->ns", lead.astype(jnp.float32), g0.astype(jnp.float32)
        )
    else:
        assert g0.shape[0] == 1, g0.shape
        g0 = g0[0].astype(jnp.float32)
    chain = [g0] + list(t.cores[1:])

    from repro.kernels.tt_contract.ops import tt_contract  # lazy: no cycle
    y2 = tt_contract(x2, chain, split=t.split, scales=chain_scales)
    return y2.reshape(*batch, *t.out_shape).astype(x.dtype)


def tt_apply_experts(x: jax.Array, t: TTLinear) -> jax.Array:
    """Expert-banked apply: y[e] = x[e] · W[e] straight from cores.

    x (E, C, *in_shape) → (E, C, *out_shape).  Every expert shares the same
    in/out cores; only the tiny (E, r_s) lead table distinguishes them, so
    the whole bank contracts as ONE batched chain (``tt_contract_batched``)
    — the dense (E, N_in, N_out) bank is never materialized."""
    assert t.experts, "plain TTLinear: use tt_apply"
    assert t.lead is not None and t.lead.ndim == 2, (
        "stacked expert TTLinear: select_layer() before apply"
    )
    e = int(t.lead.shape[0])
    assert x.shape[0] == e, (x.shape, e)
    nin = len(t.in_shape)
    assert x.shape[x.ndim - nin:] == tuple(t.in_shape), (x.shape, t.in_shape)
    batch = x.shape[1: x.ndim - nin]
    x3 = x.reshape(e, int(np.prod(batch or (1,))), -1)

    # per-expert lead-absorbed first core: (E, r_s)·(r_s, n_1, r_1)
    lead, core0 = t.lead, t.cores[0]
    tail_scales = None
    if t.quantized:
        lead = dequantize_array(lead, t.lead_scale, axis=-1)  # (E, r_s)
        core0 = dequantize_core(core0, t.scales[0])
        tail_scales = list(t.scales[1:])
    g0e = jnp.einsum(
        "er,rns->ens", lead.astype(jnp.float32), core0.astype(jnp.float32),
    )
    from repro.kernels.tt_contract.ops import tt_contract_batched
    y3 = tt_contract_batched(x3, g0e, list(t.cores[1:]), split=t.split,
                             scales=tail_scales)
    return y3.reshape(e, *batch, *t.out_shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Conversion: TTCompressor payload (whole stacked tensor) → TTLinear
# ---------------------------------------------------------------------------

def _group_dims(tt_dims: Sequence[int], orig_shape: Sequence[int]):
    """Partition the tensorized dims into per-original-axis groups (greedy
    prefix products).  Returns group sizes or None when the dims are not a
    per-axis concatenation (e.g. padded bucket members)."""
    groups, i = [], 0
    for n in orig_shape:
        prod, start = 1, i
        while prod < n and i < len(tt_dims):
            prod *= tt_dims[i]
            i += 1
        if prod != n:
            return None
        groups.append(i - start)
    return groups if i == len(tt_dims) else None


def tt_linear_from_tt(
    tt: _tt.TTTensor,
    orig_shape: Sequence[int],
    stack: int,
    in_ndim: int,
    dtype=jnp.bfloat16,
    core_dtype=jnp.float32,
    experts: int = 0,
) -> Optional[TTLinear]:
    """Build a TTLinear from a whole-tensor TT of a (stacked) dense weight.

    orig_shape = (*stack_dims, *in_dims, *out_dims); ``stack`` leading axes
    are layer-stack modes (0 for unstacked), the next ``in_ndim`` axes are
    the matmul input.  The stack modes are contracted at every concrete
    layer index on the host, yielding the ``(L, r_s)`` lead table; in/out
    cores are shared across layers.  Returns None when the TT's dims don't
    map cleanly onto the axes (padded members) — caller falls back to
    reconstruction.

    experts: how many TRAILING stack axes form an expert bank (MoE weights
    (L, E, D, F) use stack=2, experts=1).  Their modes stay a batch axis of
    the lead table — (L, E, r_s) — instead of being scanned over, so one
    layer's whole bank applies as a single batched chain.

    core_dtype: storage dtype of the resident cores.  The contraction
    upcasts to f32 regardless; bf16 storage rounds the cores exactly like
    reconstruct-then-serve rounds the dense matrix, at half the bytes.
    """
    assert 0 <= experts <= stack
    groups = _group_dims(tt.shape, orig_shape)
    if groups is None:
        return None
    ns = sum(groups[:stack])                          # cores in the stack part
    split = sum(groups[stack: stack + in_ndim])
    if split < 1 or len(tt.cores) - ns - split < 1:
        return None                  # need ≥1 input core and ≥1 output core
    if experts and ns == 0:
        return None                  # expert bank needs its stack modes

    lead = None
    n_experts = None
    cores = [jnp.asarray(c, jnp.float32) for c in tt.cores]
    if ns > 0:
        # prefix-reconstruct the stack modes: (1,n_1,r_1) ×₁ … → (L, r_s)
        acc = cores[0].reshape(-1, cores[0].shape[2])  # (n_1, r_1)
        for k in range(1, ns):
            r, n, s = cores[k].shape
            acc = (acc @ cores[k].reshape(r, n * s)).reshape(-1, s)
        lead = acc                                    # (L[·E], r_s)
        if experts:
            n_experts = int(np.prod(orig_shape[stack - experts: stack]))
            lead = lead.reshape(-1, n_experts, lead.shape[-1])  # (L, E, r_s)
        cores = cores[ns:]
    cd = jnp.dtype(core_dtype)
    return TTLinear(
        lead=None if lead is None else lead.astype(cd),
        cores=[c.astype(cd) for c in cores], split=split,
        in_shape=tuple(orig_shape[stack: stack + in_ndim]),
        out_shape=tuple(orig_shape[stack + in_ndim:]),
        dtype=dtype,
        experts=n_experts,
    )


def tt_param_bytes(tree) -> int:
    """Resident weight bytes of a params pytree: TT leaves count their
    FULL payload — cores, lead table, and (when quantized) every dequant
    scale array — dense leaves their full array.  The TT-leaf walk goes
    through ``jax.tree.leaves`` of the leaf itself, so a field added to the
    TTLinear pytree can never silently escape the accounting again (the
    quantization scales initially did).  Non-array leaves (Python step
    counters and other scalars riding in checkpoint trees) carry no
    resident weight bytes and are skipped."""
    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=is_tt_linear):
        if is_tt_linear(leaf):
            for a in jax.tree.leaves(
                (leaf.lead, leaf.cores, leaf.scales, leaf.lead_scale)
            ):
                total += int(a.size) * a.dtype.itemsize
        elif hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += int(leaf.size) * leaf.dtype.itemsize
    return total


def tt_leaf_bytes(tree) -> Tuple[int, int]:
    """(resident bytes of the TT-served leaves, dense bytes those leaves
    would occupy un-decomposed) — the byte pair the quantization roofline
    argument is about: what the ``tt_contract`` kernels actually stream
    vs the reconstruct-then-serve baseline.  Raw leaves (embeddings,
    norms) are identical between the serving modes and excluded from both
    sides."""
    tt_b, dense_b = 0, 0
    for leaf in jax.tree.leaves(tree, is_leaf=is_tt_linear):
        if not is_tt_linear(leaf):
            continue
        for a in jax.tree.leaves(
            (leaf.lead, leaf.cores, leaf.scales, leaf.lead_scale)
        ):
            tt_b += int(a.size) * a.dtype.itemsize
        n = int(np.prod(leaf.in_shape)) * int(np.prod(leaf.out_shape))
        n *= (leaf.num_layers or 1) * (leaf.experts or 1)
        dense_b += n * jnp.dtype(leaf.dtype).itemsize
    return tt_b, dense_b


def spectral_decay_pytree(params, alpha: float = 1.0, min_size: int = 8192):
    """Impose a power-law singular spectrum (σ_i ∝ i^-α) on every big ≥2-D
    leaf.  Random init has a flat spectrum — incompressible by design, and
    the TT policy correctly refuses it; trained nets decay.  Demo/benchmark
    helper for exercising the TT serving path on synthetic weights."""
    def one(p):
        if p.ndim < 2 or p.size < min_size:
            return p
        mat = np.asarray(jax.device_get(p), np.float32).reshape(-1, p.shape[-1])
        u, s, vt = np.linalg.svd(mat, full_matrices=False)
        target = s[0] * (np.arange(1, s.size + 1.0) ** -alpha)
        out = (u * target) @ vt
        return jnp.asarray(out.reshape(p.shape), p.dtype)

    return jax.tree.map(one, params)
