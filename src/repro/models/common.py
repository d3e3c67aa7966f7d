"""Shared model machinery: building blocks, TT-serving registry, decode driver.

Three layers live here (everything family-agnostic; per-family code stays
in its own module):

  * **building blocks** — norms, RoPE, embeddings, initializers, and
    ``dense_apply``/``expert_apply``, the single raw-vs-TT weight dispatch
    points every matmul in the zoo routes through;
  * **TT-native serving plumbing** — the per-family rule registry
    (``register_tt_serve_rules``/``tt_native_params``) and the TT-aware
    layer scan (``tt_scan``/``layer_at``) that keep TT cores closure
    constants of every scanned forward/decode body;
  * **the fused decode driver** — ``GenState``/``gen_init``/``gen_step``/
    ``gen_scan``: the whole generation loop (prompt consumption, sampling,
    append, step) as one ``lax.scan`` computation, including per-slot
    sampling params, the device-resident admission queue (``ScanQueue``)
    and the retired-slot output buffer (``DoneBuf``) the continuous-
    batching engine schedules against.

Conventions (used by every arch in the zoo):
  * parameters are nested dicts; per-layer tensors are STACKED on a leading
    (num_layers,) axis so layers run under ``jax.lax.scan`` — this keeps HLO
    size and compile time independent of depth (essential for the 512-way
    dry-run compiles).
  * compute dtype is bf16; norms, softmax, and losses run fp32.
  * initializers take an explicit key and are only materialized for reduced
    (smoke-test) configs and the ~100M example — full-size configs are
    touched exclusively through ``jax.eval_shape``.
"""

from __future__ import annotations

import re
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def cdtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def pin_batch(x, cfg):
    """opt_batch_pin: re-assert batch-dim data sharding inside scan bodies
    (GSPMD can drop it across scan/jvp boundaries, silently replicating the
    batch; see EXPERIMENTS.md §Perf seamless)."""
    if getattr(cfg, "opt_batch_pin", False):
        from repro.launch import sharding as _shd
        return _shd.act_constraint(x, "data", *([None] * (x.ndim - 1)))
    return x


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array,                # (..., S, H, D)
    positions: jax.Array,        # (..., S)
    theta: float,
) -> jax.Array:
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activate(x: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x)
    if act == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {act}")


def dense_apply(x: jax.Array, w, in_ndim: int = 1) -> jax.Array:
    """THE dense-weight application point: every matmul against a model
    weight in the zoo routes through here, so a weight can be either a raw
    array or a TT payload (``core/tt_linear.TTLinear``) without the call
    sites knowing.

    Raw ``w``: shape (*in_dims, *out_dims) with ``in_ndim`` leading input
    axes; contracts x's trailing ``in_ndim`` axes against them (identical
    lowering to the einsums this replaces — one dot_general; mismatched
    dtypes PROMOTE like the einsums did, so an fp32 activation against a
    bf16 gate weight computes in fp32 and a high-precision weight is never
    silently downcast).  TTLinear ``w``: contracts the activation straight
    through the TT cores via the fused ``kernels/tt_contract`` chain — the
    full dense matrix is never materialized.  Quantized TTLinear leaves
    (int8 cores + scales) take the same branch: ``tt_apply`` hands the
    storage-dtype cores and their scales to the dequant-fused kernels, so
    every family serves from int8 with zero model-code changes.
    """
    from repro.core import tt_linear as _ttl
    if _ttl.is_tt_linear(w):
        return _ttl.tt_apply(x, w)
    if w.dtype != x.dtype:
        dt = jnp.promote_types(x.dtype, w.dtype)
        x = x.astype(dt)
        w = w.astype(dt)
    cdims = (
        tuple(range(x.ndim - in_ndim, x.ndim)),
        tuple(range(in_ndim)),
    )
    # accumulate in at least f32 and round once: where the contraction is
    # sharded (row-parallel on a model mesh), the partial sums then meet in
    # f32 and the result matches one device's single rounding
    acc = jnp.promote_types(x.dtype, jnp.float32)
    return jax.lax.dot_general(x, w, (cdims, ((), ())),
                               preferred_element_type=acc).astype(x.dtype)


def expert_apply(x: jax.Array, w) -> jax.Array:
    """Expert-banked weight application: x (E, C, IN) against w (E, IN, OUT)
    — the MoE FFN's batched matmul.  Raw banks lower to the einsum they
    replace; an expert-axis TTLinear contracts the whole bank straight from
    cores via the expert-batched TT chain (``tt_apply_experts``) —
    quantized banks included (per-(layer, expert)-row lead scales, shared
    int8 tail cores dequantized inside the batched kernel)."""
    from repro.core import tt_linear as _ttl
    if _ttl.is_tt_linear(w):
        return _ttl.tt_apply_experts(x, w)
    return jnp.einsum("eci,eio->eco", x, w)


# ---------------------------------------------------------------------------
# TT-native serving: per-family rule registry + TT-aware layer-scan plumbing
# ---------------------------------------------------------------------------

class TTServeRule(NamedTuple):
    """One eligible-weight pattern of a family's params tree.

    pattern — regex over the dot-joined pytree path of the weight;
    in_ndim — matmul input axes after the stack/expert axes;
    stack   — leading layer-stack axes contracted into the lead table;
    experts — trailing stack axes that form an expert bank (kept as a batch
              axis at apply time; served via the expert-batched chain).
    """
    pattern: "re.Pattern[str]"
    in_ndim: int
    stack: int = 1
    experts: int = 0


# family name -> rules, registered BESIDE each model module (see the
# ``register_tt_serve_rules`` calls at the bottom of transformer.py,
# encdec.py, mamba2.py, rglru.py) — common.py owns only the mechanism.
_TT_SERVE_REGISTRY: dict = {}


def register_tt_serve_rules(family: str, rules) -> None:
    """Register a family's TT-native serving rules (str patterns compiled)."""
    compiled = []
    for r in rules:
        if not isinstance(r, TTServeRule):
            r = TTServeRule(*r)
        if isinstance(r.pattern, str):
            r = r._replace(pattern=re.compile(r.pattern))
        compiled.append(r)
    _TT_SERVE_REGISTRY[family] = tuple(compiled)


def tt_serve_rules(family: Optional[str] = None):
    """Rules for one family, or the union over every registered family
    (path namespaces are disjoint across the zoo, so the union is safe —
    used when the caller doesn't know which family a payload came from)."""
    from repro.models import registry as _registry  # noqa: F401  (lazy:
    # importing the registry imports every model module, which registers
    # its rules as a side effect — common.py itself stays model-agnostic)
    if family is not None:
        return _TT_SERVE_REGISTRY.get(family, ())
    out = []
    for fam in sorted(_TT_SERVE_REGISTRY):
        out.extend(_TT_SERVE_REGISTRY[fam])
    return tuple(out)


def layers_have_tt(layers) -> bool:
    """True when a stacked layer tree carries any TTLinear leaf."""
    from repro.core.tt_linear import is_tt_linear
    return any(
        is_tt_linear(leaf)
        for leaf in jax.tree.leaves(layers, is_leaf=is_tt_linear)
    )


def layer_at(layers, idx):
    """Layer ``idx``'s params from a stacked tree (``idx`` may be traced).

    Raw leaves gather their idx-th row — same dynamic-slice the scan's xs
    mechanism would emit.  TTLinear leaves gather only their (L, r) lead
    vector; the shared cores stay closure constants, so the TT-native scan
    body keeps HLO size depth-independent without duplicating cores per
    layer (the reason TT weights cannot ride in the scan's xs).  Both
    gathers clamp out-of-range indices (``mode="clip"``) — pinned so traced
    and concrete indices behave identically."""
    from repro.core.tt_linear import is_tt_linear, select_layer

    def sel(leaf):
        if is_tt_linear(leaf):
            return select_layer(leaf, idx)
        return jnp.take(leaf, idx, axis=0, mode="clip")

    return jax.tree.map(sel, layers, is_leaf=is_tt_linear)


def tt_scan(fn, init, layers, xs=(), length: Optional[int] = None):
    """``lax.scan`` over a stacked layer tree, TT-aware.

    fn(carry, layer_params, *xs_slices) -> (carry, out).  Dense trees scan
    the params as xs (the stock pattern); trees holding TTLinear leaves
    scan the layer INDEX instead and gather each layer's params inside the
    body (``layer_at``) — cores must stay closure constants, never scan
    xs.  Every family's forward/decode stack runs through here, so TT-
    native serving is a property of the scan plumbing, not of one model.
    """
    if layers_have_tt(layers):
        assert length is not None, "tt_scan over TT leaves needs length"

        def body_tt(carry, scanned):
            return fn(carry, layer_at(layers, scanned[0]), *scanned[1:])

        return jax.lax.scan(body_tt, init, (jnp.arange(length), *xs))

    def body(carry, scanned):
        return fn(carry, scanned[0], *scanned[1:])

    return jax.lax.scan(body, init, (layers, *xs))


# ---------------------------------------------------------------------------
# Fused decode driver: the whole generation loop as ONE lax.scan computation
# ---------------------------------------------------------------------------

class Sampling(NamedTuple):
    """Static sampling configuration for the decode drivers.

    temperature — 0.0 selects greedy argmax (bit-identical to the pre-
                  sampling driver: no PRNG math is even traced); > 0 scales
                  logits by 1/temperature before categorical sampling.
    top_k       — keep only the k highest logits before sampling (ties at
                  the k-th value are all kept); None disables the filter.
    per_slot    — ignore the two static fields and sample each slot under
                  its own ``GenState.temp``/``GenState.topk`` entry (the
                  per-request sampling params the continuous-batching
                  engine writes at admission).  Slots with ``temp == 0``
                  take the greedy argmax — token-identical to the static
                  greedy path.

    The tuple is hashable, so it rides the jitted drivers as a static
    argument — each distinct (temperature, top_k, per_slot) compiles once.
    """
    temperature: float = 0.0
    top_k: Optional[int] = None
    per_slot: bool = False


GREEDY = Sampling()
PER_SLOT = Sampling(per_slot=True)


def make_sampling(temperature: float, top_k: Optional[int]) -> Sampling:
    """Validated Sampling for the serving front doors: a negative
    temperature would silently sample an INVERTED distribution (it passes
    the == 0 greedy check), and top_k <= 0 only surfaces as an opaque
    broadcast error deep inside the jitted scan — reject both up front."""
    temperature = float(temperature)
    if temperature < 0.0:
        raise ValueError(
            f"temperature must be >= 0 (0 = greedy), got {temperature}"
        )
    if top_k is not None:
        top_k = int(top_k)
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1 (or None), got {top_k}")
    return Sampling(temperature, top_k)


def slot_keys(seed: int, b: int) -> jax.Array:
    """Per-row sampling base keys for a ``b``-row generation: row ``r``
    gets ``fold_in(PRNGKey(seed), r)``.  The continuous-batching engine
    gives each request the row-0 key of its own seed, so a request samples
    the same stream whether it runs isolated (batch row 0) or staggered in
    an arbitrary slot."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda r: jax.random.fold_in(base, r))(jnp.arange(b))


def sample_tokens(logits: jax.Array, keys: jax.Array,
                  sampling: Sampling) -> jax.Array:
    """Temperature/top-k sample one token per row (greedy is the caller's
    branch — this function requires temperature > 0).

    logits (B, V) are scaled by 1/temperature in fp32, optionally top-k
    masked, then sampled with ``jax.random.categorical`` under each row's
    own key — the per-row keys are what keep staggered slots independent.
    """
    assert sampling.temperature > 0.0, "greedy path must not sample"
    scaled = logits.astype(jnp.float32) / sampling.temperature
    if sampling.top_k is not None and sampling.top_k < logits.shape[-1]:
        kth = jax.lax.top_k(scaled, sampling.top_k)[0][..., -1:]
        scaled = jnp.where(scaled >= kth, scaled,
                           jnp.asarray(-1e30, jnp.float32))
    return jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)


def sample_tokens_per_slot(logits: jax.Array, keys: jax.Array,
                           temperature: jax.Array,
                           top_k: jax.Array) -> jax.Array:
    """Per-slot temperature/top-k sampling: slot ``i`` samples under
    ``(temperature[i], top_k[i])`` — the per-request params the engine
    writes at admission (``top_k == 0`` disables the filter for that slot).

    Value-identical to the static ``sample_tokens`` path at equal params
    (same scaling, same kth-largest threshold with ties kept, same
    per-row categorical keys), so a request sampled in a mixed-params slot
    pool matches its isolated static-``Sampling`` run token for token.
    Slots with ``temperature == 0`` take the greedy argmax of the raw
    logits — token-identical to the static greedy path (the PRNG math is
    traced but its result discarded by the select).
    """
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    scaled = lf / safe_t[:, None]
    # per-row kth-largest threshold: a descending sort's (k-1)-th column is
    # exactly lax.top_k(scaled, k)[0][..., -1] — but k may differ per row
    srt = -jnp.sort(-scaled, axis=-1)
    k = jnp.where(top_k > 0, top_k, v)
    kth = jnp.take_along_axis(srt, jnp.clip(k - 1, 0, v - 1)[:, None], axis=1)
    scaled = jnp.where(scaled >= kth, scaled, jnp.asarray(-1e30, jnp.float32))
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


class ScanQueue(NamedTuple):
    """Device-resident admission queue the fused scan admits from.

    A FIFO of pending requests living ON the device, so a retired slot is
    refilled inside the scan body (at most one whole-pool admission sweep
    per step) — a fused chunk never has to end at a boundary just to admit.
    The host refills the buffers between chunks (one donated dispatch) and
    mirrors the admission arithmetic exactly (deterministic lengths, FIFO
    order, lowest-free-slot placement), so scheduling still needs no
    device→host readback.

    tokens (Q, T_max) / prompt_len (Q,) / total_len (Q,) / rng (Q, 2) /
    temp (Q,) / topk (Q,) — one pending request per row, same meaning as
    the GenState per-slot fields they are copied into at admission;
    head () — next row to admit;  size () — valid rows.
    """
    tokens: jax.Array
    prompt_len: jax.Array
    total_len: jax.Array
    rng: jax.Array
    temp: jax.Array
    topk: jax.Array
    head: jax.Array
    size: jax.Array


class DoneBuf(NamedTuple):
    """Retired-slot output rows, appended inside the scan.

    With in-scan admission a slot can retire AND be re-occupied within one
    chunk, overwriting its token row — so the step that retires a slot
    first copies its tokens/prompt_logits here (slot order within a step;
    ``count`` rows are valid).  The host drains the buffer at the chunk
    boundary and resets ``count`` in the refill dispatch.
    """
    tokens: jax.Array          # (D, T_max) int32
    prompt_logits: jax.Array   # (D, V) fp32
    count: jax.Array           # () int32
    bad: Optional[jax.Array] = None   # (D,) bool — retired slot tripped the
                                      # NaN/Inf logit guard (quarantined)


def make_scan_queue(capacity: int, t_max: int) -> ScanQueue:
    """An empty device queue (all rows invalid)."""
    return ScanQueue(
        tokens=jnp.zeros((capacity, t_max), jnp.int32),
        prompt_len=jnp.ones((capacity,), jnp.int32),
        total_len=jnp.ones((capacity,), jnp.int32),
        rng=jnp.zeros((capacity, 2), jnp.uint32),
        temp=jnp.zeros((capacity,), jnp.float32),
        topk=jnp.zeros((capacity,), jnp.int32),
        head=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
    )


def make_done_buf(capacity: int, t_max: int, vocab: int) -> DoneBuf:
    """An empty retired-slot output buffer."""
    return DoneBuf(
        tokens=jnp.zeros((capacity, t_max), jnp.int32),
        prompt_logits=jnp.zeros((capacity, vocab), jnp.float32),
        count=jnp.zeros((), jnp.int32),
        bad=jnp.zeros((capacity,), bool),
    )


def zero_slot_leaf(leaf, i):
    """Zero one slot's rows of a cache leaf.  Convention (every family):
    the only 1-D cache leaves are the per-slot ``pos``/``mem_len``
    counters; everything else stacks (L, B, ...) with the slot axis second.
    Memory-awareness: zeroing an encdec slot leaves ``mem_len`` at 0 —
    every cross-attention memory row masked — which decodes exactly as the
    zeroed ``mem_k``/``mem_v`` rows would (zero output), so a token-only
    request admitted after an encdec occupant can never see stale memory.
    ``admit_memory`` then overwrites the memory rows + ``mem_len`` for
    requests that DO carry encoder input."""
    if leaf.ndim == 1:
        return leaf.at[i].set(0)
    return leaf.at[:, i].set(jnp.zeros_like(leaf[:, i]))


def _zero_slot_leaf_masked(leaf, i, on):
    """``zero_slot_leaf`` under a traced predicate: when ``on`` is False
    the slot's rows are written back unchanged (an O(row) no-op, never an
    O(leaf) one — only slot ``i``'s rows are touched either way)."""
    if leaf.ndim == 1:
        return leaf.at[i].set(jnp.where(on, jnp.zeros_like(leaf[i]), leaf[i]))
    row = leaf[:, i]
    return leaf.at[:, i].set(jnp.where(on, jnp.zeros_like(row), row))


class GenState(NamedTuple):
    """Per-slot generation state the fused decode driver scans over.

    The device never hands control back to Python between tokens: prompt
    consumption, sampling, append — and, when a queue is attached, slot
    admission and retired-slot harvest — all happen inside the scan body,
    so a whole generation (or a continuous-batching chunk) is one dispatch.

    tokens      — (B, T_max) token buffer: prompt tokens up front, generated
                  tokens appended in place at the slot's position;
    prompt_len  — (B,) per-slot prompt length;
    total_len   — (B,) per-slot prompt_len + gen budget;
    active      — (B,) slots still consuming/producing (free slots idle with
                  frozen cache.pos — their lockstep compute is discarded);
    prompt_logits — (B, V) fp32 logits after each slot's last prompt token
                  (the verification comparison point of the python loop);
    rng         — (B, 2) uint32 per-slot sampling base keys.  The scan never
                  mutates them: the key for a slot's t-th generated token is
                  ``fold_in(rng[slot], t)``, a function of slot-local
                  progress only — so a request samples identically isolated
                  or staggered, whatever slot or step it lands on.
    temp / topk — (B,) fp32 / int32 per-slot sampling params, written at
                  admission alongside ``rng`` and read by the
                  ``Sampling(per_slot=True)`` driver mode (``topk == 0``
                  disables the top-k filter for that slot).  ``None`` on the
                  uniform-batch ``generate`` path, which samples under a
                  static engine-wide ``Sampling`` instead.
    queue / done — optional device-resident admission queue and retired-
                  slot output buffer (in-scan continuous batching); ``None``
                  on the uniform-batch path and under boundary admission.
    bad         — optional (B,) bool numeric-guard accumulator: set (and
                  never cleared until re-admission) once a slot's logits go
                  non-finite.  The slot keeps its deterministic retirement
                  step — the host-mirrored schedule must not observe NaNs —
                  and the flag rides out with the done flags at harvest, so
                  quarantine costs no extra readback.  ``None`` on the
                  uniform-batch path.
    """
    cache: object
    tokens: jax.Array
    prompt_len: jax.Array
    total_len: jax.Array
    active: jax.Array
    prompt_logits: jax.Array
    rng: jax.Array
    temp: Optional[jax.Array] = None
    topk: Optional[jax.Array] = None
    queue: Optional[ScanQueue] = None
    done: Optional[DoneBuf] = None
    bad: Optional[jax.Array] = None


def gen_init(cache, tokens, prompt_len, total_len, vocab: int,
             active=None, rng=None, temp=None, topk=None,
             queue: Optional[ScanQueue] = None,
             done: Optional[DoneBuf] = None,
             bad=None) -> GenState:
    """Pack a slot pool into a GenState (per-slot lengths may differ).

    ``temp``/``topk`` attach per-slot sampling params ((B,) arrays, used by
    ``Sampling(per_slot=True)``); ``queue``/``done`` attach the in-scan
    admission machinery; ``bad`` attaches the per-slot NaN/Inf logit guard.
    All default to None — the uniform-batch ``generate`` path carries none
    of them.
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    b = tokens.shape[0]
    prompt_len = jnp.broadcast_to(
        jnp.asarray(prompt_len, jnp.int32), (b,))
    total_len = jnp.broadcast_to(jnp.asarray(total_len, jnp.int32), (b,))
    if active is None:
        active = jnp.ones((b,), bool)
    if rng is None:
        rng = jnp.zeros((b, 2), jnp.uint32)
    return GenState(
        cache=cache,
        tokens=tokens,
        prompt_len=prompt_len,
        total_len=total_len,
        active=jnp.broadcast_to(jnp.asarray(active, bool), (b,)),
        prompt_logits=jnp.zeros((b, vocab), jnp.float32),
        rng=jnp.asarray(rng, jnp.uint32),
        temp=None if temp is None else jnp.asarray(temp, jnp.float32),
        topk=None if topk is None else jnp.asarray(topk, jnp.int32),
        queue=queue,
        done=done,
        bad=None if bad is None else jnp.asarray(bad, bool),
    )


def _scan_admit(state: GenState) -> GenState:
    """In-scan admission sweep (runs at the top of every ``gen_step`` when
    a queue is attached): fill free slots from the device queue, FIFO,
    lowest slot index first.  Admission copies the queue row into the slot
    (lengths, prompt row, rng, sampling params), zeroes the slot's cache
    rows, and activates it — the slot consumes its first prompt token in
    the very same step.  The whole sweep is skipped via ``lax.cond`` when
    nothing is admittable (no free slot or empty queue), so steady-state
    full-occupancy steps pay only the predicate.

    The host mirrors this arithmetic exactly (same FIFO order, same slot
    placement, same step) to track which request occupies which slot
    without reading the device.
    """
    b = state.tokens.shape[0]
    qcap = state.queue.tokens.shape[0]

    def sweep(s: GenState) -> GenState:
        q = s.queue
        cache, tokens, plog = s.cache, s.tokens, s.prompt_logits
        plen, tlen, act = s.prompt_len, s.total_len, s.active
        rng, temp, topk, bad = s.rng, s.temp, s.topk, s.bad
        head = q.head
        for i in range(b):
            admit = jnp.logical_and(~act[i], head < q.size)
            idx = jnp.clip(head, 0, qcap - 1)
            cache = jax.tree.map(
                lambda leaf, i=i, on=admit: _zero_slot_leaf_masked(
                    leaf, i, on),
                cache,
            )
            tokens = tokens.at[i].set(
                jnp.where(admit, q.tokens[idx], tokens[i]))
            plen = plen.at[i].set(jnp.where(admit, q.prompt_len[idx],
                                            plen[i]))
            tlen = tlen.at[i].set(jnp.where(admit, q.total_len[idx],
                                            tlen[i]))
            rng = rng.at[i].set(jnp.where(admit, q.rng[idx], rng[i]))
            temp = temp.at[i].set(jnp.where(admit, q.temp[idx], temp[i]))
            topk = topk.at[i].set(jnp.where(admit, q.topk[idx], topk[i]))
            plog = plog.at[i].set(
                jnp.where(admit, jnp.zeros_like(plog[i]), plog[i]))
            act = act.at[i].set(jnp.logical_or(admit, act[i]))
            if bad is not None:   # new occupant starts with a clean guard
                bad = bad.at[i].set(jnp.where(admit, False, bad[i]))
            head = head + admit.astype(jnp.int32)
        return s._replace(
            cache=cache, tokens=tokens, prompt_len=plen, total_len=tlen,
            active=act, prompt_logits=plog, rng=rng, temp=temp, topk=topk,
            queue=q._replace(head=head), bad=bad,
        )

    admittable = jnp.logical_and(state.queue.head < state.queue.size,
                                 jnp.any(~state.active))
    return jax.lax.cond(admittable, sweep, lambda s: s, state)


def _scan_harvest(state: GenState, retired: jax.Array) -> GenState:
    """Copy slots that retired THIS step into the done buffer (slot order),
    before a later in-scan admission can overwrite their token rows.
    Skipped via ``lax.cond`` on steps with no retirement."""
    b = state.tokens.shape[0]
    dcap = state.done.tokens.shape[0]

    def sweep(s: GenState) -> GenState:
        dt, dl, cnt = s.done.tokens, s.done.prompt_logits, s.done.count
        db = s.done.bad
        for i in range(b):
            r = retired[i]
            w = jnp.clip(cnt, 0, dcap - 1)
            dt = dt.at[w].set(jnp.where(r, s.tokens[i], dt[w]))
            dl = dl.at[w].set(jnp.where(r, s.prompt_logits[i], dl[w]))
            if db is not None and s.bad is not None:
                db = db.at[w].set(jnp.where(r, s.bad[i], db[w]))
            cnt = cnt + r.astype(jnp.int32)
        return s._replace(done=DoneBuf(dt, dl, cnt, db))

    return jax.lax.cond(jnp.any(retired), sweep, lambda s: s, state)


def gen_step(decode_step, params, state: GenState,
             sampling: Sampling = GREEDY) -> GenState:
    """One fused decode step over every slot (runs inside lax.scan).

    A slot at position p consumes tokens[p] — a prompt token while
    p < prompt_len (prefill-by-stepping), its own previous sample after —
    and samples the token for p+1 (greedy argmax, or temperature/top-k
    under the slot's own PRNG stream; per-slot params under
    ``Sampling(per_slot=True)``).  Inactive slots are frozen: their
    cache.pos is pinned so the batched decode_step re-writes the same cache
    row with the same values (idempotent), and their buffers are left
    untouched.  Every update is a masked select, so heterogeneous slots run
    in lockstep without branching.

    When ``state.queue`` is attached, the step opens with an in-scan
    admission sweep (free slots refill from the device queue and consume
    their first prompt token this very step); when ``state.done`` is
    attached, slots that retire this step are copied into the done buffer
    before the next step's admission can overwrite their rows.
    """
    if state.queue is not None:
        state = _scan_admit(state)
    cache = state.cache
    pos = cache.pos                                        # (B,) per-slot
    t_max = state.tokens.shape[1]
    cur = jnp.take_along_axis(
        state.tokens, jnp.clip(pos, 0, t_max - 1)[:, None], axis=1
    )                                                      # (B, 1)
    logits, cache = decode_step(params, cache, cur)
    adv = state.active
    cache = cache._replace(pos=jnp.where(adv, cache.pos, pos))
    newpos = cache.pos
    if sampling.per_slot:
        gen_idx = jnp.maximum(newpos - state.prompt_len, 0)
        keys = jax.vmap(jax.random.fold_in)(state.rng, gen_idx)
        nxt = sample_tokens_per_slot(logits, keys, state.temp, state.topk)
    elif sampling.temperature == 0.0:
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy sample
    else:
        # key = fold_in(slot base key, # tokens this slot has generated) —
        # slot-local progress, so staggered == isolated holds under sampling
        gen_idx = jnp.maximum(newpos - state.prompt_len, 0)
        keys = jax.vmap(jax.random.fold_in)(state.rng, gen_idx)
        nxt = sample_tokens(logits, keys, sampling)
    widx = jnp.clip(newpos, 0, t_max - 1)
    write = adv & (newpos >= state.prompt_len) & (newpos < state.total_len)
    bidx = jnp.arange(state.tokens.shape[0])
    old = state.tokens[bidx, widx]
    tokens = state.tokens.at[bidx, widx].set(jnp.where(write, nxt, old))
    at_prompt_end = adv & (pos == state.prompt_len - 1)
    prompt_logits = jnp.where(
        at_prompt_end[:, None], logits.astype(jnp.float32),
        state.prompt_logits,
    )
    # the step that writes the slot's last token (index total_len-1) retires it
    active = adv & (newpos <= state.total_len - 2)
    bad = state.bad
    if bad is not None:
        # numeric guard: one isfinite reduction folded into the step.  The
        # flag only ACCUMULATES — the slot still runs to its scheduled
        # retirement (masked lockstep makes the extra steps free), because
        # retiring early would desync the host-mirrored schedule.  It rides
        # out with the done flags at harvest: no extra readback.
        finite = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
        bad = bad | (adv & ~finite)
    state = state._replace(
        cache=cache, tokens=tokens, active=active,
        prompt_logits=prompt_logits, bad=bad,
    )
    if state.done is not None:
        state = _scan_harvest(state, adv & ~active)
    return state


def gen_scan(decode_step, params, state: GenState, n_steps: int,
             sampling: Sampling = GREEDY) -> GenState:
    """``n_steps`` fused decode steps as one scanned computation — the
    while_loop-style driver body (fixed trip count, so it scans)."""
    def body(s, _):
        return gen_step(decode_step, params, s, sampling), None
    state, _ = jax.lax.scan(body, state, None, length=n_steps)
    return state


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return ".".join(parts)


def tt_native_params(compressed, core_dtype=None, family: Optional[str] = None,
                     quant: Optional[str] = None,
                     quant_calib: str = "absmax"):
    """TTCompressor payload → TT-native serving params.

    Layer-stacked matmul weights whose TT payload maps cleanly onto the
    (stack[, experts], in, out) axes become ``TTLinear`` leaves — served
    straight from cores.  Eligibility comes from the per-family rule
    registry (``register_tt_serve_rules``): every family in the zoo —
    transformer (dense/moe/vlm), encdec, ssm, hybrid — registers its own
    weight paths, including MoE expert banks (expert-axis TTLinear, served
    via the expert-batched chain).  Everything else (embeddings, norms,
    routers, raw-routed and padded params) reconstructs exactly as the
    Fig. 1 receiving node does today.  The result drops into
    ``decode_step`` / ``forward`` unchanged; peak weight bytes shrink by
    the payload's compression ratio on the converted leaves.

    family: which family's rules to apply (``cfg.family``); None applies
    the union over all registered families — path namespaces are disjoint
    across the zoo, so this is safe when the payload's origin is unknown.

    core_dtype: resident-core storage dtype; ``None`` (the sentinel — an
    explicit dtype is never second-guessed, however it compares) stores
    each leaf's cores in its original weight dtype (bf16 for the zoo) —
    the same rounding reconstruct-then-serve applies to the dense matrix.

    quant: integer storage format name (``"int8"``) or None.  When set,
    every TTLinear leaf is symmetrically quantized (per-core scales,
    per-row lead scales — ``core/tt_linear.quantize_tt``) after conversion;
    the fused kernels dequantize in-VMEM at apply time, so the serving
    contract (``decode_step``/``forward`` signatures, staggered == isolated
    under continuous batching) is unchanged — only logits move within the
    quantization error bound.  quant_calib: ``"absmax"`` (default) or
    ``"pXX"`` percentile clipping, forwarded to the calibrator.
    """
    from repro.core import compression as _comp
    from repro.core import tt_linear as _ttl

    rules = tt_serve_rules(family)
    qdt = None if quant is None else _ttl.quant_dtype(quant)

    def is_cp(x):
        return isinstance(x, _comp.CompressedParam)

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        compressed, is_leaf=is_cp
    )
    leaves = []
    for path, c in flat:
        leaf = None
        if is_cp(c) and c.kind == "tt" and c.crop_dims is None:
            name = _path_str(path)
            for rule in rules:
                if rule.pattern.search(name):
                    leaf = _ttl.tt_linear_from_tt(
                        c.tt, c.orig_shape,
                        stack=rule.stack, in_ndim=rule.in_ndim,
                        dtype=c.orig_dtype,
                        core_dtype=(c.orig_dtype if core_dtype is None
                                    else core_dtype),
                        experts=rule.experts,
                    )
                    break
        if leaf is None:
            leaf = _comp.decompress_param(c) if is_cp(c) else c
        elif qdt is not None:
            leaf = _ttl.quantize_tt(leaf, dtype=qdt, calib=quant_calib)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def logit_parity(a: jax.Array, b: jax.Array) -> Tuple[float, float, float]:
    """(max|a−b|, |b| scale, argmax agreement) — the single tolerance
    surface every TT-native-vs-reconstruct comparison (serve --verify,
    benchmarks/tt_serve, examples, tests) shares.  The accepted bound for
    same-cores comparisons is ``max_diff <= max(0.05 * scale, 1e-3)``:
    both paths contract identical cores in identical order, so only
    bf16-level rounding may differ."""
    d = float(jnp.abs(a - b).max())
    scale = float(jnp.abs(b).max()) + 1e-9
    agree = float(jnp.mean(
        (jnp.argmax(a, -1) == jnp.argmax(b, -1)).astype(jnp.float32)
    ))
    return d, scale, agree


def teacher_forced_logits(model, params, prompts) -> np.ndarray:
    """Per-position next-token logits, teacher-forced over ``prompts``
    (B, S) one jitted ``decode_step`` per position -> (B, S-1, V) f32.
    ``generate``'s prompt_logits is last-position only, far too few samples
    to state an agreement percentage."""
    b, s = prompts.shape
    step = jax.jit(model.decode_step)
    cache = model.init_cache(b, s)
    outs = []
    for t in range(s - 1):
        logits, cache = step(params, cache, jnp.asarray(prompts[:, t:t + 1]))
        outs.append(np.asarray(logits, np.float32).reshape(b, -1))
    return np.stack(outs, 1)


def tie_tolerant_agreement(lq, lref, tol: float = 0.05) -> float:
    """Share of positions where ``lq`` scores the reference's argmax token
    within ``tol`` x max|lref| of its own max — the quantized-serving gate
    (>= 0.99).  Exact argmax agreement would flip at near-tied positions,
    where synthetic spectral-decay weights put many of them."""
    top = np.argmax(lref, -1)
    deficit = np.max(lq, -1) - np.take_along_axis(lq, top[..., None], -1)[..., 0]
    return float(np.mean(deficit <= tol * float(np.max(np.abs(lref)))))


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    """Scaled-normal init (truncated at 3σ), σ = 1/√fan_in."""
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (
        jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * std
    ).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (
        jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * 0.02
    ).astype(dtype)


def stacked(keys, fn):
    """vmap an initializer over a leading layer axis."""
    return jax.vmap(fn)(keys)


def cross_entropy_loss(
    logits: jax.Array,           # (B, S, V)
    labels: jax.Array,           # (B, S)
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -ll.mean()
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def unembed(x: jax.Array, embed: jax.Array, softcap: Optional[float] = None,
            real_vocab: Optional[int] = None):
    """Logits = x @ Eᵀ (fp32), optional tanh softcap.

    real_vocab: when the table is padded (opt_pad_vocab), logits for the
    padding rows are masked to -inf so CE/argmax never select them.
    """
    # the embedding table is documented dense-resident (tied unembed; the
    # TT policy never compresses it), so this transposed lookup is the one
    # weight einsum with no dispatch to route through
    # lint: skip[AST001]
    logits = jnp.einsum(
        "...d,vd->...v", x.astype(jnp.float32), embed.astype(jnp.float32)
    )
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    if real_vocab is not None and real_vocab < embed.shape[0]:
        pad_mask = jnp.arange(embed.shape[0]) >= real_vocab
        logits = jnp.where(pad_mask, jnp.asarray(-1e30, logits.dtype), logits)
    return logits
