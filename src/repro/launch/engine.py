"""Serving engine: fused on-device decode driver + continuous batching.

Two layers, both family-agnostic (they only touch the uniform
``decode_step(params, cache, tokens) -> (logits, cache)`` /
``init_cache(batch, max_len)`` Model surface):

``generate(model, params, prompts, gen, driver=...)``
    One uniform batch, two drivers:

    * ``python`` — the legacy oracle: one jitted ``decode_step`` per token,
      driven from Python.  Pays a host→device dispatch round-trip plus a
      host sync (the sample/argmax readback) per token.
    * ``fused``  — the whole generation (prefill-by-stepping → sample →
      append → step) runs as ONE jitted ``lax.scan`` per phase
      (``models.common.gen_scan``), with the state donated between phases.
      TT cores stay closure constants of the scanned body exactly as in
      ``common.tt_scan`` — the device never waits on Python between tokens.

    Sampling (greedy, or temperature/top-k under per-row PRNG streams) and
    encoder input for encdec families (``src_tokens`` → memory populated
    before the first decode step) are part of the shared contract — the
    two drivers stay token-for-token identical under both.

``Engine``
    Continuous batching on top of the fused driver: a slot-based cache
    pool with per-slot lengths, per-slot sampling params, and two
    admission modes (``admission="scan"`` — a device-resident request
    queue admitted from INSIDE the fused scan — and ``"boundary"`` — one
    donated host dispatch per admission between chunks).  See the Engine
    docstring for the full contract.

One level up, ``launch/router.py`` spreads requests over N Engine
replicas and ``launch/server.py`` puts an async HTTP front door (SSE
streaming, deadlines, backpressure) in front of the router.
"""

from __future__ import annotations

import functools
import itertools
import time
from collections import deque
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common as model_common

DRIVERS = ("fused", "python")
ADMISSION_MODES = ("auto", "scan", "boundary")


def _decode_fn(model):
    return jax.jit(model.decode_step, donate_argnums=(1,))


def _python_loop(decode, params, cache, prompts, gen,
                 sampling=model_common.GREEDY, keys=None):
    """Legacy one-jitted-step-per-token loop (the ``--driver python``
    oracle).  Prefills by stepping the decode cache through the prompt,
    then decodes ``gen`` tokens — greedy, or sampled under the SAME
    per-row ``fold_in(keys[row], t)`` streams the fused driver uses, so
    the oracle stays token-for-token even under stochastic sampling.  Each
    token pays a dispatch plus the sample/argmax host sync."""
    b, prompt_len = prompts.shape
    t0 = time.time()
    logits = None
    for i in range(prompt_len):
        logits, cache = decode(params, cache, jnp.asarray(prompts[:, i:i+1]))
    jax.block_until_ready(logits)
    prefill_t = time.time() - t0
    prompt_logits = logits

    def pick(logits, t):
        if sampling.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        keys_t = jax.vmap(jax.random.fold_in)(
            keys, jnp.full((b,), t, jnp.int32))
        return model_common.sample_tokens(logits, keys_t, sampling)[:, None]

    tok = pick(logits, 0)
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for t in range(1, gen):
        logits, cache = decode(params, cache, tok)
        tok = pick(logits, t)
        out_tokens.append(np.asarray(tok))
    jax.block_until_ready(logits)
    decode_t = time.time() - t0
    return {
        "prefill_t": prefill_t,
        "decode_t": decode_t,
        "gen": np.concatenate(out_tokens, axis=1),
        "prompt_logits": prompt_logits,
    }


@functools.partial(jax.jit, static_argnums=(0, 3, 4), donate_argnums=(2,))
def _run_steps(decode_step, params, state, n_steps,
               sampling=model_common.GREEDY):
    """``n_steps`` fused decode steps, state donated across chunk calls so
    the cache pool is updated in place between Python-side admissions."""
    return model_common.gen_scan(decode_step, params, state, n_steps,
                                 sampling)


def _fused_generate(model, params, cache, prompts, gen,
                    sampling=model_common.GREEDY, keys=None):
    """Whole-generation fused driver: two scanned phases (prefill, decode)
    so the timing split matches the python loop's reporting boundaries."""
    decode = model.decode_step            # raw step: scanned, not re-jitted
    b, prompt_len = prompts.shape
    t_max = int(prompt_len + gen)
    tokens = np.zeros((b, t_max), np.int32)
    tokens[:, :prompt_len] = prompts
    state = model_common.gen_init(
        cache, tokens, prompt_len, t_max, model.cfg.padded_vocab_size,
        rng=keys,
    )
    t0 = time.time()
    state = _run_steps(decode, params, state, prompt_len, sampling)
    state = jax.block_until_ready(state)
    prefill_t = time.time() - t0
    t0 = time.time()
    if gen > 1:
        state = _run_steps(decode, params, state, gen - 1, sampling)
        state = jax.block_until_ready(state)
    decode_t = time.time() - t0
    return {
        "prefill_t": prefill_t,
        "decode_t": decode_t,
        "gen": np.asarray(state.tokens[:, prompt_len:]),
        "prompt_logits": state.prompt_logits,
    }


def generate(model, params, prompts, gen: int, max_len: Optional[int] = None,
             driver: str = "fused", decode=None, src_tokens=None,
             temperature: float = 0.0, top_k: Optional[int] = None,
             seed: int = 0) -> dict:
    """One uniform-batch serving run; single source of truth for
    prefill-by-stepping + sampling + timing boundaries.

    Returns ``{prefill_t, decode_t, gen (B, gen) np.int32, prompt_logits}``
    — identical contract (and, token for token, identical output) for both
    drivers.  ``decode`` lets python-driver callers share one jitted step
    across runs (the fused driver keys its compile cache on
    ``model.decode_step`` itself and needs no sharing).

    ``src_tokens`` — optional encoder input for encoder-decoder families:
    (S_src,) shared across the batch or (B, S_src) per row; encoded once up
    front and written into the cache's cross-attention memory
    (``model.populate_memory``) before any decode step runs.

    ``temperature``/``top_k``/``seed`` — stochastic sampling.  Row ``r``
    samples under ``fold_in(PRNGKey(seed), r)``; ``temperature=0`` (the
    default) is greedy argmax, bit-identical to the pre-sampling driver.
    """
    if driver not in DRIVERS:
        raise ValueError(f"unknown driver {driver!r} (choose from {DRIVERS})")
    prompts = np.asarray(prompts, np.int32)
    b = prompts.shape[0]
    if max_len is None:
        max_len = prompts.shape[1] + gen
    cache = model.init_cache(b, max_len)
    if src_tokens is not None:
        if model.populate_memory is None:
            raise ValueError(
                f"family {model.cfg.family!r} takes token-only input "
                f"(no encoder memory); src_tokens is encdec-only"
            )
        src = np.asarray(src_tokens, np.int32)
        if src.ndim == 1:
            src = np.broadcast_to(src, (b, src.shape[0]))
        cap = model.cfg.frontend_len
        if src.shape[1] > cap:
            raise ValueError(
                f"src_tokens needs {src.shape[1]} encoder positions, the "
                f"cache's memory rows hold {cap}"
            )
        cache = model.populate_memory(params, cache, jnp.asarray(src))
    sampling = model_common.make_sampling(temperature, top_k)
    keys = model_common.slot_keys(seed, b)
    if driver == "python":
        if decode is None:
            decode = _decode_fn(model)
        return _python_loop(decode, params, cache, prompts, gen,
                            sampling, keys)
    return _fused_generate(model, params, cache, prompts, gen,
                           sampling, keys)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

class Request(NamedTuple):
    uid: int
    prompt: np.ndarray            # (plen,) int32
    gen: int
    src_tokens: Optional[np.ndarray] = None   # (slen,) int32 encoder input
    key: Optional[np.ndarray] = None          # (2,) uint32 sampling base key
    temp: float = 0.0             # per-request temperature (0 = greedy)
    topk: int = 0                 # per-request top-k (0 = no filter)


class InvalidRequest(ValueError):
    """A submission rejected at validation — bad shape, bad sampling
    params, out-of-range tokens, or capacity the pool cannot hold.  The
    request never consumed a queue slot (HTTP 400)."""


class Completion(NamedTuple):
    uid: int
    tokens: np.ndarray            # (gen,) int32 generated tokens
    prompt_logits: np.ndarray     # (V,) fp32 logits after the prompt
    bad: bool = False             # tripped the NaN/Inf logit guard — the
                                  # tokens are poisoned; quarantine them


@functools.partial(jax.jit, donate_argnums=(0,))
def _admit_slot(state, i, token_row, prompt_len, total_len, key_row,
                temp, topk):
    """Reset slot ``i`` for a new request — cache rows zeroed, prompt
    written, per-slot lengths + sampling key/params set — as ONE donated
    dispatch (a leaf-by-leaf host-side reset costs a dispatch per cache
    leaf per admission, which dominates small-model chunks)."""
    return state._replace(
        cache=jax.tree.map(
            lambda leaf: model_common.zero_slot_leaf(leaf, i), state.cache),
        tokens=state.tokens.at[i].set(token_row),
        prompt_len=state.prompt_len.at[i].set(prompt_len),
        total_len=state.total_len.at[i].set(total_len),
        active=state.active.at[i].set(True),
        prompt_logits=state.prompt_logits.at[i].set(0.0),
        rng=state.rng.at[i].set(key_row),
        temp=state.temp.at[i].set(temp),
        topk=state.topk.at[i].set(topk),
        bad=(None if state.bad is None
             else state.bad.at[i].set(False)),
    )


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _admit_slot_mem(admit_memory, state, params, i, token_row, prompt_len,
                    total_len, key_row, temp, topk, src_row):
    """Admission for a request carrying encoder input: the slot reset PLUS
    one encode — ``admit_memory`` runs the model's encoder on ``src_row``
    and writes the projected cross-attention K/V into that slot's
    ``mem_k``/``mem_v`` rows (and its ``mem_len``) — all inside the same
    donated dispatch.  Compiles once per distinct source length (the encode
    is shape-specialized, like every other jitted entry point)."""
    cache = jax.tree.map(
        lambda leaf: model_common.zero_slot_leaf(leaf, i), state.cache)
    cache = admit_memory(params, cache, i, src_row)
    return state._replace(
        cache=cache,
        tokens=state.tokens.at[i].set(token_row),
        prompt_len=state.prompt_len.at[i].set(prompt_len),
        total_len=state.total_len.at[i].set(total_len),
        active=state.active.at[i].set(True),
        prompt_logits=state.prompt_logits.at[i].set(0.0),
        rng=state.rng.at[i].set(key_row),
        temp=state.temp.at[i].set(temp),
        topk=state.topk.at[i].set(topk),
        bad=(None if state.bad is None
             else state.bad.at[i].set(False)),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _deactivate_slot(state, i):
    """Cancel an in-flight slot: freeze it (active=False) without touching
    its buffers — the next admission zeroes them anyway.  A slot
    deactivated HERE (between chunks) never transitions inside a step, so
    the in-scan harvest never copies it to the done buffer."""
    return state._replace(active=state.active.at[i].set(False))


@functools.partial(jax.jit, donate_argnums=(0,))
def _refill_scan(state, q_tokens, q_plen, q_tlen, q_rng, q_temp, q_topk,
                 q_size):
    """Chunk-boundary refill for scan admission: replace the device queue
    with the current pending window and reset the drained done buffer —
    one donated dispatch per chunk, independent of how many requests it
    carries (boundary admission pays one dispatch per REQUEST instead)."""
    queue = model_common.ScanQueue(
        tokens=q_tokens, prompt_len=q_plen, total_len=q_tlen, rng=q_rng,
        temp=q_temp, topk=q_topk,
        head=jnp.zeros((), jnp.int32), size=q_size,
    )
    return state._replace(
        queue=queue,
        done=state.done._replace(count=jnp.zeros((), jnp.int32)),
    )


class Engine:
    """Slot-based continuous-batching engine over the fused decode driver.

    ``slots`` cache rows are stepped together in fused chunks of up to
    ``chunk_steps`` tokens.  Each admission resets exactly one slot —
    cache rows zeroed, prompt written, per-slot lengths, PRNG key, and
    sampling params set — so heterogeneous request streams keep every slot
    busy instead of padding to the longest request.  Two admission modes:

    * ``admission="scan"`` (the default for token-only families) — a
      device-resident FIFO (``models.common.ScanQueue``) rides the scanned
      state; every step opens with an in-scan admission sweep, so a slot
      that retires mid-chunk is refilled on the NEXT step without ending
      the chunk.  Retiring slots are copied into a device-side done buffer
      (``DoneBuf``) before re-admission can overwrite their rows; the host
      drains it once per chunk.  The host refills the queue window with one
      donated dispatch per chunk.
    * ``admission="boundary"`` — the pre-scan behavior: harvest + one
      donated ``_admit_slot`` dispatch per admission between chunks.
      Encoder-decoder engines always use this mode (admission runs the
      encode on the host side — ``_admit_slot_mem``); ``admission="auto"``
      picks ``scan`` for token-only families and ``boundary`` for encdec.

    Decode is DETERMINISTIC in length: a request admitted with prompt
    ``plen`` and budget ``gen`` retires after exactly ``plen + gen - 1``
    fused steps (sampling changes WHICH tokens come out, never how many).
    The engine therefore schedules entirely with host-side arithmetic —
    under scan admission it mirrors the device's admission sweep step by
    step (same FIFO order, same lowest-free-slot placement) — so there is
    no device→host readback at chunk boundaries; the device is read once
    per request at harvest (plus opt-in ``peek_tokens`` reads for
    streaming callers).

    Sampling is PER-REQUEST: ``submit(..., temperature=, top_k=, seed=)``
    rides the slot as ``GenState.temp``/``topk``/``rng`` — engine-level
    ``temperature``/``top_k`` are only the defaults for requests that don't
    set their own.  Keys advance with slot-local progress only, so
    staggered == isolated holds token-for-token under any mix of per-slot
    params; ``temperature=0`` requests take the greedy argmax
    (token-identical to an isolated greedy run).  "Isolated" means the
    request alone at the engine's batch shape (``slots`` rows, its
    ``max_len``): on a TPU, XLA may compute a row differently at batch 1,
    so a batch-1 ``generate`` is not the reference there.

    Encoder-decoder requests ride slots like any other: ``submit`` takes
    the request's source tokens, admission runs ONE jitted encode
    (``_admit_slot_mem`` — the slot reset and the encode share a donated
    dispatch) and writes the projected cross-attention K/V into that slot's
    ``mem_k``/``mem_v`` rows; ``mem_len`` masks the unused tail rows.
    Token-only admissions zero the memory rows and pin ``mem_len`` to 0, so
    a recycled slot never leaks a previous occupant's memory.

    ``cancel(uid)`` abandons a request (pending → dropped; in-flight → its
    slot is frozen at the next boundary and freed for re-admission); the
    serving layer uses it for deadline expiry and client disconnects.

    Limits: MoE serves, but staggered == isolated is not promised there
    (expert capacity couples batch rows; see ``mlp.moe_apply``).
    """

    def __init__(self, model, params, slots: int = 4, max_len: int = 128,
                 chunk_steps: int = 8, temperature: float = 0.0,
                 top_k: Optional[int] = None, seed: int = 0,
                 admission: str = "auto", queue_cap: Optional[int] = None):
        if admission not in ADMISSION_MODES:
            raise ValueError(
                f"unknown admission mode {admission!r} "
                f"(choose from {ADMISSION_MODES})"
            )
        if admission == "auto":
            admission = "boundary" if model.admit_memory is not None \
                else "scan"
        if admission == "scan" and model.admit_memory is not None:
            raise ValueError(
                f"family {model.cfg.family!r} carries encoder input; "
                f"admission runs the encode on the host, so it must use "
                f"admission='boundary' (or 'auto')"
            )
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.chunk_steps = chunk_steps
        self.admission = admission
        self.sampling = model_common.make_sampling(temperature, top_k)
        self.seed = seed
        self._step = model.decode_step        # raw step: scanned, not jitted
        self.queue: deque = deque()
        self._occupant: List[Optional[Request]] = [None] * slots
        self._remaining = [0] * slots         # fused steps until retirement
        self._uid = 0
        self.steps = 0            # fused steps run (occupancy accounting)
        self.slot_steps = 0       # steps × busy slots (useful work)
        # max admissions (and retirements) in one chunk is one per slot per
        # step — size the device queue window and done buffer to that bound
        self._queue_cap = (slots * chunk_steps if queue_cap is None
                           else queue_cap)
        vocab = model.cfg.padded_vocab_size
        scan_mode = admission == "scan"
        self.state = model_common.gen_init(
            model.init_cache(slots, max_len),
            np.zeros((slots, max_len), np.int32),
            prompt_len=np.ones((slots,), np.int32),
            total_len=np.ones((slots,), np.int32),
            vocab=vocab,
            active=np.zeros((slots,), bool),
            temp=np.zeros((slots,), np.float32),
            topk=np.zeros((slots,), np.int32),
            queue=(model_common.make_scan_queue(self._queue_cap, max_len)
                   if scan_mode else None),
            done=(model_common.make_done_buf(slots * chunk_steps, max_len,
                                             vocab)
                  if scan_mode else None),
            bad=np.zeros((slots,), bool),
        )

    @property
    def src_capacity(self) -> int:
        """Encoder positions a slot's memory rows hold (0 = token-only
        family)."""
        if self.model.admit_memory is None:
            return 0
        return self.model.cfg.frontend_len

    @property
    def busy_slots(self) -> int:
        """Slots currently occupied (host view; exact between chunks)."""
        return sum(1 for r in self._occupant if r is not None)

    @property
    def pending(self) -> int:
        """Requests queued but not yet admitted (host view)."""
        return len(self.queue)

    @property
    def outstanding(self) -> int:
        """Requests submitted but not yet completed: pending + in-flight.
        The router's least-outstanding admission metric."""
        return self.pending + self.busy_slots

    @property
    def occupancy(self) -> float:
        """Lifetime useful-work fraction: busy slot-steps / total
        slot-steps."""
        return self.slot_steps / max(self.steps * self.slots, 1)

    def _token_ids(self, x, what: str) -> np.ndarray:
        """Coerce one token-id field to a flat int32 array, rejecting
        garbage (non-numeric, non-integral, out-of-vocab) with a typed
        error instead of silently truncating or clamping downstream."""
        try:
            arr = np.asarray(x)
        except Exception as e:
            raise InvalidRequest(f"{what} is not array-like: {e}") from None
        if arr.dtype.kind == "f":
            if arr.size and not np.all(np.isfinite(arr) & (arr == np.floor(arr))):
                raise InvalidRequest(
                    f"{what} must be integer token ids, got non-integral "
                    f"floats")
        elif arr.dtype.kind not in "iu":
            raise InvalidRequest(
                f"{what} must be integer token ids, got dtype {arr.dtype}")
        arr = arr.reshape(-1).astype(np.int32)
        vocab = self.model.cfg.vocab_size
        if arr.size and (int(arr.min()) < 0 or int(arr.max()) >= vocab):
            raise InvalidRequest(
                f"{what} token ids must be in [0, {vocab}), got range "
                f"[{int(arr.min())}, {int(arr.max())}]")
        return arr

    def validate(self, prompt, gen: int, src_tokens=None,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None):
        """Normalize + validate a request WITHOUT queuing it — the fail-
        fast check the router/server front door runs before admission (a
        bad request must 400 before it consumes a queue slot).  Returns
        ``(prompt, src, sampling)`` ready for ``submit``; raises
        ``InvalidRequest`` (a ``ValueError``) on anything malformed —
        oversized shapes, non-integer or out-of-range token ids, bad
        sampling params — so callers can map it to a typed 400."""
        prompt = self._token_ids(prompt, "prompt")
        if not isinstance(gen, (int, np.integer)):
            raise InvalidRequest(
                f"gen must be an integer, got {type(gen).__name__}")
        if len(prompt) < 1 or gen < 1:
            raise InvalidRequest(
                f"request needs a non-empty prompt and gen >= 1, got "
                f"plen={len(prompt)} gen={gen}"
            )
        src = None
        if src_tokens is not None:
            if self.model.admit_memory is None:
                raise InvalidRequest(
                    f"family {self.model.cfg.family!r} takes token-only "
                    f"requests (no encoder input); src_tokens is "
                    f"encdec-only"
                )
            src = self._token_ids(src_tokens, "src_tokens")
            if len(src) < 1:
                raise InvalidRequest(
                    "src_tokens, when given, must be non-empty")
        need_dec = len(prompt) + gen
        need_enc = 0 if src is None else len(src)
        if need_dec > self.max_len or need_enc > self.src_capacity:
            raise InvalidRequest(
                f"request needs {need_dec} decoder positions"
                + (f" and {need_enc} encoder positions" if src is not None
                   else "")
                + f", pool rows hold {self.max_len} decoder"
                + (f" and {self.src_capacity} encoder"
                   if src is not None else "")
                + " positions"
            )
        s = model_common.make_sampling(
            self.sampling.temperature if temperature is None else temperature,
            self.sampling.top_k if top_k is None else top_k,
        )
        return prompt, src, s

    def submit(self, prompt, gen: int, src_tokens=None,
               seed: Optional[int] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None) -> int:
        """Queue one request; returns its uid (completions match by uid).

        ``temperature``/``top_k`` override the engine-wide defaults FOR
        THIS REQUEST (validated here, served per-slot); ``seed`` gives the
        request its own sampling stream — the row-0 key of an isolated
        ``generate(..., seed=seed)`` run, so sampled staggered-vs-isolated
        parity holds key-for-key.
        """
        prompt, src, s = self.validate(prompt, gen, src_tokens,
                                       temperature, top_k)
        uid = self._uid
        self._uid += 1
        if seed is not None:
            # row-0 key of the request's own seed — the same key an
            # isolated ``generate(prompt[None], ..., seed=seed)`` run gives
            # its one row, so sampled staggered-vs-isolated parity holds
            # key-for-key
            key = model_common.slot_keys(seed, 1)[0]
        else:
            # default: hash the uid into the engine's stream (fold_in, not
            # seed+uid arithmetic — adjacent engine seeds or an explicit
            # per-request seed must not collide with another request's
            # default stream)
            key = jax.random.fold_in(
                model_common.slot_keys(self.seed, 1)[0], uid)
        self.queue.append(Request(
            uid, prompt, gen, src, np.asarray(key),
            temp=s.temperature, topk=0 if s.top_k is None else s.top_k,
        ))
        return uid

    def cancel(self, uid: int) -> bool:
        """Abandon a request.  Pending → removed from the queue; in-flight
        → its slot is deactivated (one small dispatch; effective at the
        current chunk boundary) and freed for re-admission.  Returns False
        when the uid is unknown or already completed.  A canceled request
        never produces a Completion."""
        for j, req in enumerate(self.queue):
            if req.uid == uid:
                del self.queue[j]
                return True
        for i in range(self.slots):
            occ = self._occupant[i]
            if occ is not None and occ.uid == uid:
                self.state = _deactivate_slot(self.state, jnp.int32(i))
                self._occupant[i] = None
                self._remaining[i] = 0
                return True
        return False

    def progress(self, uid: int) -> Optional[int]:
        """Generated tokens available so far for an in-flight request
        (host arithmetic only; exact between chunks).  None when the uid
        is not currently in a slot."""
        for i in range(self.slots):
            occ = self._occupant[i]
            if occ is not None and occ.uid == uid:
                return max(0, occ.gen - self._remaining[i])
        return None

    def peek_tokens(self, uid: int) -> Optional[np.ndarray]:
        """The generated-so-far tokens of an in-flight request (one device
        row read — the streaming front door's per-chunk delta source).
        Call between chunks only.  None when the uid is not in a slot."""
        for i in range(self.slots):
            occ = self._occupant[i]
            if occ is not None and occ.uid == uid:
                plen = len(occ.prompt)
                avail = max(0, occ.gen - self._remaining[i])
                return np.asarray(self.state.tokens[i, plen:plen + avail])
        return None

    # -- boundary admission (between fused chunks) --------------------------

    def _harvest_slot(self, i: int) -> Completion:
        """Read a retired slot's generated rows (the once-per-request
        device read) and free it."""
        req = self._occupant[i]
        plen = len(req.prompt)
        toks = np.asarray(self.state.tokens[i, plen:plen + req.gen])
        plog = np.asarray(self.state.prompt_logits[i])
        bad = bool(np.asarray(self.state.bad[i]))
        self._occupant[i] = None
        return Completion(req.uid, toks, plog, bad=bad)

    def _admit_one(self, i: int, req: Request) -> None:
        plen = len(req.prompt)
        row = np.zeros((self.max_len,), np.int32)
        row[:plen] = req.prompt
        args = (
            jnp.int32(i), jnp.asarray(row),
            jnp.int32(plen), jnp.int32(plen + req.gen),
            jnp.asarray(req.key),
            jnp.float32(req.temp), jnp.int32(req.topk),
        )
        if req.src_tokens is None:
            self.state = _admit_slot(self.state, *args)
        else:
            # encode-at-admission: the request's encoder memory is computed
            # here (one jitted encode, donated like the plain reset) and
            # written into THIS slot's mem rows — never zeroed away
            self.state = _admit_slot_mem(
                self.model.admit_memory, self.state, self.params,
                *args, jnp.asarray(req.src_tokens),
            )
        self._occupant[i] = req
        self._remaining[i] = plen + req.gen - 1

    def _turnover(self) -> List[Completion]:
        """Harvest every retired slot; refill from the queue."""
        done = []
        for i in range(self.slots):
            if self._occupant[i] is not None and self._remaining[i] <= 0:
                done.append(self._harvest_slot(i))
            if self._occupant[i] is None and self.queue:
                self._admit_one(i, self.queue.popleft())
        return done

    def _chunk_sampling(self, requests) -> model_common.Sampling:
        """Static sampling mode for one chunk: the per-slot sampler pays a
        full-vocab sort + categorical EVERY step, so a chunk whose
        requests are all greedy (temp == 0) takes the static greedy path
        instead — token-identical (the per-slot sampler reduces to the
        same argmax at temp 0), and the host knows the chunk's request
        set, so the choice costs nothing on device."""
        if any(r is not None and r.temp > 0.0 for r in requests):
            return model_common.PER_SLOT
        return model_common.GREEDY

    def _step_chunk_boundary(self) -> List[Completion]:
        """Harvest/admit → one fused chunk.  Returns completions.

        The chunk is shortened when every busy slot retires sooner — the
        tail of a drained workload never scans frozen lockstep steps.  At
        most ``chunk_steps`` distinct scan lengths ever compile."""
        done = self._turnover()
        busy = [i for i in range(self.slots) if self._occupant[i] is not None]
        if not busy:
            return done
        n = min(self.chunk_steps, max(self._remaining[i] for i in busy))
        self.state = _run_steps(self._step, self.params, self.state, n,
                                self._chunk_sampling(self._occupant))
        self.steps += n
        for i in busy:
            self.slot_steps += min(self._remaining[i], n)
            self._remaining[i] -= n
        return done

    # -- scan admission (device-resident queue) -----------------------------

    def _queue_arrays(self, upload: List[Request]):
        """Pack the pending window into the device-queue buffers."""
        qc = self._queue_cap
        qt = np.zeros((qc, self.max_len), np.int32)
        qp = np.ones((qc,), np.int32)
        ql = np.ones((qc,), np.int32)
        qr = np.zeros((qc, 2), np.uint32)
        qtemp = np.zeros((qc,), np.float32)
        qk = np.zeros((qc,), np.int32)
        for j, req in enumerate(upload):
            plen = len(req.prompt)
            qt[j, :plen] = req.prompt
            qp[j] = plen
            ql[j] = plen + req.gen
            qr[j] = req.key
            qtemp[j] = req.temp
            qk[j] = req.topk
        return (jnp.asarray(qt), jnp.asarray(qp), jnp.asarray(ql),
                jnp.asarray(qr), jnp.asarray(qtemp), jnp.asarray(qk),
                jnp.int32(len(upload)))

    def _step_chunk_scan(self) -> List[Completion]:
        """One fused chunk with in-scan admission.

        The host first MIRRORS the device's schedule for up to
        ``chunk_steps`` steps — the same per-step sweep order the scan
        body runs (admit free slots from the FIFO lowest-index-first,
        decrement actives, retire exhausted slots in slot order) — which
        yields the exact chunk length, the admission consumption, and the
        done-buffer row → request mapping, all without touching the
        device.  Then: one refill dispatch, one fused chunk, one done-
        buffer read."""
        upload = list(itertools.islice(self.queue, self._queue_cap))
        sampling = self._chunk_sampling(list(self._occupant) + upload)
        occ = list(self._occupant)
        rem = list(self._remaining)
        qi = 0
        retired: List[Request] = []
        steps = busy_steps = 0
        for _ in range(self.chunk_steps):
            if qi >= len(upload) and all(o is None for o in occ):
                break                      # nothing left this chunk
            for i in range(self.slots):    # device Phase A: admission sweep
                if occ[i] is None and qi < len(upload):
                    req = upload[qi]
                    qi += 1
                    occ[i] = req
                    rem[i] = len(req.prompt) + req.gen - 1
            busy = [i for i in range(self.slots) if occ[i] is not None]
            busy_steps += len(busy)
            steps += 1
            for i in busy:                 # device Phase B: one decode step
                rem[i] -= 1
            for i in range(self.slots):    # device Phase C: retire + harvest
                if occ[i] is not None and rem[i] <= 0:
                    retired.append(occ[i])
                    occ[i] = None
                    rem[i] = 0
        if steps == 0:
            return []
        self.state = _refill_scan(self.state, *self._queue_arrays(upload))
        self.state = _run_steps(self._step, self.params, self.state, steps,
                                sampling)
        for _ in range(qi):
            self.queue.popleft()
        self._occupant, self._remaining = occ, rem
        self.steps += steps
        self.slot_steps += busy_steps
        out: List[Completion] = []
        if retired:
            # drain the done buffer — the once-per-request device read; row
            # order is the host-mirrored retirement order
            dt = np.asarray(self.state.done.tokens[:len(retired)])
            dl = np.asarray(self.state.done.prompt_logits[:len(retired)])
            db = np.asarray(self.state.done.bad[:len(retired)])
            for j, req in enumerate(retired):
                plen = len(req.prompt)
                out.append(Completion(
                    req.uid, dt[j, plen:plen + req.gen].copy(), dl[j],
                    bad=bool(db[j])))
        return out

    # -- main loop ----------------------------------------------------------

    def step_chunk(self) -> List[Completion]:
        """Advance the pool by one fused chunk; returns completions."""
        if self.admission == "scan":
            return self._step_chunk_scan()
        return self._step_chunk_boundary()

    def run(self) -> List[Completion]:
        """Drain the queue; returns every completion (match by uid)."""
        out: List[Completion] = []
        while self.queue or any(r is not None for r in self._occupant):
            out.extend(self.step_chunk())
        return out
