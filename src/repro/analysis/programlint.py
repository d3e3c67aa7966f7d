"""Layer 1: program lint over traced jaxprs and lowered/compiled HLO.

The registered hot entry points — the per-family fused decode scan, the
single decode step, the engine admission dispatches, and the TT-contraction
dispatch — are traced exactly as the serving stack jits them (same jit
wrappers, same donate/static argnums), then checked:

  PRG001  dtype discipline: no f64/c128 anywhere in the closed jaxpr or its
          lowering, and no weight-sized f32 closure constants (a TT core or
          dense bank silently materialized/upcast into the trace)
  PRG002  no host round-trips: no callback/infeed/outfeed/device_put
          primitives inside traced entry points (scan bodies included)
  PRG003  donation honored: every buffer engine.py marks donated shows
          input/output aliasing in the lowering (and, for the compiled
          representative, in the optimized HLO)
  PRG004  VMEM tile plans: every registered TT-contraction serving shape
          clears the fused kernels' VMEM gate at some candidate tile cap —
          sharing ``ops._fits_vmem`` so the gate and the lint can't diverge

Tracing is per-entry lazy: ``--fast`` covers one transformer arch plus the
TT/int8 variants and the admission paths; the full sweep adds every family
in the zoo (the CI lane runs full).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.analysis.base import Finding, Rule, register

PRG001 = register(Rule(
    "PRG001", "program", "f64 / weight-sized f32 const in traced entry",
    "hot entry points stay in bf16/f32 with no f64 promotion and no "
    "weight-sized float closure constants — an accidental upcast or dense "
    "materialization undoes the TT compression win without failing a test",
    guarded_since="PR 2 (TT dispatch), PR 7 (int8 cores)",
))
PRG002 = register(Rule(
    "PRG002", "program", "host callback/transfer in traced entry",
    "no callback / infeed / outfeed / device_put primitives inside the "
    "fused scan or admission dispatches — one host round-trip per step "
    "destroys the fused driver's dispatch amortization",
    guarded_since="PR 4 (fused decode driver)",
))
PRG003 = register(Rule(
    "PRG003", "program", "donation not honored",
    "buffers engine.py marks donated must show input/output aliasing in "
    "the lowering — dropped donation doubles the cache pool's memory and "
    "defeats in-place chunk updates",
    guarded_since="PR 5 (continuous batching engine)",
))
PRG004 = register(Rule(
    "PRG004", "program", "TT shape flunks the VMEM gate",
    "every registered TT-contraction serving shape must clear the fused "
    "kernels' VMEM gate at some candidate tile cap (shared _fits_vmem), "
    "or it silently rides the unfused fallback",
    guarded_since="PR 3 (fused TT kernels), PR 6 (adaptive tile caps)",
))

_BIG_CONST_ELEMS = 1 << 16      # weight-sized: ≥64Ki elements
_F64_LOWERED_RE = re.compile(r"[<x]f64\b")   # tensor<4xf64> / tensor<f64>


# --------------------------------------------------------------------------
# entry registry
# --------------------------------------------------------------------------

@dataclasses.dataclass
class EntryReport:
    """Artifacts of one traced entry point."""

    name: str
    jaxpr: object                      # jax.core.ClosedJaxpr
    lowered: Optional[str]             # StableHLO text
    compiled: Optional[str]            # optimized HLO text (representative)
    donated: bool                      # engine marks a donated argument


FAST_ARCH = "qwen1.5-0.5b"
FAMILY_ARCHS = (
    "gemma3-1b",              # transformer (dense)
    "seamless-m4t-large-v2",  # encdec
    "mamba2-1.3b",            # ssm
    "recurrentgemma-2b",      # hybrid
    "olmoe-1b-7b",            # moe expert banks
)


def _reduced(arch: str, weights: str = "dense"):
    from repro.configs import get_config
    from repro.models.registry import build
    import jax

    cfg = get_config(arch).reduced()
    model = build(cfg)
    if weights == "dense":
        return cfg, model, model.init(jax.random.PRNGKey(0))
    from repro.core import CompressionPolicy, TTCompressor, spectral_decay_pytree
    from repro.models import common as model_common
    params = spectral_decay_pytree(model.init(jax.random.PRNGKey(0)))
    comp = TTCompressor(CompressionPolicy(eps=0.2, min_size=8192))
    payload, _ = comp.compress(params)
    quant = "int8" if weights == "tt-int8" else None
    return cfg, model, model_common.tt_native_params(
        payload, family=cfg.family, quant=quant)


def _trace_gen_scan(arch: str, weights: str, compile_entry: bool) -> EntryReport:
    """The fused chunk dispatch exactly as the engine jits it
    (``engine._run_steps``: static decode/steps/sampling, donated state)."""
    import jax.numpy as jnp
    from repro.launch import engine
    from repro.models import common as model_common

    cfg, model, params = _reduced(arch, weights)
    b, t_max, plen = 2, 10, 4
    tokens = np.zeros((b, t_max), np.int32)
    state = model_common.gen_init(
        model.init_cache(b, t_max), tokens, plen, t_max,
        cfg.padded_vocab_size, rng=jnp.zeros((b, 2), jnp.uint32),
    )
    tr = engine._run_steps.trace(
        model.decode_step, params, state, 3, model_common.GREEDY)
    low = tr.lower()
    compiled = low.compile().as_text() if compile_entry else None
    suffix = "" if weights == "dense" else f"-{weights}"
    return EntryReport(f"gen_scan/{arch}{suffix}", tr.jaxpr,
                       low.as_text(), compiled, donated=True)


def _trace_decode_step(arch: str) -> EntryReport:
    """One decode step as the python-loop oracle jits it
    (``engine._decode_fn``: donated cache)."""
    import jax.numpy as jnp
    from repro.launch import engine

    _, model, params = _reduced(arch)
    cache = model.init_cache(2, 10)
    tr = engine._decode_fn(model).trace(
        params, cache, jnp.zeros((2, 1), jnp.int32))
    return EntryReport(f"decode_step/{arch}", tr.jaxpr,
                       tr.lower().as_text(), None, donated=True)


def _admission_entries(arch: str) -> Iterator[EntryReport]:
    """The engine's donated admission dispatches against a live engine
    state (scan admission: queue + done buffer attached)."""
    import jax.numpy as jnp
    from repro.launch.engine import (
        Engine, _admit_slot, _deactivate_slot, _refill_scan,
    )

    _, model, params = _reduced(arch)
    eng = Engine(model, params, slots=2, max_len=10, chunk_steps=2,
                 admission="scan")
    state = eng.state
    row = jnp.zeros((eng.max_len,), jnp.int32)
    key = jnp.zeros((2,), jnp.uint32)
    tr = _admit_slot.trace(state, 0, row, 3, 8, key,
                           jnp.float32(0.0), jnp.int32(0))
    yield EntryReport("admit/_admit_slot", tr.jaxpr, tr.lower().as_text(),
                      None, donated=True)
    tr = _deactivate_slot.trace(state, 0)
    yield EntryReport("admit/_deactivate_slot", tr.jaxpr,
                      tr.lower().as_text(), None, donated=True)
    q = state.queue
    tr = _refill_scan.trace(state, q.tokens, q.prompt_len, q.total_len,
                            q.rng, q.temp, q.topk, q.size)
    yield EntryReport("admit/_refill_scan", tr.jaxpr, tr.lower().as_text(),
                      None, donated=True)


def _trace_tt_contract(shape: "TTShape") -> EntryReport:
    """The TT-contraction dispatch at a registered serving shape (fused
    path: the VMEM gate must pass, see PRG004)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.tt_contract import ops

    x2 = jnp.zeros((shape.b, shape.n_in()), jnp.float32)
    cores = [jnp.zeros(s, _np_dtype(dt))
             for s, dt in zip(shape.cores, shape.dtypes)]
    scales = ([jnp.ones((s[0],), jnp.float32) if dt == "int8" else None
               for s, dt in zip(shape.cores, shape.dtypes)]
              if any(dt == "int8" for dt in shape.dtypes) else None)

    def run(x2, *cores):
        return ops.tt_contract(x2, cores, shape.split, scales=scales)

    tr = jax.jit(run).trace(x2, *cores)
    return EntryReport(f"tt_contract/{shape.name}", tr.jaxpr,
                       tr.lower().as_text(), None, donated=False)


def iter_entries(fast: bool = False
                 ) -> Iterator[Tuple[str, Callable[[], EntryReport]]]:
    """(name, lazy builder) for every registered entry point.

    The builder defers the expensive init/trace until the runner asks, so
    rule filtering and ``--fast`` skip work they don't need.
    """
    archs = (FAST_ARCH,) if fast else (FAST_ARCH,) + FAMILY_ARCHS
    for i, arch in enumerate(archs):
        # compile exactly one representative (the cheap fast arch) to check
        # aliasing survives XLA optimization, not just lowering
        yield (f"gen_scan/{arch}",
               lambda a=arch, c=(i == 0): _trace_gen_scan(a, "dense", c))
    yield (f"gen_scan/{FAST_ARCH}-tt",
           lambda: _trace_gen_scan(FAST_ARCH, "tt", False))
    if not fast:
        yield (f"gen_scan/{FAST_ARCH}-tt-int8",
               lambda: _trace_gen_scan(FAST_ARCH, "tt-int8", False))
    yield (f"decode_step/{FAST_ARCH}",
           lambda: _trace_decode_step(FAST_ARCH))
    yield ("admission", lambda: list(_admission_entries(FAST_ARCH)))
    for shape in REGISTERED_TT_SHAPES[: 2 if fast else None]:
        yield (f"tt_contract/{shape.name}",
               lambda s=shape: _trace_tt_contract(s))


# --------------------------------------------------------------------------
# PRG004 — registered TT serving shapes vs the shared VMEM gate
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TTShape:
    """One lead-absorbed TT chain shape the serving stack dispatches."""

    name: str
    b: int                              # flattened batch·token extent
    cores: Tuple[Tuple[int, ...], ...]  # [(n1, r1), (r, n, s)...], last s==1
    split: int
    dtypes: Tuple[str, ...]             # per-core storage dtype

    def n_in(self) -> int:
        # split counts input modes: the lead core's n1 plus the middle mode
        # of each further input-side core (see kernels/tt_contract/ref.py)
        ins = self.cores[0][0]
        for s in self.cores[1:self.split]:
            ins *= s[1]
        return ins

    def n_out(self) -> int:
        out = 1
        for s in self.cores[self.split:]:
            out *= s[1]
        return out


def _shape2(name, b, n1, r1, n2, dtypes=("f32", "f32")):
    return TTShape(name, b, ((n1, r1), (r1, n2, 1)), 1, dtypes)


REGISTERED_TT_SHAPES: Tuple[TTShape, ...] = (
    # decode-extent (B = slots) and prefill-extent (B = tokens) chains at
    # full-size factorizations; int8 variants store cores at 1 byte/elem
    _shape2("decode-2core", 8, 1152, 64, 4608),
    _shape2("prefill-2core", 2048, 1024, 48, 4096),
    _shape2("prefill-2core-int8", 2048, 1024, 48, 4096, ("int8", "int8")),
    TTShape("decode-3core-split1", 8,
            ((64, 48), (48, 32, 24), (24, 72, 1)), 1,
            ("f32", "f32", "f32")),
    TTShape("prefill-3core-split2", 1024,
            ((64, 32), (32, 32, 16), (16, 96, 1)), 2,
            ("f32", "f32", "f32")),
    TTShape("expert-tile-3core-int8", 128,
            ((512, 32), (32, 64, 16), (16, 32, 1)), 2,
            ("int8", "int8", "int8")),
)


def _np_dtype(name: str):
    return {"f32": np.float32, "bf16": np.float32, "int8": np.int8}[name]


def check_vmem_shapes(shapes: Sequence[TTShape] = REGISTERED_TT_SHAPES,
                      ) -> List[Finding]:
    """PRG004: each registered shape must clear ``ops._fits_vmem`` at some
    candidate cap from ``resolve_tile_cap`` — the exact dispatch loop."""
    from repro.kernels.tt_contract import ops

    findings = []
    for shape in shapes:
        x2 = np.zeros((shape.b, shape.n_in()), np.float32)
        cores = [np.zeros(s, _np_dtype(dt))
                 for s, dt in zip(shape.cores, shape.dtypes)]
        caps = ops.resolve_tile_cap(shape.b)
        fit = next((c for c in caps
                    if ops._fits_vmem(x2, cores, shape.n_out(), shape.split,
                                      c)), None)
        if fit is None:
            findings.append(Finding(
                "PRG004", f"entry:tt_contract/{shape.name}", 0,
                f"no candidate tile cap {tuple(caps)} fits the VMEM budget "
                f"for cores {shape.cores} at B={shape.b} — this registered "
                f"serving shape would silently ride the unfused fallback",
            ))
    return findings


# --------------------------------------------------------------------------
# jaxpr / HLO checks
# --------------------------------------------------------------------------

def _iter_jaxprs(jaxpr) -> Iterator[object]:
    """The jaxpr and every sub-jaxpr reachable through eqn params (scan
    bodies, cond branches, pjit calls, custom_vjp closures, ...)."""
    from jax._src import core as jcore

    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if isinstance(j, jcore.ClosedJaxpr):
            j = j.jaxpr
        if not isinstance(j, jcore.Jaxpr) or id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(sub, (jcore.Jaxpr, jcore.ClosedJaxpr)):
                        stack.append(sub)


def _check_dtypes(rep: EntryReport) -> List[Finding]:
    findings = []
    flagged: Set[str] = set()
    for j in _iter_jaxprs(rep.jaxpr):
        for eqn in j.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                dt = str(getattr(aval, "dtype", ""))
                if dt in ("float64", "complex128") and dt not in flagged:
                    flagged.add(dt)
                    findings.append(Finding(
                        "PRG001", f"entry:{rep.name}", 0,
                        f"{dt} value in primitive {eqn.primitive.name!r} — "
                        f"double precision in a hot entry point (x64 leak?)",
                    ))
    consts = getattr(rep.jaxpr, "consts", ()) or ()
    for c in consts:
        dt = str(getattr(c, "dtype", ""))
        size = int(getattr(c, "size", 0) or 0)
        if dt in ("float32", "float64") and size >= _BIG_CONST_ELEMS:
            findings.append(Finding(
                "PRG001", f"entry:{rep.name}", 0,
                f"weight-sized {dt} constant ({size} elems) closed over the "
                f"trace — a TT core or weight bank materialized/upcast into "
                f"the program instead of riding as a compressed argument",
            ))
    if rep.lowered and _F64_LOWERED_RE.search(rep.lowered):
        findings.append(Finding(
            "PRG001", f"entry:{rep.name}", 0,
            "f64 tensor type in the lowered StableHLO",
        ))
    if rep.compiled:
        from repro.roofline import hlo_walk
        f64 = {dt for dt, _ in hlo_walk.iter_shapes(rep.compiled)
               if dt in ("f64", "c128")}
        if f64:
            findings.append(Finding(
                "PRG001", f"entry:{rep.name}", 0,
                f"{sorted(f64)} buffers in the optimized HLO",
            ))
    return findings


_BANNED_PRIMS = {"infeed", "outfeed", "device_put"}


def _is_host_eqn(eqn) -> bool:
    """A primitive that reaches the host: a banned transfer, any callback,
    or anything carrying a debug effect.  ``jax.debug.print`` traces to its
    own ``debug_print`` primitive (no "callback" in the name), so the name
    alone misses it; its effect does not."""
    name = eqn.primitive.name
    if name in _BANNED_PRIMS or "callback" in name:
        return True
    return any(type(e).__name__ in ("DebugEffect", "OrderedDebugEffect")
               for e in eqn.effects)


def _check_callbacks(rep: EntryReport) -> List[Finding]:
    findings = []
    flagged: Set[str] = set()
    for j in _iter_jaxprs(rep.jaxpr):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if _is_host_eqn(eqn) and name not in flagged:
                flagged.add(name)
                findings.append(Finding(
                    "PRG002", f"entry:{rep.name}", 0,
                    f"host primitive {name!r} inside a traced entry point — "
                    f"a host round-trip per step defeats the fused driver",
                ))
    return findings


def _check_donation(rep: EntryReport) -> List[Finding]:
    if not rep.donated:
        return []
    findings = []
    if rep.lowered is not None and "tf.aliasing_output" not in rep.lowered:
        findings.append(Finding(
            "PRG003", f"entry:{rep.name}", 0,
            "entry is marked donated but its lowering carries no "
            "tf.aliasing_output attribute — donation was dropped (shape/"
            "dtype mismatch between the donated operand and any output?)",
        ))
    if rep.compiled is not None and "input_output_alias" not in rep.compiled:
        findings.append(Finding(
            "PRG003", f"entry:{rep.name}", 0,
            "optimized HLO carries no input_output_alias — XLA discarded "
            "the donation",
        ))
    return findings


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------

_CHECKS = (_check_dtypes, _check_callbacks, _check_donation)


def run(fast: bool = False, rules: Optional[Set[str]] = None,
        entries: Optional[Sequence[str]] = None) -> List[Finding]:
    """Trace the registered entries and apply PRG001–PRG004.

    ``rules`` restricts rule IDs; ``entries`` restricts entry names by
    substring match.  A build/trace failure is itself reported (rule
    ``ERROR``) so one broken family can't silently mask the rest.
    """
    want = rules or {"PRG001", "PRG002", "PRG003", "PRG004"}
    findings: List[Finding] = []
    need_trace = want & {"PRG001", "PRG002", "PRG003"}
    if need_trace:
        for name, build in iter_entries(fast):
            if entries and not any(e in name for e in entries):
                continue
            try:
                built = build()
            except Exception as e:  # noqa: BLE001 - surfaced as a finding
                findings.append(Finding(
                    "ERROR", f"entry:{name}", 0,
                    f"failed to build/trace: {type(e).__name__}: {e}"))
                continue
            reports = built if isinstance(built, list) else [built]
            for rep in reports:
                for check, rid in zip(_CHECKS,
                                      ("PRG001", "PRG002", "PRG003")):
                    if rid in want:
                        findings.extend(check(rep))
    if "PRG004" in want:
        findings.extend(check_vmem_shapes())
    return findings
