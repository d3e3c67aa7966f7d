"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps named by the host span that was open during them.

A trace is reduced to two lists of ``(name, start_ns, end_ns)``:

* ``ops``: device operations, from the ``XLA Ops`` line of every device
  plane (``/device:TPU:n``), named by ``op_stem`` and without the
  control-flow ops that enclose others;
* ``spans``: host spans, from every line of the host plane whose event
  names the benchmark wrote with ``jax.profiler.TraceAnnotation``.

Everything below works on those lists, so the tests can feed a small
recorded trace without the profiler.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]            # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
NO_SPAN = "no-host-span"
# control-flow ops whose event spans the ops of their body: left out, so
# that busy time is the time in which a leaf operation ran
CONTAINERS = ("while", "cond", "conditional", "call")


def op_stem(name: str) -> str:
    """``%tt_contract_3.35 = f32[...] custom-call(...)`` -> ``tt_contract_3``:
    the HLO op's name without its instance number or clone suffix."""
    parts = name.split(" = ", 1)[0].strip().lstrip("%").split(".")
    while len(parts) > 1 and (parts[-1].isdigit()
                              or parts[-1].startswith("clone")):
        parts.pop()
    return ".".join(parts)


def load_xplane(trace_dir: str, span_names: Iterable[str]):
    """(ops per device plane, host spans) from the newest ``.xplane.pb``
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    wanted = set(span_names)
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        stem = op_stem(e.name)
                        if stem not in CONTAINERS:
                            evs.append((stem, int(e.start_ns),
                                        int(e.start_ns + e.duration_ns)))
            ops[plane.name] = sorted(evs, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name in wanted)
    return ops, sorted(spans, key=lambda e: e[1])


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Event]:
    """Events cut to the window ``[t0, t1)``; those outside dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def merge(events: Sequence[Event]) -> List[Tuple[int, int]]:
    """Union of the events' intervals, as sorted disjoint intervals."""
    out: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], t0: int, t1: int) -> int:
    return sum(e - s for s, e in merge(clip(events, t0, t1)))


def gaps(events: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Intervals of ``[t0, t1)`` in which no device operation ran."""
    out, cur = [], t0
    for s, e in merge(clip(events, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def seconds_by_name(events: Sequence[Event], t0: int, t1: int,
                    match=None) -> Dict[str, float]:
    """Device seconds per operation name in the window (``match`` filters
    names)."""
    tot: Dict[str, float] = collections.Counter()
    for name, s, e in clip(events, t0, t1):
        if match is None or match(name):
            tot[name] += (e - s) / 1e9
    return dict(tot)


def span_segments(spans: Sequence[Event]) -> List[Event]:
    """The timeline cut at every span edge, each piece named by the
    innermost (shortest) span open over it; pieces under no span are
    left out."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda ev: ev[1])
    out: List[Event] = []
    open_: List[Event] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            open_.append(by_start[i])
            i += 1
        open_ = [ev for ev in open_ if ev[2] > a]
        if open_:
            name = min(open_, key=lambda ev: ev[2] - ev[1])[0]
            out.append((name, a, b))
    return out


def name_gaps(gap_list: Sequence[Tuple[int, int]],
              spans: Sequence[Event]) -> Dict[str, float]:
    """Idle seconds per host span: each instant of a gap goes to the
    innermost span open then; time under no span goes to ``NO_SPAN``."""
    out: Dict[str, float] = collections.Counter()
    segs = span_segments(spans)
    j = 0
    for gs, ge in gap_list:
        named = 0
        while j < len(segs) and segs[j][2] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][1] < ge:
            name, s, e = segs[k]
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0:
                out[name] += overlap / 1e9
                named += overlap
            k += 1
        if ge - gs > named:
            out[NO_SPAN] += (ge - gs - named) / 1e9
    return dict(out)


def reduce(ops: Dict[str, List[Event]], spans: Sequence[Event],
           t0: int, t1: int, top: int = 10) -> dict:
    """Busy seconds averaged over the device planes, per-op seconds summed
    over them, and idle seconds by host span, for the window."""
    planes = [evs for evs in ops.values() if evs]
    if not planes:
        raise ValueError("trace has no device operations")
    busy = sum(busy_ns(evs, t0, t1) for evs in planes) / len(planes) / 1e9
    by_op: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    for evs in planes:
        by_op.update(seconds_by_name(evs, t0, t1))
        idle.update(name_gaps(gaps(evs, t0, t1), spans))
    idle = {k: v / len(planes) for k, v in idle.items()}
    return {
        "busy_s": busy,
        "window_s": (t1 - t0) / 1e9,
        "ops_s": dict(by_op),
        "idle_s": idle,
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[n, s] for n, s in sorted(
                idle.items(), key=lambda kv: -kv[1])[:top]],
        },
    }

