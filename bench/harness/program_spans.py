"""Arithmetic over the program's own spans and their counts (``repro.obs``),
shared by the per-layer metric readers in ``bench/metrics``.

The program records only while a profiler session collects, and keeps a
span only if it lies wholly inside the session, so in a traced run it holds
the whole spans of the traced window.  Each metric is a sum over the spans
of one unit (a chunk, a pass) over the number of those units: what a reader
sums is taken only from inside a recorded unit span, so the window's edges
cut the numerator where they cut the divisor.

``snapshot`` returns None when the run was not traced, or when the program
records nothing (a checkout without ``repro.obs``); each reader then
returns None.
"""

from __future__ import annotations

from typing import Iterable, Optional

from bench.harness import trace


def snapshot(data) -> Optional[dict]:
    if data.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    got = obs.collected()
    return got if got["spans"] else None


def under(snap: dict, unit: str, name: str) -> list:
    """The spans called ``name`` that lie inside a recorded span called
    ``unit`` (the ``unit`` spans themselves when ``name`` is ``unit``)."""
    by_seq = {s["seq"]: s for s in snap["spans"]}
    out = []
    for s in snap["spans"]:
        if s["name"] != name:
            continue
        p = s
        while p is not None and p["name"] != unit:
            p = by_seq.get(p["parent"])
        if p is not None:
            out.append(s)
    return out


def self_ns(snap: dict, spans: list, less: Iterable[str]) -> int:
    """Summed duration of ``spans``, less the time their descendants called
    one of ``less`` cover."""
    less = set(less)
    by_seq = {s["seq"]: s for s in snap["spans"]}
    mine = {s["seq"] for s in spans}
    inner: dict = {}
    for s in snap["spans"]:
        if s["name"] not in less:
            continue
        p = by_seq.get(s["parent"])
        while p is not None and p["seq"] not in mine:
            p = by_seq.get(p["parent"])
        if p is not None:
            inner.setdefault(p["seq"], []).append(
                (s["name"], s["start_ns"], s["end_ns"]))
    out = 0
    for s in spans:
        a, b = s["start_ns"], s["end_ns"]
        out += (b - a) - trace.busy_ns(inner.get(s["seq"], ()), a, b)
    return out


def per_unit(data, unit: str, value):
    """``value(snap)`` over the number of recorded ``unit`` spans; None
    without a snapshot or without such spans."""
    snap = snapshot(data)
    if snap is None:
        return None
    n = len(under(snap, unit, unit))
    return value(snap) / n if n else None


def count_per(data, unit: str, counter: str):
    """Count ``counter`` of the ``unit`` spans, per span."""
    return per_unit(data, unit, lambda s: sum(
        u["counts"].get(counter, 0) for u in under(s, unit, unit)))


def spans_per(data, unit: str, name: str):
    """Spans called ``name`` inside the ``unit`` spans, per unit span."""
    return per_unit(data, unit, lambda s: len(under(s, unit, name)))


def ms_per(data, unit: str, name: str, less: Iterable[str] = ()):
    """Time of the spans called ``name`` inside the ``unit`` spans, less
    their descendants called one of ``less``, in ms per unit span."""
    return per_unit(data, unit, lambda s: self_ns(
        s, under(s, unit, name), less) / 1e6)


def share_pct(data, unit: str, part: str, whole: str):
    """Count ``part`` over count ``whole`` of the ``unit`` spans, in %."""
    snap = snapshot(data)
    if snap is None:
        return None
    units = under(snap, unit, unit)
    w = sum(u["counts"].get(whole, 0) for u in units)
    if not w:
        return None
    return 100.0 * sum(u["counts"].get(part, 0) for u in units) / w
