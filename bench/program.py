"""Readings of what the program records about itself: its host spans in
the traced window, the self time of its wave phases on the device, and
its compile counter.

Each reading returns None where the program records no such thing, so
that the readers built on them leave a metric out for a program that
lacks the instrumentation.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

NONE = "(none)"


CHANGE_SPANS = ("migration:shrink", "migration:grow")


def window_spans(r, names) -> Optional[list]:
    """The program's host spans called one of ``names`` that lie in the
    traced window, as ``(start_ns, end_ns, name)``; None without a
    trace."""
    if r.trace is None:
        return None
    lo, hi = r.trace.window
    return [h for h in r.trace.host if h[2] in names and h[0] >= lo
            and h[1] <= hi]


def change_device_ns(r) -> Optional[List[float]]:
    """Per membership change in the traced window, the device busy time
    inside its ``migration:shrink`` or ``migration:grow`` span, averaged
    over the devices (no wave runs while a change does); None without a
    trace or without such spans."""
    spans = window_spans(r, CHANGE_SPANS)
    if not spans:
        return None
    return [r.trace.busy_between_ns(s, e) for s, e, _ in spans]


def span_ms(r, name: str) -> Optional[float]:
    """Mean duration, in ms, of the host spans called ``name`` that lie in
    the traced window (the program's spans carry one per burst); None
    without a trace or without such spans."""
    spans = window_spans(r, (name,))
    return 1e-6 * float(np.mean([e - s for s, e, _ in spans])) \
        if spans else None


def self_time(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Per event, the time in which it is the innermost event running:
    each instant of the union of the intervals goes to the event that
    covers it and started last (for nested events, the innermost one).
    The self times add up to the length of the union."""
    order = np.lexsort((-end, start))
    s, e = start[order].tolist(), end[order].tolist()
    own = [0.0] * len(s)
    stack: List[int] = []
    t = float("-inf")
    for i, si in enumerate(s):
        while stack and e[stack[-1]] <= si:
            j = stack.pop()
            if e[j] > t:
                own[j] += e[j] - t
                t = e[j]
        if stack and si > t:
            own[stack[-1]] += si - t
        t = max(t, si)
        stack.append(i)
    while stack:
        j = stack.pop()
        if e[j] > t:
            own[j] += e[j] - t
            t = e[j]
    out = np.empty(len(s))
    out[order] = own
    return out


def merge_tables(tables) -> Dict[str, str]:
    """One instruction -> phase table from the program's tables (one per
    program it ran); a name two programs put to different phases is
    left out."""
    merged: Dict[str, str] = {}
    clash = set()
    for t in tables:
        for name, phase in t.items():
            if merged.setdefault(name, phase) != phase:
                clash.add(name)
    for name in clash:
        del merged[name]
    return merged


def phase_ns(trace, table: Dict[str, str]) -> Dict[str, float]:
    """Device self time in the traced window by wave phase, ``(none)``
    for ops the table does not place, averaged over the devices.  The
    phases add up to ``trace.busy_ns()``."""
    lo, hi = trace.window
    per: Dict[str, float] = {}
    for d in trace.devices.values():
        keep = (d.end > lo) & (d.start < hi)
        own = self_time(np.maximum(d.start[keep], lo).astype(float),
                        np.minimum(d.end[keep], hi).astype(float))
        by_name = np.bincount(d.which[keep], weights=own,
                              minlength=len(d.names))
        for i in np.flatnonzero(by_name):
            phase = table.get(d.names[i], NONE)
            per[phase] = per.get(phase, 0.0) + float(by_name[i])
    n = len(trace.devices)
    return {k: v / n for k, v in per.items()}


def wave_phase_ms(r, phase: str) -> Optional[float]:
    """Device self time of one wave phase per measured burst, in ms,
    averaged over the chips; None without a trace, or where the program
    gives no phase table."""
    if r.trace is None or not r.counts["bursts"]:
        return None
    got = getattr(r, "_wave_phase_ns", None)   # one reduction for the readers
    if got is None:
        phases = getattr(r.run.q, "wave_phases", None)
        if phases is None:
            return None
        got = r._wave_phase_ns = phase_ns(r.trace, merge_tables(phases()))
    return got.get(phase, 0.0) / r.counts["bursts"] * 1e-6


def compile_s() -> Optional[float]:
    """Seconds of backend compiles and persistent-cache loads of every
    program the process built since the program's compile counter was
    installed; None where the program has no such counter."""
    try:
        from repro.analysis.recompile import CompilationTracker
    except ImportError:
        return None
    by_program = getattr(CompilationTracker, "by_program", None)
    if by_program is None:
        return None
    return sum(p["compile_s"] + p["load_s"] for p in by_program().values())
