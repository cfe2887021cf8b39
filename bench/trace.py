"""Reduction of the traced run: device op intervals from the profiler's
trace, the host spans on the same clock, and the arithmetic the per-layer
metrics share.

Intervals are ``(start_ns, end_ns)`` pairs.  The busy time of a device is
the length of the union of its op intervals; a kind of op's exposed time
is the part of its intervals' union that no other op covers.  A trace
holds millions of op events (every iteration of a loop in a program is
one), so they are kept as numpy arrays.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "bench:window"
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench:", "queue:", "pqueue:")


def _arrays(intervals) -> Tuple[np.ndarray, np.ndarray]:
    if isinstance(intervals, tuple) and len(intervals) == 2 and \
            isinstance(intervals[0], np.ndarray):
        return intervals
    a = np.asarray(list(intervals), float).reshape(-1, 2)
    return a[:, 0], a[:, 1]


def union(intervals) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted, disjoint union: ``(starts, ends)`` arrays."""
    s, e = _arrays(intervals)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    o = np.argsort(s, kind="stable")
    s, e = s[o], np.maximum.accumulate(e[o])
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], e[last]


def length(intervals) -> float:
    s, e = union(intervals)
    return float((e - s).sum())


def exposed(mine, others) -> float:
    """Length of ``mine``'s union that ``others`` leaves uncovered:
    |A| - |A and B| = |A or B| - |B|."""
    a, b = _arrays(mine), _arrays(others)
    both = (np.concatenate([a[0], b[0]]), np.concatenate([a[1], b[1]]))
    return length(both) - length(b)


def clip(intervals, lo: float, hi: float):
    s, e = _arrays(intervals)
    keep = (e > lo) & (s < hi)
    return np.maximum(s[keep], lo), np.minimum(e[keep], hi)


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between busy intervals."""
    s, e = union(clip(busy, lo, hi))
    starts = np.concatenate([[lo], e])
    ends = np.concatenate([s, [hi]])
    keep = ends > starts
    return list(zip(starts[keep].tolist(), ends[keep].tolist()))


@dataclass
class DeviceOps:
    """One device's op events: interval arrays and an index into
    ``names``/``kinds`` per event."""

    start: np.ndarray
    end: np.ndarray
    which: np.ndarray
    names: List[str]
    kinds: List[str]


@dataclass
class Trace:
    """Per device its ops, the host spans ``(start_ns, end_ns, name)``,
    and the traced window on the same clock."""

    devices: Dict[str, DeviceOps]
    host: List[Tuple[float, float, str]]
    window: Tuple[float, float]

    def ops(self, device: str, kind: str = None, not_kind: str = None):
        """One device's op intervals inside the window, optionally only
        (or all but) those whose instruction is of ``kind``."""
        d = self.devices[device]
        sel = np.ones(d.start.size, bool)
        if kind is not None or not_kind is not None:
            has = np.array([(kind or not_kind) in k for k in d.kinds]
                           + [False])
            hit = has[d.which]
            sel = hit if kind is not None else ~hit
        return clip((d.start[sel], d.end[sel]), *self.window)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self) -> float:
        """Busy time, averaged over the devices."""
        return float(np.mean([length(self.ops(d)) for d in self.devices]))

    def exposed_ns(self, kind: str) -> float:
        """Time ops of ``kind`` run with no other op running, averaged
        over the devices."""
        return float(np.mean([exposed(self.ops(d, kind=kind),
                                      self.ops(d, not_kind=kind))
                              for d in self.devices]))

    def kind_ns(self, kind: str) -> float:
        """Summed duration of the ops of ``kind``, averaged over the
        devices."""
        out = []
        for d in self.devices:
            s, e = self.ops(d, kind=kind)
            out.append(float((e - s).sum()))
        return float(np.mean(out))

    def top_ops(self, n: int = 10) -> List[list]:
        """The instructions that took most device time inside the window,
        in seconds, summed over the devices."""
        tot: Dict[str, float] = {}
        lo, hi = self.window
        for d in self.devices.values():
            dur = np.clip(np.minimum(d.end, hi) - np.maximum(d.start, lo),
                          0, None)
            per = np.bincount(d.which, weights=dur, minlength=len(d.names))
            for i in np.flatnonzero(per):
                tot[d.names[i]] = tot.get(d.names[i], 0.0) + float(per[i])
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def idle_by_host_span(self, n: int = 10) -> List[list]:
        """Idle time of the first device, each gap put to the innermost
        host span that covers its middle, summed per span name, in
        seconds; ``(none)`` where no span covers it."""
        dev = sorted(self.devices)[0]
        spans = sorted(h for h in self.host if h[2] != WINDOW_SPAN)
        starts = [s for s, _, _ in spans]
        tot: Dict[str, float] = {}
        for s, e in gaps(self.ops(dev), *self.window):
            mid = (s + e) / 2
            label = "(none)"
            # nested spans: the innermost cover is the latest to start
            i = bisect.bisect_right(starts, mid) - 1
            for hs, he, name in spans[max(0, i - 64):i + 1][::-1]:
                if he >= mid:
                    label = name
                    break
            tot[label] = tot.get(label, 0.0) + (e - s)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]


def load(profile_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``profile_dir``: each device
    plane's ``XLA Ops`` line, and the host spans of the benchmark and of
    the program."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {profile_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = _device_ops(line for line in plane.lines
                              if line.name == OPS_LINE)
            if ops is not None:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host += [(ev.start_ns, ev.end_ns, ev.name)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(SPAN_PREFIXES)]
    win = [(s, e) for s, e, name in host if name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError("the trace has no device op events")
    return Trace(devices, host, win[0])


def _device_ops(lines):
    index: Dict[str, int] = {}
    names, kinds = [], []
    start, end, which = [], [], []
    for line in lines:
        for ev in line.events:
            text = ev.name
            i = index.get(text)
            if i is None:
                i = index[text] = len(names)
                name, kind = instruction(text)
                names.append(name)
                kinds.append(kind)
            start.append(ev.start_ns)
            end.append(ev.end_ns)
            which.append(i)
    if not start:
        return None
    return DeviceOps(np.array(start), np.array(end), np.array(which),
                     names, kinds)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


def instruction(text: str) -> Tuple[str, str]:
    """Name and kind of one instruction of a compiled module's text, as
    a device op event names it: ``"%fusion.3 = s32[8]{0} fusion(...)"``
    gives ``("fusion.3", "fusion")``; a custom call's kind carries its
    target (``custom-call:tpu_custom_call``).  Text that is not an
    instruction is its own name, of no kind."""
    m = _INSTR.match(text)
    if not m:
        return text, ""
    rest = m.group(2)
    if rest.startswith("("):          # a tuple shape: skip its parentheses
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:                             # an array shape has no spaces
        rest = rest.partition(" ")[2]
    op = rest.lstrip().partition("(")[0].strip()
    t = re.search(r'custom_call_target="([^"]+)"', rest)
    return m.group(1), op + (":" + t.group(1) if t else "")
