"""Readings of the priority wave program: the device time of its two
dispatch phases, the least HBM traffic of its segscan sweep, and the
device time of its Pallas kernels.

Each traced burst is read against the compiled text of the program it
ran: the open loop dispatches programs of several widths, whose
instruction names repeat, often for different instructions, so one
table for all of them would misplace or drop ops.  A burst's device ops
are those inside its ``bench:dispatch`` span, which ends once the
program's results are on the host (the next burst starts after it).

A program that gives no compiled programs, or whose wave phases lack a
phase, reads None, so that the readers built on it leave the metric out.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from bench.program import self_time, window_spans
from bench.trace import instruction
from repro.analysis.hlo import scope_table
from repro.dqueue.wave_engine import WAVE_PHASES

# per op, the tier assignment reads its kind, validity and tier once and
# writes its tier position and its match once, 4 bytes a word
WORDS_READ = 3
WORDS_WRITTEN = 2
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
DISPATCH_SPAN = "bench:dispatch"


def work_bytes(ops: int) -> int:
    """Least HBM bytes the sweep needs for ``ops`` ops, whatever the
    kernel's padded shapes."""
    return 4 * (WORDS_READ + WORDS_WRITTEN) * int(ops)


def kernel_names(text: str) -> Set[str]:
    """Names of the Pallas kernels (``tpu_custom_call`` instructions) in
    a compiled program's text."""
    return {instruction(line)[0] for line in text.splitlines()
            if KERNEL_TARGET in line}


def own_ns(trace, lo: float, hi: float) -> Dict[str, float]:
    """Device self time of each instruction inside ``[lo, hi]`` and the
    traced window, averaged over the devices."""
    lo, hi = max(lo, trace.window[0]), min(hi, trace.window[1])
    per: Dict[str, float] = {}
    for d in trace.devices.values():
        keep = (d.end > lo) & (d.start < hi)
        own = self_time(np.maximum(d.start[keep], lo).astype(float),
                        np.minimum(d.end[keep], hi).astype(float))
        by_name = np.bincount(d.which[keep], weights=own,
                              minlength=len(d.names))
        for i in np.flatnonzero(by_name):
            per[d.names[i]] = per.get(d.names[i], 0.0) + float(by_name[i])
    n = len(trace.devices)
    return {k: v / n for k, v in per.items()}


def bursts(r) -> Optional[List[Tuple[str, Dict[str, float]]]]:
    """Per traced burst, in dispatch order: the compiled text of the
    program it ran and the device self time of each instruction inside
    its dispatch.  None without a trace, where the program gives no
    compiled programs, or where the traced window's dispatch spans do not
    pair with its bursts."""
    if r.trace is None or not r.counts["bursts"]:
        return None
    got = getattr(r, "_tier_bursts", None)   # one reduction for the readers
    if got is not None:
        return got
    programs = getattr(r.run.q, "wave_programs", None)
    if programs is None:
        return None
    # a burst's op arrays are [K, N]: the program's key holds their shapes
    texts = {key[1]: text for key, text in programs() if key[0] == "waves"}
    run = r.run
    traced = run.log.bursts[run.traced_from:
                            run.traced_from + run.traced_bursts]
    spans = sorted(window_spans(r, (DISPATCH_SPAN,)))
    shapes = [b.is_enq.shape for b in traced]
    if len(spans) != len(traced) or not set(shapes) <= set(texts):
        return None
    got = r._tier_bursts = [(texts[shape], own_ns(r.trace, s, e))
                            for shape, (s, e, _) in zip(shapes, spans)]
    return got


def phase_ms(r, phase: str) -> Optional[float]:
    """Device self time of one wave phase per traced burst, in ms, each
    burst's ops placed by its own program's table; None where the
    program's wave phases do not include it."""
    if phase not in WAVE_PHASES:
        return None
    got = bursts(r)
    if got is None:
        return None
    tables: Dict[str, Dict[str, str]] = {}
    ns = 0.0
    for text, own in got:
        if text not in tables:
            tables[text] = scope_table(text, WAVE_PHASES)
        ns += sum(t for name, t in own.items()
                  if tables[text].get(name) == phase)
    return ns / len(got) * 1e-6


def kernel_s(r) -> Optional[float]:
    """Device time of the Pallas kernels of the traced bursts, each
    burst's named from its own program's text, averaged over the
    devices, in s; None as :func:`bursts` is."""
    got = bursts(r)
    if got is None:
        return None
    names: Dict[str, Set[str]] = {}
    ns = 0.0
    for text, own in got:
        if text not in names:
            names[text] = kernel_names(text)
        ns += sum(t for name, t in own.items() if name in names[text])
    return ns * 1e-9
