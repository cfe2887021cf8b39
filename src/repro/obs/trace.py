"""Host tracing: a span API with Chrome-trace/perfetto export and
``jax.profiler`` annotations, on the profiler's own clock.

Instrumented sites (wave bursts and their launch / overflow-check phases,
migrations, checkpoint save/restore, ServeEngine submit/refill) call
:func:`span` — a context manager that records an interval into the
module-level :data:`tracer` and, when jax is importable, also opens a
``jax.profiler.TraceAnnotation`` so the same names, with the same
arguments as stats, show up in an XLA profile.  A span records the span
it opened inside (``parent``), and the tracer keeps a per-name summary
(count, total, last) of the spans it recorded.  ``python -m repro.obs
--trace out.json`` (or :meth:`Tracer.export_chrome_trace` directly)
writes the recorded spans in the Chrome trace-event format that
``chrome://tracing`` and https://ui.perfetto.dev load natively.

One clock: spans are stamped with ``time.time_ns()`` — CLOCK_REALTIME,
the clock the profiler stamps its host events with.  A profile's host and
device events carry times relative to its ``profile_start_time`` (a stat
of its ``Task Environment`` plane, on that clock), so a span recorded
here, whether or not a profile was running, sits at ``ts * 1e3 -
profile_start_time`` ns on that profile's time axis.

This module stays jax-free at import time (the CLI forces the device
count before jax loads); jax is only touched lazily inside spans.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager


# -------------------------------------------------------------- tracer -----
def _profiler_annotation(name: str, args: dict):
    """A ``jax.profiler.TraceAnnotation`` when jax is around, else a
    no-op — imported lazily so the CLI can force devices first.  The
    arguments become the profile event's stats (the name stays bare)."""
    try:
        import jax.profiler
        return jax.profiler.TraceAnnotation(name, **args)
    except Exception:  # pragma: no cover - jax always present in CI
        from contextlib import nullcontext
        return nullcontext()


class Tracer:
    """Bounded span recorder with Chrome-trace export.

    Spans nest naturally (the trace viewer stacks same-thread ``X``
    events by time containment); each records the innermost span open on
    its thread as its ``parent``.  The event ring is bounded so an
    always-on tracer cannot grow without bound; the per-name summary
    holds one entry per span name.
    """

    def __init__(self, max_events: int = 65536, annotate: bool = True):
        self._events: deque = deque(maxlen=max_events)
        self.annotate = annotate
        self._open = threading.local()
        self._lock = threading.Lock()
        self._summary: dict = {}

    @contextmanager
    def span(self, name: str, cat: str = "repro", **args):
        stack = getattr(self._open, "names", None)
        if stack is None:
            stack = self._open.names = []
        if stack:
            args["parent"] = stack[-1]
        args = {k: _jsonable(v) for k, v in args.items()}
        ann = _profiler_annotation(name, args) if self.annotate else None
        stack.append(name)
        t0 = time.time_ns()
        if ann is not None:
            ann.__enter__()
        try:
            yield self
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            t1 = time.time_ns()
            stack.pop()
            self._events.append({
                "name": name, "cat": cat, "ph": "X", "ts": t0 / 1e3,
                "dur": (t1 - t0) / 1e3, "pid": os.getpid(),
                "tid": threading.get_ident() % (1 << 31), "args": args,
            })
            with self._lock:
                s = self._summary.setdefault(name, [0, 0, 0])
                s[0] += 1
                s[1] += t1 - t0
                s[2] = t1 - t0

    def events(self) -> list:
        return list(self._events)

    def summary(self) -> dict:
        """Per span name: ``count`` of spans recorded, their ``total_s``
        and the ``last_s`` one's duration, in seconds.  Unlike the event
        ring, it forgets nothing until :meth:`clear`."""
        with self._lock:
            return {n: {"count": c, "total_s": tot * 1e-9,
                        "last_s": last * 1e-9}
                    for n, (c, tot, last) in sorted(self._summary.items())}

    def clear(self):
        self._events.clear()
        with self._lock:
            self._summary.clear()

    def export_chrome_trace(self, path) -> str:
        """Write the recorded spans as Chrome trace-event JSON (loads in
        chrome://tracing and ui.perfetto.dev); returns the path."""
        doc = {"traceEvents": self.events(), "displayTimeUnit": "ms"}
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        return str(path)


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:
        return int(v)
    except Exception:
        return str(v)


tracer = Tracer()


@contextmanager
def span(name: str, cat: str = "repro", **args):
    """Record a span on the module-level :data:`tracer` (the instrumented
    wave/migration/checkpoint/serve sites all funnel through here).  The
    keyword arguments are kept with the span and, under a profile, become
    its event's stats."""
    with tracer.span(name, cat, **args):
        yield tracer
