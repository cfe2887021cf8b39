"""Wavescope: observability for the Skueue wave runtime.

Four layers, one package:

1. ``obs.device``   — the donated device-side metrics ring the
   :class:`~repro.dqueue.wave_engine.WaveEngine` fills with ZERO extra
   collectives (every row field is arithmetic on values the wave already
   materializes); drained to host only at burst boundaries.
2. ``obs.trace``    — a span API with ``jax.profiler`` annotations, a
   per-name summary and Chrome-trace/perfetto JSON export, stamped on
   the profiler's clock.
3. ``obs.recorder`` — the flight recorder: the last K wave summaries,
   attached to :class:`~repro.dqueue.errors.QueueOverflowError` as the
   occupancy trajectory that led to the failure.
4. ``obs.export``   — JSON / Prometheus-text emitters for
   :meth:`~repro.serve.engine.ServeEngine.metrics` snapshots.

CLI: ``python -m repro.obs --smoke`` (forced multi-device CPU smoke run
printing a live snapshot; ``--trace out.json`` also writes a perfetto
trace).  Imported lazily so the CLI can pin ``XLA_FLAGS`` device forcing
*before* jax loads.
"""
from typing import Any

__all__ = [
    "METRIC_HEAD", "MetricsState", "init_metrics_state", "record_row",
    "drain", "row_width",
    "Tracer", "tracer", "span",
    "FlightRecorder",
    "to_json", "to_prometheus",
]

_LAZY = {
    "METRIC_HEAD": "device", "MetricsState": "device",
    "init_metrics_state": "device", "record_row": "device",
    "drain": "device", "row_width": "device",
    "Tracer": "trace", "tracer": "trace", "span": "trace",
    "FlightRecorder": "recorder",
    "to_json": "export", "to_prometheus": "export",
}


def __getattr__(name: str) -> Any:
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f".{_LAZY[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
