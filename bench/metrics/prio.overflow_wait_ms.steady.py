"""Overflow check of the priority structure: per dispatch, its
``pqueue:overflow_wait`` span, in which the host waits for the
dispatch's overflow flag (that is, for it to finish on the device), mean
over the traced window's dispatches."""
from bench.program import span_ms


def read(r):
    return span_ms(r, "pqueue:overflow_wait")
