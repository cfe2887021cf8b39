"""Overflow check: per burst, the program's ``queue:overflow_wait`` span,
in which the host waits for the burst's overflow flag (that is, for the
burst to finish on the device), mean over the traced window's bursts."""
from bench.program import span_ms


def read(r):
    return span_ms(r, "queue:overflow_wait")
