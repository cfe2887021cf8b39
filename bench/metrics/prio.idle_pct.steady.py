"""The request scheduler's cell: share of the traced window in which no
op ran on the device, averaged over the cell's chips."""


def read(r):
    if r.trace is None or r.trace.window_ns <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_ns() / r.trace.window_ns)
