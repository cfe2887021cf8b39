"""Wave program: device busy time (union of the device's op intervals)
per burst, averaged over the cell's chips (traced run)."""


def read(r):
    bursts = r.counts["bursts"]
    if r.trace is None or not bursts:
        return None
    return r.trace.busy_ns() / bursts * 1e-6
