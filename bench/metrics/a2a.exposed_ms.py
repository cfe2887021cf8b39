"""Collectives: per burst on each device, the time an all-to-all runs
with no other op running on that device, averaged over the devices."""


def read(r):
    bursts = r.counts["bursts"]
    if r.trace is None or not bursts:
        return None
    if r.trace.kind_ns("all-to-all") <= 0:
        return None
    return r.trace.exposed_ns("all-to-all") / bursts * 1e-6
