"""Per op, from its due time on the generator's schedule to its reply on
the host: the 50th percentile over every op due in the window."""
import numpy as np


def read(r):
    lat = r.run.latencies
    if lat is None or lat.size == 0:
        return None
    return 1e3 * float(np.percentile(lat, 50))
