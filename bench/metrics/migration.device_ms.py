"""Migration program: per membership change in the traced window, the
device busy time inside the change's ``migration:shrink`` or
``migration:grow`` span, averaged over the chips; mean over the
changes."""
from bench.program import change_device_ns


def read(r):
    busy = change_device_ns(r)
    if not busy:
        return None
    return sum(busy) / len(busy) * 1e-6
