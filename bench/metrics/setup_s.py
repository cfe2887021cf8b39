"""Process start to the first timed op: imports, chip start, compile or
compile-cache load, the store, the prefill and the warm-up bursts."""


def read(r):
    return r.setup_seconds
