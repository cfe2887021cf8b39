"""Priority wave program: per dispatch, the device self time of the wave
phase ``deletemin`` (the in-wave batch-DeleteMin that answers the
wave's dequeues), averaged over the chips (traced run)."""
from bench.tiers import phase_ms


def read(r):
    return phase_ms(r, "deletemin")
