"""Priority wave program: per dispatch, the device self time of the wave
phase ``tier_scan`` (the per-tier enqueue positions: the segscan sweep
and its glue), averaged over the chips (traced run)."""
from bench.tiers import phase_ms


def read(r):
    return phase_ms(r, "tier_scan")
