"""Migration program: the least HBM traffic the window's membership
changes need, over the chips' peak HBM bandwidth, over the device busy
time inside the changes' spans.

Bytes from the program's own count of the records each change moved
(``moved`` in its record of the change), 4 bytes a word, whatever the
program's shapes: each moved record read once from the old store and
written once to the new one.  Each change migrates on a mesh of all the
cell's chips (the larger of the two memberships), which share the bytes."""
import math

from bench.program import change_device_ns


def work_bytes(moved: int, record_words: int) -> int:
    return 4 * 2 * record_words * moved


def read(r):
    busy = change_device_ns(r)
    made = [c for c in r.run.plan.changes if not math.isnan(c.returned)]
    if not busy or not made or any("moved" not in c.stats for c in made):
        return None
    busy_s = sum(busy) * 1e-9
    if busy_s <= 0:
        return None
    need = sum(work_bytes(c.stats["moved"], r.run.W) for c in made)
    return 100.0 * need / r.run.n / r.peaks["hbm_bytes_per_s"] / busy_s
