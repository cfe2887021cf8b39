"""Segscan sweep: the least HBM traffic of the tier assignment over the
chip's peak HBM bandwidth, over the device time of the program's Pallas
kernels (its ``tpu_custom_call`` instructions, named from the compiled
text of the program each traced burst ran) in the traced bursts.

Bytes from the traced window's ops, not from the kernel's padded shapes:
per op, its kind, validity and tier read once, its tier position and its
match written once, 4 bytes a word (``bench/tiers.py``).  Every chip
assigns every op of a wave (the op descriptors are gathered first), so
each chip's sweep needs the bytes of all the window's ops."""
from bench.tiers import kernel_s, work_bytes


def read(r):
    busy_s = kernel_s(r)
    if not busy_s:
        return None
    need = work_bytes(r.counts["ops"])
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / busy_s
