"""What the client keeps of each burst, and the check after the window.

Per burst the client keeps the ops it sent (kinds, validity, keys and the
first id it gave out; the ids of a burst's enqueues count up in op order
and their records sit at the enqueues' pool slots), every reply word the
program returned (position, matched, tier, dequeue success) and the
digest of every dequeued record.  After the window the configuration's
reference replays the same ops, and every op's reply and every dequeued
record is compared with what the reference says it must be.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

REPLY_FIELDS = ("pos", "matched", "tier")


@dataclass
class Burst:
    is_enq: np.ndarray           # [K, N] bool
    valid: np.ndarray            # [K, N] bool
    key: Optional[np.ndarray]    # [K, N] int32, tiered structures only
    first_id: int
    got: Dict[str, np.ndarray]   # reply fields and ``dok``
    digests: np.ndarray          # uint64, one per row with ``dok``


class Log:
    """Bursts in the order they were dispatched."""

    def __init__(self):
        self.bursts: List[Burst] = []
        self.next_id = 0

    def ids_for(self, is_enq, valid) -> int:
        """Hand out the ids of a burst's enqueues; returns the first."""
        first = self.next_id
        self.next_id += int((is_enq & valid).sum())
        return first

    def add(self, burst: Burst) -> None:
        self.bursts.append(burst)


def check(log: Log, replay, pool) -> Dict[str, int]:
    """Replay every burst through the reference.  ``wrong_ops`` counts the
    ops whose reply differs from the reference's or whose dequeued record
    is not the one the reference says it must be; ``wrong_replies`` and
    ``wrong_records`` split it."""
    slot_of = np.zeros(log.next_id, np.int64)
    key_of = np.zeros(log.next_id, np.int64)
    wrong_replies = wrong_records = wrong_ops = n_ops = 0
    for b in log.bursts:
        e = b.is_enq & b.valid
        ids = np.full(e.shape, -1, np.int64)
        ids[e] = b.first_id + np.arange(int(e.sum()))
        flat = np.flatnonzero(e.ravel())
        slot_of[ids[e]] = flat
        key = b.key if b.key is not None else np.zeros(e.shape, np.int32)
        key_of[ids[e]] = key.ravel()[flat]
        exp = replay.burst(b.is_enq, b.valid, key, ids)
        bad = np.zeros(e.shape, bool)
        for f in REPLY_FIELDS:
            if f in b.got:
                bad |= b.got[f] != exp[f]
        want = exp["value"] >= 0
        bad |= b.got["dok"] != want
        wrong_replies += int(bad.sum())
        n_ops += e.size
        both = want & b.got["dok"]
        v = exp["value"][both]
        expect = pool.expected(slot_of[v], v, key_of[v])
        record_bad = b.digests[both[b.got["dok"]]] != expect
        wrong_records += int(record_bad.sum())
        bad[both] |= record_bad
        wrong_ops += int(bad.sum())
    return {"wrong_ops": wrong_ops, "wrong_replies": wrong_replies,
            "wrong_records": wrong_records, "ops_checked": n_ops}
