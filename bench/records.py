"""The records a run enqueues, and the digest that checks them on return.

Records come from a pool made once in set-up from the seed: one row per
op slot of the widest burst, 31-bit random words.  Before a burst is
staged, word 0 of each enqueue's row is set to the element's id and, where
the configuration names a ``key_word``, that word to the op's key (its
tier), so a record is fixed by (pool slot, id, key).

A consumer checks each dequeued record by a 64-bit digest: the row read
as 64-bit lanes, times an odd weight per lane, summed modulo 2**64.  The
digest is linear, so the digest a record should have is the digest of its
pool row with the id and key words zeroed, plus the id and key times their
lanes' weights: the check after the window costs O(1) per op.  Any one
word changed changes the digest (the change is nonzero and under 2**32 in
its lane, and the weight is odd); two or more changes go unseen only if
they cancel modulo 2**64.
"""
from __future__ import annotations

import numpy as np

_M64 = (1 << 64) - 1


def lane_weights(lanes: int) -> np.ndarray:
    """Odd 64-bit weights, one per lane; fixed, not drawn from the seed."""
    w = [((i + 1) * 0x9E3779B97F4A7C15 & _M64) | 1 for i in range(lanes)]
    return np.array(w, np.uint64)


def digest(rows: np.ndarray) -> np.ndarray:
    """Digest of each row of an int32 ``[R, W]`` array (W even)."""
    rows = np.ascontiguousarray(rows)
    lanes = rows.view(np.uint64)
    return lanes @ lane_weights(lanes.shape[1])


class Pool:
    """The set-up's record rows and what their digests should be."""

    def __init__(self, rows: int, W: int, seed: int, key_word=None):
        if W % 2:
            raise ValueError(f"record width {W} is not a whole number of "
                             f"64-bit lanes")
        rng = np.random.default_rng([seed, 1])
        self.rows = rng.integers(0, 1 << 31, (rows, W), dtype=np.int32)
        self.key_word = key_word
        rest = self.rows.copy()
        rest[:, 0] = 0
        if key_word is not None:
            rest[:, key_word] = 0
        self.rest = digest(rest)
        w = lane_weights(W // 2)
        self.w_id = w[0]
        if key_word is not None:
            self.w_key = w[key_word // 2] << np.uint64(32 * (key_word % 2))

    def stage(self, n_rows: int, slots, ids, keys=None) -> np.ndarray:
        """Write ids (and keys) into the enqueues' rows; return the first
        ``n_rows`` rows, the burst's records in flat op order."""
        self.rows[slots, 0] = ids
        if self.key_word is not None:
            self.rows[slots, self.key_word] = keys
        return self.rows[:n_rows]

    def expected(self, slots, ids, keys=None) -> np.ndarray:
        """Digests of the records staged at ``slots`` with these ids."""
        d = self.rest[slots] + np.asarray(ids).astype(np.uint64) * self.w_id
        if self.key_word is not None:
            d = d + np.asarray(keys).astype(np.uint64) * self.w_key
        return d
