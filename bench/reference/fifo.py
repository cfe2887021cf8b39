"""One totally ordered FIFO queue: ``collections.deque`` semantics.

A wave's ops apply one after the other in wave order (shard 0's ops, then
shard 1's, ...), and the waves of a burst one after the other.  An
enqueue takes the next position (positions count enqueues from 0); a
dequeue takes the oldest element, or answers "empty" (position -1) when
there is none; an op that is not valid does nothing.
"""
from __future__ import annotations

import collections

import numpy as np


def _blank(shape) -> dict:
    return {"pos": np.full(shape, -1, np.int64),
            "matched": np.zeros(shape, bool),
            "value": np.full(shape, -1, np.int64)}


class Plain:
    """Per-op model over ``collections.deque``."""

    def __init__(self, tiers: int = 1, window: int = 0):
        self.dq = collections.deque()
        self.n_enq = 0

    def burst(self, is_enq, valid, key, ids) -> dict:
        """Expected ``pos``/``matched``/``value`` of a [K, N] burst."""
        out = _blank(is_enq.shape)
        for k, i in np.ndindex(is_enq.shape):
            if not valid[k, i]:
                continue
            if is_enq[k, i]:
                self.dq.append((self.n_enq, int(ids[k, i])))
                out["pos"][k, i] = self.n_enq
                out["matched"][k, i] = True
                self.n_enq += 1
            elif self.dq:
                out["pos"][k, i], out["value"][k, i] = self.dq.popleft()
                out["matched"][k, i] = True
        return out


class Replay:
    """The same semantics, vectorised over a burst.

    The backlog after op i, had no dequeue failed, is ``X_i = size +
    E_i - D_i`` (enqueues and dequeues up to i); dequeues that found the
    queue empty up to i number ``F_i = max(0, -min_j<=i X_j)``, and a
    dequeue succeeds exactly when it does not raise F.  ``window`` is the
    structure's capacity: the ring that maps live positions to ids.
    """

    def __init__(self, tiers: int = 1, window: int = 1 << 20):
        self.first, self.next = 0, 0      # oldest live / next position
        self.ring = np.zeros(window, np.int64)

    def burst(self, is_enq, valid, key, ids) -> dict:
        shape = is_enq.shape
        e = (is_enq & valid).ravel()
        d = (~is_enq & valid).ravel()
        out = _blank(e.shape)
        n_e = np.cumsum(e)
        pos_e = self.next + n_e[e] - 1
        self.ring[pos_e % self.ring.size] = ids.ravel()[e]
        x = (self.next - self.first) + n_e - np.cumsum(d)
        f = np.maximum(0, -np.minimum.accumulate(x))
        f_before = np.concatenate([[0], f[:-1]])
        ok = d & (f == f_before)
        pos_d = self.first + np.cumsum(ok)[ok] - 1
        out["pos"][e], out["pos"][ok] = pos_e, pos_d
        out["matched"] = e | ok
        out["value"][ok] = self.ring[pos_d % self.ring.size]
        self.next += int(n_e[-1]) if e.size else 0
        self.first += int(ok.sum())
        return {k: v.reshape(shape) for k, v in out.items()}
