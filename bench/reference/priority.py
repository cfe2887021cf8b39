"""A P-tier priority queue, tier 0 the most urgent: Skeap's constant-
priority order.

Per wave: every enqueue of the wave applies first, appended to its tier
in wave order (positions count each tier's enqueues from 0); then the
wave's dequeues, in wave order, take the priority-ordered pool from the
most urgent non-empty tier, FIFO within a tier: the d-th dequeue gets the
d-th best element.  A dequeue that finds every tier empty answers
"empty" (tier and position -1).  Waves apply one after the other.

``Oracle`` is a copy of the plain per-op oracle the program's tests use
(strict order, relaxation 0), kept here so that the benchmark's yardstick
stays fixed whatever the program does.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np


class Oracle:
    """Per-op P-tier oracle over dicts, in plain Python."""

    def __init__(self, n_prios: int):
        self.P = n_prios
        self.firsts = [0] * n_prios
        self.lasts = [-1] * n_prios
        self.store: List[dict] = [dict() for _ in range(n_prios)]

    def wave(self, ops) -> List[tuple]:
        """``ops``: ``(kind, prio, elem)`` or None (not valid), in wave
        order; returns ``(tier, pos, matched, value)`` per op."""
        recs: List[Optional[tuple]] = [None] * len(ops)
        for i, op in enumerate(ops):
            if op is None:
                recs[i] = (-1, -1, False, -1)
            elif op[0] == "enq":
                _, prio, elem = op
                self.lasts[prio] += 1
                self.store[prio][self.lasts[prio]] = elem
                recs[i] = (prio, self.lasts[prio], True, -1)
        taken = [0] * self.P
        for i, op in enumerate(ops):
            if op is None or op[0] != "deq":
                continue
            sizes = [self.lasts[p] - self.firsts[p] + 1 - taken[p]
                     for p in range(self.P)]
            nonempty = [p for p in range(self.P) if sizes[p] > 0]
            if not nonempty:
                recs[i] = (-1, -1, False, -1)
                continue
            q = nonempty[0]
            pos = self.firsts[q] + taken[q]
            taken[q] += 1
            recs[i] = (q, pos, True, self.store[q].pop(pos))
        for p in range(self.P):
            self.firsts[p] += taken[p]
        return recs


class Plain:
    """:class:`Oracle` driven a burst at a time."""

    def __init__(self, tiers: int, window: int = 0):
        self.oracle = Oracle(tiers)

    def burst(self, is_enq, valid, key, ids) -> dict:
        out = {f: np.full(is_enq.shape, -1, np.int64)
               for f in ("tier", "pos", "value")}
        out["matched"] = np.zeros(is_enq.shape, bool)
        for k in range(is_enq.shape[0]):
            ops = [None if not valid[k, i] else
                   ("enq", int(key[k, i]), int(ids[k, i])) if is_enq[k, i]
                   else ("deq", None, None)
                   for i in range(is_enq.shape[1])]
            for i, (t, p, m, v) in enumerate(self.oracle.wave(ops)):
                out["tier"][k, i], out["pos"][k, i] = t, p
                out["matched"][k, i], out["value"][k, i] = m, v
        return out


class Replay:
    """The same semantics, vectorised over each wave.  ``window`` is one
    tier's capacity, the ring that maps a tier's live positions to
    ids."""

    def __init__(self, tiers: int, window: int = 1 << 18):
        self.P = tiers
        self.firsts = np.zeros(tiers, np.int64)
        self.lasts = np.full(tiers, -1, np.int64)
        self.ring = np.zeros((tiers, window), np.int64)

    def burst(self, is_enq, valid, key, ids) -> dict:
        K, N = is_enq.shape
        out = {f: np.full((K, N), -1, np.int64)
               for f in ("tier", "pos", "value")}
        out["matched"] = np.zeros((K, N), bool)
        tiers = np.arange(self.P)
        cap = self.ring.shape[1]
        for k in range(K):
            e = is_enq[k] & valid[k]
            d = ~is_enq[k] & valid[k]
            t = key[k][e].astype(np.int64)
            hot = t[:, None] == tiers[None, :]
            rank = (np.cumsum(hot, axis=0) - hot)[np.arange(t.size), t]
            pos_e = self.lasts[t] + 1 + rank
            self.ring[t, pos_e % cap] = ids[k][e]
            self.lasts += hot.sum(axis=0)
            sizes = self.lasts - self.firsts + 1
            ends = np.cumsum(sizes)
            nth = np.cumsum(d)[d] - 1
            ok = nth < ends[-1]
            nth = nth[ok]
            tq = np.searchsorted(ends, nth, side="right")
            pos_d = self.firsts[tq] + nth - (ends[tq] - sizes[tq])
            deq = np.flatnonzero(d)[ok]
            for f, ve, vd in (("tier", t, tq), ("pos", pos_e, pos_d)):
                out[f][k, e] = ve
                out[f][k, deq] = vd
            out["matched"][k, e] = True
            out["matched"][k, deq] = True
            out["value"][k, deq] = self.ring[tq, pos_d % cap]
            self.firsts += np.bincount(tq, minlength=self.P)
        return out
