"""The one traffic generator: reads a mix file's parameters and makes the
cell's op stream from the seed.

A mix (``traffic/<name>.json``) gives:

* ``loop``: ``"closed"`` (bursts back to back, each as wide as the
  structure takes) or ``"open"`` (ops arrive on a schedule, whatever the
  system does);
* ``enqueue_share``: the share of ops that are enqueues, the rest
  dequeues;
* ``key_shares``: for a tiered structure, the share of enqueues per tier
  (tier 0 the most urgent); absent for one tier;
* ``backlog_records``: the backlog set-up enqueues before the window, in
  enqueue-only bursts, into tiers by ``prefill_key_shares`` (default
  ``key_shares``);
* open loop only: ``arrivals`` (``"poisson"``) and ``rate_ops_per_s``.

Every op is drawn in one fixed order from one generator seeded by
``--seed``, in chunks of ``CHUNK``: the same seed gives the same stream
of kinds, keys and, in an open loop, due times, however the stream is cut
into bursts.  Element ids count enqueues from 0 in stream order.
"""
from __future__ import annotations

import numpy as np

CHUNK = 1 << 16


class Stream:
    """Seeded ops: ``take(n)`` returns the next n ops' ``is_enq``,
    ``key`` and ``due`` (seconds from the window's start; open loop)."""

    def __init__(self, mix: dict, seed: int, tiers: int):
        self.rng = np.random.default_rng([seed, 0])
        self.p_enq = float(mix["enqueue_share"])
        self.shares = _shares(mix.get("key_shares"), tiers)
        self.rate = (float(mix["rate_ops_per_s"])
                     if mix["loop"] == "open" else None)
        self._buf = None
        self._t = 0.0

    def _refill(self):
        n = CHUNK
        is_enq = self.rng.random(n) < self.p_enq
        key = self.rng.choice(len(self.shares), n, p=self.shares)
        key = key.astype(np.int32)
        if self.rate is None:
            due = np.zeros(n)
        else:
            due = self._t + np.cumsum(self.rng.exponential(1 / self.rate, n))
            self._t = float(due[-1])
        key[~is_enq] = 0
        fresh = (is_enq, key, due)
        if self._buf is not None:
            fresh = tuple(np.concatenate([a, b])
                          for a, b in zip(self._buf, fresh))
        self._buf = fresh

    def available(self, n: int) -> None:
        while self._buf is None or self._buf[0].size < n:
            self._refill()

    def peek_due(self, n: int) -> np.ndarray:
        """Due times of the next n ops, without taking them."""
        self.available(n)
        return self._buf[2][:n]

    def take(self, n: int):
        self.available(n)
        out = tuple(a[:n] for a in self._buf)
        self._buf = tuple(a[n:] for a in self._buf)
        return out


def prefill_keys(mix: dict, tiers: int, seed: int, n: int) -> np.ndarray:
    """Keys of the ``n`` enqueues that prefill the backlog (their own
    generator, so the window's stream does not depend on the backlog)."""
    shares = _shares(mix.get("prefill_key_shares", mix.get("key_shares")),
                     tiers)
    rng = np.random.default_rng([seed, 2])
    return rng.choice(len(shares), n, p=shares).astype(np.int32)


def _shares(shares, tiers: int) -> np.ndarray:
    if shares is None:
        shares = [1.0] + [0.0] * (tiers - 1)
    if len(shares) != tiers:
        raise ValueError(f"the mix gives {len(shares)} key shares for a "
                         f"structure of {tiers} tiers")
    s = np.asarray(shares, float)
    return s / s.sum()
