"""A mix's membership schedule: the LEAVEs and JOINs a run issues while
its traffic keeps arriving, and when each one has recovered.

A mix's ``membership`` is a list of changes, in the order they are made:

* ``{"op": "leave", "shard": s, ...}``: a graceful LEAVE of the shard at
  mesh index ``s``, through the program's ``shrink_devices`` with that
  shard's device id, not quarantined (the device may come back);
* ``{"op": "join", ...}``: a JOIN of one device from the pool, through
  the program's ``grow(1)``;

each with its time: ``at_s``, seconds from the window's start, or
``after_recovered_s``, seconds after the change before it recovered.
Set-up makes the schedule's changes once, in order, without the times.

In the window a change is issued between dispatches, once the dispatch in
flight has returned.  It has recovered at the reply of the dispatch that
answers the last op due before the change returned: the pause, and the
drain of what came due in it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

TAIL_S = 2.0        # the window runs on this long after the last recovery
NAN = float("nan")


@dataclass
class Change:
    op: str                              # "leave" or "join"
    shard: Optional[int] = None          # leave: the leaving mesh index
    at_s: Optional[float] = None
    after_recovered_s: Optional[float] = None
    # what a run records, on the window's clock
    issued: float = NAN
    returned: float = NAN
    recovered: float = NAN
    target: int = 0                      # ops due before it returned
    stats: dict = field(default_factory=dict)   # the program's own record
    devices: frozenset = frozenset()     # where the store sits after it


def parse(entries) -> List[Change]:
    """The mix's changes, checked."""
    out = []
    for i, e in enumerate(entries):
        c = Change(e["op"], e.get("shard"), e.get("at_s"),
                   e.get("after_recovered_s"))
        if c.op not in ("leave", "join"):
            raise ValueError(f"membership change {i}: op {c.op!r} is "
                             f"neither 'leave' nor 'join'")
        if c.op == "leave" and c.shard is None:
            raise ValueError(f"membership change {i}: a leave names its "
                             f"shard")
        if (c.at_s is None) == (c.after_recovered_s is None) or \
                (i == 0 and c.at_s is None):
            raise ValueError(f"membership change {i}: give at_s or (after "
                             f"the first) after_recovered_s, not both")
        out.append(c)
    return out


class Schedule:
    """The window's changes and which of them have been issued."""

    def __init__(self, entries):
        self.changes = parse(entries)
        self.issued = 0

    def next_at(self) -> float:
        """Window time the next change is due; inf when none is left, or
        while it waits for the one before it to recover."""
        if self.issued == len(self.changes):
            return math.inf
        c = self.changes[self.issued]
        if c.at_s is not None:
            return float(c.at_s)
        t = self.changes[self.issued - 1].recovered + c.after_recovered_s
        return math.inf if math.isnan(t) else t

    def end(self, seconds: float) -> float:
        """Where the window ends: ``seconds``, or ``TAIL_S`` after the
        last change recovered if that is later; inf until it has."""
        if not self.changes:
            return seconds
        last = self.changes[-1].recovered
        return math.inf if math.isnan(last) else max(seconds, last + TAIL_S)

    def replied(self, answered: int, now: float) -> None:
        """A dispatch replied at ``now``; ``answered`` ops have replies."""
        for c in self.changes[:self.issued]:
            if math.isnan(c.recovered) and answered >= c.target:
                c.recovered = now

    @property
    def done(self) -> bool:
        return all(not math.isnan(c.recovered) for c in self.changes)

    def recovered(self) -> List[Change]:
        return [c for c in self.changes if not math.isnan(c.recovered)]


def store_devices(q) -> frozenset:
    """Ids of the devices the structure's state sits on, read from its
    arrays and not from what it reports; a structure with no device
    arrays (the plain reference) is taken at its word."""
    import jax
    leaves = [x for x in jax.tree.leaves(getattr(q, "state", None))
              if hasattr(x, "addressable_shards")]
    if not leaves:
        return frozenset(q.device_ids)
    return frozenset(s.device.id for x in leaves
                     for s in x.addressable_shards)
