"""TPU-native SKUEUE: the aggregation tree as an associative scan.

The paper's Stages 1-3 (aggregate batches up the tree, assign intervals at
the anchor, decompose down the tree) are a Blelloch exclusive prefix scan.
Queue-state evolution under a request sequence is associative in the
*min-plus (tropical) semiring*:

    a single request acts on anchor state (f, l) = (first, last) as
        ENQ:  f' = f,                 l' = l + 1
        DEQ:  f' = min(f + 1, l + 1), l' = l
    every composition stays in the 3-parameter family
        T(A,B,C):  f' = min(f + A, l + B),  l' = l + C
    with identity (0, +INF, 0) and composition
        T1 ; T2 = (A1+A2, min(B1+A2, C1+B2), C1+C2).          (associative)

Given the *exclusive* prefix state (f_i, l_i) of request i:
        ENQ  ->  position l_i + 1
        DEQ  ->  position f_i   if f_i <= l_i else ⊥

The stack variant (Sec. VI) is the max-plus analogue on (last, ticket):
        PUSH: l' = l + 1, t' = t + 1    POP: l' = max(l - 1, 0), t' = t
        family  l' = max(l + a, b);  composition (a1+a2, max(b1+a2, b2)).
        PUSH_i -> (pos l_i + 1, ticket t_i + 1)
        POP_i  -> (pos l_i, bound t_i)  if l_i >= 1 else ⊥

Consequences for TPU (DESIGN.md §2): the anchor is *virtual* (the carry is
replicated, no hot node), a batch of requests costs one O(log) scan instead
of O(log n) protocol rounds, and sequential consistency holds by
construction because the scan order IS the total order ≺.

Two distribution strategies are provided:
  * ``*_scan`` — ``jax.lax.associative_scan`` over the flat request array
    (GSPMD chooses the schedule; fine under pjit).
  * ``sharded_queue_scan`` — explicit shard_map: local scan per device +
    ``lax.ppermute`` hypercube scan over the device axis for the carries.
    This is the literal ICI analogue of the paper's O(log n) aggregation
    tree: ⌈log2 p⌉ permute rounds, constant bytes per round (Theorem 18's
    O(log n)-size batches collapse to an (A,B,C) carry).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..compat import axis_size, shard_map

INF = jnp.int32(2 ** 30)  # +infinity in the tropical semiring (no overflow:
#                           compositions add at most O(batch) to it once)
BOTTOM = jnp.int32(-1)


class QueueState(NamedTuple):
    """Replicated anchor state: occupied positions are [first, last]."""
    first: jax.Array  # int32 scalar
    last: jax.Array

    @staticmethod
    def empty() -> "QueueState":
        return QueueState(jnp.int32(0), jnp.int32(-1))

    @property
    def size(self) -> jax.Array:
        return self.last - self.first + 1


class StackState(NamedTuple):
    last: jax.Array    # top of stack; positions start at 1
    ticket: jax.Array  # monotone push counter

    @staticmethod
    def empty() -> "StackState":
        return StackState(jnp.int32(0), jnp.int32(0))


# ------------------------------------------------------------ queue scan ---
def queue_op_transforms(is_enq: jax.Array):
    """Per-request (A, B, C) transforms. is_enq: bool/int array."""
    e = is_enq.astype(jnp.int32)
    A = 1 - e                      # ENQ: 0, DEQ: 1
    B = jnp.where(e > 0, INF, 1)   # ENQ: inf, DEQ: 1
    C = e                          # ENQ: 1, DEQ: 0
    return A, B, C


def queue_compose(t1, t2):
    """(t1 then t2), elementwise; associative (used by associative_scan)."""
    A1, B1, C1 = t1
    A2, B2, C2 = t2
    return (A1 + A2,
            jnp.minimum(jnp.minimum(B1 + A2, C1 + B2), INF),
            C1 + C2)


def _exclusive(tr, fills=(0, INF, 0), axis=0):
    """Inclusive scan results -> exclusive (shift right, identity first)."""
    def shift(x, fill):
        pad = jnp.full_like(lax.slice_in_dim(x, 0, 1, axis=axis), fill)
        return lax.concatenate([pad, lax.slice_in_dim(x, 0, x.shape[axis] - 1,
                                                      axis=axis)], axis)
    return tuple(shift(x, f) for x, f in zip(tr, fills))


def queue_scan(is_enq: jax.Array, state: QueueState,
               valid: jax.Array | None = None
               ) -> Tuple[jax.Array, jax.Array, QueueState]:
    """Assign positions to a flat request batch (global order = array order).

    Args:
      is_enq: [n] bool — True for ENQUEUE, False for DEQUEUE.
      state:  incoming anchor state.
      valid:  [n] bool — padding mask (False entries are no-ops).
    Returns:
      positions [n] int32 (⊥ = -1 for unmatched dequeues; enqueue slots are
      the DHT positions to PUT into), matched mask, new state.
    """
    if valid is not None:
        # padded entries become identity transforms
        e = is_enq & valid
        tr = queue_op_transforms(e)
        A, B, C = tr
        A = jnp.where(valid, A, 0)
        B = jnp.where(valid, B, INF)
        C = jnp.where(valid, C, 0)
        tr = (A, B, C)
    else:
        tr = queue_op_transforms(is_enq)
    inc = lax.associative_scan(queue_compose, tr)
    Ax, Bx, Cx = _exclusive(inc)
    f_i = jnp.minimum(state.first + Ax, state.last + Bx)
    l_i = state.last + Cx
    pos = jnp.where(is_enq, l_i + 1, jnp.where(f_i <= l_i, f_i, BOTTOM))
    matched = pos != BOTTOM
    if valid is not None:
        pos = jnp.where(valid, pos, BOTTOM)
        matched = matched & valid
    # total transform = last element of the inclusive scan
    A_t, B_t, C_t = (x[-1] for x in inc)
    new = QueueState(jnp.minimum(state.first + A_t, state.last + B_t),
                     state.last + C_t)
    return pos, matched, new


# ------------------------------------------------------------ stack scan ---
def stack_op_transforms(is_push: jax.Array):
    p = is_push.astype(jnp.int32)
    a = 2 * p - 1                        # PUSH: +1, POP: -1
    b = jnp.where(p > 0, -INF, 0)        # POP clamps at 0
    dt = p                               # ticket increment
    return a, b, dt


def stack_compose(t1, t2):
    a1, b1, d1 = t1
    a2, b2, d2 = t2
    return (a1 + a2,
            jnp.maximum(jnp.maximum(b1 + a2, b2), -INF),
            d1 + d2)


def stack_scan(is_push: jax.Array, state: StackState,
               valid: jax.Array | None = None):
    """Returns (positions, tickets, matched, new_state).  For pushes the
    ticket is the element's unique ticket; for pops it is the bound t'."""
    tr = stack_op_transforms(is_push if valid is None else (is_push & valid))
    if valid is not None:
        a, b, d = tr
        a = jnp.where(valid, a, 0)
        b = jnp.where(valid, b, -INF)
        d = jnp.where(valid, d, 0)
        tr = (a, b, d)
    inc = lax.associative_scan(stack_compose, tr)
    a_x, b_x, d_x = _exclusive(inc, fills=(0, -INF, 0))
    l_i = jnp.maximum(state.last + a_x, b_x)
    t_i = state.ticket + d_x
    pos = jnp.where(is_push, l_i + 1, jnp.where(l_i >= 1, l_i, BOTTOM))
    tick = jnp.where(is_push, t_i + 1, t_i)
    matched = pos != BOTTOM
    if valid is not None:
        pos = jnp.where(valid, pos, BOTTOM)
        matched = matched & valid
    a_t, b_t, d_t = (x[-1] for x in inc)
    new = StackState(jnp.maximum(state.last + a_t, b_t), state.ticket + d_t)
    return pos, tick, matched, new


# -------------------------------------------------- priority-tier scan -----
def strict_batch_deletemin(deq: jax.Array, avail: jax.Array,
                           firsts: jax.Array, n_prios: int):
    """Skeap's strict batch-DeleteMin assignment as prefix arithmetic.

    The d-th dequeue of the wave (wave order) takes the d-th element of
    the priority-ordered pool: dequeue ranks index into the per-tier
    cumulative availability, no sequential loop.  Shared by
    :func:`priority_queue_scan` and the pallas path
    (``kernels.segscan.priority_queue_scan_pallas``).

    Args:
      deq: [n] bool — the wave's dequeue ops (global wave order);
      avail: [n_prios] int32 — per-tier sizes AFTER the wave's enqueues;
      firsts: [n_prios] int32 — per-tier head positions.
    Returns:
      (tier [n] int32 (clamped; gate with matched), pos [n] int32,
      matched [n] bool, taken [n_prios] int32 — heads consumed per tier).
    """
    d_in = deq.astype(jnp.int32)
    d_rank = jnp.cumsum(d_in) - d_in                # exclusive deq rank
    cum = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(avail)])
    t_d = (d_rank[:, None] >= cum[None, 1:]).sum(1).astype(jnp.int32)
    matched = deq & (t_d < n_prios)
    t_c = jnp.minimum(t_d, n_prios - 1)
    pos = firsts[t_c] + d_rank - cum[t_c]
    taken = jnp.clip(d_in.sum() - cum[:-1], 0, avail)
    return t_c, pos, matched, taken


def priority_queue_scan(is_enq: jax.Array, prio: jax.Array, valid: jax.Array,
                        firsts: jax.Array, lasts: jax.Array, *, n_prios: int,
                        relaxation: int = 0, shard_of: jax.Array | None = None,
                        n_shards: int | None = None, tier_scan=None):
    """Batch position assignment for the P-tier constant-priority queue
    (Skeap's constant-priority regime, arXiv:1805.03472).

    The queue is P independent SKUEUE position intervals, tie-broken by
    tier: each tier keeps its own dense ``[firsts[p], lasts[p]]`` window.
    One wave applies all enqueues before all dequeues (the PR 1 PUT-before-
    GET rule, lifted to tiers):

      * enqueues — per-tier FIFO positions via the min-plus transforms of
        :func:`queue_scan`, one masked scan per tier (P is a small static
        constant);
      * dequeues — resolved highest-priority-first *inside the wave*: the
        d-th dequeue (wave order) takes the d-th element of the priority-
        ordered pool, i.e. the wave's dequeue batch drains tier 0, then
        tier 1, ...  With ``relaxation=k`` a dequeue may instead take the
        head of a tier up to ``k`` below the currently-best non-empty tier
        when that lower head is *locally owned* (``head % n_shards ==
        shard_of[i]``) and the best tier's head is not — trading strict
        priority order (never per-tier FIFO, and never by more than k
        tiers) for a serve that avoids the cross-shard hop.

    Args:
      is_enq/valid: [n] bool (global wave order); prio: [n] int32 in
        [0, n_prios) (ignored for dequeues); firsts/lasts: [n_prios] int32.
      relaxation: static int k >= 0; 0 is the strict mode.
      shard_of/n_shards: issuing shard per op and shard count — required
        when relaxation > 0 (the locality rule needs owners).
      tier_scan: optional fused replacement for the per-tier enqueue
        loop, ``(enq, tier, firsts, lasts) -> (pos, new_lasts)`` —
        ``kernels.segscan.make_tier_scan`` provides the pallas sweep
        (PR 9); None keeps this jnp path, which remains the oracle.
    Returns:
      (tier [n] int32 (-1 unmatched), pos [n] int32 (⊥ = -1), matched [n]
      bool, new_firsts, new_lasts, n_relaxed) — ``n_relaxed`` counts the
      dequeues served from below the strictly-best tier (0 in strict mode).
    """
    P_ = n_prios
    enq = is_enq & valid
    deq = (~is_enq) & valid
    # the two wave phases of the dispatch (``WAVE_PHASES``)
    with jax.named_scope("tier_scan"):
        tier = jnp.full(is_enq.shape, -1, jnp.int32)
        pos = jnp.full(is_enq.shape, BOTTOM, jnp.int32)
        if tier_scan is not None:
            pos_e, new_lasts = tier_scan(enq, prio, firsts, lasts)
            tier = jnp.where(enq & (pos_e >= 0), prio.astype(jnp.int32),
                             tier)
            pos = jnp.where(enq, pos_e, pos)
        else:
            new_lasts = []
            for p in range(P_):
                mask = enq & (prio == p)
                pos_p, _, st_p = queue_scan(
                    mask, QueueState(firsts[p], lasts[p]), valid=mask)
                tier = jnp.where(mask, p, tier)
                pos = jnp.where(mask, pos_p, pos)
                new_lasts.append(st_p.last)
            new_lasts = jnp.stack(new_lasts)
        avail = new_lasts - firsts + 1              # sizes after enqueues
    with jax.named_scope("deletemin"):
        if relaxation == 0:
            # strict: pure per-tier prefix arithmetic, no sequential loop
            t_c, pos_d, d_matched, taken = strict_batch_deletemin(
                deq, avail, firsts, P_)
            tier = jnp.where(d_matched, t_c, tier)
            pos = jnp.where(d_matched, pos_d, pos)
            matched = enq | d_matched
            n_relaxed = jnp.int32(0)
        else:
            if shard_of is None or n_shards is None:
                raise ValueError(
                    "relaxation > 0 needs shard_of and n_shards")
            ar = jnp.arange(P_, dtype=jnp.int32)

            def step(taken, x):
                d_i, s_i = x
                sizes = avail - taken
                ne = sizes > 0
                pstar = jnp.argmax(ne).astype(jnp.int32)  # best non-empty
                heads = firsts + taken
                loc = (ne & (ar >= pstar) & (ar <= pstar + relaxation)
                       & (jnp.mod(heads, n_shards) == s_i))
                q = jnp.where(loc.any(), jnp.argmax(loc),
                              pstar).astype(jnp.int32)
                m = d_i & ne.any()
                out = (jnp.where(m, q, -1), jnp.where(m, heads[q], BOTTOM),
                       m, m & (q != pstar))
                return (taken + jnp.where(m, (ar == q).astype(jnp.int32), 0),
                        out)

            taken, (t_d, pos_d, m_d, rel) = lax.scan(
                step, jnp.zeros((P_,), jnp.int32),
                (deq, shard_of.astype(jnp.int32)))
            tier = jnp.where(m_d, t_d, tier)
            pos = jnp.where(m_d, pos_d, pos)
            matched = enq | m_d
            n_relaxed = rel.astype(jnp.int32).sum()

    return tier, pos, matched, firsts + taken, new_lasts, n_relaxed


# -------------------------------------------------- seap bucket scan -------
INT32_MIN = jnp.int32(-(2 ** 31))
INT32_MAX = jnp.int32(2 ** 31 - 1)


def seap_bucket_lookup(key: jax.Array, lo: jax.Array, active: jax.Array):
    """Predecessor lookup in the replicated bucket directory: for each key,
    the active bucket with the largest boundary ``lo <= key``.

    The root bucket (id 0) keeps ``lo == INT32_MIN`` and is always active,
    so every key has a home; active boundaries are distinct by the split
    rule, so the argmax is unique (and ties at ``INT32_MIN`` resolve to the
    root because argmax returns the first index).
    """
    eligible = active[None, :] & (lo[None, :] <= key[:, None])
    score = jnp.where(eligible, lo[None, :], INT32_MIN)
    return jnp.argmax(score, axis=1).astype(jnp.int32)


def seap_queue_scan(is_enq: jax.Array, key: jax.Array, valid: jax.Array,
                    firsts: jax.Array, lasts: jax.Array, lo: jax.Array,
                    active: jax.Array, key_lo: jax.Array,
                    key_hi: jax.Array, *, n_buckets: int,
                    split_occupancy: int, tier_scan=None):
    """Batch position assignment for the arbitrary-key Seap queue
    (arXiv:1805.03472's search structure collapsed to a two-level bucket
    directory; see ``core.seap.SeapOracle`` for the full semantics).

    One wave applies all enqueues before all dequeues, then rebalances:

      * enqueues — bucket from :func:`seap_bucket_lookup`, then per-bucket
        FIFO positions via B masked min-plus scans (the
        :func:`priority_queue_scan` machinery with tier := bucket);
      * dequeues — Skeap's :func:`strict_batch_deletemin` over the bucket
        directory sorted by boundary: the d-th dequeue of the wave takes
        the d-th element of the boundary-ordered pool, FIFO inside each
        bucket;
      * rebalance — at most one split per wave (halve the fullest bucket
        whose occupancy exceeds ``split_occupancy`` into the lowest free
        id), preceded by at most one *on-demand* merge (recycle the
        lowest-id active empty non-root bucket) when the split wants an
        id and none is free.  The split midpoint is clamped to the
        *observed* key range ``[key_lo, key_hi]`` (running min/max of
        enqueued keys — the paper's search structure is built over
        inserted keys, not the int32 universe), so the zoom lands in the
        live range immediately instead of halving down from
        ``INT32_MAX`` geometrically.  Pure replicated arithmetic — no
        collectives, and no element ever moves between windows.

    Args:
      is_enq/valid: [n] bool (global wave order); key: [n] int32 (ignored
        for dequeues); firsts/lasts/lo: [n_buckets] int32; active:
        [n_buckets] bool; key_lo/key_hi: replicated int32 scalars, the
        min/max key ever enqueued (INT32_MAX/INT32_MIN while empty).
    Returns:
      (bucket [n] int32 (-1 unmatched), pos [n] int32 (⊥ = -1), matched
      [n] bool, new_firsts, new_lasts, new_lo, new_active, new_key_lo,
      new_key_hi, n_active) — ``n_active`` is the replicated directory
      size after the rebalance.
    """
    B = n_buckets
    enq = is_enq & valid
    deq = (~is_enq) & valid
    bucket_e = seap_bucket_lookup(key, lo, active)
    bucket = jnp.full(is_enq.shape, -1, jnp.int32)
    pos = jnp.full(is_enq.shape, BOTTOM, jnp.int32)
    if tier_scan is not None:
        # fused per-bucket sweep (tier := bucket), same hook as the
        # priority scan — kernels.segscan.make_tier_scan (PR 9)
        pos_e, new_lasts = tier_scan(enq, bucket_e, firsts, lasts)
        bucket = jnp.where(enq & (pos_e >= 0), bucket_e, bucket)
        pos = jnp.where(enq, pos_e, pos)
    else:
        new_lasts = []
        for b in range(B):
            mask = enq & (bucket_e == b)
            pos_b, _, st_b = queue_scan(
                mask, QueueState(firsts[b], lasts[b]), valid=mask)
            bucket = jnp.where(mask, b, bucket)
            pos = jnp.where(mask, pos_b, pos)
            new_lasts.append(st_b.last)
        new_lasts = jnp.stack(new_lasts)
    avail = new_lasts - firsts + 1               # sizes after enqueues

    # dequeues: batch-DeleteMin over the directory in boundary order
    # (inactive buckets sort last and are empty, so they are never taken)
    order = jnp.argsort(jnp.where(active, lo, INT32_MAX))
    t_s, pos_d, d_matched, taken_s = strict_batch_deletemin(
        deq, avail[order], firsts[order], B)
    taken = jnp.zeros((B,), jnp.int32).at[order].set(taken_s)
    bucket = jnp.where(d_matched, order[t_s], bucket)
    pos = jnp.where(d_matched, pos_d, pos)
    matched = enq | d_matched
    new_firsts = firsts + taken

    # running observed key range (enqueued keys only)
    enq_keys_min = jnp.min(jnp.where(enq, key, INT32_MAX))
    enq_keys_max = jnp.max(jnp.where(enq, key, INT32_MIN))
    new_key_lo = jnp.minimum(key_lo, enq_keys_min)
    new_key_hi = jnp.maximum(key_hi, enq_keys_max)

    # ---- rebalance: merge-on-demand then split, replicated arithmetic
    # only.  An empty bucket is harmless future structure, so its id is
    # recycled (merged away) ONLY when a split wants an id and none is
    # free — merging eagerly would dismantle the directory between
    # bursts, exactly when the next crunch needs it refined. ----
    sizes = new_lasts - new_firsts + 1
    ids = jnp.arange(B, dtype=jnp.int32)
    occ = jnp.where(active, sizes, -1)
    over = occ > split_occupancy
    cand = active & (sizes == 0) & (lo != INT32_MIN)
    need = over.any() & ~(~active).any()          # want to split, no free id
    active = jnp.where((ids == jnp.argmax(cand)) & need & cand.any(),
                       False, active)
    free = ~active
    b_s = jnp.argmax(jnp.where(over, occ, -1))   # fullest; ties -> lowest id
    hi = jnp.min(jnp.where(active & (lo > lo[b_s]), lo, INT32_MAX))
    # clamp the halving to the observed key range (saturating +/-1 at the
    # int32 edges); a triggered split implies the bucket is non-empty, so
    # new_key_hi >= lo[b_s] and the clamped range is non-degenerate
    lo_eff = jnp.maximum(
        lo[b_s], jnp.where(new_key_lo == INT32_MIN, INT32_MIN,
                           new_key_lo - 1))
    hi_eff = jnp.minimum(
        hi, jnp.where(new_key_hi == INT32_MAX, INT32_MAX, new_key_hi + 1))
    # overflow-free floor((lo_eff + hi_eff) / 2); the split is valid only
    # when the midpoint lands strictly inside the bucket's (lo, hi) range
    mid = (lo_eff & hi_eff) + ((lo_eff ^ hi_eff) >> 1)
    do_split = over.any() & free.any() & (mid > lo[b_s]) & (mid < hi)
    b_f = jnp.argmax(free)                       # lowest free id
    new_lo = jnp.where((ids == b_f) & do_split, mid, lo)
    new_active = active | ((ids == b_f) & do_split)
    n_active = jnp.sum(new_active.astype(jnp.int32))
    return (bucket, pos, matched, new_firsts, new_lasts, new_lo,
            new_active, new_key_lo, new_key_hi, n_active)


# ------------------------------------------------- shard_map distribution ---
def sharded_queue_scan(is_enq_local: jax.Array, state: QueueState,
                       axis_name: str,
                       valid_local: jax.Array | None = None):
    """shard_map body: per-device local request arrays; returns local
    positions + matched + the (replicated) new state.

    Three phases, mirroring the paper exactly:
      1. local "batch aggregation": an associative scan on-device,
      2. "anchor assignment": an O(log p) ppermute hypercube scan of the
         per-device total transforms (constant bytes per hop),
      3. "interval decomposition": apply the device-prefix carry locally.
    """
    e = is_enq_local if valid_local is None else (is_enq_local & valid_local)
    tr = queue_op_transforms(e)
    if valid_local is not None:
        A, B, C = tr
        tr = (jnp.where(valid_local, A, 0),
              jnp.where(valid_local, B, INF),
              jnp.where(valid_local, C, 0))
    inc = lax.associative_scan(queue_compose, tr)                    # phase 1
    total = tuple(x[-1] for x in inc)

    # phase 2: exclusive hypercube scan of device totals
    p = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    incl = total
    shift = 1
    while shift < p:
        perm = [(i, i + shift) for i in range(p - shift)]
        moved = tuple(lax.ppermute(c, axis_name, perm) for c in incl)
        cand = queue_compose(moved, incl)
        use = idx >= shift
        incl = tuple(jnp.where(use, cn, cu) for cn, cu in zip(cand, incl))
        shift *= 2
    # device-exclusive carry = shift by one device
    perm1 = [(i, i + 1) for i in range(p - 1)]
    moved1 = tuple(lax.ppermute(c, axis_name, perm1) for c in incl)
    dev_excl = tuple(jnp.where(idx == 0, fill, m)
                     for m, fill in zip(moved1, (0, INF, 0)))

    # phase 3: local exclusive prefixes composed after the device carry
    Ax, Bx, Cx = _exclusive(inc)
    Ad, Bd, Cd = dev_excl
    A, B, C = queue_compose((Ad, Bd, Cd), (Ax, Bx, Cx))
    f_i = jnp.minimum(state.first + A, state.last + B)
    l_i = state.last + C
    pos = jnp.where(is_enq_local, l_i + 1,
                    jnp.where(f_i <= l_i, f_i, BOTTOM))
    matched = pos != BOTTOM
    if valid_local is not None:
        pos = jnp.where(valid_local, pos, BOTTOM)
        matched = matched & valid_local
    # new replicated state: all-devices total = inclusive scan at last device
    # (broadcast via a tiny all_gather of the 3 scalar carries)
    A_t, B_t, C_t = (
        lax.all_gather(c, axis_name)[p - 1] if p > 1 else c for c in incl)
    new = QueueState(jnp.minimum(state.first + A_t, state.last + B_t),
                     state.last + C_t)
    return pos, matched, new


def make_sharded_queue_scan(mesh, axis_name: str = "data"):
    """jit-compiled shard_map wrapper over ``mesh[axis_name]``."""
    spec = P(axis_name)
    rep = P()

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, rep, spec), out_specs=(spec, spec, rep))
    def run(is_enq, state, valid):       # new state is value-replicated by
        # the final all_gather broadcast
        pos, matched, new = sharded_queue_scan(
            is_enq, QueueState(*state), axis_name, valid_local=valid)
        return pos, matched, tuple(new)

    def call(is_enq: jax.Array, state: QueueState, valid: jax.Array):
        pos, matched, new = run(is_enq, tuple(state), valid)
        return pos, matched, QueueState(*new)

    return call
