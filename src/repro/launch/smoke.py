"""The wave queue's main path, driven end to end and checked op by op.

``chip_smoke.py`` at the repo root runs these phases on the chip at
deployment size; ``tests/test_smoke.py`` runs the same phases on the CPU
at tiny sizes.  Each phase raises :class:`SmokeFailure` at the first
disagreement with its plain host reference, and returns a dict of what it
saw (ops, compile seconds, memory) for the caller to print.

* :func:`run_discipline`: FIFO, LIFO, priority (4 tiers) or Seap (8
  buckets) through its elastic wrapper's ``run_waves``.  Traffic from a
  seed: a 70/30 enqueue-heavy mix until the backlog holds at least half
  the store, then a 30/70 dequeue-heavy mix until it is empty.  Every
  reply is checked against ``collections.deque`` (FIFO, in the wave's
  global order), a list (LIFO), ``core.priority.PriorityOracle`` or
  ``core.seap.SeapOracle``, payload words included.
* :func:`run_membership`: a FIFO backlog carried through one JOIN and one
  LEAVE (``grow``/``shrink``), then drained in order.
* :func:`run_serving`: ``ServeEngine`` with priority tiers: every request
  served, the engine drained, admission FIFO within each tier, and the
  tokens of the first and the last request admitted equal to a plain
  greedy ``decode_fn`` loop.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..analysis.hlo import count_op
from ..core.priority import PriorityOracle
from ..core.seap import SeapOracle
from ..dqueue import (ElasticDevicePriorityQueue, ElasticDeviceQueue,
                      ElasticDeviceSeapQueue, ElasticDeviceStack)

KINDS = ("fifo", "lifo", "priority", "seap")
N_TIERS = 4
N_BUCKETS = 8
# A stack position is pushed again every time the top comes back to it;
# with the 70/30 mix one position takes >= d pushes in one wave with
# probability about (3/7)**(d-1), and every push of a wave lands in its
# slot's ticket set before the pops free any.  At half a store of depth D
# a slot holds D/2 live entries, so D/2 must cover those re-pushes: the
# default depth 4 overflows in the first wave, 64 leaves 32 (a mixed wave
# at the bottom of the stack needs far more: see ``_drive``).  Pops of one
# wave resolve against the same ticket sets at once, so the heights one
# wave visits must not wrap around the slots either: cap = records / D
# must exceed the wave width L.
SLOT_DEPTH = 64
KEY_SPACE = 1 << 20
FILL_ENQ, DRAIN_ENQ = 0.7, 0.3


class SmokeFailure(AssertionError):
    """A phase disagreed with its reference, or did not finish."""


def payload_of(ids, W: int) -> np.ndarray:
    """The record an element id carries: word 0 is the id, the other
    words a hash of (id, word), so a misrouted row cannot pass."""
    ids = np.asarray(ids)
    rows = (ids.astype(np.uint32)[:, None] * np.uint32(40503)
            + np.arange(W, dtype=np.uint32))
    rows &= np.uint32(0x7FFFFFFF)
    rows = rows.view(np.int32)
    rows[:, 0] = ids
    return rows


def store_cap(kind: str, records: int) -> int:
    """Slots per shard and window (tier, bucket or stack depth entry) of
    a store that holds ``records`` records per shard."""
    return records // {"fifo": 1, "lifo": SLOT_DEPTH, "priority": N_TIERS,
                       "seap": N_BUCKETS}[kind]


def make_queue(kind: str, n_shards: int, records: int, W: int, L: int,
               devices=None):
    """The elastic structure whose store holds ``records`` records of W
    int32 words per shard, split evenly over its windows."""
    kw = dict(cap=store_cap(kind, records), payload_width=W,
              ops_per_shard=L, devices=devices)
    if kind == "fifo":
        return ElasticDeviceQueue(n_shards, **kw)
    if kind == "lifo":
        return ElasticDeviceStack(n_shards, slot_depth=SLOT_DEPTH, **kw)
    if kind == "priority":
        return ElasticDevicePriorityQueue(n_shards, n_prios=N_TIERS, **kw)
    if kind == "seap":
        bounds = [b * KEY_SPACE // N_BUCKETS for b in range(1, N_BUCKETS)]
        return ElasticDeviceSeapQueue(n_shards, n_buckets=N_BUCKETS,
                                      seed_bounds=bounds, **kw)
    raise ValueError(f"unknown discipline {kind!r}")


# ----------------------------------------------------------- traffic -------
class Traffic:
    """Seeded bursts of K waves x n ops; element ids count enqueues."""

    def __init__(self, kind: str, seed: int, W: int):
        self.kind = kind
        self.W = W
        self.rng = np.random.default_rng([seed, KINDS.index(kind)])
        self.next_id = 0

    def burst(self, K: int, n: int, p_enq: float):
        """(is_enq, ids, key, payload) for one burst; ids are -1 off
        enqueues and ``key`` is the tier or Seap key (None for FIFO/LIFO)."""
        is_enq = self.rng.random((K, n)) < p_enq
        n_enq = int(is_enq.sum())
        ids = np.full((K, n), -1, np.int64)
        ids[is_enq] = np.arange(self.next_id, self.next_id + n_enq)
        self.next_id += n_enq
        payload = np.zeros((K, n, self.W), np.int32)
        payload[is_enq] = payload_of(ids[is_enq], self.W)
        key = None
        if self.kind == "priority":
            key = self.rng.integers(0, N_TIERS, (K, n), dtype=np.int32)
        elif self.kind == "seap":
            key = self.rng.integers(0, KEY_SPACE, (K, n), dtype=np.int32)
        return is_enq, ids, key, payload


# --------------------------------------------------------- references ------
class Reference:
    """Plain host model of one discipline.  ``burst`` returns the
    expected per-op arrays: ``value`` (the id a dequeue returns, -1 for
    none) and whatever of ``matched``/``pos``/``tier`` the device reports."""

    def __init__(self, kind: str, q, L: int):
        self.kind = kind
        self.L = L
        if kind == "fifo":
            self.dq = collections.deque()
            self.n_enq = 0
        elif kind == "lifo":
            self.stack = []
        elif kind == "priority":
            self.oracle = PriorityOracle(N_TIERS)
        else:
            self.oracle = SeapOracle(N_BUCKETS, q.split_occupancy,
                                     seed_bounds=q.seed_bounds)

    def burst(self, is_enq, ids, key) -> dict:
        K, n = is_enq.shape
        exp = {f: np.full((K, n), -1, np.int64)
               for f in ("value", "pos", "tier")}
        exp["matched"] = np.zeros((K, n), bool)
        for k in range(K):
            getattr(self, "_" + self.kind)(
                k, is_enq[k].tolist(), ids[k].tolist(),
                None if key is None else key[k].tolist(), exp)
        if self.kind in ("fifo", "lifo"):
            del exp["tier"]
        return exp

    def _fifo(self, k, e, ids, _key, exp):
        dq, val, pos, m = self.dq, exp["value"][k], exp["pos"][k], \
            exp["matched"][k]
        for i, enq in enumerate(e):
            if enq:
                dq.append(ids[i])
                pos[i] = self.n_enq
                self.n_enq += 1
                m[i] = True
            elif dq:
                # FIFO ids count enqueues, so an element's id is its position
                val[i] = pos[i] = dq.popleft()
                m[i] = True

    def _lifo(self, k, e, ids, _key, exp):
        st, val, pos, m = self.stack, exp["value"][k], exp["pos"][k], \
            exp["matched"][k]
        for i, enq in enumerate(e):
            if enq:
                st.append(ids[i])
                pos[i] = len(st)
                m[i] = True
            elif st:
                pos[i] = len(st)
                val[i] = st.pop()
                m[i] = True

    def _priority(self, k, e, ids, key, exp):
        ops = [("enq", key[i], ids[i], i // self.L) if e[i]
               else ("deq", 0, None, i // self.L) for i in range(len(e))]
        self._records(k, self.oracle.wave(ops, n_shards=len(e) // self.L),
                      exp)

    def _seap(self, k, e, ids, key, exp):
        ops = [("enq", key[i], ids[i]) if e[i] else ("deq", None, None)
               for i in range(len(e))]
        self._records(k, self.oracle.wave(ops), exp)

    @staticmethod
    def _records(k, recs, exp):
        exp["tier"][k] = [r.tier if hasattr(r, "tier") else r.bucket
                          for r in recs]
        exp["pos"][k] = [r.pos for r in recs]
        exp["matched"][k] = [r.matched for r in recs]
        exp["value"][k] = [-1 if r.value is None else r.value for r in recs]


def _device_fields(kind: str, outs) -> dict:
    """Name the device's per-op outputs like the reference's."""
    if kind in ("fifo", "lifo"):
        pos, matched, dv, dok, _ = outs
        f = {"pos": pos, "matched": matched}
    else:
        tier, pos, matched, dv, dok = outs[:5]
        f = {"tier": tier, "pos": pos, "matched": matched}
    f = {k: np.asarray(v) for k, v in f.items()}
    f["dv"], f["dok"] = np.asarray(dv), np.asarray(dok)
    return f


def check_burst(kind: str, exp: dict, got: dict, W: int, where: str):
    """Raise :class:`SmokeFailure` unless the device agrees op by op."""
    for field in ("matched", "pos", "tier"):
        if field not in exp:
            continue
        bad = np.argwhere(got[field] != exp[field])
        if bad.size:
            k, i = bad[0]
            raise SmokeFailure(
                f"{where}: {field} differs at wave {k} op {i}: device "
                f"{got[field][k, i]} reference {exp[field][k, i]} "
                f"({len(bad)} ops differ)")
    want = exp["value"] >= 0
    bad = np.argwhere(got["dok"] != want)
    if bad.size:
        k, i = bad[0]
        raise SmokeFailure(f"{where}: dequeue success differs at wave {k} "
                           f"op {i} ({len(bad)} ops differ)")
    rows = got["dv"][want]
    if not np.array_equal(rows, payload_of(exp["value"][want], W)):
        j = int(np.argwhere((rows != payload_of(exp["value"][want], W))
                            .any(axis=1))[0, 0])
        raise SmokeFailure(f"{where}: dequeued record {j} of the burst is "
                           f"{rows[j, :4].tolist()}..., reference id "
                           f"{int(exp['value'][want][j])}")


# ---------------------------------------------------------- compiling ------
def _compile_stats(q, args) -> dict:
    """AOT-compile the queue's ``run_waves`` program for ``args`` (the
    later call reuses it) and report what the compiler says of it."""
    t0 = time.perf_counter()
    compiled = q.inner._run_waves.lower(q.state, *args).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    out = {"compile_s": compile_s,
           "all_to_all": count_op(text, "all-to-all"),
           "tpu_custom_call": "tpu_custom_call" in text}
    if mem is not None:
        out["memory"] = {k: int(getattr(mem, k + "_size_in_bytes"))
                         for k in ("argument", "output", "temp", "alias")}
    return out


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device, where the backend reports it."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(st.get("peak_bytes_in_use"))
    return out


def store_placement(q) -> list:
    """Device id and shard shape of every piece of the sharded store."""
    arr = max(jax.tree.leaves(q.state), key=lambda x: x.nbytes)
    return [(s.device.id, tuple(s.data.shape))
            for s in arr.addressable_shards]


def _free(q) -> None:
    for leaf in jax.tree.leaves(q.state):
        leaf.delete()
    q.state = None
    gc.collect()


# ------------------------------------------------------------- phases ------
def _placed(q, is_enq, key, payload):
    ops = [is_enq, np.ones_like(is_enq)]
    if key is not None:
        ops.append(key)
    ops.append(payload)
    return [q._place(x, lead=1) for x in ops]


def _drive(q, kind, ref, traffic, *, K, L, W, max_bursts, where, stats,
           on_full=None):
    """Fill with the enqueue-heavy mix until the fullest window is at
    least half full, call ``on_full`` (if any), then drain to empty with
    the dequeue-heavy mix, checking every burst.

    A stack is drained by pops alone once less than a burst of ops is
    left: near the bottom a mixed wave re-pushes the lowest positions
    hundreds of times, more than any slot's ticket set holds (ROADMAP,
    speed item 9)."""
    target = q.window_capacity() // 2
    filling, size = True, 0
    for b in range(max_bursts):
        n = q.n_shards * L
        p_enq = (FILL_ENQ if filling
                 else 0.0 if kind == "lifo" and size < K * n else DRAIN_ENQ)
        is_enq, ids, key, payload = traffic.burst(K, n, p_enq)
        args = _placed(q, is_enq, key, payload)
        if "compile" not in stats:
            stats["compile"] = _compile_stats(q, args)
        exp = ref.burst(is_enq, ids, key)
        got = _device_fields(kind, q.run_waves(*args))
        check_burst(kind, exp, got, W, f"{where} burst {b}")
        stats["ops"] += K * n
        stats["bursts"] += 1
        size = q.size
        stats["peak_backlog"] = max(stats["peak_backlog"], size)
        if filling and max(q.occupancy()) >= target:
            filling = False
            if on_full is not None:
                on_full()
        elif not filling and size == 0:
            return
    raise SmokeFailure(f"{where}: not drained after {max_bursts} bursts "
                       f"(backlog {q.size}, target {target})")


def _max_bursts(n_shards, records, K, L) -> int:
    """Bursts to fill half the store and drain it, four times over."""
    per_burst = max(1, int(K * n_shards * L * (FILL_ENQ - DRAIN_ENQ)))
    return 4 * (-(-n_shards * records // per_burst)) + 8


def run_discipline(kind: str, *, n_shards: int, records: int, W: int,
                   L: int, K: int, seed: int, devices=None) -> dict:
    """Fill-then-drain one discipline at ``records`` records per shard;
    returns the phase's stats and frees the store."""
    t0 = time.perf_counter()
    q = make_queue(kind, n_shards, records, W, L, devices)
    store_bytes = sum(x.nbytes for x in jax.tree.leaves(q.state))
    stats = {"kind": kind, "n_shards": n_shards, "store_bytes": store_bytes,
             "records_per_shard": records, "W": W, "L": L, "K": K,
             "fused_dispatch": getattr(q.inner.engine.disc,
                                       "fused_dispatch", None),
             "placement": store_placement(q),
             "ops": 0, "bursts": 0, "peak_backlog": 0}
    ref = Reference(kind, q, L)
    _drive(q, kind, ref, Traffic(kind, seed, W), K=K, L=L, W=W,
           max_bursts=_max_bursts(n_shards, records, K, L), where=kind,
           stats=stats)
    stats["peak_bytes_in_use"] = peak_bytes(q.devices)
    _free(q)
    stats["total_s"] = time.perf_counter() - t0
    return stats


def run_membership(*, n_from: int, records: int, W: int, L: int, K: int,
                   seed: int, devices=None) -> dict:
    """A FIFO backlog through JOIN (n_from -> n_from + 1) and LEAVE (back
    to n_from), then drained; every element must come back in order."""
    t0 = time.perf_counter()
    q = make_queue("fifo", n_from, records, W, L, devices)
    stats = {"kind": "fifo-membership", "n_from": n_from, "ops": 0,
             "bursts": 0, "peak_backlog": 0, "migrations": []}

    def join_and_leave():
        for kind, fn in (("grow", lambda: q.grow(1)),
                         ("shrink", lambda: q.shrink([q.n_shards - 1]))):
            backlog = q.size
            mig = fn()
            if q.size != backlog:
                raise SmokeFailure(f"{kind} changed the backlog "
                                   f"{backlog} -> {q.size}")
            stats["migrations"].append(
                {k: mig[k] for k in ("kind", "P_from", "P_to", "moved",
                                     "bytes_moved", "wave_s", "total_s")}
                | {"placement": store_placement(q)})

    _drive(q, "fifo", Reference("fifo", q, L), Traffic("fifo", seed, W),
           K=K, L=L, W=W, max_bursts=_max_bursts(n_from, records, K, L),
           where="membership", stats=stats, on_full=join_and_leave)
    if len(stats["migrations"]) != 2:
        raise SmokeFailure("membership: the backlog never reached its "
                           "target, so no JOIN/LEAVE ran")
    stats["peak_bytes_in_use"] = peak_bytes(q.devices)
    _free(q)
    stats["total_s"] = time.perf_counter() - t0
    return stats


def greedy_reference(model, params, prompts, max_new: int, max_seq: int,
                     n_slots: int) -> list:
    """Plain greedy decoding with ``decode_fn``: the tokens each prompt
    must get, computed without the queue or the engine's slot bookkeeping.

    A prompt fills every row of the engine's ``n_slots``-wide decode
    program (``serve.engine.slot_decode``), and row 0 is read.  Random
    weights give bf16 logits with near ties, and a batch-1 program, which
    the compiler rounds differently, breaks them the other way."""
    from ..serve.engine import slot_decode
    step = slot_decode(model)
    outs = []
    for prompt in prompts:
        cache, _ = model.init_cache(n_slots, max_seq)
        out, tok = [], prompt[0]
        for p in range(max_seq - 1):
            logits, cache = step(params, cache,
                                 jnp.full((n_slots, 1), tok, jnp.int32),
                                 jnp.full((n_slots,), p, jnp.int32))
            if p + 1 < len(prompt):
                tok = prompt[p + 1]
                continue
            tok = int(np.asarray(logits, np.float32).reshape(n_slots, -1)[0]
                      .argmax())
            out.append(tok)
            if len(out) >= max_new:
                break
        outs.append(out)
    return outs


def run_serving(cfg, *, n_requests: int, priorities: int, seed: int,
                max_slots: int = 4, max_seq: int = 32,
                max_new: int = 6) -> dict:
    """``ServeEngine`` over one device with ``priorities`` SLA tiers."""
    from ..models import build_model
    from ..runtime.base import build_mesh
    from ..serve import Request, ServeEngine

    t0 = time.perf_counter()
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(seed))
    dev = jax.devices()[0]
    eng = ServeEngine(model, params, build_mesh([dev], "data"),
                      max_slots=max_slots, max_seq=max_seq,
                      priorities=priorities)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=[int(t) for t in
                                   rng.integers(0, cfg.vocab, 4)],
                    max_new=max_new, prio=i % priorities)
            for i in range(n_requests)]
    eng.submit(reqs)
    drained = eng.run_until_drained(max_steps=50 * n_requests)
    served = eng.stats["served"]
    if not drained or served != n_requests:
        raise SmokeFailure(f"serving: served {served}/{n_requests}, "
                           f"drained={drained}")
    for p in range(priorities):
        starts = [r.start_step for r in sorted(reqs, key=lambda r: r.rid)
                  if r.prio == p]
        if starts != sorted(starts):
            raise SmokeFailure(f"serving: tier {p} admitted out of FIFO "
                               f"order: start steps {starts}")
    # the first request admitted, and the last, whose slot held others
    checked = [reqs[0], max(reqs, key=lambda r: (r.start_step, r.rid))]
    wants = greedy_reference(model, params, [r.prompt for r in checked],
                             max_new, max_seq, max_slots)
    for r, want in zip(checked, wants):
        if r.out != want:
            raise SmokeFailure(f"serving: request {r.rid} decoded {r.out}, "
                               f"plain greedy loop gives {want}")
    return {"arch": cfg.name, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab,
            "requests": n_requests, "served": served, "steps": eng.step_no,
            "tokens": sum(len(r.out) for r in reqs),
            "checked_requests": [r.rid for r in checked],
            "fused_dispatch": eng.queue.inner.engine.disc.fused_dispatch,
            "peak_bytes_in_use": peak_bytes([dev]),
            "total_s": time.perf_counter() - t0}
