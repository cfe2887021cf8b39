"""The unified fused-wave engine: Stages 1-4 once, disciplines plug in.

Before this module, ``DeviceQueue``, ``DeviceStack`` and
``DevicePriorityQueue`` each carried a full copy of the fused wave body —
position assignment, the packed two-collective Stage-4 request/reply
layout, the post-enqueue-peak capacity check, and the store rewrite — so
every wave-level fix had to land three times (the PR 3 capacity bug did).
:class:`WaveEngine` owns that body once; the three structures are now thin
:class:`Discipline` plug-ins that only answer the questions that actually
differ between FIFO, LIFO and P-tier priority semantics:

* **dispatch** (Stages 1-3): assign each op of the wave a position, an
  owner shard and a store slot — FIFO via the min-plus hypercube scan,
  LIFO via the max-plus ticket scan, priority via P masked min-plus scans
  plus the batch-DeleteMin drain;
* **commit** (Stage-4 store rewrite): apply the received PUT/GET rows to
  the local store and build the packed ``ok ‖ value`` reply — the dense
  ring rewrite (queue/priority share :func:`ring_commit`) or the
  (slot, depth) ticket-set rewrite (stack).

Everything else — the ``slot ‖ extra ‖ tag ‖ payload`` request packing,
the collectives, reply extraction, the overflow surfacing, the multi-wave
``lax.scan`` driver — is engine code, written once.

Wave pipelining
---------------
``run_waves(pipelined=True)`` (the default) software-pipelines the burst:
the scan carry holds **both buffers** of a double-buffered wave — the
committed store *and* the in-flight request buffer of the previous wave —
so iteration k dispatches wave k (scans + request packing, which never
read the store) while committing wave k-1's store rewrite.  Because wave
k-1's reply becomes available exactly when wave k's request is packed,
the two ride ONE fused ``all_to_all`` (request columns of wave k ‖ reply
columns of wave k-1): a K-wave burst costs K+1 ``all_to_all`` launches
instead of 2K, and the dispatch collectives of wave k (ppermute hypercube
/ descriptor all_gather) overlap wave k-1's store scatter.  The schedule
is a pure reordering of the same integer operations, so results are
bit-identical to the sequential path — ``pipelined=False`` keeps the
one-wave-at-a-time schedule for differential testing.

    wave k:    dispatch_k ──┐                     ┌─> outputs k-1
                            ├─ ONE all_to_all ────┤
    wave k-1:  commit_{k-1}─┘   (req_k ‖ rep_k-1) └─> in-flight k

``step`` is always the sequential single wave (two collectives, the PR 1
contract, HLO-tested).

Occupancy buckets (PR 9)
------------------------
Every wave ships a ``[n_shards, width, C]`` request and a
``[n_shards, width, 1+W]`` reply through the two all_to_alls — padded to
the envelope width whether the burst staged 3 ops or 300.  The engine's
wave bodies are deliberately *width-agnostic*: every discipline derives
its per-wave length from the op arrays themselves, so lowering the same
jitted entry point at a narrower op width yields a program whose
collective operands shrink proportionally.  :func:`bucket_ladder` defines
the static ladder of envelope widths (L/4, L/2, L — deduplicated,
minimum 1) and :func:`pick_bucket_width` picks the smallest bucket that
fits a staged burst; the host-side drivers (``ElasticDeviceQueue`` and
friends via ``pick_width``, ``ServeEngine`` refill) stage their op
arrays at that width.  ``jax.jit`` keys its executable cache on the
abstract shapes, so each bucket compiles exactly once and bouncing
between widths never recompiles (the wavecheck recompile guard drives
the whole ladder to prove it); the ``[compact]`` ProgramSpecs in
``analysis/programs.py`` pin every bucket to the same ≤2-all_to_all
budget.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from ..obs.device import (MetricsState, drain as _drain_rows,
                          init_metrics_state, record_row)

TAG_INACTIVE = 0
TAG_PUT = 1
TAG_GET = 2

# The ``jax.named_scope`` of each phase of a wave: every operation of the
# wave bodies sits under one of them, so a compiled program's instructions
# (and a device trace's ops) can be put to the phase that issued them.
# ``tier_scan`` and ``deletemin`` nest inside the priority discipline's
# ``dispatch`` (``core.scan_queue.priority_queue_scan``); the innermost
# scope names an instruction's phase.
WAVE_PHASES = ("dispatch", "pack", "exchange", "commit", "reply", "merge",
               "telemetry", "tier_scan", "deletemin")


def program_name(discipline: str, entry: str) -> str:
    """The stable name of one jitted program (its module compiles as
    ``jit_<name>``): ``skueue_fifo_waves``, ``skueue_lifo_step``,
    ``skueue_fifo_migrate_4to3``."""
    return f"skueue_{discipline}_{entry}"


def named(fn, name: str):
    """``fn`` renamed to ``name`` before ``shard_map``/``jax.jit`` wrap it,
    so that the program's module, its compile events and its device-trace
    module all carry the name."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _abstract(x):
    """Shape, dtype and (for a committed array) sharding of an argument:
    enough to lower a program again to the executable jax already holds."""
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


# ------------------------------------------------- occupancy buckets -------
def bucket_ladder(L: int) -> tuple:
    """The static ladder of per-shard envelope widths for full width
    ``L``: {L/4, L/2, L} deduplicated, ascending, floored at 1.  Small
    and static on purpose — each rung is one cached executable per entry
    point, and three rungs already cover the low-utilization regimes
    (≤25%, ≤50%) where compaction pays."""
    return tuple(sorted({max(1, L // 4), max(1, L // 2), L}))


def pick_bucket_width(L: int, n_shards: int, n_ops: int) -> int:
    """Smallest ladder width ``w`` with ``n_shards * w >= n_ops`` —
    the envelope a burst of ``n_ops`` staged ops rides.  Bursts larger
    than the full envelope return ``L`` (the multi-wave chunking above
    this call handles them)."""
    for w in bucket_ladder(L):
        if n_shards * w >= n_ops:
            return w
    return L


# ------------------------------------------------------ shared helpers -----
def post_enqueue_peak_overflow(first, new_last, capacity):
    """THE post-enqueue-peak capacity check (one copy; was fixed three
    times in PR 3 across the fused queue, the legacy queue, and the
    priority queue).

    A wave applies PUTs before GETs, so capacity must hold at the
    *post-enqueue peak*: a same-wave dequeue that shrinks the size back
    under ``capacity`` does NOT undo the head slot a wrapped-around
    enqueue already overwrote.  Only enqueues move ``last``, so
    ``new_last - first`` (with ``first`` from *before* the wave) is that
    peak.  Accepts scalars (queue) or per-tier ``[P]`` vectors (priority,
    where ``capacity`` is per tier); returns one replicated bool.
    """
    return jnp.any((new_last - first + 1) > capacity)


def build_send(owner, col_payload, active, n_shards, sentinel):
    """Scatter local ops into a [n_shards, L, ...] send buffer by owner
    row (one column per collective — the legacy five-collective path)."""
    rows = jnp.arange(n_shards, dtype=jnp.int32)[:, None]
    hit = (rows == owner[None, :]) & active[None, :]
    if col_payload.ndim == 1:
        return jnp.where(hit, col_payload[None, :], sentinel)
    return jnp.where(hit[..., None], col_payload[None, :, :], sentinel)


def build_send_packed(owner, cols, active, n_shards, fill):
    """Fused scatter: cols [L, C] into a [n_shards, L, C] send buffer;
    rows not owned by a shard carry the ``fill`` [C] sentinel column."""
    rows = jnp.arange(n_shards, dtype=jnp.int32)[:, None]
    hit = (rows == owner[None, :]) & active[None, :]
    return jnp.where(hit[..., None], cols[None, :, :], fill[None, None, :])


def ring_commit(store, recv, junk: int, W: int):
    """Stage-4 store rewrite for the dense sharded ring (queue AND
    priority queue — the tier window is already encoded in the slot).

    Applies PUTs before GETs (same-wave ENQ visible to DEQ), removes on
    read, and routes every inactive row to the ``junk`` slot.  Returns
    (new_store, packed ``ok ‖ value`` reply, commit-time overflow=False —
    ring capacity is a dispatch-time check, :func:`post_enqueue_peak_overflow`).
    """
    sv, sf = store[0][0], store[1][0]      # local shard views
    r_slot, r_tag, r_vals = recv[..., 0], recv[..., 1], recv[..., 2:]
    put_slot = jnp.where(r_tag == TAG_PUT, r_slot, junk).reshape(-1)
    sv = sv.at[put_slot].set(r_vals.reshape(-1, W))   # junk row eats
    sf = sf.at[put_slot].set(True)
    sf = sf.at[junk].set(False)
    is_get = r_tag == TAG_GET
    get_slot = jnp.where(is_get, r_slot, junk)        # [n, L]
    res_vals = sv[get_slot]                           # [n, L, W]
    res_ok = is_get & sf[get_slot] & (get_slot < junk)
    sf = sf.at[get_slot.reshape(-1)].set(False)       # remove on read
    sf = sf.at[junk].set(False)
    reply = jnp.concatenate(
        [res_ok.astype(jnp.int32)[..., None], res_vals], axis=-1)
    return (sv[None], sf[None]), reply, jnp.zeros((), bool)


# ------------------------------------------------- discipline contract -----
class Dispatch(NamedTuple):
    """What a discipline's Stages 1-3 hand to the engine for one wave."""
    owner: jax.Array        # [L] destination shard, -1 for unrouted ops
    slot: jax.Array         # [L] destination slot (junk when unrouted)
    tag: jax.Array          # [L] TAG_PUT / TAG_GET / TAG_INACTIVE
    extra: tuple            # extra request columns, each [L] int32
    payload: jax.Array      # [L, W] int32
    active: jax.Array       # [L] rows that travel (matched ops)
    wants_reply: jax.Array  # [L] ops whose reply is extracted (dequeues)
    outs: tuple             # dispatch-time per-op outputs (pos, matched, ...)
    carry: tuple            # updated interval carry
    overflow: jax.Array     # replicated bool (dispatch-time capacity check)
    aux: tuple              # replicated per-wave extras (e.g. n_relaxed)


class Discipline:
    """Position-assignment + store-rewrite plug-in for :class:`WaveEngine`.

    Subclasses define class attributes ``name`` (the discipline's part of
    its programs' names), ``n_ops`` (op input arrays per
    wave), ``n_disp_outs`` (dispatch-time per-op outputs), ``n_aux``
    (replicated per-wave extras) and ``extra_fill`` (sentinel values for
    extra request columns), instance attributes ``W`` / ``junk`` /
    ``state_specs``, and the methods below.  All methods run *inside*
    shard_map on per-shard local views.
    """

    name: str = "wave"
    n_ops: int = 3
    n_disp_outs: int = 2
    n_aux: int = 0
    extra_fill: tuple = ()
    # Wavescope telemetry: number of interval windows (1 for FIFO/LIFO,
    # one per tier/bucket otherwise) and per-window element capacity —
    # instances set both; occupancy() reads the post-dispatch carry.
    n_windows: int = 1
    window_capacity: int = 0

    def split(self, state):
        """state -> (interval carry tuple, store tuple)."""
        raise NotImplementedError

    def merge(self, carry, store):
        """(carry, store) -> state (inverse of split)."""
        raise NotImplementedError

    def dispatch(self, carry, ops) -> Dispatch:
        """Stages 1-3: assign positions/owners/slots for one wave."""
        raise NotImplementedError

    def commit(self, store, recv):
        """Stage-4 rewrite: -> (store, reply [n, L, 1+W], commit_ovf)."""
        raise NotImplementedError

    def zero_outs(self, L: int) -> tuple:
        """Dtype-correct zeros for ``Dispatch.outs`` (pipeline priming)."""
        raise NotImplementedError

    def zero_aux(self) -> tuple:
        """Dtype-correct zeros for ``Dispatch.aux``."""
        return ()

    def occupancy(self, carry) -> jax.Array:
        """Replicated ``[n_windows]`` int32 occupancy vector computed
        from a (post-dispatch) interval carry — pure arithmetic, feeds
        the Wavescope metrics row."""
        raise NotImplementedError


# --------------------------------------------------------- the engine ------
class WaveEngine:
    """One fused wave body for every device structure.

    ``step`` runs one sequential wave (two collectives: packed request +
    packed reply).  ``run_waves`` executes K pre-staged waves in one
    ``lax.scan`` dispatch — software-pipelined by default (see module
    docstring), or the sequential schedule with ``pipelined=False``.
    Both jitted entry points donate the state argument.

    With ``metrics=True`` every wave additionally writes one Wavescope
    row (ops admitted per kind, ⊥ count, per-window occupancy, headroom,
    the discipline's aux signal) into a donated device-side ring carried
    through the burst — pure arithmetic on values the wave already
    materializes, ZERO extra collectives, identical queue outputs.  The
    jitted entry points then take/return ``(state, MetricsState)`` as the
    donated leading argument; the *public* ``step``/``run_waves`` keep
    the metrics-off signature by threading the engine-owned ring
    internally, and :meth:`drain_metrics` is the one sanctioned
    device→host telemetry read (burst boundaries only).
    """

    def __init__(self, mesh, axis_name: str, discipline: Discipline, *,
                 pipelined: bool = True, metrics: bool = False,
                 metrics_ring: int = 64, runtime=None):
        if runtime is None:
            # mesh may be a Runtime (PR 10) or a bare Mesh (adopted into
            # a transparent LocalRuntime — same object, same jit keys)
            from ..runtime import as_runtime
            runtime, mesh, axis_name = as_runtime(mesh, axis_name)
        self.runtime = runtime
        self.mesh = mesh
        self.axis = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.disc = discipline
        self.pipelined = pipelined
        self.metrics = bool(metrics)
        self.metrics_ring = int(metrics_ring)
        self._mstate = self.init_metrics_state() if self.metrics else None
        self._seq0 = 0  # waves drained-and-reset before the current ring
        # (entry, op shapes) -> abstract arguments of each program
        # dispatched, for :meth:`compiled_programs`
        self._dispatched: dict = {}
        self._step = self._build_step()
        self._run_waves = self._build_run_waves()

    # --------------------------------------------------- request packing ---
    def _req_fill(self):
        d = self.disc
        return jnp.concatenate(
            [jnp.array([d.junk, *d.extra_fill, TAG_INACTIVE], jnp.int32),
             jnp.zeros((d.W,), jnp.int32)])

    def _pack_request(self, d: Dispatch):
        cols = jnp.concatenate(
            [d.slot[:, None]]
            + [e.astype(jnp.int32)[:, None] for e in d.extra]
            + [d.tag.astype(jnp.int32)[:, None], d.payload], axis=1)
        return build_send_packed(d.owner, cols, d.active, self.n_shards,
                                 self._req_fill())

    def _extract_reply(self, back, owner, wants_reply):
        """Local op j's reply sits at [owner[j], j] of the reply buffer.

        Selected with a one-hot mask over the n reply rows (one term of
        the sum is nonzero), not gathered per op: XLA:TPU lowers the
        gather ``back[own_row, j]`` to a loop of L row slices a wave.
        One shard has one row to take."""
        n = self.n_shards
        if n == 1:
            mine = back[0]
        else:
            own_row = jnp.clip(owner, 0, n - 1)
            mask = own_row[None, :] == jnp.arange(n)[:, None]
            mine = jnp.sum(jnp.where(mask[..., None], back, 0), axis=0,
                           dtype=back.dtype)
        vals = jnp.where(wants_reply[:, None], mine[:, 1:], jnp.int32(0))
        ok = wants_reply & (mine[:, 0] > 0)
        return vals, ok

    # ---------------------------------------------------------- metrics ----
    def _metric_row(self, d: Dispatch, ops, seq):
        """One Wavescope row from values wave ``seq`` already
        materialized at dispatch time — per-shard op counters plus the
        replicated occupancy/headroom gauges.  No collective, no host
        callback (see ``obs.device`` for the row schema)."""
        disc = self.disc
        valid = ops[1]
        puts = jnp.sum(((d.tag == TAG_PUT) & d.active).astype(jnp.int32))
        gets = jnp.sum(((d.tag == TAG_GET) & d.active).astype(jnp.int32))
        offered = jnp.sum(valid.astype(jnp.int32))
        bottom = jnp.sum((valid & ~d.active).astype(jnp.int32))
        occ = disc.occupancy(d.carry).astype(jnp.int32)
        headroom = (jnp.int32(disc.n_windows * disc.window_capacity)
                    - jnp.sum(occ))
        aux = (d.aux[0].astype(jnp.int32) if d.aux else jnp.int32(0))
        # the wave's per-shard envelope width — static per trace, so each
        # occupancy bucket stamps its rows with the width it rode (PR 9)
        width = jnp.int32(valid.shape[0])
        head = jnp.stack([seq.astype(jnp.int32), puts, gets, offered,
                          bottom, aux, headroom, width])
        return jnp.concatenate([head, occ])

    # ------------------------------------------------------- wave bodies ---
    def _wave(self, state, ops, m: MetricsState | None = None):
        """One sequential wave: dispatch -> request a2a -> commit ->
        reply a2a -> extract.  Exactly two all_to_all collectives —
        with or without the metrics row (``m`` threads the Wavescope
        ring; telemetry is dispatch-time arithmetic only)."""
        disc = self.disc
        carry, store = disc.split(state)
        with jax.named_scope("dispatch"):
            d = disc.dispatch(carry, ops)
        if m is not None:
            with jax.named_scope("telemetry"):
                m = record_row(m, self._metric_row(d, ops, m.count))
        with jax.named_scope("pack"):
            req = self._pack_request(d)
        with jax.named_scope("exchange"):
            recv = lax.all_to_all(req, self.axis, 0, 0, tiled=True)
        with jax.named_scope("commit"):
            store, reply, c_ovf = disc.commit(store, recv)
        with jax.named_scope("exchange"):
            back = lax.all_to_all(reply, self.axis, 0, 0, tiled=True)
        with jax.named_scope("reply"):
            dv, dok = self._extract_reply(back, d.owner, d.wants_reply)
            ovf = jnp.logical_or(d.overflow, c_ovf)
        with jax.named_scope("merge"):
            merged = disc.merge(d.carry, store)
        outs = d.outs + (dv, dok, ovf) + d.aux
        if m is None:
            return merged, outs
        return (merged, m), outs

    def _multi_sequential(self, state, ops, m: MetricsState | None = None):
        if m is None:
            st, outs = lax.scan(self._wave, state, ops)
            return (st,) + outs

        def wave_m(sm, xs):
            return self._wave(sm[0], xs, sm[1])

        sm, outs = lax.scan(wave_m, (state, m), ops)
        return (sm,) + outs

    def _multi_pipelined(self, state, ops, m: MetricsState | None = None):
        """K waves, software-pipelined: iteration k dispatches wave k and
        commits wave k-1; ONE fused all_to_all carries wave k's request
        columns alongside wave k-1's reply columns.  Outputs are all
        emitted at commit time (one iteration later than dispatch), so the
        stacked scan outputs are shifted by one and the last wave drains
        through a reply-only epilogue collective."""
        disc = self.disc
        n, L = self.n_shards, ops[0].shape[1]
        C_req = 2 + len(disc.extra_fill) + disc.W
        carry0, store0 = disc.split(state)
        with jax.named_scope("pack"):
            # an all-sentinel in-flight buffer commits as a no-op
            recv0 = jnp.tile(self._req_fill()[None, None, :], (n, L, 1))
        prime = {
            "recv": recv0,
            "owner": jnp.full((L,), -1, jnp.int32),
            "wants": jnp.zeros((L,), bool),
            "outs": disc.zero_outs(L),
            "ovf": jnp.zeros((), bool),
            "aux": disc.zero_aux(),
        }

        def body(c, xs):
            if m is None:
                carry, store, infl = c
                mm = None
            else:
                carry, store, infl, mm = c
            with jax.named_scope("dispatch"):
                d = disc.dispatch(carry, xs)              # wave k
            if mm is not None:
                with jax.named_scope("telemetry"):
                    mm = record_row(mm, self._metric_row(d, xs, mm.count))
            with jax.named_scope("commit"):               # wave k-1
                store, reply, c_ovf = disc.commit(store, infl["recv"])
            with jax.named_scope("pack"):
                fused = jnp.concatenate([self._pack_request(d), reply],
                                        axis=-1)
            with jax.named_scope("exchange"):
                out = lax.all_to_all(fused, self.axis, 0, 0, tiled=True)
                recv = out[..., :C_req]
            with jax.named_scope("reply"):
                dv, dok = self._extract_reply(out[..., C_req:],
                                              infl["owner"], infl["wants"])
                emitted = (infl["outs"]
                           + (dv, dok, jnp.logical_or(infl["ovf"], c_ovf))
                           + infl["aux"])
            infl = {"recv": recv, "owner": d.owner,
                    "wants": d.wants_reply, "outs": d.outs,
                    "ovf": jnp.asarray(d.overflow), "aux": d.aux}
            nc = ((d.carry, store, infl) if m is None
                  else (d.carry, store, infl, mm))
            return nc, emitted

        init = ((carry0, store0, prime) if m is None
                else (carry0, store0, prime, m))
        final, stacked = lax.scan(body, init, ops)
        if m is None:
            carry, store, infl = final
        else:
            carry, store, infl, m = final
        # epilogue: commit the last in-flight wave, reply-only collective
        with jax.named_scope("commit"):
            store, reply, c_ovf = disc.commit(store, infl["recv"])
        with jax.named_scope("exchange"):
            back = lax.all_to_all(reply, self.axis, 0, 0, tiled=True)
        with jax.named_scope("reply"):
            dv, dok = self._extract_reply(back, infl["owner"], infl["wants"])
            last = (infl["outs"]
                    + (dv, dok, jnp.logical_or(infl["ovf"], c_ovf))
                    + infl["aux"])
            # drop the priming wave's garbage row, append the drained last
            # wave
            outs = tuple(jnp.concatenate([s[1:], l[None]], axis=0)
                         for s, l in zip(stacked, last))
        with jax.named_scope("merge"):
            merged = disc.merge(carry, store)
        if m is None:
            return (merged,) + outs
        return ((merged, m),) + outs

    # ---------------------------------------------------- jitted wrappers --
    def _m_specs(self):
        return MetricsState(P(), P(self.axis))

    def _out_specs(self, multi: bool = False):
        d = self.disc
        op = P(None, self.axis) if multi else P(self.axis)
        rep = P(None) if multi else P()
        st = ((d.state_specs, self._m_specs()) if self.metrics
              else d.state_specs)
        return ((st,) + (op,) * (d.n_disp_outs + 2)
                + (rep,) * (1 + d.n_aux))

    def _build_step(self):
        if self.metrics:
            def fn(sm, *ops):
                smm, outs = self._wave(sm[0], ops, sm[1])
                return (smm,) + outs
        else:
            def fn(state, *ops):
                st, outs = self._wave(state, ops)
                return (st,) + outs
        in_state = ((self.disc.state_specs, self._m_specs())
                    if self.metrics else self.disc.state_specs)
        wrapped = shard_map(
            named(fn, program_name(self.disc.name, "step")), mesh=self.mesh,
            in_specs=(in_state,) + (P(self.axis),) * self.disc.n_ops,
            out_specs=self._out_specs())
        return jax.jit(wrapped, donate_argnums=(0,))

    def _build_run_waves(self):
        body = (self._multi_pipelined if self.pipelined
                else self._multi_sequential)

        if self.metrics:
            def fn(sm, *ops):
                return body(sm[0], ops, sm[1])
        else:
            def fn(state, *ops):
                return body(state, ops)
        in_state = ((self.disc.state_specs, self._m_specs())
                    if self.metrics else self.disc.state_specs)
        wrapped = shard_map(
            named(fn, program_name(self.disc.name, "waves")), mesh=self.mesh,
            in_specs=(in_state,) + (P(None, self.axis),) * self.disc.n_ops,
            out_specs=self._out_specs(multi=True))
        return jax.jit(wrapped, donate_argnums=(0,))

    def step(self, state, *ops):
        """One wave.  The state argument is DONATED.  With metrics on,
        the engine-owned telemetry ring rides the donated tuple
        internally — same external signature either way."""
        return self._call("step", self._step, state, ops)

    def run_waves(self, state, *ops):
        """K pre-staged waves in ONE device dispatch (state DONATED)."""
        return self._call("waves", self._run_waves, state, ops)

    def _call(self, entry: str, prog, state, ops):
        arg = (state, self._mstate) if self.metrics else state
        key = (entry,) + tuple(o.shape for o in ops)
        if key not in self._dispatched:
            self._dispatched[key] = jax.tree.map(_abstract, (arg,) + ops)
        out = prog(arg, *ops)
        if not self.metrics:
            return out
        st, self._mstate = out[0]
        return (st,) + tuple(out[1:])

    def compiled_programs(self) -> list:
        """``(key, compiled text)`` of each program this engine has
        dispatched: one per entry point, burst length and width, ``key``
        being ``(entry, shape of each op array)``.  The executables come
        from jax's cache of the programs already run; nothing compiles."""
        out = []
        for key, args in self._dispatched.items():
            prog = self._run_waves if key[0] == "waves" else self._step
            out.append((key, prog.lower(*args).compile().as_text()))
        return out

    def phase_tables(self) -> list:
        """For each program of :meth:`compiled_programs`, the phase of each
        instruction: ``{instruction name: phase}`` over
        :data:`WAVE_PHASES`.  Instruction names are those a device trace
        gives its ops."""
        from ..analysis.hlo import scope_table
        return [scope_table(t, WAVE_PHASES)
                for _, t in self.compiled_programs()]

    # ----------------------------------------------------- metrics drain ---
    def init_metrics_state(self) -> MetricsState:
        """A zeroed Wavescope ring placed on this engine's mesh (the
        placement itself rides the runtime handle)."""
        return init_metrics_state(self.n_shards, self.metrics_ring,
                                  self.disc.n_windows, self.mesh, self.axis,
                                  runtime=self.runtime)

    def drain_metrics(self, *, reset: bool = False) -> list:
        """Drain the telemetry ring to host wave-summary dicts (oldest
        first).  THE sanctioned burst-boundary device→host telemetry
        read; with ``reset=True`` the ring restarts empty (the wave
        sequence number keeps running)."""
        if not self.metrics:
            return []
        rows = _drain_rows(self._mstate)
        for r in rows:
            r["seq"] += self._seq0
        if reset:
            self._seq0 += int(jnp.asarray(self._mstate.count))
            self._mstate = self.init_metrics_state()
        return rows


# -------------------------------------------------- migration machinery ----
def dest_rank(owner: jax.Array, live: jax.Array, n_mesh: int) -> jax.Array:
    """Exclusive rank of each live entry among earlier entries with the
    same destination — its row in the packed per-destination send buffer."""
    ids = jnp.arange(n_mesh, dtype=jnp.int32)
    oh = ((owner[:, None] == ids[None, :]) & live[:, None]).astype(jnp.int32)
    excl = jnp.cumsum(oh, axis=0) - oh
    return excl[jnp.arange(owner.shape[0]), jnp.clip(owner, 0, n_mesh - 1)]


def fanout_bound(P_old: int, P_new: int, cap: int) -> int:
    """Max elements one source shard can owe one destination shard.

    Live positions occupy a window of at most ``min(P_old, P_new) * cap``
    consecutive integers (old occupancy and new capacity both bound it);
    positions on shard ``s`` (mod P_old) owned by ``d`` (mod P_new) recur
    with stride ``lcm(P_old, P_new)``."""
    window = min(P_old, P_new) * cap
    per_pair = -(-window // math.lcm(P_old, P_new))
    return min(cap, per_pair + 1)  # +1 alignment slack


def recover_positions(s, t, first, P_old: int, cap: int):
    """Invert the round-robin layout: the position slot ``t`` on shard
    ``s`` holds is the unique ``p = s + P_old*j`` with ``j ≡ t (mod cap)``
    and ``p`` in the live window starting at ``first`` (unique because a
    live window spans at most ``P_old * cap`` positions)."""
    j_lo = -((s - first) // P_old)
    j = j_lo + jnp.mod(t - j_lo, cap)
    return s + P_old * j


def migrate_packed(axis: str, n_mesh: int, M: int, live, owner, cols, fill):
    """The ONE packed migration all_to_all every elastic structure uses:
    scatter ``cols`` rows (column 0 = destination slot / junk sentinel)
    into rank-within-destination rows, exchange, and return the received
    rows flattened.  Also returns (moved count, fanout-overflow flag)."""
    rank = dest_rank(owner, live, n_mesh)
    lost = lax.pmax(
        (live & (rank >= M)).any().astype(jnp.int32), axis) > 0
    buf = jnp.tile(fill[None, None, :], (n_mesh, M + 1, 1))
    d_i = jnp.where(live, owner, 0)
    r_i = jnp.where(live, jnp.minimum(rank, M), M)
    buf = buf.at[d_i, r_i].set(
        jnp.where(live[:, None], cols, fill[None, :]))
    recv = lax.all_to_all(buf[:, :M], axis, 0, 0, tiled=True)
    moved = lax.psum(jnp.sum(live.astype(jnp.int32)), axis)
    return recv.reshape(-1, cols.shape[1]), moved, lost


def rewrite_ring_store(rows, junk: int, W: int):
    """Rebuild a dense ring store from received ``new_slot ‖ payload``
    migration rows (sentinel rows land on — and are wiped from — the junk
    row)."""
    rs = rows[:, 0]
    nsv = jnp.zeros((junk + 1, W), jnp.int32).at[rs].set(rows[:, 1:])
    nsv = nsv.at[junk].set(0)
    nsf = jnp.zeros((junk + 1,), bool).at[rs].set(True)
    nsf = nsf.at[junk].set(False)
    return nsv[None], nsf[None]
