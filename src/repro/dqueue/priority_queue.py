"""Device-resident P-tier priority queue on the fused Stage-4 wave path.

Skeap (arXiv:1805.03472) extends SKUEUE's batch-aggregation protocol to
distributed priority queues; in the constant-priority regime the queue is
P independent SKUEUE position intervals tie-broken by tier.  This module is
that design as a :class:`~.wave_engine.WaveEngine` discipline: the sharded
ring store gains one round-robin slot *window per tier* — tier ``p``'s
position ``q`` lives on shard ``q % n_shards`` at slot
``p * cap + (q // n_shards) % cap`` — and Stage-4 dispatch stays TWO fused
``all_to_all`` collectives per wave (ONE per wave in the pipelined burst
schedule; the slot already encodes the tier window, so nothing else
changes on the wire).

Only the *dispatch* differs from FIFO (the commit is the shared dense-ring
rewrite, :func:`~.wave_engine.ring_commit`):

* op descriptors (enq/valid/prio: 5 bits per op) ride one tiny
  ``all_gather`` — the same trick the stack discipline uses — after which
  position assignment is fully replicated;
* enqueues get per-tier FIFO positions from P masked min-plus scans
  (``core.scan_queue.priority_queue_scan``, reusing the PR 1 transforms);
* the wave's dequeues are resolved highest-priority-first *inside the
  wave*: the d-th dequeue (wave order) takes the d-th element of the
  priority-ordered pool — Skeap's batch-DeleteMin assignment — via
  per-tier prefix sums, no sequential loop in strict mode;
* ``relaxation=k`` switches the resolution to a replicated in-wave scan
  that lets a dequeue take a *locally owned* lower-tier head (at most k
  tiers below the strictly-best one) instead of a remote best-tier head —
  bounded tier skew (never per-tier FIFO violation) traded for serves
  that avoid the cross-shard hop, after arXiv:2503.02164.

Differentially tested op-by-op against the host
:class:`repro.core.priority.PriorityOracle` (same wave semantics,
independent implementation).  :class:`ElasticDevicePriorityQueue` adds the
PR 2 membership story: grow/shrink re-materializes every tier window with
ONE packed migration all_to_all, and the per-tier layout (n_prios, cap,
relaxation) is recorded in checkpoint manifests for cold-start resharding.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.scan_queue import priority_queue_scan
from ..kernels.backend import use_fused_dispatch
from .elastic import _MultiWindowElastic
from .wave_engine import (Discipline, Dispatch, TAG_GET, TAG_INACTIVE,
                          TAG_PUT, WaveEngine,
                          post_enqueue_peak_overflow, ring_commit)


class PriorityQueueState(NamedTuple):
    """P-tier queue state: per-tier replicated ``[firsts, lasts]`` live
    windows plus the sharded ring store (one slot window per tier)."""

    firsts: jax.Array         # [P] replicated int32
    lasts: jax.Array          # [P] replicated int32
    store_vals: jax.Array     # [n_shards(sharded), P*cap + 1, W] int32
    store_full: jax.Array     # [n_shards(sharded), P*cap + 1] bool

    @property
    def sizes(self) -> jax.Array:
        """Per-tier occupancy vector ``[P]`` (traced)."""
        return self.lasts - self.firsts + 1


class PriorityDiscipline(Discipline):
    """Skeap constant-priority order: P masked min-plus scans + in-wave
    batch-DeleteMin dequeue resolution over the shared dense-ring store."""

    name = "prio"
    n_ops = 4           # (is_enq, valid, prio, payload)
    n_disp_outs = 3     # (tier, pos, matched)
    n_aux = 1           # n_relaxed

    def __init__(self, axis: str, n_shards: int, n_prios: int, cap: int,
                 W: int, relaxation: int,
                 fused_dispatch: bool | None = None):
        self.axis = axis
        self.n_shards = n_shards
        self.n_prios = n_prios
        self.cap = cap
        self.W = W
        self.relaxation = relaxation
        self.junk = n_prios * cap
        self.n_windows = n_prios
        self.window_capacity = n_shards * cap
        # on compiled backends the P masked min-plus scans collapse to ONE
        # pallas sweep (grid = tiers x tiles); the jnp loop stays the CPU
        # path AND the differential oracle (None = autodetect, PR 9)
        if fused_dispatch is None:
            fused_dispatch = use_fused_dispatch()
        self.fused_dispatch = bool(fused_dispatch)
        if self.fused_dispatch:
            from ..kernels.segscan import make_tier_scan
            self._tier_scan = make_tier_scan(n_prios)
        else:
            self._tier_scan = None
        self.state_specs = PriorityQueueState(P(), P(), P(axis), P(axis))

    def split(self, state):
        """Split state into its (replicated carry, sharded store) halves."""
        return (state.firsts, state.lasts), (state.store_vals,
                                             state.store_full)

    def merge(self, carry, store):
        """Reassemble the full state from (carry, store) halves."""
        return PriorityQueueState(carry[0], carry[1], store[0], store[1])

    def dispatch(self, carry, ops) -> Dispatch:
        """Stages 1-3: assign positions and build the routed Dispatch."""
        is_enq, valid, prio, payload = ops
        firsts, lasts = carry
        n_shards, cap, P_ = self.n_shards, self.cap, self.n_prios
        L = is_enq.shape[0]

        # ---- gather the op descriptors (5ish bits/op) and assign
        #      replicated: every shard runs the same per-tier scans ----
        code = (prio.astype(jnp.int32) * 4
                + is_enq.astype(jnp.int32) * 2 + valid.astype(jnp.int32))
        g = lax.all_gather(code, self.axis, tiled=True)     # [n_shards * L]
        shard_of = (jnp.arange(g.shape[0], dtype=jnp.int32) // L)
        tier_g, pos_g, matched_g, new_firsts, new_lasts, n_relaxed = (
            priority_queue_scan(
                (g & 2) > 0, g >> 2, (g & 1) > 0, firsts, lasts,
                n_prios=P_, relaxation=self.relaxation,
                shard_of=shard_of, n_shards=n_shards,
                tier_scan=self._tier_scan))

        i0 = lax.axis_index(self.axis) * L
        tier = lax.dynamic_slice_in_dim(tier_g, i0, L)
        pos = lax.dynamic_slice_in_dim(pos_g, i0, L)
        matched = lax.dynamic_slice_in_dim(matched_g, i0, L)

        owner = jnp.where(matched, pos % n_shards, -1).astype(jnp.int32)
        slot = jnp.where(matched, tier * cap + (pos // n_shards) % cap,
                         self.junk).astype(jnp.int32)
        tag = jnp.where(matched & is_enq, TAG_PUT,
                        jnp.where(matched & ~is_enq, TAG_GET, TAG_INACTIVE))
        # capacity holds per tier (each tier owns its own slot window)
        ovf = post_enqueue_peak_overflow(firsts, new_lasts, n_shards * cap)
        return Dispatch(owner, slot, tag, (), payload, matched,
                        matched & ~is_enq, (tier, pos, matched),
                        (new_firsts, new_lasts), ovf, (n_relaxed,))

    def commit(self, store, recv):
        """Stage 4: apply this shard's routed requests to its store."""
        return ring_commit(store, recv, self.junk, self.W)

    def zero_outs(self, L: int) -> tuple:
        """All-invalid per-op dispatch outputs (padding waves)."""
        return (jnp.full((L,), -1, jnp.int32),
                jnp.full((L,), -1, jnp.int32), jnp.zeros((L,), bool))

    def zero_aux(self) -> tuple:
        """Zeroed auxiliary per-wave outputs (padding waves)."""
        return (jnp.int32(0),)

    def occupancy(self, carry):
        """Per-window occupancy vector from the carry (traced)."""
        return carry[1] - carry[0] + 1


class DevicePriorityQueue:
    """Distributed constant-priority queue over one mesh axis.

    Args:
      mesh/axis_name: the shard axis; n_prios: number of priority tiers P
        (0 = most urgent); cap: slots per shard PER TIER; payload_width:
        int32 words per element; ops_per_shard: wave width L;
      relaxation: 0 = strict priority order; k > 0 allows a dequeue to be
        served from a locally-owned head up to k tiers below the best
        non-empty tier (see module docstring);
      pipelined: multi-wave bursts use the engine's software-pipelined
        schedule (False = sequential; results identical).
    """

    def __init__(self, mesh, axis_name: str = "data", n_prios: int = 2,
                 cap: int = 1024, payload_width: int = 4,
                 ops_per_shard: int = 64, relaxation: int = 0,
                 pipelined: bool = True, metrics: bool = False,
                 metrics_ring: int = 64,
                 fused_dispatch: bool | None = None, runtime=None):
        if n_prios < 1:
            raise ValueError("need at least one priority tier")
        from ..runtime import as_runtime
        self.runtime, mesh, axis_name = as_runtime(mesh, axis_name,
                                                   runtime=runtime)
        self.mesh = mesh
        self.axis = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.n_prios = n_prios
        self.cap = cap
        self.W = payload_width
        self.L = ops_per_shard
        self.relaxation = relaxation
        self.pipelined = pipelined
        self.metrics = metrics
        self.engine = WaveEngine(
            mesh, axis_name,
            PriorityDiscipline(axis_name, self.n_shards, n_prios, cap,
                               payload_width, relaxation,
                               fused_dispatch=fused_dispatch),
            pipelined=pipelined, metrics=metrics, metrics_ring=metrics_ring,
            runtime=self.runtime)
        self._step = self.engine._step
        self._run_waves = self.engine._run_waves

    def init_state(self) -> PriorityQueueState:
        """Freshly sharded empty state on this structure's mesh."""
        n, cap, W, P_ = self.n_shards, self.cap, self.W, self.n_prios
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        rep = jax.sharding.NamedSharding(self.mesh, P())
        put = self.runtime.put
        return PriorityQueueState(
            firsts=put(jnp.zeros((P_,), jnp.int32), rep),
            lasts=put(jnp.full((P_,), -1, jnp.int32), rep),
            store_vals=put(
                jnp.zeros((n, P_ * cap + 1, W), jnp.int32), sharding),
            store_full=put(
                jnp.zeros((n, P_ * cap + 1), bool), sharding),
        )

    def step(self, state: PriorityQueueState, is_enq, valid, prio, payload):
        """Process one global wave.  The state argument is DONATED.

        is_enq/valid: [n_shards * L] bool; prio: [n_shards * L] int32 in
        [0, n_prios) (ignored for dequeues); payload: [n_shards * L, W].
        Returns (new_state, tier, pos, matched, deq_vals, deq_ok, overflow,
        n_relaxed) — tier/pos are -1/⊥ for unmatched ops.
        """
        return self.engine.step(state, is_enq, valid, prio, payload)

    def run_waves(self, state: PriorityQueueState, is_enq, valid, prio,
                  payload):
        """K pre-staged waves in ONE lax.scan dispatch (state DONATED).

        Shapes: is_enq/valid/prio [K, n_shards * L]; payload [K, ..., W].
        """
        return self.engine.run_waves(state, is_enq, valid, prio, payload)

    def drain_metrics(self, *, reset: bool = False) -> list:
        """Burst-boundary Wavescope drain (empty when metrics are off)."""
        return self.engine.drain_metrics(reset=reset)


class ElasticDevicePriorityQueue(_MultiWindowElastic):
    """P-tier priority queue whose shard count is a runtime variable.

    Owns its state like :class:`~.elastic.ElasticDeviceQueue`; ``grow`` /
    ``shrink`` / ``resize`` re-materialize every tier window onto the new
    mesh with one packed migration all_to_all (the PR 2 wave, vectorized
    over the P tier windows via the shared
    :class:`~.elastic._MultiWindowElastic` machinery), and checkpoint
    manifests record the per-tier layout so cold starts can reshard."""

    _kind = "pqueue"
    _discipline = "prio"
    _ovf_out = 5            # after (tier|bucket, pos, matched, deq_vals, deq_ok)
    _pad_fill = (0, False)
    _sharded_keys = frozenset({"store_vals", "store_full"})

    @property
    def _n_windows(self) -> int:
        return self.n_prios

    def __init__(self, n_shards: int, *, n_prios: int = 2,
                 relaxation: int = 0, axis_name: str = "data",
                 cap: int = 1024, payload_width: int = 4,
                 ops_per_shard: int = 64, devices=None, runtime=None,
                 hlo_stats: bool = False, pipelined: bool = True,
                 metrics: bool = False, metrics_ring: int = 64,
                 flight_k: int = 16):
        self.n_prios = n_prios
        self.relaxation = relaxation
        super().__init__(n_shards, axis_name=axis_name, cap=cap,
                         payload_width=payload_width,
                         ops_per_shard=ops_per_shard, devices=devices,
                         runtime=runtime,
                         hlo_stats=hlo_stats, pipelined=pipelined,
                         metrics=metrics, metrics_ring=metrics_ring,
                         flight_k=flight_k)

    def _make_inner(self, mesh):
        return DevicePriorityQueue(mesh, self.axis, n_prios=self.n_prios,
                                   cap=self.cap, payload_width=self.W,
                                   ops_per_shard=self.L,
                                   relaxation=self.relaxation,
                                   pipelined=self.pipelined,
                                   metrics=self.metrics,
                                   metrics_ring=self.metrics_ring,
                                   runtime=self.runtime)

    # ------------------------------------------------------------ waves ----
    def step(self, is_enq, valid, prio, payload):
        """One wave on the current mesh; state is threaded internally.
        Returns (tier, pos, matched, deq_vals, deq_ok, overflow,
        n_relaxed); raises :class:`~.errors.QueueOverflowError` when the
        wave overflowed a tier window."""
        return self._burst(self.inner.step, (is_enq, valid, prio, payload),
                           0)

    def run_waves(self, is_enq, valid, prio, payload):
        """K pre-staged waves in one dispatch (shapes [K, n_shards * L]).
        Raises :class:`~.errors.QueueOverflowError` on tier overflow."""
        return self._burst(self.inner.run_waves,
                           (is_enq, valid, prio, payload), 1)

    # -------------------------------------------------------- migration ----
    def _unpack(self, state):
        return state.firsts, state.lasts, state.store_vals, state.store_full

    def _pack(self, a, b, X, Y):
        return PriorityQueueState(a, b, X, Y)

    def _layout(self) -> dict:
        return {**super()._layout(), "P": self.n_prios,
                "relaxation": self.relaxation}

    @classmethod
    def _layout_kwargs(cls, lay: dict) -> dict:
        return {**super()._layout_kwargs(lay), "n_prios": lay["P"],
                "relaxation": lay.get("relaxation", 0)}

    def _state_dict(self) -> dict:
        return {"firsts": self.state.firsts, "lasts": self.state.lasts,
                "store_vals": self.state.store_vals,
                "store_full": self.state.store_full}

    def _from_state_dict(self, d: dict):
        return PriorityQueueState(d["firsts"], d["lasts"], d["store_vals"],
                                  d["store_full"])
