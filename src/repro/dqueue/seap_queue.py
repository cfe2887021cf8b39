"""Device-resident arbitrary-priority queue: Seap on the fused wave path.

Seap (arXiv:1805.03472, second half) generalizes Skeap's constant-priority
tiers to **arbitrary priority keys** by running a distributed search
structure over the tier set.  On the unified
:class:`~.wave_engine.WaveEngine` that search tree collapses to a
**two-level bucket directory** — the fourth discipline plug-in rather than
a fourth wave body:

* the sharded ring store gains one round-robin slot window per *bucket id*
  (exactly the priority queue's tier windows: bucket ``b``'s position ``q``
  lives on shard ``q % n_shards`` at slot ``b * cap + (q // n_shards) %
  cap``), so Stage 4 stays the packed TWO-collective layout (ONE per wave
  in the pipelined burst) — the slot already encodes the bucket;
* a replicated **boundary table** ``(lo[B], active[B])`` maps keys to
  buckets by predecessor lookup (``core.scan_queue.seap_bucket_lookup``);
  op descriptors (key ‖ 2 flag bits) ride one tiny ``all_gather``, after
  which assignment is fully replicated;
* enqueues get per-bucket FIFO positions from B masked min-plus scans;
  dequeues are Skeap's batch-DeleteMin over the directory sorted by
  boundary (``strict_batch_deletemin`` over the permuted availability);
* the directory is **rebalanced in-wave** by a cheap split/merge rule —
  halve an over-full bucket's key range (clamped to the observed min/max
  enqueued keys) into a free id, recycling an empty bucket's id on
  demand when none is free — pure replicated arithmetic that never moves
  elements.  Priority order is therefore *bucket-granular*: inversions
  are bounded by the key-range width a bucket had when the element
  entered, FIFO always holds inside a bucket, and under drifting keys
  (deadlines) the refined window rolls with the live range.
  ``core.seap.SeapOracle`` implements the identical semantics
  independently and is the differential reference.

:class:`ElasticDeviceSeapQueue` adds the PR 2 membership story: grow /
shrink re-materializes every bucket window with ONE packed migration
all_to_all (the boundary table is replicated and passes through
untouched), and checkpoint manifests record the bucket layout so cold
starts can reshard.  Host-raised :class:`~.errors.QueueOverflowError`
replaces the PR 1-4 replicated-bool-plus-assert overflow contract.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.scan_queue import seap_queue_scan
from ..core.seap import INT32_MAX, INT32_MIN, check_seed_bounds
from ..kernels.backend import use_fused_dispatch
from .elastic import _MultiWindowElastic
from .wave_engine import (Discipline, Dispatch, TAG_GET, TAG_INACTIVE,
                          TAG_PUT, WaveEngine,
                          post_enqueue_peak_overflow, ring_commit)


class SeapQueueState(NamedTuple):
    """Seap queue state: per-bucket replicated intervals, the replicated
    bucket directory (``lo``/``active`` boundary table plus observed key
    range), and the sharded ring store (one slot window per bucket)."""

    firsts: jax.Array         # [B] replicated int32 (per-bucket interval)
    lasts: jax.Array          # [B] replicated int32
    lo: jax.Array             # [B] replicated int32 bucket key boundaries
    active: jax.Array         # [B] replicated bool directory membership
    key_lo: jax.Array         # [] replicated int32: min key ever enqueued
    key_hi: jax.Array         # [] replicated int32: max key ever enqueued
    store_vals: jax.Array     # [n_shards(sharded), B*cap + 1, W] int32
    store_full: jax.Array     # [n_shards(sharded), B*cap + 1] bool

    @property
    def sizes(self) -> jax.Array:
        """Per-bucket occupancy vector ``[B]`` (traced)."""
        return self.lasts - self.firsts + 1


class SeapDiscipline(Discipline):
    """Seap arbitrary-key order: bucket-directory lookup + B masked
    min-plus scans + boundary-ordered batch-DeleteMin, over the shared
    dense-ring store, with the in-wave split/merge directory rebalance."""

    name = "seap"
    n_ops = 4           # (is_enq, valid, key, payload)
    n_disp_outs = 3     # (bucket, pos, matched)
    n_aux = 1           # n_active (directory size after the rebalance)

    def __init__(self, axis: str, n_shards: int, n_buckets: int, cap: int,
                 W: int, split_occupancy: int,
                 fused_dispatch: bool | None = None):
        self.axis = axis
        self.n_shards = n_shards
        self.n_buckets = n_buckets
        self.cap = cap
        self.W = W
        self.split_occupancy = split_occupancy
        self.junk = n_buckets * cap
        self.n_windows = n_buckets
        self.window_capacity = n_shards * cap
        # on compiled backends the B masked min-plus scans collapse to ONE
        # pallas sweep (grid = buckets x tiles); the jnp loop stays the
        # CPU path AND the differential oracle (None = autodetect, PR 9)
        if fused_dispatch is None:
            fused_dispatch = use_fused_dispatch()
        self.fused_dispatch = bool(fused_dispatch)
        if self.fused_dispatch:
            from ..kernels.segscan import make_tier_scan
            self._tier_scan = make_tier_scan(n_buckets)
        else:
            self._tier_scan = None
        self.state_specs = SeapQueueState(P(), P(), P(), P(), P(), P(),
                                          P(axis), P(axis))

    def split(self, state):
        """Split state into its (replicated carry, sharded store) halves."""
        return ((state.firsts, state.lasts, state.lo, state.active,
                 state.key_lo, state.key_hi),
                (state.store_vals, state.store_full))

    def merge(self, carry, store):
        """Reassemble the full state from (carry, store) halves."""
        return SeapQueueState(*carry, store[0], store[1])

    def dispatch(self, carry, ops) -> Dispatch:
        """Stages 1-3: assign positions and build the routed Dispatch."""
        is_enq, valid, key, payload = ops
        firsts, lasts, lo, active, key_lo, key_hi = carry
        n_shards, cap = self.n_shards, self.cap
        L = is_enq.shape[0]

        # ---- gather op descriptors (key ‖ flags) and assign replicated:
        #      every shard runs the same directory lookup + scans ----
        code = is_enq.astype(jnp.int32) * 2 + valid.astype(jnp.int32)
        desc = jnp.stack([code, key.astype(jnp.int32)], axis=1)    # [L, 2]
        g = lax.all_gather(desc, self.axis, tiled=True)   # [n_shards*L, 2]
        (bucket_g, pos_g, matched_g, new_firsts, new_lasts, new_lo,
         new_active, new_key_lo, new_key_hi, n_active) = seap_queue_scan(
            (g[:, 0] & 2) > 0, g[:, 1], (g[:, 0] & 1) > 0,
            firsts, lasts, lo, active, key_lo, key_hi,
            n_buckets=self.n_buckets, split_occupancy=self.split_occupancy,
            tier_scan=self._tier_scan)

        i0 = lax.axis_index(self.axis) * L
        bucket = lax.dynamic_slice_in_dim(bucket_g, i0, L)
        pos = lax.dynamic_slice_in_dim(pos_g, i0, L)
        matched = lax.dynamic_slice_in_dim(matched_g, i0, L)

        owner = jnp.where(matched, pos % n_shards, -1).astype(jnp.int32)
        slot = jnp.where(matched, bucket * cap + (pos // n_shards) % cap,
                         self.junk).astype(jnp.int32)
        tag = jnp.where(matched & is_enq, TAG_PUT,
                        jnp.where(matched & ~is_enq, TAG_GET, TAG_INACTIVE))
        # capacity holds per bucket (each bucket owns its own slot window)
        ovf = post_enqueue_peak_overflow(firsts, new_lasts, n_shards * cap)
        return Dispatch(owner, slot, tag, (), payload, matched,
                        matched & ~is_enq, (bucket, pos, matched),
                        (new_firsts, new_lasts, new_lo, new_active,
                         new_key_lo, new_key_hi), ovf, (n_active,))

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


def default_split_occupancy(n_shards: int, cap: int) -> int:
    """Split a bucket when it passes 3/4 of its window (leaves headroom
    for the wave in flight while the upper half diverts to the new id)."""
    return max(1, (3 * n_shards * cap) // 4)


class DeviceSeapQueue:
    """Distributed arbitrary-priority queue over one mesh axis.

    Args:
      mesh/axis_name: the shard axis; n_buckets: directory capacity B
        (bucket ids, each owning a slot window); cap: slots per shard PER
        BUCKET; payload_width: int32 words per element; ops_per_shard:
        wave width L;
      split_occupancy: occupancy above which a bucket's key range is
        halved into a free id (default: 3/4 of the bucket window) —
        must match the :class:`~repro.core.seap.SeapOracle` threshold in
        differential runs;
      seed_bounds: optional warm-start boundaries for the directory
        (strictly increasing ints; see
        :func:`repro.core.seap.check_seed_bounds`) — without them every
        key starts in the root bucket and ordering only refines as
        splits zoom in;
      pipelined: multi-wave bursts use the engine's software-pipelined
        schedule (False = sequential; results identical).
    """

    def __init__(self, mesh, axis_name: str = "data", n_buckets: int = 8,
                 cap: int = 1024, payload_width: int = 4,
                 ops_per_shard: int = 64,
                 split_occupancy: Optional[int] = None,
                 seed_bounds=None, pipelined: bool = True,
                 metrics: bool = False, metrics_ring: int = 64,
                 fused_dispatch: bool | None = None, runtime=None):
        if n_buckets < 1:
            raise ValueError("need at least one bucket")
        from ..runtime import as_runtime
        self.runtime, mesh, axis_name = as_runtime(mesh, axis_name,
                                                   runtime=runtime)
        self.mesh = mesh
        self.axis = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.n_buckets = n_buckets
        self.cap = cap
        self.W = payload_width
        self.L = ops_per_shard
        if split_occupancy is None:
            split_occupancy = default_split_occupancy(self.n_shards, cap)
        if split_occupancy < 1:
            raise ValueError("split_occupancy must be >= 1")
        self.split_occupancy = split_occupancy
        self.seed_bounds = check_seed_bounds(seed_bounds, n_buckets)
        self.pipelined = pipelined
        self.metrics = metrics
        self.engine = WaveEngine(
            mesh, axis_name,
            SeapDiscipline(axis_name, self.n_shards, n_buckets, cap,
                           payload_width, split_occupancy,
                           fused_dispatch=fused_dispatch),
            pipelined=pipelined, metrics=metrics, metrics_ring=metrics_ring,
            runtime=self.runtime)
        self._step = self.engine._step
        self._run_waves = self.engine._run_waves

    def init_state(self) -> SeapQueueState:
        """Freshly sharded empty state on this structure's mesh."""
        n, cap, W, B = self.n_shards, self.cap, self.W, self.n_buckets
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        rep = jax.sharding.NamedSharding(self.mesh, P())
        lo = np.full((B,), INT32_MAX, np.int32)
        lo[0] = INT32_MIN
        active = np.zeros((B,), bool)
        active[0] = True
        ns = len(self.seed_bounds)
        lo[1:1 + ns] = self.seed_bounds
        active[1:1 + ns] = True
        put = self.runtime.put
        return SeapQueueState(
            firsts=put(jnp.zeros((B,), jnp.int32), rep),
            lasts=put(jnp.full((B,), -1, jnp.int32), rep),
            lo=put(jnp.asarray(lo), rep),
            active=put(jnp.asarray(active), rep),
            key_lo=put(jnp.int32(INT32_MAX), rep),
            key_hi=put(jnp.int32(INT32_MIN), rep),
            store_vals=put(
                jnp.zeros((n, B * cap + 1, W), jnp.int32), sharding),
            store_full=put(
                jnp.zeros((n, B * cap + 1), bool), sharding),
        )

    def step(self, state: SeapQueueState, is_enq, valid, key, payload):
        """Process one global wave.  The state argument is DONATED.

        is_enq/valid: [n_shards * L] bool; key: [n_shards * L] int32
        priority keys (any int32; smaller = more urgent; ignored for
        dequeues); payload: [n_shards * L, W].  Returns (new_state,
        bucket, pos, matched, deq_vals, deq_ok, overflow, n_active) —
        bucket/pos are -1/⊥ for unmatched ops, ``n_active`` is the
        directory size after the wave's rebalance.
        """
        return self.engine.step(state, is_enq, valid, key, payload)

    def run_waves(self, state: SeapQueueState, is_enq, valid, key, payload):
        """K pre-staged waves in ONE lax.scan dispatch (state DONATED).

        Shapes: is_enq/valid/key [K, n_shards * L]; payload [K, ..., W].
        """
        return self.engine.run_waves(state, is_enq, valid, key, payload)

    def drain_metrics(self, *, reset: bool = False) -> list:
        """Burst-boundary Wavescope drain (empty when metrics are off)."""
        return self.engine.drain_metrics(reset=reset)


class ElasticDeviceSeapQueue(_MultiWindowElastic):
    """Arbitrary-priority queue whose shard count is a runtime variable.

    ``grow`` / ``shrink`` / ``resize`` re-materialize every bucket window
    onto the new mesh with one packed migration all_to_all (the PR 2 wave
    vectorized over windows via the shared
    :class:`~.elastic._MultiWindowElastic` machinery); the replicated
    boundary table rides around the migration untouched, and checkpoint
    manifests record the bucket layout so cold starts can reshard."""

    _kind = "squeue"
    _discipline = "seap"
    _ovf_out = 5            # after (tier|bucket, pos, matched, deq_vals, deq_ok)
    _pad_fill = (0, False)
    _sharded_keys = frozenset({"store_vals", "store_full"})

    @property
    def _n_windows(self) -> int:
        return self.n_buckets

    def __init__(self, n_shards: int, *, n_buckets: int = 8,
                 split_occupancy: Optional[int] = None,
                 seed_bounds=None, axis_name: str = "data", cap: int = 1024,
                 payload_width: int = 4, ops_per_shard: int = 64,
                 devices=None, runtime=None, hlo_stats: bool = False,
                 pipelined: bool = True, metrics: bool = False,
                 metrics_ring: int = 64, flight_k: int = 16):
        self.n_buckets = n_buckets
        if split_occupancy is None:
            split_occupancy = default_split_occupancy(n_shards, cap)
        self.split_occupancy = split_occupancy
        self.seed_bounds = check_seed_bounds(seed_bounds, n_buckets)
        super().__init__(n_shards, axis_name=axis_name, cap=cap,
                         payload_width=payload_width,
                         ops_per_shard=ops_per_shard, devices=devices,
                         runtime=runtime,
                         hlo_stats=hlo_stats, pipelined=pipelined,
                         metrics=metrics, metrics_ring=metrics_ring,
                         flight_k=flight_k)

    def _make_inner(self, mesh):
        return DeviceSeapQueue(mesh, self.axis, n_buckets=self.n_buckets,
                               cap=self.cap, payload_width=self.W,
                               ops_per_shard=self.L,
                               split_occupancy=self.split_occupancy,
                               seed_bounds=self.seed_bounds,
                               pipelined=self.pipelined,
                               metrics=self.metrics,
                               metrics_ring=self.metrics_ring,
                               runtime=self.runtime)

    # ------------------------------------------------------------ waves ----
    def step(self, is_enq, valid, key, payload):
        """One wave on the current mesh; state is threaded internally.
        Returns (bucket, pos, matched, deq_vals, deq_ok, overflow,
        n_active); raises :class:`~.errors.QueueOverflowError` when the
        wave overflowed a bucket window."""
        return self._burst(self.inner.step, (is_enq, valid, key, payload), 0)

    def run_waves(self, is_enq, valid, key, payload):
        """K pre-staged waves in one dispatch (shapes [K, n_shards * L]).
        Raises :class:`~.errors.QueueOverflowError` on bucket overflow."""
        return self._burst(self.inner.run_waves,
                           (is_enq, valid, key, payload), 1)

    @property
    def n_active(self) -> int:
        """Active buckets in the directory (host read, no dispatch)."""
        return int(np.asarray(self.state.active).sum())

    def directory(self) -> list:
        """Active (lo, bucket_id) entries in ascending key order."""
        lo = np.asarray(self.state.lo)
        act = np.asarray(self.state.active)
        return sorted((int(lo[b]), int(b))
                      for b in range(self.n_buckets) if act[b])

    # -------------------------------------------------------- migration ----
    def _unpack(self, state):
        # the replicated directory (boundary table + observed key range)
        # is not touched by the migration wave; stash it and re-attach on
        # the destination mesh in _pack
        self._mig_directory = tuple(
            self.runtime.to_host(x) for x in (state.lo, state.active,
                                              state.key_lo, state.key_hi))
        return state.firsts, state.lasts, state.store_vals, state.store_full

    def _pack(self, a, b, X, Y):
        rep = a.sharding                      # replicated on the final mesh
        lo_h, act_h, klo_h, khi_h = (self.runtime.put(x, rep)
                                     for x in self._mig_directory)
        return SeapQueueState(a, b, lo_h, act_h, klo_h, khi_h, X, Y)

    def _layout(self) -> dict:
        return {**super()._layout(), "B": self.n_buckets,
                "split": self.split_occupancy, "seed": self.seed_bounds}

    @classmethod
    def _layout_kwargs(cls, lay: dict) -> dict:
        # the live directory (lo/active) restores from the state dict;
        # the seed only shapes a fresh init_state
        return {**super()._layout_kwargs(lay), "n_buckets": lay["B"],
                "split_occupancy": lay["split"],
                "seed_bounds": lay.get("seed") or None}

    def _state_dict(self) -> dict:
        return {"firsts": self.state.firsts, "lasts": self.state.lasts,
                "lo": self.state.lo, "active": self.state.active,
                "key_lo": self.state.key_lo, "key_hi": self.state.key_hi,
                "store_vals": self.state.store_vals,
                "store_full": self.state.store_full}

    def _from_state_dict(self, d: dict):
        return SeapQueueState(d["firsts"], d["lasts"], d["lo"], d["active"],
                              d["key_lo"], d["key_hi"],
                              d["store_vals"], d["store_full"])
