"""Elastic membership for the device path: live JOIN/LEAVE resharding.

The paper's distinguishing feature over prior distributed queues is dynamic
membership — JOIN and LEAVE processed under sequential consistency (Sec. IV).
In this repo that capability lived only in the host-side ``Skueue`` protocol
simulator; the fused ``DeviceQueue``/``DeviceStack`` hot path (PR 1) assumed
a fixed shard set for its entire lifetime.  This module makes the mesh shape
a *runtime variable*: :class:`ElasticDeviceQueue` and
:class:`ElasticDeviceStack` wrap the fixed-mesh implementations and support
``grow(k)`` / ``shrink(ids)`` / ``resize(n)`` between wave bursts,
re-materializing the sharded element store from a P-shard layout onto a
P±k-shard mesh while preserving FIFO (resp. LIFO) order and every in-flight
element.

The migration wave
------------------
Between bursts the store is quiescent, and — because SKUEUE positions are
dense integers and the device layout is round-robin (position ``p`` on shard
``p % P`` at slot ``(p // P) % cap``) — the set of live positions is exactly
the interval ``[first, last]``.  Each shard can therefore *recover* the
position held by any of its occupied slots without scanning: slot ``t`` on
shard ``s`` holds the unique ``p = s + P*j`` with ``j ≡ t (mod cap)`` and
``p ∈ [first, last]`` (unique because the live window spans at most
``P * cap`` positions).  One jitted shard_map wave then

1. recomputes each live element's owner under the *new* shard count
   (``p % P'`` — the device path's perfectly-fair specialization of the
   paper's consistent hashing; the paper-faithful hashed owner distribution
   for the same live set is reported via ``kernels/hash_route`` in the
   migration stats),
2. scatters ``new_slot ‖ payload`` columns into a packed per-destination
   send buffer (``wave_engine.migrate_packed``, the engine's packed-send
   idiom with rank-within-destination rows), moves everything with ONE
   ``lax.all_to_all``, and
3. rewrites the receiving shards' stores; ``first``/``last`` (queue) and
   ``last``/``ticket`` (stack) interval bookkeeping pass through unchanged —
   membership changes never disturb the position order, which is the whole
   point of the paper's Sec. IV design.

The migration mesh is the *larger* of the two shard sets: a grow pads the
old store with empty shards and routes on the new mesh; a shrink routes on
the old mesh (every new owner is a surviving shard) and then drops the
now-empty rows.  Crossing between meshes of different device counts is a
host-staged ``device_put`` in this single-process container (a real
deployment would stream shard state device-to-device); the part that scales
with queue *contents* — owner routing, packing, the all_to_all, the store
rewrite — runs jitted on device and is what ``benchmarks/micro.py --pr2``
measures.

Failure semantics: ``shrink`` is the paper's *graceful* LEAVE — the leaving
shard participates in its own migration wave (like the leaving node handing
its interval to its predecessor before departing).  A hard crash is outside
the LEAVE protocol's model there too; its recovery path here is the
checkpoint cold start (:meth:`save` / :meth:`restore` via
``checkpoint.restore_sharded``), and ``fault.run_with_restarts`` composes
both: LEAVE the dead shard and keep running, restore from checkpoint only
when elasticity cannot help.
"""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compat import shard_map
from ..obs.recorder import FlightRecorder
from ..obs.trace import span
from .device_queue import DeviceQueue, DeviceQueueState, DeviceStack
from .errors import QueueOverflowError
from .wave_engine import (fanout_bound, migrate_packed, named,
                          program_name, recover_positions, rewrite_ring_store)

HASH_BALANCE_MAX_SIZE = 1 << 16  # skip the fidelity report for huge queues


def _mesh_key(devices) -> tuple:
    return tuple(d.id for d in devices)


class _ElasticBase:
    """Shared machinery: device bookkeeping, mesh/inner/migration caches,
    the resize driver, migration stats, and checkpoint save/restore."""

    _kind: str  # span prefix: "queue" | "stack" | "pqueue" | "squeue"
    _discipline: str  # program-name part: "fifo" | "lifo" | "prio" | "seap"
    _ovf_out: int  # index of the overflow flag among a wave's outputs

    def __init__(self, n_shards: int, *, axis_name: str = "data",
                 cap: int = 1024, payload_width: int = 4,
                 ops_per_shard: int = 64, devices=None, runtime=None,
                 hlo_stats: bool = False, pipelined: bool = True,
                 metrics: bool = False, metrics_ring: int = 64,
                 flight_k: int = 16):
        from ..runtime import LocalRuntime
        if runtime is not None:
            if devices is not None:
                raise ValueError("pass devices= OR runtime=, not both "
                                 "(the runtime owns the device pool)")
            self.runtime = runtime
            axis_name = runtime.axis_name
        else:
            self.runtime = LocalRuntime(devices=devices,
                                        axis_name=axis_name)
        if not 1 <= n_shards <= self.runtime.pool_size:
            raise ValueError(f"n_shards={n_shards} outside the device pool "
                             f"of {self.runtime.pool_size}")
        self.axis = axis_name
        self.cap = cap
        self.W = payload_width
        self.L = ops_per_shard
        self.pipelined = pipelined
        self.metrics = bool(metrics)
        self.metrics_ring = int(metrics_ring)
        self.recorder = FlightRecorder(flight_k)
        self._hlo_stats = hlo_stats
        self._active = list(self.runtime.pool()[:n_shards])
        self._mesh_cache: Dict[tuple, jax.sharding.Mesh] = {}
        self._inner_cache: Dict[tuple, object] = {}
        self._mig_cache: Dict[tuple, list] = {}
        self._burst_seq = 0
        from ..analysis.recompile import CompilationTracker
        CompilationTracker.install()
        self.inner = self._get_inner(self._mesh_for(self._active))
        self.state = self.inner.init_state()
        self.migrations: List[dict] = []

    # ------------------------------------------------------------ caches ---
    def _mesh_for(self, devices) -> jax.sharding.Mesh:
        # the runtime is the one mesh builder; the local mirror keeps the
        # cache inspectable (the wavecheck recompile guard asserts on it)
        key = _mesh_key(devices)
        if key not in self._mesh_cache:
            self._mesh_cache[key] = self.runtime.mesh(list(devices))
        return self._mesh_cache[key]

    def _get_inner(self, mesh):
        """Fixed-mesh DeviceQueue/DeviceStack per mesh, cached so that
        bouncing between shard counts (grow 4→8, shrink 8→4, grow again)
        never recompiles the wave programs."""
        key = _mesh_key(mesh.devices.flat)
        if key not in self._inner_cache:
            self._inner_cache[key] = self._make_inner(mesh)
        return self._inner_cache[key]

    def _migration_for(self, mesh, P_old: int, P_new: int):
        key = (_mesh_key(mesh.devices.flat), P_old, P_new)
        if key not in self._mig_cache:
            fn = self._build_migration(mesh, P_old, P_new)
            # [jitted, its compiled executable, collective count]
            self._mig_cache[key] = [fn, None, None]
        return self._mig_cache[key]

    # ---------------------------------------------------------- overflow ---
    def _wave_capacity(self) -> int:
        """Elements one store window holds (per tier/bucket where tiered)."""
        return self.n_shards * self.cap

    def _occupancies(self) -> list:
        """Post-wave occupancy per window (subclasses with tier/bucket
        windows override with the per-window vector)."""
        return [self.size]

    _overflow_detail: str = ""

    def _drain_telemetry(self) -> list:
        """Burst-boundary Wavescope drain into the flight recorder (the
        one sanctioned device→host telemetry read; no-op with metrics
        off).  Returns the freshly drained wave summaries."""
        eng = getattr(self.inner, "engine", None)
        if not self.metrics or eng is None or not eng.metrics:
            return []
        rows = eng.drain_metrics(reset=True)
        self.recorder.extend(rows)
        return rows

    def trajectory(self) -> list:
        """The flight recorder's last-K wave summaries, oldest first."""
        return self.recorder.trajectory()

    # ------------------------------------------------------ pressure API ---
    def window_capacity(self) -> int:
        """Elements ONE store window holds under the current membership.

        ``n_shards * cap`` for FIFO (per tier/bucket for the tiered
        structures; times ``slot_depth`` for the stack).  A host-side
        constant of the current shard count — admission policies
        (:mod:`repro.serve.admission`) compare it against
        :meth:`occupancy` without any device work.

        Returns:
            Per-window element capacity as a host int.
        """
        return self._wave_capacity()

    def occupancy(self) -> List[int]:
        """Committed post-burst occupancy per window, as host ints.

        Reads only the replicated interval bookkeeping (``first``/``last``
        scalars) the last wave already materialized — a tiny device→host
        scalar copy with NO collective and NO wave dispatch, so pre-wave
        admission decisions cannot perturb the wave pipeline.

        Returns:
            One entry per window: ``[size]`` for FIFO/LIFO, a per-tier
            vector for the priority queue, per-bucket for the Seap queue.
        """
        # ``_occupancies`` builds on the ``size``/``sizes`` host properties,
        # which already return concrete Python ints — no cast needed here
        # (and ``occupancy`` doubles as a Discipline *device* method name,
        # so wavecheck's no-traced-cast rule watches this scope).
        return list(self._occupancies())

    def headroom(self) -> List[int]:
        """Free slots per window before the next enqueue overwrites data.

        ``window_capacity() - occupancy()`` per window; enqueueing into a
        window with zero headroom is exactly the wrap-around that raises
        :class:`~.errors.QueueOverflowError` mid-wave.  Same zero-cost
        host read as :meth:`occupancy`.

        Returns:
            One int per window (negative only after an overflow already
            corrupted the window).
        """
        cap = self._wave_capacity()
        return [cap - o for o in self.occupancy()]

    def pressure(self) -> dict:
        """One-call snapshot for host-side admission/autoscale decisions.

        Returns:
            Dict with ``capacity`` (per-window int), ``occupancy`` /
            ``headroom`` (per-window vectors), ``n_windows``,
            ``n_shards``, ``pool_size``, and ``utilization`` — the
            hottest window's ``occupancy / capacity`` as a float in
            ``[0, 1]`` (above 1 only after an overflow already happened).
        """
        cap = self._wave_capacity()
        occ = self.occupancy()
        return {
            "capacity": cap,
            "occupancy": occ,
            "headroom": [cap - o for o in occ],
            "n_windows": len(occ),
            "n_shards": self.n_shards,
            "pool_size": self.pool_size,
            "utilization": (max(occ) / cap) if cap else 1.0,
        }

    # ------------------------------------------------- occupancy buckets ---
    def bucket_widths(self) -> tuple:
        """The occupancy-bucket envelope ladder for this queue (PR 9).

        Ascending per-shard wave widths ``{L/4, L/2, L}`` (deduplicated,
        floored at 1).  Every width is a separately cached wave program —
        same discipline, same ≤2-all_to_all budget, smaller request/reply
        columns on the wire.  A host-side constant of ``L``; no device
        work.

        Returns:
            Tuple of ints, ascending, ending in ``L``.
        """
        from .wave_engine import bucket_ladder
        return bucket_ladder(self.L)

    def pick_width(self, n_ops: int) -> int:
        """Smallest ladder width whose global wave fits ``n_ops`` (PR 9).

        The burst driver's envelope choice: the narrowest ``w`` with
        ``n_shards * w >= n_ops``, falling back to the full ``L`` when
        even the widest bucket cannot hold the burst in one wave.  Pure
        host arithmetic on the current membership.

        Args:
            n_ops: Valid ops staged for the next wave (global count).

        Returns:
            A width from :meth:`bucket_widths`.
        """
        from .wave_engine import pick_bucket_width
        return pick_bucket_width(self.L, self.n_shards, n_ops)

    def _burst(self, entry, ops, lead: int) -> tuple:
        """One wave (``lead`` 0) or K-wave burst (``lead`` 1, ops shaped
        ``[K, n_shards * L, ...]``) of ``entry`` on the current mesh; the
        state is threaded internally.  Returns the wave outputs; raises
        :class:`~.errors.QueueOverflowError` when a wave overflowed.

        Spans, each with the burst's sequence number ``burst``:
        ``<kind>:burst`` around the whole call; inside it
        ``<kind>:launch`` (placing the op arrays, the runtime's burst hook
        — SimRuntime charges its modeled all_to_all launches there — and
        the enqueue of the program) and the overflow check's spans."""
        seq = self._burst_seq
        self._burst_seq += 1
        K = int(np.shape(ops[0])[0]) if lead else 1
        with span(f"{self._kind}:burst", cat="wave", K=K,
                  n_shards=self.n_shards, burst=seq):
            with span(f"{self._kind}:launch", cat="wave", burst=seq):
                self.runtime.on_burst(self._kind, K, self.n_shards,
                                      width=self.L, payload_width=self.W,
                                      pipelined=self.pipelined)
                placed = [self._place(x, lead) for x in ops]
                self.state, *out = entry(self.state, *placed)
            self._check_overflow(out[self._ovf_out], seq)
        return tuple(out)

    def wave_phases(self) -> list:
        """For each wave program this structure has dispatched, on every
        membership it has had: instruction name -> wave phase, from the
        program's compiled text (see ``WaveEngine.phase_tables``).  Read
        after the fact; nothing compiles."""
        return [t for e in self._engines() for t in e.phase_tables()]

    def wave_programs(self) -> list:
        """``(key, compiled text)`` of each wave program this structure has
        dispatched, on every membership it has had (see
        ``WaveEngine.compiled_programs``); nothing compiles."""
        return [p for e in self._engines() for p in e.compiled_programs()]

    def _engines(self) -> list:
        return [inner.engine for inner in self._inner_cache.values()
                if getattr(inner, "engine", None) is not None]

    def _place(self, x, lead: int = 0):
        """Stage one op array onto the active mesh via the runtime
        (``jnp.asarray`` under LocalRuntime — bit-identical to the
        pre-runtime path; an explicit global device_put under
        DistributedRuntime)."""
        return self.runtime.place(x, self.mesh, lead)

    def _check_overflow(self, ovf, burst: int) -> None:
        """Drain telemetry, then host-raise the wave's replicated
        overflow flag as a structured
        :class:`~.errors.QueueOverflowError` (was a bare assert in every
        caller before PR 5) carrying the flight-recorder trajectory.
        ``ovf`` is a scalar bool (``step``) or a [K] vector
        (``run_waves``); this runs once per step/burst, so the recorder
        sees every wave even when nothing overflowed.  Reading the flag
        blocks until the burst has finished on the device: that wait is
        the ``<kind>:overflow_wait`` span."""
        if self.metrics:
            with span(f"{self._kind}:telemetry_drain", cat="wave",
                      burst=burst):
                self._drain_telemetry()
        with span(f"{self._kind}:overflow_wait", cat="wave", burst=burst):
            o = self.runtime.to_host(ovf)   # replicated scalar/[K] — cheap
        if not bool(o.any()):
            return
        wave = int(np.flatnonzero(o)[0]) if o.ndim >= 1 else None
        raise QueueOverflowError(self._kind, self._wave_capacity(),
                                 self._occupancies(), wave=wave,
                                 detail=self._overflow_detail,
                                 trajectory=self.recorder.trajectory())

    # -------------------------------------------------------- membership ---
    @property
    def n_shards(self) -> int:
        """Current number of active shards (the runtime-variable P)."""
        return len(self._active)

    @property
    def _pool(self) -> list:
        """The runtime's live device pool (failed devices excluded)."""
        return self.runtime.pool()

    @property
    def pool_size(self) -> int:
        """Total live devices available to this queue (active + spare);
        the hard upper bound :meth:`grow` can reach.  Quarantined
        (failed) devices do not count."""
        return self.runtime.pool_size

    @property
    def mesh(self):
        """The active shards' jax mesh (changes identity across resizes)."""
        return self.inner.mesh

    @property
    def devices(self) -> list:
        """The active shard devices, in shard-index order."""
        return list(self._active)

    @property
    def device_ids(self) -> list:
        """Stable device ids of the active shards, in shard-index order
        — the membership key failure attribution uses (mesh indices are
        only stable while membership never changes)."""
        return [d.id for d in self._active]

    def grow(self, k: int = 1) -> dict:
        """JOIN: add ``k`` shards from the device pool (P → P + k).

        Spares come from the runtime's *live* pool, so a device the
        fault layer quarantined (``shrink_devices(..., quarantine=
        True)``) is never handed back out — a LEAVE of a dead shard
        followed by a regrow cannot resurrect state on it."""
        if k < 1:
            raise ValueError("grow(k) needs k >= 1")
        active_ids = set(self.device_ids)
        spare = [d for d in self.runtime.pool() if d.id not in active_ids]
        if len(spare) < k:
            raise ValueError(f"cannot grow by {k}: only {len(spare)} spare "
                             f"devices in the pool")
        return self._rematerialize(self._active + spare[:k], kind="grow")

    def shrink(self, ids: Sequence[int]) -> dict:
        """Graceful LEAVE of the shards with indices ``ids`` (P → P - |ids|).

        The leaving shards participate in the migration wave (their elements
        are routed out before they drop from the mesh), mirroring the
        paper's LEAVE where the departing node hands its interval over
        before disconnecting."""
        ids = sorted(set(int(i) for i in ids))
        if not ids:
            raise ValueError("shrink(ids) needs at least one shard id")
        if ids[0] < 0 or ids[-1] >= self.n_shards:
            raise ValueError(f"shard ids {ids} out of range "
                             f"[0, {self.n_shards})")
        if len(ids) >= self.n_shards:
            raise ValueError("cannot shrink to zero shards")
        survivors = [d for i, d in enumerate(self._active) if i not in ids]
        return self._rematerialize(survivors, kind="shrink")

    def shrink_devices(self, dev_ids: Sequence[int], *,
                       quarantine: bool = False) -> dict:
        """Graceful LEAVE keyed by **stable device id** instead of mesh
        index (the PR 10 failure-rekey surface).

        Args:
          dev_ids: stable ids of the leaving devices (must be active).
          quarantine: additionally mark them failed in the runtime, so
            a later :meth:`grow` can never pick them again — the fault
            layer sets this for failure-LEAVEs (a dead device must not
            rejoin), and leaves it False for capacity scaling (the
            autoscaler may legitimately re-JOIN a healthy device).

        Returns:
          The migration stats dict, like :meth:`shrink`.
        """
        ids = [int(i) for i in dev_ids]
        mine = self.device_ids
        missing = [i for i in ids if i not in mine]
        if missing:
            raise ValueError(f"device id(s) {missing} are not active "
                             f"shards (active ids: {mine})")
        stats = self.shrink([mine.index(i) for i in ids])
        if quarantine:
            for i in ids:
                self.runtime.mark_failed(i)
        return stats

    def resize(self, n_new: int) -> dict:
        """Reshape to ``n_new`` shards (grow or shrink as needed)."""
        if n_new == self.n_shards:
            return {"kind": "noop", "P_from": self.n_shards,
                    "P_to": n_new, "moved": 0}
        if n_new > self.n_shards:
            return self.grow(n_new - self.n_shards)
        return self.shrink(range(n_new, self.n_shards))

    # ----------------------------------------------------- rematerialize ---
    def _rematerialize(self, new_active: list, kind: str) -> dict:
        P_old, P_new = self.n_shards, len(new_active)
        need = self._live_span()
        if need > P_new * self.cap:
            raise ValueError(
                f"cannot reshard to {P_new} shards: {need} live elements "
                f"exceed the new capacity {P_new} * {self.cap}")
        with span(f"migration:{kind}", cat="membership", kind=self._kind,
                  P_from=P_old, P_to=P_new):
            return self._rematerialize_traced(new_active, kind, P_old,
                                              P_new)

    def _rematerialize_traced(self, new_active: list, kind: str,
                              P_old: int, P_new: int) -> dict:
        t_total = time.perf_counter()
        a, b, X, Y = self._unpack(self.state)

        rt = self.runtime
        if P_new > P_old:
            # grow: pad empty shards, route on the NEW mesh.  Crossing
            # between meshes of different device sets is host-staged
            # through the runtime (np.asarray locally; a process_allgather
            # + global device_put under DistributedRuntime).
            mig_mesh = self._mesh_for(new_active)
            shard = NamedSharding(mig_mesh, P(self.axis))
            rep = NamedSharding(mig_mesh, P())
            fx, fy = self._pad_fill
            with span("migration:stage", cat="membership"):
                Xh, Yh = rt.to_host(X), rt.to_host(Y)
                pad = P_new - P_old
                Xh = np.concatenate(
                    [Xh, np.full((pad,) + Xh.shape[1:], fx, Xh.dtype)])
                Yh = np.concatenate(
                    [Yh, np.full((pad,) + Yh.shape[1:], fy, Yh.dtype)])
                a = rt.put(rt.to_host(a), rep)
                b = rt.put(rt.to_host(b), rep)
                X, Y = rt.put(Xh, shard), rt.put(Yh, shard)
        else:
            # shrink: route on the OLD mesh (owners are surviving shards)
            mig_mesh = self.mesh

        entry = self._migration_for(mig_mesh, P_old, P_new)
        t_compile = time.perf_counter()
        if entry[1] is None:
            # compiled ahead of its first use, so that the wave below
            # times the migration alone
            with span("migration:compile", cat="membership"):
                entry[1] = entry[0].lower(a, b, X, Y).compile()
        t_compile = time.perf_counter() - t_compile
        if self._hlo_stats and entry[2] is None:
            from ..analysis.hlo import count_op
            entry[2] = count_op(entry[1].as_text(), "all-to-all")
        t_wave = time.perf_counter()
        a, b, X, Y, moved, lost = entry[1](a, b, X, Y)
        jax.block_until_ready(Y)
        t_wave = time.perf_counter() - t_wave
        if bool(rt.to_host(lost)):
            raise RuntimeError("migration fanout overflow — internal bound "
                               "violated, elements would have been dropped")

        if P_new < P_old:
            # drop the emptied rows, land on the smaller mesh
            with span("migration:land", cat="membership"):
                new_mesh = self._mesh_for(new_active)
                shard = NamedSharding(new_mesh, P(self.axis))
                rep = NamedSharding(new_mesh, P())
                a = rt.put(rt.to_host(a), rep)
                b = rt.put(rt.to_host(b), rep)
                X = rt.put(rt.to_host(X)[:P_new], shard)
                Y = rt.put(rt.to_host(Y)[:P_new], shard)

        self.state = self._pack(a, b, X, Y)
        self._active = list(new_active)
        self.inner = self._get_inner(self._mesh_for(new_active))
        n_moved = int(rt.to_host(moved))
        stats = {
            "kind": kind, "P_from": P_old, "P_to": P_new,
            "moved": n_moved,
            "bytes_moved": n_moved * self._entry_bytes,
            "compile_s": t_compile,
            "wave_s": t_wave,
            "total_s": time.perf_counter() - t_total,
            "collectives": entry[2],
        }
        hb = self._hash_balance(P_new)
        if hb is not None:
            stats["hash_balance"] = hb
        rt.on_migration(stats)   # SimRuntime charges the wire model here
        self.migrations.append(stats)
        return stats

    def _hash_balance(self, P_new: int) -> Optional[dict]:
        """Paper-fidelity report: what the consistent-hashing layer
        (``kernels/hash_route``) would assign each shard for the SAME live
        position set that round-robin just re-placed perfectly evenly."""
        lo, hi = self._live_window()
        size = hi - lo + 1
        if size <= 0 or size > HASH_BALANCE_MAX_SIZE:
            return None
        from ..kernels.hash_route import hash_route_ref
        pos = jnp.arange(lo, hi + 1, dtype=jnp.int32)
        _, counts = hash_route_ref(pos, jnp.ones((size,), bool), P_new)
        counts = np.asarray(counts)
        return {"n": size, "max": int(counts.max()),
                "min": int(counts.min()),
                "roundrobin_max": -(-size // P_new)}

    # ------------------------------------------------------- checkpoints ---
    def _layout(self) -> dict:
        return {"kind": self._kind, "n_shards": self.n_shards,
                "cap": self.cap, "W": self.W, "L": self.L}

    @classmethod
    def _layout_kwargs(cls, lay: dict) -> dict:
        return {"cap": lay["cap"], "payload_width": lay["W"],
                "ops_per_shard": lay["L"]}

    def save(self, ckpt_dir, step: int):
        """Checkpoint the queue state (layout recorded in the manifest)."""
        from ..checkpoint import save_checkpoint
        with span("checkpoint:save", cat="checkpoint", kind=self._kind,
                  step=step):
            return save_checkpoint(ckpt_dir, step, self._state_dict(),
                                   meta={"layout": self._layout()})

    @classmethod
    def restore(cls, ckpt_dir, step: Optional[int] = None, *,
                n_shards: Optional[int] = None, devices=None,
                runtime=None, **kw):
        """Cold-start analogue of the live migration: rebuild from a
        checkpoint written under a possibly different shard count, via
        ``checkpoint.restore_sharded`` + one migration wave.

        Requires ``max(saved, target)`` shards' worth of devices (the
        migration mesh is the larger of the two layouts).  ``runtime``
        selects the mesh runtime the restored queue lives on (mutually
        exclusive with ``devices``, like the constructor)."""
        from ..checkpoint import latest_step, restore_sharded
        if step is None:
            step = latest_step(ckpt_dir)
        manifest = json.loads(
            (Path(ckpt_dir) / f"step_{step}" / "manifest.json").read_text())
        lay = manifest["meta"]["layout"]
        if lay["kind"] != cls._kind:
            raise ValueError(f"checkpoint holds a {lay['kind']}, "
                             f"not a {cls._kind}")
        inst = cls(lay["n_shards"], devices=devices, runtime=runtime,
                   **cls._layout_kwargs(lay), **kw)
        shard = NamedSharding(inst.mesh, P(inst.axis))
        rep = NamedSharding(inst.mesh, P())
        shardings = {k: (shard if k in cls._sharded_keys else rep)
                     for k in inst._state_dict()}
        placed, _ = restore_sharded(ckpt_dir, step, inst._state_dict(),
                                    shardings)
        inst.state = inst._from_state_dict(placed)
        if n_shards is not None and n_shards != lay["n_shards"]:
            inst.resize(n_shards)
        return inst

    # ------------------------------------------------- subclass contract ---
    _pad_fill: tuple  # fill values for (X, Y) padding rows
    _sharded_keys: frozenset = frozenset()  # state-dict keys on the axis

    def _make_inner(self, mesh):
        raise NotImplementedError

    def _build_migration(self, mesh, P_old, P_new):
        raise NotImplementedError

    def _migration_name(self, P_old: int, P_new: int) -> str:
        return program_name(self._discipline, f"migrate_{P_old}to{P_new}")

    def _unpack(self, state):
        raise NotImplementedError

    def _pack(self, a, b, X, Y):
        raise NotImplementedError

    def _live_span(self) -> int:
        raise NotImplementedError

    def _live_window(self) -> tuple:
        raise NotImplementedError

    def _state_dict(self) -> dict:
        raise NotImplementedError

    def _from_state_dict(self, d: dict):
        raise NotImplementedError

    @property
    def _entry_bytes(self) -> int:
        raise NotImplementedError


class _MultiWindowElastic(_ElasticBase):
    """Shared elastic machinery for structures whose ring store is split
    into ``_n_windows`` round-robin slot windows over one ``[first, last]``
    interval each — priority tiers (window = tier) and Seap buckets
    (window = bucket).  State must expose ``firsts``/``lasts`` ``[W]``
    vectors; the migration wave recovers every window's positions and
    moves all windows with ONE packed all_to_all (the PR 2 wave
    vectorized over windows).  Lives here ONCE so a migration fix cannot
    need landing per discipline (the PR 3 'patched three times' lesson)."""

    @property
    def _n_windows(self) -> int:
        raise NotImplementedError

    @property
    def sizes(self) -> list:
        """Per-window occupancy vector (one host int per tier/bucket)."""
        f = np.asarray(self.state.firsts)
        l = np.asarray(self.state.lasts)
        return [int(x) for x in (l - f + 1)]

    @property
    def size(self) -> int:
        """Total live elements across every window."""
        return sum(self.sizes)

    def _occupancies(self) -> list:
        return self.sizes

    def _live_span(self) -> int:
        # capacity check is per window (each owns its own slot range)
        return max([0] + self.sizes)

    def _hash_balance(self, P_new: int):
        """Combined consistent-hashing fidelity report over every
        window's live range (positions in different windows hash
        independently)."""
        f = np.asarray(self.state.firsts)
        l = np.asarray(self.state.lasts)
        pos = np.concatenate([np.arange(lo, hi + 1)
                              for lo, hi in zip(f, l)] or [np.zeros(0)])
        if pos.size == 0 or pos.size > HASH_BALANCE_MAX_SIZE:
            return None
        from ..kernels.hash_route import hash_route_ref
        _, counts = hash_route_ref(jnp.asarray(pos, jnp.int32),
                                   jnp.ones((pos.size,), bool), P_new)
        counts = np.asarray(counts)
        return {"n": int(pos.size), "max": int(counts.max()),
                "min": int(counts.min()),
                "roundrobin_max": -(-int(pos.size) // P_new)}

    @property
    def _entry_bytes(self) -> int:
        return 4 * (1 + self.W)  # slot ‖ payload columns

    def _build_migration(self, mesh, P_old: int, P_new: int):
        axis, cap, W = self.axis, self.cap, self.W
        n_win = self._n_windows
        n_mesh = mesh.shape[axis]
        M = min(n_win * cap, n_win * fanout_bound(P_old, P_new, cap))
        junk = n_win * cap

        def body(firsts, lasts, sv, sf):
            s = lax.axis_index(axis).astype(jnp.int32)
            u = jnp.arange(junk, dtype=jnp.int32)
            win = u // cap
            # recover the window-local position each occupied slot holds
            # (unique in the window's live range; PR 2 invariant per
            # window)
            p = recover_positions(s, u % cap, firsts[win], P_old, cap)
            live = sf[0, :junk] & (p >= firsts[win]) & (p <= lasts[win])
            owner = jnp.mod(p, P_new).astype(jnp.int32)
            slot_new = (win * cap + jnp.mod(p // P_new, cap)).astype(
                jnp.int32)
            cols = jnp.concatenate([slot_new[:, None], sv[0, :junk]], axis=1)
            fill = jnp.zeros((1 + W,), jnp.int32).at[0].set(junk)
            rows, moved, lost = migrate_packed(axis, n_mesh, M, live, owner,
                                               cols, fill)
            nsv, nsf = rewrite_ring_store(rows, junk, W)
            return firsts, lasts, nsv, nsf, moved, lost

        specs = (P(), P(), P(axis), P(axis))
        wrapped = shard_map(named(body, self._migration_name(P_old, P_new)),
                            mesh=mesh, in_specs=specs,
                            out_specs=specs + (P(), P()))
        return jax.jit(wrapped, donate_argnums=(2, 3))


class ElasticDeviceQueue(_ElasticBase):
    """Distributed FIFO whose shard count is a runtime variable.

    Owns its state (the inner ``DeviceQueue``'s donated-state discipline is
    internal): ``step``/``run_waves`` mirror :class:`DeviceQueue` minus the
    state argument, and ``grow``/``shrink``/``resize`` re-materialize the
    store between bursts.  See the module docstring for the mechanism."""

    _kind = "queue"
    _discipline = "fifo"
    _ovf_out = 4            # (pos, matched, deq_vals, deq_ok, overflow)
    _pad_fill = (0, False)
    _sharded_keys = frozenset({"store_vals", "store_full"})

    def __init__(self, n_shards: int, *, axis_name: str = "data",
                 cap: int = 1024, payload_width: int = 4,
                 ops_per_shard: int = 64, fused: bool = True,
                 devices=None, runtime=None, hlo_stats: bool = False,
                 pipelined: bool = True, metrics: bool = False,
                 metrics_ring: int = 64, flight_k: int = 16):
        self.fused = fused
        super().__init__(n_shards, axis_name=axis_name, cap=cap,
                         payload_width=payload_width,
                         ops_per_shard=ops_per_shard, devices=devices,
                         runtime=runtime,
                         hlo_stats=hlo_stats, pipelined=pipelined,
                         metrics=metrics, metrics_ring=metrics_ring,
                         flight_k=flight_k)

    def _make_inner(self, mesh):
        return DeviceQueue(mesh, self.axis, cap=self.cap,
                           payload_width=self.W, ops_per_shard=self.L,
                           fused=self.fused, pipelined=self.pipelined,
                           metrics=self.metrics and self.fused,
                           metrics_ring=self.metrics_ring,
                           runtime=self.runtime)

    # ------------------------------------------------------------ waves ----
    def step(self, is_enq, valid, payload):
        """One wave on the current mesh; state is threaded internally.
        Returns (positions, matched, deq_vals, deq_ok, overflow); raises
        :class:`~.errors.QueueOverflowError` when the wave overflowed."""
        return self._burst(self.inner.step, (is_enq, valid, payload), 0)

    def run_waves(self, is_enq, valid, payload):
        """K pre-staged waves in one dispatch (shapes [K, n_shards * L]).
        Raises :class:`~.errors.QueueOverflowError` on overflow."""
        return self._burst(self.inner.run_waves, (is_enq, valid, payload),
                           1)

    @property
    def size(self) -> int:
        """Live elements in the FIFO window (``last - first + 1``)."""
        return int(self.state.last) - int(self.state.first) + 1

    # -------------------------------------------------------- migration ----
    def _unpack(self, state):
        return state.first, state.last, state.store_vals, state.store_full

    def _pack(self, a, b, X, Y):
        return DeviceQueueState(a, b, X, Y)

    def _live_window(self):
        return int(self.state.first), int(self.state.last)

    def _live_span(self) -> int:
        lo, hi = self._live_window()
        return max(0, hi - lo + 1)

    @property
    def _entry_bytes(self) -> int:
        return 4 * (1 + self.W)  # slot ‖ payload columns

    def _state_dict(self) -> dict:
        return {"first": self.state.first, "last": self.state.last,
                "store_vals": self.state.store_vals,
                "store_full": self.state.store_full}

    def _from_state_dict(self, d: dict):
        return DeviceQueueState(d["first"], d["last"], d["store_vals"],
                                d["store_full"])

    def _build_migration(self, mesh, P_old: int, P_new: int):
        axis, cap, W = self.axis, self.cap, self.W
        n_mesh = mesh.shape[axis]
        M = fanout_bound(P_old, P_new, cap)

        def body(first, last, sv, sf):
            s = lax.axis_index(axis).astype(jnp.int32)
            t = jnp.arange(cap, dtype=jnp.int32)
            # recover the position each occupied slot holds (unique in the
            # live window [first, last]; see module docstring)
            p = recover_positions(s, t, first, P_old, cap)
            live = sf[0, :cap] & (p >= first) & (p <= last)
            owner = jnp.mod(p, P_new).astype(jnp.int32)
            slot_new = jnp.mod(p // P_new, cap).astype(jnp.int32)
            # ---- packed request: new_slot ‖ payload, one all_to_all ----
            cols = jnp.concatenate([slot_new[:, None], sv[0, :cap]], axis=1)
            fill = jnp.zeros((1 + W,), jnp.int32).at[0].set(cap)
            rows, moved, lost = migrate_packed(axis, n_mesh, M, live, owner,
                                               cols, fill)
            nsv, nsf = rewrite_ring_store(rows, cap, W)
            return first, last, nsv, nsf, moved, lost

        specs = (P(), P(), P(axis), P(axis))
        wrapped = shard_map(named(body, self._migration_name(P_old, P_new)),
                            mesh=mesh, in_specs=specs,
                            out_specs=specs + (P(), P()))
        return jax.jit(wrapped, donate_argnums=(2, 3))


class ElasticDeviceStack(_ElasticBase):
    """Distributed LIFO with runtime-variable shard count.

    Migration flattens the (slot, depth) entry set; an entry's position is
    recovered from its slot exactly as for the queue (live window
    ``[1, last]``), and its depth index travels with it — distinct positions
    land on distinct new slots, so (new_slot, depth) addressing is
    collision-free on the receiving side."""

    _kind = "stack"
    _discipline = "lifo"
    _ovf_out = 4            # (pos, matched, pop_vals, pop_ok, overflow)
    _pad_fill = (0, -1)  # vals pad 0, tickets pad -1 (= empty)
    _sharded_keys = frozenset({"vals", "ticks"})

    def __init__(self, n_shards: int, *, axis_name: str = "data",
                 cap: int = 1024, payload_width: int = 4,
                 ops_per_shard: int = 64, slot_depth: int = 4,
                 devices=None, runtime=None, hlo_stats: bool = False,
                 pipelined: bool = True, metrics: bool = False,
                 metrics_ring: int = 64, flight_k: int = 16):
        self.D = slot_depth
        super().__init__(n_shards, axis_name=axis_name, cap=cap,
                         payload_width=payload_width,
                         ops_per_shard=ops_per_shard, devices=devices,
                         runtime=runtime,
                         hlo_stats=hlo_stats, pipelined=pipelined,
                         metrics=metrics, metrics_ring=metrics_ring,
                         flight_k=flight_k)

    def _make_inner(self, mesh):
        return DeviceStack(mesh, self.axis, cap=self.cap,
                           payload_width=self.W, ops_per_shard=self.L,
                           slot_depth=self.D, pipelined=self.pipelined,
                           metrics=self.metrics,
                           metrics_ring=self.metrics_ring,
                           runtime=self.runtime)

    _overflow_detail = ("a store slot's depth-D ticket set was exhausted "
                        "at commit time")

    def _wave_capacity(self) -> int:
        return self.n_shards * self.cap * self.D

    # ------------------------------------------------------------ waves ----
    def step(self, is_push, valid, payload):
        """One wave on the current mesh; state is threaded internally.
        Returns (positions, matched, pop_vals, pop_ok, overflow); raises
        :class:`~.errors.QueueOverflowError` when the wave overflowed."""
        return self._burst(self.inner.step, (is_push, valid, payload), 0)

    def run_waves(self, is_push, valid, payload):
        """K pre-staged waves in one dispatch (shapes [K, n_shards * L]).
        Raises :class:`~.errors.QueueOverflowError` on overflow."""
        return self._burst(self.inner.run_waves, (is_push, valid, payload),
                           1)

    @property
    def size(self) -> int:
        """Live elements on the stack (positions start at 1)."""
        return int(self.state["last"])

    # -------------------------------------------------------- migration ----
    def _unpack(self, state):
        return state["last"], state["ticket"], state["vals"], state["ticks"]

    def _pack(self, a, b, X, Y):
        return {"last": a, "ticket": b, "vals": X, "ticks": Y}

    def _live_window(self):
        return 1, int(self.state["last"])

    def _live_span(self) -> int:
        return int(self.state["last"])

    @property
    def _entry_bytes(self) -> int:
        return 4 * (3 + self.W)  # slot ‖ depth ‖ ticket ‖ payload

    def _layout(self) -> dict:
        return {**super()._layout(), "D": self.D}

    @classmethod
    def _layout_kwargs(cls, lay: dict) -> dict:
        return {**super()._layout_kwargs(lay), "slot_depth": lay["D"]}

    def _state_dict(self) -> dict:
        return dict(self.state)

    def _from_state_dict(self, d: dict):
        return {"last": d["last"], "ticket": d["ticket"],
                "vals": d["vals"], "ticks": d["ticks"]}

    def _build_migration(self, mesh, P_old: int, P_new: int):
        axis, cap, W, D = self.axis, self.cap, self.W, self.D
        n_mesh = mesh.shape[axis]
        M = min(cap * D, fanout_bound(P_old, P_new, cap) * D)

        def body(last, ticket, sv, stk):
            s = lax.axis_index(axis).astype(jnp.int32)
            t = jnp.arange(cap, dtype=jnp.int32)
            p = recover_positions(s, t, 1, P_old, cap)  # positions start at 1
            in_range = (p >= 1) & (p <= last)
            owner = jnp.mod(p, P_new).astype(jnp.int32)
            slot_new = jnp.mod(p // P_new, cap).astype(jnp.int32)
            ticks = stk[0, :cap]                             # [cap, D]
            live = ((ticks >= 0) & in_range[:, None]).reshape(-1)
            dep = jnp.tile(jnp.arange(D, dtype=jnp.int32), cap)
            # ---- packed request: slot ‖ depth ‖ ticket ‖ payload ----
            cols = jnp.concatenate(
                [jnp.repeat(slot_new, D)[:, None], dep[:, None],
                 ticks.reshape(-1)[:, None], sv[0, :cap].reshape(-1, W)],
                axis=1)
            fill = jnp.zeros((3 + W,), jnp.int32).at[0].set(cap).at[2].set(-1)
            rows, moved, lost = migrate_packed(
                axis, n_mesh, M, live, jnp.repeat(owner, D), cols, fill)
            rs, rd, rt = rows[:, 0], rows[:, 1], rows[:, 2]
            rv = rows[:, 3:]
            nstk = jnp.full((cap + 1, D), -1, jnp.int32).at[rs, rd].set(rt)
            nstk = nstk.at[cap].set(-1)
            nsv = jnp.zeros((cap + 1, D, W), jnp.int32).at[rs, rd].set(rv)
            nsv = nsv.at[cap].set(0)
            return last, ticket, nsv[None], nstk[None], moved, lost

        specs = (P(), P(), P(axis), P(axis))
        wrapped = shard_map(named(body, self._migration_name(P_old, P_new)),
                            mesh=mesh, in_specs=specs,
                            out_specs=specs + (P(), P()))
        return jax.jit(wrapped, donate_argnums=(2, 3))
