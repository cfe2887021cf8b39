"""Continuous-batching serving engine fed by the SKUEUE request queue.

Requests arrive at any host and are enqueued into the distributed queue
(payload = request id); the engine dequeues in the queue's sequentially-
consistent FIFO order — cross-host fairness is Definition 1, not a
scheduler heuristic.  Decode runs vmapped over the
slot set with per-slot positions; finished slots are refilled from the queue each step
(continuous batching).  Prompt ingestion is teacher-forced through the
decode path (slot-local), which shares one compiled step for prefill and
decode at engine scale; the 32k-prefill fast path is the dedicated
``prefill`` lowering exercised by the dry-run.

Queue traffic rides the multi-wave API (PR 1): ``submit`` stages arrivals
host-side, and each engine step flushes staged enqueues *and* the free-slot
dequeues as ONE fused queue wave (``run_waves``), chunked across
K waves in a single device dispatch when a submission burst exceeds one
wave's capacity.  The engine mirrors the queue size host-side
(enqueues flushed minus dequeues granted), so ``run_until_drained`` never
synchronizes on device state between steps.

Elastic membership (PR 2): the request queue is an
:class:`~repro.dqueue.ElasticDeviceQueue`, so the engine can JOIN/LEAVE
queue shards at runtime — :meth:`resize` drains staged submissions into the
queue, re-materializes it onto the new shard count (every queued request id
survives, FIFO order intact), and resumes bursts on the new mesh.  This is
the elastic-serving story: scale the admission fabric with traffic, shed a
failed shard without dropping queued work.

Unified wave engine (PR 4): every queue flavor the engine can ride — FIFO,
priority-tiered, elastic — is now one
:class:`~repro.dqueue.WaveEngine` under a discipline plug-in, and the
chunked multi-wave bursts ``_queue_wave`` stages are software-pipelined by
default (wave k's dispatch overlaps wave k-1's store rewrite; one fused
``all_to_all`` per wave in steady state).  ``ServeEngine(pipelined=False)``
forwards the engine's sequential burst schedule for differential testing;
results are identical either way.

Priority tiers (PR 3): ``ServeEngine(priorities=P)`` swaps the admission
fabric for an :class:`~repro.dqueue.ElasticDevicePriorityQueue` —
``submit(reqs, prio=...)`` stages requests into SLA tiers (0 = interactive,
higher = batch), each step's fused wave admits higher tiers first (the
queue's highest-priority-first wave resolution, NOT a host scheduler
heuristic), and per-tier queue waits are tracked so mixed-load tail-latency
separation is measurable (``tier_wait_stats``).  ``relaxation=k`` forwards
Skeap's bounded tier-relaxation knob to the queue.

Deadline scheduling (PR 5): ``ServeEngine(deadline=True)`` swaps the
admission fabric for an :class:`~repro.dqueue.ElasticDeviceSeapQueue` —
the Seap arbitrary-key discipline with key = the request's deadline step,
so each step's fused wave admits **earliest-deadline-first** (EDF, at the
bucket granularity of the Seap directory), and ``deadline_stats`` reports
the miss rate.  Queue overflow is no longer an assert anywhere on this
path: the elastic wrappers raise
:class:`~repro.dqueue.QueueOverflowError` with per-tier/bucket occupancy,
and :meth:`resize` raises :class:`~repro.dqueue.ServeInvariantError`
instead of a stripped-under-``-O`` bare assert when its enqueue-only
drain wave misbehaves.

Backpressure (PR 8): ``ServeEngine(admission=...)`` installs an admission
policy (``"shed"`` / ``"defer"`` / ``"degrade"``, see
:mod:`repro.serve.admission`) that :meth:`submit` consults against the
queue's zero-cost pre-wave pressure API before staging anything — a full
window rejects with a structured, retryable
:class:`~repro.serve.AdmissionRejected` instead of overwriting live data
mid-wave; deferred requests wait in a bounded host-side spill buffer that
drains ahead of new arrivals on every refill.  ``autoscale=`` wires a
:class:`~repro.serve.HysteresisController` that turns sustained pressure
above its high watermark into ``resize(n + k)`` (and sustained idleness
into a shrink) over the PR 2 one-collective migration — the system's
first closed feedback loop.  ``docs/BACKPRESSURE.md`` is the design doc.

Observability (PR 7): ``ServeEngine(telemetry=True)`` turns on Wavescope —
each fused queue wave also writes one row of admission/occupancy counters
into a device-side metrics ring (pure arithmetic on values the wave already
materializes; zero extra collectives), drained host-side at burst
boundaries into the queue's flight recorder.  :meth:`metrics` returns the
structured snapshot (export via ``repro.obs.to_json`` /
``to_prometheus``), ``submit``/refill/resize emit ``repro.obs.trace``
spans, and overflow/invariant errors carry the last-K wave trajectory.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dqueue import (ElasticDeviceQueue, ElasticDevicePriorityQueue,
                      ElasticDeviceSeapQueue, ServeInvariantError)
from ..obs.trace import span
from .admission import AdmissionRejected, PressureSignal, resolve_policy


def slot_decode(model):
    """The engine's decode step: ``model.decode_fn`` vmapped over slots.

    Called as ``(params, cache, toks[B, 1], idxs[B])`` with cache leaves
    batched on axis 1 (``[layers, B, ...]``); every slot decodes at ITS
    OWN position, and the unit batch dim the model expects is re-added
    inside the map.  Returns ``(logits[B, ...], cache)``.
    """
    def _one(p, c, t, i):
        c = jax.tree.map(lambda x: x[:, None], c)
        lg, nc = model.decode_fn(p, c, t[None], i)
        nc = jax.tree.map(lambda x: x[:, 0], nc)
        return lg[0], nc

    return jax.jit(jax.vmap(_one, in_axes=(None, 1, 0, 0), out_axes=(0, 1)))


@dataclasses.dataclass
class Request:
    """One serving request and its lifecycle bookkeeping.

    Attributes:
      rid: caller-chosen unique request id (rides the queue as payload).
      prompt: prompt token ids, teacher-forced through the decode path.
      max_new: tokens to generate after the prompt.
      prio: SLA tier on ``priorities > 1`` engines (0 = most urgent; the
        degrade admission policy may raise this).
      deadline: absolute engine step to start by on EDF engines (the
        degrade policy may extend it); -1 = unset.
      out: generated token ids (filled by the engine).
      done: True once ``max_new`` tokens (or ``max_seq``) were produced.
      enqueue_step: step the request was accepted (staged or deferred).
      start_step: step it won a decode slot; -1 while queued.
      finish_step: step it completed; -1 while running.
    """

    rid: int
    prompt: List[int]
    max_new: int = 8
    prio: int = 0
    deadline: int = -1            # absolute engine step to start by (EDF)
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    enqueue_step: int = -1
    start_step: int = -1
    finish_step: int = -1


class ServeEngine:
    """Continuous-batching serving engine over the SKUEUE device queue.

    See the module docstring for the architecture.  Constructor args:

    Args:
      model / params / mesh: the decode model, its parameters, and the
        jax mesh whose ``"data"`` axis sizes the queue's shard count.
      max_slots: concurrent decode slots (continuous-batching width).
      max_seq: per-slot sequence capacity.
      queue_cap: per-shard ring capacity of the request queue.
      priorities: > 1 swaps in the priority queue with that many SLA
        tiers (exclusive with ``deadline``).
      relaxation: Skeap bounded tier-relaxation knob (tiers only).
      deadline: True swaps in the Seap queue for EDF admission.
      n_buckets / deadline_horizon: Seap directory shape (EDF only).
      pipelined: software-pipelined multi-wave bursts (default).
      telemetry: enable Wavescope device metrics + flight recorder.
      flight_k: flight-recorder depth.
      admission: None, a policy name ("shed" / "defer" / "degrade"), or
        an :class:`~repro.serve.admission.AdmissionPolicy` — consulted by
        :meth:`submit` before staging (PR 8).
      spill_cap: bound of the defer policy's host-side spill buffer.
      autoscale: a :class:`~repro.serve.HysteresisController` driving
        :meth:`resize` from sustained pressure (PR 8); its
        ``max_shards`` defaults to the queue's device-pool size.
      runtime: a :class:`~repro.runtime.Runtime` handle (PR 10).  The
        queue's shard pool, placement, and host staging all go through
        it; when omitted, one is derived from ``mesh`` (a bare Mesh is
        adopted into a transparent ``LocalRuntime``).  ``mesh`` itself
        may also BE a runtime, in which case the engine's mesh is the
        runtime's current mesh.

    Raises:
      ValueError: incompatible discipline flags or unknown policy name.
    """

    def __init__(self, model, params, mesh, *, max_slots: int = 4,
                 max_seq: int = 64, queue_cap: int = 256,
                 priorities: int = 1, relaxation: int = 0,
                 deadline: bool = False, n_buckets: int = 8,
                 deadline_horizon: int = 64, pipelined: bool = True,
                 telemetry: bool = False, flight_k: int = 16,
                 admission=None, spill_cap: int = 64,
                 autoscale=None, runtime=None):
        from ..runtime import Runtime
        if runtime is None and isinstance(mesh, Runtime):
            runtime, mesh = mesh, None
        if runtime is not None and mesh is None:
            mesh = runtime.mesh()
        self.runtime = runtime
        n_shards = (runtime.n_shards if runtime is not None
                    else mesh.shape["data"])
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.mesh = mesh
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.priorities = priorities
        self.deadline = deadline
        self.telemetry = bool(telemetry)
        if deadline and priorities > 1:
            raise ValueError("deadline=True (EDF via the Seap queue) and "
                             "priorities > 1 (SLA tiers) are exclusive "
                             "admission disciplines")
        if deadline:
            # seed the directory on a step grid over the deadline horizon
            # (a cold directory serves near-FIFO until splits zoom in);
            # the split/merge rule then rolls the refined window forward
            # as past buckets drain and future ones fill.  Splits trigger
            # at roughly one refill's worth of waiting requests.
            grid = max(1, deadline_horizon // n_buckets)
            self.queue = ElasticDeviceSeapQueue(
                n_shards, n_buckets=n_buckets, cap=queue_cap,
                payload_width=2, ops_per_shard=max(8, 2 * max_slots),
                split_occupancy=max(1, 2 * max_slots),
                seed_bounds=[i * grid for i in range(1, n_buckets)],
                pipelined=pipelined, metrics=telemetry,
                flight_k=flight_k, runtime=runtime)
        elif priorities > 1:
            self.queue = ElasticDevicePriorityQueue(
                n_shards, n_prios=priorities,
                relaxation=relaxation, cap=queue_cap, payload_width=2,
                ops_per_shard=max(8, 2 * max_slots), pipelined=pipelined,
                metrics=telemetry, flight_k=flight_k, runtime=runtime)
        else:
            self.queue = ElasticDeviceQueue(n_shards,
                                            cap=queue_cap, payload_width=2,
                                            ops_per_shard=max(8, 2 * max_slots),
                                            pipelined=pipelined,
                                            metrics=telemetry,
                                            flight_k=flight_k,
                                            runtime=runtime)
        self.requests: Dict[int, Request] = {}
        self.slots: List[Optional[int]] = [None] * max_slots
        self.slot_pos = np.zeros(max_slots, np.int64)
        self.cache, _ = model.init_cache(max_slots, max_seq)
        self.step_no = 0
        self._staged: List[int] = []   # rids submitted but not yet flushed
        self._host_qsize = 0           # host mirror of the device queue size
        self._decode = slot_decode(model)
        # a refilled slot starts from the zero cache init_cache gives: a
        # recurrent (SSM) state left by the slot's previous request would
        # otherwise leak into the new one
        self._clear_slots = jax.jit(lambda c, fresh: jax.tree.map(
            lambda x: jnp.where(
                fresh.reshape((1, -1) + (1,) * (x.ndim - 2)),
                jnp.zeros((), x.dtype), x), c))
        self.stats = {"served": 0, "queue_waits": [],
                      "queue_waits_by_prio": {p: [] for
                                              p in range(priorities)},
                      "deadline_lateness": []}
        # ---- backpressure control plane (PR 8) ----
        self.admission = resolve_policy(admission)
        self.spill_cap = int(spill_cap)
        self._spill: deque = deque()   # deferred Requests, oldest first
        self.autoscale = autoscale
        if autoscale is not None and autoscale.cfg.max_shards is None:
            autoscale.cfg.max_shards = self.queue.pool_size
        self._overloaded = False       # shed/defer seen since last tick
        self._in_autoscale = False     # resize() call is the controller's
        self.admission_stats = {"offered": 0, "admitted": 0, "shed": 0,
                                "deferred": 0, "degraded": 0,
                                "spill_peak": 0, "decide_us": []}

    # ---------------------------------------------------------- frontend ---
    def submit(self, reqs: List[Request], prio: Optional[int] = None,
               deadline: Optional[int] = None):
        """Stage arrivals for the distributed queue.

        They enter the queue on the next engine step, fused with that step's
        refill dequeues; oversized bursts are chunked across as many queue
        waves as needed (all inside one ``run_waves`` dispatch), so a submit
        can exceed ``n_shards * L`` requests without overflowing a wave.

        With ``priorities > 1``, ``prio`` (or each request's ``.prio``
        field) selects the SLA tier: 0 is served ahead of 1, etc.

        With ``deadline=True`` on the engine, ``deadline`` (steps from
        now) or each request's ``.deadline`` field (an absolute engine
        step) sets the EDF key — requests with earlier deadlines are
        admitted first, bucket-granular.

        With an admission policy installed (``admission=``), the batch is
        first decided against the queue's live pressure (PR 8): what fits
        is staged, the defer policy spills the rest host-side, and
        anything rejected raises — AFTER the fitting part was staged.

        Raises:
          ValueError: bad tier / missing deadline.
          AdmissionRejected: the policy rejected part of the batch (or
            the spill buffer was full); ``err.shed`` holds the untouched,
            resubmittable requests.
        """
        with span("serve:submit", cat="serve", n=len(reqs),
                  step=self.step_no):
            self._submit(reqs, prio, deadline)

    def _submit(self, reqs: List[Request], prio: Optional[int],
                deadline: Optional[int]):
        for r in reqs:
            if prio is not None:
                r.prio = prio
            if not 0 <= r.prio < self.priorities:
                raise ValueError(f"request {r.rid} prio {r.prio} outside "
                                 f"[0, {self.priorities})")
            if self.deadline:
                if deadline is not None:
                    r.deadline = self.step_no + deadline
                if r.deadline < 0:
                    raise ValueError(f"request {r.rid} needs a deadline "
                                     "(engine runs EDF admission)")
        if self.admission is None:
            for r in reqs:
                self._accept(r, stage=True)
            return
        t0 = time.perf_counter()
        sig = self._pressure_signal()
        dec = self.admission.decide(list(reqs), sig)
        st = self.admission_stats
        st["decide_us"].append((time.perf_counter() - t0) * 1e6)
        st["offered"] += len(reqs)
        st["admitted"] += len(dec.admit)
        st["deferred"] += len(dec.defer)
        st["degraded"] += dec.degraded
        for r in dec.admit:
            self._accept(r, stage=True)
        for r in dec.defer:
            self._accept(r, stage=False)
            self._spill.append(r)
        st["spill_peak"] = max(st["spill_peak"], len(self._spill))
        if dec.shed or dec.defer or dec.degraded:
            self._overloaded = True
            self.queue.recorder.record({
                "event": "admission", "step": self.step_no,
                "policy": self.admission.name, "shed": len(dec.shed),
                "deferred": len(dec.defer), "degraded": dec.degraded,
                "occ": list(sig.occupancy)})
        if dec.shed:
            st["shed"] += len(dec.shed)
            backlog = len(dec.shed) + len(self._spill)
            raise AdmissionRejected(
                self.admission.name,
                "spill-overflow" if dec.spill_overflow else "shed",
                dec.shed, admitted=len(dec.admit),
                deferred=len(dec.defer), degraded=dec.degraded,
                pressure=sig.snapshot(),
                retry_after=-(-backlog // max(1, self.max_slots)))

    def _accept(self, r: Request, *, stage: bool):
        """Register an admitted request; stage it for the next flush (or
        leave it to the spill buffer when ``stage`` is False)."""
        self.requests[r.rid] = r
        r.enqueue_step = self.step_no
        if stage:
            self._staged.append(r.rid)

    # ------------------------------------------------------- backpressure ---
    def _pressure_signal(self) -> PressureSignal:
        """Snapshot the queue + host pressure for an admission decision.

        Occupancy and the Seap directory come from the elastic wrapper's
        pre-wave pressure API — replicated host reads, no collective and
        no wave dispatch; staged/spill counts are pure host bookkeeping."""
        q = self.queue
        occ = q.occupancy()
        staged = [0] * len(occ)
        window_order = None
        window_lo = None
        if self.deadline:
            entries = q.directory()       # (lo, bucket) in key order
            los = [lo for lo, _ in entries]
            ids = [b for _, b in entries]
            window_order = ids
            window_lo = {b: lo for lo, b in entries}

            def window_of(r, _los=los, _ids=ids):
                return _ids[max(0, bisect.bisect_right(_los,
                                                       r.deadline) - 1)]
        elif self.priorities > 1:
            def window_of(r):
                return r.prio
        else:
            def window_of(r):
                return 0
        for rid in self._staged:
            staged[window_of(self.requests[rid])] += 1
        late = self.stats["deadline_lateness"][-128:]
        p99 = (float(np.percentile(np.asarray(late, np.float64), 99))
               if late else 0.0)
        return PressureSignal(
            capacity=q.window_capacity(), occupancy=occ, staged=staged,
            spill=len(self._spill), spill_cap=self.spill_cap,
            step=self.step_no,
            mode=("edf" if self.deadline
                  else "tiers" if self.priorities > 1 else "fifo"),
            lateness_p99=p99, drain_per_step=self.max_slots,
            window_of=window_of, window_order=window_order,
            window_lo=window_lo)

    def _drain_spill(self):
        """Re-offer deferred requests ahead of new arrivals, as far as the
        current headroom allows (oldest first; the rest keep waiting)."""
        if not self._spill:
            return
        sig = self._pressure_signal()
        keep: deque = deque()
        front: List[int] = []
        while self._spill:
            r = self._spill.popleft()
            w = sig.window_of(r)
            if sig.headroom(w) > 0:
                sig.take(w)
                front.append(r.rid)
            else:
                keep.append(r)
        self._spill = keep
        self._staged = front + self._staged

    def _autoscale_tick(self):
        """One controller observation; executes the resize it decides.

        Utilization feeds the hottest window's occupancy PLUS everything
        still host-side (staged + spilled), so load a policy absorbed
        before the device saw it still registers as pressure."""
        q = self.queue
        cap = q.window_capacity()
        occ = q.occupancy()
        backlog = max(occ, default=0) + len(self._staged) + len(self._spill)
        util = backlog / cap if cap else 1.0
        target = self.autoscale.observe(util, q.n_shards,
                                        overloaded=self._overloaded)
        self._overloaded = False
        if target is None or target == q.n_shards:
            return
        with span("serve:autoscale", cat="serve", step=self.step_no,
                  target=target):
            self._in_autoscale = True
            try:
                self.resize(target)
            finally:
                self._in_autoscale = False
        self.autoscale.notify_resize(target)
        q.recorder.record({"event": "autoscale", "step": self.step_no,
                           "n_shards": target, "occ": occ})

    def _queue_wave(self, enq_rids: List[int], n_deq: int) -> List[int]:
        """Run enqueues + dequeues as chunked fused waves; returns granted
        request ids.  Wave width tracks the queue's CURRENT shard count —
        and, within it, the burst's occupancy bucket: a refill that fits a
        single wave rides the narrowest envelope of the queue's bucket
        ladder that holds it (PR 9), shrinking both all_to_all payloads.
        Oversized bursts chunk at the full width as before."""
        n_ops = len(enq_rids) + n_deq
        if n_ops == 0:
            return []
        n_full = self.queue.n_shards * self.queue.L
        if n_ops <= n_full:
            # the admission layer knows the staged count: pick the
            # smallest bucket that fits (each width is a cached program)
            n = self.queue.n_shards * self.queue.pick_width(n_ops)
        else:
            n = n_full
        n_waves = -(-n_ops // n)  # ceil: chunk oversized bursts
        # pad the wave count to a power of two (extra waves are all-invalid
        # no-ops) so fluctuating burst sizes only ever compile the scanned
        # program for O(log K) distinct shapes
        n_waves = 1 << (n_waves - 1).bit_length()
        is_enq = np.zeros((n_waves, n), bool)
        valid = np.zeros((n_waves, n), bool)
        prio = np.zeros((n_waves, n), np.int32)
        payload = np.zeros((n_waves, n, 2), np.int32)
        for j, rid in enumerate(enq_rids):
            k, i = divmod(j, n)
            is_enq[k, i] = valid[k, i] = True
            prio[k, i] = (self.requests[rid].deadline if self.deadline
                          else self.requests[rid].prio)
            payload[k, i, 0] = rid
        for m in range(n_deq):
            k, i = divmod(len(enq_rids) + m, n)
            valid[k, i] = True  # dequeue request
        # overflow is raised by the elastic wrapper as QueueOverflowError
        # (with per-tier/bucket occupancy) — no bare assert on this path
        if self.deadline or self.priorities > 1:
            _, _, _, dv, dok, _, _ = self.queue.run_waves(
                jnp.array(is_enq), jnp.array(valid), jnp.array(prio),
                jnp.array(payload))
        else:
            _, _, dv, dok, _ = self.queue.run_waves(
                jnp.array(is_enq), jnp.array(valid), jnp.array(payload))
        to_host = self.queue.runtime.to_host
        dv = to_host(dv).reshape(n_waves * n, 2)
        dok = to_host(dok).reshape(n_waves * n)
        got = [int(dv[j, 0]) for j in range(n_waves * n) if dok[j]]
        self._host_qsize += len(enq_rids) - len(got)
        return got

    def _flush_and_refill(self):
        """ONE fused queue dispatch: staged enqueues + free-slot dequeues.
        Deferred (spilled) requests drain first, ahead of new arrivals."""
        self._drain_spill()
        free = [i for i, s in enumerate(self.slots) if s is None]
        enq_rids, self._staged = self._staged, []
        with span("serve:refill", cat="serve", step=self.step_no,
                  enq=len(enq_rids), free=len(free)):
            got = self._queue_wave(enq_rids, len(free))
        if got:
            fresh = np.zeros(self.max_slots, bool)
            fresh[free[:len(got)]] = True
            self.cache = self._clear_slots(self.cache, jnp.asarray(fresh))
        for slot, rid in zip(free, got):
            r = self.requests[rid]
            r.start_step = self.step_no
            self.stats["queue_waits"].append(r.start_step - r.enqueue_step)
            self.stats["queue_waits_by_prio"][r.prio].append(
                r.start_step - r.enqueue_step)
            if self.deadline and r.deadline >= 0:
                self.stats["deadline_lateness"].append(
                    r.start_step - r.deadline)
            self.slots[slot] = rid
            self.slot_pos[slot] = 0

    def _pending_by_prio(self) -> Dict[int, int]:
        """Submitted-but-not-yet-admitted request count per tier — the
        starvation the wait stats exist to expose."""
        pending = {p: 0 for p in range(self.priorities)}
        for r in self.requests.values():
            if r.start_step < 0 and not r.done:
                pending[r.prio] += 1
        return pending

    def tier_wait_stats(self) -> Dict[int, dict]:
        """Per-tier admission latency (engine steps from submit to slot):
        count / mean / p50 / p99 plus the tier's ``pending`` (submitted,
        never admitted) count — the mixed-load separation the priority
        fabric exists to provide.  EVERY configured tier gets a row: a
        starved tier shows ``{"n": 0, "pending": k}`` instead of being
        silently omitted (which hid exactly the starvation this report
        exists to surface)."""
        pending = self._pending_by_prio()
        out = {}
        for p in range(self.priorities):
            waits = self.stats["queue_waits_by_prio"].get(p, [])
            row = {"n": len(waits), "pending": pending[p]}
            if waits:
                w = np.asarray(waits, np.float64)
                row.update(mean=float(w.mean()),
                           p50=float(np.percentile(w, 50)),
                           p99=float(np.percentile(w, 99)))
            out[p] = row
        return out

    def deadline_stats(self) -> dict:
        """EDF admission outcome (``deadline=True`` engines): admissions,
        misses (started after the deadline step), miss rate, lateness
        percentiles, and the still-pending count."""
        late = np.asarray(self.stats["deadline_lateness"], np.float64)
        missed = int((late > 0).sum()) if late.size else 0
        out = {"n": int(late.size), "missed": missed,
               "miss_rate": missed / late.size if late.size else 0.0,
               "pending": sum(self._pending_by_prio().values())}
        if late.size:
            out.update(lateness_mean=float(late.mean()),
                       lateness_p99=float(np.percentile(late, 99)),
                       lateness_max=float(late.max()))
        return out

    # ----------------------------------------------------------- elastic ---
    def resize(self, n_shards: int) -> dict:
        """Live JOIN/LEAVE of queue shards between engine steps.

        Drains staged submissions into the device queue (so the migration
        wave carries them too), re-materializes the queue onto ``n_shards``
        shards, and resumes — queued request ids and FIFO admission order
        are preserved exactly.  Returns the migration stats dict."""
        enq_rids, self._staged = self._staged, []
        got = self._queue_wave(enq_rids, 0)
        if got:
            # an enqueue-only drain wave granted dequeues: the host-side
            # queue mirror and the device queue have diverged (was a bare
            # assert, invisible under ``python -O``)
            raise ServeInvariantError(
                "resize drain wave granted requests from an enqueue-only "
                "wave", granted_rids=got, staged=len(enq_rids),
                n_shards_from=self.queue.n_shards, n_shards_to=n_shards,
                host_qsize=self._host_qsize, step=self.step_no,
                trajectory=self.queue.trajectory())
        stats = self.queue.resize(n_shards)
        if self.autoscale is not None and not self._in_autoscale:
            # a resize the controller did NOT decide (operator or fault
            # layer): reset its counters so it re-learns the new shape
            self.autoscale.notify_resize(n_shards, external=True)
        return stats

    # ------------------------------------------------------ observability ---
    def metrics(self) -> dict:
        """Structured Wavescope snapshot of the serving fabric — feed it to
        :func:`repro.obs.to_json` / :func:`repro.obs.to_prometheus`.

        Always-on scalars come from host bookkeeping (served count, slot
        utilization, queue-depth mirror, admission-wait percentiles,
        per-tier / deadline stats where configured).  With
        ``telemetry=True`` the snapshot additionally drains the device-side
        metrics ring into the flight recorder and attaches the recent wave
        summaries under ``"waves"`` — no extra collectives, the drain is a
        burst-boundary host read."""
        q = self.queue
        occ = q.occupancy()
        snap = {
            "step": self.step_no,
            "served": self.stats["served"],
            "slots": {"active": sum(s is not None for s in self.slots),
                      "max": self.max_slots},
            "staged": len(self._staged),
            "queue": {
                "kind": q._kind,
                "n_shards": q.n_shards,
                "depth": self._host_qsize,
                "window_capacity": q.window_capacity(),
                "occupancy": occ,
                "headroom": q.window_capacity() - max(occ, default=0),
                "migrations": len(q.migrations),
            },
        }
        if self.admission is not None:
            st = self.admission_stats
            ac = {"policy": self.admission.name,
                  "offered": st["offered"], "admitted": st["admitted"],
                  "shed": st["shed"], "deferred": st["deferred"],
                  "degraded": st["degraded"],
                  "spill": len(self._spill), "spill_cap": self.spill_cap,
                  "spill_peak": st["spill_peak"]}
            if st["decide_us"]:
                d = np.asarray(st["decide_us"], np.float64)
                ac.update(decide_us_mean=float(d.mean()),
                          decide_us_p99=float(np.percentile(d, 99)))
            snap["admission_control"] = ac
        if self.autoscale is not None:
            snap["autoscale"] = self.autoscale.snapshot()
        waits = self.stats["queue_waits"]
        adm = {"n": len(waits)}
        if waits:
            w = np.asarray(waits, np.float64)
            adm.update(mean=float(w.mean()),
                       p50=float(np.percentile(w, 50)),
                       p99=float(np.percentile(w, 99)))
        snap["admission"] = adm
        if self.priorities > 1:
            snap["tiers"] = self.tier_wait_stats()
        if self.deadline:
            snap["deadline"] = self.deadline_stats()
        if self.telemetry:
            q._drain_telemetry()
            snap["waves"] = q.trajectory()
        return snap

    # ------------------------------------------------------------ decode ---
    def step(self):
        """One engine step: flush+refill in one fused wave, advance slots.
        With ``autoscale=`` set, also runs one controller tick (which may
        execute a resize migration between the wave and the decode)."""
        self.step_no += 1
        self._flush_and_refill()
        if self.autoscale is not None:
            self._autoscale_tick()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return
        toks = np.zeros((self.max_slots, 1), np.int32)
        for i in active:
            r = self.requests[self.slots[i]]
            p = int(self.slot_pos[i])
            if p < len(r.prompt):
                toks[i, 0] = r.prompt[p]
            else:
                toks[i, 0] = r.out[-1] if r.out else r.prompt[-1]
        # ONE vmapped decode: every slot advances at its own position
        idxs = jnp.array(self.slot_pos, jnp.int32)
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.array(toks), idxs)
        lg = np.asarray(logits, np.float32).reshape(self.max_slots, -1)
        for i in active:
            r = self.requests[self.slots[i]]
            self.slot_pos[i] += 1
            if self.slot_pos[i] >= len(r.prompt):
                nxt = int(lg[i].argmax())
                r.out.append(nxt)
                if (len(r.out) >= r.max_new
                        or self.slot_pos[i] >= self.max_seq - 1):
                    r.done = True
                    r.finish_step = self.step_no
                    self.stats["served"] += 1
                    self.slots[i] = None

    def run_until_drained(self, max_steps: int = 1000):
        """Drive steps until everything is served.  Drain detection uses the
        host-side queue-size mirror — no device synchronization per step."""
        for _ in range(max_steps):
            self.step()
            if (all(r.done for r in self.requests.values())
                    and not self._staged and not self._spill
                    and self._host_qsize == 0):
                return True
        return False
