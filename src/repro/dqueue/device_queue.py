"""Device-resident distributed queue/stack: disciplines over the WaveEngine.

The element store is sharded across a mesh axis: position ``p`` lives on
shard ``p % n_shards`` at slot ``(p // n_shards) % cap`` — a dense sharded
ring buffer.  Because SKUEUE positions are *dense consecutive integers*,
round-robin placement is **perfectly** fair (a strict improvement over the
paper's consistent hashing, which is fair only in expectation — recorded as
a beyond-paper adaptation in DESIGN.md §6; a hashed-owner mode computed by
``kernels/hash_route`` exists for fidelity benchmarking).

One ``step`` call = one paper "wave": position assignment via the
associative scan (Stages 1-3) + PUT/GET dispatch via ``lax.all_to_all``
(Stage 4).  PUTs apply before GETs inside the step, which resolves the
paper's GET-outruns-PUT asynchrony *by construction*; FIFO consistency
guarantees a matched GET's element is present (enqueued this step or
earlier).

As of PR 4 the wave body itself — packed two-collective Stage-4 layout,
capacity check, store rewrite, multi-wave ``lax.scan`` driver, and the
pipelined burst schedule — lives ONCE in
:class:`~.wave_engine.WaveEngine`; this module defines only what is
FIFO/LIFO-specific:

* :class:`FifoDiscipline` — positions from the min-plus hypercube scan
  (``core.scan_queue.sharded_queue_scan``), the shared dense-ring commit,
  and the post-enqueue-peak capacity check;
* :class:`LifoDiscipline` — positions/tickets from the max-plus stack
  scan over one packed descriptor ``all_gather``, plus the (slot, depth)
  ticket-set commit that makes concurrent pops conflict-free (each pop
  takes the unique max ticket <= its bound).

``run_waves`` executes K waves inside one device dispatch; with
``pipelined=True`` (default) wave k's dispatch overlaps wave k-1's store
rewrite and the request/reply collectives fuse to ONE ``all_to_all`` per
wave in steady state (see the engine docstring) — bit-identical results,
``pipelined=False`` keeps the sequential schedule for differential tests.

The seed five-collective Stage 4 is preserved as ``DeviceQueue(fused=
False)`` so benchmarks and differential tests can compare against it.
Payloads are fixed-width int32 vectors (token ids / request descriptors);
the serving engine keeps richer request metadata host-side keyed by payload.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from ..core.scan_queue import (QueueState, StackState, sharded_queue_scan,
                               stack_scan)
from ..kernels.backend import use_fused_dispatch
from .wave_engine import (TAG_GET, TAG_INACTIVE, TAG_PUT, Discipline,
                          Dispatch, WaveEngine, build_send, named,
                          post_enqueue_peak_overflow, program_name,
                          ring_commit)


class DeviceQueueState(NamedTuple):
    """FIFO queue state: replicated ``[first, last]`` live window plus the
    per-shard ring store (``store_vals`` ``[n_shards, cap+1, W]`` sharded,
    ``store_full`` occupancy bits; the extra slot is the junk row)."""

    first: jax.Array          # replicated int32
    last: jax.Array           # replicated int32
    store_vals: jax.Array     # [n_shards(sharded), cap+1, W] int32
    store_full: jax.Array     # [n_shards(sharded), cap+1] bool

    @property
    def size(self) -> jax.Array:
        """Live element count (``last - first + 1``), as a traced scalar."""
        return self.last - self.first + 1


# ------------------------------------------------------------ FIFO ---------
class FifoDiscipline(Discipline):
    """SKUEUE FIFO order: min-plus hypercube scan + dense-ring commit."""

    name = "fifo"
    n_ops = 3           # (is_enq, valid, payload)
    n_disp_outs = 2     # (pos, matched)

    def __init__(self, axis: str, n_shards: int, cap: int, W: int):
        self.axis = axis
        self.n_shards = n_shards
        self.cap = cap
        self.W = W
        self.junk = cap
        self.n_windows = 1
        self.window_capacity = n_shards * cap
        self.state_specs = DeviceQueueState(P(), P(), P(axis), P(axis))

    def split(self, state):
        """Split state into its (replicated carry, sharded store) halves."""
        return (state.first, state.last), (state.store_vals,
                                           state.store_full)

    def merge(self, carry, store):
        """Reassemble the full state from (carry, store) halves."""
        return DeviceQueueState(carry[0], carry[1], store[0], store[1])

    def dispatch(self, carry, ops) -> Dispatch:
        """Stages 1-3: assign positions and build the routed Dispatch."""
        is_enq, valid, payload = ops
        pos, matched, new_qs = sharded_queue_scan(
            is_enq, QueueState(carry[0], carry[1]), self.axis,
            valid_local=valid)
        owner = jnp.where(matched, pos % self.n_shards, -1).astype(jnp.int32)
        slot = jnp.where(matched, (pos // self.n_shards) % self.cap,
                         self.cap).astype(jnp.int32)
        tag = jnp.where(matched & is_enq, TAG_PUT,
                        jnp.where(matched & ~is_enq, TAG_GET, TAG_INACTIVE))
        ovf = post_enqueue_peak_overflow(carry[0], new_qs.last,
                                         self.n_shards * self.cap)
        return Dispatch(owner, slot, tag, (), payload, matched,
                        matched & ~is_enq, (pos, matched),
                        (new_qs.first, new_qs.last), ovf, ())

    def commit(self, store, recv):
        """Stage 4: apply this shard's routed requests to its store."""
        return ring_commit(store, recv, self.junk, self.W)

    def zero_outs(self, L: int) -> tuple:
        """All-invalid per-op dispatch outputs (padding waves)."""
        return (jnp.full((L,), -1, jnp.int32), jnp.zeros((L,), bool))

    def occupancy(self, carry):
        """Per-window occupancy vector from the carry (traced)."""
        return jnp.reshape(carry[1] - carry[0] + 1, (1,))


class DeviceQueue:
    """Distributed FIFO over one mesh axis.

    Args:
      mesh: jax Mesh; axis_name: the shard axis; cap: slots per shard;
      payload_width: int32 words per element; ops_per_shard: wave width L;
      fused: two-collective fused Stage 4 via the WaveEngine (default) vs.
        the five-collective seed path (kept for benchmarking and
        differential tests);
      pipelined: multi-wave bursts overlap wave k's dispatch with wave
        k-1's store rewrite (one fused all_to_all per wave); False keeps
        the sequential burst schedule.  Results are identical either way.
        Only meaningful with ``fused=True`` — the seed path is always
        sequential, and ``self.pipelined`` reports False there.
    """

    def __init__(self, mesh, axis_name: str = "data", cap: int = 1024,
                 payload_width: int = 4, ops_per_shard: int = 64,
                 fused: bool = True, pipelined: bool = True,
                 metrics: bool = False, metrics_ring: int = 64,
                 runtime=None):
        from ..runtime import as_runtime
        self.runtime, mesh, axis_name = as_runtime(mesh, axis_name,
                                                   runtime=runtime)
        self.mesh = mesh
        self.axis = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.cap = cap
        self.W = payload_width
        self.L = ops_per_shard
        self.fused = fused
        self.pipelined = pipelined and fused  # the seed path is sequential
        self.metrics = metrics
        self._state_specs = DeviceQueueState(P(), P(), P(self.axis),
                                             P(self.axis))
        if fused:
            self.engine = WaveEngine(
                mesh, axis_name,
                FifoDiscipline(axis_name, self.n_shards, cap, payload_width),
                pipelined=pipelined, metrics=metrics,
                metrics_ring=metrics_ring, runtime=self.runtime)
            self._step = self.engine._step
            self._run_waves = self.engine._run_waves
        else:
            if metrics:
                raise ValueError("Wavescope metrics need the fused engine "
                                 "path (fused=True)")
            self.engine = None
            self._step = self._build_legacy_step()
            self._run_waves = self._build_legacy_run_waves()

    def init_state(self) -> DeviceQueueState:
        """Freshly sharded empty state on this structure's mesh (placed
        through the runtime handle's data plane)."""
        n, cap, W = self.n_shards, self.cap, self.W
        put = self.runtime.put
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        rep = jax.sharding.NamedSharding(self.mesh, P())
        return DeviceQueueState(
            first=put(jnp.int32(0), rep),
            last=put(jnp.int32(-1), rep),
            store_vals=put(jnp.zeros((n, cap + 1, W), jnp.int32), sharding),
            store_full=put(jnp.zeros((n, cap + 1), bool), sharding),
        )

    # ------------------------------------------------------------ step -----
    def step(self, state: DeviceQueueState, is_enq: jax.Array,
             valid: jax.Array, payload: jax.Array):
        """Process one global batch.  The state argument is DONATED.

        is_enq/valid: [n_shards * L] bool; payload: [n_shards * L, W] int32.
        Returns (new_state, positions, matched, deq_vals, deq_ok, overflow).
        """
        if self.engine is not None:
            return self.engine.step(state, is_enq, valid, payload)
        return self._step(state, is_enq, valid, payload)

    def run_waves(self, state: DeviceQueueState, is_enq: jax.Array,
                  valid: jax.Array, payload: jax.Array):
        """Execute K pre-staged waves in ONE device dispatch (lax.scan).

        The state argument is DONATED.  is_enq/valid: [K, n_shards * L] bool;
        payload: [K, n_shards * L, W] int32.  Wave k's global order follows
        wave k-1's.  Returns (new_state, positions [K, n], matched [K, n],
        deq_vals [K, n, W], deq_ok [K, n], overflow [K]) with no host
        synchronization between waves.
        """
        if self.engine is not None:
            return self.engine.run_waves(state, is_enq, valid, payload)
        return self._run_waves(state, is_enq, valid, payload)

    def drain_metrics(self, *, reset: bool = False) -> list:
        """Burst-boundary Wavescope drain (empty when metrics are off)."""
        return self.engine.drain_metrics(reset=reset) if self.engine else []

    # ------------------------------------------- legacy five-collective ----
    def _legacy_wave(self, state: DeviceQueueState, is_enq, valid, payload):
        """The seed five-collective wave (benchmark/differential baseline)."""
        axis, n_shards, cap, W = self.axis, self.n_shards, self.cap, self.W
        qs = QueueState(state.first, state.last)
        pos, matched, new_qs = sharded_queue_scan(
            is_enq, qs, axis, valid_local=valid)
        owner = jnp.where(matched, pos % n_shards, -1).astype(jnp.int32)
        slot = jnp.where(matched, (pos // n_shards) % cap,
                         cap).astype(jnp.int32)

        # ---- stage 4a: PUT dispatch (enqueues) ----
        put_active = matched & is_enq
        send_slot = build_send(owner, slot, put_active, n_shards,
                               jnp.int32(cap))
        send_vals = build_send(owner, payload, put_active, n_shards,
                               jnp.int32(0))
        recv_slot = lax.all_to_all(send_slot, axis, 0, 0, tiled=True)
        recv_vals = lax.all_to_all(send_vals, axis, 0, 0, tiled=True)
        flat_slot = recv_slot.reshape(-1)
        flat_vals = recv_vals.reshape(-1, W)
        sv = state.store_vals[0]
        sf = state.store_full[0]
        sv = sv.at[flat_slot].set(flat_vals)     # cap row is the junk row
        sf = sf.at[flat_slot].set(True)
        sf = sf.at[cap].set(False)

        # ---- stage 4b: GET dispatch (dequeues) ----
        get_active = matched & (~is_enq)
        gsend = build_send(owner, slot, get_active, n_shards,
                           jnp.int32(cap))
        grecv = lax.all_to_all(gsend, axis, 0, 0, tiled=True)
        res_vals = sv[grecv]                      # [n_shards, L, W]
        res_ok = sf[grecv] & (grecv < cap)
        sf = sf.at[grecv.reshape(-1)].set(False)  # remove on read
        sf = sf.at[cap].set(False)
        back_vals = lax.all_to_all(res_vals, axis, 0, 0, tiled=True)
        back_ok = lax.all_to_all(res_ok, axis, 0, 0, tiled=True)
        j = jnp.arange(owner.shape[0])
        own_row = jnp.clip(owner, 0, n_shards - 1)
        deq_vals = jnp.where(get_active[:, None],
                             back_vals[own_row, j], jnp.int32(0))
        deq_ok = get_active & back_ok[own_row, j]

        overflow = post_enqueue_peak_overflow(state.first, new_qs.last,
                                              n_shards * cap)
        return (DeviceQueueState(new_qs.first, new_qs.last, sv[None],
                                 sf[None]),
                pos, matched, deq_vals, deq_ok, overflow)

    def _build_legacy_step(self):
        state_specs = self._state_specs

        def step(state, is_enq, valid, payload):
            return self._legacy_wave(state, is_enq, valid, payload)

        wrapped = shard_map(
            named(step, program_name("fifo", "legacy_step")), mesh=self.mesh,
            in_specs=(state_specs, P(self.axis), P(self.axis), P(self.axis)),
            out_specs=(state_specs, P(self.axis), P(self.axis), P(self.axis),
                       P(self.axis), P()))
        return jax.jit(wrapped, donate_argnums=(0,))

    def _build_legacy_run_waves(self):
        state_specs = self._state_specs

        def multi(state, is_enq, valid, payload):
            def wave(st, xs):
                e, v, p = xs
                st2, pos, matched, dv, dok, ovf = self._legacy_wave(
                    st, e, v, p)
                return st2, (pos, matched, dv, dok, ovf)
            st, (pos, matched, dv, dok, ovf) = lax.scan(
                wave, state, (is_enq, valid, payload))
            return st, pos, matched, dv, dok, ovf

        wrapped = shard_map(
            named(multi, program_name("fifo", "legacy_waves")),
            mesh=self.mesh,
            in_specs=(state_specs, P(None, self.axis), P(None, self.axis),
                      P(None, self.axis)),
            out_specs=(state_specs, P(None, self.axis), P(None, self.axis),
                       P(None, self.axis), P(None, self.axis), P(None)))
        return jax.jit(wrapped, donate_argnums=(0,))


# ------------------------------------------------------------ LIFO ---------
class LifoDiscipline(Discipline):
    """Stack order (paper Sec. VI): max-plus ticket scan + (slot, depth)
    ticket-set commit.

    Positions are reused, so each store slot keeps a small (ticket,
    payload) set of depth ``D``; the monotone ticket bound makes
    concurrent pops conflict-free (each pop takes the unique max ticket
    <= its bound)."""

    name = "lifo"
    n_ops = 3           # (is_push, valid, payload)
    n_disp_outs = 2     # (pos, matched)
    extra_fill = (-1,)  # the ticket/bound request column

    TAG_PUSH = TAG_PUT
    TAG_POP = TAG_GET

    def __init__(self, axis: str, n_shards: int, cap: int, W: int, D: int,
                 fused_dispatch: bool | None = None):
        self.axis = axis
        self.n_shards = n_shards
        self.cap = cap
        self.W = W
        self.D = D
        self.junk = cap
        self.n_windows = 1
        self.window_capacity = n_shards * cap * D
        # route the replicated max-plus scan through the compiled pallas
        # sweep on TPU/GPU; the jnp stack_scan stays the CPU path AND the
        # differential oracle (None = backend autodetect, PR 9)
        self.fused_dispatch = (use_fused_dispatch() if fused_dispatch is None
                               else bool(fused_dispatch))
        self.state_specs = {"last": P(), "ticket": P(), "vals": P(axis),
                            "ticks": P(axis)}

    def split(self, state):
        """Split state into its (replicated carry, sharded store) halves."""
        return (state["last"], state["ticket"]), (state["vals"],
                                                  state["ticks"])

    def merge(self, carry, store):
        """Reassemble the full state from (carry, store) halves."""
        return {"last": carry[0], "ticket": carry[1],
                "vals": store[0], "ticks": store[1]}

    def dispatch(self, carry, ops) -> Dispatch:
        """Stages 1-3: assign positions and build the routed Dispatch."""
        is_push, valid, payload = ops
        n_shards, cap = self.n_shards, self.cap
        # global order over shards: one packed descriptor all_gather, then
        # the replicated max-plus scan (its carries are 3 ints — cheap)
        code = (is_push.astype(jnp.int32) * 2 + valid.astype(jnp.int32))
        g = lax.all_gather(code, self.axis, tiled=True)
        if self.fused_dispatch:
            from ..kernels.segscan import stack_scan_pallas
            pos_g, tick_g, matched_g, nl, nt = stack_scan_pallas(
                (g & 2) > 0, (g & 1) > 0, carry[0], carry[1])
            new_ss = StackState(nl, nt)
        else:
            pos_g, tick_g, matched_g, new_ss = stack_scan(
                (g & 2) > 0, StackState(carry[0], carry[1]),
                valid=(g & 1) > 0)
        L = is_push.shape[0]
        i0 = lax.axis_index(self.axis) * L
        pos = lax.dynamic_slice_in_dim(pos_g, i0, L)
        tick = lax.dynamic_slice_in_dim(tick_g, i0, L)
        matched = lax.dynamic_slice_in_dim(matched_g, i0, L)

        owner = jnp.where(matched, pos % n_shards, -1).astype(jnp.int32)
        slot = jnp.where(matched, (pos // n_shards) % cap,
                         cap).astype(jnp.int32)
        tag = jnp.where(matched & is_push, self.TAG_PUSH,
                        jnp.where(matched & ~is_push, self.TAG_POP,
                                  TAG_INACTIVE))
        return Dispatch(owner, slot, tag, (tick,), payload, matched,
                        matched & ~is_push, (pos, matched),
                        (new_ss.last, new_ss.ticket),
                        jnp.zeros((), bool), ())   # capacity is commit-time

    def commit(self, store, recv):
        """Stage 4: apply this shard's routed requests to its store."""
        cap, W, D = self.cap, self.W, self.D
        sv = store[0][0]     # [cap+1, D, W]
        stk = store[1][0]    # [cap+1, D]
        r_all_slot, r_tb, r_tag = recv[..., 0], recv[..., 1], recv[..., 2]
        r_all_vals = recv[..., 3:]

        # ---- PUSH inserts ----
        is_push_r = r_tag == self.TAG_PUSH
        r_slot = jnp.where(is_push_r, r_all_slot, cap).reshape(-1)
        r_tick = jnp.where(is_push_r, r_tb, -1).reshape(-1)
        r_vals = r_all_vals.reshape(-1, W)
        # insert each arriving element into the first free depth entry
        # of its slot; arrivals to one slot in one step get distinct
        # entries via rank-within-slot.
        order = jnp.argsort(r_slot)  # group same-slot arrivals
        rs, rt, rv = r_slot[order], r_tick[order], r_vals[order]
        same = jnp.concatenate([jnp.array([False]), rs[1:] == rs[:-1]])
        idx = jnp.arange(rs.shape[0], dtype=jnp.int32)
        run_start = lax.associative_scan(
            jnp.maximum, jnp.where(same, -1, idx))
        rank = idx - run_start  # 0,1,2,... within each same-slot run
        free = (stk[rs] < 0).astype(jnp.int32)      # [Nr, D]
        base_free = jnp.cumsum(free, axis=1) - free  # rank of each free
        want = rank[:, None]
        pick = (stk[rs] < 0) & (base_free == want)
        depth_idx = jnp.argmax(pick, axis=1)
        ok_ins = pick.any(axis=1) & (rt >= 0) & (rs < cap)
        stk = stk.at[jnp.where(ok_ins, rs, cap),
                     jnp.where(ok_ins, depth_idx, D - 1)].set(
                         jnp.where(ok_ins, rt, stk[cap, D - 1]))
        sv = sv.at[jnp.where(ok_ins, rs, cap),
                   jnp.where(ok_ins, depth_idx, D - 1)].set(
                       jnp.where(ok_ins[:, None], rv, sv[cap, D - 1]))
        slot_overflow = ((rt >= 0) & (rs < cap) & ~ok_ins).any()
        slot_overflow = lax.pmax(slot_overflow.astype(jnp.int32),
                                 self.axis) > 0  # replicated flag

        # ---- POP picks: take max ticket <= bound at the slot ----
        is_pop_r = r_tag == self.TAG_POP
        q_slot = jnp.where(is_pop_r, r_all_slot, cap)        # [n, L]
        q_bound = jnp.where(is_pop_r, r_tb, -1)
        cand = stk[q_slot]                                   # [n,L,D]
        eligible = (cand >= 0) & (cand <= q_bound[..., None])
        best = jnp.where(eligible, cand, -1).max(axis=-1)    # [n,L]
        got = best >= 0
        d_pick = jnp.argmax(jnp.where(eligible, cand, -1), axis=-1)
        res_vals = sv[q_slot, d_pick]
        # remove the picked entries (unique per pop: tickets are unique)
        stk = stk.at[jnp.where(got, q_slot, cap),
                     jnp.where(got, d_pick, D - 1)].set(
                         jnp.where(got, -1, stk[cap, D - 1]))
        reply = jnp.concatenate(
            [got.astype(jnp.int32)[..., None], res_vals], axis=-1)
        return (sv[None], stk[None]), reply, slot_overflow

    def zero_outs(self, L: int) -> tuple:
        """All-invalid per-op dispatch outputs (padding waves)."""
        return (jnp.full((L,), -1, jnp.int32), jnp.zeros((L,), bool))

    def occupancy(self, carry):
        """Per-window occupancy vector from the carry (traced)."""
        # stack positions start at 1: the live window is [1, last]
        return jnp.reshape(carry[0], (1,))


class DeviceStack:
    """Distributed LIFO (paper Sec. VI) over one mesh axis.

    Stage 4 uses the same fused two-collective layout as
    :class:`DeviceQueue` (request packs ``slot ‖ ticket/bound ‖ tag ‖
    payload``; reply packs ``ok ‖ value``) via the shared WaveEngine, and
    the jitted entry points donate the stack state.  ``run_waves`` is the
    engine's multi-wave driver — pipelined by default.
    """

    TAG_PUSH = LifoDiscipline.TAG_PUSH
    TAG_POP = LifoDiscipline.TAG_POP

    def __init__(self, mesh, axis_name: str = "data", cap: int = 1024,
                 payload_width: int = 4, ops_per_shard: int = 64,
                 slot_depth: int = 4, pipelined: bool = True,
                 metrics: bool = False, metrics_ring: int = 64,
                 fused_dispatch: bool | None = None, runtime=None):
        from ..runtime import as_runtime
        self.runtime, mesh, axis_name = as_runtime(mesh, axis_name,
                                                   runtime=runtime)
        self.mesh = mesh
        self.axis = axis_name
        self.n_shards = mesh.shape[axis_name]
        self.cap = cap
        self.W = payload_width
        self.L = ops_per_shard
        self.D = slot_depth
        self.pipelined = pipelined
        self.metrics = metrics
        self.engine = WaveEngine(
            mesh, axis_name,
            LifoDiscipline(axis_name, self.n_shards, cap, payload_width,
                           slot_depth, fused_dispatch=fused_dispatch),
            pipelined=pipelined, metrics=metrics, metrics_ring=metrics_ring,
            runtime=self.runtime)
        self._step = self.engine._step
        self._run_waves = self.engine._run_waves

    def init_state(self):
        """Freshly sharded empty state on this structure's mesh (placed
        through the runtime handle's data plane)."""
        n, cap, W, D = self.n_shards, self.cap, self.W, self.D
        put = self.runtime.put
        sharding = jax.sharding.NamedSharding(self.mesh, P(self.axis))
        rep = jax.sharding.NamedSharding(self.mesh, P())
        return {
            "last": put(jnp.int32(0), rep),
            "ticket": put(jnp.int32(0), rep),
            "vals": put(jnp.zeros((n, cap + 1, D, W), jnp.int32), sharding),
            "ticks": put(jnp.full((n, cap + 1, D), -1, jnp.int32), sharding),
        }

    def step(self, state, is_push, valid, payload):
        """One wave; the state argument is DONATED."""
        return self.engine.step(state, is_push, valid, payload)

    def run_waves(self, state, is_push, valid, payload):
        """K pushes/pops waves in one lax.scan dispatch (state DONATED)."""
        return self.engine.run_waves(state, is_push, valid, payload)

    def drain_metrics(self, *, reset: bool = False) -> list:
        """Burst-boundary Wavescope drain (empty when metrics are off)."""
        return self.engine.drain_metrics(reset=reset)
