"""PR 4 unified WaveEngine: pipelined-vs-sequential differential equality,
the HLO collective matrix for all three disciplines, the ONE shared
post-enqueue-peak overflow check, and a hypothesis property test driving
random mixed op/JOIN/LEAVE schedules through every discipline against its
host oracle."""
import numpy as np

from _hyp import given, settings, strategies as st
from multidev import run_multidev

# --------------------------------------------------------------------------
# Acceptance: pipelined == sequential == step loop, op-by-op, all three
# disciplines, on 8 devices.
# --------------------------------------------------------------------------
PIPELINED_DIFFERENTIAL = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.dqueue import DeviceQueue, DeviceStack, DevicePriorityQueue

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(29)
K, L = 7, 8
n = 8 * L

CASES = [
    ("queue", lambda p: DeviceQueue(mesh, "data", cap=64, payload_width=2,
                                    ops_per_shard=L, pipelined=p), False),
    ("stack", lambda p: DeviceStack(mesh, "data", cap=64, payload_width=2,
                                    ops_per_shard=L, slot_depth=8,
                                    pipelined=p), False),
    ("pqueue", lambda p: DevicePriorityQueue(
        mesh, "data", n_prios=3, cap=64, payload_width=2, ops_per_shard=L,
        pipelined=p), True),
]
for name, make, has_prio in CASES:
    seq, pipe = make(False), make(True)
    E = rng.random((K, n)) < 0.6
    V = rng.random((K, n)) < 0.9
    PW = rng.integers(0, 999, (K, n, 2)).astype(np.int32)
    args = [jnp.array(E), jnp.array(V)]
    if has_prio:
        args.append(jnp.array(rng.integers(0, 3, (K, n)), jnp.int32))
    args.append(jnp.array(PW))
    # reference: K host-driven sequential single waves
    st_ref = seq.init_state()
    ref = []
    for k in range(K):
        st_ref, *o = seq.step(st_ref, *(a[k] for a in args))
        ref.append([np.asarray(x) for x in o])
    for mode, q in (("sequential", seq), ("pipelined", pipe)):
        sa, *oa = q.run_waves(q.init_state(), *args)
        oa = [np.asarray(x) for x in oa]
        for k in range(K):
            for a, b in zip(oa, ref[k]):
                assert (a[k] == b).all(), (name, mode, k)
        fa = jax.tree.leaves(sa)
        fb = jax.tree.leaves(st_ref)
        for a, b in zip(fa, fb):
            assert (np.asarray(a) == np.asarray(b)).all(), (name, mode)
    print("OK", name, "pipelined == sequential == step loop")
"""


def test_pipelined_matches_sequential_all_disciplines_8dev():
    """Acceptance: the software-pipelined burst schedule is bit-identical
    to the sequential one (and to K host-driven steps) for the FIFO, LIFO
    and priority disciplines — outputs AND final state."""
    out = run_multidev(PIPELINED_DIFFERENTIAL, n_dev=8)
    for name in ("queue", "stack", "pqueue"):
        assert f"OK {name} pipelined == sequential == step loop" in out


# --------------------------------------------------------------------------
# CI satellite: the HLO collective matrix.  The pipelined K-wave program
# must keep <= 2 all_to_all per wave for queue, stack AND priority — it
# actually has ONE in the scan body (fused request_k ‖ reply_{k-1}) plus a
# single drain epilogue, i.e. 2 static / (K+1)/K per wave amortized.
# --------------------------------------------------------------------------
HLO_MATRIX = r"""
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.dqueue import DeviceQueue, DeviceStack, DevicePriorityQueue
from repro.analysis import count_all_to_all

mesh = make_mesh((8,), ("data",))
K, L = 6, 4
n = 8 * L
for name, make, has_prio in (
    ("queue", lambda p: DeviceQueue(mesh, "data", cap=32, payload_width=2,
                                    ops_per_shard=L, pipelined=p), False),
    ("stack", lambda p: DeviceStack(mesh, "data", cap=32, payload_width=2,
                                    ops_per_shard=L, pipelined=p), False),
    ("priority", lambda p: DevicePriorityQueue(
        mesh, "data", n_prios=2, cap=32, payload_width=2, ops_per_shard=L,
        pipelined=p), True),
):
    seq, pipe = make(False), make(True)
    for tag, q in (("seq", seq), ("pipe", pipe)):
        args = [q.init_state(), jnp.zeros((K, n), bool),
                jnp.zeros((K, n), bool)]
        if has_prio:
            args.append(jnp.zeros((K, n), jnp.int32))
        args.append(jnp.zeros((K, n, 2), jnp.int32))
        c = count_all_to_all(q._run_waves, tuple(args))
        if tag == "seq":
            # sequential scan body: request + reply = 2 per wave
            assert c == 2, f"{name} sequential run_waves has {c}"
        else:
            # pipelined: ONE fused a2a in the body + one drain epilogue;
            # the per-wave bound <= 2 holds with room to spare
            assert c <= 2, f"{name} pipelined run_waves has {c}"
        print(f"OK hlo {name} {tag}: {c}")
"""


def test_pipelined_hlo_collective_matrix_8dev():
    """Satellite: the pipelined path keeps <= 2 all_to_all per wave for
    queue, stack, AND priority (static count: 1 fused collective in the
    scan body + 1 drain epilogue for the whole burst)."""
    out = run_multidev(HLO_MATRIX, n_dev=8)
    for name in ("queue", "stack", "priority"):
        assert f"OK hlo {name} seq: 2" in out
        assert f"OK hlo {name} pipe:" in out


# --------------------------------------------------------------------------
# Satellite: THE post-enqueue-peak overflow check lives once in
# wave_engine.post_enqueue_peak_overflow (it was patched three times in
# PR 3: fused queue, legacy queue, priority queue).  One regression test
# covers overflow surfacing for all three disciplines through the engine.
# --------------------------------------------------------------------------
def test_overflow_surfaces_once_for_all_disciplines():
    """With a queue/tier at exact capacity, a same-wave enq+deq transiently
    exceeds the store (PUTs apply before GETs), so the flag must check the
    post-enqueue peak, not the post-wave size — for the fused FIFO wave,
    the legacy five-collective wave, and the priority wave alike.  The
    stack's capacity hazard is commit-time (depth exhaustion) and must
    surface through the same per-wave overflow output."""
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.dqueue import DevicePriorityQueue, DeviceQueue, DeviceStack

    mesh = make_mesh((1,), ("data",))
    one = jnp.ones((4, 1), jnp.int32)
    fill = jnp.array([True, True, False, False])
    e = jnp.array([True, False, False, False])
    v = jnp.array([True, True, False, False])  # 1 enq + 1 deq: peak = 3

    for fused in (True, False):                # engine AND legacy paths
        dq = DeviceQueue(mesh, "data", cap=2, payload_width=1,
                         ops_per_shard=4, fused=fused)
        st = dq.init_state()
        st, _, _, _, _, ovf = dq.step(st, fill, fill, one)
        assert not bool(ovf), fused            # 2 live == capacity: fine
        st, _, _, _, _, ovf = dq.step(st, e, v, one)
        assert bool(ovf), ("post-enqueue peak went undetected", fused)

    pq = DevicePriorityQueue(mesh, "data", n_prios=2, cap=2,
                             payload_width=1, ops_per_shard=4)
    ps = pq.init_state()
    tier1 = jnp.ones((4,), jnp.int32)
    ps, *_, ovf, _ = pq.step(ps, fill, fill, tier1, one)
    assert not bool(ovf)
    ps, *_, ovf, _ = pq.step(ps, e, v, tier1, one)
    assert bool(ovf), "tier-level post-enqueue peak went undetected"

    # stack: two pushes fill cap=1 x depth=2; a third push has no free
    # depth entry -> the commit-time slot overflow must surface
    ds = DeviceStack(mesh, "data", cap=1, payload_width=1, ops_per_shard=4,
                     slot_depth=2)
    ss = ds.init_state()
    ss, *_, ovf = ds.step(ss, fill, fill, one)
    assert not bool(ovf)
    ss, *_, ovf = ds.step(ss, e, e, one)       # third push: depth exhausted
    assert bool(ovf), "stack depth exhaustion went undetected"


# --------------------------------------------------------------------------
# Satellite: hypothesis property test — a random mixed op/JOIN/LEAVE
# schedule through the unified engine, all three disciplines, against the
# host oracles (Skueue protocol sim for FIFO/LIFO order through membership
# changes, PriorityOracle for the tier semantics).
# --------------------------------------------------------------------------
PROPERTY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.protocol import DEQ, ENQ, Skueue
from repro.core.priority import DEQ as PDEQ, ENQ as PENQ, PriorityOracle
from repro.core.seap import DEQ as SDEQ, ENQ as SENQ, SeapOracle
from repro.dqueue import (ElasticDeviceQueue, ElasticDeviceStack,
                          ElasticDevicePriorityQueue, ElasticDeviceSeapQueue)

OPS = %(ops)r
PRIOS = %(prios)r
KEYS = %(keys)r
SCHEDULE = %(schedule)r
P_ = %(n_prios)d
RELAX = %(relax)d
L = 4
B_ = 4
SPLIT_OCC = 6


def run_device(elastic, W, codes=None):
    outs = []
    cut = sorted(SCHEDULE) + [len(OPS)]
    start = 0
    for end in cut:
        chunk = OPS[start:end]
        if chunk:
            n = elastic.n_shards * elastic.L
            K = -(-len(chunk) // n)
            E = np.zeros((K, n), bool)
            V = np.zeros((K, n), bool)
            PR = np.zeros((K, n), np.int32)
            PW = np.zeros((K, n, W), np.int32)
            for j, op in enumerate(chunk):
                k, i = divmod(j, n)
                E[k, i] = bool(op)
                V[k, i] = True
                if codes is not None:
                    PR[k, i] = codes[start + j]
                PW[k, i, 0] = start + j
            if codes is not None:
                tier, pos, m, dv, dok, ovf, _ = elastic.run_waves(E, V, PR,
                                                                  PW)
            else:
                pos, m, dv, dok, ovf = elastic.run_waves(E, V, PW)
                tier = pos
            assert not np.asarray(ovf).any()
            pos = np.asarray(pos).reshape(-1)[:len(chunk)]
            m = np.asarray(m).reshape(-1)[:len(chunk)]
            tier = np.asarray(tier).reshape(-1)[:len(chunk)]
            dv = np.asarray(dv).reshape(K * n, W)[:len(chunk)]
            dok = np.asarray(dok).reshape(-1)[:len(chunk)]
            for j, op in enumerate(chunk):
                res = None
                if (not op) and m[j]:
                    assert dok[j], f"matched op {start + j} lost its element"
                    res = int(dv[j, 0])
                outs.append((int(pos[j]), bool(m[j]), res, int(tier[j])))
        if end in SCHEDULE:
            kind, arg = SCHEDULE[end]
            s = (elastic.grow(arg) if kind == "grow"
                 else elastic.shrink(arg))
            assert s["moved"] == elastic.size, (s, elastic.size)
        start = end
    return outs


def run_protocol(mode):
    sk = Skueue(4, mode=mode, seed=0, local_combining=False)
    nid = sk.ring.node_ids()[0]
    rids = []

    def inject(s, rnd):
        i = rnd - 1
        if i < len(OPS):
            rids.append(s.inject(nid, ENQ if OPS[i] else DEQ))
        if i in SCHEDULE:
            kind, arg = SCHEDULE[i]
            if kind == "grow":
                for _ in range(arg):
                    s.request_join()
            else:
                keep = s.ring.proc[nid]
                alive = sorted({s.ring.proc[v] for v in s.ring.node_ids()})
                for pid in [p for p in alive if p != keep][:len(arg)]:
                    s.request_leave(pid)

    sk.run_rounds(len(OPS) + 80, inject_fn=inject)
    assert all(sk.requests[r].done for r in rids)
    return [(sk.requests[r].pos if sk.requests[r].pos is not None else -1,
             not (sk.requests[r].kind == DEQ
                  and sk.requests[r].result == -1),
             sk.requests[r].result
             if sk.requests[r].kind == DEQ and sk.requests[r].result != -1
             else None)
            for r in rids]


# ---- FIFO and LIFO vs the Skueue protocol sim through JOIN/LEAVE ----
for mode, cls, kw in (("queue", ElasticDeviceQueue, {}),
                      ("stack", ElasticDeviceStack, {"slot_depth": 8})):
    eq = cls(4, cap=32, payload_width=2, ops_per_shard=L, **kw)
    dev = run_device(eq, 2)
    ref = run_protocol(mode)
    assert [d[0] for d in dev] == [r[0] for r in ref], f"{mode} positions"
    assert [d[1] for d in dev] == [r[1] for r in ref], f"{mode} matched"
    assert [d[2] for d in dev] == [r[2] for r in ref], f"{mode} results"
    print(f"OK property {mode}")

# ---- priority vs the host P-tier oracle (membership-oblivious) ----
eq = ElasticDevicePriorityQueue(4, n_prios=P_, relaxation=RELAX, cap=32,
                                payload_width=2, ops_per_shard=L)
dev = run_device(eq, 2, codes=PRIOS)
# replay the SAME wave partitioning run_device used (the shard count at
# the time each chunk ran) through the membership-oblivious oracle
cut = sorted(SCHEDULE) + [len(OPS)]
oracle = PriorityOracle(P_, relaxation=RELAX)
recs = []
start = 0
shards = 4
for end in cut:
    chunk = OPS[start:end]
    if chunk:
        n = shards * L
        K = -(-len(chunk) // n)
        for k in range(K):
            wave = []
            for i in range(n):
                j = k * n + i
                if j >= len(chunk):
                    wave.append(None)
                elif chunk[j]:
                    wave.append((PENQ, PRIOS[start + j], start + j, i // L))
                else:
                    wave.append((PDEQ, 0, None, i // L))
            recs.extend(r for r in oracle.wave(wave, n_shards=shards)
                        [:len(chunk) - k * n])
    if end in SCHEDULE:
        kind, arg = SCHEDULE[end]
        shards += arg if kind == "grow" else -len(arg)
    start = end
assert len(recs) == len(dev) == len(OPS)
for j, (d, r) in enumerate(zip(dev, recs)):
    assert d[1] == r.matched, ("pqueue matched", j)
    assert d[0] == r.pos, ("pqueue pos", j)
    if r.matched:
        assert d[3] == r.tier, ("pqueue tier", j)
    if r.matched and r.value is not None:
        assert d[2] == r.value, ("pqueue value", j)
assert eq.sizes == oracle.sizes
print("OK property pqueue")

# ---- seap (arbitrary keys) vs the host bucket-directory oracle ----
eq = ElasticDeviceSeapQueue(4, n_buckets=B_, split_occupancy=SPLIT_OCC,
                            cap=32, payload_width=2, ops_per_shard=L)
dev = run_device(eq, 2, codes=KEYS)
cut = sorted(SCHEDULE) + [len(OPS)]
oracle = SeapOracle(B_, split_occupancy=SPLIT_OCC)
recs = []
start = 0
shards = 4
for end in cut:
    chunk = OPS[start:end]
    if chunk:
        n = shards * L
        K = -(-len(chunk) // n)
        for k in range(K):
            wave = []
            for i in range(n):
                j = k * n + i
                if j >= len(chunk):
                    wave.append(None)
                elif chunk[j]:
                    wave.append((SENQ, KEYS[start + j], start + j))
                else:
                    wave.append((SDEQ, 0, None))
            recs.extend(oracle.wave(wave)[:len(chunk) - k * n])
    if end in SCHEDULE:
        kind, arg = SCHEDULE[end]
        shards += arg if kind == "grow" else -len(arg)
    start = end
assert len(recs) == len(dev) == len(OPS)
for j, (d, r) in enumerate(zip(dev, recs)):
    assert d[1] == r.matched, ("seap matched", j)
    assert d[0] == r.pos, ("seap pos", j)
    if r.matched:
        assert d[3] == r.bucket, ("seap bucket", j)
    if r.matched and r.value is not None:
        assert d[2] == r.value, ("seap value", j)
assert eq.sizes == oracle.sizes
assert eq.directory() == oracle.directory()
print("OK property seap")
"""


@settings(max_examples=2, deadline=None)
@given(st.lists(st.booleans(), min_size=16, max_size=40),
       st.integers(0, 2 ** 31 - 1), st.integers(0, 2), st.integers(0, 1))
def test_random_mixed_membership_schedule_matches_oracles_8dev(
        ops, seed, n_events, relax):
    """Satellite property test: a randomized mixed enq/deq trace with a
    randomized JOIN/LEAVE schedule produces, through the unified engine,
    exactly the host oracles' positions, ⊥ sets, results, tiers and
    buckets — for all FOUR disciplines on 8 devices (PR 5 adds the Seap
    arbitrary-key discipline against its bucket-directory oracle)."""
    rng = np.random.default_rng(seed)
    n_prios = int(rng.integers(2, 4))
    prios = [int(p) for p in rng.integers(0, n_prios, len(ops))]
    keys = [int(k) for k in rng.integers(-1000, 1000, len(ops))]
    schedule = {}
    shards = 4
    for idx in sorted(rng.choice(np.arange(1, max(2, len(ops))),
                                 size=n_events, replace=False).tolist()):
        if rng.random() < 0.5 and shards <= 6:
            k = int(rng.integers(1, min(2, 8 - shards) + 1))
            schedule[int(idx)] = ("grow", k)
            shards += k
        elif shards >= 3:
            m = int(rng.integers(1, min(2, shards - 2) + 1))
            ids = sorted(rng.choice(np.arange(shards), size=m,
                                    replace=False).tolist())
            schedule[int(idx)] = ("shrink", [int(i) for i in ids])
            shards -= m
    script = PROPERTY % {"ops": [bool(o) for o in ops], "prios": prios,
                         "keys": keys, "schedule": schedule,
                         "n_prios": n_prios, "relax": int(relax)}
    out = run_multidev(script, n_dev=8)
    assert "OK property queue" in out
    assert "OK property stack" in out
    assert "OK property pqueue" in out
    assert "OK property seap" in out


# --------------------------------------------------------------------------
# PR 7 Wavescope: telemetry-on legs of the HLO matrix.  Metrics must add
# ZERO collectives (static a2a count identical on vs off for step,
# sequential burst AND pipelined burst, all four disciplines) and must not
# perturb results (outputs and final state bit-identical on vs off).
# --------------------------------------------------------------------------
TELEMETRY_MATRIX = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.dqueue import (DeviceQueue, DeviceStack, DevicePriorityQueue,
                          DeviceSeapQueue)
from repro.analysis import count_all_to_all

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(5)
K, L = 5, 4
n = 8 * L

CASES = [
    ("queue", lambda p, m: DeviceQueue(
        mesh, "data", cap=32, payload_width=2, ops_per_shard=L,
        pipelined=p, metrics=m), 0),
    ("stack", lambda p, m: DeviceStack(
        mesh, "data", cap=32, payload_width=2, ops_per_shard=L,
        slot_depth=8, pipelined=p, metrics=m), 0),
    ("priority", lambda p, m: DevicePriorityQueue(
        mesh, "data", n_prios=2, cap=32, payload_width=2, ops_per_shard=L,
        pipelined=p, metrics=m), 2),
    ("seap", lambda p, m: DeviceSeapQueue(
        mesh, "data", n_buckets=4, cap=32, payload_width=2,
        ops_per_shard=L, pipelined=p, metrics=m), 50),
]
for name, make, kmax in CASES:
    E = rng.random((K, n)) < 0.6
    V = rng.random((K, n)) < 0.9
    args = [jnp.array(E), jnp.array(V)]
    if kmax:
        args.append(jnp.array(rng.integers(0, kmax, (K, n)), jnp.int32))
    args.append(jnp.array(rng.integers(0, 999, (K, n, 2)), jnp.int32))
    step_args = tuple(a[0] for a in args)
    args = tuple(args)

    # --- static collective counts: telemetry adds ZERO, all three modes
    q_off, q_on = make(True, False), make(True, True)
    c_off = count_all_to_all(q_off._step, (q_off.init_state(),) + step_args)
    c_on = count_all_to_all(
        q_on._step,
        ((q_on.init_state(), q_on.engine.init_metrics_state()),)
        + step_args)
    assert c_on == c_off == 2, (name, "step", c_off, c_on)
    print(f"OK obs-hlo {name} step: off={c_off} on={c_on}")
    for tag, pipe in (("seq", False), ("pipe", True)):
        q_off, q_on = make(pipe, False), make(pipe, True)
        c_off = count_all_to_all(q_off._run_waves,
                                 (q_off.init_state(),) + args)
        c_on = count_all_to_all(
            q_on._run_waves,
            ((q_on.init_state(), q_on.engine.init_metrics_state()),) + args)
        assert c_on == c_off <= 2, (name, tag, c_off, c_on)
        print(f"OK obs-hlo {name} {tag}: off={c_off} on={c_on}")

    # --- bit-identity: metrics-on run == metrics-off run (outputs AND
    #     final state), pipelined burst
    q_off, q_on = make(True, False), make(True, True)
    s_off, *o_off = q_off.run_waves(q_off.init_state(), *args)
    s_on, *o_on = q_on.run_waves(q_on.init_state(), *args)
    for a, b in zip(o_off, o_on):
        assert (np.asarray(a) == np.asarray(b)).all(), name
    for a, b in zip(jax.tree.leaves(s_off), jax.tree.leaves(s_on)):
        assert (np.asarray(a) == np.asarray(b)).all(), name
    rows = q_on.drain_metrics()
    assert len(rows) == K, (name, len(rows))
    assert [r["seq"] for r in rows] == list(range(K)), name
    occ_w = {"queue": 1, "stack": 1, "priority": 2, "seap": 4}[name]
    assert all(len(r["occ"]) == occ_w for r in rows), name
    print(f"OK obs-id {name}: outputs+state bit-identical, {len(rows)} rows")
"""


def test_telemetry_hlo_matrix_and_bit_identity_8dev():
    """PR 7 acceptance: Wavescope metrics keep the collective budget
    (all_to_all count identical with telemetry on vs off for step /
    sequential burst / pipelined burst, all four disciplines) and results
    are bit-identical with telemetry on vs off."""
    out = run_multidev(TELEMETRY_MATRIX, n_dev=8, timeout=900)
    for name in ("queue", "stack", "priority", "seap"):
        assert f"OK obs-hlo {name} step: off=2 on=2" in out
        assert f"OK obs-hlo {name} seq: off=2 on=2" in out
        assert f"OK obs-hlo {name} pipe:" in out
        assert f"OK obs-id {name}" in out


# --------------------------------------------------------------------------
# PR 7 Wavescope: the flight recorder attaches the occupancy trajectory to
# QueueOverflowError, and the trajectory is consistent with a host replay
# of its own puts/gets counters.
# --------------------------------------------------------------------------
def test_flight_recorder_trajectory_on_overflow():
    """Drive an elastic FIFO with telemetry into a deliberate overflow:
    the raised QueueOverflowError must carry the last-K wave summaries,
    whose occupancies replay exactly from the recorded puts/gets."""
    import numpy as np
    import pytest
    from repro.dqueue import ElasticDeviceQueue, QueueOverflowError

    q = ElasticDeviceQueue(1, cap=8, payload_width=1, ops_per_shard=4,
                           metrics=True)
    # each wave: 3 puts + 1 get = net +2; with per-window capacity 8 the
    # post-enqueue peak first exceeds capacity on wave 3 (6 live + 3 puts)
    is_enq = np.array([True, True, True, False])
    valid = np.ones(4, bool)
    payload = np.arange(4, dtype=np.int32).reshape(4, 1)
    with pytest.raises(QueueOverflowError) as ei:
        for _ in range(10):
            q.step(is_enq, valid, payload)
    err = ei.value
    assert err.trajectory, "overflow must carry the flight recorder"
    assert err.trajectory == q.trajectory()
    assert "flight recorder" in str(err)
    # host replay: occupancy must integrate the recorded puts - gets
    occ = 0
    for r in err.trajectory:
        occ += r["puts"] - r["gets"]
        assert r["occ"] == [occ], err.trajectory
        assert r["headroom"] == 8 - occ
    # the failing wave is the last summary, already past capacity's edge
    assert occ + 3 > 8 or occ > 8


# --------------------------------------------------------------------------
# Stable program names and wave-phase scopes: every program compiles as
# jit_skueue_<discipline>_<entry>, and every scatter and gather of a wave
# sits under one of the phase scopes a device trace is split by.
# --------------------------------------------------------------------------
import pytest  # noqa: E402


KINDS = ["fifo", "lifo", "prio", "seap"]


def _lowered_wave_programs(kind, entries=("waves", "step")):
    """(entry, opts, HLO text with op_name metadata) of ``kind``'s jitted
    wave programs at toy size on one shard: the pipelined, sequential and
    metrics-on builds of each entry."""
    import jax
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.dqueue import (DevicePriorityQueue, DeviceQueue,
                              DeviceSeapQueue, DeviceStack)

    mesh = make_mesh((1,), ("data",))
    kw = dict(cap=16, payload_width=2, ops_per_shard=4)
    make = {"fifo": lambda **k: DeviceQueue(mesh, "data", **kw, **k),
            "lifo": lambda **k: DeviceStack(mesh, "data", **kw, **k),
            "prio": lambda **k: DevicePriorityQueue(mesh, "data", n_prios=2,
                                                    **kw, **k),
            "seap": lambda **k: DeviceSeapQueue(mesh, "data", n_buckets=4,
                                                **kw, **k)}[kind]
    K, n = 3, 4
    for opts in ({}, {"pipelined": False}, {"metrics": True}):
        q = make(**opts)
        eng = q.engine
        state = jax.eval_shape(q.init_state)
        if eng.metrics:
            state = (state, jax.eval_shape(lambda: eng._mstate))
        ops = [jax.ShapeDtypeStruct((K, n), jnp.bool_)] * 2
        if eng.disc.n_ops == 4:
            ops.append(jax.ShapeDtypeStruct((K, n), jnp.int32))
        ops.append(jax.ShapeDtypeStruct((K, n, 2), jnp.int32))
        one = [jax.ShapeDtypeStruct(o.shape[1:], o.dtype) for o in ops]
        for entry, prog, args in (("waves", eng._run_waves, ops),
                                  ("step", eng._step, one)):
            if entry in entries:
                yield entry, opts, prog.lower(state, *args).as_text(
                    dialect="hlo", debug_info=True)


@pytest.mark.parametrize("kind", KINDS)
def test_programs_are_named_and_every_scatter_gather_is_phased(kind):
    import re
    from repro.analysis.hlo import parse_hlo
    from repro.dqueue.wave_engine import WAVE_PHASES

    for entry, opts, text in _lowered_wave_programs(kind):
        assert text.startswith(f"HloModule jit_skueue_{kind}_{entry},")
        lines = text.splitlines()
        moved = [op for op in parse_hlo(text).ops
                 if op.opcode in ("scatter", "gather")]
        assert moved, (kind, entry)
        for op in moved:
            meta = re.search(r'op_name="([^"]*)"', lines[op.line_no - 1])
            assert meta and set(meta.group(1).split("/")) & set(
                WAVE_PHASES), (kind, entry, opts, op, meta)


# The phases the FIFO and LIFO waves had before the priority dispatch
# split its own into ``tier_scan`` and ``deletemin``.
BASE_PHASES = ("dispatch", "pack", "exchange", "commit", "reply", "merge",
               "telemetry")


@pytest.mark.parametrize("kind", ["fifo", "lifo"])
def test_fifo_and_lifo_phase_tables_are_unchanged(kind):
    from repro.analysis.hlo import scope_table
    from repro.dqueue.wave_engine import WAVE_PHASES

    for entry, opts, text in _lowered_wave_programs(kind):
        table = scope_table(text, WAVE_PHASES)
        assert table == scope_table(text, BASE_PHASES), (entry, opts)
        assert not {"tier_scan", "deletemin"} & set(table.values())


def test_priority_programs_phase_tier_scan_and_deletemin():
    """Inside the priority dispatch, the per-tier enqueue scan and the
    in-wave batch-DeleteMin are phases of their own; the rest of the
    dispatch stays ``dispatch``."""
    from repro.analysis.hlo import scope_table
    from repro.dqueue.wave_engine import WAVE_PHASES

    for entry, opts, text in _lowered_wave_programs("prio"):
        phases = set(scope_table(text, WAVE_PHASES).values())
        assert {"dispatch", "tier_scan", "deletemin", "commit",
                "reply"} <= phases, (entry, opts, phases)


def test_elastic_priority_queue_gives_its_programs_texts_and_tables(
        monkeypatch):
    """``wave_programs`` and ``wave_phases`` read one compiled program per
    dispatched width, with the fused sweep (interpret mode) as on a TPU,
    and compile nothing."""
    from repro.analysis import CompilationTracker
    from repro.dqueue import ElasticDevicePriorityQueue, priority_queue

    monkeypatch.setattr(priority_queue, "use_fused_dispatch", lambda: True)
    q = ElasticDevicePriorityQueue(1, n_prios=4, cap=64, payload_width=8,
                                   ops_per_shard=8)
    assert q.inner.engine.disc.fused_dispatch
    rng = np.random.default_rng(0)
    for w in (4, 8):
        e = rng.random((2, w)) < 0.6
        q.run_waves(e, np.ones((2, w), bool),
                    rng.integers(0, 4, (2, w)).astype(np.int32),
                    np.zeros((2, w, 8), np.int32))
    with CompilationTracker() as t:
        programs, tables = q.wave_programs(), q.wave_phases()
    assert t.count == 0
    assert len(programs) == len(tables) == 2
    assert sorted(key[1] for key, _ in programs) == [(2, 4), (2, 8)]
    for table in tables:
        assert {"tier_scan", "deletemin"} <= set(table.values())


# --------------------------------------------------------------------------
# Reply extraction: each op's reply row is selected from the [n, L, 1+W]
# reply buffer with a mask over the n rows, never gathered per op (XLA:TPU
# lowers that gather to a loop of L row slices a wave).
# --------------------------------------------------------------------------
@pytest.mark.parametrize("W", [1, 256])
@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_extract_reply_is_bit_identical_to_the_row_gather(n, W):
    from types import SimpleNamespace
    import jax
    import jax.numpy as jnp
    from repro.analysis.hlo import count_op
    from repro.dqueue.wave_engine import WaveEngine

    L = 64
    rng = np.random.default_rng(1000 * n + W)
    back = rng.integers(-2**31, 2**31, (n, L, 1 + W), dtype=np.int64)
    back[:, :8, 0] = 0                       # some ops' ok word is 0
    back = back.astype(np.int32)
    owner = rng.integers(-1, n + 2, L).astype(np.int32)  # -1, past n-1
    owner[:3] = [-1, n, n + 1]
    wants = rng.random(L) < 0.7

    # the plain reference: the per-op gather the select replaced
    j = np.arange(L)
    own_row = np.clip(owner, 0, n - 1)
    ref_vals = np.where(wants[:, None], back[own_row, j, 1:], 0)
    ref_ok = wants & (back[own_row, j, 0] > 0)

    extract = jax.jit(lambda b, o, w: WaveEngine._extract_reply(
        SimpleNamespace(n_shards=n), b, o, w))
    vals, ok = extract(jnp.asarray(back), jnp.asarray(owner),
                       jnp.asarray(wants))
    assert vals.dtype == jnp.int32 and ok.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(vals), ref_vals)
    np.testing.assert_array_equal(np.asarray(ok), ref_ok)
    text = extract.lower(back, owner, wants).as_text(dialect="hlo")
    assert count_op(text, "gather") == 0


@pytest.mark.parametrize("entry", ["waves", "step"])
@pytest.mark.parametrize("kind", KINDS)
def test_no_gather_in_the_reply_phase(kind, entry):
    import re
    from repro.analysis.hlo import parse_hlo

    for _, opts, text in _lowered_wave_programs(kind, (entry,)):
        lines = text.splitlines()
        for op in parse_hlo(text).ops:
            if op.opcode != "gather":
                continue
            meta = re.search(r'op_name="([^"]*)"', lines[op.line_no - 1])
            assert meta is None or "reply" not in meta.group(1).split("/"), (
                kind, entry, opts, op, meta)
