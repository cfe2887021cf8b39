"""PR 3 priority tiers: DevicePriorityQueue differential vs. the host
P-tier oracle (op-by-op, P in {2, 4}, across grow+shrink migrations),
HLO collective count, bounded relaxation, and serve/fault/checkpoint
integration."""
import numpy as np
import pytest

from multidev import run_multidev

DIFFERENTIAL = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.priority import DEQ, ENQ, PriorityOracle
from repro.dqueue import ElasticDevicePriorityQueue

# randomized mixed enq/deq schedule with random tiers; migration schedule
# applied between waves (one grow, one shrink) — the oracle is membership-
# oblivious, so op-by-op equality proves migrations lose/reorder nothing.
for P_ in (2, 4):
    for relax in (0, 1):
        eq = ElasticDevicePriorityQueue(4, n_prios=P_, relaxation=relax,
                                        cap=32, payload_width=2,
                                        ops_per_shard=4)
        oracle = PriorityOracle(P_, relaxation=relax)
        rng = np.random.default_rng(100 * P_ + relax)
        relaxed_served = 0
        for it in range(14):
            if it == 5:
                st = eq.grow(2)
                assert st["moved"] == eq.size == oracle.size, (st, it)
            if it == 10:
                st = eq.shrink([0, 3])
                assert st["moved"] == eq.size == oracle.size, (st, it)
            n = eq.n_shards * eq.L
            e = rng.random(n) < 0.55
            v = rng.random(n) < 0.9
            pr = rng.integers(0, P_, n).astype(np.int32)
            pw = np.zeros((n, 2), np.int32)
            pw[:, 0] = rng.integers(0, 1 << 20, n)
            tier, pos, m, dv, dok, ovf, nrel = eq.step(e, v, pr, pw)
            assert not bool(np.asarray(ovf))
            ops = [None if not v[i] else
                   ((ENQ, int(pr[i]), int(pw[i, 0]), i // eq.L) if e[i]
                    else (DEQ, 0, None, i // eq.L)) for i in range(n)]
            recs = oracle.wave(ops, n_shards=eq.n_shards)
            tier, pos, m, dv, dok = map(np.asarray, (tier, pos, m, dv, dok))
            for i, r in enumerate(recs):
                assert bool(m[i]) == r.matched, (P_, relax, it, i)
                assert int(tier[i]) == r.tier, (P_, relax, it, i)
                assert int(pos[i]) == r.pos, (P_, relax, it, i)
                if r.matched and r.value is not None:
                    # matched dequeue MUST find its element (none lost)
                    assert bool(dok[i]), (P_, relax, it, i)
                    assert int(dv[i, 0]) == r.value, (P_, relax, it, i)
            n_rel_oracle = sum(r.relaxed for r in recs)
            assert int(nrel) == n_rel_oracle, (P_, relax, it)
            relaxed_served += n_rel_oracle
        assert eq.sizes == oracle.sizes, (P_, relax)
        if relax == 0:
            assert relaxed_served == 0
        print(f"OK pqueue P={P_} relax={relax} sizes={oracle.sizes} "
              f"relaxed={relaxed_served}")
"""


def test_priority_queue_matches_oracle_across_migrations_8dev():
    """Acceptance: strict mode matches the P-tier host oracle op-by-op
    under a randomized mixed schedule on 8 CPU devices for P in {2, 4},
    including across one grow and one shrink migration (and the relaxed
    mode matches the oracle's bounded-relaxation rule)."""
    out = run_multidev(DIFFERENTIAL, n_dev=8)
    for P_ in (2, 4):
        for relax in (0, 1):
            assert f"OK pqueue P={P_} relax={relax}" in out


COLLECTIVES = r"""
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.dqueue import DevicePriorityQueue
from repro.analysis import count_all_to_all
mesh = make_mesh((8,), ("data",))
for P_, relax in ((2, 0), (4, 0), (2, 1)):
    dq = DevicePriorityQueue(mesh, "data", n_prios=P_, cap=32,
                             payload_width=2, ops_per_shard=4,
                             relaxation=relax)
    n = dq.n_shards * dq.L
    args = (dq.init_state(), jnp.zeros(n, bool), jnp.zeros(n, bool),
            jnp.zeros(n, jnp.int32), jnp.zeros((n, 2), jnp.int32))
    c = count_all_to_all(dq._step, args)
    assert c <= 2, f"P={P_} relax={relax}: {c} all-to-alls per wave"
    print(f"OK collectives P={P_} relax={relax}:", c)
"""


def test_priority_wave_lowers_to_two_all_to_alls_8dev():
    """Acceptance: the priority wave still costs <= 2 all_to_all
    collectives, for multiple tier counts and in relaxed mode."""
    out = run_multidev(COLLECTIVES, n_dev=8)
    assert "OK collectives P=2 relax=0: 2" in out
    assert "OK collectives P=4 relax=0: 2" in out
    assert "OK collectives P=2 relax=1: 2" in out


RUN_WAVES = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.dqueue import DevicePriorityQueue
mesh = make_mesh((8,), ("data",))
dq = DevicePriorityQueue(mesh, "data", n_prios=3, cap=64, payload_width=2,
                         ops_per_shard=4)
n = dq.n_shards * dq.L
K = 6
rng = np.random.default_rng(41)
E = rng.random((K, n)) < 0.6
V = rng.random((K, n)) < 0.9
PR = rng.integers(0, 3, (K, n)).astype(np.int32)
PW = rng.integers(0, 99, (K, n, 2)).astype(np.int32)
sb = dq.init_state()
outs = []
for k in range(K):
    sb, *o = dq.step(sb, jnp.array(E[k]), jnp.array(V[k]), jnp.array(PR[k]),
                     jnp.array(PW[k]))
    outs.append([np.asarray(x) for x in o])
sa, *oa = dq.run_waves(dq.init_state(), jnp.array(E), jnp.array(V),
                       jnp.array(PR), jnp.array(PW))
oa = [np.asarray(x) for x in oa]
for k in range(K):
    for a, b in zip(oa, outs[k]):
        assert (a[k] == b).all(), k
assert (np.asarray(sa.firsts) == np.asarray(sb.firsts)).all()
assert (np.asarray(sa.lasts) == np.asarray(sb.lasts)).all()
assert (np.asarray(sa.store_full) == np.asarray(sb.store_full)).all()
print("OK pqueue run_waves == K steps")
"""


def test_priority_run_waves_equals_stepwise_8dev():
    out = run_multidev(RUN_WAVES, n_dev=8)
    assert "OK pqueue run_waves == K steps" in out


CHECKPOINT_FAULT = r"""
import tempfile
import numpy as np, jax
from repro.dqueue import ElasticDevicePriorityQueue
from repro.fault import FailureInjector, elastic_queue_policy, \
    run_with_restarts

# ---- fault: ShardFailure => LEAVE of the priority fabric, zero replay ----
q = ElasticDevicePriorityQueue(4, n_prios=2, cap=64, payload_width=2,
                               ops_per_shard=4)
got = []

def step_fn(state, step):
    n = q.n_shards * q.L
    e = np.zeros(n, bool); v = np.zeros(n, bool)
    pr = np.zeros(n, np.int32)
    pw = np.zeros((n, 2), np.int32)
    e[:4] = v[:4] = True
    pr[:4] = step % 2                       # alternate tiers
    pw[:4, 0] = np.arange(step * 4, step * 4 + 4)
    v[4:6] = True                           # 2 dequeues: queue grows
    _, _, _, dv, dok, _, _ = q.step(e, v, pr, pw)
    dv, dok = np.asarray(dv), np.asarray(dok)
    got.extend(int(dv[i, 0]) for i in range(n) if dok[i])
    return {"done": np.int64(step + 1)}

inj = FailureInjector(shard_fail_at={3: 1})
with tempfile.TemporaryDirectory() as d:
    state, metrics = run_with_restarts(
        init_state=lambda: {"done": np.int64(0)},
        step_fn=step_fn, n_steps=8, ckpt_dir=d, ckpt_every=100,
        injector=inj, elastic=elastic_queue_policy(q, regrow_after=2),
        log=lambda *a: None)
assert metrics["leaves"] == 1 and metrics["restarts"] == 0, metrics
assert metrics["joins"] == 1 and metrics["steps_run"] == 8, metrics
assert q.n_shards == 4
served = len(got)
while q.size > 0:
    n = q.n_shards * q.L
    _, _, _, dv, dok, _, _ = q.step(np.zeros(n, bool), np.ones(n, bool),
                                    np.zeros(n, np.int32),
                                    np.zeros((n, 2), np.int32))
    dv, dok = np.asarray(dv), np.asarray(dok)
    got.extend(int(dv[i, 0]) for i in range(n) if dok[i])
assert sorted(got) == list(range(32)), got
print("OK pqueue fault LEAVE/JOIN: zero replay, no element lost")

# ---- checkpoint cold-start reshard (per-tier layout in the manifest) ----
q2 = ElasticDevicePriorityQueue(6, n_prios=3, relaxation=1, cap=16,
                                payload_width=2, ops_per_shard=4)
n = q2.n_shards * q2.L
e = np.ones(n, bool)
pr = (np.arange(n) % 3).astype(np.int32)
pw = np.zeros((n, 2), np.int32)
pw[:, 0] = np.arange(n)
q2.step(e, e, pr, pw)
with tempfile.TemporaryDirectory() as d:
    q2.save(d, 7)
    q3 = ElasticDevicePriorityQueue.restore(d, n_shards=3)
assert q3.n_shards == 3 and q3.n_prios == 3 and q3.relaxation == 1
assert q3.sizes == q2.sizes and q3.size == n
assert q3.migrations[-1]["kind"] == "shrink"
# drain: every element survives, and each tier comes out in FIFO order
# (the restored queue keeps relaxation=1, so TIERS may interleave — that
# is the relaxation knob working; per-tier FIFO must still hold)
got = []
while len(got) < n:
    m = q3.n_shards * q3.L
    t, _, _, dv, dok, _, _ = q3.step(np.zeros(m, bool), np.ones(m, bool),
                                     np.zeros(m, np.int32),
                                     np.zeros((m, 2), np.int32))
    t, dv, dok = np.asarray(t), np.asarray(dv), np.asarray(dok)
    got.extend((int(t[i]), int(dv[i, 0])) for i in range(m) if dok[i])
for tier_id in range(3):
    per_tier = [v for t, v in got if t == tier_id]
    assert per_tier == sorted(per_tier), (tier_id, "FIFO broken in tier")
    assert per_tier == [v for v in range(n) if v % 3 == tier_id]
assert sorted(v for _, v in got) == list(range(n))
print("OK pqueue checkpoint cold-start reshard 6 -> 3")
"""


def test_priority_fault_and_checkpoint_8dev():
    """Satellite integration: shard failure LEAVEs the priority fabric via
    fault.elastic_queue_policy (zero replayed steps, no element lost), and
    checkpoint manifests carry the per-tier layout so a cold start can
    reshard (n_prios/relaxation restored, priority order intact)."""
    out = run_multidev(CHECKPOINT_FAULT, n_dev=8)
    assert "OK pqueue fault LEAVE/JOIN" in out
    assert "OK pqueue checkpoint cold-start reshard" in out


def test_priority_scan_pallas_matches_core():
    """kernels/segscan extension: the pallas-path P-tier assignment equals
    core.scan_queue.priority_queue_scan (strict mode) on random batches."""
    import jax.numpy as jnp
    from repro.core.scan_queue import priority_queue_scan
    from repro.kernels.segscan import priority_queue_scan_pallas

    rng = np.random.default_rng(2)
    for P_ in (2, 4):
        n = 96
        is_enq = jnp.array(rng.random(n) < 0.5)
        valid = jnp.array(rng.random(n) < 0.9)
        prio = jnp.array(rng.integers(0, P_, n), jnp.int32)
        firsts = jnp.array(rng.integers(0, 5, P_), jnp.int32)
        lasts = firsts + jnp.array(rng.integers(-1, 6, P_), jnp.int32)
        ref = priority_queue_scan(is_enq, prio, valid, firsts, lasts,
                                  n_prios=P_)
        out = priority_queue_scan_pallas(is_enq, prio, valid, firsts,
                                         lasts, P_)
        for a, b in zip(out, ref[:5]):
            assert (np.asarray(a) == np.asarray(b)).all(), P_


@pytest.mark.parametrize("n", [2048, 4096, 8192])
def test_fused_sweep_matches_jnp_scan_at_each_ladder_width(n):
    """At the bucket ladder's widths of an 8192-op wave, the fused
    segscan sweep (interpret mode) inside ``priority_queue_scan`` answers
    every op as the jnp scans do, four tiers at skewed shares."""
    import jax
    import jax.numpy as jnp
    from repro.core.scan_queue import priority_queue_scan
    from repro.kernels.segscan import make_tier_scan

    rng = np.random.default_rng(n)
    is_enq = jnp.array(rng.random(n) < 0.5)
    valid = jnp.array(rng.random(n) < 0.95)
    prio = jnp.array(rng.choice(4, n, p=[0.05, 0.55, 0.15, 0.25]),
                     jnp.int32)
    firsts = jnp.array(rng.integers(0, 1 << 20, 4), jnp.int32)
    lasts = firsts + jnp.array([-1, 3, 0, 900], jnp.int32)

    def scan(tier_scan):
        return jax.jit(lambda *a: priority_queue_scan(
            *a, n_prios=4, tier_scan=tier_scan))(is_enq, prio, valid,
                                                 firsts, lasts)
    ref = scan(None)
    got = scan(make_tier_scan(4, interpret=True))
    for name, a, b in zip(("tier", "pos", "matched", "firsts", "lasts",
                           "n_relaxed"), got, ref):
        assert (np.asarray(a) == np.asarray(b)).all(), (n, name)
    assert int(np.asarray(ref[2]).sum()) > n // 2


# The post-enqueue-peak overflow regression moved to
# tests/test_wave_engine.py::test_overflow_surfaces_once_for_all_disciplines
# when the check itself was deduplicated into
# wave_engine.post_enqueue_peak_overflow (PR 4): one helper, one test,
# all three disciplines.


def test_priority_oracle_rejects_bad_tier():
    from repro.core.priority import ENQ, PriorityOracle
    with pytest.raises(ValueError):
        PriorityOracle(0)
    orc = PriorityOracle(2)
    with pytest.raises(ValueError):
        orc.wave([(ENQ, 5, 1, 0)])


SERVE_PRIORITY = r"""
import numpy as np, jax
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve import Request, ServeEngine

cfg = get_config("mamba2_130m").reduced(n_layers=1)
model = build_model(cfg)
params, _ = model.init_params(jax.random.key(0))
eng = ServeEngine(model, params, make_host_mesh(n_data=2), max_slots=2,
                  max_seq=16, priorities=2)
batch = [Request(rid=i, prompt=[1, 2], max_new=2, prio=1) for i in range(6)]
inter = [Request(rid=100 + i, prompt=[3, 4], max_new=2) for i in range(3)]
eng.submit(batch)                 # batch flood staged first
eng.submit(inter, prio=0)         # interactive arrives later, same step
assert eng.run_until_drained(max_steps=400)
assert eng.stats["served"] == 9
# interactive admitted ahead of every batch request despite arriving later
i_starts = [r.start_step for r in inter]
b_starts = [r.start_step for r in batch]
assert max(i_starts) <= min(b_starts), (i_starts, b_starts)
# batch requests keep FIFO order WITHIN their tier
assert b_starts == sorted(b_starts), b_starts
st = eng.tier_wait_stats()
assert st[0]["p99"] <= st[1]["p50"], st  # tier separation is visible
# live resize of the priority fabric mid-traffic
eng.submit([Request(rid=200 + i, prompt=[5], max_new=2,
                    prio=i % 2) for i in range(4)])
eng.step()
mig = eng.resize(1)
assert mig["P_to"] == 1 and eng.queue.n_shards == 1
assert eng.run_until_drained(max_steps=400)
assert eng.stats["served"] == 13
print("OK serve priorities", st)
"""


def test_serve_engine_priorities_8dev():
    """ServeEngine(priorities=2): interactive admitted ahead of batch
    traffic in the fused wave, per-tier latency reported, live resize of
    the priority fabric under traffic."""
    out = run_multidev(SERVE_PRIORITY, n_dev=8)
    assert "OK serve priorities" in out
