"""PR 2 elastic membership: live JOIN/LEAVE resharding of the device path.

Differential tests: ElasticDeviceQueue / ElasticDeviceStack under a
grow+shrink schedule must produce the exact op-by-op results of the host
``Skueue`` protocol reference under the same trace with a JOIN/LEAVE
schedule — zero lost or reordered elements.  Plus integration: ServeEngine
live resize, fault shrink-on-failure, checkpoint cold-start reshard."""
from multidev import run_multidev

DIFFERENTIAL = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.consistency import check_sequential_consistency
from repro.core.protocol import DEQ, ENQ, Skueue
from repro.dqueue import ElasticDeviceQueue, ElasticDeviceStack

rng = np.random.default_rng(23)
N_OPS = 96
ops = (rng.random(N_OPS) < 0.6).tolist()
# membership schedule, keyed by trace index (applied between wave bursts on
# the device side, injected as JOIN/LEAVE on the protocol side)
SCHEDULE = {24: ("grow", 2), 48: ("shrink", [0, 4]), 72: ("grow", 1)}


def run_device(elastic, W):
    # Drive the op trace through an elastic wrapper, resizing at the
    # scheduled trace indices; payload word 0 = trace index.
    pos_l, bot_l, res_l = [], [], []
    cut = sorted(SCHEDULE) + [len(ops)]
    start = 0
    for end in cut:
        chunk = ops[start:end]
        if chunk:
            n = elastic.n_shards * elastic.L
            K = -(-len(chunk) // n)
            E = np.zeros((K, n), bool)
            V = np.zeros((K, n), bool)
            PW = np.zeros((K, n, W), np.int32)
            for j, op in enumerate(chunk):
                k, i = divmod(j, n)
                E[k, i] = bool(op)
                V[k, i] = True
                PW[k, i, 0] = start + j
            pos, m, dv, dok, ovf = elastic.run_waves(E, V, PW)
            assert not np.asarray(ovf).any()
            pos = np.asarray(pos).reshape(-1)[:len(chunk)]
            m = np.asarray(m).reshape(-1)[:len(chunk)]
            dv = np.asarray(dv).reshape(K * n, W)[:len(chunk)]
            dok = np.asarray(dok).reshape(-1)[:len(chunk)]
            for j, op in enumerate(chunk):
                pos_l.append(int(pos[j]))
                bot_l.append((not op) and not m[j])
                if (not op) and m[j]:
                    # matched dequeue/pop MUST find its element (none lost)
                    assert dok[j], f"matched op {start + j} lost its element"
                    res_l.append(int(dv[j, 0]))
                else:
                    res_l.append(None)
        if end in SCHEDULE:
            kind, arg = SCHEDULE[end]
            st = elastic.grow(arg) if kind == "grow" else elastic.shrink(arg)
            assert st["moved"] == elastic.size, (st, elastic.size)
        start = end
    return pos_l, bot_l, res_l


def run_protocol(mode):
    # Same trace through the paper protocol, one op injected per round at a
    # fixed node, JOIN/LEAVE requested at the scheduled trace indices.
    sk = Skueue(4, mode=mode, seed=0, local_combining=False)
    nid = sk.ring.node_ids()[0]
    rids = []

    def inject(s, rnd):
        i = rnd - 1
        if i < len(ops):
            rids.append(s.inject(nid, ENQ if ops[i] else DEQ))
        if i in SCHEDULE:
            kind, arg = SCHEDULE[i]
            if kind == "grow":
                for _ in range(arg):
                    s.request_join()
            else:
                # LEAVE processes that do not own the injection node
                keep = s.ring.proc[nid]
                alive = sorted({s.ring.proc[v] for v in s.ring.node_ids()})
                for pid in [p for p in alive if p != keep][:len(arg)]:
                    s.request_leave(pid)

    sk.run_rounds(len(ops) + 80, inject_fn=inject)
    assert all(sk.requests[r].done for r in rids)
    assert sk.update_phases >= 2, "membership schedule never took effect"
    check_sequential_consistency(sk)
    sk.check_dht_placement()
    pos_l = [-1 if sk.requests[r].pos is None else sk.requests[r].pos
             for r in rids]
    bot_l = [sk.requests[r].kind == DEQ and sk.requests[r].result == -1
             for r in rids]
    res_l = [sk.requests[r].result
             if sk.requests[r].kind == DEQ and sk.requests[r].result != -1
             else None for r in rids]
    return sk, pos_l, bot_l, res_l


# ------------------------------- queue mode --------------------------------
eq = ElasticDeviceQueue(4, cap=32, payload_width=2, ops_per_shard=4)
d_pos, d_bot, d_res = run_device(eq, 2)
sk, p_pos, p_bot, p_res = run_protocol("queue")
assert d_pos == p_pos, "positions diverged"
assert d_bot == p_bot, "unmatched-dequeue (bottom) sets diverged"
# protocol results are elem ids == trace index of the matching enqueue
assert d_res == p_res, "dequeue sequences diverged (lost/reordered!)"
assert (int(eq.state.first), int(eq.state.last)) == (
    sk.anchor_state.first, sk.anchor_state.last)
assert eq.n_shards == 5 and len(eq.migrations) == 3
print("OK elastic queue == Skueue through JOIN/LEAVE",
      sum(r is not None for r in d_res), "dequeues")

# ------------------------------- stack mode --------------------------------
es = ElasticDeviceStack(4, cap=32, payload_width=2, ops_per_shard=4,
                        slot_depth=8)
d_pos, d_bot, d_res = run_device(es, 2)
sk, p_pos, p_bot, p_res = run_protocol("stack")
assert d_pos == p_pos, "stack positions diverged"
assert d_bot == p_bot, "unmatched-pop (bottom) sets diverged"
assert d_res == p_res, "pop sequences diverged (lost/reordered!)"
assert int(es.state["last"]) == sk.anchor_state.last
assert int(es.state["ticket"]) == sk.anchor_state.ticket
print("OK elastic stack == Skueue through JOIN/LEAVE",
      sum(r is not None for r in d_res), "pops")

# --------------------- capacity guard + noop resize ------------------------
small = ElasticDeviceQueue(2, cap=4, payload_width=2, ops_per_shard=4)
e = np.ones(8, bool); pw = np.zeros((8, 2), np.int32)
small.step(e, e, pw)   # 8 live elements
try:
    small.shrink([0])  # 1 shard * cap 4 < 8 live -> must refuse
    raise SystemExit("shrink accepted an impossible capacity")
except ValueError:
    pass
assert small.resize(2)["kind"] == "noop"
print("OK capacity guard")
"""


def test_elastic_matches_protocol_reference_8dev():
    """Acceptance: grow (P->P+k) and shrink (P->P-k) under live traffic
    dequeue the exact sequence the host Skueue reference produces under the
    same JOIN/LEAVE schedule — both queue and stack modes."""
    out = run_multidev(DIFFERENTIAL, n_dev=8)
    assert "OK elastic queue == Skueue" in out
    assert "OK elastic stack == Skueue" in out
    assert "OK capacity guard" in out


INTEGRATION = r"""
import tempfile
import numpy as np, jax, jax.numpy as jnp

# ------------------ fault: shrink-on-failure / regrow-on-recovery ----------
from repro.dqueue import ElasticDeviceQueue
from repro.fault import ElasticPolicy, FailureInjector, run_with_restarts

q = ElasticDeviceQueue(4, cap=64, payload_width=2, ops_per_shard=4)
got = []

def step_fn(state, step):
    n = q.n_shards * q.L
    e = np.zeros(n, bool); v = np.zeros(n, bool)
    pw = np.zeros((n, 2), np.int32)
    e[:4] = v[:4] = True                      # 4 enqueues
    pw[:4, 0] = np.arange(step * 4, step * 4 + 4)
    v[4:7] = True                             # 3 dequeues (queue grows)
    _, _, dv, dok, _ = q.step(e, v, pw)
    dv, dok = np.asarray(dv), np.asarray(dok)
    got.extend(int(dv[i, 0]) for i in range(n) if dok[i])
    return {"done": np.int64(step + 1)}

policy = ElasticPolicy(
    shrink=lambda state, shard: (q.shrink([shard]), state)[1],
    regrow=lambda state: (q.grow(1), state)[1],
    regrow_after=2)
inj = FailureInjector(shard_fail_at={3: 1, 6: 0})
with tempfile.TemporaryDirectory() as d:
    state, metrics = run_with_restarts(
        init_state=lambda: {"done": np.int64(0)},
        step_fn=step_fn, n_steps=10, ckpt_dir=d, ckpt_every=100,
        injector=inj, elastic=policy, log=lambda *a: None)
assert metrics["leaves"] == 2, metrics
assert metrics["joins"] >= 1, metrics
assert metrics["restarts"] == 0, metrics          # zero checkpoint restarts
assert metrics["steps_run"] == 10, metrics        # zero replayed steps
# drain what's left; the full stream must come out in FIFO order
while q.size > 0:
    n = q.n_shards * q.L
    _, _, dv, dok, _ = q.step(np.zeros(n, bool), np.ones(n, bool),
                              np.zeros((n, 2), np.int32))
    dv, dok = np.asarray(dv), np.asarray(dok)
    got.extend(int(dv[i, 0]) for i in range(n) if dok[i])
assert got == list(range(40)), got
assert q.n_shards == 4 - 2 + metrics["joins"]
print("OK fault LEAVE/JOIN: no restarts, no replay, FIFO intact")

# ------------------ checkpoint cold-start reshard --------------------------
q2 = ElasticDeviceQueue(6, cap=16, payload_width=2, ops_per_shard=4)
n = q2.n_shards * q2.L
e = np.ones(n, bool); pw = np.zeros((n, 2), np.int32)
pw[:, 0] = np.arange(n)
q2.step(e, e, pw)
with tempfile.TemporaryDirectory() as d:
    q2.save(d, 11)
    q3 = ElasticDeviceQueue.restore(d, n_shards=3)   # cold start, resharded
assert q3.n_shards == 3 and q3.size == n
assert q3.migrations[-1]["kind"] == "shrink"
got = []
while len(got) < n:
    m = q3.n_shards * q3.L
    _, _, dv, dok, _ = q3.step(np.zeros(m, bool), np.ones(m, bool),
                               np.zeros((m, 2), np.int32))
    dv, dok = np.asarray(dv), np.asarray(dok)
    got.extend(int(dv[i, 0]) for i in range(m) if dok[i])
assert got == list(range(n))
print("OK checkpoint cold-start reshard 6 -> 3")

# ---- stack cold-start with non-default slot_depth (D in the manifest) -----
from repro.dqueue import ElasticDeviceStack
s1 = ElasticDeviceStack(2, cap=8, payload_width=2, ops_per_shard=4,
                        slot_depth=8)
n = s1.n_shards * s1.L
e = np.ones(n, bool)
pw = np.zeros((n, 2), np.int32)
pw[:, 0] = np.arange(n)
s1.step(e, e, pw)
with tempfile.TemporaryDirectory() as d:
    s1.save(d, 1)
    s2 = ElasticDeviceStack.restore(d, n_shards=3)
assert s2.D == 8 and s2.n_shards == 3 and s2.size == n
got = []
while len(got) < n:
    m = s2.n_shards * s2.L
    _, _, pv, pok, _ = s2.step(np.zeros(m, bool), np.ones(m, bool),
                               np.zeros((m, 2), np.int32))
    pv, pok = np.asarray(pv), np.asarray(pok)
    got.extend(int(pv[i, 0]) for i in range(m) if pok[i])
assert got == list(range(n - 1, -1, -1)), got
print("OK stack cold-start preserves slot_depth")
"""


def test_fault_leave_and_cold_start_8dev():
    """Satellite: failure => LEAVE of the dead shard instead of full
    restart (zero replayed steps); checkpoint restore_sharded is the
    cold-start analogue of the live migration."""
    out = run_multidev(INTEGRATION, n_dev=8)
    assert "OK fault LEAVE/JOIN" in out
    assert "OK checkpoint cold-start reshard" in out
    assert "OK stack cold-start preserves slot_depth" in out


def test_fault_regrow_deficit_survives_checkpoint_restart(tmp_path):
    """Regression: the LEAVEd-capacity deficit lives outside the
    checkpointed tree, so a plain-failure restart between a LEAVE and its
    regrow must not forget it — regrow still fires once healthy."""
    import numpy as np
    from repro.fault import (ElasticPolicy, FailureInjector,
                             run_with_restarts)
    events = []
    policy = ElasticPolicy(
        shrink=lambda st, shard: (events.append(("leave", shard)), st)[1],
        regrow=lambda st: (events.append(("join",)), st)[1],
        regrow_after=2)
    inj = FailureInjector(shard_fail_at={1: 0}, fail_at_steps=(2,))
    _, metrics = run_with_restarts(
        init_state=lambda: {"x": np.int64(0)},
        step_fn=lambda st, step: {"x": np.int64(step + 1)},
        n_steps=8, ckpt_dir=tmp_path, ckpt_every=100,
        injector=inj, elastic=policy, log=lambda *a: None)
    # step 1: ShardFailure => LEAVE; step 2: plain failure => restart from
    # scratch; the deficit survives and regrows after 2 healthy steps
    assert metrics["leaves"] == 1 and metrics["restarts"] == 1
    assert metrics["joins"] == 1, (metrics, events)
    assert events == [("leave", 0), ("join",)]


SERVE_RESIZE = r"""
import numpy as np, jax
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve import Request, ServeEngine

cfg = get_config("mamba2_130m").reduced(n_layers=1)
model = build_model(cfg)
params, _ = model.init_params(jax.random.key(0))
mesh = make_host_mesh(n_data=2)
eng = ServeEngine(model, params, mesh, max_slots=2, max_seq=16)
rng = np.random.default_rng(3)
reqs = [Request(rid=i, prompt=list(rng.integers(0, cfg.vocab, 2)), max_new=2)
        for i in range(12)]
eng.submit(reqs[:8])
eng.step()                       # some admitted, some still queued on device
st = eng.resize(4)               # JOIN: queue fabric 2 -> 4 shards
assert st["P_to"] == 4 and eng.queue.n_shards == 4
eng.submit(reqs[8:])             # traffic keeps flowing on the wider mesh
eng.step()
st = eng.resize(1)               # LEAVE down to a single shard
assert st["P_to"] == 1
assert eng.run_until_drained(max_steps=400)
assert eng.stats["served"] == 12
starts = [r.start_step for r in reqs]
assert starts == sorted(starts), ("FIFO admission broken by resize", starts)
print("OK serve resize", [m["kind"] for m in eng.queue.migrations])
"""


def test_serve_engine_resize_8dev():
    """ServeEngine.resize: drain staged, reshard live, resume bursts —
    every request served, FIFO admission preserved across JOIN and LEAVE."""
    out = run_multidev(SERVE_RESIZE, n_dev=8)
    assert "OK serve resize" in out


MIGRATION_COMPILE = r"""
import numpy as np
from repro.analysis import CompilationTracker
from repro.dqueue import ElasticDeviceQueue
from repro.obs.trace import tracer

q = ElasticDeviceQueue(1, cap=64, payload_width=2, ops_per_shard=4)
e = np.ones((2, 4), bool)
q.run_waves(e, e, np.zeros((2, 4, 2), np.int32))
g1, s1 = q.grow(1), q.shrink([1])
with CompilationTracker() as again:
    g2 = q.grow(1)
assert again.count == 0, again.programs
assert g1["compile_s"] > 0 and g2["compile_s"] < g1["compile_s"] / 10
assert g1["moved"] == g2["moved"] == 8 and s1["moved"] == 8
progs = CompilationTracker.by_program()
assert progs["jit(skueue_fifo_migrate_1to2)"]["compiles"] == 1
assert progs["jit(skueue_fifo_migrate_2to1)"]["compiles"] == 1
spans = [(ev["name"], ev["args"].get("parent")) for ev in tracer.events()
         if ev["name"].startswith("migration:")]
assert spans == [
    ("migration:stage", "migration:grow"),
    ("migration:compile", "migration:grow"), ("migration:grow", None),
    ("migration:compile", "migration:shrink"),
    ("migration:land", "migration:shrink"), ("migration:shrink", None),
    ("migration:stage", "migration:grow"), ("migration:grow", None)], spans
print("OK")
"""


def test_migration_compiles_ahead_of_its_timed_wave_2dev():
    """A migration program is compiled once, under its own span and
    stats entry, ahead of the wave that ``wave_s`` times; a second use of
    it compiles nothing; host staging and landing have spans."""
    out = run_multidev(MIGRATION_COMPILE, n_dev=2)
    assert "OK" in out
