"""The membership schedule: the churn cell end to end on four CPU devices
at a tiny size, the check failing under the faults a LEAVE or a JOIN can
have and under the control, the recovery times read from a scripted
clock, and mixes without a schedule dispatching what they did before."""
import hashlib
import math

import numpy as np
import pytest

from tiny import Clock, cell_named, run_devices, tiny
from bench.control import ReferenceStructure
from bench.harness import CellRun, Layout, Result, build_structure, limits_for
from bench.membership import TAIL_S, Schedule
from bench.trace import DeviceOps, Trace, WINDOW_SPAN
from bench.traffic import Stream

LAYOUT = Layout()
CHURN = "msglog.churn-4chip"


def churn():
    return tiny(LAYOUT.cell(CHURN))


def drive(cell, seed=3, structure=None, clock=None):
    """Set-up, a window on a scripted clock, the check; the program on
    the cell's devices unless ``structure`` stands in for it."""
    import jax
    run = CellRun(cell, seed, None if structure is not None
                  else jax.devices()[:cell.chips], structure=structure)
    run.setup()
    clock = clock or Clock(0.01)
    win = run.window(0.5, clock=clock, sleep=clock.sleep)
    run.drain()
    return run, win, run.check()


# ------------------------------------------------ faults of the program --
class Skipping:
    """The program with its membership changes skipped, while the shard
    count it reports follows the schedule."""

    def __init__(self, q):
        self.q, self.n = q, q.n_shards

    def __getattr__(self, name):
        return getattr(self.q, name)

    @property
    def n_shards(self):
        return self.n

    def shrink_devices(self, ids):
        self.n -= len(ids)
        return {"moved": 0}

    def grow(self, k=1):
        self.n += k
        return {"moved": 0}


def faulty(cell, fault):
    """The program with a fault planted in a LEAVE or a JOIN."""
    import jax
    q = build_structure(cell.config, cell.chips, jax.devices()[:cell.chips])
    if fault == "skipped":
        return Skipping(q)
    shrink, grow = q.shrink_devices, q.grow

    def change_word(pos):
        """Add 1 to word 5 of the record at position ``pos``."""
        st, P = q.state, q.n_shards
        q.state = st._replace(store_vals=st.store_vals.at[
            pos % P, (pos // P) % q.cap, 5].add(1))

    if fault in ("leave_last_word", "join_last_word"):
        # the newest record the change migrated: the window's dequeues
        # never reach it, only the drain after the window does
        name = "shrink_devices" if fault == "leave_last_word" else "grow"
        made = getattr(q, name)

        def change_then_alter(*a, **k):
            stats = made(*a, **k)
            change_word(int(q.state.last) - 1)
            return stats
        setattr(q, name, change_then_alter)
    elif fault == "leave_word":
        def shrink_devices(ids):
            stats = shrink(ids)
            change_word(int(q.state.first))    # the next record dequeued
            return stats
        q.shrink_devices = shrink_devices
    elif fault == "join_drop":
        def grow_one(k=1):
            stats = grow(k)
            q.state = q.state._replace(last=q.state.last - 1)
            return stats
        q.grow = grow_one
    return q


def test_churn_cell_runs_correct():
    """One LEAVE 4->3 and one JOIN 3->4 in the window; between them the
    store sits on three devices and bursts are three shards wide; every op
    is answered and right, and the migration spans are the changes'."""
    out = run_devices("""
from repro.obs.trace import tracer
import test_bench_churn as t

tracer.clear()
run, win, ok = t.drive(t.churn())
assert ok["wrong_ops"] == 0 and ok["changes_not_made"] == 0, ok
assert win["answered"] == win["attempted"] > 0 and run.failed == 0
leave, join = run.plan.changes
assert (leave.op, join.op) == ("leave", "join")
assert leave.devices == {0, 1, 2} and join.devices == {0, 1, 2, 3}
assert leave.stats["P_from"] == join.stats["P_to"] == 4
assert leave.stats["P_to"] == join.stats["P_from"] == 3
assert leave.issued < leave.recovered < join.issued < join.recovered
# the set-up's cycle, then the window's
assert [ok for _, ok in run.marks] == [True] * 5
(s_leave, _), (s_join, _), (w_leave, _), (w_join, _) = run.marks[1:]
widths = [b.valid.shape[1] for b in run.log.bursts]
for lo, hi, n in ((w_leave, w_join, 3), (w_join, len(widths), 4),
                  (run.window_log_start, w_leave, 4)):
    assert hi > lo and all(w % n == 0 and w // n in (8, 16, 32)
                           for w in widths[lo:hi]), (n, widths[lo:hi])
assert {w // 3 for w in widths[s_leave:s_join]} == {8, 16, 32}
# the drain after the window dequeued every record the run enqueued, at
# full width on four shards, down to a burst that found the queue empty
drain = run.log.bursts[run.window_log_end:]
assert drain and all(b.valid.shape[1] == 4 * 32 and not b.is_enq.any()
                     for b in drain)
assert not drain[-1].got["dok"].all()
assert sum(int(b.got["dok"].sum()) for b in run.log.bursts) == \
    run.log.next_id
spans = tracer.summary()
assert spans["migration:shrink"]["count"] == 2
assert spans["migration:grow"]["count"] == 2
print("OK")
""", n_dev=4)
    assert "OK" in out


@pytest.mark.parametrize("fault", ["leave_word", "join_drop", "skipped",
                                   "leave_last_word", "join_last_word"])
def test_churn_fault_fails_the_check(fault):
    out = run_devices(f"""
import test_bench_churn as t

c = t.churn()
run, win, bad = t.drive(c, structure=t.faulty(c, {fault!r}))
assert bad["wrong_ops"] > 0, bad
print("OK", bad)
""", n_dev=4)
    assert "OK" in out


def test_over_capacity_on_three_shards_fails():
    """Enqueues past the capacity of three shards while the store sits on
    three: the program refuses the burst, and a program that accepts it
    (its overflow check skipped) fails the check, although the
    reference's ring is as long as four shards' store."""
    out = run_devices("""
import dataclasses
import test_bench_churn as t
from bench.harness import Overflow

c = t.churn()
cap = c.config["store_records_per_chip"]
# the backlog fits three shards; the set-up's warm bursts on three
# shards, enqueues only, carry it past their capacity
c = dataclasses.replace(c, mix=dict(c.mix, enqueue_share=1.0,
                                    backlog_records=3 * cap - 1200))
try:
    t.drive(c)
    raise AssertionError("the program accepted a burst past capacity")
except Overflow:
    pass
q = t.faulty(c, "none")
q._check_overflow = lambda ovf, burst: None
run, _, bad = t.drive(c, structure=q)
assert run.marks[1][1]          # the set-up LEAVE was made
assert bad["wrong_ops"] > 0, bad
print("OK", bad)
""", n_dev=4)
    assert "OK" in out


def test_churn_wave_faults_fail_the_check():
    """The faults every cell's wave program can have, on the churn cell's
    meshes: a state left unchanged, half of the batch left out, a dequeued
    word or a position altered, and the exchange between chips left out
    (the migration's with it)."""
    out = run_devices("""
from jax import lax
import test_bench_cells as cells
import test_bench_churn as t

c = t.churn()
for fault in ("state_unchanged", "half_batch", "dv:0", "pos:0"):
    _, _, bad = t.drive(c, structure=cells._structure(c, fault))
    assert bad["wrong_ops"] > 0, (fault, bad)
lax.all_to_all = lambda x, *a, **k: x
_, _, bad = t.drive(c)
assert bad["wrong_ops"] > 0, bad
print("OK")
""", n_dev=4)
    assert "OK" in out


def test_churn_control_fails_and_plain_reference_passes():
    c = churn()
    _, _, ctl = drive(c, structure=ReferenceStructure(c, control=True))
    assert ctl["wrong_ops"] > 0 and ctl["wrong_replies"] > 0, ctl
    run, _, ref = drive(c, structure=ReferenceStructure(c, control=False))
    assert ref["wrong_ops"] == 0 and ref["changes_not_made"] == 0, ref
    assert [ch.devices for ch in run.plan.changes] == [{0, 1, 2},
                                                        {0, 1, 2, 3}]


def test_churn_result_line():
    out = run_devices("""
import jax
import test_bench_churn as t
from bench.harness import peaks_for, run_cell

out = run_cell(t.LAYOUT, t.churn(), 5, 0.3, False, jax.devices()[:4],
               peaks_for("TPU v5 lite"), 0.0)
assert out["correct"] is True and out["failed"] == 0, out
assert set(out["metrics"]) == {"recover_s", "setup_s"}, out
assert out["metrics"]["recover_s"]["value"] > 0
assert list(out)[-1] == "check"
assert out["check"] == {"wrong_ops": {"value": 0, "limit": 0},
                        "changes_not_made": {"value": 0, "limit": 0}}
print("OK")
""", n_dev=4)
    assert "OK" in out


# -------------------------------------------------- the scripted clock --
class Pausing(ReferenceStructure):
    """The plain reference, each membership change taking ``pause``
    seconds of the scripted clock."""

    def __init__(self, cell, clock, pause):
        super().__init__(cell, control=False)
        self.clock, self.pause = clock, pause

    def shrink_devices(self, dev_ids):
        self.clock.sleep(self.pause)
        return super().shrink_devices(dev_ids)

    def grow(self, k=1):
        self.clock.sleep(self.pause)
        return super().grow(k)


def test_recovery_times_from_a_scripted_clock():
    c, dt, pause = churn(), 0.01, 0.2
    clock = Clock(dt)
    run, win, numbers = drive(c, structure=Pausing(c, clock, pause),
                              clock=clock)
    assert numbers["wrong_ops"] == 0
    leave, join = run.plan.changes
    at, after = (e.get("at_s", e.get("after_recovered_s"))
                 for e in c.mix["membership"])
    assert at <= leave.issued < at + 2 * dt
    assert leave.recovered + after <= join.issued < leave.recovered + \
        after + 2 * dt
    assert win["seconds"] >= join.recovered + TAIL_S
    # each op's reply time, from its due time on the seed's schedule
    _, _, due = Stream(c.mix, 3, 1).take(win["attempted"])
    reply = due + run.latencies
    for ch in (leave, join):
        assert ch.returned - ch.issued == pytest.approx(pause + dt)
        last = ch.target - 1            # the last op due before it returned
        assert due[last] <= ch.returned < due[last + 1]
        assert ch.recovered == pytest.approx(reply[last])
        assert ch.recovered > ch.returned
    res = Result(run, win, 0.0, {})
    got = {m: LAYOUT.metric(m).read(res)
           for m in ("recover_s", "recover.drain_s")}
    assert got["recover_s"] == pytest.approx(
        np.mean([ch.recovered - ch.issued for ch in (leave, join)]))
    assert got["recover.drain_s"] == pytest.approx(
        np.mean([ch.recovered - ch.returned for ch in (leave, join)]))
    assert got["recover_s"] - got["recover.drain_s"] == pytest.approx(
        pause + dt)


class Refusing(ReferenceStructure):
    """The plain reference refusing every JOIN after the set-up's."""

    joins = 0

    def grow(self, k=1):
        self.joins += 1
        if self.joins > 1:
            raise ValueError("no spare device")
        return super().grow(k)


def test_a_refused_change_strands_its_ops():
    c = churn()
    run, win, numbers = drive(c, structure=Refusing(c, control=False))
    assert numbers["changes_not_made"] == 1 and numbers["wrong_ops"] == 0
    assert run.failed > 0
    assert win["attempted"] == win["answered"] + run.failed
    limits = limits_for(run)
    assert any(numbers[k] > v for k, v in limits.items())


@pytest.mark.parametrize("entries", [
    [{"at_s": 1, "op": "rejoin"}],
    [{"at_s": 1, "op": "leave"}],
    [{"after_recovered_s": 1, "op": "join"}],
    [{"at_s": 1, "op": "leave", "shard": 0},
     {"at_s": 2, "after_recovered_s": 1, "op": "join"}],
])
def test_schedule_refuses_what_it_cannot_run(entries):
    with pytest.raises(ValueError, match="membership change"):
        Schedule(entries)


# --------------------------------------------------- mixes without it --
def fingerprint(run, win) -> str:
    """The ops of every burst of the run, the window's numbers and every
    op's latency."""
    h = hashlib.sha256()
    for b in run.log.bursts:
        h.update(np.int64([b.first_id, *b.valid.shape]).tobytes())
        for a in (b.is_enq, b.valid) + ((b.key,) if b.key is not None
                                        else ()):
            h.update(np.ascontiguousarray(a).tobytes())
    h.update(repr(sorted(win.items())).encode())
    if run.latencies is not None:
        h.update(np.ascontiguousarray(run.latencies).tobytes())
    return h.hexdigest()[:16]


# read from the harness before it had membership schedules, seed 7
BEFORE = {"msglog.saturate": "396da0601e12b723",
          "msglog.steady": "63f8471a6f4390eb",
          "tiers4": "4679f8fd2059f1ae"}


@pytest.mark.parametrize("cell", sorted(BEFORE))
def test_mix_without_membership_dispatches_as_before(cell):
    import jax
    from repro.obs.trace import tracer
    tracer.clear()
    c = tiny(cell_named(LAYOUT, cell))
    assert "membership" not in c.mix
    run = CellRun(c, 7, jax.devices()[:1])
    run.setup()
    clock = Clock(0.01)
    win = run.window(0.5, clock=clock, sleep=clock.sleep)
    assert fingerprint(run, win) == BEFORE[cell]
    assert run.marks == [] and run.plan.changes == []
    assert not [n for n in tracer.summary() if n.startswith("migration:")]


# --------------------------------------------------- migration readers --
MIGRATION = ["migration.host_ms", "migration.device_ms",
             "migration.hbm_roofline_pct"]
MS = 1e6


def _trace():
    """A LEAVE (land inside it) and a JOIN (stage inside it), a wave
    between them, on two devices."""
    host = [(10 * MS, 50 * MS, "migration:shrink"),
            (30 * MS, 45 * MS, "migration:land"),
            (55 * MS, 58 * MS, "queue:burst"),
            (60 * MS, 100 * MS, "migration:grow"),
            (60 * MS, 80 * MS, "migration:stage"),
            (0, 120 * MS, WINDOW_SPAN)]
    ops = {"/device:TPU:0": [(35, 48), (56, 58), (82, 90)],
           "/device:TPU:1": [(36, 46), (56, 58), (82, 94)]}
    devices = {d: DeviceOps(np.array([s * MS for s, _ in ev]),
                            np.array([e * MS for _, e in ev]),
                            np.zeros(len(ev), int), ["fusion.1"], ["fusion"])
               for d, ev in ops.items()}
    return Trace(devices, host, (0, 120 * MS))


def _churn_result(trace, moved=(1000, 900)):
    run = CellRun(churn(), 3, structure=ReferenceStructure(churn(), False))
    for c, m in zip(run.plan.changes, moved):
        c.issued, c.returned, c.recovered = 1.0, 2.0, 3.0
        c.stats = {"moved": m}
    return Result(run, {}, 0.0, {"hbm_bytes_per_s": 819e9}, trace)


def test_migration_readers():
    res = _churn_result(_trace())
    got = {m: LAYOUT.metric(m).read(res) for m in MIGRATION}
    assert got["migration.host_ms"] == pytest.approx((15 + 20) / 2)
    busy = [(13 + 10) / 2, (8 + 12) / 2]        # per change, over devices
    assert got["migration.device_ms"] == pytest.approx(np.mean(busy))
    need = 4 * 2 * res.run.W * (1000 + 900)
    assert got["migration.hbm_roofline_pct"] == pytest.approx(
        100 * need / 4 / 819e9 / (sum(busy) * 1e-3))


def test_migration_readers_leave_out_what_is_not_there():
    untraced = _churn_result(None)
    tr = _trace()
    tr.host = [h for h in tr.host if not h[2].startswith("migration:")]
    no_spans = _churn_result(tr)
    for res in (untraced, no_spans):
        for m in MIGRATION:
            assert LAYOUT.metric(m).read(res) is None, m
    unmade = _churn_result(_trace())
    for c in unmade.run.plan.changes:
        c.returned = c.recovered = math.nan
    assert LAYOUT.metric("migration.hbm_roofline_pct").read(unmade) is None
    for m in ("recover_s", "recover.drain_s"):
        assert LAYOUT.metric(m).read(unmade) is None
