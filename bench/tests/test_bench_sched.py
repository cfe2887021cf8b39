"""The request scheduler's cell on the CPU at a tiny size: faults of its
own guarantees fail the check, and the readers of its per-layer metrics
count what they should and leave a metric out where the program lacks
what they read."""
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from tiny import cell_named, tiny
from bench import tiers
from bench.harness import Layout, build_structure
from bench.program import merge_tables
from bench.trace import DeviceOps, Trace, WINDOW_SPAN
from test_bench_cells import drive

LAYOUT = Layout()
CELL = "sched.steady"
ROOFLINE = LAYOUT.metric("segscan.hbm_roofline_pct.steady").read
READ = {m: LAYOUT.metric(m).read for m in (
    "prio.tier_scan_ms.steady", "prio.deletemin_ms.steady",
    "prio.device_ms.steady", "prio.overflow_wait_ms.steady",
    "prio.idle_pct.steady")}


def _cell():
    return tiny(cell_named(LAYOUT, CELL))


def _dequeues(out, name, k):
    """Indices of wave k's successful dequeues, in wave order."""
    return np.flatnonzero(out[name["dok"]][k])


def _swap(out, name, k, i, j, fields):
    for f in fields:
        a = out[name[f]]
        a[k, [i, j]] = a[k, [j, i]]


def _lower_tier_first(out, name, k):
    """Two dequeues of one wave, the first answered from a more urgent
    tier: swap them, so the first is served from the lower tier while
    the higher one still holds work."""
    d = _dequeues(out, name, k)
    t = out[name["tier"]][k]
    for a in range(d.size):
        later = d[a + 1:][t[d[a + 1:]] > t[d[a]]]
        if later.size:
            _swap(out, name, k, d[a], later[0], ("tier", "pos", "dv"))
            return True
    return False


def _fifo_broken(out, name, k):
    """Two dequeues of one wave from the same tier: swap their records
    and positions, so the newer request leaves its tier first."""
    d = _dequeues(out, name, k)
    t = out[name["tier"]][k]
    for a in range(d.size):
        same = d[a + 1:][t[d[a + 1:]] == t[d[a]]]
        if same.size:
            _swap(out, name, k, d[a], same[0], ("pos", "dv"))
            return True
    return False


def _tier_word_changed(out, name, k):
    """One dequeued record's tier word changed to another tier."""
    d = _dequeues(out, name, k)
    if not d.size:
        return False
    kw = 7
    out[name["dv"]][k, d[0], kw] = (out[name["dv"]][k, d[0], kw] + 1) % 4
    return True


FAULTS = {"lower_tier_first": _lower_tier_first,
          "fifo_within_tier_broken": _fifo_broken,
          "tier_word_changed": _tier_word_changed}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_scheduler_fault_fails_the_check(fault):
    """Each fault is planted once a burst, in the first wave it fits,
    under the elastic wrapper's replies; every run plants it."""
    c = _cell()
    assert c.config["key_word"] == 7 and c.config["tiers"] == 4
    q = build_structure(c.config, 1, jax.devices()[:1])
    name = dict(zip(c.config["outputs"], range(99)))
    run_waves, planted = q.run_waves, []

    def faulty(*ops):
        out = [np.array(o) for o in run_waves(*ops)]
        for k in range(out[name["dok"]].shape[0]):
            if FAULTS[fault](out, name, k):
                planted.append(k)
                break
        return tuple(out)
    q.run_waves = faulty
    _, _, numbers = drive(c, structure=q)
    assert planted
    assert numbers["wrong_ops"] > 0, numbers


def test_scheduler_cell_serves_every_tier():
    """The tiny cell's run reaches every tier: the mix's shares and the
    backlog in tier 3 give each tier dequeues to check."""
    run, _, numbers = drive(_cell())
    assert numbers["wrong_ops"] == 0, numbers
    served = {int(t) for b in run.log.bursts
              for t in b.got["tier"][b.got["dok"]]}
    assert served == {0, 1, 2, 3}


# ------------------------------------------------------------- readers --
# Two programs of the open loop's ladder, widths 2 and 4, as their compiled
# text names their instructions: the names repeat, and ``fusion.1`` and
# ``fusion.2`` are in different phases in each.
def _line(name, phase, tiles=0):
    meta = f'metadata={{op_name="jit(w)/{phase}/op"}}'
    if tiles:
        return (f"  %{name} = s32[4,{tiles},8,128]{{3,2,1,0}} custom-call("
                f'%a), custom_call_target="tpu_custom_call", {meta}')
    return f"  %{name} = s32[8]{{0}} fusion(%x), kind=kLoop, {meta}"


def _program(width: int, tiles: int) -> str:
    if width == 2:
        body = [_line("k.1", "dispatch/tier_scan", tiles),
                _line("fusion.1", "dispatch/tier_scan"),
                _line("fusion.2", "dispatch/deletemin")]
    else:
        body = [_line("k.1", "dispatch/tier_scan", tiles),
                _line("fusion.1", "commit"),
                _line("fusion.2", "dispatch/tier_scan"),
                _line("fusion.3", "dispatch/tier_scan", tiles)]
    return "\n".join(["ENTRY %main (a: s32[8]) -> s32[8] {"] + body + ["}"])


# (start, end, name) of the device's ops: a burst of width 2 dispatched in
# [10, 400], one of width 4 in [500, 900], and an op after both
OPS = [(20, 50, "k.1"), (60, 100, "fusion.1"), (110, 130, "fusion.2"),
       (510, 530, "k.1"), (540, 600, "fusion.1"), (610, 650, "fusion.2"),
       (660, 700, "fusion.3"), (950, 960, "fusion.1")]
SPANS = [(10, 400), (500, 900)]


def _result(ops: int, tiles: int = 2, widths=(2, 4), programs=True):
    names = sorted({n for _, _, n in OPS})
    dev = DeviceOps(np.array([s for s, _, _ in OPS], float),
                    np.array([e for _, e, _ in OPS], float),
                    np.array([names.index(n) for _, _, n in OPS]), names,
                    [""] * len(names))
    host = [(s, e, "bench:dispatch") for s, e in SPANS] + [
        (s + 5, e - 5, "pqueue:overflow_wait") for s, e in SPANS] + [
        (5, 8, "queue:overflow_wait"), (0, 1000, WINDOW_SPAN)]
    trace = Trace({"/device:TPU:0": dev}, host, (0.0, 1000.0))
    wave_programs = [(("waves", (1, w), (1, w)), _program(w, tiles))
                     for w in (4, 2)]
    q = SimpleNamespace(wave_programs=lambda: wave_programs) if programs \
        else SimpleNamespace()
    log = SimpleNamespace(bursts=[SimpleNamespace(is_enq=np.zeros((1, w)))
                                  for w in (8,) + tuple(widths)])
    run = SimpleNamespace(q=q, log=log, traced_from=1,
                          traced_bursts=len(widths))
    return SimpleNamespace(trace=trace, counts={"bursts": 2, "ops": ops},
                           run=run, peaks={"hbm_bytes_per_s": 819e9})


def test_sweep_bytes_come_from_the_ops_not_the_padding():
    assert tiers.work_bytes(1000) == 20 * 1000
    assert tiers.kernel_names(_program(4, 2)) == {"k.1", "fusion.3"}
    # the width-2 burst's kernel ran 30 ns, the width-4 burst's two 20 +
    # 40 ns; fusion.3 is no kernel of the width-2 program, and the op
    # after both bursts is neither's
    want = 100.0 * 20 * 12345 / 819e9 / 90e-9
    for tiles in (2, 4, 8):
        assert ROOFLINE(_result(12345, tiles)) == \
            pytest.approx(want, rel=1e-12)
    assert ROOFLINE(_result(2 * 12345)) == pytest.approx(2 * want,
                                                         rel=1e-12)


def test_phase_reader_reads_the_phase_per_dispatch():
    """Each burst's ops are placed by the program it ran: names two
    programs put to different phases still count, each burst's under its
    own program; one table of both would leave them out."""
    r = _result(10)
    # tier_scan: k.1 and fusion.1 of the width-2 burst (30 + 40 ns), k.1,
    # fusion.2 and fusion.3 of the width-4 one (20 + 40 + 40 ns); over 2
    # dispatches
    assert READ["prio.tier_scan_ms.steady"](r) == pytest.approx(85e-6)
    assert READ["prio.deletemin_ms.steady"](r) == pytest.approx(10e-6)
    merged = merge_tables(
        [tiers.scope_table(t, tiers.WAVE_PHASES)
         for _, t in r.run.q.wave_programs()])
    assert "fusion.1" not in merged and "fusion.2" not in merged
    # the widths the other way round: the second burst's fusion.2 is the
    # one in deletemin
    r = _result(10, widths=(4, 2))
    assert READ["prio.deletemin_ms.steady"](r) == pytest.approx(20e-6)


def test_host_and_device_readers_of_the_cell():
    r = _result(10)
    assert READ["prio.overflow_wait_ms.steady"](r) == pytest.approx(
        385e-6)
    assert READ["prio.device_ms.steady"](r) == pytest.approx(
        r.trace.busy_ns() / 2 * 1e-6)
    assert READ["prio.idle_pct.steady"](r) == pytest.approx(
        100.0 * (1 - 260 / 1000))


def test_readers_leave_out_what_the_program_lacks(monkeypatch):
    """A program without compiled programs, or whose wave phases lack the
    priority dispatch's two, or a trace whose dispatches do not pair with
    the traced bursts, gives no reading (and raises nothing)."""
    phased = ("prio.tier_scan_ms.steady", "prio.deletemin_ms.steady")
    for r in (_result(10, programs=False), _result(10, widths=(2,)),
              _result(10, widths=(2, 16))):
        assert ROOFLINE(r) is None
        assert all(READ[m](r) is None for m in phased)
    r = _result(10)
    r.trace = None
    assert ROOFLINE(r) is None
    assert all(READ[m](r) is None for m in READ)
    for phase in ("tier_scan", "deletemin"):
        assert phase in tiers.WAVE_PHASES
    monkeypatch.setattr(tiers, "WAVE_PHASES",
                        ("dispatch", "pack", "exchange", "commit", "reply",
                         "merge", "telemetry"))
    assert all(READ[m](_result(10)) is None for m in phased)
