"""The readings of the program's own records on synthetic traces: host
spans in the window, device self time by wave phase, and the compile
counter; and that each reader leaves its metric out where the program
records nothing."""
from types import SimpleNamespace

import numpy as np
import pytest

from tiny import ROOT  # noqa: F401  (puts the repo on the path)
from bench.harness import Layout, Result
from bench.program import merge_tables, phase_ns, self_time
from bench.trace import DeviceOps, Trace, WINDOW_SPAN

LAYOUT = Layout()
PHASED = ["wave.dispatch_ms.sat", "wave.pack_ms.sat", "wave.commit_ms.sat",
          "wave.reply_ms.sat"]
SPANNED = ["queue.launch_ms.sat", "queue.overflow_wait_ms.sat",
           "queue.overflow_wait_ms.steady"]

# the wave program as a device trace shows it: the burst's scan holds a
# per-row loop, whose body runs once a row, and the phases' own ops; a
# relayout copy has no scope
TABLE = {"while.2": "reply", "fusion.3": "reply", "fusion.4": "dispatch",
         "fusion.5": "commit", "fusion.6": "pack"}
TPU0 = [(0, 90, "while.1"), (10, 60, "while.2"), (12, 20, "fusion.3"),
        (22, 30, "fusion.3"), (40, 58, "fusion.3"), (62, 70, "fusion.4"),
        (70, 80, "fusion.5"), (80, 85, "fusion.6"), (92, 98, "copy.7")]
# the second chip: the same program, one row fewer, and an op cut by the
# window's end
TPU1 = [(0, 90, "while.1"), (10, 60, "while.2"), (12, 20, "fusion.3"),
        (22, 30, "fusion.3"), (62, 70, "fusion.4"), (70, 80, "fusion.5"),
        (80, 85, "fusion.6"), (95, 120, "copy.7")]
# per chip: (none) is the scan's own time and the copy
EXPECT = {"(none)": ([10 + 2 + 5 + 6], [10 + 2 + 5 + 5]),
          "reply": ([16 + 34], [34 + 16]), "dispatch": ([8], [8]),
          "commit": ([10], [10]), "pack": ([5], [5])}


def _trace(window=(0, 100)):
    devices = {}
    for dev, evs in (("/device:TPU:0", TPU0), ("/device:TPU:1", TPU1)):
        names = sorted({n for _, _, n in evs})
        devices[dev] = DeviceOps(
            np.array([s for s, _, _ in evs], float),
            np.array([e for _, e, _ in evs], float),
            np.array([names.index(n) for _, _, n in evs]), names,
            [""] * len(names))
    host = [(1, 3, "queue:launch"), (3, 60, "queue:overflow_wait"),
            (61, 62, "queue:launch"), (62, 98, "queue:overflow_wait"),
            (101, 130, "queue:launch"),            # after the window
            (window[0], window[1], WINDOW_SPAN)]
    return Trace(devices, host, window)


def _result(trace, q, bursts=2):
    run = SimpleNamespace(q=q, window_counts=lambda: {"bursts": bursts})
    return Result(run, {}, 0.0, {}, trace)


def test_self_time_gives_each_instant_to_the_innermost_event():
    s = np.array([0, 10, 12, 22, 40, 92, 5.0])
    e = np.array([90, 60, 20, 30, 58, 98, 95.0])   # the last one overlaps
    own = self_time(s, e)
    assert own.tolist() == [5, 16, 8, 8, 18, 6, 37]
    assert own.sum() == 98                            # the union of [0, 98]


def test_phases_and_none_add_up_to_the_busy_time():
    tr = _trace()
    got = phase_ns(tr, TABLE)
    assert set(got) == set(EXPECT)
    for phase, per_chip in EXPECT.items():
        assert got[phase] == pytest.approx(np.mean([sum(c) for c in
                                                    per_chip]))
    assert sum(got.values()) == pytest.approx(tr.busy_ns())


def test_tables_of_two_programs_keep_only_the_names_they_agree_on():
    merged = merge_tables([{"fusion.1": "pack", "fusion.2": "commit"},
                           {"fusion.1": "pack", "fusion.2": "reply",
                            "while.3": "reply"}])
    assert merged == {"fusion.1": "pack", "while.3": "reply"}


def test_readers_of_the_programs_records():
    q = SimpleNamespace(wave_phases=lambda: [TABLE])
    res = _result(_trace(), q)
    ms = {m: LAYOUT.metric(m).read(res) for m in PHASED + SPANNED}
    assert ms["wave.dispatch_ms.sat"] == pytest.approx(8 / 2 * 1e-6)
    assert ms["wave.pack_ms.sat"] == pytest.approx(5 / 2 * 1e-6)
    assert ms["wave.commit_ms.sat"] == pytest.approx(10 / 2 * 1e-6)
    assert ms["wave.reply_ms.sat"] == pytest.approx(50 / 2 * 1e-6)
    assert ms["queue.launch_ms.sat"] == pytest.approx(1.5e-6)
    assert ms["queue.overflow_wait_ms.sat"] == pytest.approx(46.5e-6)
    assert ms["queue.overflow_wait_ms.steady"] == pytest.approx(46.5e-6)


def test_readers_leave_out_what_the_program_does_not_record():
    """A program without spans, phase table or compile counter (the
    parent of these metrics) gives no value and raises nothing."""
    tr = _trace()
    tr.host = [h for h in tr.host if not h[2].startswith("queue:")]
    res = _result(tr, SimpleNamespace())
    for m in PHASED + SPANNED:
        assert LAYOUT.metric(m).read(res) is None, m
    untraced = _result(None, SimpleNamespace(wave_phases=lambda: [TABLE]))
    for m in PHASED + SPANNED:
        assert LAYOUT.metric(m).read(untraced) is None, m


def test_compile_seconds_come_from_the_programs_counter():
    import jax
    import jax.numpy as jnp
    from repro.analysis import CompilationTracker

    CompilationTracker.install()
    before = LAYOUT.metric("setup.compile_s").read(None)
    jax.jit(lambda x: x * 3 - 1)(jnp.arange(11)).block_until_ready()
    after = LAYOUT.metric("setup.compile_s").read(None)
    assert after > before >= 0
