"""The trace reductions on synthetic event lists, and the byte counts
the roofline shares are built from."""
import numpy as np
import pytest

from tiny import ROOT  # noqa: F401  (puts the repo on the path)
from bench.harness import Layout, Result, peaks_for
from bench.trace import (DeviceOps, Trace, WINDOW_SPAN, exposed, gaps,
                         instruction, length, union)

LAYOUT = Layout()


def test_busy_union_with_overlapping_events():
    ev = [(10, 20), (15, 30), (40, 45), (44, 50), (0, 5), (60, 60)]
    s, e = union(ev)
    assert s.tolist() == [0, 10, 40] and e.tolist() == [5, 30, 50]
    assert length(ev) == 5 + 20 + 10
    assert gaps(ev, 0, 70) == [(5.0, 10.0), (30.0, 40.0), (50.0, 70.0)]


def test_exposed_collective_arithmetic():
    # a2a [0,10) and [20,30); compute covers 5-8, 9-22, 29-40
    a2a, compute = [(0, 10), (20, 30)], [(5, 8), (9, 22), (29, 40)]
    assert exposed(a2a, compute) == (10 - 3 - 1) + (10 - 2 - 1)
    assert exposed(a2a, []) == 20
    assert exposed(a2a, [(-5, 50)]) == 0


def _trace(events_per_device, host=(), window=(0, 100)):
    devices = {}
    for dev, evs in events_per_device.items():
        names = sorted({n for _, _, n in evs})
        devices[dev] = DeviceOps(
            np.array([s for s, _, _ in evs], float),
            np.array([e for _, e, _ in evs], float),
            np.array([names.index(n) for _, _, n in evs]),
            [instruction(n)[0] for n in names],
            [instruction(n)[1] for n in names])
    host = list(host) + [(window[0], window[1], WINDOW_SPAN)]
    return Trace(devices, host, window)


A2A = "%all-to-all.1 = s32[8,4]{1,0} all-to-all(s32[8,4]{1,0} %x)"
FUSION = "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop"
KERNEL = ('%scan.3 = s32[8]{0} custom-call(s32[8]{0} %p), '
          'custom_call_target="tpu_custom_call"')


def test_trace_busy_exposed_and_kernel_time_average_over_devices():
    tr = _trace({"/device:TPU:0": [(0, 10, A2A), (5, 20, FUSION),
                                   (30, 40, KERNEL)],
                 "/device:TPU:1": [(0, 10, A2A), (50, 70, KERNEL)]},
                host=[(20, 30, "bench:readback"),
                      (45, 110, "bench:dispatch")])
    assert tr.busy_ns() == np.mean([30, 30])
    assert tr.exposed_ns("all-to-all") == np.mean([5, 10])
    assert tr.kind_ns("tpu_custom_call") == np.mean([10, 20])
    top = tr.top_ops(2)
    assert [n for n, _ in top] == ["scan.3", "all-to-all.1"]
    assert [t for _, t in top] == pytest.approx([30e-9, 20e-9])
    idle = dict(tr.idle_by_host_span())
    assert idle.keys() == {"bench:dispatch", "bench:readback"}
    assert idle["bench:dispatch"] == pytest.approx(60e-9)
    assert idle["bench:readback"] == pytest.approx(10e-9)


def test_events_outside_the_window_do_not_count():
    tr = _trace({"/device:TPU:0": [(-50, 10, FUSION), (90, 150, FUSION)]})
    assert tr.busy_ns() == 20 and tr.window_ns == 100


def _metric(name, counts, busy_ns=None, chips=1, trace=None):
    c = LAYOUT.cell("msglog.saturate")

    class Run:
        n = chips

        @staticmethod
        def window_counts():
            return counts

    Run.cell = c
    res = Result(Run(), {}, 0.0, peaks_for("TPU v5 lite"))

    class T:
        window_ns = 1e9

        @staticmethod
        def busy_ns():
            return busy_ns

    res.trace = trace or T()
    return LAYOUT.metric(name).read(res)


def test_hbm_roofline_counts_the_work_not_the_shapes():
    mod = LAYOUT.metric("wave.hbm_roofline_pct.sat")
    counts = {"bursts": 2, "ops": 1000, "enqueues": 500, "dequeued": 400}
    need = mod.work_bytes(counts, 256, {"descriptor": 2, "reply": 3})
    assert need == 4 * (2 * 256 * 900 + 5 * 1000)
    busy = 1e-3 * 1e9                          # 1 ms of device time
    got = _metric("wave.hbm_roofline_pct.sat", counts, busy_ns=busy)
    assert got == pytest.approx(100 * need / 819e9 / 1e-3)
    # four chips share the bytes, each with its own bandwidth
    got4 = _metric("wave.hbm_roofline_pct.sat", counts, busy_ns=busy,
                   chips=4)
    assert got4 == pytest.approx(got / 4)


def test_exposed_all_to_all_is_per_burst_and_silent_without_one():
    counts = {"bursts": 2, "ops": 1000, "enqueues": 500, "dequeued": 400}
    # per device: a2a [0,10e6) half under compute, and [20e6,30e6) bare
    ops = [(0, 10e6, A2A), (5e6, 10e6, FUSION), (20e6, 30e6, A2A)]
    tr = _trace({"/device:TPU:0": ops, "/device:TPU:1": ops},
                window=(0, 40e6))
    got = _metric("a2a.exposed_ms", counts, chips=2, trace=tr)
    assert got == pytest.approx((5 + 10) / 2)
    alone = _trace({"/device:TPU:0": [(0, 10e6, FUSION)]}, window=(0, 40e6))
    assert _metric("a2a.exposed_ms", counts, trace=alone) is None


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks_for("TPU v9")


@pytest.mark.parametrize("text,name,kind", [
    ("%while.15 = (s32[]{:T(128)}, s32[4]{0:T(128)}) while((s32[]{:T(128)}"
     ", s32[4]{0:T(128)}) %tuple.1), condition=%c, body=%b",
     "while.15", "while"),
    (KERNEL, "scan.3", "custom-call:tpu_custom_call"),
    ("%dynamic-slice_bitcast_fusion.14 = s32[1,8]{1,0:T(1,128)S(1)} "
     "fusion(s32[1,8192,9]{2,1,0:T(8,128)S(1)} %g), kind=kLoop",
     "dynamic-slice_bitcast_fusion.14", "fusion"),
    (A2A, "all-to-all.1", "all-to-all"),
    ("not an instruction", "not an instruction", ""),
])
def test_instruction_names_and_kinds(text, name, kind):
    assert instruction(text) == (name, kind)
