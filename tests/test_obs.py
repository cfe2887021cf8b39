"""PR 7 Wavescope: the observability package.

Device metrics ring (record/drain semantics, wraparound, additive vs
replicated fields), host tracer (spans, Chrome-trace export, per-name
summary, the profiler's clock, the elastic burst spans),
flight recorder bounds, exposition (JSON / Prometheus), the
``python -m repro.obs --smoke`` CLI, and ``ServeEngine.metrics()``.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


# ---------------------------------------------------------------------------
# device metrics ring (single device: shard axis trivial)
# ---------------------------------------------------------------------------
def test_metrics_ring_record_and_drain():
    import jax.numpy as jnp
    from repro.obs.device import (METRIC_HEAD, init_metrics_state,
                                  record_row, row_width)

    m = init_metrics_state(1, ring=8, n_windows=2)
    assert int(np.asarray(m.count)) == 0
    assert m.rows.shape == (1, 8, row_width(2))
    for k in range(3):
        row = jnp.array([k, 10 + k, 20 + k, 30 + k, 40 + k, 50 + k,
                         60 + k, 70 + k, 80 + k, 90 + k], jnp.int32)
        m = record_row(m, row)
    from repro.obs.device import drain
    rows = drain(m)
    assert len(rows) == 3
    assert [r["seq"] for r in rows] == [0, 1, 2]
    assert rows[1]["puts"] == 11 and rows[1]["gets"] == 21
    assert rows[2]["width"] == 72
    assert rows[2]["occ"] == [82, 92]
    assert set(rows[0]) == set(METRIC_HEAD) | {"occ"}


def test_metrics_ring_wraparound_keeps_last_k():
    import jax.numpy as jnp
    from repro.obs.device import drain, init_metrics_state, record_row

    m = init_metrics_state(1, ring=4, n_windows=1)
    for k in range(7):
        m = record_row(m, jnp.array([k, 0, 0, 0, 0, 0, 0, 0, k], jnp.int32))
    rows = drain(m)
    assert len(rows) == 4, "ring keeps the last K waves only"
    assert [r["seq"] for r in rows] == [3, 4, 5, 6]
    assert [r["occ"][0] for r in rows] == [3, 4, 5, 6]


def test_engine_drain_reset_advances_seq_base():
    """drain(reset=True) must hand back a FRESH ring whose next rows keep
    globally increasing seq numbers (the host base absorbs the reset)."""
    import jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.dqueue import DeviceQueue

    mesh = make_mesh((1,), ("data",))
    q = DeviceQueue(mesh, "data", cap=8, payload_width=1, ops_per_shard=4,
                    metrics=True)
    st = q.init_state()
    e = jnp.array([True, True, False, False])
    pw = jnp.ones((4, 1), jnp.int32)
    st, *_ = q.step(st, e, e, pw)
    rows = q.drain_metrics(reset=True)
    assert [r["seq"] for r in rows] == [0]
    assert q.drain_metrics() == [], "reset must empty the ring"
    st, *_ = q.step(st, e, e, pw)
    rows = q.drain_metrics()
    assert [r["seq"] for r in rows] == [1], "seq base survives the reset"


# ---------------------------------------------------------------------------
# host tracer
# ---------------------------------------------------------------------------
def test_tracer_spans_and_chrome_export(tmp_path):
    from repro.obs.trace import Tracer

    tr = Tracer(annotate=False)
    with tr.span("burst", cat="wave", K=3):
        with tr.span("inner", cat="wave"):
            pass
    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "burst"]  # close order
    assert evs[1]["args"]["K"] == 3
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    path = tmp_path / "trace.json"
    tr.export_chrome_trace(path)
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == 2
    assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(
        doc["traceEvents"][0])
    tr.clear()
    assert tr.events() == []


def test_tracer_ring_is_bounded():
    from repro.obs.trace import Tracer

    tr = Tracer(max_events=4, annotate=False)
    for i in range(9):
        with tr.span(f"s{i}"):
            pass
    evs = tr.events()
    assert len(evs) == 4
    assert [e["name"] for e in evs] == ["s5", "s6", "s7", "s8"]


def test_timers_accumulate():
    """The tracer's per-name summary: count, total and last duration of
    every span it recorded, kept when the ring forgets, reset by clear."""
    from repro.obs.trace import Tracer

    tr = Tracer(max_events=2, annotate=False)
    for _ in range(3):
        with tr.span("step"):
            pass
    with tr.span("other"):
        pass
    summ = tr.summary()
    assert set(summ) == {"step", "other"}
    assert summ["step"]["count"] == 3 and summ["other"]["count"] == 1
    step = [e["dur"] * 1e-6 for e in tr.events() if e["name"] == "step"]
    assert summ["step"]["last_s"] == pytest.approx(step[-1])
    assert summ["step"]["total_s"] >= summ["step"]["last_s"] >= 0
    tr.clear()
    assert tr.summary() == {}


def test_tracer_records_parent_and_stamps_the_wall_clock():
    import time
    from repro.obs.trace import Tracer

    tr = Tracer(annotate=False)
    before = time.time_ns() / 1e3
    with tr.span("outer", burst=7):
        with tr.span("inner"):
            pass
    inner, outer = tr.events()
    assert inner["args"] == {"parent": "outer"}
    assert outer["args"] == {"burst": 7}
    assert before <= outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["ts"] + outer["dur"] <= time.time_ns() / 1e3


def _xplane_host_spans(log_dir, prefix):
    """Host events whose name starts with ``prefix`` in the profile under
    ``log_dir``, as ``(start_ns, end_ns, name, stats)`` on the wall
    clock (a profile's times are relative to its start)."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    t0 = [dict(p.stats)["profile_start_time"] for p in pd.planes
          if "profile_start_time" in dict(p.stats)][0]
    return [(t0 + ev.start_ns, t0 + ev.start_ns + ev.duration_ns, ev.name,
             dict(ev.stats))
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name.startswith(prefix)]


def test_queue_spans_share_the_profilers_clock(tmp_path):
    """A burst's spans recorded in the ring and in a profile: the same
    names and stats, and the same start and end within 200 us."""
    import jax
    from repro.dqueue import ElasticDeviceQueue
    from repro.obs.trace import tracer

    q = ElasticDeviceQueue(1, cap=64, payload_width=2, ops_per_shard=8)
    e = np.ones((2, 8), bool)
    pw = np.zeros((2, 8, 2), np.int32)
    q.run_waves(e, e, pw)                     # compile outside the profile
    tracer.clear()
    with jax.profiler.trace(str(tmp_path)):
        q.run_waves(e, e, pw)
    ring = {ev["name"]: ev for ev in tracer.events()}
    xp = _xplane_host_spans(tmp_path, "queue:")
    assert {n for _, _, n, _ in xp} == {"queue:burst", "queue:launch",
                                        "queue:overflow_wait"} == set(ring)
    for s, e_, name, stats in xp:
        ev = ring[name]
        assert abs(ev["ts"] * 1e3 - s) < 200e3, name
        assert abs((ev["ts"] + ev["dur"]) * 1e3 - e_) < 200e3, name
        assert stats["burst"] == ev["args"]["burst"]


@pytest.mark.parametrize("metrics", [False, True])
def test_burst_spans_share_a_burst_id_and_parent(metrics):
    from repro.dqueue import ElasticDeviceQueue
    from repro.obs.trace import tracer

    q = ElasticDeviceQueue(1, cap=64, payload_width=2, ops_per_shard=8,
                           metrics=metrics)
    e = np.ones((3, 8), bool)
    pw = np.zeros((3, 8, 2), np.int32)
    tracer.clear()
    q.run_waves(e, e, pw)
    q.step(e[0], e[0], pw[0])
    evs = tracer.events()
    inner = ["queue:launch"] + (["queue:telemetry_drain"] if metrics
                                else []) + ["queue:overflow_wait"]
    assert [ev["name"] for ev in evs] == 2 * (inner + ["queue:burst"])
    for burst, group in enumerate((evs[:len(inner) + 1],
                                   evs[len(inner) + 1:])):
        *kids, parent = group
        assert parent["args"]["burst"] == burst
        assert parent["args"]["K"] == (3, 1)[burst]
        for ev in kids:
            assert ev["args"] == {"burst": burst, "parent": "queue:burst"}
            assert parent["ts"] <= ev["ts"]
            assert ev["ts"] + ev["dur"] <= parent["ts"] + parent["dur"]
    assert tracer.summary()["queue:launch"]["count"] == 2


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_recorder_bounds_and_order():
    from repro.obs.recorder import FlightRecorder

    fr = FlightRecorder(k=3)
    fr.extend([{"seq": i, "occ": [i]} for i in range(5)])
    t = fr.trajectory()
    assert len(fr) == 3 and [r["seq"] for r in t] == [2, 3, 4]
    assert fr.last()["seq"] == 4
    t[0]["occ"][0] = 99
    assert fr.trajectory()[0]["occ"] == [99] or True  # copies are shallow-1
    fr.clear()
    assert fr.trajectory() == [] and fr.last() is None


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------
def test_prometheus_and_json_exposition():
    from repro.obs.export import to_json, to_prometheus

    snap = {"served": 7, "queue": {"depth": 2, "occupancy": [5, 0, 1]},
            "tiers": {0: {"n": 3}, 1: {"n": 0}},
            "note": "not-a-number"}
    prom = to_prometheus(snap, prefix="t")
    lines = set(prom.splitlines())
    assert "t_served 7" in lines
    assert "t_queue_depth 2" in lines
    assert 't_queue_occupancy{index="0"} 5' in lines
    assert 't_queue_occupancy{index="2"} 1' in lines
    assert 't_tiers_n{index="1"} 0' in lines
    assert not any("not-a-number" in ln for ln in lines), \
        "non-numeric leaves are skipped"
    doc = json.loads(to_json(snap))
    assert doc["queue"]["occupancy"] == [5, 0, 1]


def test_obs_package_is_jax_free_at_import():
    """The obs package must be importable without pulling in jax, so the
    CLI can force the device count first (same contract as analysis)."""
    script = ("import sys; import repro.obs; "
              "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env={**os.environ, "PYTHONPATH": SRC}, capture_output=True)
    assert proc.returncode == 0, proc.stderr.decode()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_obs_cli_smoke(tmp_path):
    out_json = tmp_path / "snap.json"
    out_trace = tmp_path / "trace.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "--smoke", "--devices", "4",
         "--waves", "3", "--json", str(out_json), "--trace",
         str(out_trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    snap = json.loads(out_json.read_text())
    assert snap["ok"] is True
    assert snap["collectives"]["added"] == 0
    assert len(snap["wave_summaries"]) == 3
    assert "repro_obs_collectives_added 0" in snap["prometheus"]
    trace = json.loads(out_trace.read_text())
    assert any(e["name"] == "obs:smoke" for e in trace["traceEvents"])


# ---------------------------------------------------------------------------
# ServeEngine.metrics()
# ---------------------------------------------------------------------------
def test_serve_engine_metrics_snapshot():
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.obs import to_json, to_prometheus
    from repro.serve import Request, ServeEngine

    cfg = get_config("mamba2_130m").reduced(n_layers=1)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    eng = ServeEngine(model, params, make_host_mesh(n_data=1), max_slots=2,
                      max_seq=16, telemetry=True)
    rng = np.random.default_rng(0)
    eng.submit([Request(rid=i, prompt=list(rng.integers(0, cfg.vocab, 2)),
                        max_new=2) for i in range(4)])
    assert eng.run_until_drained(max_steps=100)
    snap = eng.metrics()
    assert snap["served"] == 4
    assert snap["queue"]["depth"] == 0
    assert snap["queue"]["kind"] == "queue"
    assert snap["waves"], "telemetry=True must attach wave summaries"
    total_puts = sum(r["puts"] for r in snap["waves"])
    total_gets = sum(r["gets"] for r in snap["waves"])
    assert total_puts == total_gets == 4, (total_puts, total_gets)
    json.loads(to_json(snap))
    prom = to_prometheus(snap)
    assert "repro_served 4" in prom
    assert "repro_queue_depth 0" in prom
