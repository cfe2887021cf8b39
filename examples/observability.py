"""Wavescope observability (PR 7): watch the queue without slowing it.

1. device metrics — every wave leaves one summary row in a donated
   device-side ring (ZERO extra collectives); drained at burst ends,
2. host tracing — span API (each burst's launch and overflow wait
   included) + per-name summary + Chrome-trace/perfetto export,
3. flight recorder — an overflow arrives with the occupancy trajectory
   that led to it,
4. exposition — ServeEngine.metrics() -> JSON / Prometheus text.

Run:  PYTHONPATH=src python examples/observability.py
"""
import numpy as np

import jax

from repro.obs import span, to_prometheus, tracer


def section_device_metrics():
    """§1 every wave records one metrics row, free of collectives."""
    from repro.dqueue import ElasticDeviceQueue

    q = ElasticDeviceQueue(len(jax.devices()), cap=256, payload_width=2,
                           ops_per_shard=8, metrics=True)
    n = q.n_shards * 8
    rng = np.random.default_rng(0)
    K = 6
    is_enq = rng.random((K, n)) < 0.7
    valid = rng.random((K, n)) < 0.8
    payload = rng.integers(0, 99, (K, n, 2)).astype(np.int32)
    q.run_waves(is_enq, valid, payload)
    rows = q.trajectory()   # drained into the flight recorder at burst end
    burst = tracer.summary()["queue:burst"]
    print(f"[device]   {len(rows)} wave rows drained after one "
          f"{burst['last_s'] * 1e3:.1f} ms burst:")
    for r in rows[:3]:
        print(f"           wave {r['seq']}: +{r['puts']} puts "
              f"-{r['gets']} gets  occ={r['occ']}  "
              f"headroom={r['headroom']}")
    return q


def section_tracing(tmp="wavescope_trace.json"):
    """§2 spans nest, annotate jax profiles, and export a perfetto trace."""
    with span("example:outer", cat="demo", note=1):
        with span("example:inner", cat="demo"):
            pass
    path = tracer.export_chrome_trace(tmp)
    names = [e["name"] for e in tracer.events()]
    print(f"[trace]    {len(names)} spans recorded; open {path} in "
          "ui.perfetto.dev.  Per name (count, total):")
    for name, s in tracer.summary().items():
        print(f"           {name:24s} {s['count']:3d} "
              f"{s['total_s'] * 1e3:9.3f} ms")


def section_flight_recorder():
    """§3 an overflow carries the occupancy ramp that caused it."""
    from repro.dqueue import ElasticDeviceQueue, QueueOverflowError

    q = ElasticDeviceQueue(1, cap=8, payload_width=1, ops_per_shard=4,
                           metrics=True)
    e = np.array([True, True, True, False])       # net +2 per wave
    v = np.array([True, True, True, True])
    pw = np.ones((4, 1), np.int32)
    try:
        for _ in range(8):
            q.step(e, v, pw)
    except QueueOverflowError as err:
        ramp = [r["occ"][0] for r in err.trajectory]
        print(f"[recorder] overflow at cap=8; flight recorder replays the "
              f"occupancy ramp {ramp}")


def section_serve_metrics():
    """§4 ServeEngine.metrics() -> Prometheus text exposition."""
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.serve import Request, ServeEngine

    cfg = get_config("mamba2_130m").reduced(n_layers=1)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    eng = ServeEngine(model, params, make_host_mesh(n_data=1), max_slots=2,
                      max_seq=16, telemetry=True)
    rng = np.random.default_rng(0)
    eng.submit([Request(rid=i, prompt=list(rng.integers(0, cfg.vocab, 2)),
                        max_new=2) for i in range(3)])
    eng.run_until_drained(max_steps=100)
    snap = eng.metrics()
    prom = to_prometheus(snap)
    print(f"[serve]    served={snap['served']} over {len(snap['waves'])} "
          "queue waves; Prometheus exposition (excerpt):")
    for line in prom.splitlines():
        if line.startswith(("repro_served", "repro_queue_depth",
                            "repro_queue_occupancy")):
            print(f"           {line}")


def main():
    section_device_metrics()
    section_tracing()
    section_flight_recorder()
    section_serve_metrics()


if __name__ == "__main__":
    main()
