"""Framework microbenchmarks: scan-queue ops, device-queue steps, kernels.

PR 1 adds the wave-pipeline benchmark: the seed single-wave dispatch
discipline (one jitted step per wave, host round-trip between waves,
five all_to_all collectives per wave) vs. the fused path (two collectives
per wave, donated state, K waves inside one lax.scan dispatch).  Results
are written to ``BENCH_PR1.json``; run directly with

    PYTHONPATH=src python -m benchmarks.micro --pr1 [path]

PR 2 adds the elastic-membership benchmark: steady-state waves/sec through
the ``ElasticDeviceQueue`` wrapper vs. the raw PR 1 fused path (acceptance:
within 10%), and the reshard cost of live grow/shrink migrations —
elements moved, bytes, collectives per migration (one packed all_to_all),
and wall time split into the jitted wave vs. the total including the
host-staged mesh crossing.  Results go to ``BENCH_PR2.json``:

    PYTHONPATH=src python -m benchmarks.micro --pr2 [path] [--quick]

PR 3 adds the priority-tier mixed-load benchmark: interactive + batch
traffic at identical arrival schedules and identical service capacity
through (a) the single-tier FIFO ``DeviceQueue`` and (b) the two-tier
``DevicePriorityQueue`` — per-class wait distributions (p50/p99 in waves)
show the tail-latency separation the priority fabric buys, plus the
steady-state wave overhead of the priority path and its collective count.
Results go to ``BENCH_PR3.json``:

    PYTHONPATH=src python -m benchmarks.micro --pr3 [path] [--quick]

PR 4 adds two measurements.  (a) The wave-pipelining benchmark: K-wave
bursts through the unified WaveEngine with the sequential schedule
(``pipelined=False``: request + reply all_to_all per wave, one wave at a
time) vs. the software-pipelined schedule (``pipelined=True``: wave k's
dispatch overlaps wave k-1's store rewrite and the two collectives fuse
into ONE all_to_all per wave) — waves/sec and static collective counts
for all three disciplines.  Results go to ``BENCH_PR4.json``:

    PYTHONPATH=src python -m benchmarks.micro --pr4 [path] [--quick]

(b) The ROADMAP relaxation study, folded into ``BENCH_PR3.json``: a
``relaxation=k`` sweep (k in {0, 1, 2}) under tier-skewed traffic with
per-shard dequeues, reporting the local-serve fraction (serves that avoid
the cross-shard hop) against the tier skew it costs.

PR 5 adds the deadline-scheduling benchmark: bursty traffic whose
per-request slack is continuous AND drifts mid-run, at the SAME arrival
schedule and SAME per-wave service capacity through (a) the single-tier
FIFO queue, (b) the two-tier priority queue with the best static cut
(the trace median — which each phase of a drifting distribution lands
almost entirely on one side of, degenerating to FIFO) and (c) the Seap
arbitrary-key queue with key = deadline wave — earliest-deadline-first
at bucket granularity, the directory rolling with the drift.
Deadline-miss rates and lateness per urgency band show what EDF buys
over both.  Results go to ``BENCH_PR5.json``:

    PYTHONPATH=src python -m benchmarks.micro --pr5 [path] [--quick]

PR 7 adds the Wavescope telemetry-cost benchmark: the SAME pipelined
K-wave burst with ``metrics=False`` vs ``metrics=True``, timed with the
two flavors interleaved inside one best-of loop (machine drift cancels),
plus the static all_to_all count of both lowered programs (must match:
telemetry adds ZERO collectives) and the burst-boundary drain cost timed
separately.  Results go to ``BENCH_PR7.json``:

    PYTHONPATH=src python -m benchmarks.micro --pr7 [path] [--quick]

PR 8 adds the backpressure benchmark: the SAME bursty arrival schedule
(10x bursts over a steady near-capacity base rate) through a ServeEngine
with (a) no admission policy — staging is unconditional and the burst
overflows the queue mid-wave, (b) shed, (c) defer, (d) degrade, and
(e) shed plus the hysteresis autoscale controller.  The baseline must
overflow; every policy must sustain ZERO QueueOverflowError, trading it
for structured sheds/spills — goodput, shed rate, resize count, and p99
admission-decision latency per flavor.  Results go to
``BENCH_PR8.json``:

    PYTHONPATH=src python -m benchmarks.micro --pr8 [path] [--quick]

PR 9 adds the compact-waves benchmark: the SAME logical op stream at 5% /
25% / 100% of the full wave envelope, staged (a) compact — each wave at
the smallest bucket-ladder width {L/4, L/2, L} that fits its live ops —
vs (b) padded to the full ``n_shards * L`` envelope, for the FIFO and
priority disciplines.  Bit-identical per-op outputs and final device
state are ASSERTED inside the emitter (compaction must not change one
answer), as is the headline >= 1.3x waves/sec at <= 25% occupancy.  A
second section times the segscan dispatch modes: the jnp core scan (the
compiled-XLA oracle / CPU hot path) vs the pallas kernel in interpret
mode, plus compiled pallas on TPU/GPU.  Results go to
``BENCH_PR9.json``, and ``benchmarks.gate`` compares them against the
committed ``BENCH_BASELINE.json`` floors (CI fails a >25% regression):

    PYTHONPATH=src python -m benchmarks.micro --pr9 [path] [--quick]
    PYTHONPATH=src python -m benchmarks.gate BENCH_PR9.json

**PR 10 (the runtime seam).**  ``BENCH_PR10.json`` prices the pluggable
mesh-runtime layer: a LocalRuntime parity section (the runtime-built
wave path asserted BIT-identical to the bare-mesh PR 9 path), a
SimRuntime latency sweep (steady-state waves/sec and the migration-wave
cost under modeled per-collective costs of 0 us / 100 us / 1 ms), and
the same measurement over a REAL wire — 2 ``jax.distributed`` processes
on localhost TCP via ``repro.runtime.launch_localhost``:

    PYTHONPATH=src python -m benchmarks.micro --pr10 [path] [--quick]

``--all [--quick]`` runs EVERY emitter above (the CI bench-smoke entry
point: one invocation emits every BENCH_PR*.json, and any emitter crash
fails the run — future PRs add an emitter here instead of editing the
workflow).  PR numbers with no benchmark (PR 6, the static analyzer)
are listed in ``_NO_BENCH`` and reported with an explicit skip line
instead of a silent hole in the artifact.  Each emitter re-execs itself
on a forced 8-device CPU mesh when needed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _time_us(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_scan_queue():
    from repro.core.scan_queue import QueueState, queue_scan
    rows = []
    for n in (1024, 16384, 262144):
        rng = np.random.default_rng(0)
        e = jnp.array(rng.random(n) < 0.6)
        v = jnp.ones((n,), bool)
        f = jax.jit(lambda a, b: queue_scan(a, QueueState.empty(), valid=b))
        us = _time_us(f, e, v)
        rows.append((f"scan_queue_n{n}", us, f"{n/us:.1f} ops/us"))
    return rows


def bench_segscan_kernel():
    from repro.kernels.segscan import queue_scan_pallas
    rows = []
    n = 4096
    rng = np.random.default_rng(1)
    e = jnp.array(rng.random(n) < 0.5)
    v = jnp.ones((n,), bool)
    us = _time_us(lambda a, b: queue_scan_pallas(a, b, jnp.int32(0),
                                                 jnp.int32(-1)), e, v,
                  iters=5)
    rows.append((f"segscan_pallas_interp_n{n}", us,
                 "interpret-mode (correctness path)"))
    return rows


def bench_device_queue():
    from repro.dqueue import DeviceQueue
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(n_data=len(jax.devices()))
    dq = DeviceQueue(mesh, "data", cap=1024, payload_width=4,
                     ops_per_shard=256)
    n = dq.n_shards * dq.L
    rng = np.random.default_rng(2)
    is_enq = jnp.array(rng.random(n) < 0.6)
    valid = jnp.ones((n,), bool)
    payload = jnp.array(rng.integers(0, 100, (n, 4)), jnp.int32)

    # the step donates its state argument, so thread it through the loop
    state = dq.init_state()
    for _ in range(3):  # warmup
        state = dq.step(state, is_enq, valid, payload)[0]
    jax.block_until_ready(state.store_full)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        state = dq.step(state, is_enq, valid, payload)[0]
    jax.block_until_ready(state.store_full)
    us = (time.perf_counter() - t0) / iters * 1e6
    return [(f"device_queue_step_{n}ops", us, f"{n/us:.2f} ops/us")]


# ------------------------------------------------- PR 1: wave pipeline -----
def count_all_to_all(jitted, args) -> int:
    """Number of all-to-all collectives in the compiled HLO of ``jitted``."""
    import re
    txt = jitted.lower(*args).compile().as_text()
    return len(re.findall(r"all-to-all(?:-start)?\(", txt))


def _measure_wave_pipeline(n_dev: int, K: int, ops_per_shard: int = 64,
                           iters: int = 10) -> dict:
    from repro.compat import make_mesh
    from repro.dqueue import DeviceQueue
    mesh = make_mesh((n_dev,), ("data",))
    kwargs = dict(cap=max(256, K * ops_per_shard // n_dev + 1),
                  payload_width=4, ops_per_shard=ops_per_shard)
    legacy = DeviceQueue(mesh, "data", fused=False, **kwargs)
    fused = DeviceQueue(mesh, "data", **kwargs)
    n = n_dev * ops_per_shard
    rng = np.random.default_rng(5)
    E = jnp.array(rng.random((K, n)) < 0.5)
    V = jnp.ones((K, n), bool)
    PW = jnp.array(rng.integers(0, 100, (K, n, 4)), jnp.int32)
    # pre-split per-wave inputs so slicing is not charged to the seed path
    Es = [E[k] for k in range(K)]
    Vs = [V[k] for k in range(K)]
    Ps = [PW[k] for k in range(K)]

    def run_single_wave_loop():
        # the seed dispatch discipline: one jitted call per wave with a host
        # round-trip (bool(overflow)) between waves, exactly what the seed
        # ServeEngine/WorkQueue did.
        state = legacy.init_state()
        for k in range(K):
            state, pos, m, dv, dok, ovf = legacy.step(
                state, Es[k], Vs[k], Ps[k])
            assert not bool(ovf)
        jax.block_until_ready(state.store_full)

    def run_fused_multi_wave():
        state = fused.init_state()
        out = fused.run_waves(state, E, V, PW)
        jax.block_until_ready(out[0].store_full)

    def best_time(fn):
        fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_single = best_time(run_single_wave_loop)
    t_fused = best_time(run_fused_multi_wave)

    step_args = (legacy.init_state(), E[0], V[0], PW[0])
    coll_legacy = count_all_to_all(legacy._step, step_args)
    step_args = (fused.init_state(), E[0], V[0], PW[0])
    coll_fused = count_all_to_all(fused._step, step_args)
    return {
        "n_dev": n_dev, "K": K, "ops_per_wave": n,
        "seed_single_wave": {
            "waves_per_sec": K / t_single,
            "us_per_wave": t_single / K * 1e6,
            "collectives_per_wave": coll_legacy,
        },
        "fused_multi_wave": {
            "waves_per_sec": K / t_fused,
            "us_per_wave": t_fused / K * 1e6,
            "collectives_per_wave": coll_fused,
        },
        "speedup_waves_per_sec": t_single / t_fused,
    }


def _reexec_on_mesh(tag: str, path: str, n_dev: int, child_args: list):
    """Re-run ``benchmarks.micro`` in a subprocess on a forced ``n_dev``
    CPU mesh and return its JSON, or None if this process already has the
    right mesh (or IS the child).  Drops any pre-existing device-count flag
    (last one wins in XLA flag parsing) and marks the child so it never
    re-execs itself.

    These emitters time XLA:CPU only.  On an accelerator this process
    already holds the device, and a CPU-forced child would measure the
    host instead, so it refuses: the chip path is ``chip_smoke.py``."""
    in_child = os.environ.get(f"_REPRO_BENCH_{tag}_CHILD") == "1"
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"benchmarks.micro times the forced-device CPU mesh only; this "
            f"process runs on {jax.default_backend()!r}.  Run chip_smoke.py "
            f"at the repo root for the accelerator path.")
    if in_child or len(jax.devices()) == n_dev:
        return None
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n_dev}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env[f"_REPRO_BENCH_{tag}_CHILD"] = "1"
    env["PYTHONPATH"] = (os.path.join(_REPO_ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    subprocess.run([sys.executable, "-m", "benchmarks.micro"] + child_args,
                   cwd=_REPO_ROOT, env=env, check=True)
    with open(path) as f:
        return json.load(f)


def emit_bench_pr1(path: str = "BENCH_PR1.json", n_dev: int = 8,
                   K: int = 32, quick: bool = False) -> dict:
    """Measure the wave pipeline on an ``n_dev`` CPU mesh and write JSON."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    if quick:
        K = min(K, 8)
    child = _reexec_on_mesh("PR1", path, n_dev,
                            ["--pr1", path, "--n-dev", str(n_dev),
                             "--waves", str(K)]
                            + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_wave_pipeline(n_dev=n_dev, K=K,
                                  iters=3 if quick else 10)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


# ----------------------------------------- PR 2: elastic membership --------
def _measure_elastic(n_dev: int, K: int, ops_per_shard: int = 64,
                     iters: int = 10, quick: bool = False) -> dict:
    from repro.compat import make_mesh
    from repro.dqueue import DeviceQueue, ElasticDeviceQueue
    if quick:
        K, iters = min(K, 8), 3
    cap = max(256, K * ops_per_shard // n_dev + 1)
    kwargs = dict(cap=cap, payload_width=4, ops_per_shard=ops_per_shard)
    n = n_dev * ops_per_shard
    rng = np.random.default_rng(5)
    E = jnp.array(rng.random((K, n)) < 0.5)
    V = jnp.ones((K, n), bool)
    PW = jnp.array(rng.integers(0, 100, (K, n, 4)), jnp.int32)

    # ---- steady state: raw fused path vs. the elastic wrapper ----
    mesh = make_mesh((n_dev,), ("data",))
    dq = DeviceQueue(mesh, "data", **kwargs)
    eq = ElasticDeviceQueue(n_dev, hlo_stats=True, **kwargs)

    def run_fused():
        state = dq.init_state()
        out = dq.run_waves(state, E, V, PW)
        jax.block_until_ready(out[0].store_full)

    def run_elastic():
        eq.state = eq.inner.init_state()  # fresh state (donated each burst)
        eq.run_waves(E, V, PW)
        jax.block_until_ready(eq.state.store_full)

    def best_time(fn):
        fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_fused = best_time(run_fused)
    t_elastic = best_time(run_elastic)

    # ---- reshard cost: grow/shrink cycles with a loaded queue ----
    P_lo = max(1, n_dev // 2)
    eq2 = ElasticDeviceQueue(P_lo, hlo_stats=True, **kwargs)
    fill = min(P_lo * cap, 256 if quick else 2048)
    done = 0
    while done < fill:
        w = eq2.n_shards * eq2.L
        k = min(w, fill - done)
        e = np.zeros(w, bool)
        e[:k] = True
        pw = np.zeros((w, 4), np.int32)
        pw[:k, 0] = np.arange(done, done + k)
        eq2.step(e, e, pw)
        done += k
    eq2.resize(n_dev)  # warm both migration programs (compile outside timing)
    eq2.resize(P_lo)
    eq2.migrations.clear()
    for _ in range(2 if quick else 5):
        eq2.resize(n_dev)
        eq2.resize(P_lo)

    def summarize(kind):
        ms = [m for m in eq2.migrations if m["kind"] == kind]
        return {
            "migrations": len(ms),
            "moved_per_migration": ms[0]["moved"],
            "bytes_per_migration": ms[0]["bytes_moved"],
            "collectives_per_migration": ms[0]["collectives"],
            "wave_ms_best": min(m["wave_s"] for m in ms) * 1e3,
            "wave_ms_mean": sum(m["wave_s"] for m in ms) / len(ms) * 1e3,
            "total_ms_mean": sum(m["total_s"] for m in ms) / len(ms) * 1e3,
        }

    return {
        "n_dev": n_dev, "K": K, "ops_per_wave": n, "live_elements": fill,
        "steady_state": {
            "fused_device_queue_waves_per_sec": K / t_fused,
            "elastic_wrapper_waves_per_sec": K / t_elastic,
            "overhead_pct": (t_elastic - t_fused) / t_fused * 100.0,
        },
        "reshard": {
            f"grow_{P_lo}_to_{n_dev}": summarize("grow"),
            f"shrink_{n_dev}_to_{P_lo}": summarize("shrink"),
        },
        "hash_balance_last": eq2.migrations[-1].get("hash_balance"),
    }


def emit_bench_pr2(path: str = "BENCH_PR2.json", n_dev: int = 8,
                   K: int = 32, quick: bool = False) -> dict:
    """Measure elastic steady-state + reshard cost and write JSON
    (re-execs on a forced ``n_dev``-device CPU mesh when needed)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR2", path, n_dev,
        ["--pr2", path, "--n-dev", str(n_dev), "--waves", str(K)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_elastic(n_dev=n_dev, K=K, quick=quick)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


# -------------------------------- PR 3: priority tiers, mixed load ---------
def _measure_priority_mixed(n_dev: int, quick: bool = False) -> dict:
    """Interactive + batch traffic at the SAME arrival schedule and the
    SAME per-wave service capacity through the single-tier FIFO queue vs.
    the two-tier priority queue.  The total queue size evolves identically
    in both runs (arrivals and dequeue capacity are equal), so throughput
    is equal by construction — the difference is WHO waits: FIFO makes
    interactive requests queue behind every batch burst, the priority wave
    admits them first."""
    from repro.compat import make_mesh
    from repro.dqueue import DeviceQueue, DevicePriorityQueue

    L, W, C = 16, 2, 8                 # wave width / payload / service cap
    waves = 48 if quick else 160
    inter_rate = 2                     # interactive arrivals per wave
    batch_burst, batch_every = 32, 4   # avg 8/wave: with the interactive
    #                                    traffic the arrival window is
    #                                    oversubscribed (10 > C=8), so batch
    #                                    backlog grows until the drain tail
    iters = 3 if quick else 10
    cap = 4096                         # per shard (and per tier) — ample
    mesh = make_mesh((n_dev,), ("data",))
    n = n_dev * L
    INTER_BASE = 1_000_000             # rid space: class = rid >= base

    def arrivals(w):
        out = [(0, INTER_BASE + w * 64 + i) for i in range(inter_rate)]
        if w % batch_every == 0:
            out += [(1, w * 64 + i) for i in range(batch_burst)]
        return out

    def run(use_priority):
        if use_priority:
            q = DevicePriorityQueue(mesh, "data", n_prios=2, cap=cap,
                                    payload_width=W, ops_per_shard=L)
        else:
            q = DeviceQueue(mesh, "data", cap=cap, payload_width=W,
                            ops_per_shard=L)
        state = q.init_state()
        enq_wave = {}
        waits = {0: [], 1: []}
        backlog, w = 0, 0
        while w < waves or backlog > 0:   # drain tail: serve EVERY request
            arr = arrivals(w) if w < waves else []
            e = np.zeros(n, bool)
            v = np.zeros(n, bool)
            pr = np.zeros(n, np.int32)
            pw = np.zeros((n, W), np.int32)
            for j, (p, rid) in enumerate(arr):
                e[j] = v[j] = True
                pr[j] = p
                pw[j, 0] = rid
                enq_wave[rid] = w
            v[len(arr):len(arr) + C] = True          # C dequeue requests
            if use_priority:
                state, _, _, _, dv, dok, ovf, _ = q.step(
                    state, jnp.array(e), jnp.array(v), jnp.array(pr),
                    jnp.array(pw))
            else:
                state, _, _, dv, dok, ovf = q.step(
                    state, jnp.array(e), jnp.array(v), jnp.array(pw))
            assert not bool(np.asarray(ovf).any())
            dv, dok = np.asarray(dv), np.asarray(dok)
            served = 0
            for i in range(n):
                if dok[i]:
                    rid = int(dv[i, 0])
                    served += 1
                    waits[0 if rid >= INTER_BASE else 1].append(
                        w - enq_wave.pop(rid))
            backlog += len(arr) - served
            w += 1
        return waits, w

    def pct(xs):
        a = np.asarray(xs, np.float64)
        return {"n": len(xs), "mean": float(a.mean()),
                "p50": float(np.percentile(a, 50)),
                "p99": float(np.percentile(a, 99)),
                "max": float(a.max())}

    fifo_waits, fifo_total = run(False)
    pq_waits, pq_total = run(True)
    assert fifo_total == pq_total, "throughput diverged between runs"

    # ---- steady-state wave rate + collective count of the priority path ---
    K = 8 if quick else 32
    rng = np.random.default_rng(5)
    E = jnp.array(rng.random((K, n)) < 0.5)
    V = jnp.ones((K, n), bool)
    PR = jnp.array(rng.integers(0, 2, (K, n)), jnp.int32)
    PW = jnp.array(rng.integers(0, 100, (K, n, W)), jnp.int32)
    fifo = DeviceQueue(mesh, "data", cap=cap, payload_width=W,
                       ops_per_shard=L)
    pq = DevicePriorityQueue(mesh, "data", n_prios=2, cap=cap,
                             payload_width=W, ops_per_shard=L)

    def run_fifo():
        out = fifo.run_waves(fifo.init_state(), E, V, PW)
        jax.block_until_ready(out[0].store_full)

    def run_pq():
        out = pq.run_waves(pq.init_state(), E, V, PR, PW)
        jax.block_until_ready(out[0].store_full)

    def best_time(fn):
        fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_fifo = best_time(run_fifo)
    t_pq = best_time(run_pq)
    zeros = (fifo.init_state(), jnp.zeros(n, bool), jnp.zeros(n, bool),
             jnp.zeros((n, W), jnp.int32))
    coll_fifo = count_all_to_all(fifo._step, zeros)
    zeros = (pq.init_state(), jnp.zeros(n, bool), jnp.zeros(n, bool),
             jnp.zeros(n, jnp.int32), jnp.zeros((n, W), jnp.int32))
    coll_pq = count_all_to_all(pq._step, zeros)

    return {
        "n_dev": n_dev, "waves": waves, "total_waves_to_drain": fifo_total,
        "capacity_per_wave": C,
        "arrivals": {"interactive_per_wave": inter_rate,
                     "batch_burst": batch_burst,
                     "batch_burst_every": batch_every},
        "fifo_baseline": {"interactive": pct(fifo_waits[0]),
                          "batch": pct(fifo_waits[1])},
        "priority_2tier": {"interactive": pct(pq_waits[0]),
                           "batch": pct(pq_waits[1])},
        "interactive_p99_speedup": (pct(fifo_waits[0])["p99"]
                                    / max(pct(pq_waits[0])["p99"], 0.5)),
        "steady_state": {
            "fifo_waves_per_sec": K / t_fifo,
            "priority_waves_per_sec": K / t_pq,
            "overhead_pct": (t_pq - t_fifo) / t_fifo * 100.0,
            "collectives_per_wave": {"fifo": coll_fifo, "priority": coll_pq},
        },
    }


def _measure_relaxation_sweep(n_dev: int, quick: bool = False) -> dict:
    """The ROADMAP relaxation study: what does ``relaxation=k`` buy?

    Tier-skewed traffic (most arrivals in the low-urgency tiers, so the
    best non-empty tier's head is usually remote) with one dequeue per
    shard per wave.  For k in {0, 1, 2}: the fraction of serves that were
    *local* (head owned by the issuing shard — the hop the relaxation
    exists to avoid) vs. the tier skew it costs (served tier minus the
    strictly-best tier at serve time, replayed exactly host-side)."""
    from repro.compat import make_mesh
    from repro.dqueue import DevicePriorityQueue

    P_, L, W = 4, 8, 2
    waves = 24 if quick else 96
    tier_probs = np.array([0.1, 0.2, 0.3, 0.4])
    mesh = make_mesh((n_dev,), ("data",))
    n = n_dev * L
    out = {}
    for k in (0, 1, 2):
        q = DevicePriorityQueue(mesh, "data", n_prios=P_, cap=4096,
                                payload_width=W, ops_per_shard=L,
                                relaxation=k)
        state = q.init_state()
        rng = np.random.default_rng(17)        # same traffic for every k
        sizes = [0] * P_                       # host mirror of tier sizes
        serves = local = relaxed = 0
        skews = []
        for w in range(waves):
            e = np.zeros(n, bool)
            v = np.zeros(n, bool)
            pr = np.zeros(n, np.int32)
            pw = np.zeros((n, W), np.int32)
            n_arr = int(rng.integers(n_dev, n_dev + 4))
            for j in range(n_arr):             # arrivals, tier-skewed, kept
                i = (j // (L - 1)) * L + j % (L - 1)  # off the last slot of
                e[i] = v[i] = True                    # each shard (reserved
                pr[i] = rng.choice(P_, p=tier_probs)  # for its dequeue)
            for s in range(n_dev):             # one dequeue per shard
                v[s * L + L - 1] = True
            state, tier, pos, m, dv, dok, ovf, nrel = q.step(
                state, jnp.array(e), jnp.array(v), jnp.array(pr),
                jnp.array(pw))
            assert not bool(np.asarray(ovf))
            tier, pos, m = map(np.asarray, (tier, pos, m))
            relaxed += int(np.asarray(nrel))
            # exact host replay, in wave order: enqueues first, then each
            # dequeue sees the sizes left by the previous ones
            for i in range(n):
                if e[i] and m[i]:
                    sizes[int(tier[i])] += 1
            for i in range(n):
                if v[i] and not e[i] and m[i]:
                    best = next(p for p in range(P_) if sizes[p] > 0)
                    t = int(tier[i])
                    skews.append(t - best)
                    sizes[t] -= 1
                    serves += 1
                    local += int(int(pos[i]) % n_dev == i // L)
        out[f"k={k}"] = {
            "serves": serves,
            "local_serve_fraction": local / max(serves, 1),
            "relaxed_fraction": relaxed / max(serves, 1),
            "tier_skew_mean": float(np.mean(skews)) if skews else 0.0,
            "tier_skew_max": int(max(skews)) if skews else 0,
        }
    return out


def emit_bench_pr3(path: str = "BENCH_PR3.json", n_dev: int = 8,
                   quick: bool = False) -> dict:
    """Measure priority-tier tail-latency separation under mixed load plus
    the relaxation=k sweep, and write JSON (re-execs on a forced
    ``n_dev``-device CPU mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR3", path, n_dev,
        ["--pr3", path, "--n-dev", str(n_dev)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_priority_mixed(n_dev=n_dev, quick=quick)
    data["relaxation_sweep"] = _measure_relaxation_sweep(n_dev=n_dev,
                                                         quick=quick)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


# ------------------------------ PR 4: unified engine, wave pipelining ------
def _measure_pipelining(n_dev: int, K: int, ops_per_shard: int = 64,
                        iters: int = 10, quick: bool = False) -> dict:
    """K-wave bursts through the unified WaveEngine: the sequential burst
    schedule vs. the software-pipelined one (wave k's dispatch overlapped
    with wave k-1's store rewrite; request_k ‖ reply_{k-1} fused into ONE
    all_to_all per wave), for all three disciplines.  Identical op
    schedules, identical results — only the wave schedule differs."""
    from repro.compat import make_mesh
    from repro.dqueue import (DevicePriorityQueue, DeviceQueue, DeviceStack)
    if quick:
        K, iters = min(K, 8), 3
    mesh = make_mesh((n_dev,), ("data",))
    n = n_dev * ops_per_shard
    cap = max(256, K * ops_per_shard // n_dev + 1)
    rng = np.random.default_rng(5)
    E = jnp.array(rng.random((K, n)) < 0.5)
    V = jnp.ones((K, n), bool)
    PR = jnp.array(rng.integers(0, 2, (K, n)), jnp.int32)
    PW = jnp.array(rng.integers(0, 100, (K, n, 4)), jnp.int32)

    def best_time(fn):
        fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    cases = {
        "queue": (lambda p: DeviceQueue(
            mesh, "data", cap=cap, payload_width=4,
            ops_per_shard=ops_per_shard, pipelined=p), (E, V, PW)),
        "stack": (lambda p: DeviceStack(
            mesh, "data", cap=cap, payload_width=4,
            ops_per_shard=ops_per_shard, slot_depth=4, pipelined=p),
            (E, V, PW)),
        "priority": (lambda p: DevicePriorityQueue(
            mesh, "data", n_prios=2, cap=cap, payload_width=4,
            ops_per_shard=ops_per_shard, pipelined=p), (E, V, PR, PW)),
    }
    out = {"n_dev": n_dev, "K": K, "ops_per_wave": n, "disciplines": {}}
    for name, (make, args) in cases.items():
        row = {}
        for mode, q in (("sequential", make(False)),
                        ("pipelined", make(True))):
            def run(q=q):
                res = q.run_waves(q.init_state(), *args)
                jax.block_until_ready(jax.tree.leaves(res[0])[0])
            t = best_time(run)
            hlo_args = (q.init_state(),) + args
            row[mode] = {
                "waves_per_sec": K / t,
                "us_per_wave": t / K * 1e6,
                # static count for the whole K-wave program: sequential =
                # 2 in the scan body; pipelined = 1 fused in the body + 1
                # drain epilogue (amortized (K+1)/K per wave)
                "all_to_all_static": count_all_to_all(q._run_waves,
                                                      hlo_args),
            }
        row["speedup_waves_per_sec"] = (row["pipelined"]["waves_per_sec"]
                                        / row["sequential"]["waves_per_sec"])
        out["disciplines"][name] = row
    return out


def emit_bench_pr4(path: str = "BENCH_PR4.json", n_dev: int = 8,
                   K: int = 32, quick: bool = False) -> dict:
    """Measure pipelined vs. sequential burst schedules on the unified
    engine and write JSON (re-execs on a forced ``n_dev`` CPU mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR4", path, n_dev,
        ["--pr4", path, "--n-dev", str(n_dev), "--waves", str(K)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_pipelining(n_dev=n_dev, K=K, quick=quick)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


# ------------------------------- PR 5: Seap EDF deadline scheduling --------
def _measure_edf_mixed(n_dev: int, quick: bool = False) -> dict:
    """Deadline traffic with *heterogeneous, drifting per-request slack*
    at the SAME arrival schedule and SAME per-wave service capacity
    through FIFO, static 2-tier priority (slack below the trace median ->
    tier 0 — the best one static cut can do), and Seap with key = the
    deadline wave (EDF at bucket granularity).  The slack distribution
    DRIFTS mid-run (tight-slack phase, then loose-slack phase — think
    diurnal traffic): any cut tuned to the whole trace puts each phase
    almost entirely in one tier, so the static discipline degenerates to
    FIFO exactly when the periodic bursts pile up backlog, while EDF keys
    on each request's own deadline and the Seap directory re-zooms as the
    key distribution moves (splits chase the full buckets, drained ones
    merge away).  Total throughput is identical by construction — the
    difference is WHO waits, measured as deadline misses."""
    from repro.compat import make_mesh
    from repro.dqueue import (DevicePriorityQueue, DeviceQueue,
                              DeviceSeapQueue)

    L, W, C = 16, 2, 8                 # wave width / payload / service cap
    waves = 64 if quick else 192
    steady, burst, burst_every = 4, 40, 12  # avg ~7.3/wave vs C=8: near-
    #                                         critical, ~36-deep transient
    #                                         backlog after each burst that
    #                                         just drains before the next
    slack_lo, slack_hi = 2, 30         # overall slack range across phases
    phase_slacks = ((2, 9), (13, 30))  # tight-phase / loose-phase U[lo,hi)
    iters = 3 if quick else 10
    cap = 4096
    mesh = make_mesh((n_dev,), ("data",))
    n = n_dev * L

    # one arrival trace shared by every flavor (slack is per REQUEST and
    # its distribution drifts at half-time — the continuous, non-
    # stationary urgency a constant-P queue cannot key on)
    rng = np.random.default_rng(11)
    trace, slack_by_rid = [], {}
    rid = 0
    for w in range(waves):
        lo_, hi_ = phase_slacks[int(w >= waves // 2)]
        k = steady + (burst if w % burst_every == 0 else 0)
        arr = []
        for _ in range(k):
            slack = int(rng.integers(lo_, hi_))
            slack_by_rid[rid] = slack
            arr.append((w + slack, rid))
            rid += 1
        trace.append(arr)
    tier_cut = int(np.median(list(slack_by_rid.values())))

    def run(flavor):
        if flavor == "seap_edf":
            # seed a FINE grid over the near-term deadline range only (3
            # waves per bucket); the split/merge rule rolls the refined
            # window forward as early buckets drain and later deadlines
            # pile up, so far-future deadlines share coarse buckets until
            # they come due
            B, grid = 16, 3
            q = DeviceSeapQueue(mesh, "data", n_buckets=B, cap=cap,
                                payload_width=W, ops_per_shard=L,
                                split_occupancy=C // 2,
                                seed_bounds=[i * grid
                                             for i in range(1, B)])
        elif flavor == "priority_2tier":
            q = DevicePriorityQueue(mesh, "data", n_prios=2, cap=cap,
                                    payload_width=W, ops_per_shard=L)
        else:
            q = DeviceQueue(mesh, "data", cap=cap, payload_width=W,
                            ops_per_shard=L)
        state = q.init_state()
        deadline_of, lateness = {}, {}
        backlog, w = 0, 0
        while w < waves or backlog > 0:   # drain tail: serve EVERY request
            arr = trace[w] if w < waves else []
            e = np.zeros(n, bool)
            v = np.zeros(n, bool)
            pr = np.zeros(n, np.int32)
            pw = np.zeros((n, W), np.int32)
            for j, (dl, r) in enumerate(arr):
                e[j] = v[j] = True
                # seap keys on the deadline itself; the static discipline
                # can only threshold the slack into two tiers
                pr[j] = dl if flavor == "seap_edf" else int(dl - w >= tier_cut)
                pw[j, 0] = r
                deadline_of[r] = dl
            v[len(arr):len(arr) + C] = True          # C dequeue requests
            if flavor == "fifo":
                state, _, _, dv, dok, ovf = q.step(
                    state, jnp.array(e), jnp.array(v), jnp.array(pw))
            else:
                state, _, _, _, dv, dok, ovf, _ = q.step(
                    state, jnp.array(e), jnp.array(v), jnp.array(pr),
                    jnp.array(pw))
            if bool(np.asarray(ovf).any()):
                raise RuntimeError(f"{flavor} overflowed the benchmark cap")
            dv, dok = np.asarray(dv), np.asarray(dok)
            served = 0
            for i in range(n):
                if dok[i]:
                    r = int(dv[i, 0])
                    served += 1
                    lateness[r] = w - deadline_of.pop(r)
            backlog += len(arr) - served
            w += 1
        return lateness, w

    def summarize(late):
        a = np.asarray(late, np.float64)
        if a.size == 0:
            return {"n": 0}
        return {"n": int(a.size), "missed": int((a > 0).sum()),
                "miss_rate": float((a > 0).mean()),
                "lateness_mean": float(a.mean()),
                "lateness_p99": float(np.percentile(a, 99)),
                "lateness_max": float(a.max())}

    # slack band edges for the per-urgency breakdown
    bands = [(slack_lo, 8, "urgent_slack_2_7"),
             (8, 16, "mid_slack_8_15"),
             (16, slack_hi, "relaxed_slack_16_29")]

    out = {"n_dev": n_dev, "waves": waves, "capacity_per_wave": C,
           "arrivals": {"steady_per_wave": steady, "burst": burst,
                        "burst_every": burst_every,
                        "slack_uniform": [slack_lo, slack_hi],
                        "tier_cut_2tier": tier_cut}}
    totals = {}
    for flavor in ("fifo", "priority_2tier", "seap_edf"):
        late, total = run(flavor)
        totals[flavor] = total
        assert set(late) == set(slack_by_rid), "requests lost"
        row = {"overall": summarize(list(late.values()))}
        for lo_, hi_, name in bands:
            row[name] = summarize([lt for r, lt in late.items()
                                   if lo_ <= slack_by_rid[r] < hi_])
        out[flavor] = row
    assert len(set(totals.values())) == 1, f"throughput diverged: {totals}"
    for base in ("fifo", "priority_2tier"):
        # miss-count ratio with a floor of one EDF miss, so a zero-miss
        # EDF run reports "N missed -> at least N x fewer" finitely
        out[f"edf_miss_improvement_vs_{base}"] = (
            out[base]["overall"]["missed"]
            / max(out["seap_edf"]["overall"]["missed"], 1))

    # ---- steady-state wave rate + collective count of the seap path ----
    K = 8 if quick else 32
    rng = np.random.default_rng(5)
    E = jnp.array(rng.random((K, n)) < 0.5)
    V = jnp.ones((K, n), bool)
    KY = jnp.array(rng.integers(0, 1000, (K, n)), jnp.int32)
    PW = jnp.array(rng.integers(0, 100, (K, n, W)), jnp.int32)
    fifo = DeviceQueue(mesh, "data", cap=cap, payload_width=W,
                       ops_per_shard=L)
    sq = DeviceSeapQueue(mesh, "data", n_buckets=8, cap=cap,
                         payload_width=W, ops_per_shard=L)

    def best_time(fn):
        fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def run_fifo():
        out_ = fifo.run_waves(fifo.init_state(), E, V, PW)
        jax.block_until_ready(out_[0].store_full)

    def run_seap():
        out_ = sq.run_waves(sq.init_state(), E, V, KY, PW)
        jax.block_until_ready(out_[0].store_full)

    t_fifo, t_seap = best_time(run_fifo), best_time(run_seap)
    zeros = (sq.init_state(), jnp.zeros(n, bool), jnp.zeros(n, bool),
             jnp.zeros(n, jnp.int32), jnp.zeros((n, W), jnp.int32))
    out["steady_state"] = {
        "fifo_waves_per_sec": K / t_fifo,
        "seap_waves_per_sec": K / t_seap,
        "overhead_pct": (t_seap - t_fifo) / t_fifo * 100.0,
        "collectives_per_wave": count_all_to_all(sq._step, zeros),
    }
    return out


def emit_bench_pr5(path: str = "BENCH_PR5.json", n_dev: int = 8,
                   quick: bool = False) -> dict:
    """Measure EDF deadline-miss rates vs FIFO and static tiers and write
    JSON (re-execs on a forced ``n_dev``-device CPU mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR5", path, n_dev,
        ["--pr5", path, "--n-dev", str(n_dev)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_edf_mixed(n_dev=n_dev, quick=quick)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


# ------------------------------- PR 7: Wavescope telemetry overhead --------
def _measure_telemetry(n_dev: int, K: int, ops_per_shard: int = 64,
                       iters: int = 40, quick: bool = False) -> dict:
    """Telemetry-on vs telemetry-off on the SAME pipelined K-wave burst:
    Wavescope's metrics row is pure arithmetic on values the wave already
    materializes, accumulated in a donated device ring — so the static
    all_to_all count must not move and the wall-clock overhead should be
    noise.  The burst-boundary drain (device->host read of the ring) is
    timed separately: it is the ONE sanctioned sync and happens once per
    burst, not per wave."""
    from repro.compat import make_mesh
    from repro.dqueue import DevicePriorityQueue, DeviceQueue
    if quick:
        K, iters = min(K, 8), 3
    mesh = make_mesh((n_dev,), ("data",))
    n = n_dev * ops_per_shard
    cap = max(256, K * ops_per_shard // n_dev + 1)
    rng = np.random.default_rng(7)
    E = jnp.array(rng.random((K, n)) < 0.5)
    V = jnp.ones((K, n), bool)
    PR = jnp.array(rng.integers(0, 2, (K, n)), jnp.int32)
    PW = jnp.array(rng.integers(0, 100, (K, n, 4)), jnp.int32)

    cases = {
        "queue": (lambda m: DeviceQueue(
            mesh, "data", cap=cap, payload_width=4,
            ops_per_shard=ops_per_shard, pipelined=True, metrics=m,
            metrics_ring=max(64, K)), (E, V, PW)),
        "priority": (lambda m: DevicePriorityQueue(
            mesh, "data", n_prios=2, cap=cap, payload_width=4,
            ops_per_shard=ops_per_shard, pipelined=True, metrics=m,
            metrics_ring=max(64, K)), (E, V, PR, PW)),
    }
    out = {"n_dev": n_dev, "K": K, "ops_per_wave": n, "disciplines": {}}
    for name, (make, args) in cases.items():
        row = {}
        q_off, q_on = make(False), make(True)

        def run(q):
            res = q.run_waves(q.init_state(), *args)
            jax.block_until_ready(jax.tree.leaves(res[0])[0])

        # interleave the off/on timings so machine drift (CI neighbors,
        # frequency scaling) hits both flavors symmetrically; best-of
        run(q_off), run(q_on)          # warmup / compile both first
        t_off = t_on = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            run(q_off)
            t_off = min(t_off, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run(q_on)
            t_on = min(t_on, time.perf_counter() - t0)
        for mode, q, t in (("telemetry_off", q_off, t_off),
                           ("telemetry_on", q_on, t_on)):
            st = q.init_state()
            if q.engine.metrics:
                st = (st, q.engine.init_metrics_state())
            row[mode] = {
                "waves_per_sec": K / t,
                "us_per_wave": t / K * 1e6,
                "all_to_all_static": count_all_to_all(q._run_waves,
                                                      (st,) + args),
            }
        q_on.drain_metrics(reset=True)
        run(q_on)
        t0 = time.perf_counter()
        rows = q_on.drain_metrics(reset=True)
        row["drain_us_per_burst"] = (time.perf_counter() - t0) * 1e6
        row["rows_per_burst"] = len(rows)
        row["overhead_pct"] = 100.0 * (t_on / t_off - 1.0)
        row["all_to_all_added"] = (
            row["telemetry_on"]["all_to_all_static"]
            - row["telemetry_off"]["all_to_all_static"])
        out["disciplines"][name] = row
    return out


def emit_bench_pr7(path: str = "BENCH_PR7.json", n_dev: int = 8,
                   K: int = 32, quick: bool = False) -> dict:
    """Measure Wavescope telemetry overhead on the pipelined burst and
    write JSON (re-execs on a forced ``n_dev``-device CPU mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR7", path, n_dev,
        ["--pr7", path, "--n-dev", str(n_dev), "--waves", str(K)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_telemetry(n_dev=n_dev, K=K, quick=quick)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


def _measure_backpressure(n_dev: int, quick: bool = False) -> dict:
    """The SAME 10x-burst arrival schedule through a ServeEngine with no
    admission policy (the pre-PR 8 baseline: staging is unconditional, so
    the burst overflows the device queue MID-WAVE and poisons the engine)
    vs. the shed / defer / degrade policies and shed + the hysteresis
    autoscale controller.  The baseline must overflow; every policy must
    finish with ZERO QueueOverflowError — overload becomes structured,
    resubmittable AdmissionRejected sheds (or host-side spills, or tier
    downgrades) decided BEFORE staging, against the zero-cost pressure
    API.  Reported per flavor: goodput, shed rate, overflow count, p99
    admission-decision latency; the autoscale flavor adds resize counts
    and the shard trajectory."""
    from repro.configs import get_config
    from repro.dqueue import QueueOverflowError
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.serve import (AdmissionRejected, HysteresisController,
                             Request, ServeEngine)

    steps = 30 if quick else 80
    steady, burst, burst_len, burst_every = 2, 20, 3, 10 if quick else 20
    max_slots, max_new, queue_cap = 6, 2, 8   # window cap = 2 shards x 8
    spill_cap = 64

    cfg = get_config("mamba2_130m").reduced(n_layers=1)
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))

    # one arrival trace shared by every flavor: steady near-capacity base
    # rate + 10x bursts that exceed the whole queue window several-fold
    # (offset into the cycle so the baseline provably serves the steady
    # rate fine before the first burst overflows it)
    arrivals = [steady + (burst if 4 <= (w % burst_every) < 4 + burst_len
                          else 0) for w in range(steps)]

    def make_engine(flavor):
        mesh = make_host_mesh(n_data=2)
        kw = dict(max_slots=max_slots, max_seq=4 + max_new + 2,
                  queue_cap=queue_cap, spill_cap=spill_cap)
        if flavor == "baseline":
            return ServeEngine(model, params, mesh, **kw)
        if flavor == "degrade":
            return ServeEngine(model, params, mesh, priorities=2,
                               admission="degrade", **kw)
        if flavor == "shed_autoscale":
            ctl = HysteresisController(high_watermark=0.6, high_patience=2,
                                       low_watermark=0.15, low_patience=12,
                                       cooldown=3, grow_k=2)
            return ServeEngine(model, params, mesh, admission="shed",
                               autoscale=ctl, **kw)
        return ServeEngine(model, params, mesh, admission=flavor, **kw)

    def run(flavor):
        eng = make_engine(flavor)
        offered = shed = spill_overflows = overflows = 0
        rid = 0
        for w in range(steps):
            reqs = [Request(rid=rid + j, prompt=[1, 2, 3],
                            max_new=max_new) for j in range(arrivals[w])]
            rid += len(reqs)
            offered += len(reqs)
            try:
                eng.submit(reqs)
            except AdmissionRejected as e:
                shed += len(e.shed)
                spill_overflows += int(e.kind == "spill-overflow")
            except QueueOverflowError:
                overflows += 1
                break
            try:
                eng.step()
            except QueueOverflowError:
                overflows += 1
                break
        else:
            try:
                eng.run_until_drained(max_steps=1000)
            except QueueOverflowError:
                overflows += 1
        st = eng.admission_stats
        lat = np.asarray(st["decide_us"], np.float64)
        row = {"offered": offered, "served": eng.stats["served"],
               "goodput": eng.stats["served"] / offered,
               "shed": shed, "shed_rate": shed / offered,
               "degraded": st["degraded"],
               "spill_peak": st["spill_peak"],
               "spill_overflow_rejects": spill_overflows,
               "queue_overflows": overflows,
               "admission_decide_us_p99":
                   float(np.percentile(lat, 99)) if lat.size else None}
        if eng.autoscale is not None:
            snap = eng.autoscale.snapshot()
            row["resizes"] = snap["grows"] + snap["shrinks"]
            row["grows"] = snap["grows"]
            row["shrinks"] = snap["shrinks"]
            row["final_shards"] = eng.queue.n_shards
        return row

    out = {"n_dev": n_dev, "n_shards": 2,
           "window_capacity": 2 * queue_cap, "steps": steps,
           "arrivals": {"steady_per_step": steady, "burst": burst,
                        "burst_len": burst_len,
                        "burst_every": burst_every},
           "service": {"max_slots": max_slots, "max_new": max_new},
           "spill_cap": spill_cap}
    for flavor in ("baseline", "shed", "defer", "degrade",
                   "shed_autoscale"):
        out[flavor] = run(flavor)
    # ---- the headline claims, asserted so the artifact can't lie ----
    assert out["baseline"]["queue_overflows"] > 0, \
        "baseline failed to overflow — the burst no longer stresses it"
    for flavor in ("shed", "defer", "degrade", "shed_autoscale"):
        assert out[flavor]["queue_overflows"] == 0, \
            f"{flavor} let the queue overflow"
        assert out[flavor]["served"] > out["baseline"]["served"], \
            f"{flavor} served less than the overflowing baseline"
    assert out["shed_autoscale"]["resizes"] > 0, \
        "controller never resized under sustained bursts"
    return out


def emit_bench_pr8(path: str = "BENCH_PR8.json", n_dev: int = 8,
                   quick: bool = False) -> dict:
    """Measure backpressure policies vs. the overflowing baseline under
    10x bursts and write JSON (re-execs on a forced ``n_dev``-device CPU
    mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR8", path, n_dev,
        ["--pr8", path, "--n-dev", str(n_dev)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = _measure_backpressure(n_dev=n_dev, quick=quick)
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


def _measure_compact_occupancy(n_dev: int, K: int, ops_per_shard: int = 256,
                               iters: int = 12, quick: bool = False) -> dict:
    """Occupancy-adaptive envelopes (PR 9): the SAME logical op stream
    driven (a) compact — every wave staged at the smallest bucket-ladder
    width that fits its live ops — vs (b) full — every wave padded to the
    full ``n_shards * L`` envelope.  At 5% / 25% occupancy the compact
    envelope is 4x narrower (ladder {L/4, L/2, L}); at 100% the bucket
    choice degenerates to the full width and the ratio must be ~1.  Both
    drivers advance twin queue instances through identical logical ops,
    so the emitter can assert bit-identical per-op outputs AND final
    device state (modulo the write-only padding scratch row) — the
    speedup is not allowed to change a single answer."""
    from repro.dqueue import ElasticDevicePriorityQueue, ElasticDeviceQueue
    if quick:
        K, iters = min(K, 8), 3
    L = ops_per_shard
    n_full = n_dev * L
    cap = max(512, 2 * K * L // n_dev)
    occupancies = [("5%", max(1, n_full * 5 // 100)),
                   ("25%", n_full // 4),
                   ("100%", n_full)]
    cases = {
        "queue": (lambda: ElasticDeviceQueue(
            n_dev, cap=cap, payload_width=4, ops_per_shard=L), False),
        "priority": (lambda: ElasticDevicePriorityQueue(
            n_dev, n_prios=2, cap=cap, payload_width=4, ops_per_shard=L),
            True),
    }
    out = {"n_dev": n_dev, "ops_per_shard": L, "full_ops_per_wave": n_full,
           "K": K, "bucket_ladder": None, "disciplines": {}}
    for name, (make, has_prio) in cases.items():
        rows = {}
        for label, n_ops in occupancies:
            eq_c, eq_f = make(), make()
            out["bucket_ladder"] = list(eq_c.bucket_widths())
            rng = np.random.default_rng(abs(hash((name, label))) % 9973)
            # exactly balanced enq/deq per wave keeps the depth bounded
            # across every warmup+timing pass without touching the cap
            enq = np.zeros((K, n_ops), bool)
            for k in range(K):
                enq[k, rng.permutation(n_ops)[:n_ops // 2 + 1]] = True
            pri = rng.integers(0, 2, (K, n_ops)).astype(np.int32)
            pay = rng.integers(0, 1 << 20, (K, n_ops, 4)).astype(np.int32)

            def drive(eq, compact, n_ops=n_ops, enq=enq, pri=pri, pay=pay,
                      has_prio=has_prio):
                outs = []
                for k in range(K):
                    w = eq.pick_width(n_ops) if compact else L
                    n = eq.n_shards * w
                    E = np.zeros(n, bool)
                    E[:n_ops] = enq[k]
                    V = np.zeros(n, bool)
                    V[:n_ops] = True
                    PW = np.zeros((n, 4), np.int32)
                    PW[:n_ops] = pay[k]
                    if has_prio:
                        PR = np.zeros(n, np.int32)
                        PR[:n_ops] = pri[k]
                        res = eq.step(E, V, PR, PW)[:5]
                    else:
                        res = eq.step(E, V, PW)[:4]
                    outs.append([np.asarray(x)[:n_ops] for x in res])
                return outs

            # compile pass: hits every envelope width each driver uses,
            # advancing BOTH states through the same logical ops
            drive(eq_c, True), drive(eq_f, False)
            t_c = t_f = float("inf")
            outs_c = outs_f = None
            for _ in range(iters):
                t0 = time.perf_counter()
                outs_c = drive(eq_c, True)
                t_c = min(t_c, time.perf_counter() - t0)
                t0 = time.perf_counter()
                outs_f = drive(eq_f, False)
                t_f = min(t_f, time.perf_counter() - t0)
            # ---- bit-identity, asserted so the artifact can't lie ----
            for k, (oc, of) in enumerate(zip(outs_c, outs_f)):
                for a, b in zip(oc, of):
                    assert np.array_equal(a, b), \
                        (name, label, k, "per-op outputs differ")
            dc, df = eq_c._state_dict(), eq_f._state_dict()
            assert set(dc) == set(df)
            for key in sorted(dc):
                a, b = np.asarray(dc[key]), np.asarray(df[key])
                if key in type(eq_c)._sharded_keys:
                    # the trailing slot row is write-only padding scratch;
                    # its garbage legitimately differs across widths
                    a, b = a[:, :-1], b[:, :-1]
                assert np.array_equal(a, b), \
                    (name, label, key, "final device state differs")
            rows[label] = {
                "n_ops_per_wave": n_ops,
                "bucket_width_compact": eq_c.pick_width(n_ops),
                "envelope_compact": n_dev * eq_c.pick_width(n_ops),
                "envelope_full": n_full,
                "compact": {"waves_per_sec": K / t_c,
                            "us_per_wave": t_c / K * 1e6},
                "full": {"waves_per_sec": K / t_f,
                         "us_per_wave": t_f / K * 1e6},
                "speedup_waves_per_sec": t_f / t_c,
                "bit_identical": True,
            }
        out["disciplines"][name] = rows
    # headline acceptance: >= 1.3x waves/sec at <= 25% occupancy
    for name, rows in out["disciplines"].items():
        for label in ("5%", "25%"):
            sp = rows[label]["speedup_waves_per_sec"]
            assert sp >= 1.3, \
                (name, label, f"compact speedup {sp:.2f}x < 1.3x")
    return out


def _measure_segscan_modes(n: int = 1 << 15, iters: int = 10) -> dict:
    """Single-shard segscan dispatch: the jnp core scan (the compiled-XLA
    oracle and the CPU hot path) vs the pallas kernel in interpret mode
    (the CPU CI correctness path) vs compiled pallas (TPU/GPU only — on
    the CPU mesh it is reported as null with a note, because
    ``use_fused_dispatch()`` keeps the wave off the kernel there)."""
    from repro.core.scan_queue import QueueState, queue_scan
    from repro.kernels.backend import default_interpret, use_fused_dispatch
    from repro.kernels.segscan import queue_scan_pallas

    rng = np.random.default_rng(5)
    e = jnp.array(rng.random(n) < 0.5)
    v = jnp.ones((n,), bool)
    f0, l0 = jnp.int32(0), jnp.int32(-1)
    core = jax.jit(lambda a, b: queue_scan(a, QueueState.empty(), valid=b))
    row = {"n": n, "backend": jax.default_backend(),
           "default_interpret": bool(default_interpret()),
           "use_fused_dispatch": bool(use_fused_dispatch())}
    row["core_jnp_us"] = _time_us(core, e, v, iters=iters)
    row["pallas_interpret_us"] = _time_us(
        lambda a, b: queue_scan_pallas(a, b, f0, l0, interpret=True), e, v,
        iters=max(3, iters // 3), warmup=1)
    if jax.default_backend() != "cpu":
        row["pallas_compiled_us"] = _time_us(
            lambda a, b: queue_scan_pallas(a, b, f0, l0, interpret=False),
            e, v, iters=iters)
    else:
        row["pallas_compiled_us"] = None
        row["note"] = ("compiled pallas needs a TPU/GPU backend; on the "
                       "CPU mesh the wave hot path keeps the jnp core "
                       "scans and tests drive the kernels in interpret "
                       "mode")
    # the interpret kernel must agree with the oracle on this input
    ref = core(e, v)
    pos, matched, *_ = queue_scan_pallas(e, v, f0, l0, interpret=True)
    assert np.array_equal(np.asarray(pos), np.asarray(ref[0])), \
        "pallas interpret pos diverged from the core oracle"
    return row


def emit_bench_pr9(path: str = "BENCH_PR9.json", n_dev: int = 8,
                   K: int = 32, quick: bool = False) -> dict:
    """Measure occupancy-adaptive compact waves vs the full envelope (with
    bit-identity asserted) plus segscan dispatch-mode timings, and write
    JSON (re-execs on a forced ``n_dev``-device CPU mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR9", path, n_dev,
        ["--pr9", path, "--n-dev", str(n_dev), "--waves", str(K)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = {
        "occupancy": _measure_compact_occupancy(n_dev=n_dev, K=K,
                                                quick=quick),
        "segscan": _measure_segscan_modes(iters=3 if quick else 10),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


PR10_WIRE_MARK = "PR10-WIRE-JSON "

_PR10_WIRE_CHILD = r"""
import json
import time

import numpy as np

from repro.runtime import DistributedRuntime

rt = DistributedRuntime.from_env()      # BEFORE any jax computation

from repro.dqueue import ElasticDeviceQueue

q = ElasticDeviceQueue(6, cap=64, payload_width=2, ops_per_shard=8,
                       runtime=rt)
K, reps = %(K)d, %(reps)d
n = q.n_shards * q.L
zb = np.zeros((K, n), bool)
zi = np.zeros((K, n, 2), np.int32)
q.run_waves(zb, zb, zi)                    # compile + warm the socket path
rt.sync()
t = time.perf_counter()
for _ in range(reps):
    q.run_waves(zb, zb, zi)
rt.sync()
steady_s = time.perf_counter() - t
ones = np.ones(n, bool)
fill = np.arange(n * 2, dtype=np.int32).reshape(n, 2)
for _ in range(4):
    q.step(ones, ones, fill)
rt.sync()
t = time.perf_counter()
q.grow(2)
grow_s = time.perf_counter() - t
t = time.perf_counter()
q.shrink([6, 7])
shrink_s = time.perf_counter() - t
out = {
    "n_procs": rt.process_role.count,
    "n_shards": 6,
    "waves": K * reps,
    "real_waves_per_sec": (K * reps) / steady_s,
    "migration": {
        "grow_us": grow_s * 1e6,
        "grow_bytes_moved": int(q.migrations[-2]["bytes_moved"]),
        "shrink_us": shrink_s * 1e6,
        "shrink_bytes_moved": int(q.migrations[-1]["bytes_moved"]),
    },
}
if rt.process_role.coordinator:
    print("%(mark)s" + json.dumps(out))
"""


def _measure_pr10_parity(n_dev: int, waves: int) -> dict:
    """Assert the runtime seam is behavior-preserving: the same op stream
    through a bare-mesh DeviceQueue (the PR 9 path) and a
    LocalRuntime-built one must be BIT-identical, state and outputs."""
    from repro.dqueue import DeviceQueue
    from repro.launch.mesh import make_elastic_mesh
    from repro.runtime import LocalRuntime

    mesh = make_elastic_mesh(n_dev)
    n = n_dev * 8
    rng = np.random.default_rng(17)
    ops = [(rng.random(n) < 0.5, rng.random(n) < 0.85,
            rng.integers(0, 1 << 20, (n, 2)).astype(np.int32))
           for _ in range(waves)]

    def drive(q):
        st = q.init_state()
        outs = []
        for e, v, pw in ops:
            st, *rest = q.step(st, e, v, pw)
            outs.append([np.asarray(x) for x in rest])
        return outs, [np.asarray(x) for x in jax.tree.leaves(st)]

    a, sa = drive(DeviceQueue(mesh, "data", cap=64, payload_width=2,
                              ops_per_shard=8))
    b, sb = drive(DeviceQueue(
        LocalRuntime(devices=list(mesh.devices.flat)), cap=64,
        payload_width=2, ops_per_shard=8))
    for xa, xb in zip(a, b):
        for ya, yb in zip(xa, xb):
            assert (ya == yb).all(), "runtime path diverged from mesh path"
    for la, lb in zip(sa, sb):
        assert (la == lb).all(), "runtime path diverged in final state"
    return {"bit_identical": True, "waves": waves, "n_shards": n_dev}


def _measure_pr10_sim_sweep(n_dev: int, K: int, quick: bool) -> dict:
    """Steady-state waves/sec and migration-wave cost under the SimRuntime
    latency points {0us, 100us, 1ms} (base per-collective cost; 8 us/MiB
    on the wire everywhere)."""
    from repro.dqueue import ElasticDeviceQueue
    from repro.runtime import LatencyModel, SimRuntime

    reps = 3 if quick else 10
    P0 = n_dev - 2
    out = {}
    for base_us in (0.0, 100.0, 1000.0):
        sim = SimRuntime(latency=LatencyModel(base_us=base_us,
                                              per_mib_us=8.0))
        q = ElasticDeviceQueue(P0, cap=64, payload_width=2,
                               ops_per_shard=8, runtime=sim)
        n = q.n_shards * q.L
        zb = np.zeros((K, n), bool)
        zi = np.zeros((K, n, 2), np.int32)
        q.run_waves(zb, zb, zi)            # compile
        wire0 = sim.sim_time_s
        t = time.perf_counter()
        for _ in range(reps):
            q.run_waves(zb, zb, zi)
        real_s = time.perf_counter() - t
        n_waves = K * reps
        wire_s = sim.sim_time_s - wire0
        # fill before migrating so the packed-migration wave carries a
        # real payload (an empty queue moves zero bytes)
        ones = np.ones(n, bool)
        fill = np.arange(n * 2, dtype=np.int32).reshape(n, 2)
        for _ in range(4):
            q.step(ones, ones, fill)
        q.grow(2)
        grow = dict(q.migrations[-1])
        q.shrink([P0, P0 + 1])
        shrink = dict(q.migrations[-1])
        # modeled waves/sec = compute-bound rate slowed by the modeled
        # wire (serial launches); the 3-point sweep prices the pipelined
        # K+1 schedule under LAN/WAN regimes
        modeled = n_waves / (real_s + wire_s)
        out[f"{base_us:g}us"] = {
            "real_waves_per_sec": n_waves / real_s,
            "sim_wire_us_per_wave": wire_s / n_waves * 1e6,
            "modeled_waves_per_sec": modeled,
            "migration": {
                "grow_bytes_moved": int(grow["bytes_moved"]),
                "grow_sim_us": float(grow["sim_s"]) * 1e6,
                "shrink_bytes_moved": int(shrink["bytes_moved"]),
                "shrink_sim_us": float(shrink["sim_s"]) * 1e6,
            },
        }
    return out


def _measure_pr10_wire(K: int, quick: bool) -> dict:
    """The same steady-state + migration measurement on the REAL wire: 2
    jax.distributed processes over localhost TCP (gloo collectives)."""
    from repro.runtime import launch_localhost

    reps = 2 if quick else 5
    code = _PR10_WIRE_CHILD % {"K": K, "reps": reps,
                               "mark": PR10_WIRE_MARK}
    results = launch_localhost(code=code, n_procs=2, devs_per_proc=4,
                               timeout=420.0)
    for line in results[0].stdout.splitlines():
        if line.startswith(PR10_WIRE_MARK):
            return json.loads(line[len(PR10_WIRE_MARK):])
    raise RuntimeError(
        f"2-process wire child emitted no result:\n{results[0].stdout}\n"
        f"{results[0].stderr}")


def emit_bench_pr10(path: str = "BENCH_PR10.json", n_dev: int = 8,
                    K: int = 16, quick: bool = False) -> dict:
    """Price the runtime seam (PR 10): LocalRuntime parity (asserted
    bit-identical vs the bare-mesh path), migration-wave cost and
    steady-state waves/sec under SimRuntime latency points
    {0us, 100us, 1ms}, and the same on a real 2-process localhost wire.
    Writes JSON (re-execs on a forced ``n_dev``-device CPU mesh)."""
    if not os.path.isabs(path):
        path = os.path.join(_REPO_ROOT, path)
    child = _reexec_on_mesh(
        "PR10", path, n_dev,
        ["--pr10", path, "--n-dev", str(n_dev), "--waves", str(K)]
        + (["--quick"] if quick else []))
    if child is not None:
        return child
    data = {
        "parity": _measure_pr10_parity(n_dev, waves=4 if quick else 12),
        "sim_sweep": _measure_pr10_sim_sweep(n_dev, K=K, quick=quick),
        "wire_2proc": _measure_pr10_wire(K=max(4, K // 4), quick=quick),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
    return data


# PR numbers that deliberately ship NO benchmark emitter.  emit_all
# prints one explicit skip line per entry so a missing BENCH_PRn.json in
# the CI artifact is documented output, not a silent gap (PR 8 satellite
# bugfix: --all used to skip PR 6 without a trace).
_NO_BENCH = {
    "BENCH_PR6.json": "PR 6 is the wavecheck static analyzer — nothing "
                      "to time; run `python -m repro.analysis --all`",
}


def emit_all(quick: bool = False, n_dev: int = 8) -> dict:
    """The CI bench-smoke entry point: run EVERY BENCH_PR*.json emitter.

    Any emitter crash fails the whole run (after attempting the rest, so
    one regression doesn't mask another's numbers).  PRs with no
    benchmark are announced via ``_NO_BENCH`` skip lines."""
    emitters = [("BENCH_PR1.json", lambda p: emit_bench_pr1(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR2.json", lambda p: emit_bench_pr2(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR3.json", lambda p: emit_bench_pr3(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR4.json", lambda p: emit_bench_pr4(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR5.json", lambda p: emit_bench_pr5(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR7.json", lambda p: emit_bench_pr7(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR8.json", lambda p: emit_bench_pr8(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR9.json", lambda p: emit_bench_pr9(
                     p, n_dev=n_dev, quick=quick)),
                ("BENCH_PR10.json", lambda p: emit_bench_pr10(
                     p, n_dev=n_dev, quick=quick))]
    for path, why in sorted(_NO_BENCH.items()):
        print(f"bench: skipping {path} ({why})")
    out, failures = {}, []
    for path, emit in emitters:
        try:
            out[path] = emit(path)
        except Exception as e:
            failures.append(f"{path}: {type(e).__name__}: {e}")
    if failures:
        raise SystemExit("bench emitters failed:\n  " + "\n  ".join(failures))
    return out


def bench_wave_pipeline():
    try:
        data = emit_bench_pr1()
    except Exception as e:  # keep the rest of the CSV usable
        return [("dq_wave_pipeline", 0.0, f"unavailable: {e}", "", "")]
    rows = []
    for key, label in (("seed_single_wave", "dq_seed_single_wave"),
                       ("fused_multi_wave", "dq_fused_multi_wave")):
        d = data[key]
        rows.append((f"{label}_K{data['K']}", d["us_per_wave"],
                     f"{d['waves_per_sec']:.1f} waves/s",
                     d["waves_per_sec"], d["collectives_per_wave"]))
    rows.append((f"dq_fused_speedup_K{data['K']}", 0.0,
                 f"{data['speedup_waves_per_sec']:.2f}x waves/sec", "", ""))
    return rows


def bench_attention():
    from repro.kernels.flash_attention import attention_ref
    rows = []
    rng = np.random.default_rng(3)
    B, H, L, D = 1, 8, 1024, 64
    q = jnp.array(rng.standard_normal((B * H, L, D)), jnp.bfloat16)
    f = jax.jit(lambda q: attention_ref(q, q, q))
    us = _time_us(f, q, iters=5)
    flops = 4 * B * H * L * L * D
    rows.append((f"attention_ref_L{L}", us, f"{flops/us/1e3:.1f} GF/s"))
    return rows


def run_all():
    rows = []
    for fn in (bench_scan_queue, bench_segscan_kernel, bench_device_queue,
               bench_wave_pipeline, bench_attention):
        rows += fn()
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--pr1", nargs="?", const="BENCH_PR1.json", default=None,
                    help="measure the wave pipeline and write BENCH_PR1.json")
    ap.add_argument("--pr2", nargs="?", const="BENCH_PR2.json", default=None,
                    help="measure elastic reshard cost and write "
                         "BENCH_PR2.json")
    ap.add_argument("--pr3", nargs="?", const="BENCH_PR3.json", default=None,
                    help="measure priority-tier mixed-load latency and "
                         "write BENCH_PR3.json")
    ap.add_argument("--pr4", nargs="?", const="BENCH_PR4.json", default=None,
                    help="measure pipelined vs sequential wave bursts and "
                         "write BENCH_PR4.json")
    ap.add_argument("--pr5", nargs="?", const="BENCH_PR5.json", default=None,
                    help="measure EDF deadline-miss rates vs FIFO and "
                         "static tiers and write BENCH_PR5.json")
    ap.add_argument("--pr7", nargs="?", const="BENCH_PR7.json", default=None,
                    help="measure Wavescope telemetry overhead and write "
                         "BENCH_PR7.json")
    ap.add_argument("--pr8", nargs="?", const="BENCH_PR8.json", default=None,
                    help="measure admission backpressure vs the "
                         "overflowing baseline and write BENCH_PR8.json")
    ap.add_argument("--pr9", nargs="?", const="BENCH_PR9.json", default=None,
                    help="measure occupancy-adaptive compact waves vs the "
                         "full envelope and write BENCH_PR9.json")
    ap.add_argument("--pr10", nargs="?", const="BENCH_PR10.json",
                    default=None,
                    help="measure the runtime seam: LocalRuntime parity, "
                         "SimRuntime latency sweep, and the 2-process "
                         "localhost wire; write BENCH_PR10.json")
    ap.add_argument("--all", action="store_true",
                    help="run every BENCH_PR*.json emitter (CI bench smoke)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: fewer waves/iterations")
    ap.add_argument("--n-dev", type=int, default=8)
    ap.add_argument("--waves", type=int, default=32)
    cli = ap.parse_args()
    if cli.all:
        out = emit_all(quick=cli.quick, n_dev=cli.n_dev)
        print(json.dumps({k: "ok" for k in out}, indent=2))
    elif cli.pr1:
        out = emit_bench_pr1(cli.pr1, n_dev=cli.n_dev, K=cli.waves,
                             quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr2:
        out = emit_bench_pr2(cli.pr2, n_dev=cli.n_dev, K=cli.waves,
                             quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr3:
        out = emit_bench_pr3(cli.pr3, n_dev=cli.n_dev, quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr4:
        out = emit_bench_pr4(cli.pr4, n_dev=cli.n_dev, K=cli.waves,
                             quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr5:
        out = emit_bench_pr5(cli.pr5, n_dev=cli.n_dev, quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr7:
        out = emit_bench_pr7(cli.pr7, n_dev=cli.n_dev, K=cli.waves,
                             quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr8:
        out = emit_bench_pr8(cli.pr8, n_dev=cli.n_dev, quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr9:
        out = emit_bench_pr9(cli.pr9, n_dev=cli.n_dev, K=cli.waves,
                             quick=cli.quick)
        print(json.dumps(out, indent=2))
    elif cli.pr10:
        out = emit_bench_pr10(cli.pr10, n_dev=cli.n_dev, K=cli.waves,
                              quick=cli.quick)
        print(json.dumps(out, indent=2))
    else:
        for row in run_all():
            print(",".join(str(c) for c in row))
