#!/usr/bin/env python3
"""Run the Skueue wave queue's main path on TPU, at deployment size.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the paths that exist only across chips

One chip: FIFO, LIFO, priority (4 tiers) and Seap (8 buckets), each with a
store of about 1 GiB (2^20 records of 1 KiB, the OpenMessaging Benchmark
message size), waves of 8192 ops per shard and 8 waves per burst, filled
to at least half the store and drained to empty, every reply checked op by
op against a plain host reference; then ``ServeEngine`` over the published
``mamba2_130m`` with 4 priority tiers.  Four chips: the four disciplines on
a 4-chip mesh with a 1 GiB store per chip, and one JOIN (3 -> 4) and one
LEAVE (4 -> 3) under a backlog.

Stops with a non-zero exit code, before running anything, where JAX finds
no TPU.  Every line but the last is one smoke run's observations, not a
benchmark; the last line is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

RECORD_WORDS = 256        # 1 KiB records
RECORDS_PER_CHIP = 1 << 20
WAVE_OPS = 8192           # ops per shard per wave
BURST_WAVES = 8


def _emit(report: list, phase: str, stats: dict) -> None:
    report.append({"phase": phase, **stats})
    print(f"smoke {phase}: {json.dumps(stats, default=str)}", flush=True)


def _tpu_or_exit():
    try:
        import jax
        devices = jax.devices()
    except Exception as e:  # no backend at all
        sys.exit(f"chip_smoke: no TPU found ({e})")
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX runs on "
                 f"{devices[0].platform!r})")
    return jax, devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", help="also write the full report here (JSON)")
    args = ap.parse_args(argv)

    jax, devices = _tpu_or_exit()
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, JAX sees {len(devices)}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    from repro.launch import smoke

    compiles = {"backend_compile_s": 0.0, "persistent_cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["backend_compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["persistent_cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    dev = devices[0]
    print(f"# chip_smoke --chips {args.chips} on {len(devices)} x "
          f"{dev.device_kind}: one smoke run; the figures below are single "
          f"observations, not benchmark numbers.  compile cache: {cache_dir}",
          flush=True)
    report: list = []
    t0 = time.perf_counter()
    n = args.chips
    for kind in smoke.KINDS:
        stats = smoke.run_discipline(
            kind, n_shards=n, records=RECORDS_PER_CHIP, W=RECORD_WORDS,
            L=WAVE_OPS, K=BURST_WAVES, seed=args.seed,
            devices=devices[:n])
        comp = stats["compile"]
        if kind != "fifo" and not comp["tpu_custom_call"]:
            raise smoke.SmokeFailure(
                f"{kind}: no tpu_custom_call in the compiled wave program; "
                f"the segscan sweep did not compile for the chip")
        if n > 1 and len({d for d, _ in stats["placement"]}) != n:
            raise smoke.SmokeFailure(
                f"{kind}: store shards are not one per device: "
                f"{stats['placement']}")
        _emit(report, kind, stats)
    if n > 1:
        _emit(report, "membership", smoke.run_membership(
            n_from=n - 1, records=RECORDS_PER_CHIP, W=RECORD_WORDS,
            L=WAVE_OPS, K=BURST_WAVES, seed=args.seed, devices=devices[:n]))
    else:
        from repro.configs import get_config
        _emit(report, "serving", smoke.run_serving(
            get_config("mamba2_130m"), n_requests=16, priorities=4,
            seed=args.seed))
    _emit(report, "compiles", {**compiles,
                               "wall_s": time.perf_counter() - t0})
    result = {"ok": True, "device": {"platform": dev.platform,
                                     "kind": dev.device_kind,
                                     "count": len(devices)}}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"result": result, "phases": report}, f, indent=1,
                      default=str)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
