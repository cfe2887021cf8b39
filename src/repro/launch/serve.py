"""Serving driver: continuous batching fed by the SKUEUE request queue.

  python -m repro.launch.serve --arch mamba2_130m --requests 12
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_config
from ..launch.compile_cache import enable_compile_cache
from ..launch.mesh import make_host_mesh
from ..models import build_model
from ..serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_8b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=6)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(0))
    mesh = make_host_mesh(n_data=len(jax.devices()))
    eng = ServeEngine(model, params, mesh, max_slots=args.slots, max_seq=32)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=list(rng.integers(0, cfg.vocab, 4)),
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    eng.submit(reqs[: len(reqs) // 2])
    for _ in range(3):
        eng.step()
    eng.submit(reqs[len(reqs) // 2:])
    ok = eng.run_until_drained()
    dt = time.time() - t0
    tok = sum(len(r.out) for r in reqs)
    print(f"served {eng.stats['served']}/{len(reqs)} requests, {tok} tokens "
          f"in {dt:.1f}s ({tok/dt:.1f} tok/s); drained={ok}")
    order = sorted(reqs, key=lambda r: r.start_step)
    fifo = all(order[i].enqueue_step <= order[i + 1].enqueue_step
               for i in range(len(order) - 1))
    print(f"queue FIFO admission order preserved: {fifo}")
    for r in reqs[:3]:
        print(f"  rid={r.rid} prompt={r.prompt} -> out={r.out}")


if __name__ == "__main__":
    main()
