"""The control of the check: the reference in the program's place, with
one guarantee broken, must come out not correct.

The system states no precision, so the control breaks the order the
configuration guarantees: ``ReferenceStructure(control=True)`` answers
``run_waves`` as the program does, from a plain host store, but hands
each wave's successful dequeues their answers in reverse order.  With
``control=False`` it is the plain reference and must come out correct.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] --seconds <s>

runs the cell's set-up and a window of ``--seconds`` with the control in
the program's place, at the cell's own sizes, and prints one JSON line
per seed with the compared numbers.  It needs no chip; the benchmark's
own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reverse_within_waves(out: dict, ok, fields) -> None:
    """The control's broken guarantee: within each wave, the successful
    dequeues get their answers in reverse order."""
    for k in range(ok.shape[0]):
        idx = np.flatnonzero(ok[k])
        for f in fields:
            out[f][k, idx] = out[f][k, idx[::-1]]


class ReferenceStructure:
    """The configuration's reference with ``run_waves``'s interface;
    with ``control`` it hands each wave's successful dequeues their
    answers in reverse order."""

    def __init__(self, cell, control: bool = True):
        cfg = cell.config
        self.tiers = cfg["tiers"]
        self.W = cfg["record_words"]
        self.L = cfg["wave_ops_per_chip"]
        self.inputs, self.outputs = cfg["inputs"], cfg["outputs"]
        self.window = (cell.chips * cfg["store_records_per_chip"]
                       // self.tiers)
        self.replay = cell.reference.Replay(self.tiers, self.window)
        self.control = control
        self.store = np.zeros((self.tiers, self.window, self.W), np.int32)

    def bucket_widths(self) -> tuple:
        return tuple(sorted({max(1, self.L // 4), max(1, self.L // 2),
                             self.L}))

    @staticmethod
    def _place(x, lead: int = 0):
        return np.asarray(x)

    def run_waves(self, *arrays):
        a = dict(zip(self.inputs, arrays))
        is_enq, valid, payload = a["is_enq"], a["valid"], a["payload"]
        key = a.get("tier")
        if key is None:
            key = np.zeros(is_enq.shape, np.int32)
        e = is_enq & valid
        ids = np.where(e, payload[..., 0], -1)
        exp = self.replay.burst(is_enq, valid, key, ids)
        if self.control:
            reverse_within_waves(exp, ~is_enq & valid & exp["matched"],
                                 [f for f in ("tier", "pos", "value")
                                  if f in exp])
        tier = exp.get("tier", np.where(exp["matched"], 0, -1))
        slot = exp["pos"] % self.window
        self.store[tier[e], slot[e]] = payload[e]
        deq = ~is_enq & valid & exp["matched"]
        dv = np.zeros(payload.shape, np.int32)
        dv[deq] = self.store[tier[deq], slot[deq]]
        K = is_enq.shape[0]
        out = {"pos": exp["pos"], "matched": exp["matched"], "tier": tier,
               "dv": dv, "dok": deq, "ovf": np.zeros(K, bool),
               "n_relaxed": np.zeros(K, np.int32)}
        return tuple(out[o] for o in self.outputs)


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import LIMITS, CellRun, Layout
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plain", action="store_true",
                    help="the plain reference, not the control")
    args = ap.parse_args(argv)
    cell = Layout().cell(args.workload)
    for seed in args.seeds:
        run = CellRun(cell, seed, structure=ReferenceStructure(
            cell, control=not args.plain))
        run.setup()
        win = run.window(args.seconds)
        numbers = run.check()
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": not args.plain,
            "correct": all(numbers[k] <= v for k, v in LIMITS.items()),
            "window": win, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
