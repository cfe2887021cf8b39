"""One run of one benchmark cell: set-up, the measured window, the check.

``Layout`` finds a cell's pieces by the names ``BENCHMARK.json`` gives:
the configuration file its ``configs`` entry names, ``traffic/<mix>.json``,
``reference/<name>.py`` and ``metrics/<metric>.py``.  ``CellRun`` builds
the configuration's structure through the program's elastic wrapper,
prefills its backlog and warms every shape the window uses through the
same ``run_waves`` program (and, where the mix has a membership schedule,
every membership it will have), drives the window as the mix says
(with a membership schedule, then drains the store at full width), and
then checks every op against the reference.  ``main`` is the command.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench.check import Burst, Log, check
from bench.membership import Schedule, store_devices
from bench.records import Pool, digest
from bench.trace import WINDOW_SPAN
from bench.traffic import Stream, prefill_keys

BENCH = Path(__file__).resolve().parent
KEPT = ("pos", "matched", "tier", "dok", "dv")
WARM_BURSTS = 2            # closed loop: mixed bursts after the prefill
# the traced run records the window's first TRACE_BURSTS // chips bursts
# (at least 2): every loop iteration of a wave program is a trace event,
# some 300,000 a burst on each chip, and writing the trace out costs some
# 20 us an event
TRACE_BURSTS = 5


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    reference: object          # the reference module
    end_to_end: List[dict]
    per_layer: List[dict]


class Layout:
    """The benchmark's files, found by name under ``bench_dir``."""

    def __init__(self, bench_dir: Path = BENCH):
        self.bench = Path(bench_dir)
        self.root = self.bench.parent
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    @staticmethod
    def _named(entries, name: str, what: str) -> dict:
        for e in entries:
            if e["name"] == name:
                return e
        raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")

    def config(self, name: str) -> dict:
        entry = self._named(self.spec["configs"], name, "configuration")
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def reference(self, name: str):
        return _module(self.bench / "reference" / f"{name}.py",
                       f"bench_reference_{name}")

    def metric(self, name: str):
        return _module(self.bench / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))

    def cell(self, name: str) -> Cell:
        w = self._named(self.spec["workloads"], name, "workload")
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        moved = {m["name"] for m in e2e}
        per_layer = [m for m in self.spec["per_layer"]
                     if name in m.get("workloads", [name])
                     and ("workloads" in m or m["moves"] in moved)]
        config = self.config(w["config"])
        return Cell(name, int(w["chips"]), config, self.traffic(w["traffic"]),
                    self.reference(config["reference"]), e2e, per_layer)


def build_structure(config: dict, n_shards: int, devices):
    """The configuration's elastic structure, its store split evenly over
    its tiers."""
    import repro.dqueue as dq
    cls = getattr(dq, config["structure"])
    return cls(n_shards,
               cap=config["store_records_per_chip"] // config["tiers"],
               payload_width=config["record_words"],
               ops_per_shard=config["wave_ops_per_chip"], devices=devices,
               **config.get("structure_args", {}))


class Overflow(Exception):
    """The program refused a burst (its store window would wrap)."""


class Refused(Overflow):
    """The program refused a membership change; the ``stranded`` ops,
    due by then and not yet dispatched, never get a reply."""

    def __init__(self, msg: str, stranded: int):
        super().__init__(msg)
        self.stranded = stranded


class CellRun:
    """One run of one cell.  ``structure`` replaces the program (the
    control, and the tests' faults); ``trace`` adds the host spans and the
    syncs that the per-layer metrics read."""

    def __init__(self, cell: Cell, seed: int, devices=None, *,
                 structure=None, trace: bool = False):
        cfg = cell.config
        self.cell, self.seed, self.trace = cell, seed, trace
        self.n = cell.chips
        self.K = cfg["waves_per_burst"]
        self.L = cfg["wave_ops_per_chip"]
        self.W = cfg["record_words"]
        self.tiers = cfg["tiers"]
        self.inputs, self.outputs = cfg["inputs"], cfg["outputs"]
        self.mix = cell.mix
        self.plan = Schedule(self.mix.get("membership", []))
        if self.plan.changes and self.mix["loop"] != "open":
            raise ValueError("a membership schedule needs an open loop")
        self.q = (structure if structure is not None
                  else build_structure(cfg, self.n, devices))
        self.pool = Pool(self.K * self.n * self.L, self.W, seed,
                         cfg.get("key_word"))
        self.log = Log()
        self.spans: Dict[str, List[float]] = {}
        self.failed = 0
        self.window_log_start = 0
        self.window_log_end = None     # set where bursts follow the window
        self.latencies = None
        self.traced_bursts = None
        self.traced_from = 0
        self._trace_dir = None
        self.members, self.pool_ids = [], frozenset()   # as scheduled
        self.marks = []            # (first burst, membership as scheduled)

    # ------------------------------------------------------------ spans --
    @contextlib.contextmanager
    def span(self, name: str):
        """Host span: in the traced run, a profiler annotation and a
        duration kept per name; otherwise nothing."""
        if not self.trace:
            yield
            return
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    # ----------------------------------------------------------- bursts --
    def widths(self) -> tuple:
        """Per-chip wave widths the mix uses: the program's bucket ladder
        for an open loop, the full width for a closed one."""
        if self.mix["loop"] == "open":
            return tuple(self.q.bucket_widths())
        return (self.L,)

    def dispatch(self, is_enq, valid, key, on_reply=None) -> None:
        """Stage one burst, run it, read the replies back (then call
        ``on_reply``), keep them."""
        K, N = is_enq.shape
        first = self.log.ids_for(is_enq, valid)
        e = (is_enq & valid).ravel()
        slots = np.flatnonzero(e)
        ids = np.arange(first, first + slots.size)
        keys = key.ravel()[slots] if key is not None else None
        payload = self.pool.stage(K * N, slots, ids, keys).reshape(K, N,
                                                                   self.W)
        arrays = {"is_enq": is_enq, "valid": valid, "tier": key,
                  "payload": payload}
        with self.span("place"):
            placed = [self.q._place(arrays[a], lead=1) for a in self.inputs]
            if self.trace:
                import jax
                jax.block_until_ready(placed)
        from repro.dqueue import QueueOverflowError
        with self.span("dispatch"):
            try:
                outs = self.q.run_waves(*placed)
            except QueueOverflowError as err:
                self.failed += int(valid.sum())
                raise Overflow(str(err)) from err
        with self.span("readback"):
            got = {a: np.asarray(o) for a, o in zip(self.outputs, outs)
                   if a in KEPT}
        if on_reply is not None:
            on_reply()
        with self.span("digest"):
            # every row's digest, then the dequeued ones: selecting the
            # rows first copies them into fresh memory, which costs more
            # and varies more than the digest of the rows left out
            dv = got.pop("dv").reshape(K * N, self.W)
            digests = digest(dv)[got["dok"].ravel()]
        self.log.add(Burst(is_enq, valid, key if self.tiers > 1 else None,
                           first, got, digests))

    def _full(self, stream_ops, N: int):
        is_enq, key, _ = stream_ops
        return (is_enq.reshape(self.K, N), np.ones((self.K, N), bool),
                key.reshape(self.K, N) if self.tiers > 1 else None)

    # ------------------------------------------------------------ set-up --
    def setup(self) -> None:
        """Prefill the mix's backlog with enqueue-only bursts, then warm
        each width the window uses with mixed bursts; all through the
        window's own ``run_waves`` programs, all checked afterwards."""
        N = self.n * self.L
        per = self.K * N
        keys = prefill_keys(self.mix, self.tiers, self.seed,
                            int(self.mix["backlog_records"]))
        for i in range(0, keys.size, per):
            m = min(per, keys.size - i)
            valid = np.zeros(per, bool)
            valid[:m] = True
            k = np.zeros(per, np.int32)
            k[:m] = keys[i:i + m]
            valid = valid.reshape(self.K, N)
            self.dispatch(valid, valid,
                          k.reshape(self.K, N) if self.tiers > 1 else None)
        warm = Stream(dict(self.mix, loop="closed"), self.seed + 1,
                      self.tiers)
        self._warm(warm)
        if self.plan.changes:
            # one pass of the schedule: the wave programs of every
            # membership and the migration programs between them
            self.members = list(self.q.device_ids)
            self.pool_ids = frozenset(self.members)
            self.marks.append((0, store_devices(self.q) == self.pool_ids))
            for change in Schedule(self.mix["membership"]).changes:
                self._apply(change)
                self._warm(warm)
        self.window_log_start = len(self.log.bursts)

    def _warm(self, warm: Stream) -> None:
        """Mixed bursts at each width the window uses, on the mesh the
        structure has now."""
        n = self.q.n_shards
        widths = self.widths()
        for w in widths * (WARM_BURSTS if len(widths) == 1 else 1):
            self.dispatch(*self._full(warm.take(self.K * n * w), n * w))

    def _apply(self, change) -> None:
        """One membership change through the program's own path; keeps
        its record, and whether the store then sits on the devices the
        schedule says."""
        if change.op == "leave":
            gone = self.members[change.shard]
            change.stats = self.q.shrink_devices(
                [self.q.device_ids[change.shard]])
            self.members.remove(gone)
        else:
            change.stats = self.q.grow(1)
            self.members.append(min(self.pool_ids - set(self.members)))
        change.devices = store_devices(self.q)
        self.marks.append((len(self.log.bursts),
                           change.devices == frozenset(self.members)))

    # ------------------------------------------------------------ window --
    def window(self, seconds: float, clock=time.perf_counter,
               sleep=time.sleep, trace_dir: Optional[str] = None) -> dict:
        """Drive the window; returns ``attempted``, ``answered`` and the
        window's ``seconds`` (the whole time, to the last reply).  With
        ``trace_dir`` the profiler records the window's first bursts
        there (``TRACE_BURSTS``); with a membership schedule, from just
        before the first change to the last one's recovery."""
        stream = Stream(self.mix, self.seed, self.tiers)
        self.traced_from = len(self.log.bursts)
        self._trace_dir = trace_dir
        self._trace_start(None if self.plan.changes else trace_dir)
        try:
            if self.mix["loop"] == "open":
                return self._open(stream, seconds, clock, sleep)
            return self._closed(stream, seconds, clock)
        finally:
            self._trace_stop()

    def _trace_start(self, trace_dir) -> None:
        self._tracing = None
        if trace_dir is None:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        ann.__enter__()
        self._tracing = ann

    def _tick(self) -> None:
        """After each dispatch: end the trace once it is long enough."""
        if self.plan.changes:
            if self._tracing is not None and self.plan.done:
                self._trace_stop()
        elif self._tracing is not None and len(self.log.bursts) - \
                self.window_log_start >= max(2, TRACE_BURSTS // self.n):
            self._trace_stop()

    def _trace_stop(self) -> None:
        if getattr(self, "_tracing", None) is None:
            return
        import jax
        self._tracing.__exit__(None, None, None)
        self._tracing = None
        jax.profiler.stop_trace()
        self.traced_bursts = len(self.log.bursts) - self.traced_from

    def _closed(self, stream, seconds, clock) -> dict:
        N = self.n * self.L
        t0 = clock()
        bursts = 0
        try:
            while clock() - t0 < seconds:
                with self.span("generate"):
                    ops = self._full(stream.take(self.K * N), N)
                bursts += 1
                self.dispatch(*ops)
                self._tick()
        except Overflow:
            pass
        t = clock() - t0
        return {"attempted": bursts * self.K * N,
                "answered": bursts * self.K * N - self.failed, "seconds": t}

    def _open(self, stream, seconds, clock, sleep) -> dict:
        """Ops arrive when due; those due while a dispatch runs make the
        next batch, staged at the narrowest ladder width that holds
        them.  Each op's latency runs from its due time to the moment its
        batch's replies are on the host.  A membership change due is made
        between dispatches; bursts are as wide as the shards then are."""
        widths = self.widths()
        plan = self.plan
        lat, attempted = [], 0
        t0 = clock()

        def on_reply():
            now = clock() - t0
            lat.append(now - d)
            plan.replied(attempted, now)
        try:
            while True:
                t = clock() - t0
                if plan.next_at() <= t:
                    self._change(stream, t, attempted, clock, t0)
                    continue
                end = plan.end(seconds)
                n = self.q.n_shards
                due = stream.peek_due(self.K * n * widths[-1])
                m = int(np.searchsorted(due, min(t, end), "right"))
                if m == 0:
                    if due[0] >= end:
                        break
                    sleep(min(due[0], plan.next_at()) - t)
                    continue
                with self.span("generate"):
                    is_enq, key, d = stream.take(m)
                    attempted += m
                    w = next(w for w in widths if self.K * n * w >= m)
                    N = n * w
                    flat = np.zeros((3, self.K * N), np.int32)
                    flat[0, :m], flat[1, :m], flat[2, :m] = is_enq, 1, key
                    shaped = flat.reshape(3, self.K, N)
                self.dispatch(shaped[0].astype(bool), shaped[1].astype(bool),
                              shaped[2] if self.tiers > 1 else None,
                              on_reply=on_reply)
                self._tick()
        except Refused as err:
            attempted += err.stranded
        except Overflow:
            pass
        self.latencies = np.concatenate(lat) if lat else np.zeros(0)
        answered = int(self.latencies.size)
        return {"attempted": attempted, "answered": answered,
                "seconds": clock() - t0}

    def _change(self, stream, t, attempted, clock, t0) -> None:
        """Issue the schedule's next change at window time ``t``; it
        recovers once the ops due before it returned are answered.  A
        change the program refuses ends the window: the ops due by then
        that were not dispatched fail."""
        plan = self.plan
        if plan.issued == 0 and self._trace_dir is not None:
            self.traced_from = len(self.log.bursts)
            self._trace_start(self._trace_dir)
        c = plan.changes[plan.issued]
        c.issued = t
        try:
            self._apply(c)
        except (ValueError, RuntimeError) as err:
            stranded = stream.count_due(clock() - t0)
            self.failed += stranded
            raise Refused(f"{c.op} refused: {err}", stranded) from err
        c.returned = clock() - t0
        c.target = attempted + stream.count_due(c.returned)
        plan.issued += 1

    def drain(self) -> int:
        """With a membership schedule, after the window: dequeue-only
        bursts at full width on the membership the window left, through
        the same ``run_waves`` program, until one finds the queue empty.
        The check then compares every record the store held, each one the
        changes migrated among them, and not only those the window
        dequeued.  Untimed; returns the ops it dequeued."""
        self.window_log_end = len(self.log.bursts)
        if not self.plan.changes:
            return 0
        N = self.q.n_shards * self.L
        valid = np.ones((self.K, N), bool)
        key = np.zeros((self.K, N), np.int32) if self.tiers > 1 else None
        most = self.n * self.cell.config["store_records_per_chip"]
        dequeued = 0
        for _ in range(most // (self.K * N) + 2):
            try:
                self.dispatch(~valid, valid, key)
            except Overflow:
                break
            got = self.log.bursts[-1].got["dok"]
            dequeued += int(got.sum())
            if not got.all():
                break
        return dequeued

    # ------------------------------------------------------------- check --
    def check(self) -> Dict[str, int]:
        """Every op of the run (set-up's included) against the reference.
        The reference's ring maps live positions to ids: it is as long as
        the largest store the run has, which holds the live span of every
        membership.  It does not shrink with a LEAVE: a ring as long as
        the capacity in force would wrap where a program that accepts ops
        past that capacity wraps, and agree with it.  Where the schedule
        changes membership, every op answered while the store sat on
        other devices than the schedule says is wrong, and each scheduled
        change not made counts."""
        window = (self.n * self.cell.config["store_records_per_chip"]
                  // self.tiers)
        replay = self.cell.reference.Replay(self.tiers, window)
        if not self.plan.changes:
            return check(self.log, replay, self.pool)
        ends = [i for i, _ in self.marks[1:]] + [len(self.log.bursts)]
        void = {b for (i, ok), end in zip(self.marks, ends) if not ok
                for b in range(i, end)}
        numbers = check(self.log, replay, self.pool, void)
        numbers["wrong_membership"] = sum(
            int(self.log.bursts[b].valid.sum()) for b in void)
        numbers["changes_not_made"] = sum(
            math.isnan(c.returned) for c in self.plan.changes)
        return numbers

    def free(self) -> None:
        """Drop the program's state so that the check runs beside an
        empty device."""
        import jax
        for leaf in jax.tree.leaves(getattr(self.q, "state", None)):
            leaf.delete()

    def _measured(self) -> slice:
        """The window's bursts, or in the traced run the traced ones."""
        if self.traced_bursts is None:
            end = self.window_log_end
            return slice(self.window_log_start,
                         len(self.log.bursts) if end is None else end)
        return slice(self.traced_from, self.traced_from + self.traced_bursts)

    def window_counts(self) -> dict:
        """Ops, enqueues and answered dequeues of the measured bursts."""
        c = {"bursts": 0, "ops": 0, "enqueues": 0, "dequeued": 0}
        for b in self.log.bursts[self._measured()]:
            c["bursts"] += 1
            c["ops"] += int(b.valid.sum())
            c["enqueues"] += int((b.is_enq & b.valid).sum())
            c["dequeued"] += int(b.got["dok"].sum())
        return c

    def span_ms(self, name: str) -> Optional[float]:
        """Mean of a per-burst host span over the measured bursts, in ms
        (every burst, set-up's included, has one of each)."""
        v = self.spans.get(name, [])[self._measured()]
        return 1e3 * float(np.mean(v)) if v else None


LIMITS = {"wrong_ops": 0}
MEMBERSHIP_LIMITS = {"changes_not_made": 0}


def limits_for(run: CellRun) -> dict:
    """The compared numbers and their limits: with a membership schedule,
    every scheduled change has to be made as well."""
    return dict(LIMITS, **(MEMBERSHIP_LIMITS if run.plan.changes else {}))


@dataclass
class Result:
    """What a metric reader reads: the run, the window, the set-up time,
    the trace (traced run only) and the chip's peaks."""

    run: CellRun
    window: dict
    setup_seconds: float
    peaks: dict
    trace: Optional[object] = None

    @property
    def counts(self) -> dict:
        return self.run.window_counts()


def peaks_for(kind: str, bench_dir: Path = BENCH) -> dict:
    """The peaks of a device kind; a kind not in the table is an error."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def read_metrics(layout: Layout, entries: List[dict], res: Result) -> dict:
    """Each metric's reader, by the metric's name; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        v = layout.metric(m["name"]).read(res)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


class CompileCounter:
    """Backend compiles and persistent-cache loads seen by the process."""

    def __init__(self, jax):
        self.compiles = self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def total(self) -> int:
        return self.compiles + self.cache_hits


def _say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def run_cell(layout: Layout, cell: Cell, seed: int, seconds: float,
             trace: bool, devices, peaks: dict, t_start: float) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import tempfile
    import jax
    counter = CompileCounter(jax)
    run = CellRun(cell, seed, devices, trace=trace)
    run.setup()
    setup_seconds = time.perf_counter() - t_start
    _say(f"{cell.name} seed {seed}: set-up {setup_seconds:.3f} s "
         f"({counter.compiles} backend compiles, {counter.cache_hits} "
         f"compile-cache loads)")
    before = counter.total
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        win = run.window(seconds, trace_dir=tdir if trace else None)
        in_window = counter.total - before
        if run.plan.changes:
            t = time.perf_counter()
            drained = run.drain()
            _say(f"drain after the window: {drained} records dequeued in "
                 f"{len(run.log.bursts) - run.window_log_end} bursts, "
                 f"{time.perf_counter() - t:.3f} s, "
                 f"{counter.total - before - in_window} compiles")
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devices)
        run.free()
        tr = None
        if trace:
            from bench.trace import load
            t = time.perf_counter()
            tr = load(tdir)
            _say(f"trace: {run.traced_bursts} bursts in "
                 f"{tr.window_ns * 1e-9:.6f} s, read in "
                 f"{time.perf_counter() - t:.3f} s")
    _say(f"window: {win['attempted']} ops attempted, {win['answered']} "
         f"answered in {win['seconds']:.6f} s; {run.failed} failed; "
         f"{in_window} compiles in the window")
    for c in run.plan.changes:
        _say(f"{c.op}: issued at {c.issued:.6f} s, returned "
             f"{c.returned:.6f}, recovered {c.recovered:.6f}; moved "
             f"{c.stats.get('moved')} records; store on devices "
             f"{sorted(c.devices)}")
    t = time.perf_counter()
    numbers = run.check()
    _say(f"check of {numbers['ops_checked']} ops (every op of the run) "
         f"took {time.perf_counter() - t:.3f} s; wrong replies "
         f"{numbers['wrong_replies']}, wrong records "
         f"{numbers['wrong_records']}")
    limits = limits_for(run)
    res = Result(run, win, setup_seconds, peaks, tr)
    metrics = read_metrics(layout, cell.per_layer if trace
                           else cell.end_to_end, res)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem}
    out = {"correct": all(numbers[k] <= lim for k, lim in limits.items())
           and win["answered"] > 0,
           "attempted": win["attempted"], "failed": run.failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_ns() * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        out["breakdown"] = {"device_ops": tr.top_ops(10),
                            "idle_gaps": tr.idle_by_host_span(10)}
    out["check"] = {k: {"value": numbers[k], "limit": lim}
                    for k, lim in limits.items()}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    layout = Layout()
    cell = layout.cell(args.workload)
    import repro.dqueue  # noqa: F401  (the system under test, or no run)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _say(f"no TPU: JAX runs on {devs[0].platform!r}; no CPU fallback")
        return 2
    if len(devs) < cell.chips:
        _say(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devs)}")
        return 2
    peaks = peaks_for(devs[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    _say(f"compile cache: {enable_compile_cache()}")
    # every program of the run, however quick to compile, is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run_cell(layout, cell, args.seed, args.seconds, bool(args.trace),
                   devs[:cell.chips], peaks, t_start)
    for k, v in out["check"].items():
        _say(f"check {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)
    return 0
