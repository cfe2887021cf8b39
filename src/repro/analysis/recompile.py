"""Rule family 3 — recompile guard, and the compile counter by program.

``jax.monitoring`` emits ``/jax/core/compile/backend_compile_duration``
(with the program's ``fun_name``) around jax's ``compile_or_get_cached``,
once for each executable jax does not yet hold in memory: a real backend
compile, or a load from the persistent compilation cache.  A load also
emits ``/jax/compilation_cache/cache_retrieval_time_sec`` inside the same
call (beside ``/jax/compilation_cache/cache_hits``), which tells the two
apart.  An executable jax already holds (a repeated call, or a
``lower().compile()`` of a program already run) emits neither.

That is exactly the observable we need to assert the elastic layer's mesh
/ inner-engine / migration caches prevent recompilation when
membership bounces between shard counts, that the burst-length jit cache
holds when K bounces, and that bouncing across the occupancy-bucket
envelope ladder re-uses the per-width executables instead of
recompiling.

The scenario runs every bounce twice: the first pass is allowed (and
expected) to compile; the second identical pass must compile *nothing*.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List

import numpy as np

from .report import Violation

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompilationTracker:
    """Counts compiles-or-loads inside a ``with`` block (and the programs
    they were for), and, for the
    whole process, the compiles and persistent-cache loads of each
    program by name (:meth:`by_program`).

    jax.monitoring listeners cannot be individually unregistered, so one
    process-wide listener is installed on first use (or by
    :meth:`install`, which the elastic structures call before they build
    their first program) and fans out to the stack of active trackers.
    """
    _installed = False
    _active: List["CompilationTracker"] = []
    _lock = threading.Lock()
    _loading = threading.local()   # a cache load is under way
    # fun_name -> [compiles, compile seconds, loads, load seconds]
    _programs: Dict[str, list] = {}

    def __init__(self) -> None:
        self.count = 0
        self.events: List[float] = []
        self.programs: List[str] = []

    @classmethod
    def _on_event(cls, event: str, duration: float, **kw: Any) -> None:
        if event == _LOAD_EVENT:
            cls._loading.hit = True
            return
        if event != _COMPILE_EVENT:
            return
        loaded = getattr(cls._loading, "hit", False)
        cls._loading.hit = False
        name = str(kw.get("fun_name", "?"))
        with cls._lock:
            row = cls._programs.setdefault(name, [0, 0.0, 0, 0.0])
            row[2 * loaded] += 1
            row[2 * loaded + 1] += duration
        for t in cls._active:
            t.count += 1
            t.events.append(duration)
            t.programs.append(name)

    @classmethod
    def install(cls) -> None:
        """Install the process-wide listener (once)."""
        with cls._lock:
            if cls._installed:
                return
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(cls._on_event)
            cls._installed = True

    @classmethod
    def by_program(cls) -> Dict[str, dict]:
        """Per program name (``jit(skueue_fifo_waves)``), since the
        listener was installed: backend ``compiles`` and their
        ``compile_s``, persistent-cache ``loads`` and their ``load_s``."""
        with cls._lock:
            return {n: {"compiles": c, "compile_s": cs, "loads": ld,
                        "load_s": ls}
                    for n, (c, cs, ld, ls) in sorted(cls._programs.items())}

    def __enter__(self) -> "CompilationTracker":
        self.install()
        CompilationTracker._active.append(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        CompilationTracker._active.remove(self)


def _bounce(eq, K_a: int, K_b: int, grow_by: int) -> None:
    """One full membership + burst-length + bucket-width bounce on an
    elastic queue: step, burst K_a, burst K_b, the occupancy-bucket
    ladder, grow, step, shrink back.  The PR 9 envelope buckets are pure
    jit shape keys, so bouncing across widths must hit the same
    per-shape executable cache the K bounce exercises."""
    import jax.numpy as jnp

    P0 = eq.n_shards

    def drive_step(w=None):
        n = eq.n_shards * (eq.L if w is None else w)
        eq.step(jnp.zeros(n, bool), jnp.zeros(n, bool),
                jnp.zeros((n, eq.W), jnp.int32))

    def drive_waves(K: int):
        n = eq.n_shards * eq.L
        eq.run_waves(jnp.zeros((K, n), bool), jnp.zeros((K, n), bool),
                     jnp.zeros((K, n, eq.W), jnp.int32))

    def drive_ladder():
        for w in eq.bucket_widths():      # narrow -> full envelope
            drive_step(w)
        for w in reversed(eq.bucket_widths()):   # bounce back down
            drive_step(w)

    drive_step()
    drive_waves(K_a)
    drive_waves(K_b)
    drive_waves(K_a)                      # K bounce back: cached jit shape
    drive_ladder()                        # width bounce: cached jit shapes
    eq.grow(grow_by)
    drive_step()
    drive_waves(K_a)
    drive_ladder()                        # ladder on the grown membership
    eq.shrink(list(range(P0, P0 + grow_by)))
    drive_step()


def check_recompile_guard() -> "tuple[List[Violation], Dict[str, Any]]":
    """Warm one bounce (compiles allowed), then repeat it and require the
    compilation counter to stay at zero."""
    import jax

    from ..dqueue import ElasticDeviceQueue

    n_dev = len(jax.devices())
    if n_dev < 3:
        return [], {"skipped": f"needs >= 3 devices, have {n_dev}"}
    grow_by = 1 if n_dev < 6 else 2
    P0 = min(4, n_dev - grow_by)

    eq = ElasticDeviceQueue(P0, cap=16, payload_width=2, ops_per_shard=2)
    with CompilationTracker() as warm:
        _bounce(eq, K_a=2, K_b=3, grow_by=grow_by)
    with CompilationTracker() as second:
        _bounce(eq, K_a=2, K_b=3, grow_by=grow_by)

    info: Dict[str, Any] = {
        "warm_compiles": warm.count,
        "second_bounce_compiles": second.count,
        "second_bounce_programs": sorted(set(second.programs)),
        "P0": P0, "grow_by": grow_by,
    }
    out: List[Violation] = []
    if warm.count == 0:
        out.append(Violation(
            "recompile_guard", "elastic.bounce",
            "tracker observed no compiles on the cold bounce — the "
            "compile-event hook is broken, guard is vacuous", dict(info)))
    if second.count != 0:
        out.append(Violation(
            "recompile_guard", "elastic.bounce",
            f"{second.count} recompilation(s) on an identical second "
            f"membership/burst bounce — a mesh/program cache is leaking: "
            f"{', '.join(sorted(set(second.programs)))}", dict(info)))
    # sanity: the caches must actually be populated, not bypassed
    if not eq._inner_cache or not eq._mig_cache or not eq._mesh_cache:
        out.append(Violation(
            "recompile_guard", "elastic.bounce",
            "elastic caches empty after a bounce — cache keying bypassed",
            {"inner": len(eq._inner_cache), "mig": len(eq._mig_cache),
             "mesh": len(eq._mesh_cache)}))
    moved = sum(int(np.asarray(m["moved"])) for m in eq.migrations)
    info["migrations"] = len(eq.migrations)
    info["moved_total"] = moved
    return out, info
