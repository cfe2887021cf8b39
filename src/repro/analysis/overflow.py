"""Rule family 4 — int32-overflow lint over the tropical-semiring jaxprs.

The scan-queue arithmetic lives on int32 with ``INF = 2**30`` as tropical
+infinity and the Seap directory carrying genuinely full-range keys
(``key_lo``/``key_hi`` start at +-2^31).  The invariant that keeps this
sound is *structural*: every add/sub touching an extreme value must be
immediately clamped (``min``/``max``), selected around (``where`` with an
explicit extreme guard), or be one of two blessed idioms —

* the overflow-free midpoint ``(a & b) + ((a ^ b) >> 1)``;
* ``associative_scan``'s interleave, which adds two *disjointly*
  zero-interior-padded arrays (one operand is always the 0 padding).

The lint inlines nested ``jit`` calls (``jnp.where`` & friends trace as
sub-jaxprs) into one flat equation list, runs a forward taint pass and
reports:

  V1 ``both-extreme-add``: add/sub/mul with *both* operands reachable
     from extreme values (wraps regardless of downstream guards);
  V2 ``unclamped-extreme-add``: add/sub with one tainted operand whose
     result never reaches a clamp (min/max/clamp/reduce_min/reduce_max)
     or a ``select_n`` guard.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from .report import Violation

TAINT_BOUND = 2 ** 30

# ops whose output is index-like / boolean — never extreme-valued
_UNTAINT_OUT = frozenset({
    "eq", "ne", "lt", "le", "gt", "ge", "argmax", "argmin", "iota",
    "reduce_and", "reduce_or", "sign", "is_finite",
})
# ops that merely move values around: taint and guard-search pass through
_PASS_THROUGH = frozenset({
    "reshape", "broadcast_in_dim", "concatenate", "slice", "squeeze",
    "transpose", "convert_element_type", "pad", "gather", "dynamic_slice",
    "dynamic_update_slice", "rev", "expand_dims", "copy", "stop_gradient",
    "scatter",
})
# consuming one of these bounds the result again (or explicitly branches
# on the extreme case): the add is considered guarded
_GUARDS = frozenset({
    "min", "max", "clamp", "select_n", "reduce_min", "reduce_max",
})
_ARITH = frozenset({"add", "sub", "mul"})
_INLINE_PRIMS = frozenset({"jit", "closed_call", "core_call", "remat",
                           "checkpoint", "custom_jvp_call",
                           "custom_vjp_call"})


class _FakeLit:
    """Stand-in literal for a sub-jaxpr const, so taint can read its
    value the same way it reads a jax Literal."""
    __slots__ = ("val",)

    def __init__(self, val: Any) -> None:
        self.val = val


class _FlatEqn(NamedTuple):
    prim: str
    invars: Tuple[Any, ...]   # Var | Literal | _FakeLit, jit-resolved
    outvars: Tuple[Any, ...]
    params: Dict[str, Any]
    eqn: Any                  # original JaxprEqn (for messages)


def _is_int(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and np.issubdtype(dt, np.integer)


def _const_tainted(val) -> bool:
    try:
        arr = np.asarray(val)
    except Exception:
        return False
    if arr.size == 0 or not np.issubdtype(arr.dtype, np.integer):
        return False
    return bool(np.abs(arr.astype(np.int64)).max() >= TAINT_BOUND)


def _flatten_into(jaxpr, consts: Sequence, sub: Dict[int, Any],
                  out: List[_FlatEqn]) -> Dict[int, Any]:
    """Inline every jit-like call into one flat equation list, rewriting
    operand references through the call boundary."""
    env = dict(sub)
    for cv, c in zip(jaxpr.constvars, consts):
        env[id(cv)] = _FakeLit(c)

    def res(atom):
        return env.get(id(atom), atom)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        cj = None
        if name in _INLINE_PRIMS:
            cj = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if cj is not None:
            inner = cj.jaxpr if hasattr(cj, "jaxpr") else cj
            iconsts = (cj.consts if hasattr(cj, "consts")
                       else [None] * len(inner.constvars))
            isub = {id(iv): res(pv)
                    for iv, pv in zip(inner.invars, eqn.invars)}
            ienv = _flatten_into(inner, iconsts, isub, out)
            for pov, iov in zip(eqn.outvars, inner.outvars):
                env[id(pov)] = ienv.get(id(iov), iov)
        else:
            out.append(_FlatEqn(name, tuple(res(v) for v in eqn.invars),
                                tuple(eqn.outvars), dict(eqn.params), eqn))
    return env


def _fmt(fe: _FlatEqn) -> str:
    s = str(fe.eqn)
    return s if len(s) <= 200 else s[:197] + "..."


def _is_zero_interleave_pad(fe: "_FlatEqn | None") -> bool:
    """``pad(x, 0)`` with interior padding — associative_scan's
    interleave operand (its support is disjoint from its partner's)."""
    if fe is None or fe.prim != "pad":
        return False
    cfg = fe.params.get("padding_config", ())
    if not any(len(d) >= 3 and d[2] >= 1 for d in cfg):
        return False
    if len(fe.invars) < 2:
        return False
    pv = fe.invars[1]
    if not hasattr(pv, "val"):
        return False
    try:
        return bool((np.asarray(pv.val) == 0).all())
    except Exception:
        return False


class _Lint:
    def __init__(self, flat: List[_FlatEqn], invars, taint_in,
                 outvars, program: str) -> None:
        self.flat = flat
        self.program = program
        self.taint: Dict[int, bool] = {
            id(v): bool(t) for v, t in zip(invars, taint_in)}
        self.producer: Dict[int, _FlatEqn] = {}
        self.consumers: Dict[int, List[_FlatEqn]] = {}
        self.out_ids = {id(v) for v in outvars}

    def get(self, atom) -> bool:
        if hasattr(atom, "val"):
            return _const_tainted(atom.val)
        return self.taint.get(id(atom), False)

    # ---------------------------------------------------- forward pass ---
    def propagate(self) -> List[Tuple[_FlatEqn, List[bool]]]:
        arith: List[Tuple[_FlatEqn, List[bool]]] = []
        for fe in self.flat:
            in_t = [self.get(v) for v in fe.invars]
            for v in fe.invars:
                if not hasattr(v, "val"):
                    self.consumers.setdefault(id(v), []).append(fe)
            if fe.prim == "sort":
                # operands are co-sorted: output i is a permutation of
                # operand i (argsort's index output stays index-like)
                out_t = list(in_t[:len(fe.outvars)])
                out_t += [False] * (len(fe.outvars) - len(out_t))
            elif fe.prim in _UNTAINT_OUT:
                out_t = [False] * len(fe.outvars)
            else:
                out_t = [any(in_t)] * len(fe.outvars)
            for var, t in zip(fe.outvars, out_t):
                self.taint[id(var)] = t
                self.producer[id(var)] = fe
            if fe.prim in _ARITH and any(in_t):
                arith.append((fe, in_t))
        return arith

    # ------------------------------------------------- blessed idioms ---
    def _is_midpoint_idiom(self, fe: _FlatEqn) -> bool:
        if fe.prim != "add" or len(fe.invars) != 2:
            return False

        def prod(atom):
            return self.producer.get(id(atom))

        def inputs(e: _FlatEqn):
            return frozenset(id(v) for v in e.invars
                             if not hasattr(v, "val"))

        for x, y in ((fe.invars[0], fe.invars[1]),
                     (fe.invars[1], fe.invars[0])):
            px, py = prod(x), prod(y)
            if px is None or py is None or px.prim != "and":
                continue
            if py.prim not in ("shift_right_arithmetic",
                               "shift_right_logical"):
                continue
            pxor = prod(py.invars[0])
            if pxor is not None and pxor.prim == "xor" \
                    and inputs(px) == inputs(pxor):
                return True
        return False

    def _is_interleave(self, fe: _FlatEqn) -> bool:
        return all(_is_zero_interleave_pad(self.producer.get(id(v)))
                   for v in fe.invars if not hasattr(v, "val")) \
            and len(fe.invars) == 2 and not any(
                hasattr(v, "val") for v in fe.invars)

    # ----------------------------------------------------- guard search ---
    def guarded(self, var, depth: int = 8) -> bool:
        seen = set()
        frontier = [id(var)]
        for _ in range(depth):
            nxt: List[int] = []
            for vid in frontier:
                if vid in seen:
                    continue
                seen.add(vid)
                if vid in self.out_ids:
                    return False        # escapes the program unclamped
                for fe in self.consumers.get(vid, []):
                    if fe.prim in _GUARDS:
                        return True
                    if fe.prim in _PASS_THROUGH:
                        nxt.extend(id(v) for v in fe.outvars)
            if not nxt:
                break
            frontier = nxt
        return False

    # -------------------------------------------------------- verdicts ---
    def check(self) -> List[Violation]:
        out: List[Violation] = []
        for fe, in_t in self.propagate():
            ov = fe.outvars[0]
            if not _is_int(getattr(ov, "aval", None)):
                continue
            if sum(bool(t) for t in in_t) >= 2:
                if self._is_midpoint_idiom(fe) or self._is_interleave(fe):
                    continue
                out.append(Violation(
                    "int32_overflow", self.program,
                    f"{fe.prim} with BOTH operands reachable from "
                    f"int32-extreme values (can wrap regardless of "
                    f"downstream guards): {_fmt(fe)}",
                    {"kind": "both-extreme-add", "eqn": _fmt(fe)}))
            elif not self.guarded(ov):
                out.append(Violation(
                    "int32_overflow", self.program,
                    f"{fe.prim} on an int32-extreme operand whose result "
                    f"is never clamped (min/max/clamp) or selected around "
                    f"(where): {_fmt(fe)}",
                    {"kind": "unclamped-extreme-add", "eqn": _fmt(fe)}))
        return out


def lint_jaxpr(fn, avals: Sequence, *, program: str,
               tainted_args: Sequence[int] = ()) -> List[Violation]:
    """Trace ``fn(*avals)``, inline nested jit calls, and lint the flat
    jaxpr.  ``tainted_args`` are flat positional indices whose values are
    full-range int32 (keys, directory boundaries)."""
    import jax

    closed = jax.make_jaxpr(fn)(*avals)
    flat: List[_FlatEqn] = []
    env = _flatten_into(closed.jaxpr, closed.consts, {}, flat)
    outvars = [env.get(id(v), v) for v in closed.jaxpr.outvars]
    taint_in = [i in set(tainted_args)
                for i in range(len(closed.jaxpr.invars))]
    lint = _Lint(flat, closed.jaxpr.invars, taint_in, outvars, program)
    return lint.check()


def check_int32_overflow() -> "tuple[List[Violation], Dict[str, Any]]":
    """Lint the full core/scan_queue.py surface the wave path traces."""
    import functools

    import jax
    import jax.numpy as jnp

    from ..core import scan_queue as sq

    n, P_, B_ = 16, 3, 4
    i32 = jnp.int32
    vec = lambda k, dt=i32: jax.ShapeDtypeStruct((k,), dt)
    sc = jax.ShapeDtypeStruct((), i32)

    def queue_entry(e, first, last, v):
        return sq.queue_scan(e, sq.QueueState(first, last), v)

    def stack_entry(e, last, ticket, v):
        return sq.stack_scan(e, sq.StackState(last, ticket), v)

    entries = [
        ("core/scan_queue.py:queue_scan", queue_entry,
         (vec(n, jnp.bool_), sc, sc, vec(n, jnp.bool_)), ()),
        ("core/scan_queue.py:stack_scan", stack_entry,
         (vec(n, jnp.bool_), sc, sc, vec(n, jnp.bool_)), ()),
        ("core/scan_queue.py:strict_batch_deletemin",
         functools.partial(sq.strict_batch_deletemin, n_prios=P_),
         (vec(n, jnp.bool_), vec(P_), vec(P_)), ()),
        ("core/scan_queue.py:priority_queue_scan",
         functools.partial(sq.priority_queue_scan, n_prios=P_),
         (vec(n, jnp.bool_), vec(n), vec(n, jnp.bool_), vec(P_), vec(P_)),
         ()),
        ("core/scan_queue.py:seap_bucket_lookup", sq.seap_bucket_lookup,
         (vec(n), vec(B_), vec(B_, jnp.bool_)), (0, 1)),
        ("core/scan_queue.py:seap_queue_scan",
         functools.partial(sq.seap_queue_scan, n_buckets=B_,
                           split_occupancy=6),
         (vec(n, jnp.bool_), vec(n), vec(n, jnp.bool_), vec(B_), vec(B_),
          vec(B_), vec(B_, jnp.bool_), sc, sc),
         (1, 5, 7, 8)),   # key, lo, key_lo, key_hi are full-range int32
    ]
    violations: List[Violation] = []
    info: Dict[str, Any] = {"entries": []}
    for name, fn, avals, tainted in entries:
        vs = lint_jaxpr(fn, avals, program=name, tainted_args=tainted)
        violations.extend(vs)
        info["entries"].append({"program": name, "violations": len(vs)})
    return violations, info
