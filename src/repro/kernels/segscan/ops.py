"""Jit'd public wrappers for the segscan kernels (auto-padding, dtypes).

``interpret=None`` (the default everywhere) resolves through
``repro.kernels.default_interpret()``: interpret mode on CPU, compiled on
TPU/GPU, overridable with ``REPRO_PALLAS_INTERPRET`` (docs/OPERATIONS.md).
``core/scan_queue`` stays the pure-jnp differential oracle for every
function here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import default_interpret
from .kernel import (TILE, queue_scan_kernel, stack_scan_kernel,
                     tiered_queue_scan_kernel)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _queue_scan_pallas(is_enq, valid, first, last, interpret):
    n = is_enq.shape[0]
    pad = (-n) % TILE
    if pad:
        is_enq = jnp.concatenate([is_enq, jnp.zeros((pad,), is_enq.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)])
    pos, matched, nf, nl = queue_scan_kernel(
        is_enq, valid, jnp.asarray(first), jnp.asarray(last),
        interpret=interpret)
    return pos[:n], matched[:n], nf, nl


def queue_scan_pallas(is_enq: jax.Array, valid: jax.Array,
                      first: jax.Array, last: jax.Array,
                      interpret: bool | None = None):
    """Position assignment for a request batch (SKUEUE Stages 1-3).

    is_enq/valid: [n] bool.  Returns (pos[n] int32 ⊥=-1, matched[n] bool,
    new_first, new_last).  n is padded internally to a multiple of 1024.
    """
    if interpret is None:
        interpret = default_interpret()
    return _queue_scan_pallas(is_enq, valid, first, last, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _stack_scan_pallas(is_push, valid, last, ticket, interpret):
    n = is_push.shape[0]
    pad = (-n) % TILE
    if pad:
        is_push = jnp.concatenate([is_push, jnp.zeros((pad,), is_push.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)])
    pos, tick, nl, nt = stack_scan_kernel(
        is_push, valid, jnp.asarray(last), jnp.asarray(ticket),
        interpret=interpret)
    pos, tick = pos[:n], tick[:n]
    return pos, tick, pos != -1, nl, nt


def stack_scan_pallas(is_push: jax.Array, valid: jax.Array,
                      last: jax.Array, ticket: jax.Array,
                      interpret: bool | None = None):
    """Max-plus LIFO position assignment (the stack analogue, Sec. VI).

    is_push/valid: [n] bool; last/ticket: int32 scalars.  Returns
    (pos[n] int32 ⊥=-1, tick[n] int32, matched[n] bool, new_last,
    new_ticket) — bit-identical to ``core.scan_queue.stack_scan``.
    """
    if interpret is None:
        interpret = default_interpret()
    return _stack_scan_pallas(is_push, valid, last, ticket, interpret)


@functools.partial(jax.jit, static_argnames=("n_tiers", "interpret"))
def _tiered_queue_scan_pallas(enq, tier, firsts, lasts, n_tiers, interpret):
    n = enq.shape[0]
    pad = (-n) % TILE
    if pad:
        enq = jnp.concatenate([enq, jnp.zeros((pad,), enq.dtype)])
        tier = jnp.concatenate([tier, jnp.zeros((pad,), tier.dtype)])
    pos_all, new_lasts = tiered_queue_scan_kernel(
        tier, enq, firsts, lasts, n_tiers, interpret=interpret)
    # each op's position from its own tier's row, selected over the few
    # tiers (exactly one term is kept): XLA:TPU runs a gather of one
    # scalar an op element by element (~0.1 ms an 8192-op wave on a v5e)
    t_c = jnp.clip(tier[:n].astype(jnp.int32), 0, n_tiers - 1)
    own = t_c[None, :] == jnp.arange(n_tiers, dtype=jnp.int32)[:, None]
    pos = jnp.where(own, pos_all[:, :n], 0).sum(axis=0)
    return jnp.where(enq[:n] != 0, pos, jnp.int32(-1)), new_lasts


def tiered_queue_scan_pallas(enq: jax.Array, tier: jax.Array,
                             firsts: jax.Array, lasts: jax.Array,
                             n_tiers: int,
                             interpret: bool | None = None):
    """Fused per-tier enqueue sweep: ONE kernel pair over grid
    (n_tiers, tiles), replacing n_tiers separate masked launches.

    enq: [n] bool (the wave's valid enqueues); tier: [n] int32 (tier or
    Seap bucket per op; out-of-range tiers assign no position).  Returns
    (pos[n] int32 ⊥=-1, new_lasts[n_tiers]); an enqueue-only sweep never
    moves ``firsts``.  This is the ``tier_scan`` hook consumed by
    ``core.scan_queue.priority_queue_scan`` / ``seap_queue_scan``.
    """
    if interpret is None:
        interpret = default_interpret()
    return _tiered_queue_scan_pallas(enq, tier, firsts, lasts, n_tiers,
                                     interpret)


def make_tier_scan(n_tiers: int, interpret: bool | None = None):
    """Bind :func:`tiered_queue_scan_pallas` to the 4-arg ``tier_scan``
    hook signature the core scans accept."""
    def tier_scan(enq, tier, firsts, lasts):
        return tiered_queue_scan_pallas(enq, tier, firsts, lasts,
                                        n_tiers=n_tiers, interpret=interpret)
    return tier_scan


def priority_queue_scan_pallas(is_enq: jax.Array, prio: jax.Array,
                               valid: jax.Array, firsts: jax.Array,
                               lasts: jax.Array, n_prios: int,
                               interpret: bool | None = None):
    """P-tier priority position assignment (strict mode) on the pallas path.

    The per-tier enqueue scans — the O(n) part — are ONE fused
    :func:`tiered_queue_scan_pallas` sweep (grid (P, tiles); PR 9 — this
    used to be P separate masked kernel launches), and the wave's
    dequeues are then resolved highest-priority-first by the batch-drain
    prefix arithmetic of ``core.scan_queue.strict_batch_deletemin`` on
    the tiny per-tier totals, fused into the same jitted program.

    is_enq/valid: [n] bool; prio: [n] int32; firsts/lasts: [n_prios] int32.
    Returns (tier [n] int32 (-1 unmatched), pos [n] int32 (⊥ = -1),
    matched [n] bool, new_firsts, new_lasts).
    """
    from ...core.scan_queue import strict_batch_deletemin
    if interpret is None:
        interpret = default_interpret()
    enq = is_enq & valid
    deq = (~is_enq) & valid
    pos_e, new_lasts = tiered_queue_scan_pallas(
        enq, prio, firsts, lasts, n_tiers=n_prios, interpret=interpret)
    tier = jnp.where(enq & (pos_e >= 0), prio.astype(jnp.int32), -1)
    pos = jnp.where(enq, pos_e, jnp.int32(-1))
    avail = new_lasts - firsts + 1
    # the dequeue resolution is the SAME batch-DeleteMin prefix arithmetic
    # the core scan uses — one copy, shared (PR 4)
    t_c, pos_d, d_matched, taken = strict_batch_deletemin(
        deq, avail, firsts, n_prios)
    tier = jnp.where(d_matched, t_c, tier)
    pos = jnp.where(d_matched, pos_d, pos)
    matched = enq | d_matched
    return tier, pos, matched, firsts + taken, new_lasts
