"""Pallas TPU kernels: SKUEUE batch position-assignment sweeps.

The paper's Stages 1-3 for one device's request array, as a two-phase
Blelloch scan tiled for VMEM:

  phase A (parallel over tiles): per-tile total (A,B,C) transform,
          one (8,128) VPU tile at a time;
  phase B (parallel over tiles, given the exclusive tile-prefix carries):
          intra-tile Hillis-Steele scan in the min-plus semiring +
          position emission.

The inter-tile exclusive scan of the tiny per-tile carries happens in jnp
between the two pallas_calls (it is O(n/TILE) elements — negligible), which
mirrors the paper's anchor step: the carries ARE the aggregated batches.

Three sweeps share the machinery (one per discipline family):

  * :func:`queue_scan_kernel`  — FIFO min-plus (ENQ/DEQ transforms);
  * :func:`stack_scan_kernel`  — LIFO max-plus (PUSH/POP on (last, ticket));
  * :func:`tiered_queue_scan_kernel` — the fused per-tier sweep: ONE
    pallas_call pair with grid (n_tiers, tiles) replacing n_tiers separate
    masked launches — this is the dispatch arithmetic of the priority
    (tier := SLA class) and Seap (tier := bucket) disciplines; the
    batch-DeleteMin epilogue stays prefix arithmetic on the tiny per-tier
    totals (``core.scan_queue.strict_batch_deletemin``) inside the same
    jitted program.

Layout: requests are reshaped to [T, 8, 128] tiles; the scan order is the
row-major flattened order.  All arithmetic is int32 in VMEM; the MXU is not
involved (these are VPU kernels).  ``interpret=None`` resolves through
``repro.kernels.default_interpret()`` (interpret on CPU, compiled on
TPU/GPU; env override ``REPRO_PALLAS_INTERPRET``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..backend import default_interpret

INF = 2 ** 30  # plain int: Pallas kernels need literals, not traced consts
TILE_ROWS = 8
TILE_LANES = 128
TILE = TILE_ROWS * TILE_LANES


# ------------------------------------------------- min-plus (queue) ---------
def _compose(t1, t2):
    A1, B1, C1 = t1
    A2, B2, C2 = t2
    return (A1 + A2,
            jnp.minimum(jnp.minimum(B1 + A2, C1 + B2), INF),
            C1 + C2)


def _tile_transforms(is_enq, valid):
    e = jnp.logical_and(is_enq != 0, valid != 0).astype(jnp.int32)
    v = (valid != 0)
    A = jnp.where(v, 1 - e, 0)
    B = jnp.where(v, jnp.where(e > 0, INF, 1), INF)
    C = jnp.where(v, e, 0)
    return A, B, C


# ------------------------------------------------- max-plus (stack) ---------
def _stack_compose(t1, t2):
    a1, b1, d1 = t1
    a2, b2, d2 = t2
    return (a1 + a2,
            jnp.maximum(jnp.maximum(b1 + a2, b2), -INF),
            d1 + d2)


def _stack_tile_transforms(is_push, valid):
    e = jnp.logical_and(is_push != 0, valid != 0).astype(jnp.int32)
    v = (valid != 0)
    a = jnp.where(v, 2 * e - 1, 0)
    b = jnp.where(v, jnp.where(e > 0, -INF, 0), -INF)
    d = jnp.where(v, e, 0)
    return a, b, d


# ---------------------------------------------------- shared scan bodies ----
# Both tropical composes are exactly associative on the values the
# transforms produce (A, C >= 0 for min-plus; every b >= -INF for
# max-plus), so any scan schedule yields the same prefixes bit for bit.
# The schedule below keeps every op 2-D on the (8, 128) tile, as Mosaic
# requires: lane shifts and sublane shifts are ``pltpu.roll``, masks are
# 2-D iotas, and the identity fill is only ever composed on the right.
def _iota(axis):
    return lax.broadcasted_iota(jnp.int32, (TILE_ROWS, TILE_LANES), axis)


def _shift_compose(compose, tr, shift, axis):
    """One Hillis-Steele step along ``axis``: element i becomes
    tr[i - shift] ∘ tr[i] where i >= shift, and is kept where i < shift."""
    prev = tuple(pltpu.roll(x, shift, axis) for x in tr)
    keep = _iota(axis) < shift
    return tuple(jnp.where(keep, x, y)
                 for x, y in zip(tr, compose(prev, tr)))


def _lane_scan(compose, tr):
    """Inclusive scan of each row of the tile, left to right."""
    shift = 1
    while shift < TILE_LANES:
        tr = _shift_compose(compose, tr, shift, 1)
        shift *= 2
    return tr


def _row_prefix(compose, lane_incl):
    """Inclusive scan over the rows' totals, broadcast along the lanes:
    row r holds the composition of rows 0..r."""
    rows = tuple(jnp.broadcast_to(x[:, TILE_LANES - 1:], x.shape)
                 for x in lane_incl)
    shift = 1
    while shift < TILE_ROWS:
        rows = _shift_compose(compose, rows, shift, 0)
        shift *= 2
    return rows


def _tile_totals(compose, tr):
    """The tile's total transform, written as rows 0..2 of an (8, 128)
    tile (component k in row k, every lane equal)."""
    rows = _row_prefix(compose, _lane_scan(compose, tr))
    last = [jnp.broadcast_to(x[TILE_ROWS - 1:, :], x.shape) for x in rows]
    r = _iota(0)
    return jnp.where(r == 0, last[0], jnp.where(r == 1, last[1], last[2]))


def _tile_exclusive(compose, fills, tr):
    """Exclusive row-major scan of the tile; element (0, 0) gets ``fills``."""
    lane_incl = _lane_scan(compose, tr)
    rows = _row_prefix(compose, lane_incl)
    before = tuple(pltpu.roll(x, 1, 0) for x in rows)  # rows 0..r-1
    r, j = _iota(0), _iota(1)
    incl = tuple(jnp.where(r == 0, x, y)
                 for x, y in zip(lane_incl, compose(before, lane_incl)))
    return tuple(jnp.where(j > 0, pltpu.roll(x, 1, 1),
                           jnp.where(r > 0, b, f))
                 for x, b, f in zip(incl, before, fills))


def _totals_kernel(is_enq_ref, valid_ref, out_ref):
    """Phase A: reduce one [8,128] tile to its total (A,B,C)."""
    out_ref[0] = _tile_totals(
        _compose, _tile_transforms(is_enq_ref[0], valid_ref[0]))


def _scan_kernel(is_enq_ref, valid_ref, carry_ref, state_ref,
                 pos_ref, match_ref):
    """Phase B: intra-tile exclusive scan after the tile's carry."""
    is_enq, valid = is_enq_ref[0], valid_ref[0]
    a_x, b_x, c_x = _tile_exclusive(
        _compose, (0, INF, 0), _tile_transforms(is_enq, valid))
    # prepend the inter-tile carry and the initial anchor state
    t = 3 * pl.program_id(0)
    a_x, b_x, c_x = _compose(
        (carry_ref[t], carry_ref[t + 1], carry_ref[t + 2]), (a_x, b_x, c_x))
    first0 = state_ref[0]
    last0 = state_ref[1]
    f_i = jnp.minimum(first0 + a_x, last0 + b_x)
    l_i = last0 + c_x
    pos = jnp.where(is_enq != 0, l_i + 1,
                    jnp.where(f_i <= l_i, f_i, jnp.int32(-1)))
    pos = jnp.where(valid != 0, pos, jnp.int32(-1))
    pos_ref[0] = pos
    match_ref[0] = jnp.where(pos >= 0, 1, 0)


def _stack_totals_kernel(is_push_ref, valid_ref, out_ref):
    """Phase A (max-plus): reduce one tile to its total (a, b, dt)."""
    out_ref[0] = _tile_totals(
        _stack_compose,
        _stack_tile_transforms(is_push_ref[0], valid_ref[0]))


def _stack_scan_kernel(is_push_ref, valid_ref, carry_ref, state_ref,
                       pos_ref, tick_ref):
    """Phase B (max-plus): positions + tickets after the tile's carry."""
    is_push, valid = is_push_ref[0], valid_ref[0]
    a_x, b_x, d_x = _tile_exclusive(
        _stack_compose, (0, -INF, 0), _stack_tile_transforms(is_push, valid))
    t = 3 * pl.program_id(0)
    a_x, b_x, d_x = _stack_compose(
        (carry_ref[t], carry_ref[t + 1], carry_ref[t + 2]), (a_x, b_x, d_x))
    last0 = state_ref[0]
    tick0 = state_ref[1]
    l_i = jnp.maximum(last0 + a_x, b_x)
    t_i = tick0 + d_x
    pos = jnp.where(is_push != 0, l_i + 1,
                    jnp.where(l_i >= 1, l_i, jnp.int32(-1)))
    pos_ref[0] = jnp.where(valid != 0, pos, jnp.int32(-1))
    tick_ref[0] = jnp.where(is_push != 0, t_i + 1, t_i)


def _tier_mask(tier_ref, enq_ref):
    """This grid row's tier: the masked enqueues as int32."""
    return jnp.logical_and(enq_ref[0] != 0,
                           tier_ref[0] == pl.program_id(0)).astype(jnp.int32)


def _tiered_totals_kernel(tier_ref, enq_ref, out_ref):
    """Phase A over grid (tier, tile): totals of THIS tier's enqueue mask."""
    mask = _tier_mask(tier_ref, enq_ref)
    out_ref[0, 0] = _tile_totals(_compose, _tile_transforms(mask, mask))


def _tiered_scan_kernel(tier_ref, enq_ref, carry_ref, state_ref, pos_ref):
    """Phase B over grid (tier, tile): per-tier enqueue positions."""
    p = pl.program_id(0)
    mask = _tier_mask(tier_ref, enq_ref)
    a_x, b_x, c_x = _tile_exclusive(
        _compose, (0, INF, 0), _tile_transforms(mask, mask))
    t = 3 * (p * pl.num_programs(1) + pl.program_id(1))
    a_x, b_x, c_x = _compose(
        (carry_ref[t], carry_ref[t + 1], carry_ref[t + 2]), (a_x, b_x, c_x))
    l_i = state_ref[2 * p + 1] + c_x
    pos_ref[0, 0] = jnp.where(mask != 0, l_i + 1, jnp.int32(-1))


# -------------------------------------------------------- entry points ------
# Request tiles and per-tile results are (1, 8, 128) VMEM blocks; the
# per-tile carries and the (first, last) state are whole flat int32 arrays
# in SMEM, read as scalars at the grid step's own offset.
_TILE_SPEC = pl.BlockSpec((1, TILE_ROWS, TILE_LANES), lambda t: (t, 0, 0))
_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _tiles(T):
    return jax.ShapeDtypeStruct((T, TILE_ROWS, TILE_LANES), jnp.int32)


def _carry_scan(compose, totals, ident_row, axis=0):
    """Inter-tile exclusive scan of the tiny per-tile carries (jnp)."""
    def comp(x, y):
        return jnp.stack(compose((x[..., 0], x[..., 1], x[..., 2]),
                                 (y[..., 0], y[..., 1], y[..., 2])), -1)
    incl = lax.associative_scan(comp, totals, axis=axis)
    ident = jnp.broadcast_to(
        jnp.asarray(ident_row, jnp.int32),
        totals.shape[:axis] + (1,) + totals.shape[axis + 1:])
    excl = lax.concatenate(
        [ident, lax.slice_in_dim(incl, 0, totals.shape[axis] - 1, axis=axis)],
        axis)
    tot = lax.index_in_dim(incl, totals.shape[axis] - 1, axis=axis,
                           keepdims=False)
    return excl, tot


def queue_scan_kernel(is_enq: jax.Array, valid: jax.Array,
                      first: jax.Array, last: jax.Array,
                      interpret: bool | None = None):
    """n must be a multiple of 1024 (pad with valid=False).

    Returns (pos[n], matched[n], new_first, new_last)."""
    if interpret is None:
        interpret = default_interpret()
    n = is_enq.shape[0]
    assert n % TILE == 0, f"pad request batch to a multiple of {TILE}"
    T = n // TILE
    e2 = is_enq.astype(jnp.int32).reshape(T, TILE_ROWS, TILE_LANES)
    v2 = valid.astype(jnp.int32).reshape(T, TILE_ROWS, TILE_LANES)

    # ---- phase A: per-tile totals ----
    totals = pl.pallas_call(
        _totals_kernel,
        grid=(T,),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=_TILE_SPEC,
        out_shape=_tiles(T),
        interpret=interpret,
    )(e2, v2)[:, :3, 0]

    # ---- inter-tile exclusive scan of carries (tiny; jnp) ----
    excl, tot = _carry_scan(_compose, totals, [0, INF, 0])
    state = jnp.stack([first.astype(jnp.int32), last.astype(jnp.int32)])

    # ---- phase B: positions ----
    pos, match = pl.pallas_call(
        _scan_kernel,
        grid=(T,),
        in_specs=[_TILE_SPEC, _TILE_SPEC, _SMEM_SPEC, _SMEM_SPEC],
        out_specs=[_TILE_SPEC, _TILE_SPEC],
        out_shape=[_tiles(T), _tiles(T)],
        interpret=interpret,
    )(e2, v2, excl.reshape(-1), state)

    new_first = jnp.minimum(first + tot[0], last + tot[1])
    new_last = last + tot[2]
    return (pos.reshape(n), match.reshape(n).astype(bool),
            new_first.astype(jnp.int32), new_last.astype(jnp.int32))


def stack_scan_kernel(is_push: jax.Array, valid: jax.Array,
                      last: jax.Array, ticket: jax.Array,
                      interpret: bool | None = None):
    """Max-plus LIFO sweep.  n must be a multiple of 1024.

    Returns (pos[n], tick[n], new_last, new_ticket) with the exact
    semantics of ``core.scan_queue.stack_scan``: for pushes ``tick`` is
    the element's unique ticket, for pops the max-ticket bound."""
    if interpret is None:
        interpret = default_interpret()
    n = is_push.shape[0]
    assert n % TILE == 0, f"pad request batch to a multiple of {TILE}"
    T = n // TILE
    e2 = is_push.astype(jnp.int32).reshape(T, TILE_ROWS, TILE_LANES)
    v2 = valid.astype(jnp.int32).reshape(T, TILE_ROWS, TILE_LANES)

    totals = pl.pallas_call(
        _stack_totals_kernel,
        grid=(T,),
        in_specs=[_TILE_SPEC, _TILE_SPEC],
        out_specs=_TILE_SPEC,
        out_shape=_tiles(T),
        interpret=interpret,
    )(e2, v2)[:, :3, 0]

    excl, tot = _carry_scan(_stack_compose, totals, [0, -INF, 0])
    state = jnp.stack([last.astype(jnp.int32), ticket.astype(jnp.int32)])

    pos, tick = pl.pallas_call(
        _stack_scan_kernel,
        grid=(T,),
        in_specs=[_TILE_SPEC, _TILE_SPEC, _SMEM_SPEC, _SMEM_SPEC],
        out_specs=[_TILE_SPEC, _TILE_SPEC],
        out_shape=[_tiles(T), _tiles(T)],
        interpret=interpret,
    )(e2, v2, excl.reshape(-1), state)

    new_last = jnp.maximum(last + tot[0], tot[1])
    new_ticket = ticket + tot[2]
    return (pos.reshape(n), tick.reshape(n),
            new_last.astype(jnp.int32), new_ticket.astype(jnp.int32))


def tiered_queue_scan_kernel(tier: jax.Array, enq: jax.Array,
                             firsts: jax.Array, lasts: jax.Array,
                             n_tiers: int,
                             interpret: bool | None = None):
    """The fused per-tier enqueue sweep: grid (n_tiers, tiles), ONE
    pallas_call pair total — versus n_tiers separate masked launches.

    tier: [n] int32 (the element's tier/bucket; dequeues may carry any
    value — gate with ``enq``); enq: [n] bool, the masked enqueue ops.
    n must be a multiple of 1024.  Returns (pos_all [n_tiers, n] int32
    with -1 off-tier, new_lasts [n_tiers]); firsts are unchanged by an
    enqueue-only sweep."""
    if interpret is None:
        interpret = default_interpret()
    P_ = n_tiers
    n = enq.shape[0]
    assert n % TILE == 0, f"pad request batch to a multiple of {TILE}"
    T = n // TILE
    t2 = tier.astype(jnp.int32).reshape(T, TILE_ROWS, TILE_LANES)
    e2 = enq.astype(jnp.int32).reshape(T, TILE_ROWS, TILE_LANES)

    tile_spec = pl.BlockSpec((1, TILE_ROWS, TILE_LANES),
                             lambda p, t: (t, 0, 0))
    tier_spec = pl.BlockSpec((1, 1, TILE_ROWS, TILE_LANES),
                             lambda p, t: (p, t, 0, 0))
    tier_tiles = jax.ShapeDtypeStruct((P_, T, TILE_ROWS, TILE_LANES),
                                      jnp.int32)
    totals = pl.pallas_call(
        _tiered_totals_kernel,
        grid=(P_, T),
        in_specs=[tile_spec, tile_spec],
        out_specs=tier_spec,
        out_shape=tier_tiles,
        interpret=interpret,
    )(t2, e2)[:, :, :3, 0]

    excl, tot = _carry_scan(_compose, totals, [0, INF, 0], axis=1)
    state = jnp.stack([firsts.astype(jnp.int32),
                       lasts.astype(jnp.int32)], axis=-1)  # [P, 2]

    pos_all = pl.pallas_call(
        _tiered_scan_kernel,
        grid=(P_, T),
        in_specs=[tile_spec, tile_spec, _SMEM_SPEC, _SMEM_SPEC],
        out_specs=tier_spec,
        out_shape=tier_tiles,
        interpret=interpret,
    )(t2, e2, excl.reshape(-1), state.reshape(-1))

    new_lasts = lasts + tot[:, 2]
    return pos_all.reshape(P_, n), new_lasts.astype(jnp.int32)
