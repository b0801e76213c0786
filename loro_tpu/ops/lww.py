"""Batched LWW-map merge + counter-sum kernels.

The device equivalents of MapDiffCalculator (reference diff_calc.rs:
515-538: keep max (lamport, peer) per key) and CounterState.  A whole
batch of documents' map ops merges in one launch: three scatter-max
passes over (doc, slot) cells — no sorting, no host loop.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

NEG = jnp.int32(-(2**31) + 1)


class MapOpCols(NamedTuple):
    """[D, M] per-doc padded op columns (see columnar.MapExtract)."""

    slot: jax.Array  # i32 (doc-local slot id in [0, S))
    lamport: jax.Array
    peer: jax.Array
    value_idx: jax.Array
    valid: jax.Array  # bool


def lww_merge_doc(cols: MapOpCols, n_slots: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-doc LWW: winner per slot.

    Returns (value_idx i32[S] (-2 = slot untouched, -1 = deleted),
    win_lamport i32[S], win_peer i32[S])."""
    slot = jnp.where(cols.valid, cols.slot, n_slots)  # pads -> dump slot
    # pass 1: max lamport per slot
    lam = jnp.where(cols.valid, cols.lamport, NEG)
    win_lam = jnp.full(n_slots + 1, NEG, jnp.int32).at[slot].max(lam)
    # pass 2: among max-lamport ops, max peer
    at_max = cols.valid & (cols.lamport == win_lam[slot])
    peer = jnp.where(at_max, cols.peer, NEG)
    win_peer = jnp.full(n_slots + 1, NEG, jnp.int32).at[slot].max(peer)
    # pass 3: the unique winner's value (op ids are unique per
    # (slot, lamport, peer), so exactly one op matches)
    is_win = at_max & (cols.peer == win_peer[slot])
    val = jnp.where(is_win, cols.value_idx, NEG)
    win_val = jnp.full(n_slots + 1, NEG, jnp.int32).at[slot].max(val)
    untouched = win_lam[:n_slots] == NEG
    value_idx = jnp.where(untouched, -2, win_val[:n_slots])
    return value_idx, win_lam[:n_slots], win_peer[:n_slots]


def counter_merge_doc(slot: jax.Array, delta: jax.Array, valid: jax.Array, n_slots: int) -> jax.Array:
    """Sum deltas per (doc-local) counter slot: f32[S]."""
    s = jnp.where(valid, slot, n_slots)
    d = jnp.where(valid, delta, 0.0)
    return jnp.zeros(n_slots + 1, jnp.float32).at[s].add(d)[:n_slots]


@functools.partial(jax.jit, static_argnums=(1,))
def lww_merge_batch(cols: MapOpCols, n_slots: int):
    """[D, M] op columns -> per-doc winners [D, S] in one launch."""
    return jax.vmap(lambda c: lww_merge_doc(c, n_slots))(cols)


@functools.partial(jax.jit, static_argnums=(3,))
def counter_merge_batch(slot, delta, valid, n_slots: int):
    return jax.vmap(lambda s, d, v: counter_merge_doc(s, d, v, n_slots))(slot, delta, valid)


def make_lww_sharded(mesh, n_slots: int):
    """Op-axis-sharded LWW merge (SURVEY.md §2.4 item 2: "sequence
    parallelism" for very large imports).  Each (docs, ops) shard
    computes per-slot partial winners with the same three scatter-max
    passes as lww_merge_doc; partials combine across the ops axis with
    three pmax collectives over the lexicographic (lamport, peer,
    value) order.  Returns a jitted fn: MapOpCols [D, M] sharded
    P(docs, ops) -> (value_idx, lamport, peer) [D, S] P(docs)."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DOC_AXIS, OP_AXIS

    def local(cols: MapOpCols):
        def per_doc(c: MapOpCols):
            v, l, p = lww_merge_doc(c, n_slots)
            return v, l, p

        val, lam, peer = jax.vmap(per_doc)(cols)
        # cross-shard lexicographic argmax, one field at a time
        g_lam = jax.lax.pmax(lam, OP_AXIS)
        peer_c = jnp.where(lam == g_lam, peer, NEG)
        g_peer = jax.lax.pmax(peer_c, OP_AXIS)
        val_c = jnp.where((lam == g_lam) & (peer == g_peer), val, jnp.int32(-2))
        g_val = jax.lax.pmax(val_c, OP_AXIS)
        g_val = jnp.where(g_lam == NEG, -2, g_val)
        return g_val, g_lam, g_peer

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(MapOpCols(*([P(DOC_AXIS, OP_AXIS)] * 5)),),
            out_specs=(P(DOC_AXIS), P(DOC_AXIS), P(DOC_AXIS)),
        )
    )


class LwwResident(NamedTuple):
    """Device-resident per-(doc, slot) LWW winners.  Peers as u64 halves
    so no batch-wide rank dictionary is needed (append path)."""

    lamport: jax.Array  # i32[D, S]; NEG = slot untouched
    peer_hi: jax.Array  # u32[D, S]
    peer_lo: jax.Array  # u32[D, S]
    value: jax.Array  # i32[D, S]; -1 = deleted, -2 = untouched


def _blk_winners(slot, lam, hi, lo, val, valid, n_slots: int):
    """Per-slot winners of one op block (four scatter-max passes over
    the (lamport, peer_hi, peer_lo) order)."""
    s = jnp.where(valid, slot, n_slots)
    l = jnp.where(valid, lam, NEG)
    w_l = jnp.full(n_slots + 1, NEG, jnp.int32).at[s].max(l)
    at_l = valid & (lam == w_l[s])
    # peers compare as unsigned u32 halves; sentinel 0 is safe for max
    # because every slot with w_l > NEG has >= 1 candidate
    h = jnp.where(at_l, hi, jnp.uint32(0))
    w_h = jnp.zeros(n_slots + 1, jnp.uint32).at[jnp.where(at_l, s, n_slots)].max(h)
    at_h = at_l & (hi == w_h[s])
    lo_c = jnp.where(at_h, lo, jnp.uint32(0))
    w_lo = jnp.zeros(n_slots + 1, jnp.uint32).at[jnp.where(at_h, s, n_slots)].max(lo_c)
    is_win = at_h & (lo == w_lo[s])
    w_v = jnp.full(n_slots + 1, -2, jnp.int32).at[jnp.where(is_win, s, n_slots)].max(
        jnp.where(is_win, val, -2)
    )
    return w_l[:n_slots], w_h[:n_slots], w_lo[:n_slots], w_v[:n_slots]


@functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(0,))
def lww_update_resident(
    res: LwwResident, slot, lam, hi, lo_, valid, n_slots: int, value=None
) -> LwwResident:
    """Fold one append block into the resident winners (donated update).
    `value` rides as the last arg for jit-arity reasons."""

    def per_doc(r_lam, r_hi, r_lo, r_val, b_slot, b_lam, b_hi, b_lo, b_val, b_valid):
        w_l, w_h, w_lo, w_v = _blk_winners(b_slot, b_lam, b_hi, b_lo, b_val, b_valid, n_slots)
        blk_newer = (w_l > r_lam) | (
            (w_l == r_lam) & ((w_h > r_hi) | ((w_h == r_hi) & (w_lo > r_lo)))
        )
        take = blk_newer & (w_l > NEG)
        return (
            jnp.where(take, w_l, r_lam),
            jnp.where(take, w_h, r_hi),
            jnp.where(take, w_lo, r_lo),
            jnp.where(take, w_v, r_val),
        )

    out = jax.vmap(per_doc)(
        res.lamport, res.peer_hi, res.peer_lo, res.value, slot, lam, hi, lo_, value, valid
    )
    return LwwResident(*out)
