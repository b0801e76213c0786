"""Batched MovableList merge kernel.

reference semantics: MovableListDiffCalculator (diff_calc.rs:1669-2020)
— position slots live in the shared Fugue sequence; per element the
winning slot (last move, max (lamport, peer)) and winning value (last
set) are LWW selections.  Device formulation: the shared Fugue order
kernel ranks *slots*; two scatter-max passes pick winners; an element is
visible iff its winning slot is not tombstoned (a newer concurrent move
revives it — matching models/movable_list_state.py).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .fugue_batch import SeqColumns, doc_batch_jit, fugue_order, rank_bound

NEG = jnp.int32(-(2**31) + 1)


class MovableCols(NamedTuple):
    """[S] slot rows + [K] set rows for one doc (padded).

    Slots (sequence elements): seq (SeqColumns over slots; `content` is
    the slot's element index), lamport i32[S].
    Sets: set_elem i32[K] element index, set_lamport, set_peer,
    set_value i32[K] value-dictionary index, set_valid bool[K].
    n_elems is carried statically by the caller.
    """

    seq: SeqColumns
    lamport: jax.Array
    set_elem: jax.Array
    set_lamport: jax.Array
    set_peer: jax.Array
    set_value: jax.Array
    set_valid: jax.Array


def movable_merge_doc(cols: MovableCols, n_elems: int) -> Tuple[jax.Array, jax.Array]:
    """Returns (ordered value indexes i32[S] padded with -1, count).

    CONTRACT: every element index in cols (seq.content, set_elem) must
    be < n_elems — larger indexes are silently clamped into the dump
    slot by XLA scatter semantics.  Callers must size/assert n_elems
    host-side (see extract_movable's elems list)."""
    seq = cols.seq
    elem = jnp.where(seq.valid, seq.content, n_elems)  # pads -> dump elem
    visible, value = _winners(cols, elem, n_elems)
    return _place(fugue_order(seq), visible, value)


@jax.named_scope("movable_winners")
def _winners(cols: MovableCols, elem: jax.Array, n_elems: int):
    """The two LWW folds, per slot: (visible bool[S] — the slot is its
    element's last move and not tombstoned —, value i32[S] — its
    element's last set)."""
    seq = cols.seq
    # winning slot per element: max (lamport, peer); tie-break by peer is
    # safe because slot ids are unique per (lamport, peer)
    lam = jnp.where(seq.valid, cols.lamport, NEG)
    win_lam = jnp.full(n_elems + 1, NEG, jnp.int32).at[elem].max(lam)
    at_lam = seq.valid & (cols.lamport == win_lam[elem])
    peer = jnp.where(at_lam, seq.peer, NEG)
    win_peer = jnp.full(n_elems + 1, NEG, jnp.int32).at[elem].max(peer)
    is_win_slot = at_lam & (seq.peer == win_peer[elem])
    # among winner candidates with equal (lamport, peer) (same-run slots
    # impossible: one move per counter) — unique winner
    win_deleted = jnp.full(n_elems + 1, 0, jnp.int32).at[
        jnp.where(is_win_slot, elem, n_elems)
    ].max(jnp.where(seq.deleted, 1, 0))

    # winning value per element (creation values ship as set rows too)
    sv_lam = jnp.where(cols.set_valid, cols.set_lamport, NEG)
    se = jnp.where(cols.set_valid, cols.set_elem, n_elems)
    v_lam = jnp.full(n_elems + 1, NEG, jnp.int32).at[se].max(sv_lam)
    at_v = cols.set_valid & (cols.set_lamport == v_lam[se])
    v_peer = jnp.full(n_elems + 1, NEG, jnp.int32).at[
        jnp.where(at_v, se, n_elems)
    ].max(jnp.where(at_v, cols.set_peer, NEG))
    is_win_set = at_v & (cols.set_peer == v_peer[se])
    win_value = jnp.full(n_elems + 1, -1, jnp.int32).at[
        jnp.where(is_win_set, se, n_elems)
    ].max(jnp.where(is_win_set, cols.set_value, -1))

    # visible slots: the element's winning slot, not tombstoned
    visible = is_win_slot & ~seq.deleted & (win_deleted[elem] == 0)
    return visible, win_value[jnp.clip(elem, 0, n_elems)]


@jax.named_scope("movable_place")
def _place(rank: jax.Array, visible: jax.Array, value: jax.Array):
    """The visible slots' values in rank order: (i32[S] padded with -1,
    count)."""
    s = rank.shape[0]
    m = rank_bound(s)
    rk = jnp.clip(rank, 0, m - 1)
    hist = jnp.zeros(m, jnp.int32).at[jnp.where(visible, rk, m - 1)].add(
        visible.astype(jnp.int32)
    )
    pos_of_rank = jnp.cumsum(hist) - hist
    pos = pos_of_rank[rk]
    count = visible.sum().astype(jnp.int32)
    out = jnp.full(s, -1, jnp.int32).at[jnp.where(visible, pos, s)].set(
        value, mode="drop"
    )
    return out, count


@doc_batch_jit
def movable_merge_batch(cols: MovableCols, n_elems: int):
    return jax.vmap(lambda c: movable_merge_doc(c, n_elems))(cols)


def extract_movable(changes, cid):
    """Host: explode a MovableList container's ops into MovableCols
    (numpy) + (elems list, values list).  Rows follow the
    (peer, counter) ordering contract of fugue_order."""
    from ..core.change import MovableMove, MovableSet, SeqDelete, SeqInsert
    from ..oplog.oplog import _RunCont

    peers_seen = sorted({ch.peer for ch in changes})
    peer_rank = {p: i for i, p in enumerate(peers_seen)}
    slots = []  # (parent_idx, side, peer_rank, counter, lamport, elem_idx)
    id2slot = {}
    elems = []  # elem ids
    elem_idx = {}
    values = []
    sets = []  # (elem_idx, lamport, peer_rank, value_idx)
    deletes = []

    def eidx(eid):
        if eid not in elem_idx:
            elem_idx[eid] = len(elems)
            elems.append(eid)
        return elem_idx[eid]

    for ch in changes:
        for op in ch.ops:
            if op.container != cid:
                continue
            c = op.content
            lam = ch.lamport + (op.counter - ch.ctr_start)
            if isinstance(c, SeqInsert):
                body = c.content
                for j in range(len(body)):
                    if j == 0:
                        if isinstance(c.parent, _RunCont):
                            pidx = id2slot[(ch.peer, op.counter - 1)]
                        elif c.parent is None:
                            pidx = -1
                        else:
                            pidx = id2slot[(c.parent.peer, c.parent.counter)]
                        side = int(c.side)
                    else:
                        pidx = len(slots) - 1
                        side = 1
                    eid = (ch.peer, op.counter + j)
                    ei = eidx(eid)
                    id2slot[eid] = len(slots)
                    slots.append((pidx, side, peer_rank[ch.peer], op.counter + j, lam + j, ei))
                    vi = len(values)
                    values.append(body[j])
                    sets.append((ei, lam + j, peer_rank[ch.peer], vi))
            elif isinstance(c, MovableMove):
                if isinstance(c.parent, _RunCont):
                    pidx = id2slot[(ch.peer, op.counter - 1)]
                elif c.parent is None:
                    pidx = -1
                else:
                    pidx = id2slot[(c.parent.peer, c.parent.counter)]
                ei = eidx((c.elem.peer, c.elem.counter))
                id2slot[(ch.peer, op.counter)] = len(slots)
                slots.append((pidx, int(c.side), peer_rank[ch.peer], op.counter, lam, ei))
            elif isinstance(c, MovableSet):
                ei = eidx((c.elem.peer, c.elem.counter))
                vi = len(values)
                values.append(c.value)
                sets.append((ei, lam, peer_rank[ch.peer], vi))
            elif isinstance(c, SeqDelete):
                for sp in c.spans:
                    deletes.append((sp.peer, sp.start, sp.end))

    n = len(slots)
    arr = np.asarray(slots, np.int64).reshape(n, 6) if n else np.zeros((0, 6), np.int64)
    deleted = np.zeros(n, bool)
    for peer, start, end in deletes:
        for ctr in range(start, end):
            i = id2slot.get((peer, ctr))
            if i is not None:
                deleted[i] = True
    from .columnar import peer_counter_perm

    perm, _inv, parent = peer_counter_perm(arr[:, 2], arr[:, 3], arr[:, 0])
    k = len(sets)
    sarr = np.asarray(sets, np.int64).reshape(k, 4) if k else np.zeros((0, 4), np.int64)
    seq = SeqColumns(
        parent=parent.astype(np.int32),
        side=arr[perm, 1].astype(np.int32),
        peer=arr[perm, 2].astype(np.int32),
        counter=arr[perm, 3].astype(np.int32),
        deleted=deleted[perm],
        content=arr[perm, 5].astype(np.int32),  # element index
        valid=np.ones(n, bool),
    )
    cols = MovableCols(
        seq=seq,
        lamport=arr[perm, 4].astype(np.int32),
        set_elem=sarr[:, 0].astype(np.int32),
        set_lamport=sarr[:, 1].astype(np.int32),
        set_peer=sarr[:, 2].astype(np.int32),
        set_value=sarr[:, 3].astype(np.int32),
        set_valid=np.ones(k, bool),
    )
    return cols, elems, values


@jax.jit
def movable_by_key_batch(valid, deleted, key_hi, key_lo, win_row, win_lam, val_idx):
    """RESIDENT materialization (DeviceMovableBatch): element-level
    output from standing state — per element, the move-winner's slot
    row (LWW fold) carries the element's standing ShadowOrder key and
    tombstone; ONE [E]-sized sort realizes the list (E elements, not S
    slots).  Returns (value ordinals i32[D, E] padded -1, counts).

    valid/deleted/key_hi/key_lo: [D, S] slot-buffer columns;
    win_row/win_lam: [D, E] move-winner fold (row index, lamport;
    win_lam == NEG means the element was never placed);
    val_idx: [D, E] value-winner fold (value ordinals)."""

    def per_doc(v, dl, kh, kl, wrow, wlam, vidx):
        s = v.shape[0]
        e_cap = wrow.shape[0]
        row = jnp.clip(wrow, 0, s - 1)
        alive = (wlam > NEG) & v[row] & ~dl[row]
        ekh = jnp.where(alive, kh[row], jnp.uint32(0xFFFFFFFF))
        ekl = jnp.where(alive, kl[row], jnp.uint32(0xFFFFFFFF))
        alive_i = alive.astype(jnp.int32)
        _, _, vis_s, vid_s = jax.lax.sort((ekh, ekl, alive_i, vidx), num_keys=2)
        pos = jnp.cumsum(vis_s) - vis_s
        out = jnp.full(e_cap, -1, jnp.int32).at[
            jnp.where(vis_s == 1, pos, e_cap)
        ].set(vid_s, mode="drop")
        return out, alive_i.sum()

    return jax.vmap(per_doc)(valid, deleted, key_hi, key_lo, win_row, win_lam, val_idx)


class LazyPayloadValue:
    """Undecoded value: payload bytes + offset (decoded only if it wins
    the set-LWW — mirrors the map batch's lazy cells)."""

    __slots__ = ("payload", "offset", "cids")

    def __init__(self, payload: bytes, offset: int, cids):
        self.payload = payload
        self.offset = offset
        self.cids = cids

    def get(self):
        from ..native import decode_value_at

        return decode_value_at(self.payload, self.offset, self.cids)


def extract_movable_from_payload(payload: bytes, cid):
    """Native fast path: binary updates payload -> (MovableCols, elems,
    values) with lazy value cells (same contract as extract_movable).
    Returns None when the native library is unavailable; raises
    ValueError on malformed payloads / out-of-payload references
    (caller falls back to Python)."""
    from ..codec.binary import read_tables
    from ..native import available, explode_movable_payload

    if not available():
        return None
    peers_wire, _keys, cids, _r = read_tables(payload)
    try:
        target = cids.index(cid)
    except ValueError:
        target = -1
    if target < 0:
        return extract_movable([], cid)
    out = explode_movable_payload(payload, target)
    sl, st, dl = out["slots"], out["sets"], out["dels"]
    n = len(sl["parent"])
    from .columnar import pack_wire_ids, wire_peer_ranks

    rank_of = wire_peer_ranks(peers_wire)

    # vectorized element dictionary over slot + set references: pack
    # (wire peer idx, ctr) into i64 and unique+inverse
    k = len(st["elem_peer_idx"])
    se_packed = pack_wire_ids(sl["elem_peer_idx"], sl["elem_ctr"])
    st_packed = pack_wire_ids(st["elem_peer_idx"], st["elem_ctr"])
    uniq, inv = np.unique(np.concatenate([se_packed, st_packed]), return_inverse=True)
    elems = [
        (int(peers_wire[int(q) >> 32]), int(q) & 0xFFFFFFFF) for q in uniq
    ]
    slot_elem = inv[:n].astype(np.int32)
    set_elem = inv[n:].astype(np.int32)

    # tombstones: resolve delete spans through the packed slot id map
    # (spans referencing slots outside the payload drop, matching the
    # Python fallback's id2slot.get semantics)
    deleted = np.zeros(n, bool)
    if n:
        slot_packed = pack_wire_ids(sl["peer_idx"], sl["counter"])
        slot_order = np.argsort(slot_packed, kind="stable")
        slot_sorted = slot_packed[slot_order]
        for j in range(len(dl["peer_idx"])):
            dp = np.int64(int(dl["peer_idx"][j])) << 32
            span = np.arange(int(dl["start"][j]), int(dl["end"][j]), dtype=np.int64) | dp
            pos = np.searchsorted(slot_sorted, span)
            pos = np.clip(pos, 0, n - 1)
            hit = slot_sorted[pos] == span
            deleted[slot_order[pos[hit]]] = True

    from .columnar import peer_counter_perm

    slot_rank = rank_of[sl["peer_idx"]].astype(np.int64) if n else np.zeros(0, np.int64)
    perm, _inv, parent = peer_counter_perm(slot_rank, sl["counter"], sl["parent"])
    from .fugue_batch import SeqColumns

    seq = SeqColumns(
        parent=parent.astype(np.int32),
        side=sl["side"][perm].astype(np.int32),
        peer=slot_rank[perm].astype(np.int32),
        counter=sl["counter"][perm].astype(np.int32),
        deleted=deleted[perm],
        content=slot_elem[perm].astype(np.int32),
        valid=np.ones(n, bool),
    )
    values = [LazyPayloadValue(payload, int(off), cids) for off in st["value_off"]]
    cols = MovableCols(
        seq=seq,
        lamport=sl["lamport"][perm].astype(np.int32),
        set_elem=set_elem,
        set_lamport=st["lamport"].astype(np.int32),
        set_peer=rank_of[st["peer_idx"]].astype(np.int32) if k else np.zeros(0, np.int32),
        set_valid=np.ones(k, bool),
        set_value=np.arange(k, dtype=np.int32),
    )
    return cols, elems, values
