"""Batched rich-text merge kernel: text order + style resolution.

reference semantics: the Peritext-style style anchors of
crates/loro-internal/src/container/richtext (StyleAnchor rope elements,
style_range_map.rs): a (start, end) anchor pair styles the characters
between them; per key the winning pair covering a char is the one with
max (lamport, peer); value None = unstyled.

Device formulation: anchors ride the same Fugue order kernel as chars
(zero-width).  With P pairs per doc, anchor positions induce <= 2P+1
constant-style regions; winners resolve as masked maxima over the
[P, R, K] cover tensor — tiny dense work after the big order solve.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .fugue_batch import (
    ChainColumns,
    SeqColumns,
    _order_core,
    chain_positions,
    doc_batch_jit,
    fugue_order,
    rank_bound,
)
from .text_codes import text_from_codes

NEG = jnp.int32(-(2**31) + 1)


def _resolve_styles(
    pair_valid, pair_key, pair_value, pair_lamport, pair_peer, a_start, a_end, count, n_keys
):
    """Shared style-winner resolution from anchor char-positions.

    Winner per (region, key) = covering pair with max (lamport, peer) —
    the host tuple comparison (text_state._resolve_attrs).  Pairs get a
    dense i32 priority (rank in (lamport, peer) order via a tiny P-row
    lexsort; the tuple is unique per pair, so max priority IS the
    lexicographic winner).  Each pair covers a CONTIGUOUS run of
    regions (lo/hi are sorted), so winners resolve as range-chmax of
    priorities on an iterative segment forest (one subtree per style
    key, <= 2 node updates per pair per level) + per-leaf ancestor-max
    queries: O((P + K R) log R) work, replacing the dense [P, R, K]
    masked-max passes that dominated the richtext merge (measured ~5x
    the rest of the kernel combined, on CPU and in the byte model).

    Returns (bounds i32[2P+2], win_value i32[2P+1, n_keys])."""
    p = pair_valid.shape[0]
    bounds = jnp.sort(jnp.concatenate([a_start, a_end]))  # [2P]
    lo = jnp.concatenate([jnp.zeros(1, jnp.int32), bounds])  # [2P+1]
    hi = jnp.concatenate([bounds, count[None].astype(jnp.int32)])
    out_bounds = jnp.concatenate([lo, hi[-1:]])
    r_count = 2 * p + 1
    if p == 0:
        return out_bounds, jnp.full((r_count, n_keys), -1, jnp.int32)
    order = jnp.lexsort((pair_peer, pair_lamport))  # ascending (lam, peer)
    prio = jnp.zeros(p, jnp.int32).at[order].set(jnp.arange(p, dtype=jnp.int32))

    # pair i covers exactly the contiguous region run [r_lo_i, r_hi_i):
    # lo/hi are sorted, so {r : a_start_i <= lo[r]} is a suffix and
    # {r : a_end_i >= hi[r]} a prefix.  Range-chmax the pair's priority
    # over its run on an iterative segment tree (<= 2 nodes per level),
    # then point-query each (region, key): O((P + K R) log R) total work
    # instead of the dense [P, R] cover relation.
    r_lo = jnp.searchsorted(lo, a_start, side="left").astype(jnp.int32)
    r_hi = jnp.searchsorted(hi, a_end, side="right").astype(jnp.int32)
    r_lo = jnp.where(pair_valid, r_lo, 0)
    r_hi = jnp.where(pair_valid, r_hi, 0)
    s = 1
    while s < r_count:
        s *= 2
    levels = s.bit_length()  # node depth of the size-s tree
    key_c = jnp.clip(pair_key, 0, n_keys - 1)
    base = key_c * (2 * s)  # per-key subtree offset in the flat forest
    tree_size = n_keys * 2 * s
    tree = jnp.full(tree_size + 1, -1, jnp.int32)  # +1 dump slot
    lcur = r_lo + s
    rcur = r_hi + s
    for _ in range(levels):
        upd_l = ((lcur & 1) == 1) & (lcur < rcur)
        tree = tree.at[jnp.where(upd_l, base + lcur, tree_size)].max(
            jnp.where(upd_l, prio, -1), mode="drop"
        )
        lcur = lcur + upd_l
        upd_r = ((rcur & 1) == 1) & (lcur < rcur)
        rcur = rcur - upd_r
        tree = tree.at[jnp.where(upd_r, base + rcur, tree_size)].max(
            jnp.where(upd_r, prio, -1), mode="drop"
        )
        lcur = lcur >> 1
        rcur = rcur >> 1
    pos = jnp.arange(r_count, dtype=jnp.int32) + s  # leaf ids [R]
    kbase = (jnp.arange(n_keys, dtype=jnp.int32) * (2 * s))[:, None]
    win_prio = jnp.full((n_keys, r_count), -1, jnp.int32)
    lev = pos[None, :]
    for _ in range(levels):
        win_prio = jnp.maximum(win_prio, tree[kbase + lev])
        lev = lev >> 1
    win_pair = order[jnp.clip(win_prio, 0, p - 1)]
    win_value = jnp.where(win_prio >= 0, pair_value[win_pair], -1)  # [K, R]
    # empty regions (lo >= hi) style nothing — match the dense cover's
    # (lo < hi) conjunct
    win_value = jnp.where((lo < hi)[None, :], win_value, -1)
    return out_bounds, win_value.T  # [R, K]


class RichtextCols(NamedTuple):
    """[N] element rows (chars: content = codepoint; anchors: content=-1)
    + [P] anchor-pair rows."""

    seq: SeqColumns
    pair_start: jax.Array  # i32[P] element row of the start anchor
    pair_end: jax.Array  # i32[P] element row of the end anchor
    pair_key: jax.Array  # i32[P] style-key index
    pair_value: jax.Array  # i32[P] value index; -1 = null (unmark)
    pair_lamport: jax.Array
    pair_peer: jax.Array
    pair_valid: jax.Array  # bool[P] (False for pads / deleted anchors)


def richtext_merge_doc(
    cols: RichtextCols, n_keys: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (codes i32[N] in order (-1 pad tail), char count,
    region boundaries i32[2P+2] (ascending char positions, padded with
    count), winner value idx i32[2P+1, n_keys] (-1 = unstyled))."""
    seq = cols.seq
    n = seq.parent.shape[0]
    p = cols.pair_start.shape[0]
    rank = fugue_order(seq)
    m = rank_bound(n)
    rk = jnp.clip(rank, 0, m - 1)
    is_char = seq.content >= 0
    visible = seq.valid & ~seq.deleted & is_char
    hist = jnp.zeros(m, jnp.int32).at[jnp.where(visible, rk, m - 1)].add(
        visible.astype(jnp.int32)
    )
    pos_of_rank = jnp.cumsum(hist) - hist
    pos = pos_of_rank[rk]
    count = visible.sum().astype(jnp.int32)
    codes = jnp.full(n, -1, jnp.int32).at[jnp.where(visible, pos, n)].set(
        seq.content, mode="drop"
    )

    # anchor char-positions (chars before the anchor in final order).
    # pair_end < 0 = end anchor deleted while the start lives: the host
    # walk never pops the active entry, so the style runs to EOF
    ps = jnp.clip(cols.pair_start, 0, n - 1)
    pe = jnp.clip(cols.pair_end, 0, n - 1)
    a_start = jnp.where(cols.pair_valid, pos[ps], count)
    a_end = jnp.where(cols.pair_valid & (cols.pair_end >= 0), pos[pe], count)

    bounds, win_value = _resolve_styles(
        cols.pair_valid,
        cols.pair_key,
        cols.pair_value,
        cols.pair_lamport,
        cols.pair_peer,
        a_start,
        a_end,
        count,
        n_keys,
    )
    return codes, count, bounds, win_value


@doc_batch_jit
def richtext_merge_batch(cols: RichtextCols, n_keys: int):
    return jax.vmap(lambda c: richtext_merge_doc(c, n_keys))(cols)


class RichtextChainCols(NamedTuple):
    """Chain-contracted richtext batch: the gather-heavy ranking runs on
    the contracted chain tree (C << N — char runs contract exactly like
    the flagship text path), while anchors/deleted chars keep per-row
    positions via one stable N-row sort."""

    chain: ChainColumns
    pair_start: jax.Array  # i32[P] element row of the start anchor
    pair_end: jax.Array
    pair_key: jax.Array
    pair_value: jax.Array
    pair_lamport: jax.Array
    pair_peer: jax.Array
    pair_valid: jax.Array


def richtext_chain_merge_doc(
    cols: RichtextChainCols, n_keys: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Chain-contracted richtext merge: rank C chains (not N elements —
    char runs contract exactly as in the flagship text kernel), then
    realize every row's char-position with the histogram placement
    (chain-rank histogram + cumsum for chain bases, row-cumsum for
    within-chain offsets) — positions exist for ALL rows, so zero-width
    anchors get theirs for free.  Output contract matches
    richtext_merge_doc."""
    ch = cols.chain
    c = ch.c_parent.shape[0]
    n = ch.chain_id.shape[0]
    crank = _order_core(ch.c_parent, ch.c_side, ch.c_valid)  # i32[C]
    is_char = ch.content >= 0
    visible = ch.valid & ~ch.deleted & is_char
    cid = jnp.where(ch.valid, ch.chain_id, c)
    pos_row, count = chain_positions(crank, ch.c_valid, cid, ch.head_row, visible)
    codes = jnp.full(n, -1, jnp.int32).at[jnp.where(visible, pos_row, n)].set(
        ch.content, mode="drop"
    )
    # pair_end < 0 = deleted end anchor -> style runs to EOF (host walk)
    ps = jnp.clip(cols.pair_start, 0, n - 1)
    pe = jnp.clip(cols.pair_end, 0, n - 1)
    a_start = jnp.where(cols.pair_valid, pos_row[ps], count)
    a_end = jnp.where(cols.pair_valid & (cols.pair_end >= 0), pos_row[pe], count)
    bounds, win_value = _resolve_styles(
        cols.pair_valid,
        cols.pair_key,
        cols.pair_value,
        cols.pair_lamport,
        cols.pair_peer,
        a_start,
        a_end,
        count,
        n_keys,
    )
    return codes, count, bounds, win_value


@doc_batch_jit
def richtext_chain_merge_batch(cols: RichtextChainCols, n_keys: int):
    return jax.vmap(lambda c: richtext_chain_merge_doc(c, n_keys))(cols)


class RichtextPairs(NamedTuple):
    """Anchor-pair table for the RESIDENT richtext path ([D, P] device
    rows into a SeqColumnsU buffer; see DeviceDocBatch.richtexts)."""

    start: jax.Array  # i32[P] device row of the start anchor
    end: jax.Array
    key: jax.Array  # i32[P] batch-uniform style-key index
    value: jax.Array  # i32[P] per-doc value ordinal; -1 = null (unmark)
    lamport: jax.Array
    peer: jax.Array  # i32[P] per-doc peer rank (order-isomorphic to id)
    valid: jax.Array


def _richtext_by_key_doc(cols, key_hi, key_lo, pairs: RichtextPairs, n_keys: int):
    """Resident richtext materialization: ONE stable multi-key sort by
    the standing ShadowOrder keys realizes the text AND every row's
    char-position (anchors are zero-width rows needing positions), then
    styles resolve on the segment forest.  The incremental analog of
    richtext_chain_merge_doc — no rank solve, order work happened on
    ingest (O(delta))."""
    n = cols.content.shape[0]
    inf = jnp.uint32(0xFFFFFFFF)
    hi = jnp.where(cols.valid, key_hi, inf)
    lo = jnp.where(cols.valid, key_lo, inf)
    visible = cols.valid & ~cols.deleted & (cols.content >= 0)
    vis_i = visible.astype(jnp.int32)
    row_idx = jnp.arange(n, dtype=jnp.int32)
    _, _, vis_s, row_s, content_s = jax.lax.sort(
        (hi, lo, vis_i, row_idx, cols.content), num_keys=2, is_stable=True
    )
    pos_s = jnp.cumsum(vis_s) - vis_s
    count = vis_i.sum().astype(jnp.int32)
    codes = jnp.full(n, -1, jnp.int32).at[jnp.where(vis_s == 1, pos_s, n)].set(
        content_s, mode="drop"
    )
    pos_row = jnp.zeros(n, jnp.int32).at[row_s].set(pos_s)
    # end < 0 = deleted end anchor -> style runs to EOF (host walk)
    ps = jnp.clip(pairs.start, 0, n - 1)
    pe = jnp.clip(pairs.end, 0, n - 1)
    a_start = jnp.where(pairs.valid, pos_row[ps], count)
    a_end = jnp.where(pairs.valid & (pairs.end >= 0), pos_row[pe], count)
    bounds, win_value = _resolve_styles(
        pairs.valid,
        pairs.key,
        pairs.value,
        pairs.lamport,
        pairs.peer,
        a_start,
        a_end,
        count,
        n_keys,
    )
    return codes, count, bounds, win_value


@functools.partial(jax.jit, static_argnums=(4,))
def richtext_by_key_batch(cols, key_hi, key_lo, pairs: RichtextPairs, n_keys: int):
    return jax.vmap(
        lambda c, h, lo_, p: _richtext_by_key_doc(c, h, lo_, p, n_keys)
    )(cols, key_hi, key_lo, pairs)


def segments_from_device(codes, count, bounds, win, keys, values):
    """Reconstruct Quill-style [{insert, attributes?}] segments from one
    doc's device outputs — the comparison form against the host's
    TextState.get_richtext_value() (differential tests + bench gates)."""
    text = text_from_codes(codes, count)
    bounds = np.asarray(bounds)
    win = np.asarray(win)
    segs = []
    for r in range(len(bounds) - 1):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        if lo >= hi:
            continue
        attrs = {}
        for k in range(len(keys)):
            vi = int(win[r, k])
            if vi >= 0:
                attrs[keys[k]] = values[vi]
        seg = {"insert": text[lo:hi]}
        if attrs:
            seg["attributes"] = attrs
        if segs and segs[-1].get("attributes") == seg.get("attributes"):
            segs[-1]["insert"] += seg["insert"]
        else:
            segs.append(seg)
    return segs


def _explode_richtext(changes, cid):
    """Host: explode a Text container (chars + anchors) into a
    SeqExtract (anchors carry content=-1) + pair arrays + (keys,
    values).  Pairing invariant: a start anchor at id (p, c) pairs with
    the end anchor (p, c+1) (TextHandler.mark emits exactly that)."""
    from ..core.change import SeqDelete, SeqInsert, StyleAnchor
    from ..oplog.oplog import _RunCont

    peers_seen = sorted({ch.peer for ch in changes})
    peer_rank = {pr: i for i, pr in enumerate(peers_seen)}
    rows = []  # (parent, side, peer_rank, counter, content)
    id2row = {}
    keys, key_idx = [], {}
    values = []
    anchors = {}  # (peer, counter) -> dict
    deletes = []

    def kidx(k):
        if k not in key_idx:
            key_idx[k] = len(keys)
            keys.append(k)
        return key_idx[k]

    for ch in changes:
        for op in ch.ops:
            if op.container != cid:
                continue
            c = op.content
            lam = ch.lamport + (op.counter - ch.ctr_start)
            if isinstance(c, SeqInsert):
                if isinstance(c.parent, _RunCont):
                    pidx = id2row[(ch.peer, op.counter - 1)]
                elif c.parent is None:
                    pidx = -1
                else:
                    pidx = id2row[(c.parent.peer, c.parent.counter)]
                if isinstance(c.content, StyleAnchor):
                    a = c.content
                    row = len(rows)
                    id2row[(ch.peer, op.counter)] = row
                    rows.append((pidx, int(c.side), peer_rank[ch.peer], op.counter, -1))
                    if a.value is None:
                        vi = -1
                    else:
                        vi = len(values)
                        values.append(a.value)
                    anchors[(ch.peer, op.counter)] = {
                        "row": row,
                        "key": kidx(a.key),
                        "value": vi,
                        "lamport": lam,
                        "peer": peer_rank[ch.peer],
                        "start": a.is_start,
                        "deleted": False,
                    }
                else:
                    for j, chr_ in enumerate(c.content):
                        row = len(rows)
                        id2row[(ch.peer, op.counter + j)] = row
                        rows.append(
                            (
                                pidx if j == 0 else row - 1,
                                int(c.side) if j == 0 else 1,
                                peer_rank[ch.peer],
                                op.counter + j,
                                ord(chr_),
                            )
                        )
            elif isinstance(c, SeqDelete):
                for sp in c.spans:
                    deletes.append((sp.peer, sp.start, sp.end))

    n = len(rows)
    arr = np.asarray(rows, np.int64).reshape(n, 5) if n else np.zeros((0, 5), np.int64)
    deleted = np.zeros(n, bool)
    for peer, start, end in deletes:
        for ctr in range(start, end):
            i = id2row.get((peer, ctr))
            if i is not None:
                deleted[i] = True
                a = anchors.get((peer, ctr))
                if a is not None:
                    a["deleted"] = True
    from .columnar import SeqExtract, peer_counter_perm

    perm, inv, parent = peer_counter_perm(arr[:, 2], arr[:, 3], arr[:, 0])
    ex = SeqExtract(
        parent=parent.astype(np.int32),
        side=arr[perm, 1].astype(np.int32),
        peer=arr[perm, 2].astype(np.int32),
        counter=arr[perm, 3].astype(np.int32),
        deleted=deleted[perm],
        content=arr[perm, 4].astype(np.int32),
        valid=np.ones(n, bool),
        peers=peers_seen,
    )
    # pairs: start anchor (p,c) + end anchor (p,c+1).  Host-walk
    # semantics (_iter_char_attrs): a pair is active iff its START
    # anchor is live; a deleted END anchor never pops the active entry,
    # so the style runs to EOF — encoded as end row -1
    pairs = []
    for (peer, ctr), a in anchors.items():
        if not a["start"]:
            continue
        end = anchors.get((peer, ctr + 1))
        if end is None or end["start"]:
            continue  # unpaired (mid-transfer); inactive
        pairs.append(
            (
                inv[a["row"]],
                -1 if end["deleted"] else inv[end["row"]],
                a["key"],
                a["value"],
                a["lamport"],
                a["peer"],
                not a["deleted"],
            )
        )
    pp = len(pairs)
    parr = np.asarray(pairs, np.int64).reshape(pp, 7) if pp else np.zeros((0, 7), np.int64)
    return ex, parr, keys, values


def _pair_fields(parr: np.ndarray) -> dict:
    return dict(
        pair_start=parr[:, 0].astype(np.int32),
        pair_end=parr[:, 1].astype(np.int32),
        pair_key=parr[:, 2].astype(np.int32),
        pair_value=parr[:, 3].astype(np.int32),
        pair_lamport=parr[:, 4].astype(np.int32),
        pair_peer=parr[:, 5].astype(np.int32),
        pair_valid=parr[:, 6].astype(bool),
    )


def extract_richtext(changes, cid):
    """Host: RichtextCols (numpy) + (keys, values) — the uncontracted
    element-level kernel input (kept as the differential second
    implementation; the fleet/bench path is extract_richtext_chain)."""
    ex, parr, keys, values = _explode_richtext(changes, cid)
    return (
        RichtextCols(seq=ex.to_seq_columns(), **_pair_fields(parr)),
        keys,
        values,
    )


def pad_richtext_chain_cols(
    cols: RichtextChainCols, pad_n: int, pad_c: int, pad_p: int
) -> RichtextChainCols:
    """Pad numpy RichtextChainCols to uniform (N, C, P) device shapes."""

    def pad(a, size, fill):
        if a.shape[0] >= size:
            return a
        out = np.full(size, fill, a.dtype)
        out[: a.shape[0]] = a
        return out

    ch = cols.chain
    chain = ChainColumns(
        c_parent=pad(ch.c_parent, pad_c, -1),
        c_side=pad(ch.c_side, pad_c, 0),
        c_valid=pad(ch.c_valid, pad_c, False),
        head_row=pad(ch.head_row, pad_c, 0),
        chain_id=pad(ch.chain_id, pad_n, 0),
        deleted=pad(ch.deleted, pad_n, True),
        content=pad(ch.content, pad_n, -1),
        valid=pad(ch.valid, pad_n, False),
    )
    return RichtextChainCols(
        chain=chain,
        pair_start=pad(cols.pair_start, pad_p, 0),
        pair_end=pad(cols.pair_end, pad_p, 0),
        pair_key=pad(cols.pair_key, pad_p, 0),
        pair_value=pad(cols.pair_value, pad_p, -1),
        pair_lamport=pad(cols.pair_lamport, pad_p, 0),
        pair_peer=pad(cols.pair_peer, pad_p, 0),
        pair_valid=pad(cols.pair_valid, pad_p, False),
    )


def extract_richtext_chain(changes, cid):
    """Host: chain-contracted RichtextChainCols (numpy) + (keys, values)
    — ranking cost scales with chain count C, not element count N.
    Pad to device shapes with pad_richtext_chain_cols."""
    from .columnar import chain_columns

    ex, parr, keys, values = _explode_richtext(changes, cid)
    return (
        RichtextChainCols(chain=chain_columns(ex), **_pair_fields(parr)),
        keys,
        values,
    )
