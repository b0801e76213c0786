"""Host-side columnar (SoA) extraction: change lists -> padded device
arrays.

The analog of the reference's columnar block decode
(crates/loro-internal/src/oplog/change_store/block_encode.rs) feeding
the merge engine: ops are exploded into per-element / per-atom columns
that the device kernels consume directly.  numpy only — this is the
host pipeline stage that overlaps with device compute in the fleet.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.change import Change, MapSet, SeqDelete, SeqInsert, StyleAnchor
from ..core.ids import ContainerID
from ..oplog.oplog import _RunCont
from .fugue_batch import SeqColumns, pack_chain_doc_into


@dataclass
class SeqExtract:
    """Numpy element table for one container's full history."""

    parent: np.ndarray  # i32[N], -1 root
    side: np.ndarray  # i32[N]
    peer: np.ndarray  # i32[N] peer rank
    counter: np.ndarray  # i32[N]
    deleted: np.ndarray  # bool[N]
    content: np.ndarray  # i32[N] codepoint (text) or value index
    valid: np.ndarray  # bool[N]
    peers: List[int]  # rank -> peer id dictionary (sorted)
    values: Optional[List] = None  # value dictionary for list payloads

    @property
    def n(self) -> int:
        return int(self.parent.shape[0])

    def sort_by_peer_counter(self) -> "SeqExtract":
        """Reorder rows to (peer, counter) order and remap parent indices
        — the input contract of ops.fugue_batch.fugue_order (lets the
        device do a single stable sort).  Ordering plumbing (incl. the
        radix fast path for causally-ordered rows) is shared with the
        other extractors via peer_counter_perm."""
        perm, _inv, parent = peer_counter_perm(
            self.peer, self.counter, self.parent
        )
        return SeqExtract(
            parent=parent,
            side=self.side[perm],
            peer=self.peer[perm],
            counter=self.counter[perm],
            deleted=self.deleted[perm],
            content=self.content[perm],
            valid=self.valid[perm],
            peers=self.peers,
            values=self.values,
        )

    def to_seq_columns(self, pad_to: Optional[int] = None) -> SeqColumns:
        n = self.n if pad_to is None else pad_to
        assert n >= self.n

        def pad(a, fill):
            if n == a.shape[0]:
                return a
            out = np.full(n, fill, a.dtype)
            out[: a.shape[0]] = a
            return out

        return SeqColumns(
            parent=pad(self.parent, -1),
            side=pad(self.side, 0),
            peer=pad(self.peer, 0),
            counter=pad(self.counter, 0),
            deleted=pad(self.deleted, True),
            content=pad(self.content, -1),
            valid=pad(self.valid, False),
        )


def extract_seq_container(
    changes: Sequence[Change], cid: ContainerID, as_text: bool = True
) -> SeqExtract:
    """Explode all SeqInsert/SeqDelete ops targeting `cid` (causal order)
    into an element table.  Anchors and movable-list machinery are out of
    scope here (plain text/list payloads)."""
    peers_seen = sorted({ch.peer for ch in changes})
    peer_rank = {p: i for i, p in enumerate(peers_seen)}
    parents: List[int] = []
    sides: List[int] = []
    peers: List[int] = []
    counters: List[int] = []
    contents: List[int] = []
    values: List = []
    id2idx: Dict[Tuple[int, int], int] = {}
    deletes: List[Tuple[int, int, int]] = []  # (peer, start, end)

    for ch in changes:
        for op in ch.ops:
            if op.container != cid:
                continue
            c = op.content
            if isinstance(c, SeqInsert):
                if isinstance(c.content, StyleAnchor):
                    continue
                body = c.content
                for j in range(len(body)):
                    if j == 0:
                        if isinstance(c.parent, _RunCont):
                            pkey = (ch.peer, op.counter - 1)
                            pidx = id2idx[pkey]
                        elif c.parent is None:
                            pidx = -1
                        else:
                            pidx = id2idx[(c.parent.peer, c.parent.counter)]
                        side = int(c.side)
                    else:
                        pidx = len(parents) - 1
                        side = 1
                    idx = len(parents)
                    id2idx[(ch.peer, op.counter + j)] = idx
                    parents.append(pidx)
                    sides.append(side)
                    peers.append(peer_rank[ch.peer])
                    counters.append(op.counter + j)
                    if as_text:
                        contents.append(ord(body[j]))
                    else:
                        contents.append(len(values))
                        values.append(body[j])
            elif isinstance(c, SeqDelete):
                for s in c.spans:
                    deletes.append((s.peer, s.start, s.end))

    n = len(parents)
    deleted = np.zeros(n, bool)
    for peer, start, end in deletes:
        for ctr in range(start, end):
            idx = id2idx.get((peer, ctr))
            if idx is not None:
                deleted[idx] = True
    return SeqExtract(
        parent=np.asarray(parents, np.int32),
        side=np.asarray(sides, np.int32),
        peer=np.asarray(peers, np.int32),
        counter=np.asarray(counters, np.int32),
        deleted=deleted,
        content=np.asarray(contents, np.int32),
        valid=np.ones(n, bool),
        peers=peers_seen,
        values=values if not as_text else None,
    ).sort_by_peer_counter()


@dataclass
class MapExtract:
    """Columns for batched LWW map merge: one row per MapSet atom."""

    slot: np.ndarray  # i32[M] (container,key) slot index
    lamport: np.ndarray  # i32[M]
    peer: np.ndarray  # i32[M] peer rank
    value_idx: np.ndarray  # i32[M]
    valid: np.ndarray  # bool[M]
    slots: List[Tuple[ContainerID, str]]  # slot dictionary
    values: List  # value dictionary (index -1 = deletion)
    peers: List[int]


def extract_map_ops(changes: Sequence[Change]) -> MapExtract:
    peers_seen = sorted({ch.peer for ch in changes})
    peer_rank = {p: i for i, p in enumerate(peers_seen)}
    slot_of: Dict[Tuple[ContainerID, str], int] = {}
    slots: List[Tuple[ContainerID, str]] = []
    values: List = []
    rows: List[Tuple[int, int, int, int]] = []
    for ch in changes:
        for op in ch.ops:
            c = op.content
            if not isinstance(c, MapSet):
                continue
            key = (op.container, c.key)
            if key not in slot_of:
                slot_of[key] = len(slots)
                slots.append(key)
            lam = ch.lamport + (op.counter - ch.ctr_start)
            if c.deleted:
                vi = -1
            else:
                vi = len(values)
                values.append(c.value)
            rows.append((slot_of[key], lam, peer_rank[ch.peer], vi))
    m = len(rows)
    arr = np.asarray(rows, np.int64).reshape(m, 4) if m else np.zeros((0, 4), np.int64)
    return MapExtract(
        slot=arr[:, 0].astype(np.int32),
        lamport=arr[:, 1].astype(np.int32),
        peer=arr[:, 2].astype(np.int32),
        value_idx=arr[:, 3].astype(np.int32),
        valid=np.ones(m, bool),
        slots=slots,
        values=values,
        peers=peers_seen,
    )


def peer_counter_perm(peer: np.ndarray, counter: np.ndarray, parent: np.ndarray):
    """Shared (peer, counter)-ordering plumbing for extractors: returns
    (perm, inv, remapped_parent) where parent indexes are rewritten
    through the permutation (the fugue_order input contract); `inv` maps
    old row -> new row for remapping any other row references.

    Fast path: causally-ordered inputs already have counters ascending
    within each peer in row order, so a single-key stable radix argsort
    by peer suffices (measured 1.6 ms vs 7.0 ms for the two-key lexsort
    on the 182k-row trace); the post-condition is verified vectorized
    and falls back to the full lexsort for arbitrary row orders."""
    n = len(peer)
    if n == 0:
        perm = np.zeros(0, np.int64)
    else:
        perm = np.argsort(peer, kind="stable")
        if n > 1:
            ctr_s = counter[perm].astype(np.int64)
            peer_s = peer[perm].astype(np.int64)
            if not ((np.diff(ctr_s) > 0) | (np.diff(peer_s) != 0)).all():
                perm = np.lexsort((counter, peer))
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    out_parent = np.asarray(parent)[perm].astype(np.int64)
    mask = out_parent >= 0
    out_parent[mask] = inv[out_parent[mask]]
    return perm, inv, out_parent.astype(np.int32)


def wire_peer_ranks(peers_wire) -> np.ndarray:
    """rank_of[wire_idx] -> sorted-u64 peer rank (the LWW/sibling
    tie-break ordering contract; wire registration order must not
    leak)."""
    peer_u64 = np.asarray(peers_wire, np.uint64)
    rank_of = np.empty(len(peers_wire), np.int64)
    rank_of[np.argsort(peer_u64, kind="stable")] = np.arange(len(peers_wire))
    return rank_of


def pack_wire_ids(peer_idx, ctr) -> np.ndarray:
    """(wire peer idx, counter) packed into i64 for vectorized id
    dictionaries (peer indexes are small; counters non-negative)."""
    return (np.asarray(peer_idx, np.int64) << 32) | np.asarray(ctr, np.int64)


def extract_seq_from_payload(payload: bytes, cid: ContainerID) -> Optional[SeqExtract]:
    """Native-decoder fast path: binary updates payload -> SeqExtract
    without materializing Python Change objects (the fleet ingest path;
    ~1000x the Python explode loop).  Returns None when the native
    library is unavailable; raises ValueError on malformed payloads."""
    from ..codec.binary import read_tables
    from ..native import available, explode_seq_payload

    if not available():
        return None
    peers, _keys, cids, _r = read_tables(payload)
    try:
        target = cids.index(cid)
    except ValueError:
        return SeqExtract(
            parent=np.zeros(0, np.int32),
            side=np.zeros(0, np.int32),
            peer=np.zeros(0, np.int32),
            counter=np.zeros(0, np.int32),
            deleted=np.zeros(0, bool),
            content=np.zeros(0, np.int32),
            valid=np.zeros(0, bool),
            peers=[],
        )
    out = explode_seq_payload(payload, target)
    if out is None:
        return None
    parent, side, peer_idx, counter, deleted, content = out
    # wire peer table is registration-ordered; the kernel contract needs
    # order-preserving ranks of the sorted u64 peer ids
    order = np.argsort(np.asarray(peers, np.uint64), kind="stable")
    rank_of = np.empty(len(peers), np.int32)
    rank_of[order] = np.arange(len(peers), dtype=np.int32)
    peer_rank = rank_of[peer_idx] if len(peers) else peer_idx
    return SeqExtract(
        parent=parent,
        side=side,
        peer=peer_rank.astype(np.int32),
        counter=counter,
        deleted=deleted,
        content=content,
        valid=np.ones(parent.shape[0], bool),
        peers=sorted(peers),
    ).sort_by_peer_counter()


def pad_rows(a: np.ndarray, n: int, fill) -> np.ndarray:
    if a.shape[0] == n:
        return a
    out = np.full((n,) + a.shape[1:], fill, a.dtype)
    out[: a.shape[0]] = a
    return out


@dataclass
class ChainExtract:
    """Right-spine chains (RLE runs) of a SeqExtract — the contraction
    that makes device ranking cheap (the reference's FugueSpan RLE
    serves the same purpose for its B-tree, fugue_span.rs runs).

    An element i is chained to row i-1 iff parent[i]==i-1, side==Right,
    row i-1 has exactly one child and no left children, and row i has no
    left children.  On the *final* tree these conditions make chain
    units contiguous in traversal, so contracting them is exact.
    Chains are contiguous row ranges; `chain_id` maps element row ->
    chain index (chains numbered in row order, preserving the
    (peer, counter) sibling-order contract at chain level)."""

    parent: np.ndarray  # i32[C] chain-level fugue parent (chain idx, -1 root)
    side: np.ndarray  # i32[C]
    valid: np.ndarray  # bool[C]
    head_row: np.ndarray  # i32[C] first element row of each chain
    chain_id: np.ndarray  # i32[N] element row -> chain

    @property
    def n_chains(self) -> int:
        return int(self.parent.shape[0])


def chain_columns(
    ex: SeqExtract,
    pad_n: Optional[int] = None,
    pad_c: Optional[int] = None,
    bucket: bool = False,
    chains: Optional["ChainExtract"] = None,
):
    """Padded numpy ChainColumns for the chain-contracted device path.
    With bucket=True, both dims pad to power-of-two buckets (shares the
    jit cache across varying sizes) without a separate contract pass.
    ``chains`` is ``contract_chains(ex)`` where the caller has made it."""
    from .fugue_batch import ChainColumns, pad_bucket

    ch = chains if chains is not None else contract_chains(ex)
    if bucket:
        n = pad_n or pad_bucket(max(1, ex.n))
        c = pad_c or pad_bucket(max(1, ch.n_chains))
    else:
        n = pad_n or ex.n
        c = pad_c or ch.n_chains

    def pad(a, size, fill):
        if a.shape[0] == size:
            return a
        out = np.full(size, fill, a.dtype)
        out[: a.shape[0]] = a
        return out

    return ChainColumns(
        c_parent=pad(ch.parent, c, -1),
        c_side=pad(ch.side, c, 0),
        c_valid=pad(ch.valid, c, False),
        head_row=pad(ch.head_row, c, 0),
        chain_id=pad(ch.chain_id, n, 0),
        deleted=pad(ex.deleted, n, True),
        content=pad(ex.content, n, -1),
        valid=pad(ex.valid, n, False),
    )


def contract_chains(ex: SeqExtract) -> ChainExtract:
    """The chains of ``ex`` (rule: ``ChainExtract``): one native call
    where the library is there (``native.contract_chains``: three linear
    passes, no interpreter lock), else the numpy body below, which is
    also the differential reference (``_contract_chains_numpy``)."""
    from ..native import contract_chains as native_contract

    out = native_contract(ex.parent, ex.side)
    if out is None:
        return _contract_chains_numpy(ex)
    chain_id, head_row, c_parent, c_side = out
    return ChainExtract(
        parent=c_parent,
        side=c_side,
        valid=np.ones(len(head_row), bool),
        head_row=head_row,
        chain_id=chain_id,
    )


def _contract_chains_numpy(ex: SeqExtract) -> ChainExtract:
    n = ex.n
    parent, side = ex.parent, ex.side
    pp = np.maximum(parent, 0)
    cc = np.bincount(parent[parent >= 0], minlength=n)
    lc = np.bincount(parent[(parent >= 0) & (side == 0)], minlength=n)
    rows = np.arange(n)
    link = (
        (parent == rows - 1)
        & (side == 1)
        & (cc[pp] == 1)
        & (lc[pp] == 0)
        & (lc[rows] == 0)
        & (parent >= 0)
    )
    chain_id = np.cumsum(~link) - 1
    head_mask = ~link
    head_row = np.flatnonzero(head_mask).astype(np.int32)
    c_parent_elem = parent[head_row]  # element row of the chain's parent
    c_parent = np.where(c_parent_elem >= 0, chain_id[np.maximum(c_parent_elem, 0)], -1)
    return ChainExtract(
        parent=c_parent.astype(np.int32),
        side=side[head_row].astype(np.int32),
        valid=np.ones(len(head_row), bool),
        head_row=head_row,
        chain_id=chain_id.astype(np.int32),
    )


def pack_chain_row(
    ex: SeqExtract, chains: ChainExtract, pad_c: int, pad_n: int, out_row: np.ndarray
) -> None:
    """One document's packed u8 row (``packed_row_bytes(pad_c, pad_n)``
    bytes, layout above it) straight from the unpadded extract and its
    chains: byte for byte ``pack_chain_doc_into(chain_columns(ex, pad_n,
    pad_c, chains=chains), out_row)``, which it is where the native
    library is absent, in one native call and no padded copy between."""
    from ..native import pack_chain_row as native_pack

    if not native_pack(
        chains.parent, chains.side, chains.valid, chains.head_row, chains.chain_id,
        ex.content, ex.deleted, ex.valid, pad_c, pad_n, out_row,
    ):
        pack_chain_doc_into(chain_columns(ex, pad_n=pad_n, pad_c=pad_c, chains=chains), out_row)
