"""Binary columnar wire codec.

reference: crates/loro-internal/src/oplog/change_store/block_encode.rs +
encoding/ (LEB128 headers, peer table, delta-encoded counters/lamports,
delta-of-delta timestamps, columnar op table).  Same layout ideas,
different format (we are not wire-compatible with the reference —
SURVEY.md §7 treats wire compat as a test oracle only, and our op model
ships Fugue (parent, side) placements).

Layout (after the doc-level LTPU envelope):
  [peer table]   varint n, then n u64 LE peers (dictionary; ids below
                 are peer *indices*)
  [key table]    varint n, n length-prefixed utf8 strings (map keys +
                 style keys)
  [cid table]    varint n, n encoded ContainerIDs
  [change meta]  varint n_changes, then columnar arrays:
                   peer_idx (varint each)
                   counter (zigzag delta per peer stream)
                   lamport (zigzag delta vs counter delta)
                   timestamp (zigzag delta)
                   n_deps + deps (peer_idx, zigzag counter)
                   message (tag + utf8)
                   n_ops
  [ops]          per change, per op: container_idx varint, kind byte,
                 kind-specific payload (varints/values)
Values use a compact tagged encoding (VNULL..VCID below).
"""
from __future__ import annotations

import struct
from typing import Any, Dict, List

from ..core.change import (
    Change,
    CounterIncr,
    MapSet,
    MovableMove,
    MovableSet,
    Op,
    SeqDelete,
    SeqInsert,
    Side,
    StyleAnchor,
    TreeMove,
    UnknownContent,
)
from ..core.ids import ContainerID, ContainerType, ID, IdSpan, TreeID
from ..core.version import Frontiers

# op kind tags
K_MAP_SET = 0
K_MAP_DEL = 1
K_INSERT_TEXT = 2
K_INSERT_VALUES = 3
K_INSERT_ANCHOR = 4
K_DELETE = 5
K_TREE = 6
K_COUNTER = 7
K_MSET = 8
K_MMOVE = 9
K_UNKNOWN = 10

# value tags
VNULL, VTRUE, VFALSE, VINT, VF64, VSTR, VBYTES, VLIST, VMAP, VCID = range(10)

RUN_CONT_TAG = 2  # parent encoding: 0=None, 1=id, 2=run-continuation


class Writer:
    __slots__ = ("buf",)

    def __init__(self) -> None:
        self.buf = bytearray()

    def u8(self, v: int) -> None:
        self.buf.append(v & 0xFF)

    def varint(self, v: int) -> None:
        """LEB128 unsigned."""
        assert v >= 0
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                self.buf.append(b | 0x80)
            else:
                self.buf.append(b)
                return

    def zigzag(self, v: int) -> None:
        self.varint((v << 1) ^ (v >> 63) if v >= 0 else ((-v) << 1) - 1)

    def u64le(self, v: int) -> None:
        self.buf += struct.pack("<Q", v)

    def u32le(self, v: int) -> None:
        self.buf += struct.pack("<I", v)

    def f64(self, v: float) -> None:
        self.buf += struct.pack("<d", v)

    def bytes_(self, b: bytes) -> None:
        self.varint(len(b))
        self.buf += b

    def str_(self, s: str) -> None:
        self.bytes_(s.encode())


class Reader:
    __slots__ = ("buf", "i")

    def __init__(self, buf: bytes):
        self.buf = buf
        self.i = 0

    def u8(self) -> int:
        v = self.buf[self.i]
        self.i += 1
        return v

    def varint(self) -> int:
        v = 0
        shift = 0
        while True:
            b = self.buf[self.i]
            self.i += 1
            v |= (b & 0x7F) << shift
            if not (b & 0x80):
                return v
            shift += 7
            if shift > 70:
                raise ValueError("varint overflow")

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) if not (v & 1) else -((v + 1) >> 1)

    def u64le(self) -> int:
        v = struct.unpack_from("<Q", self.buf, self.i)[0]
        self.i += 8
        return v

    def u32le(self) -> int:
        v = struct.unpack_from("<I", self.buf, self.i)[0]
        self.i += 4
        return v

    def f64(self) -> float:
        v = struct.unpack_from("<d", self.buf, self.i)[0]
        self.i += 8
        return v

    def bytes_(self) -> bytes:
        n = self.varint()
        if self.i + n > len(self.buf):
            raise ValueError("truncated bytes")
        b = self.buf[self.i : self.i + n]
        self.i += n
        return bytes(b)

    def str_(self) -> str:
        return self.bytes_().decode()

    def eof(self) -> bool:
        return self.i >= len(self.buf)


class _Dicts:
    """Encoding dictionaries (peer / key / container tables)."""

    def __init__(self) -> None:
        self.peers: List[int] = []
        self._peer_idx: Dict[int, int] = {}
        self.keys: List[str] = []
        self._key_idx: Dict[str, int] = {}
        self.cids: List[ContainerID] = []
        self._cid_idx: Dict[ContainerID, int] = {}

    def peer(self, p: int) -> int:
        i = self._peer_idx.get(p)
        if i is None:
            i = len(self.peers)
            self.peers.append(p)
            self._peer_idx[p] = i
        return i

    def key(self, k: str) -> int:
        i = self._key_idx.get(k)
        if i is None:
            i = len(self.keys)
            self.keys.append(k)
            self._key_idx[k] = i
        return i

    def cid(self, c: ContainerID) -> int:
        i = self._cid_idx.get(c)
        if i is None:
            i = len(self.cids)
            self.cids.append(c)
            self._cid_idx[c] = i
        return i


def _write_value(w: Writer, d: _Dicts, v: Any) -> None:
    if v is None:
        w.u8(VNULL)
    elif v is True:
        w.u8(VTRUE)
    elif v is False:
        w.u8(VFALSE)
    elif isinstance(v, int):
        w.u8(VINT)
        w.zigzag(v)
    elif isinstance(v, float):
        w.u8(VF64)
        w.f64(v)
    elif isinstance(v, str):
        w.u8(VSTR)
        w.str_(v)
    elif isinstance(v, bytes):
        w.u8(VBYTES)
        w.bytes_(v)
    elif isinstance(v, (list, tuple)):
        w.u8(VLIST)
        w.varint(len(v))
        for x in v:
            _write_value(w, d, x)
    elif isinstance(v, dict):
        w.u8(VMAP)
        w.varint(len(v))
        for k in sorted(v):
            w.str_(k)
            _write_value(w, d, v[k])
    elif isinstance(v, ContainerID):
        w.u8(VCID)
        w.varint(d.cid(v))
    else:
        raise TypeError(f"cannot encode value {type(v)}")


def _read_value(r: Reader, cids: List[ContainerID]) -> Any:
    t = r.u8()
    if t == VNULL:
        return None
    if t == VTRUE:
        return True
    if t == VFALSE:
        return False
    if t == VINT:
        return r.zigzag()
    if t == VF64:
        return r.f64()
    if t == VSTR:
        return r.str_()
    if t == VBYTES:
        return r.bytes_()
    if t == VLIST:
        return [_read_value(r, cids) for _ in range(r.varint())]
    if t == VMAP:
        return {r.str_(): _read_value(r, cids) for _ in range(r.varint())}
    if t == VCID:
        return cids[r.varint()]
    raise ValueError(f"bad value tag {t}")


def read_values_at(buf: bytes, offsets, cids: List[ContainerID]) -> list:
    """The tagged values that start at byte ``offsets`` of ``buf``, in
    that order.  When every one is a string of under 128 ASCII bytes (a
    one-byte length: what a list of typed characters holds), they are
    gathered by array operations and decoded at once; otherwise each is
    read by ``_read_value``, as are a few values, for which the array
    operations would cost more than they save."""
    import numpy as np

    offsets = np.asarray(offsets, np.int64)
    data = np.frombuffer(buf, np.uint8)
    if len(offsets) >= 16 and (data[offsets] == VSTR).all():
        n = data[offsets + 1].astype(np.int64)
        if (n < 0x80).all():
            ends = np.cumsum(n)
            raw = data[np.repeat(offsets + 2 - ends + n, n)
                       + np.arange(int(ends[-1]), dtype=np.int64)]
            if not (raw & 0x80).any():
                text = raw.tobytes().decode("ascii")
                if (n == 1).all():
                    return list(text)
                ends = ends.tolist()
                return [text[a:b] for a, b in zip([0] + ends[:-1], ends)]
    r = Reader(buf)
    out = []
    for at in offsets.tolist():
        r.i = at
        out.append(_read_value(r, cids))
    return out


def _write_cid(w: Writer, d: _Dicts, c: ContainerID) -> None:
    if c.is_root:
        w.u8(int(c.ctype) | 0x80)
        w.str_(c.name)  # type: ignore[arg-type]
    else:
        w.u8(int(c.ctype))
        w.varint(d.peer(c.peer))  # type: ignore[arg-type]
        w.zigzag(c.counter)  # type: ignore[arg-type]


def _read_cid(r: Reader, peers: List[int]) -> ContainerID:
    b = r.u8()
    ctype = ContainerType(b & 0x7F)
    if b & 0x80:
        return ContainerID.root(r.str_(), ctype)
    pi = r.varint()
    if pi >= len(peers):
        raise ValueError(f"cid peer index {pi} out of table ({len(peers)} peers)")
    return ContainerID.normal(peers[pi], r.zigzag(), ctype)


def encode_changes(changes: List[Change]) -> bytes:
    d = _Dicts()
    # pass 1: dictionaries (stable order)
    for ch in changes:
        d.peer(ch.peer)
        for dep in ch.deps:
            d.peer(dep.peer)
        for op in ch.ops:
            d.cid(op.container)
            c = op.content
            if isinstance(c, MapSet):
                d.key(c.key)
            elif isinstance(c, SeqInsert):
                if isinstance(c.parent, ID):
                    d.peer(c.parent.peer)
                if isinstance(c.content, StyleAnchor):
                    d.key(c.content.key)
            elif isinstance(c, SeqDelete):
                for s in c.spans:
                    d.peer(s.peer)
            elif isinstance(c, TreeMove):
                d.peer(c.target.peer)
                if c.parent is not None:
                    d.peer(c.parent.peer)
            elif isinstance(c, (MovableSet, MovableMove)):
                d.peer(c.elem.peer)
                if isinstance(c, MovableMove) and isinstance(c.parent, ID):
                    d.peer(c.parent.peer)
    # values may reference cids — collect by dry-encoding values last;
    # VCID entries are registered during the value write below, so write
    # ops to a scratch buffer first, then emit tables, then the scratch.
    ops_w = Writer()
    for ch in changes:
        for op in ch.ops:
            _write_op(ops_w, d, op)

    # container ids can reference peers that appear in no change meta
    # (e.g. a partial update editing a container created long ago) —
    # register them BEFORE the peer table is emitted, or the cid table
    # below would append peers past the already-written table
    for c in d.cids:
        if not c.is_root:
            d.peer(c.peer)  # type: ignore[arg-type]

    w = Writer()
    w.varint(len(d.peers))
    for p in d.peers:
        w.u64le(p)
    w.varint(len(d.keys))
    for k in d.keys:
        w.str_(k)
    w.varint(len(d.cids))
    for c in d.cids:
        _write_cid(w, d, c)
    # change meta (columnar-ish: one field at a time per change)
    w.varint(len(changes))
    prev_ts = 0
    for ch in changes:
        w.varint(d.peer(ch.peer))
        w.zigzag(ch.ctr_start)
        w.zigzag(ch.lamport)
        w.zigzag(ch.timestamp - prev_ts)
        prev_ts = ch.timestamp
        w.varint(len(ch.deps))
        for dep in ch.deps:
            w.varint(d.peer(dep.peer))
            w.zigzag(dep.counter)
        if ch.message is None:
            w.u8(0)
        else:
            w.u8(1)
            w.str_(ch.message)
        w.varint(len(ch.ops))
    w.buf += ops_w.buf
    return bytes(w.buf)


def _write_op(w: Writer, d: _Dicts, op: Op) -> None:
    c = op.content
    w.varint(d.cid(op.container))
    if isinstance(c, MapSet):
        if c.deleted:
            w.u8(K_MAP_DEL)
            w.varint(d.key(c.key))
        else:
            w.u8(K_MAP_SET)
            w.varint(d.key(c.key))
            _write_value(w, d, c.value)
    elif isinstance(c, SeqInsert):
        if isinstance(c.content, StyleAnchor):
            w.u8(K_INSERT_ANCHOR)
            self_anchor = c.content
            _write_parent(w, d, c.parent)
            w.u8(int(c.side))
            w.varint(d.key(self_anchor.key))
            _write_value(w, d, self_anchor.value)
            w.u8(1 if self_anchor.is_start else 0)
            w.varint(self_anchor.info)
        elif isinstance(c.content, str):
            w.u8(K_INSERT_TEXT)
            _write_parent(w, d, c.parent)
            w.u8(int(c.side))
            w.str_(c.content)
        else:
            w.u8(K_INSERT_VALUES)
            _write_parent(w, d, c.parent)
            w.u8(int(c.side))
            w.varint(len(c.content))
            for v in c.content:
                _write_value(w, d, v)
    elif isinstance(c, SeqDelete):
        w.u8(K_DELETE)
        w.varint(len(c.spans))
        for s in c.spans:
            w.varint(d.peer(s.peer))
            w.zigzag(s.start)
            w.varint(s.end - s.start)
    elif isinstance(c, TreeMove):
        w.u8(K_TREE)
        w.varint(d.peer(c.target.peer))
        w.zigzag(c.target.counter)
        flags = (1 if c.is_create else 0) | (2 if c.is_delete else 0) | (4 if c.parent is not None else 0) | (
            8 if c.position is not None else 0
        )
        w.u8(flags)
        if c.parent is not None:
            w.varint(d.peer(c.parent.peer))
            w.zigzag(c.parent.counter)
        if c.position is not None:
            w.bytes_(c.position)
    elif isinstance(c, CounterIncr):
        w.u8(K_COUNTER)
        w.f64(c.delta)
    elif isinstance(c, MovableSet):
        w.u8(K_MSET)
        w.varint(d.peer(c.elem.peer))
        w.zigzag(c.elem.counter)
        _write_value(w, d, c.value)
    elif isinstance(c, MovableMove):
        w.u8(K_MMOVE)
        w.varint(d.peer(c.elem.peer))
        w.zigzag(c.elem.counter)
        _write_parent(w, d, c.parent)
        w.u8(int(c.side))
    elif isinstance(c, UnknownContent):
        w.u8(K_UNKNOWN)
        w.varint(c.kind)
        w.bytes_(c.data)
    else:  # pragma: no cover
        raise TypeError(f"cannot encode op content {type(c)}")


def _write_parent(w: Writer, d: _Dicts, parent) -> None:
    from ..oplog.oplog import _RunCont

    if parent is None:
        w.u8(0)
    elif isinstance(parent, _RunCont):
        w.u8(RUN_CONT_TAG)
    else:
        w.u8(1)
        w.varint(d.peer(parent.peer))
        w.zigzag(parent.counter)


def _read_parent(r: Reader, peers: List[int]):
    from ..oplog.oplog import _RUN_CONT

    t = r.u8()
    if t == 0:
        return None
    if t == RUN_CONT_TAG:
        return _RUN_CONT
    return ID(peers[r.varint()], r.zigzag())


def read_tables(buf: bytes):
    """Parse just the payload prelude dictionaries.  Returns
    (peers, keys, cids, reader-positioned-after-tables) — the single
    place that knows the header layout besides encode_changes.
    Truncated/corrupt preludes raise a typed CodecDecodeError (a
    ValueError subclass, so every per-payload ``except ValueError``
    fallback path catches it)."""
    from ..errors import CodecDecodeError

    r = Reader(buf)
    try:
        peers = [r.u64le() for _ in range(r.varint())]
        keys = [r.str_() for _ in range(r.varint())]
        cids = [_read_cid(r, peers) for _ in range(r.varint())]
    except CodecDecodeError:
        raise
    except (IndexError, struct.error, UnicodeDecodeError, ValueError,
            OverflowError) as e:
        raise CodecDecodeError(
            f"malformed payload tables ({type(e).__name__}: {e})"
        ) from e
    return peers, keys, cids, r


def decode_changes(buf: bytes) -> List[Change]:
    """Decode a bare (envelope-stripped) updates payload.  Truncated or
    bit-flipped input raises a typed CodecDecodeError (a ValueError and
    DecodeError subclass) — never an untyped IndexError/struct.error
    escaping from the Reader."""
    from ..errors import CodecDecodeError

    try:
        return _decode_changes_inner(buf)
    except CodecDecodeError:
        raise
    except (IndexError, struct.error, UnicodeDecodeError, ValueError,
            KeyError, OverflowError) as e:
        raise CodecDecodeError(
            f"malformed updates payload ({type(e).__name__}: {e})"
        ) from e


def _decode_changes_inner(buf: bytes) -> List[Change]:
    peers, keys, cids, r = read_tables(buf)
    n_changes = r.varint()
    metas = []
    prev_ts = 0
    for _ in range(n_changes):
        peer = peers[r.varint()]
        ctr = r.zigzag()
        lamport = r.zigzag()
        ts = prev_ts + r.zigzag()
        prev_ts = ts
        deps = Frontiers(ID(peers[r.varint()], r.zigzag()) for _ in range(r.varint()))
        msg = r.str_() if r.u8() else None
        n_ops = r.varint()
        metas.append((peer, ctr, lamport, ts, deps, msg, n_ops))
    out: List[Change] = []
    for peer, ctr, lamport, ts, deps, msg, n_ops in metas:
        ops: List[Op] = []
        counter = ctr
        for _ in range(n_ops):
            op = _read_op(r, peers, keys, cids, counter)
            ops.append(op)
            counter = op.ctr_end
        out.append(Change(ID(peer, ctr), lamport, deps, ops, ts, msg))
    return out


def _read_op(r: Reader, peers, keys, cids, counter: int) -> Op:
    cid = cids[r.varint()]
    kind = r.u8()
    if kind == K_MAP_SET:
        content: Any = MapSet(keys[r.varint()], _read_value(r, cids))
    elif kind == K_MAP_DEL:
        content = MapSet(keys[r.varint()], None, True)
    elif kind == K_INSERT_TEXT:
        parent = _read_parent(r, peers)
        side = Side(r.u8())
        content = SeqInsert(parent, side, r.str_())
    elif kind == K_INSERT_VALUES:
        parent = _read_parent(r, peers)
        side = Side(r.u8())
        content = SeqInsert(parent, side, tuple(_read_value(r, cids) for _ in range(r.varint())))
    elif kind == K_INSERT_ANCHOR:
        parent = _read_parent(r, peers)
        side = Side(r.u8())
        key = keys[r.varint()]
        value = _read_value(r, cids)
        is_start = bool(r.u8())
        info = r.varint()
        content = SeqInsert(parent, side, StyleAnchor(key, value, is_start, info))
    elif kind == K_DELETE:
        spans = []
        for _ in range(r.varint()):
            p = peers[r.varint()]
            s = r.zigzag()
            ln = r.varint()
            spans.append(IdSpan(p, s, s + ln))
        content = SeqDelete(tuple(spans))
    elif kind == K_TREE:
        target = TreeID(peers[r.varint()], r.zigzag())
        flags = r.u8()
        parent_t = TreeID(peers[r.varint()], r.zigzag()) if flags & 4 else None
        position = r.bytes_() if flags & 8 else None
        content = TreeMove(target, parent_t, position, bool(flags & 1), bool(flags & 2))
    elif kind == K_COUNTER:
        content = CounterIncr(r.f64())
    elif kind == K_MSET:
        content = MovableSet(ID(peers[r.varint()], r.zigzag()), _read_value(r, cids))
    elif kind == K_MMOVE:
        elem = ID(peers[r.varint()], r.zigzag())
        parent = _read_parent(r, peers)
        content = MovableMove(elem, parent, Side(r.u8()))
    elif kind == K_UNKNOWN:
        content = UnknownContent(r.varint(), r.bytes_())
    else:
        raise ValueError(f"bad op kind {kind}")
    return Op(counter, cid, content)
