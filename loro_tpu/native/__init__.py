"""ctypes binding + on-demand build of the native wire->SoA decoder.

Builds codec.cpp with g++ on first use, cached next to the source as
``codec.<hash>.so`` where the hash covers the source and the compiler
flags: a binary built from other source — one copied along with a
tree, say — has another name and is never loaded.  Library users fall
back gracefully: every caller handles ``available() == False``
(pure-Python paths exist for everything — the native decoder is the
throughput path for fleet decode, reference-parity with loro's Rust
block decode).  Measurement paths (benchmarks/, chip_smoke.py) call
``require()`` instead, which makes a failed build an error.

Beside the explode entries (wire bytes -> columns, one a container
family) and the order / id-map engines, two entries take the text
paths' columns the rest of the way to the upload: ``contract_chains``
(element table -> right-spine chains) and ``pack_chain_row`` (chains +
element columns -> one padded u8 row of the packed transport).  Like
every entry they run with the interpreter lock released, tick
``codec.native_*_calls_total{fn}``, and have a numpy twin
(ops/columnar.py) that answers without the library.
"""
from __future__ import annotations

import ctypes
import functools
import glob
import hashlib
import os
import subprocess
import threading
import time
from typing import Optional

import numpy as np

from ..errors import CodecDecodeError, LoroError
from ..obs import metrics as _obs
from ..resilience import faultinject as _fi
from ..utils import tracing as _tracing

_fi.register_site(
    "decode", "native explode entries: truncate/bit-flip the wire bytes "
    "before the C++ parser sees them (typed CodecDecodeError -> the "
    "caller's Python-decoder fallback)")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "codec.cpp")
_CXX = ("g++", "-O3", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False
_build_error = ""  # why the last build/load failed (require() reports it)


def _so_path() -> str:
    """``codec.<hash>.so``: the hash is over codec.cpp and the compiler
    command, so the name changes whenever either does."""
    h = hashlib.blake2b(" ".join(_CXX).encode(), digest_size=8)
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"codec.{h.hexdigest()}.so")


def _build(so: str) -> bool:
    global _build_error
    tmp = f"{so}.{os.getpid()}.tmp"  # per-process: concurrent builds don't race
    _obs.counter("codec.native_build_total").inc()
    try:
        subprocess.run(
            [*_CXX, "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
    except (subprocess.SubprocessError, OSError) as e:
        _obs.counter("codec.native_build_failed_total").inc()
        stderr = getattr(e, "stderr", None) or b""
        _build_error = f"{type(e).__name__}: {e} {stderr.decode(errors='replace')[-2000:]}"
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    # binaries of older source: nothing loads them any more
    for old in glob.glob(os.path.join(_DIR, "codec*.so")):
        if old != so:
            try:
                os.unlink(old)
            except OSError:
                pass
    return True


def _obs_decode(fn: str, payload: bytes) -> bytes:
    """Per-call decode accounting (docs/OBSERVABILITY.md): which native
    explode entry ran and how many wire bytes it chewed.  Also the
    fault-injection choke point: an armed ``decode`` fault truncates or
    bit-flips the payload here, before the C++ parser sees it — the
    parser must answer with a typed CodecDecodeError, never a crash."""
    payload = _fi.mangle("decode", payload)
    _obs.counter("codec.native_decode_calls_total").inc(fn=fn)
    _obs.counter("codec.native_decode_bytes_total").inc(len(payload), fn=fn)
    return payload


def _decode_ns(fn: str):
    """Decorator of an explode entry: the wall time of every call that
    decoded (the library there, no error) into
    ``codec.native_decode_ns_total{fn}``, beside ``_obs_decode``'s calls
    and bytes — a run, traced or not, says ns a byte, and what of a span
    around the entry is the C++ decode."""

    def wrap(entry):
        @functools.wraps(entry)
        def timed(*args, **kw):
            t0 = time.perf_counter_ns()
            out = entry(*args, **kw)
            if out is not None:
                _obs.counter("codec.native_decode_ns_total").inc(
                    time.perf_counter_ns() - t0, fn=fn)
            return out

        return timed

    return wrap


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed, _build_error
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            _build_failed = True
            _build_error = f"{type(e).__name__}: {e}"
            return None
        lib.loro_count_seq_elements.restype = ctypes.c_longlong
        lib.loro_count_seq_elements.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ]
        lib.loro_set_rowtable_budget.restype = None
        lib.loro_set_rowtable_budget.argtypes = [ctypes.c_longlong]
        lib.loro_explode_seq.restype = ctypes.c_longlong
        lib.loro_explode_seq.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 6 + [ctypes.c_longlong]
        lib.loro_count_seq_deletes.restype = ctypes.c_longlong
        lib.loro_count_seq_deletes.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ]
        lib.loro_count_seq_delta_rows.restype = ctypes.c_longlong
        lib.loro_count_seq_delta_rows.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ]
        lib.loro_explode_seq_delta.restype = ctypes.c_longlong
        lib.loro_explode_seq_delta.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 7 + [ctypes.c_longlong] + [ctypes.c_void_p] * 3 + [
            ctypes.c_longlong,
            ctypes.c_void_p,
        ]
        lib.loro_explode_seq_anchor_meta.restype = ctypes.c_longlong
        lib.loro_explode_seq_anchor_meta.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 5 + [ctypes.c_longlong]
        lib.loro_count_map_ops.restype = ctypes.c_longlong
        lib.loro_count_map_ops.argtypes = [ctypes.c_char_p, ctypes.c_longlong]
        lib.loro_explode_map.restype = ctypes.c_longlong
        lib.loro_explode_map.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
        ] + [ctypes.c_void_p] * 6 + [ctypes.c_longlong]
        lib.loro_count_tree_ops.restype = ctypes.c_longlong
        lib.loro_count_tree_ops.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ]
        lib.loro_explode_tree.restype = ctypes.c_longlong
        lib.loro_explode_tree.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 10 + [ctypes.c_longlong]
        lib.loro_count_movable.restype = ctypes.c_longlong
        lib.loro_count_movable.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 3
        lib.loro_explode_movable.restype = ctypes.c_longlong
        lib.loro_explode_movable.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 15 + [ctypes.c_longlong] * 3
        lib.loro_explode_movable_delta.restype = ctypes.c_longlong
        lib.loro_explode_movable_delta.argtypes = [
            ctypes.c_char_p,
            ctypes.c_longlong,
            ctypes.c_int,
        ] + [ctypes.c_void_p] * 15 + [ctypes.c_longlong] * 3 + [ctypes.c_void_p] * 2
        lib.loro_contract_chains.restype = ctypes.c_longlong
        lib.loro_contract_chains.argtypes = (
            [ctypes.c_void_p] * 2 + [ctypes.c_longlong] + [ctypes.c_void_p] * 4
        )
        lib.loro_pack_chain_row.restype = ctypes.c_longlong
        lib.loro_pack_chain_row.argtypes = (
            [ctypes.c_void_p] * 4 + [ctypes.c_longlong] + [ctypes.c_void_p] * 4
            + [ctypes.c_longlong] * 3 + [ctypes.c_void_p]
        )
        lib.loro_order_new.restype = ctypes.c_void_p
        lib.loro_order_new.argtypes = []
        lib.loro_order_free.restype = None
        lib.loro_order_free.argtypes = [ctypes.c_void_p]
        lib.loro_order_nrows.restype = ctypes.c_longlong
        lib.loro_order_nrows.argtypes = [ctypes.c_void_p]
        lib.loro_order_renumbers.restype = ctypes.c_longlong
        lib.loro_order_renumbers.argtypes = [ctypes.c_void_p]
        lib.loro_order_all_keys.restype = None
        lib.loro_order_all_keys.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.loro_order_append.restype = ctypes.c_longlong
        lib.loro_order_append.argtypes = [
            ctypes.c_void_p,
            ctypes.c_longlong,
        ] + [ctypes.c_void_p] * 4 + [ctypes.c_longlong, ctypes.c_void_p]
        lib.loro_idmap_new.restype = ctypes.c_void_p
        lib.loro_idmap_new.argtypes = []
        lib.loro_idmap_free.restype = None
        lib.loro_idmap_free.argtypes = [ctypes.c_void_p]
        lib.loro_idmap_len.restype = ctypes.c_longlong
        lib.loro_idmap_len.argtypes = [ctypes.c_void_p]
        lib.loro_idmap_insert.restype = None
        lib.loro_idmap_insert.argtypes = [
            ctypes.c_void_p,
            ctypes.c_longlong,
        ] + [ctypes.c_void_p] * 3
        lib.loro_idmap_stage.restype = None
        lib.loro_idmap_stage.argtypes = [
            ctypes.c_void_p,
            ctypes.c_longlong,
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.loro_idmap_commit.restype = None
        lib.loro_idmap_commit.argtypes = [ctypes.c_void_p]
        lib.loro_idmap_abort.restype = None
        lib.loro_idmap_abort.argtypes = [ctypes.c_void_p]
        lib.loro_idmap_lookup.restype = None
        lib.loro_idmap_lookup.argtypes = [
            ctypes.c_void_p,
            ctypes.c_longlong,
        ] + [ctypes.c_void_p] * 3
        lib.loro_idmap_get.restype = ctypes.c_longlong
        lib.loro_idmap_get.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_longlong,
        ]
        _lib = lib
        return lib


def available() -> bool:
    return _load() is not None


def require() -> None:
    """Raise unless the native decoder is built and loaded.  For paths
    whose numbers or checks are about the native decode (benchmarks/,
    chip_smoke.py): there a Python fallback would be a different
    program, so a failed build is an error and says why."""
    if _load() is None:
        raise LoroError(
            "native decoder unavailable (g++ build or load of "
            f"{os.path.basename(_SRC)} failed): {_build_error}"
        )


@_decode_ns("seq")
def explode_seq_payload(payload: bytes, target_cid_index: int):
    """Parse a binary updates payload and return the element table of
    the target sequence container as numpy columns
    (parent, side, peer_idx, counter, deleted, content) or None if the
    native decoder is unavailable.  Raises ValueError on malformed
    payloads or unresolvable references (caller falls back to Python).
    """
    lib = _load()
    if lib is None:
        return None
    with _tracing.span("native.explode", bytes=len(payload)):
        payload = _obs_decode("seq", payload)
        n = lib.loro_count_seq_elements(payload, len(payload), target_cid_index)
        if n < 0:
            raise CodecDecodeError("native decode failed (malformed payload?)")
        parent = np.empty(n, np.int32)
        side = np.empty(n, np.int32)
        peer = np.empty(n, np.int32)
        counter = np.empty(n, np.int32)
        deleted = np.zeros(n, np.uint8)
        content = np.empty(n, np.int32)
        wrote = lib.loro_explode_seq(
            payload,
            len(payload),
            target_cid_index,
            parent.ctypes.data_as(ctypes.c_void_p),
            side.ctypes.data_as(ctypes.c_void_p),
            peer.ctypes.data_as(ctypes.c_void_p),
            counter.ctypes.data_as(ctypes.c_void_p),
            deleted.ctypes.data_as(ctypes.c_void_p),
            content.ctypes.data_as(ctypes.c_void_p),
            n,
        )
        if wrote != n:
            raise CodecDecodeError("native decode failed (unresolvable refs or count mismatch)")
        return parent, side, peer, counter, deleted.astype(bool), content


@_decode_ns("seq_delta")
def explode_seq_delta_payload(payload: bytes, target_cid_index: int):
    """Incremental decode: element rows whose cross-payload parents come
    back as (peer_idx, counter) for host resolution (out_parent == -2),
    plus raw delete spans.  Returns a dict of numpy arrays or None if
    the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    payload = _obs_decode("seq_delta", payload)
    n = lib.loro_count_seq_delta_rows(payload, len(payload), target_cid_index)
    nd = lib.loro_count_seq_deletes(payload, len(payload), target_cid_index)
    if n < 0 or nd < 0:
        raise CodecDecodeError("native decode failed (malformed payload?)")
    parent = np.empty(n, np.int32)
    side = np.empty(n, np.int32)
    peer = np.empty(n, np.int32)
    counter = np.empty(n, np.int32)
    content = np.empty(n, np.int32)
    ext_peer = np.empty(n, np.int32)
    ext_ctr = np.empty(n, np.int64)
    del_peer = np.empty(nd, np.int32)
    del_start = np.empty(nd, np.int64)
    del_end = np.empty(nd, np.int64)
    n_del_out = ctypes.c_longlong(0)
    wrote = lib.loro_explode_seq_delta(
        payload,
        len(payload),
        target_cid_index,
        parent.ctypes.data_as(ctypes.c_void_p),
        side.ctypes.data_as(ctypes.c_void_p),
        peer.ctypes.data_as(ctypes.c_void_p),
        counter.ctypes.data_as(ctypes.c_void_p),
        content.ctypes.data_as(ctypes.c_void_p),
        ext_peer.ctypes.data_as(ctypes.c_void_p),
        ext_ctr.ctypes.data_as(ctypes.c_void_p),
        n,
        del_peer.ctypes.data_as(ctypes.c_void_p),
        del_start.ctypes.data_as(ctypes.c_void_p),
        del_end.ctypes.data_as(ctypes.c_void_p),
        nd,
        ctypes.byref(n_del_out),
    )
    if wrote != n:
        raise CodecDecodeError("native delta decode failed")
    return {
        "parent": parent,
        "side": side,
        "peer_idx": peer,
        "counter": counter,
        "content": content,
        "ext_peer_idx": ext_peer,
        "ext_counter": ext_ctr,
        "del_peer_idx": del_peer[: n_del_out.value],
        "del_start": del_start[: n_del_out.value],
        "del_end": del_end[: n_del_out.value],
    }


@_decode_ns("seq_anchor")
def explode_seq_anchor_meta(payload: bytes, target_cid_index: int):
    """Style-anchor metadata in the same row numbering as
    explode_seq_delta_payload (host pairs anchors to device rows by the
    `row` ordinal).  Values stay encoded — `voffset` feeds
    decode_value_at.  Returns a dict of numpy columns or None when the
    native library is unavailable; raises ValueError on malformed
    payloads."""
    lib = _load()
    if lib is None:
        return None
    payload = _obs_decode("seq_anchor", payload)
    n = lib.loro_explode_seq_anchor_meta(
        payload, len(payload), target_cid_index, None, None, None, None, None, 0
    )
    if n < 0:
        raise CodecDecodeError("native anchor decode failed (malformed payload?)")
    row = np.empty(n, np.int64)
    key = np.empty(n, np.int32)
    voff = np.empty(n, np.int64)
    lam = np.empty(n, np.int32)
    flags = np.empty(n, np.int32)
    wrote = lib.loro_explode_seq_anchor_meta(
        payload,
        len(payload),
        target_cid_index,
        row.ctypes.data_as(ctypes.c_void_p),
        key.ctypes.data_as(ctypes.c_void_p),
        voff.ctypes.data_as(ctypes.c_void_p),
        lam.ctypes.data_as(ctypes.c_void_p),
        flags.ctypes.data_as(ctypes.c_void_p),
        n,
    )
    if wrote != n:
        raise CodecDecodeError("native anchor decode failed")
    return {"row": row, "key_idx": key, "voffset": voff, "lamport": lam, "flags": flags}


@_decode_ns("map")
def explode_map_payload(payload: bytes):
    """All MapSet/MapDel rows of a payload, or None when the native
    library is unavailable.  Returns a dict with numpy columns
    (cid_idx, key_idx, lamport, peer_rank, value_ordinal|-1) and the
    decoding tables (peers sorted-u64, keys, cids).  peer_rank follows
    the sorted-peer ordering the LWW kernels' (lamport, peer) tie-break
    contract requires — NOT wire registration order."""
    lib = _load()
    if lib is None:
        return None
    payload = _obs_decode("map", payload)
    n = lib.loro_count_map_ops(payload, len(payload))
    if n < 0:
        raise CodecDecodeError("native decode failed (malformed payload?)")
    cid = np.empty(n, np.int32)
    key = np.empty(n, np.int32)
    lamport = np.empty(n, np.int32)
    peer = np.empty(n, np.int32)
    value = np.empty(n, np.int32)
    voffset = np.empty(n, np.int64)
    wrote = lib.loro_explode_map(
        payload,
        len(payload),
        cid.ctypes.data_as(ctypes.c_void_p),
        key.ctypes.data_as(ctypes.c_void_p),
        lamport.ctypes.data_as(ctypes.c_void_p),
        peer.ctypes.data_as(ctypes.c_void_p),
        value.ctypes.data_as(ctypes.c_void_p),
        voffset.ctypes.data_as(ctypes.c_void_p),
        n,
    )
    if wrote != n:
        raise CodecDecodeError("native decode failed (count mismatch)")
    # wire peer table is registration-ordered; remap to sorted ranks
    # (same contract handling as extract_seq_from_payload).  read_tables
    # raises a typed CodecDecodeError itself on truncated preludes.
    from ..codec.binary import read_tables

    peers_wire, keys, cids, _r = read_tables(payload)
    order = np.argsort(np.asarray(peers_wire, np.uint64), kind="stable")
    rank_of = np.empty(len(peers_wire), np.int32)
    rank_of[order] = np.arange(len(peers_wire), dtype=np.int32)
    peer_rank = rank_of[peer] if len(peers_wire) else peer
    return {
        "cid_idx": cid,
        "key_idx": key,
        "lamport": lamport,
        "peer_rank": peer_rank.astype(np.int32),
        "peer_u64": np.asarray([peers_wire[i] for i in peer], dtype=object),
        "value_ordinal": value,
        "value_offset": voffset,  # byte offset into the payload (-1 = delete)
        "peers": sorted(peers_wire),
        "keys": keys,
        "cids": cids,
    }


def decode_value_at(payload: bytes, offset: int, cids):
    """Decode one tagged value at a native-reported byte offset (lazy
    winner-only decoding for DeviceMapBatch)."""
    from ..codec.binary import Reader, _read_value

    r = Reader(payload)
    r.i = offset
    return _read_value(r, cids)


@_decode_ns("tree")
def explode_tree_payload(payload: bytes, target_cid_index: int):
    """All TreeMove rows of one container (wire order) as numpy
    columns, or None when the native library is unavailable.  Peer
    columns are WIRE indexes; positions are (offset, len) into the
    payload."""
    lib = _load()
    if lib is None:
        return None
    with _tracing.span("native.explode_tree", bytes=len(payload)):
        payload = _obs_decode("tree", payload)
        n = lib.loro_count_tree_ops(payload, len(payload), target_cid_index)
        if n < 0:
            raise CodecDecodeError("native decode failed (malformed payload?)")
        cols = {
            "lamport": np.empty(n, np.int32),
            "peer_idx": np.empty(n, np.int32),
            "counter": np.empty(n, np.int32),
            "target_peer_idx": np.empty(n, np.int32),
            "target_ctr": np.empty(n, np.int32),
            "flags": np.empty(n, np.int32),
            "parent_peer_idx": np.empty(n, np.int32),
            "parent_ctr": np.empty(n, np.int32),
            "pos_off": np.empty(n, np.int64),
            "pos_len": np.empty(n, np.int32),
        }
        wrote = lib.loro_explode_tree(
            payload,
            len(payload),
            target_cid_index,
            *[a.ctypes.data_as(ctypes.c_void_p) for a in cols.values()],
            n,
        )
        if wrote != n:
            raise CodecDecodeError("native decode failed (count mismatch)")
        return cols


@_decode_ns("movable")
def explode_movable_payload(payload: bytes, target_cid_index: int):
    """Slots / sets / delete spans of one MovableList container, or
    None when unavailable.  Raises ValueError on malformed input or
    out-of-payload references (caller falls back to Python).  Value
    columns carry byte offsets; winners decode lazily."""
    lib = _load()
    if lib is None:
        return None
    payload = _obs_decode("movable", payload)
    n_slots = ctypes.c_longlong()
    n_sets = ctypes.c_longlong()
    n_dels = ctypes.c_longlong()
    rc = lib.loro_count_movable(
        payload,
        len(payload),
        target_cid_index,
        ctypes.byref(n_slots),
        ctypes.byref(n_sets),
        ctypes.byref(n_dels),
    )
    if rc < 0:
        raise CodecDecodeError("native decode failed (malformed payload?)")
    ns, nv, nd = n_slots.value, n_sets.value, n_dels.value
    slots = {
        "parent": np.empty(ns, np.int32),
        "side": np.empty(ns, np.int32),
        "peer_idx": np.empty(ns, np.int32),
        "counter": np.empty(ns, np.int32),
        "lamport": np.empty(ns, np.int32),
        "elem_peer_idx": np.empty(ns, np.int32),
        "elem_ctr": np.empty(ns, np.int32),
    }
    sets = {
        "elem_peer_idx": np.empty(nv, np.int32),
        "elem_ctr": np.empty(nv, np.int32),
        "lamport": np.empty(nv, np.int32),
        "peer_idx": np.empty(nv, np.int32),
        "value_off": np.empty(nv, np.int64),
    }
    dels = {
        "peer_idx": np.empty(nd, np.int32),
        "start": np.empty(nd, np.int64),
        "end": np.empty(nd, np.int64),
    }
    wrote = lib.loro_explode_movable(
        payload,
        len(payload),
        target_cid_index,
        *[a.ctypes.data_as(ctypes.c_void_p) for a in slots.values()],
        *[a.ctypes.data_as(ctypes.c_void_p) for a in sets.values()],
        *[a.ctypes.data_as(ctypes.c_void_p) for a in dels.values()],
        ns,
        nv,
        nd,
    )
    if wrote != ns:
        raise CodecDecodeError("native decode failed (unresolvable refs or count mismatch)")
    return {"slots": slots, "sets": sets, "dels": dels}


@_decode_ns("movable_delta")
def explode_movable_delta_payload(payload: bytes, target_cid_index: int):
    """Delta variant of explode_movable_payload: slot parents that don't
    resolve inside the payload come back as parent == -2 with
    (ext_peer_idx, ext_counter) pairs for host resolution against the
    resident batch's id map (DeviceMovableBatch.append_payloads)."""
    lib = _load()
    if lib is None:
        return None
    payload = _obs_decode("movable_delta", payload)
    n_slots = ctypes.c_longlong()
    n_sets = ctypes.c_longlong()
    n_dels = ctypes.c_longlong()
    rc = lib.loro_count_movable(
        payload,
        len(payload),
        target_cid_index,
        ctypes.byref(n_slots),
        ctypes.byref(n_sets),
        ctypes.byref(n_dels),
    )
    if rc < 0:
        raise CodecDecodeError("native decode failed (malformed payload?)")
    ns, nv, nd = n_slots.value, n_sets.value, n_dels.value
    slots = {
        "parent": np.empty(ns, np.int32),
        "side": np.empty(ns, np.int32),
        "peer_idx": np.empty(ns, np.int32),
        "counter": np.empty(ns, np.int32),
        "lamport": np.empty(ns, np.int32),
        "elem_peer_idx": np.empty(ns, np.int32),
        "elem_ctr": np.empty(ns, np.int32),
    }
    sets = {
        "elem_peer_idx": np.empty(nv, np.int32),
        "elem_ctr": np.empty(nv, np.int32),
        "lamport": np.empty(nv, np.int32),
        "peer_idx": np.empty(nv, np.int32),
        "value_off": np.empty(nv, np.int64),
    }
    dels = {
        "peer_idx": np.empty(nd, np.int32),
        "start": np.empty(nd, np.int64),
        "end": np.empty(nd, np.int64),
    }
    ext_peer = np.empty(ns, np.int32)
    ext_ctr = np.empty(ns, np.int64)
    wrote = lib.loro_explode_movable_delta(
        payload,
        len(payload),
        target_cid_index,
        *[a.ctypes.data_as(ctypes.c_void_p) for a in slots.values()],
        *[a.ctypes.data_as(ctypes.c_void_p) for a in sets.values()],
        *[a.ctypes.data_as(ctypes.c_void_p) for a in dels.values()],
        ns,
        nv,
        nd,
        ext_peer.ctypes.data_as(ctypes.c_void_p),
        ext_ctr.ctypes.data_as(ctypes.c_void_p),
    )
    if wrote != ns:
        raise CodecDecodeError("native delta decode failed")
    slots["ext_peer_idx"] = ext_peer
    slots["ext_counter"] = ext_ctr
    return {"slots": slots, "sets": sets, "dels": dels}


def _ptr(a: np.ndarray, dtype) -> tuple:
    """``(array, pointer)`` of ``a`` as a C-contiguous ``dtype`` array (a
    copy only where ``a`` is not one already; a bool column is its own
    bytes).  The array keeps the pointer alive."""
    if dtype is np.uint8 and a.dtype == np.bool_:
        a = a.view(np.uint8)
    a = np.ascontiguousarray(a, dtype)
    return a, a.ctypes.data_as(ctypes.c_void_p)


def contract_chains(parent, side):
    """Right-spine chain contraction of one element table (the rule of
    ``ops/columnar.contract_chains``, whose numpy body is the reference):
    ``(chain_id i32[N], head_row i32[C], c_parent i32[C], c_side i32[C])``,
    or None when the native library is unavailable or a parent lies past
    the table.  One call, the interpreter lock released for all of it."""
    lib = _load()
    if lib is None:
        return None
    _obs.counter("codec.native_chain_calls_total").inc(fn="contract")
    parent, p_parent = _ptr(parent, np.int32)
    side, p_side = _ptr(side, np.int32)
    n = parent.shape[0]
    if side.shape != (n,):
        raise ValueError("parent and side of unequal lengths")
    chain_id = np.empty(n, np.int32)
    per_chain = np.empty((3, n), np.int32)  # a chain a row at most
    c = lib.loro_contract_chains(
        p_parent, p_side, n, chain_id.ctypes.data_as(ctypes.c_void_p),
        *[row.ctypes.data_as(ctypes.c_void_p) for row in per_chain],
    )
    if c < 0:
        return None
    head_row, c_parent, c_side = per_chain[:, :c]
    return chain_id, head_row, c_parent, c_side


def pack_chain_row(c_parent, c_side, c_valid, head_row, chain_id, content,
                   deleted, valid, pad_c: int, pad_n: int, out_row) -> bool:
    """Write one document's packed u8 row (layout: ops/fugue_batch.py,
    above ``packed_row_bytes``) into ``out_row``, a C-contiguous
    ``u8[8 * (pad_c + pad_n)]``, from its UNPADDED chain columns (``C``
    entries) and element columns (``N`` entries), the pads filled as
    ``chain_columns`` fills them.  False when the native library is
    unavailable (nothing written); ValueError where the document does
    not fit its pads.  One call, the interpreter lock released."""
    lib = _load()
    if lib is None:
        return False
    n_chains, n = c_parent.shape[0], chain_id.shape[0]
    if not (out_row.dtype == np.uint8 and out_row.flags.c_contiguous
            and out_row.shape == (8 * (pad_c + pad_n),)):
        raise ValueError(f"out_row is not a contiguous u8[{8 * (pad_c + pad_n)}]")
    if not (c_side.shape[0] == c_valid.shape[0] == head_row.shape[0] == n_chains
            and content.shape[0] == deleted.shape[0] == valid.shape[0] == n):
        raise ValueError("chain columns or element columns of unequal lengths")
    _obs.counter("codec.native_chain_calls_total").inc(fn="pack")
    per_chain = [_ptr(c_parent, np.int32), _ptr(c_side, np.int32),
                 _ptr(c_valid, np.uint8), _ptr(head_row, np.int32)]
    per_row = [_ptr(chain_id, np.int32), _ptr(content, np.int32),
               _ptr(deleted, np.uint8), _ptr(valid, np.uint8)]
    rc = lib.loro_pack_chain_row(
        *[p for _a, p in per_chain], n_chains, *[p for _a, p in per_row], n,
        pad_c, pad_n, out_row.ctypes.data_as(ctypes.c_void_p),
    )
    if rc < 0:
        raise ValueError(
            f"a document of {n_chains} chains and {n} elements does not fit "
            f"pads ({pad_c}, {pad_n})")
    return True


class NativeShadowOrder:
    """C++ twin of parallel.order_maintenance.ShadowOrder (same
    algorithm — keys are bit-identical; the Python engine is the
    differential oracle).  Construct via native_order() which returns
    None when the library is unavailable."""

    __slots__ = ("_lib", "_h")

    def __init__(self, lib):
        self._lib = lib
        self._h = lib.loro_order_new()

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.loro_order_free(h)
            self._h = None

    @property
    def renumbers(self) -> int:
        return int(self._lib.loro_order_renumbers(self._h))

    @property
    def n(self) -> int:
        return int(self._lib.loro_order_nrows(self._h))

    def append_rows(self, rows, base_row: int):
        parent = np.asarray([r[0] for r in rows], np.int32)
        side = np.asarray([r[1] for r in rows], np.int32)
        peer = np.asarray([r[2] for r in rows], np.uint64)
        ctr = np.asarray([r[3] for r in rows], np.int64)
        return self.append_arrays(parent, side, peer, ctr, base_row)

    def append_arrays(self, parent, side, peer, ctr, base_row: int):
        """Columnar append (the hot resident-ingest path — no Python
        tuple round trip).  Same return contract as append_rows."""
        parent = np.ascontiguousarray(parent, np.int32)
        side = np.ascontiguousarray(side, np.int32)
        peer = np.ascontiguousarray(peer, np.uint64)
        ctr = np.ascontiguousarray(ctr, np.int64)
        out = np.empty(len(parent), np.int64)
        rc = self._lib.loro_order_append(
            self._h,
            len(parent),
            parent.ctypes.data_as(ctypes.c_void_p),
            side.ctypes.data_as(ctypes.c_void_p),
            peer.ctypes.data_as(ctypes.c_void_p),
            ctr.ctypes.data_as(ctypes.c_void_p),
            base_row,
            out.ctypes.data_as(ctypes.c_void_p),
        )
        if rc < 0:
            raise ValueError("native order append: non-contiguous base row")
        if rc == 1:
            return None  # renumbered: caller re-uploads all_keys()
        return out  # int64 ndarray (split_keys consumes it directly)

    def all_keys(self) -> np.ndarray:
        n = self.n
        out = np.empty(n, np.int64)
        self._lib.loro_order_all_keys(self._h, out.ctypes.data_as(ctypes.c_void_p))
        return out


def native_order():
    lib = _load()
    if lib is None:
        return None
    return NativeShadowOrder(lib)


class NativeIdMap:
    """C++ (peer, counter) -> device-row map with the staging contract
    the resident batches need (stage / staged-aware lookup / commit |
    abort) plus the dict-like subset the Python fallback paths use.
    Bit-compatible drop-in for the per-doc id2row dicts — the per-row
    Python dict traffic was the r4 host-funnel cost center."""

    __slots__ = ("_lib", "_h", "_staged")

    def __init__(self, lib):
        self._lib = lib
        self._h = lib.loro_idmap_new()
        self._staged = 0  # ids staged since the last commit or abort

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.loro_idmap_free(h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.loro_idmap_len(self._h))

    def __bool__(self) -> bool:
        return len(self) > 0

    # -- dict-like subset (fallback walks, resolve_row) ---------------
    def get(self, key, default=None):
        r = self._lib.loro_idmap_get(
            self._h, ctypes.c_uint64(key[0]), ctypes.c_longlong(key[1])
        )
        return default if r < 0 else int(r)

    def __getitem__(self, key):
        r = self.get(key)
        if r is None:
            raise KeyError(key)
        return r

    def __contains__(self, key) -> bool:
        return self.get(key) is not None

    def update(self, d) -> None:
        """Committed bulk insert from a Python dict (fallback-path
        overlay commits)."""
        if not d:
            return
        n = len(d)
        peer = np.fromiter((k[0] for k in d), np.uint64, n)
        ctr = np.fromiter((k[1] for k in d), np.int64, n)
        rows = np.fromiter(d.values(), np.int32, n)
        self.insert_arrays(peer, ctr, rows)

    # -- columnar hot path --------------------------------------------
    def insert_arrays(self, peer, ctr, rows) -> None:
        peer = np.ascontiguousarray(peer, np.uint64)
        ctr = np.ascontiguousarray(ctr, np.int64)
        rows = np.ascontiguousarray(rows, np.int32)
        self._lib.loro_idmap_insert(
            self._h,
            len(peer),
            peer.ctypes.data_as(ctypes.c_void_p),
            ctr.ctypes.data_as(ctypes.c_void_p),
            rows.ctypes.data_as(ctypes.c_void_p),
        )

    def stage_base(self, peer, ctr, base_row: int) -> None:
        t0 = time.perf_counter_ns()
        peer = np.ascontiguousarray(peer, np.uint64)
        ctr = np.ascontiguousarray(ctr, np.int64)
        self._lib.loro_idmap_stage(
            self._h,
            len(peer),
            peer.ctypes.data_as(ctypes.c_void_p),
            ctr.ctypes.data_as(ctypes.c_void_p),
            base_row,
        )
        self._staged += len(peer)
        _idmap_tick("stage", len(peer), t0)

    def lookup(self, peer, ctr) -> np.ndarray:
        """Staged-first batch lookup; -1 = missing."""
        t0 = time.perf_counter_ns()
        peer = np.ascontiguousarray(peer, np.uint64)
        ctr = np.ascontiguousarray(ctr, np.int64)
        out = np.empty(len(peer), np.int32)
        self._lib.loro_idmap_lookup(
            self._h,
            len(peer),
            peer.ctypes.data_as(ctypes.c_void_p),
            ctr.ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
        )
        _idmap_tick("lookup", len(peer), t0)
        return out

    def commit(self) -> None:
        t0 = time.perf_counter_ns()
        self._lib.loro_idmap_commit(self._h)
        staged, self._staged = self._staged, 0
        _idmap_tick("commit", staged, t0)

    def abort(self) -> None:
        self._lib.loro_idmap_abort(self._h)
        self._staged = 0


def _idmap_tick(op: str, ids: int, t0: int) -> None:
    """The id map's boundary, always on: the ids a batch call took and the
    ns it took them in (``fleet.idmap_ids_total{op}``,
    ``fleet.idmap_ns_total{op}``; docs/OBSERVABILITY.md) — ns an id of
    ``stage`` / ``lookup`` / ``commit``, in a traced run or not, without a
    span under the spans that hold the calls."""
    _obs.counter("fleet.idmap_ns_total").inc(time.perf_counter_ns() - t0, op=op)
    _obs.counter("fleet.idmap_ids_total").inc(ids, op=op)


def native_idmap():
    lib = _load()
    if lib is None:
        return None
    return NativeIdMap(lib)
