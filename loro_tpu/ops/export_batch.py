"""Batched delta-export selection: the device half of the read plane.

The sync pull path serves "updates since the client frontier" — the
eg-walker retreat/advance reconstruction (PAPERS.md) — and the repo's
write path already batches, pipelines and tiers ingest across the doc
axis.  This module gives READS the same shape: one vmapped launch
answers a whole window of ``(doc, frontier)`` pull requests at once.

The device holds a **change-span index**: one row per stored change,
in oracle import order, carrying exactly the columns row selection
needs — the u64 peer halves (the ``SeqColumnsU`` convention: sibling
and export order never needs a batch-wide peer dictionary), the
counter span ``[ctr_start, ctr_end)`` and the lamport stamp.  A pull
request is a frontier table; the kernel computes, per request,

- the beyond-frontier mask: a row is selected iff ``ctr_end >
  frontier[peer]`` (absent peers read 0 — exactly
  ``OpLog.changes_since``'s per-peer trim bound);
- the oracle's export order ``(lamport, peer, ctr_start)`` — with the
  straddle correction: a change half-known to the client exports
  TRIMMED, so its sort key uses ``lamport + (frontier_ctr -
  ctr_start)`` and ``max(ctr_start, frontier_ctr)``, matching the
  ``trim_known_prefix`` rewrite byte-for-byte;
- the compact gather: selected row indices first, in export order.

Framing back into the columnar-updates envelope stays on the host
(``sync/readbatch.py``): the index keeps the stored ``Change`` objects
per doc, and the wire bytes carry values/deps/timestamps the device
columns never see.  The host-side contract that makes the bytes
identical: ``note_changes`` applies the same known-prefix trim the
oracle's ``plan_import`` applies, so index rows ARE the oracle's
stored changes.

Shapes bucket-pad (``pad_bucket``) on all three axes — row capacity,
requests per window, frontier width — so the jit cache stays a handful
of entries however traffic fluctuates.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.version import VersionVector
from ..obs import metrics as obs
from .fugue_batch import pad_bucket

_U32 = np.uint32
_MASK32 = (1 << 32) - 1


def _split_peer(peer: int) -> Tuple[int, int]:
    return (peer >> 32) & _MASK32, peer & _MASK32


def _pad_request_shapes(n_req: int, n_peers: int) -> Tuple[int, int]:
    """Request-table bucketing shared by ``select`` and ``warm`` — one
    place, so warmed shapes can never drift from the shapes real
    windows launch."""
    return (pad_bucket(max(1, int(n_req)), floor=8),
            pad_bucket(max(1, int(n_peers)), floor=4))


def _scatter_rows(dev_cols, host_cols, idx):
    """The dirty-doc delta upload shared by ``_device_cols`` and
    ``warm``: one functional scatter per column, returning the new
    device tuple."""
    return tuple(
        dev.at[idx].set(host[idx]) for dev, host in zip(dev_cols, host_cols)
    )


def _select_fn():
    """Build (once) the jitted batched selection kernel."""
    import jax
    import jax.numpy as jnp

    def one(doc, f_hi, f_lo, f_ctr, f_n, hi, lo, cs, ce, lam, n_rows):
        # gather this request's doc rows (the only cross-request axis)
        dhi, dlo = hi[doc], lo[doc]
        dcs, dce, dlam = cs[doc], ce[doc], lam[doc]
        n = n_rows[doc]
        cap = dhi.shape[0]
        rows = jnp.arange(cap, dtype=jnp.int32)
        valid = rows < n
        # frontier counter per row: match the row peer over the
        # request's (padded) frontier table; absent peers read 0
        fi = jnp.arange(f_hi.shape[0], dtype=jnp.int32)
        m = (
            (dhi[:, None] == f_hi[None, :])
            & (dlo[:, None] == f_lo[None, :])
            & (fi[None, :] < f_n)
        )
        fctr = jnp.max(jnp.where(m, f_ctr[None, :], 0), axis=1)
        sel = valid & (dce > fctr)
        # straddle-corrected export keys (trim_known_prefix semantics)
        off = jnp.maximum(0, fctr - dcs)
        eff_lam = dlam + off
        eff_cs = jnp.maximum(dcs, fctr)
        unsel = (~sel).astype(jnp.int32)  # selected rows sort first
        srt = jax.lax.sort(
            (unsel, eff_lam, dhi, dlo, eff_cs, rows), num_keys=5
        )
        return srt[-1], jnp.sum(sel.astype(jnp.int32))

    batched = jax.vmap(one, in_axes=(0, 0, 0, 0, 0) + (None,) * 6)
    return jax.jit(batched)


_SELECT = None


def select_since_batch(doc, f_hi, f_lo, f_ctr, f_n,
                       hi, lo, cs, ce, lam, n_rows):
    """One launch: per-request beyond-frontier row selection in export
    order.  Returns ``(order i32[R, cap], count i32[R])`` — the first
    ``count[r]`` entries of ``order[r]`` are the selected row indices
    of doc ``doc[r]`` in oracle export order."""
    global _SELECT
    if _SELECT is None:
        _SELECT = _select_fn()
    return _SELECT(doc, f_hi, f_lo, f_ctr, f_n, hi, lo, cs, ce, lam, n_rows)


class ExportIndex:
    """Host-fed, device-resident change-span index over one doc fleet.

    Feed (``note_changes``) mirrors the oracle's import rule exactly:
    fully-known spans drop, straddles trim (``trim_known_prefix``), the
    per-doc head VV advances — so row ``i`` of doc ``di`` IS the
    oracle's ``i``-th stored change and the device selection reproduces
    ``OpLog.changes_since`` row-for-row.  The stored ``Change`` objects
    ride along per doc for host framing.

    The device copy syncs lazily: appends land in numpy staging arrays
    and ship right before the next ``select`` launch — the full grid
    on first sync / capacity grow, a dirty-doc scatter delta otherwise
    (the grid re-pads through ``pad_bucket`` so capacity growth costs
    a bounded number of recompiles).  ``floor_vv`` is each doc's index
    birth frontier: a pull whose client frontier does not dominate it
    needs history the index never saw and must stay on the oracle
    path (docs/SYNC.md "Read plane").

    Retention: the index (host ``Change`` lists + device rows) keeps
    every change since its floor; ``prune_below(di, floor_vv)`` drops
    rows fully below a frontier every connected session already holds
    and advances ``floor_vvs`` past them, so frontiers under the new
    floor re-route to the oracle through the existing ``covers`` path
    (the SyncServer wires it to ``compact()`` — docs/SYNC.md "Read
    plane").  Straddling rows stay: a client at the floor may still
    need their trimmed tails.

    Thread contract: the OWNER serializes calls (the read plane takes
    ``sync.readplane`` around every entry); this class has no lock of
    its own.
    """

    def __init__(self, n_docs: int, family: str = "",
                 floor_vvs: Optional[Sequence[VersionVector]] = None,
                 capacity: int = 256):
        self.n_docs = int(n_docs)
        self.family = family
        cap = pad_bucket(max(1, int(capacity)))
        self._cap = cap
        self._hi = np.zeros((n_docs, cap), _U32)
        self._lo = np.zeros((n_docs, cap), _U32)
        self._cs = np.zeros((n_docs, cap), np.int32)
        self._ce = np.zeros((n_docs, cap), np.int32)
        self._lam = np.zeros((n_docs, cap), np.int32)
        self._n = np.zeros((n_docs,), np.int32)
        self.changes: List[List] = [[] for _ in range(n_docs)]
        self.head_vvs: List[VersionVector] = [
            VersionVector() for _ in range(n_docs)
        ]
        self.floor_vvs: List[VersionVector] = [
            (floor_vvs[i].copy() if floor_vvs is not None else VersionVector())
            for i in range(n_docs)
        ]
        for i in range(n_docs):
            self.head_vvs[i].merge(self.floor_vvs[i])
        self._dev = None          # device tuple, or None before first sync
        # docs whose host rows moved past the device copy; None means
        # the whole grid must re-upload (first sync / capacity grow)
        self._dirty_docs: Optional[set] = None
        self.launches = 0         # count guard: one per select() call
        self.warm_launches = 0    # warm() pre-compiles, never windows
        self.rows_fed = 0
        self.rows_pruned = 0

    # -- feed (owner holds the read-plane lock) ------------------------
    def note_changes(self, di: int, chs: Sequence) -> None:
        """Append one committed round's changes for doc ``di`` with the
        oracle's dedup/trim rule.  Known-decodable, gate-passed changes
        only (the sync commit path hands us exactly those)."""
        from ..oplog.oplog import trim_known_prefix

        vv = self.head_vvs[di]
        for ch in chs:
            known = vv.get(ch.peer)
            if ch.ctr_end <= known:
                continue  # fully known: the oracle dropped it too
            if ch.ctr_start < known:
                ch = trim_known_prefix(ch, known)
            self._append_row(di, ch)
            vv.set_end(ch.peer, max(vv.get(ch.peer), ch.ctr_end))

    def _append_row(self, di: int, ch) -> None:
        n = int(self._n[di])
        if n >= self._cap:
            self._grow()
        hi, lo = _split_peer(ch.peer)
        self._hi[di, n] = hi
        self._lo[di, n] = lo
        self._cs[di, n] = ch.ctr_start
        self._ce[di, n] = ch.ctr_end
        self._lam[di, n] = ch.lamport
        self._n[di] = n + 1
        self.changes[di].append(ch)
        if self._dirty_docs is not None:
            self._dirty_docs.add(di)
        self.rows_fed += 1

    def _grow(self) -> None:
        new_cap = pad_bucket(self._cap * 2)
        for name in ("_hi", "_lo", "_cs", "_ce", "_lam"):
            old = getattr(self, name)
            fresh = np.zeros((self.n_docs, new_cap), old.dtype)
            fresh[:, : self._cap] = old
            setattr(self, name, fresh)
        self._cap = new_cap
        self._dev = None
        self._dirty_docs = None  # shape changed: full re-upload

    def head_vv(self, di: int) -> VersionVector:
        return self.head_vvs[di].copy()

    def prune_below(self, di: int, floor_vv: VersionVector) -> int:
        """Drop rows wholly at/under ``floor_vv`` (every connected
        session already holds them) and advance the doc's index floor
        past it: pruned history re-routes to the oracle through
        ``covers`` — never a silently-short delta.  Straddling rows
        survive whole (a client at the floor needs their trimmed
        tails; selection's straddle correction keeps serving them).
        Device rows rewrite via the ordinary dirty-doc scatter; rows
        past the new count stay allocated but masked by ``n_rows``.
        Returns rows pruned."""
        old = self.changes[di]
        keep = [ch for ch in old if ch.ctr_end > floor_vv.get(ch.peer)]
        pruned = len(old) - len(keep)
        if pruned == 0:
            return 0
        self.changes[di] = keep
        for j, ch in enumerate(keep):
            hi, lo = _split_peer(ch.peer)
            self._hi[di, j] = hi
            self._lo[di, j] = lo
            self._cs[di, j] = ch.ctr_start
            self._ce[di, j] = ch.ctr_end
            self._lam[di, j] = ch.lamport
        self._n[di] = len(keep)
        # floor advances by REFERENCE SWAP, never in-place merge:
        # ``covers`` reads the floor lock-free under the server lock
        # while pruning holds only the plane lock — a reader must see
        # a complete old or complete new floor, never a half-merged VV
        # (and never a dict mutating under its iteration)
        new_floor = self.floor_vvs[di].copy()
        new_floor.merge(floor_vv)
        self.floor_vvs[di] = new_floor
        if self._dirty_docs is not None:
            self._dirty_docs.add(di)
        self.rows_pruned += pruned
        obs.counter(
            "readbatch.index_rows_pruned_total",
            "change-span index rows dropped below the session ack "
            "floors at compaction",
        ).inc(pruned, family=self.family)
        return pruned

    def covers(self, di: int, from_vv: VersionVector) -> bool:
        """Whether a pull from ``from_vv`` is servable off the index:
        the client must already hold everything below the index floor
        (else the delta needs pre-index history only the oracle has)."""
        return self.floor_vvs[di] <= from_vv

    # -- device sync + selection ---------------------------------------
    def _device_cols(self):
        """Lazy device sync.  First sync (and every capacity grow)
        uploads the whole grid; steady-state commits re-ship only the
        DIRTY doc rows as one scatter-update per column — a window
        after K docs committed pays O(K x cap), not O(n_docs x cap)
        (the full grid would be a whole-HBM transfer per read window
        on a real chip)."""
        import jax.numpy as jnp

        if self._dev is not None and not self._dirty_docs:
            return self._dev
        if self._dev is None or self._dirty_docs is None:
            self._dev = tuple(
                jnp.asarray(a)
                for a in (self._hi, self._lo, self._cs, self._ce, self._lam,
                          self._n)
            )
            kind = "full"
        else:
            docs = sorted(self._dirty_docs)
            # pad the dirty-doc list (repeat the first index — the
            # scatter is idempotent) so the update shapes bucket
            idx = np.asarray(docs, np.int32)
            pad = pad_bucket(len(docs), floor=8)
            idx = np.concatenate([idx, np.full(pad - len(docs), idx[0],
                                               np.int32)])
            hosts = (self._hi, self._lo, self._cs, self._ce, self._lam)
            self._dev = _scatter_rows(self._dev[:5], hosts, idx) + (
                jnp.asarray(self._n),
            )
            kind = "delta"
        self._dirty_docs = set()
        obs.counter(
            "readbatch.index_uploads_total",
            "change-span index uploads to device (full grid or "
            "dirty-doc delta scatter)",
        ).inc(family=self.family, kind=kind)
        return self._dev

    def select(self, requests: Sequence[Tuple[int, VersionVector]]
               ) -> List[np.ndarray]:
        """ONE launch for the whole window: per request ``(di,
        from_vv)``, the beyond-frontier row indices of doc ``di`` in
        oracle export order.  The caller frames them into wire bytes
        host-side."""
        import jax.numpy as jnp

        cols = self._device_cols()
        r_pad, f_pad = _pad_request_shapes(
            len(requests),
            max((len(vv) for _di, vv in requests), default=1),
        )
        doc = np.zeros((r_pad,), np.int32)
        f_hi = np.zeros((r_pad, f_pad), _U32)
        f_lo = np.zeros((r_pad, f_pad), _U32)
        f_ctr = np.zeros((r_pad, f_pad), np.int32)
        f_n = np.zeros((r_pad,), np.int32)
        for r, (di, vv) in enumerate(requests):
            doc[r] = di
            for j, (peer, ctr) in enumerate(vv.items()):
                f_hi[r, j], f_lo[r, j] = _split_peer(peer)
                f_ctr[r, j] = ctr
            f_n[r] = len(vv)
        order, count = select_since_batch(
            jnp.asarray(doc), jnp.asarray(f_hi), jnp.asarray(f_lo),
            jnp.asarray(f_ctr), jnp.asarray(f_n), *cols,
        )
        self.launches += 1
        obs.counter(
            "readbatch.export_launches_total",
            "batched delta-export selection launches (one per window)",
        ).inc(family=self.family)
        order = np.asarray(order)  # fetch drains the launch
        count = np.asarray(count)
        return [
            order[r, : int(count[r])] for r in range(len(requests))
        ]

    def warm(self, max_requests: int, max_peers: int = 4) -> int:
        """Pre-compile the selection kernel over the request-bucket
        ladder up to ``pad_bucket(max_requests)`` (frontier width
        bucketed from ``max_peers`` — pass the widest per-doc writer
        count expected, or wider frontier buckets still compile on
        first use) at the CURRENT row capacity.  The kernel jit-caches
        per (requests, frontier-width, capacity) bucket, so without
        this the first window at each fresh bucket pays the XLA
        compile INSIDE a session's pull latency — a p99 spike (the top
        rung takes ~20 s to compile for a v5e).  Also pre-compiles
        the dirty-doc scatter delta (``_device_cols``) over its own
        idx-bucket ladder — on the CPU mesh the scatter's first
        compile dominates the first post-commit window, not the
        selection kernel.

        Every warm launch runs against throwaway all-zero tables and
        columns of the LIVE shapes (the jit cache keys on shape +
        dtype, and ``_pad_request_shapes`` / ``_scatter_rows`` are the
        same code real windows run): no index or device state is read
        or written, so the owner may call this WITHOUT holding the
        read-plane lock across the multi-hundred-ms compiles — serving
        never stalls behind a warm.  Counted separately
        (``warm_launches``): warm launches are not windows, so the
        launches <= windows count guard stays exact.  Capacity is
        sampled once at entry; a concurrent grow (or a later one)
        re-pads the row axis and re-compiles once per bucket — re-warm
        after a known bulk load if first-window latency matters."""
        import jax.numpy as jnp

        n_docs, cap = self.n_docs, self._cap
        dtypes = (_U32, _U32, np.int32, np.int32, np.int32)
        dev = tuple(jnp.zeros((n_docs, cap), d) for d in dtypes)
        cols = dev + (jnp.zeros((n_docs,), np.int32),)
        target, f_pad = _pad_request_shapes(max_requests, max_peers)
        done = 0
        r = 8
        while r <= target:
            doc = jnp.zeros((r,), jnp.int32)
            f_hi = jnp.zeros((r, f_pad), jnp.uint32)
            f_lo = jnp.zeros((r, f_pad), jnp.uint32)
            f_ctr = jnp.zeros((r, f_pad), jnp.int32)
            f_n = jnp.zeros((r,), jnp.int32)
            _order, count = select_since_batch(
                doc, f_hi, f_lo, f_ctr, f_n, *cols
            )
            np.asarray(count)  # fetch drains the compile + launch
            done += 1
            r *= 2
        hosts = tuple(np.zeros((n_docs, cap), d) for d in dtypes)
        k = 8
        kmax = pad_bucket(n_docs, floor=8)
        while k <= kmax:
            idx = np.zeros((k,), np.int32)
            scat = _scatter_rows(dev, hosts, idx)
            np.asarray(scat[0])  # fetch drains the compile + launch
            done += 1
            k *= 2
        self.warm_launches += done
        return done

    def report(self) -> Dict[str, int]:
        return {
            "rows": int(self._n.sum()),
            "capacity": self._cap,
            "launches": self.launches,
            "warm_launches": self.warm_launches,
            "rows_fed": self.rows_fed,
            "rows_pruned": self.rows_pruned,
        }
