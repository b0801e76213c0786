"""Batteries-included resident sync server: one device batch + the
ack bookkeeping that makes its lifecycle (grow/compact) safe to use.

The resident batches expose a precise but easy-to-misuse contract:
``compact(stable_epochs)`` may only receive epochs that EVERY replica
of a doc has acknowledged integrating — passing a too-new epoch can
reclaim a tombstone some replica still references (see
DeviceDocBatch.compact).  This wrapper owns that bookkeeping:

- ``ingest(per_doc_updates)`` feeds a sync round into the batch and
  returns the epoch to hand to clients with the round's fan-out;
- ``ack(di, replica, epoch)`` records a replica's acknowledgment;
- ``compact()`` reclaims with each doc's stability floor =
  min over its registered replicas' acked epochs (docs with no
  registered replicas never compact — safe default);
- ``checkpoint()/restore()`` round-trip batch + acks through LTKV
  bytes, so a restarted server resumes with its compaction floors.

Resilience (docs/RESILIENCE.md): every device append routes through
the DeviceSupervisor; the server auto-checkpoints before its first
risky (first-compile) launch; a data error in one round isolates to
the offending doc (host-decode fallback, then poison-skip with a
typed record); a supervisor-declared DeviceFailure transparently
degrades the epoch to the host ``models/`` engine (byte-identical by
the differential-fuzz contract) and ``recover()`` replays the round
journal back onto a fresh device batch.

Reference analog: the two-round sync loop of the reference's README
(crates/loro/README) plus its shallow-snapshot floor
(crates/loro-internal/src/encoding/shallow_snapshot.rs:16-40), packaged
server-side at fleet scale.
"""
from __future__ import annotations

import time as _time
from typing import Dict, List, Optional, Sequence

from ..errors import DeviceFailure, ResilienceError
from ..obs import flight
from ..obs import metrics as obs
from ..resilience import faultinject, get_supervisor
from ..utils import tracing

faultinject.register_site(
    "poison_doc", "ResidentServer.ingest: corrupt one doc's payload in "
    "a round (per-doc poison isolation)")
from .fleet import (
    DeviceCounterBatch,
    DeviceDocBatch,
    DeviceMapBatch,
    DeviceMovableBatch,
    DeviceTreeBatch,
)

# ONE table per family: (batch class for restore, constructor) — both
# checkpoint/restore and __init__ resolve from it, so they cannot drift
_FAMILIES = {
    "text": (DeviceDocBatch, lambda n, mesh, auto_grow, kw: DeviceDocBatch(
        n, kw.get("capacity", 1 << 14), mesh=mesh, auto_grow=auto_grow
    )),
    "list": (DeviceDocBatch, lambda n, mesh, auto_grow, kw: DeviceDocBatch(
        n, kw.get("capacity", 1 << 14), mesh=mesh, as_text=False,
        auto_grow=auto_grow,
    )),
    "map": (DeviceMapBatch, lambda n, mesh, auto_grow, kw: DeviceMapBatch(
        n, kw.get("slot_capacity", 1 << 10), mesh=mesh, auto_grow=auto_grow
    )),
    "tree": (DeviceTreeBatch, lambda n, mesh, auto_grow, kw: DeviceTreeBatch(
        n, kw.get("move_capacity", 1 << 12), kw.get("node_capacity", 1 << 10),
        mesh=mesh, auto_grow=auto_grow,
    )),
    "movable": (DeviceMovableBatch, lambda n, mesh, auto_grow, kw: DeviceMovableBatch(
        n, kw.get("capacity", 1 << 13), kw.get("elem_capacity", 1 << 10),
        mesh=mesh, auto_grow=auto_grow,
    )),
    "counter": (DeviceCounterBatch, lambda n, mesh, auto_grow, kw: DeviceCounterBatch(
        n, kw.get("slot_capacity", 1 << 6), mesh=mesh, auto_grow=auto_grow
    )),
}
_COMPACTABLE = ("text", "list", "tree", "movable")

# host-side data errors: poison payloads / bad change lists.  These
# route to the per-doc isolation pass — anything else escaping an
# append is a config/logic error that must surface to the caller.
import struct as _struct  # noqa: E402  (stdlib, for _struct.error)

_DATA_ERRORS = (ValueError, TypeError, KeyError, IndexError, _struct.error)


class _StagedGroup:
    """Handle between ``ingest_stage`` and ``ingest_commit``: the
    normalized rounds, their stage-time epochs, and the detached device
    work.  ``mode``: "group" (normal), "serial" (server was degraded at
    stage time), "done" (stage already produced the final epochs, e.g.
    the auto-checkpoint launch degraded)."""

    __slots__ = ("mode", "rounds", "staged", "cid", "epochs", "pending",
                 "error_index")

    def __init__(self, rounds, cid):
        self.mode = "group"
        self.rounds = rounds
        self.staged: List[tuple] = []
        self.cid = cid
        self.epochs: List[int] = []
        self.pending = None
        self.error_index: Optional[int] = None


class ResidentServer:
    """One resident device batch + per-doc replica-ack bookkeeping.

    ``family``: "text" | "list" | "map" | "tree" | "movable" |
    "counter".  Capacity knobs pass through (capacity, slot_capacity,
    move_capacity, node_capacity, elem_capacity).  The underlying batch
    is ``self.batch`` — every read API (texts/richtexts/values/
    value_lists/parent_maps/...) is available directly on it, or
    through the same-named delegating methods here, which keep working
    when the server is degraded to the host engine.

    ``host_fallback=True`` keeps a round journal (frozen as encoded
    wire bytes) so a supervisor-declared device failure can rebuild
    the state host-side.  The journal is BOUNDED by checkpoints: every
    ``checkpoint()`` folds the journaled rounds into a per-doc
    shallow-snapshot *mirror anchor* (persist.MirrorAnchor) and drops
    rounds at/under the checkpoint epoch, so journal length stays
    O(rounds since the last checkpoint) and both the host mirror and
    ``recover()`` re-anchor on the checkpoint instead of on birth.
    Memory-constrained deployments pass ``host_fallback=False``
    (degradation then surfaces as a typed DeviceFailure instead).
    ``auto_checkpoint=True`` snapshots the server into
    ``last_checkpoint`` right before the first risky (first-compile)
    device launch.

    ``durable_dir=`` makes the journal crash-durable: rounds append to
    a segmented WAL (``loro_tpu/persist/``), checkpoints land on a
    retention ladder and rotate/prune the WAL segments;
    ``persist.recover_server(durable_dir)`` reopens after a crash with
    bounded replay (docs/PERSISTENCE.md).
    """

    # wall clock for the WAL round stamps (replication-lag attribution);
    # a class-level reference so tests can inject a fake
    _wall = staticmethod(_time.time)

    def __init__(self, family: str, n_docs: int, mesh=None,
                 auto_grow: bool = True, supervisor=None,
                 host_fallback: bool = True, auto_checkpoint: bool = True,
                 durable_dir: Optional[str] = None,
                 durable_fsync=True,
                 fsync_window: int = 8,
                 mirror_anchor=True,
                 hot_slots: Optional[int] = None,
                 **caps):
        if family not in _FAMILIES:
            raise ValueError(f"unknown family {family!r} (one of {sorted(_FAMILIES)})")
        if hot_slots is not None:
            # tiered residency (parallel/residency.py, docs/RESIDENCY.md):
            # the device batch holds only the hot set; warm/cold tiers
            # live on the anchor+journal plane, so both are required —
            # and the anchor must be DEEP (history-complete) because a
            # revive re-exports the doc's full history for the landing
            from ..errors import ResidencyError

            if not host_fallback:
                raise ResidencyError(
                    "tiered residency (hot_slots=) needs host_fallback="
                    "True — the warm/cold tiers are the mirror-anchor + "
                    "journal plane"
                )
            if not mirror_anchor:
                raise ResidencyError(
                    "tiered residency (hot_slots=) needs a mirror anchor"
                )
            mirror_anchor = "deep"
            caps = dict(caps)
            caps["hot_slots"] = int(hot_slots)
        self.family = family
        self.batch = self._build_batch(family, n_docs, mesh, auto_grow, caps)
        self.n_docs = n_docs
        # acks[di][replica] = newest epoch that replica confirmed
        self.acks: List[Dict[str, int]] = [dict() for _ in range(n_docs)]
        self._compacted_at: List[int] = [0] * n_docs
        durable = None
        if durable_dir is not None:
            from ..errors import PersistError
            from ..persist import DurableLog, WalMeta

            durable = DurableLog(durable_dir, fsync=durable_fsync)
            try:
                if durable.in_use():
                    raise PersistError(
                        f"{durable_dir}: directory already holds journaled "
                        "rounds or checkpoints — use persist.recover_server()"
                        "/open_server() instead of constructing a fresh "
                        "server over them"
                    )
                durable.ensure_meta(WalMeta(
                    family=family, n_docs=n_docs, caps=dict(caps),
                    auto_grow=auto_grow, host_fallback=host_fallback,
                    fsync_mode=durable.fsync_mode,
                    deep_anchor=(mirror_anchor == "deep"),
                ))
            except BaseException:
                durable.close()  # never leak the active segment handle
                raise
        anchor = None
        if host_fallback and mirror_anchor:
            from ..persist import MirrorAnchor

            # mirror_anchor="deep" folds full snapshots (history kept)
            # instead of StateOnly blobs — the sharded fleet passes it
            # so live doc migration can re-export history (SHARDING.md)
            anchor = MirrorAnchor(family, n_docs,
                                  deep=(mirror_anchor == "deep"))
        self._init_resilience(
            mesh=mesh, auto_grow=auto_grow, caps=dict(caps),
            supervisor=supervisor, host_fallback=host_fallback,
            auto_checkpoint=auto_checkpoint, history_complete=True,
            anchor=anchor, durable=durable, fsync_window=fsync_window,
        )
        self._bind_batch(self.batch)

    # -- batch construction (tiered-aware; parallel/residency.py) -------
    @staticmethod
    def _build_batch(family: str, n_docs: int, mesh, auto_grow, caps):
        """One construction point for the device batch: a ``hot_slots``
        entry in ``caps`` builds a TieredBatch (doc-space window over a
        hot-set device batch) instead of the plain family batch — the
        same caps dict rides the WAL meta and v3 checkpoints, so cold
        recovery and restore rebuild the same shape."""
        hs = (caps or {}).get("hot_slots")
        if hs:
            from .residency import TieredBatch

            return TieredBatch(family, n_docs, hs, mesh, auto_grow, caps)
        return _FAMILIES[family][1](n_docs, mesh, auto_grow, caps)

    @staticmethod
    def _import_batch(family: str, data: bytes, caps, mesh):
        if (caps or {}).get("hot_slots"):
            from .residency import TieredBatch

            return TieredBatch.import_state(data, mesh=mesh)
        return _FAMILIES[family][0].import_state(data, mesh=mesh)

    def _bind_batch(self, batch) -> None:
        """Attach a back-reference on batches that need the server's
        anchor/journal plane (TieredBatch warm/cold mirrors)."""
        b = getattr(batch, "bind", None)
        if b is not None:
            b(self)

    @property
    def residency(self):
        """The ResidencyManager when this server is tiered
        (``hot_slots=``), else None — tier queries, ``report()`` and
        the demotion policy hang off it (docs/RESIDENCY.md)."""
        return getattr(self.batch, "mgr", None)

    def _init_resilience(self, mesh, auto_grow, caps, supervisor,
                         host_fallback, auto_checkpoint,
                         history_complete, anchor=None, durable=None,
                         replay_base=None, ckpt_epoch=0,
                         fsync_window: int = 8) -> None:
        self._mesh = mesh
        self._auto_grow = auto_grow
        self._caps = caps
        self._supervisor = supervisor
        self._host_fallback = host_fallback
        # journal of (epoch, frozen_updates, cid) rounds; the tail
        # since the last checkpoint once one exists (checkpoint() folds
        # older rounds into the mirror anchor and drops them).  With no
        # anchor the journal must be complete since birth to seed a
        # host mirror — a restore()d pre-v3 server has neither, so its
        # degradation surfaces typed instead.
        self._history: List[tuple] = []
        self._history_complete = history_complete
        # shallow-snapshot mirror anchor (persist.MirrorAnchor): the
        # host-mirror base at the last checkpoint epoch
        self._anchor = anchor
        # durable journal (persist.DurableLog) when durable_dir= given
        self._durable = durable
        self._durable_closed = False
        # group commit (docs/PERSISTENCE.md): in "group" fsync mode the
        # WAL defers fsyncs; the server syncs every `fsync_window`
        # journaled rounds and tracks the acked-epoch watermark — the
        # newest epoch a crash is guaranteed not to lose.  The
        # watermark advances to the newest JOURNALED epoch (not
        # self.epoch, which a concurrently-staging pipeline group may
        # already have pushed past what is on disk).
        self._fsync_window = max(1, int(fsync_window))
        self._unsynced_rounds = 0
        self._journaled_epoch = 0
        self._durable_epoch = 0
        # attached PipelinedIngest executor (parallel/pipeline.py):
        # close()/checkpoint() drain it so no staged round is stranded
        self._pipeline = None
        # epoch-commit subscribers (loro_tpu/sync fan-out): called with
        # each newly VISIBLE epoch, on whichever thread committed it
        self._epoch_subs: List = []
        # bounded recover(): batch bytes to re-seed from (the last
        # checkpoint blob) + the visible epoch it covers
        self._replay_base: Optional[bytes] = replay_base
        self._ckpt_epoch = ckpt_epoch
        self.last_recovery = None
        self._degraded = False
        self._host = None
        self._epoch_base = 0
        self._host_rounds = 0
        # visible epoch = batch-internal epoch + offset: a degrade/
        # recover cycle may replay fewer internal epochs than clients
        # already acked (the failed round can commit on device but land
        # in the journal only once), so the offset keeps the VISIBLE
        # epoch monotone across recovery
        self._epoch_offset = 0
        self._cid = None
        self._auto_ckpt_pending = auto_checkpoint
        self.last_checkpoint: Optional[bytes] = None
        self.last_poison_docs: List[int] = []

    @property
    def degraded(self) -> bool:
        return self._degraded

    def _sup(self):
        return self._supervisor if self._supervisor is not None else get_supervisor()

    # -- sync rounds ---------------------------------------------------
    def ingest(self, per_doc_updates: Sequence, cid=None) -> int:
        """Feed one sync round (per-doc update payloads via the native
        path when bytes, else change lists; None = no update) and
        return the epoch clients must ack once they integrate the
        round's fan-out.

        Entries are normalized PER DOC (ADVICE r5 finding 1): a round
        mixing bytes payloads and Change lists decodes the bytes
        entries host-side instead of mis-routing the change lists
        through the payload path (where a TypeError escaped the
        per-doc fallback)."""
        # one trace id a round, and the count of its documents, only
        # where a record is kept: a round with tracing off pays one flag
        told = {}
        if tracing.is_enabled():
            told = {
                "trace_id": tracing.current() or tracing.new_trace_id("s"),
                "docs": sum(1 for u in per_doc_updates if u is not None),
            }
        with tracing.span("server.ingest", **told):
            return self._ingest_round(per_doc_updates, cid)

    def _ingest_round(self, per_doc_updates: Sequence, cid) -> int:
        if getattr(self, "_durable_closed", False):
            from ..errors import PersistError

            raise PersistError(
                "durable server is closed — a round applied now could "
                "never be journaled; reopen via persist.recover_server()"
            )
        batch = self.batch
        self.last_poison_docs = []
        per_doc_updates, use_payloads, n_updated = self._normalize_round(
            per_doc_updates, batch
        )
        if self.family not in ("map", "counter") and cid is None:
            # API misuse, not a poison round: surface it before the
            # isolation machinery can misread it as per-doc poison
            raise ValueError(f"{self.family} ingest needs the container id")
        if cid is not None:
            self._cid = cid
        self._tick_round_counters(use_payloads, n_updated)
        if self._degraded:
            # decode EVERYTHING first (per-doc poison -> skip, typed),
            # then apply: a poison doc never half-applies a mirror round
            per_doc_updates = self._decode_bytes_entries(per_doc_updates)
            with obs.histogram(
                "server.epoch_seconds", "ingest wall time per sync round"
            ).time(family=self.family):
                self._host.apply(per_doc_updates, cid)
            self._host_rounds += 1
            self._record_round(per_doc_updates, cid)
            obs.counter("server.degraded_rounds_total").inc(family=self.family)
            return self.epoch
        sup = self._sup()
        if self._auto_ckpt_pending:
            # the FIRST device append compiles the scatter kernels — the
            # riskiest launch of a server's life (a failure here loses the
            # epoch).  Snapshot first so the round is recoverable via
            # checkpoint()/restore().  The checkpoint itself reads
            # device state, so it is guarded too: a failure HERE is
            # already a device failure and takes the degradation path.
            self._auto_ckpt_pending = False
            try:
                with tracing.span("server.checkpoint"):
                    self.last_checkpoint = sup.guard(
                        self.checkpoint, label=f"server.checkpoint.{self.family}"
                    )
            except DeviceFailure as e:
                return self._degrade_round(per_doc_updates, cid, e)
            obs.counter("server.auto_checkpoints_total").inc(family=self.family)
        try:
            with obs.histogram(
                "server.epoch_seconds", "ingest wall time per sync round"
            ).time(family=self.family):
                sup.launch(
                    lambda: self._append(batch, per_doc_updates, cid, use_payloads),
                    label=f"server.ingest.{self.family}",
                    retry=False,  # appends donate buffers: never re-run
                    drain=self._drain_fetch,
                )
        except DeviceFailure as e:
            return self._degrade_round(per_doc_updates, cid, e)
        except _DATA_ERRORS:
            # data error (poison payload / bad change list): the
            # columnar walk raises BEFORE any device commit, so
            # re-attempting per doc is safe — isolate the offender
            self._ingest_isolated(per_doc_updates, cid, sup)
            return self.epoch
        except Exception:
            # host-side config/logic error (e.g. capacity exceeded with
            # auto_grow=False): surface it loudly, don't misread it as
            # poison or degrade on it
            obs.counter("server.errors_total").inc(family=self.family)
            raise
        self._record_round(per_doc_updates, cid)
        return self.epoch

    def _normalize_round(self, per_doc_updates, batch):
        """Fault-mangle + route one round (shared by ingest and
        ingest_coalesced): returns ``(updates, use_payloads,
        n_updated)``.  Bytes entries decode host-side when the round is
        mixed or the family lacks a native payload path; an entry that
        won't decode is poison for THAT doc only — skipped with a
        typed record (``last_poison_docs``), never an uncaught error."""
        per_doc_updates = [
            faultinject.mangle("poison_doc", u, doc=di) if u is not None else None
            for di, u in enumerate(per_doc_updates)
        ]
        n_updated = sum(1 for u in per_doc_updates if u is not None)
        obs.gauge("server.queue_depth").set(n_updated, family=self.family)
        has_bytes = any(isinstance(u, (bytes, bytearray))
                        for u in per_doc_updates if u is not None)
        has_changes = any(u is not None and not isinstance(u, (bytes, bytearray))
                          for u in per_doc_updates)
        if has_bytes and (has_changes or not hasattr(batch, "append_payloads")):
            # mixed round, or a family without a native payload path
            # (counter): decode bytes entries host-side per doc
            reason = "mixed_round" if has_changes else "no_payload_path"
            n_decoded = sum(
                1 for u in per_doc_updates if isinstance(u, (bytes, bytearray))
            )
            obs.counter("server.ingest_fallback_total").inc(
                n_decoded, family=self.family, reason=reason
            )
            per_doc_updates = self._decode_bytes_entries(per_doc_updates)
            use_payloads = False
        else:
            use_payloads = has_bytes
        return per_doc_updates, use_payloads, n_updated

    def _tick_round_counters(self, use_payloads: bool, n_updated: int) -> None:
        route = "payloads" if use_payloads else "changes"
        obs.counter("server.ingest_rounds_total").inc(
            family=self.family, route=route
        )
        obs.counter("server.ingest_docs_total").inc(n_updated, family=self.family)

    def _append(self, batch, updates, cid, use_payloads: bool) -> None:
        if self.family in ("map", "counter"):
            if use_payloads:
                batch.append_payloads(updates)
            else:
                batch.append_changes(updates)
        else:
            if cid is None:
                raise ValueError(
                    f"{self.family} ingest needs the container id"
                )
            if use_payloads:
                batch.append_payloads(updates, cid)
            else:
                batch.append_changes(updates, cid)

    def _decode_bytes_entries(self, updates):
        """Bytes entries -> Change lists, per doc.  An entry that will
        not decode is poison for that doc only: skipped (None) with a
        typed record + counter, never an uncaught decode error."""
        from ..codec.binary import decode_changes

        out = list(updates)
        for di, u in enumerate(out):
            if isinstance(u, (bytes, bytearray)):
                try:
                    out[di] = decode_changes(bytes(u))
                except _DATA_ERRORS:
                    out[di] = None
                    self.last_poison_docs.append(di)
                    obs.counter("server.poison_docs_total").inc(family=self.family)
        return out

    def _record_round(self, updates, cid, epoch: Optional[int] = None) -> None:
        """Journal one APPLIED round (stamped with the round's visible
        epoch — coalesced ingest passes each round's epoch explicitly,
        since the batch clock has already advanced past it by journal
        time).  Change-list entries are FROZEN as encoded bytes: the
        live Change objects are aliased with the producing doc's oplog,
        which extends them in place on later commits (change RLE) —
        journaling the objects themselves would double-apply those ops
        on replay.  Bytes entries are immutable already and stored
        as-is.  With ``durable_dir`` the round also lands in the WAL
        before this method returns (fsync'd per round, or deferred to
        the group-commit window in ``durable_fsync="group"`` mode —
        ``durable_epoch`` is the watermark a crash cannot lose)."""
        if epoch is None:
            epoch = self.epoch
        self._notify_epoch(epoch)
        if not (self._host_fallback or self._durable is not None):
            return
        from ..codec.binary import encode_changes

        frozen = [
            u if u is None or isinstance(u, (bytes, bytearray))
            else bytes(encode_changes(list(u)))
            for u in updates
        ]
        # in-memory journal FIRST: the round is already on the device,
        # and the mirror/recover() paths must see it even if the
        # durable append below fails
        if self._host_fallback:
            self._history.append((epoch, frozen, cid))
            if not self._degraded:
                # tiered residency: a journaled round's device work is
                # committed, so its docs become eviction-eligible
                nj = getattr(self.batch, "note_journaled", None)
                if nj is not None:
                    nj()
        if self._durable is not None:
            # fail-stop durability: a failed append means served state
            # has diverged from the WAL — continuing to journal would
            # make every later recovery silently wrong.  Detach the log
            # and surface typed; the in-memory paths stay consistent,
            # the operator recovers durability from the last checkpoint.
            try:
                # request-tracing stamps: the ambient trace id of the
                # committing thread (the pipeline/fan-in set it from
                # the round-leading push) and the leader wall clock —
                # a follower turns the stamp into measured apply lag
                told = {}
                if tracing.is_enabled():
                    told = {"bytes": sum(len(u) for u in frozen if u is not None)}
                with tracing.span("server.journal", epoch=epoch, **told):
                    self._durable.append_round(
                        epoch, cid, frozen,
                        trace=tracing.current(),
                        stamp_us=int(self._wall() * 1e6),
                    )
            except BaseException as e:
                from ..errors import FencedLeader, PersistError

                log, self._durable = self._durable, None
                self._durable_closed = True  # later ingests raise typed
                try:
                    log.close()
                except Exception:  # tpulint: disable=LT-EXC(best-effort WAL close while the typed fail-stop PersistError is already in flight)
                    pass
                obs.counter("server.errors_total").inc(family=self.family)
                if isinstance(e, FencedLeader):
                    # replication fencing (docs/REPLICATION.md): the
                    # fence fires BEFORE any bytes land, so the WAL is
                    # intact — surface the deposition itself, not a
                    # disk-failure wrap; journaling stays detached
                    # (fail-stop) either way.
                    raise
                raise PersistError(
                    f"durable journal append failed at epoch {epoch} — "
                    "the WAL no longer matches served state; journaling "
                    "is DETACHED (fail-stop), recover durability from "
                    f"{log.dir!r}: {type(e).__name__}: {e}"
                ) from e
            self._journaled_epoch = max(self._journaled_epoch, epoch)
            if self._durable.fsync_mode == "group":
                self._unsynced_rounds += 1
                if self._unsynced_rounds >= self._fsync_window:
                    self.flush_durable()
            else:
                # per-round fsync: the round is already on disk
                self._durable_epoch = epoch
            obs.gauge(
                "persist.checkpoint_age_rounds",
                "journaled rounds since the last checkpoint",
            ).set(epoch - self._ckpt_epoch, family=self.family)

    def flush_durable(self) -> int:
        """Group-commit flush point: fsync every journaled-but-unsynced
        WAL append (the WAL's own pending count includes control
        records the per-round window never sees) and advance the
        ``durable_epoch`` watermark to the newest JOURNALED epoch —
        never ``self.epoch``, which a concurrently-staging pipeline
        group may already have pushed past what is on disk.  Returns
        appends covered (0 when nothing was pending or the server is
        not durable).  Fail-stop like the append path: a failed fsync
        detaches the journal typed."""
        if self._durable is None:
            return 0
        try:
            with tracing.span("server.fsync"):
                n = self._durable.sync()
        except BaseException as e:
            from ..errors import PersistError

            log, self._durable = self._durable, None
            self._durable_closed = True
            try:
                log.close()
            except Exception:  # tpulint: disable=LT-EXC(best-effort WAL close while the typed fail-stop PersistError is already in flight)
                pass
            obs.counter("server.errors_total").inc(family=self.family)
            raise PersistError(
                f"durable group-commit fsync failed — journaling is "
                f"DETACHED (fail-stop), recover durability from "
                f"{log.dir!r}: {type(e).__name__}: {e}"
            ) from e
        self._unsynced_rounds = 0
        self._durable_epoch = max(self._durable_epoch, self._journaled_epoch)
        return n

    @property
    def durable_epoch(self) -> int:
        """The acked-epoch watermark: the newest visible epoch whose
        journal record is known fsync'd.  A crash loses at most rounds
        after it (group mode); equals the newest journaled epoch in
        per-round mode.  0 for non-durable servers."""
        return self._durable_epoch

    def _replay_round(self, batch, updates, cid) -> None:
        """Re-apply a journaled round to `batch` with the same routing
        rule ingest used (all-bytes + payload path -> payloads; mixed
        or no payload path -> decode host-side).  Journaled bytes were
        applied once already, so they are known-decodable."""
        from ..codec.binary import decode_changes

        has_bytes = any(isinstance(u, (bytes, bytearray))
                        for u in updates if u is not None)
        has_changes = any(u is not None and not isinstance(u, (bytes, bytearray))
                          for u in updates)
        if has_bytes and (has_changes or not hasattr(batch, "append_payloads")):
            updates = [
                decode_changes(bytes(u)) if isinstance(u, (bytes, bytearray)) else u
                for u in updates
            ]
            has_bytes = False
        self._append(batch, updates, cid, has_bytes)

    def _drain_fetch(self) -> None:
        """Tiny host fetch that drains the async device queue: fetch
        the smallest device array the batch holds."""
        import jax
        import numpy as np
        from contextlib import nullcontext

        dev = getattr(self.batch, "device_batch", self.batch)
        # under the device lock: a tiered eviction (release_doc) DONATES
        # the old column buffers — collecting a leaf here and fetching
        # it after the donation would read a deleted buffer.  The lock
        # spans collect+fetch so the snapshot stays coherent.
        lk = getattr(dev, "_dev_lock", None)
        with (lk if lk is not None else nullcontext()):
            leaves = []
            for v in dev.__dict__.values():
                for leaf in jax.tree_util.tree_leaves(v):
                    if isinstance(leaf, jax.Array):
                        leaves.append(leaf)
            if leaves:
                np.asarray(min(leaves, key=lambda a: a.size))

    # -- coalesced sync rounds ----------------------------------------
    def ingest_coalesced(self, rounds: Sequence[Sequence], cid=None) -> List[int]:
        """Apply several pending sync rounds as ONE coalesced device
        group (docs/RESILIENCE.md "round coalescing"): every round's
        host work — routing, order maintenance, id maps, epoch clock —
        runs per round exactly as serial ``ingest`` would (the final
        state is byte-for-byte identical), but the device scatters/
        folds of the whole group ship as one launch, amortizing the
        per-round dispatch floor across the group.

        Journal records, poison isolation, host-mirror degradation and
        ack bookkeeping stay PER ROUND: returns one visible epoch per
        round, in order, for clients to ack.  With
        ``durable_fsync="group"`` the group's journal records share one
        fsync and the epochs are returned only after it — an acked
        round is never lost to a crash (``durable_epoch``).

        ``ingest_stage``/``ingest_commit`` are the two-phase form the
        pipeline executor uses to overlap group N's device commit with
        group N+1's host staging; this method is simply stage+commit
        back-to-back."""
        rounds = [list(r) for r in rounds]
        if not rounds:
            return []
        if self._degraded or len(rounds) == 1:
            # host mirror rounds have no launch to amortize; a solo
            # round IS the serial path
            return [self.ingest(r, cid) for r in rounds]
        hs = getattr(self.batch, "hot_slots", None)
        if hs is not None:
            # tiered residency: a group's distinct docs co-reside in
            # device slots, so chunk the group to the hot budget (each
            # chunk commits — and journals — before the next stages,
            # so consecutive chunks may reuse the whole budget)
            out: List[int] = []
            chunk: List[list] = []
            docs_seen: set = set()
            for r in rounds:
                nxt = {di for di, u in enumerate(r) if u is not None}
                if chunk and len(docs_seen | nxt) > hs:
                    out.extend(self.ingest_commit(self.ingest_stage(chunk, cid)))
                    chunk, docs_seen = [], set()
                chunk.append(r)
                docs_seen |= nxt
            out.extend(self.ingest_commit(self.ingest_stage(chunk, cid)))
            return out
        return self.ingest_commit(self.ingest_stage(rounds, cid))

    def ingest_stage(self, rounds: Sequence[Sequence], cid=None):
        """Phase 1 of a coalesced group: normalize + HOST-stage every
        round (order maintenance, id maps, per-round epoch stamps) with
        the device work deferred, and return an opaque handle for
        ``ingest_commit``.  Touches no device arrays (modulo a rare
        capacity grow, which the batch's device lock serializes against
        an in-flight commit), so it may run while the PREVIOUS group's
        commit is still on the device — the host/device overlap of
        docs/RESILIENCE.md."""
        rounds = [list(r) for r in rounds]
        if getattr(self, "_durable_closed", False):
            from ..errors import PersistError

            raise PersistError(
                "durable server is closed — a round applied now could "
                "never be journaled; reopen via persist.recover_server()"
            )
        if self.family not in ("map", "counter") and cid is None:
            raise ValueError(f"{self.family} ingest needs the container id")
        h = _StagedGroup(rounds, cid)
        if not rounds:
            h.mode = "done"
            return h
        if self._degraded:
            h.mode = "serial"  # commit routes through degraded ingest
            return h
        batch = self.batch
        self.last_poison_docs = []
        for r in rounds:
            ups, use_pl, n_upd = self._normalize_round(r, batch)
            h.staged.append((ups, use_pl))
            self._tick_round_counters(use_pl, n_upd)
        if cid is not None:
            self._cid = cid
        sup = self._sup()
        if self._auto_ckpt_pending:
            # same contract as serial ingest: snapshot before the first
            # risky (first-compile) launch of the server's life.  Only
            # ever runs before the FIRST group, so no commit can be in
            # flight behind it.
            self._auto_ckpt_pending = False
            try:
                self.last_checkpoint = sup.guard(
                    self.checkpoint, label=f"server.checkpoint.{self.family}"
                )
            except DeviceFailure as e:
                h.mode = "done"
                h.epochs = self._degrade_rounds(
                    [s[0] for s in h.staged], cid, e
                )
                return h
            obs.counter("server.auto_checkpoints_total").inc(family=self.family)
        batch.begin_coalesce()
        try:
            for i, (ups, use_pl) in enumerate(h.staged):
                try:
                    self._append(batch, ups, cid, use_pl)
                except _DATA_ERRORS:
                    # poison round: staging stops here; commit isolates
                    # it per doc and runs the tail serially
                    h.error_index = i
                    break
                h.epochs.append(self.epoch)
        except BaseException:
            # host config/logic error (capacity with auto_grow=False,
            # API misuse): ship the staged prefix so host and device
            # agree, journal it, then surface loudly — same contract as
            # serial ingest
            batch.flush_coalesce()
            for j, ep in enumerate(h.epochs):
                self._record_round(h.staged[j][0], cid, epoch=ep)
            self.flush_durable()
            obs.counter("server.errors_total").inc(family=self.family)
            raise
        h.pending = batch.detach_coalesce()
        return h

    def ingest_commit(self, h) -> List[int]:
        """Phase 2 of a coalesced group: ship the staged device work as
        one supervised launch, journal each round with its stage-time
        epoch, and fsync the group-commit window.  Returns the
        per-round ack epochs.  A DeviceFailure here degrades with the
        WHOLE group (none of it is journaled before this method), so
        staged work replays in order on the host mirror — never lost,
        never double-applied."""
        if h.mode == "done":
            return h.epochs
        if h.mode == "serial":
            # server was degraded at stage time: plain serial ingest
            # (host mirror application, journaled per round).  The
            # group-end fsync still applies: a pipeline epoch future
            # must never resolve before its journal record is durable.
            out = [self.ingest(r, h.cid) for r in h.rounds]
            self.flush_durable()
            return out
        cid = h.cid
        sup = self._sup()
        batch = self.batch
        if self._degraded:
            # a previous group's commit degraded the server AFTER this
            # group host-staged into the now-discarded device batch:
            # re-apply the normalized rounds on the mirror (the mirror
            # seeded from the journal, which holds none of them)
            out: List[int] = []
            for ups, _pl in h.staged:
                obs.counter("server.degraded_rounds_total").inc(family=self.family)
                ups = self._decode_bytes_entries(ups)
                self._host.apply(ups, cid)
                self._host_rounds += 1
                self._record_round(ups, cid)
                out.append(self.epoch)
            self.flush_durable()
            return out
        obs.counter("pipeline.groups_total").inc(family=self.family)
        obs.histogram(
            "pipeline.coalesce_group_rounds", "rounds per coalesced group",
            buckets=(1, 2, 4, 8, 16, 32, 64),
        ).observe(len(h.staged))
        try:
            with obs.histogram(
                "server.epoch_seconds", "ingest wall time per sync round"
            ).time(family=self.family):
                sup.launch(
                    lambda: batch.commit_detached(h.pending),
                    label=f"server.ingest.{self.family}",
                    retry=False,  # scatters donate buffers: never re-run
                    drain=self._drain_fetch,
                )
        except DeviceFailure as e:
            return self._degrade_rounds([s[0] for s in h.staged], cid, e)
        epochs = list(h.epochs)
        # journal per round (each with ITS stage-time epoch)
        for (ups, _pl), ep in zip(h.staged, epochs):
            self._record_round(ups, cid, epoch=ep)
        if h.error_index is not None:
            # the poison round + unstaged tail: isolate per doc, then
            # run the remainder serially (another device failure there
            # degrades with the remaining rounds)
            i = h.error_index
            self._ingest_isolated(h.staged[i][0], cid, sup)
            epochs.append(self.epoch)
            i += 1
            while i < len(h.staged):
                ups, use_pl = h.staged[i]
                try:
                    sup.launch(
                        lambda ups=ups, up=use_pl: self._append(
                            batch, ups, cid, up
                        ),
                        label=f"server.ingest.{self.family}",
                        retry=False,
                        drain=self._drain_fetch,
                    )
                except DeviceFailure as e:
                    return epochs + self._degrade_rounds(
                        [s[0] for s in h.staged[i:]], cid, e
                    )
                except _DATA_ERRORS:
                    self._ingest_isolated(ups, cid, sup)
                else:
                    self._record_round(ups, cid)
                epochs.append(self.epoch)
                i += 1
        # one group-commit sync point: every returned epoch is durable
        self.flush_durable()
        return epochs

    # -- per-doc error isolation --------------------------------------
    def _ingest_isolated(self, updates, cid, sup) -> None:
        """Re-apply a failed round one doc at a time: good docs commit,
        bytes entries that misparse get one host-decode fallback, and
        a doc that still fails is poison — skipped with a typed record
        (``last_poison_docs`` + the server.poison_docs_total counter),
        never an uncaught exception for the whole round."""
        from ..codec.binary import decode_changes

        obs.counter("server.isolation_rounds_total").inc(family=self.family)
        for di, u in enumerate(updates):
            if u is None:
                continue
            one = [None] * len(updates)
            one[di] = u
            use_payloads = isinstance(u, (bytes, bytearray)) and hasattr(
                self.batch, "append_payloads"
            )
            try:
                sup.launch(
                    lambda one=one, up=use_payloads: self._append(
                        self.batch, one, cid, up
                    ),
                    label=f"server.ingest.{self.family}",
                    retry=False,
                    drain=self._drain_fetch,
                )
                # each per-doc append bumps batch.epoch once, so it is
                # journaled as its OWN round — recovery replay then
                # reproduces the same epoch numbering clients acked
                self._record_round(one, cid)
                continue
            except DeviceFailure:
                raise  # double fault: device died mid-isolation — typed
            except _DATA_ERRORS:
                pass
            if isinstance(u, (bytes, bytearray)):
                # host-decode fallback for THIS doc only (extends the
                # mixed-round fallback to per-doc poison isolation)
                try:
                    chs = decode_changes(bytes(u))
                    one[di] = chs
                    sup.launch(
                        lambda one=one: self._append(self.batch, one, cid, False),
                        label=f"server.ingest.{self.family}",
                        retry=False,
                        drain=self._drain_fetch,
                    )
                    self._record_round(one, cid)
                    obs.counter("server.ingest_fallback_total").inc(
                        family=self.family, reason="doc_isolated"
                    )
                    continue
                except DeviceFailure:
                    raise
                except _DATA_ERRORS:
                    pass
            self.last_poison_docs.append(di)
            obs.counter("server.poison_docs_total").inc(family=self.family)

    # -- graceful degradation -----------------------------------------
    def _degrade_round(self, updates, cid, cause: DeviceFailure) -> int:
        """Supervisor declared the device dead mid-epoch: re-run the
        epoch on the host engine (anchor seed / journal replay + this
        round) and stay degraded until ``recover()``."""
        return self._degrade_rounds([updates], cid, cause)[-1]

    def _degrade_rounds(self, rounds_updates, cid,
                        cause: DeviceFailure) -> List[int]:
        """Group form of ``_degrade_round`` (coalesced ingest): seed
        the host mirror once — anchor / journal replay, which holds
        NOTHING of the failed group — then apply and journal every
        group round in order, so staged work replays exactly once.
        Returns one visible epoch per round."""
        anchored = self._anchor is not None
        if not (self._host_fallback and (self._history_complete or anchored)):
            obs.counter("server.errors_total").inc(family=self.family)
            raise cause
        self._sup().note_degradation(f"server.{self.family}")
        obs.gauge("server.degraded").set(1, family=self.family)
        # base = the VISIBLE epoch (batch.epoch may already include
        # rounds of the failed group that committed before the drain
        # raised — the offset keeps visible epochs monotone)
        self._epoch_base = self.epoch
        host = self.seed_mirror_engine()
        self._host = host
        self._degraded = True
        self._host_rounds = 0
        out: List[int] = []
        for updates in rounds_updates:
            obs.counter("server.degraded_rounds_total").inc(family=self.family)
            # the failed rounds' bytes never committed anywhere, so
            # they are NOT known-decodable: poison-skip per doc
            updates = self._decode_bytes_entries(updates)
            host.apply(updates, cid)
            self._host_rounds += 1
            self._record_round(updates, cid)
            out.append(self.epoch)
        self.flush_durable()
        return out

    def _seed_mirror(self):
        """Host mirror base: anchor-seeded docs when a mirror anchor
        exists (state at the last checkpoint, history trimmed below
        it), else fresh docs (the journal is then complete since
        birth)."""
        if self._anchor is not None:
            return self._anchor.seed_engine()
        from ..resilience.hostpath import HostEngine

        return HostEngine(self.family, self.n_docs)

    def seed_mirror_engine(self):
        """A ``hostpath.HostEngine`` at the server's current APPLIED
        state: the mirror-anchor seed plus the journal tail.  The one
        replay rule both consumers share — the degradation mirror
        (``_degrade_rounds``) and the sync front-end's delta-export
        oracle (``loro_tpu/sync``).  Requires ``host_fallback`` (the
        journal/anchor machinery); callers that may hold a pre-v3
        restore check ``_history_complete``/``_anchor`` first."""
        rh = getattr(self.batch, "rehydrate_anchor", None)
        if rh is not None:
            # tiered residency: cold docs' blobs come back first — the
            # mirror engine must hold EVERY doc, whatever its tier
            rh()
        host = self._seed_mirror()
        floor = self._anchor.epoch if self._anchor is not None else 0
        for _e, ups, c in self._history:
            if _e > floor:
                host.apply(ups, c)
        if self._cid is not None:
            host._cid = self._cid
        return host

    # -- epoch-commit subscription (loro_tpu/sync fan-out) -------------
    def subscribe_epochs(self, cb) -> "callable":
        """Register ``cb(epoch)`` to run for every newly VISIBLE epoch
        (device commit, coalesced group member, isolated per-doc round,
        or degraded host-mirror round alike).  Fires on the committing
        thread, after the round is applied but before pipeline epoch
        futures resolve — a subscriber observes a commit no later than
        the client that pushed it.  Commit-visibility semantics, not
        durability: in ``durable_fsync="group"`` mode the epoch may not
        be fsync'd yet (gate on ``durable_epoch`` for that).  Recovery
        replay (``_replay_journal_tail``) does NOT re-fire — those
        epochs were announced in their original life.  Returns an
        unsubscribe callable."""
        self._epoch_subs.append(cb)
        return lambda: self._epoch_subs.remove(cb)

    def _notify_epoch(self, epoch: int) -> None:
        flight.record("server.epoch", family=self.family, epoch=epoch,
                      trace=tracing.current())
        for cb in list(self._epoch_subs):
            try:
                cb(epoch)
            except Exception:  # tpulint: disable=LT-EXC(subscriber isolation: a broken epoch subscriber must never poison ingest; counted below)
                obs.counter(
                    "server.epoch_sub_errors_total",
                    "epoch-commit subscriber callbacks that raised",
                ).inc(family=self.family)

    def attach_durable(self, log) -> None:
        """Adopt a ``persist.DurableLog`` (recover_server re-attaches
        the reopened directory so future rounds keep journaling).
        Every replayed round came FROM disk, so the durable watermark
        starts at the recovered epoch."""
        self._durable = log
        self._durable_closed = False
        self._unsynced_rounds = 0
        self._journaled_epoch = self.epoch
        self._durable_epoch = self.epoch

    @property
    def pipeline_doc_budget(self) -> Optional[int]:
        """Max DISTINCT docs a coalesced group may touch (None = no
        bound).  Tiered servers (hot_slots=) bound it to half the hot
        budget: a group's docs must co-reside in device slots — their
        merged scatter references the slots, so none is evictable until
        the group commits and journals — and the staging group overlaps
        the in-flight one, so two groups' worth must fit.  A single
        round touching more docs than hot_slots still fails typed
        (ResidencyError) whatever the grouping."""
        hs = getattr(self.batch, "hot_slots", None)
        if hs is None:
            return None
        return max(1, hs // 2)

    def pipeline(self, cid=None, coalesce: int = 4, depth: int = 2):
        """Attach a ``PipelinedIngest`` executor (parallel/pipeline.py):
        submitted rounds stage on the host while the device group in
        flight drains, and consecutive staged rounds coalesce into one
        launch.  ``close()``/``checkpoint()`` drain it automatically."""
        from .pipeline import PipelinedIngest

        if self._pipeline is not None and not self._pipeline.closed:
            raise RuntimeError(
                "server already has a live pipeline — close() it first"
            )
        self._pipeline = PipelinedIngest(
            self, cid=cid, coalesce=coalesce, depth=depth
        )
        return self._pipeline

    def _drain_pipeline(self) -> None:
        """Flush the attached pipeline (no-op from the pipeline's own
        worker thread — e.g. the auto-checkpoint a worker ingest
        triggers — and when no pipeline is attached)."""
        if self._pipeline is not None and not self._pipeline.closed:
            self._pipeline.flush()

    def close(self) -> None:
        """Drain the attached pipeline, fsync any pending group-commit
        window, and release the durable log (flush + close the active
        WAL segment) so ``persist.recover_server``/``open_server`` can
        reopen the directory.  The server stays READABLE, but further
        ``ingest()`` raises a typed PersistError — applying a round the
        closed WAL can't journal would silently diverge served state
        from recovery."""
        try:
            if self._pipeline is not None and not self._pipeline.closed:
                self._pipeline.close()
        finally:
            # the durable teardown must run even when the pipeline
            # drain re-raises a worker error: a WAL handle left open
            # would make the directory refuse a later recover_server
            if self._durable is not None:
                self.flush_durable()
                self._durable.close()
                self._durable = None
                self._durable_closed = True

    def _replay_journal_tail(self, rounds) -> None:
        """Apply recovered WAL rounds (``(epoch, cid, frozen)``) to the
        batch and re-seed the in-memory journal tail — recovery-only
        (persist.recover_server); appends route through the supervisor
        but are NOT re-journaled (the WAL already holds them)."""
        sup = self._sup()
        last_epoch = self._ckpt_epoch
        nj = getattr(self.batch, "note_journaled", None)
        for epoch, cid, ups in rounds:
            sup.launch(
                lambda ups=ups, cid=cid: self._replay_round(self.batch, list(ups), cid),
                label=f"server.recover.{self.family}",
                retry=False,
                drain=self._drain_fetch,
            )
            if cid is not None:
                self._cid = cid
            if self._host_fallback:
                self._history.append((epoch, list(ups), cid))
            if nj is not None:
                # replayed rounds come FROM the WAL: journaled by
                # definition, so tiered eviction stays possible while
                # the replay revives the docs it touches
                nj()
            last_epoch = epoch
        # visible epochs must continue exactly where the WAL left off
        self._epoch_offset = max(
            0, last_epoch - getattr(self.batch, "epoch", 0)
        )

    def recover(self, mesh=None) -> bool:
        """Rebuild the device batch — from the last checkpoint's batch
        state plus the journal tail when a checkpoint exists (bounded
        replay), else a fresh batch plus the full journal — and switch
        reads back to the device.  Replay launches pass ``retry=False``
        on purpose: a transiently-failed append may have half-mutated
        the new batch's order engines / donated buffers, so the only
        safe unit of retry is this whole method (the failed batch is
        discarded — call ``recover()`` again).  Returns True on
        success; stays degraded and returns False if the device is
        still failing."""
        if not self._degraded:
            return True
        if self._caps is None and self._replay_base is None:
            raise ResilienceError(
                "cannot recover a restore()d pre-v3 server (no construction "
                "caps in the checkpoint); build a fresh server and "
                "restore() a v3 checkpoint into it"
            )
        sup = self._sup()
        try:
            if self._replay_base is not None:
                # bounded replay: re-seed the batch from the last
                # checkpoint's device state, then replay only the
                # journal tail (rounds after the checkpoint epoch)
                from ..storage import MemKvStore

                kv = MemKvStore()
                kv.import_all(self._replay_base)
                batch = sup.guard(
                    lambda: self._import_batch(
                        self.family, kv.get(b"batch"), self._caps,
                        mesh if mesh is not None else self._mesh,
                    ),
                    label=f"server.recover.{self.family}",
                )
                tail = [r for r in self._history if r[0] > self._ckpt_epoch]
            else:
                batch = self._build_batch(
                    self.family, self.n_docs,
                    mesh if mesh is not None else self._mesh,
                    self._auto_grow, self._caps,
                )
                tail = self._history
            # bind BEFORE replay: a tiered batch builds its revive
            # mirrors from this server's anchor + journal.  The journal
            # is rebuilt INCREMENTALLY alongside the replay (same shape
            # as persist's _replay_journal_tail): a tiered revive mid-
            # replay must see only the rounds already replayed — a full
            # journal would land FUTURE ops in the revive payload and
            # the remaining replay would then duplicate them on device.
            self._bind_batch(batch)
            nj = getattr(batch, "note_journaled", None)
            full_hist = self._history
            self._history = (
                [r for r in full_hist if r[0] <= self._ckpt_epoch]
                if self._replay_base is not None else []
            )
            try:
                for _e, ups, c in tail:
                    sup.launch(
                        lambda ups=ups, c=c: self._replay_round(batch, ups, c),
                        label=f"server.recover.{self.family}",
                        retry=False,
                    )
                    self._history.append((_e, ups, c))
                    if nj is not None:
                        nj()  # journal rounds are journaled by definition
            except BaseException:
                # stay degraded with the journal intact: the degraded
                # mirror (and a later recover() retry) needs it whole
                self._history = full_hist
                raise
        except DeviceFailure:
            obs.counter("server.recovery_failures_total").inc(family=self.family)
            return False
        prev_visible = self.epoch
        self.batch = batch
        self._degraded = False
        self._host = None
        self._host_rounds = 0
        # epochs clients acked must stay reachable: never regress the
        # visible epoch below what the degraded server handed out
        self._epoch_offset = max(
            0, prev_visible - getattr(batch, "epoch", 0)
        )
        obs.counter("server.recoveries_total").inc(family=self.family)
        obs.gauge("server.degraded").set(0, family=self.family)
        return True

    # -- reads (device batch, or the host mirror when degraded) --------
    def _read(self, name: str, *args, **kw):
        target = self._host if self._degraded else self.batch
        return getattr(target, name)(*args, **kw)

    def texts(self) -> List[str]:
        return self._read("texts")

    def richtexts(self) -> List[list]:
        return self._read("richtexts")

    def values(self) -> List[list]:
        return self._read("values")

    def value_maps(self):
        return self._read("value_maps")

    def root_value_maps(self, name: str):
        return self._read("root_value_maps", name)

    def parent_maps(self) -> List[dict]:
        return self._read("parent_maps")

    def children_maps(self) -> List[dict]:
        return self._read("children_maps")

    def value_lists(self) -> List[list]:
        return self._read("value_lists")

    @property
    def epoch(self) -> int:
        if self._degraded:
            return self._epoch_base + self._host_rounds
        return getattr(self.batch, "epoch", 0) + self._epoch_offset

    # -- acknowledgment bookkeeping -----------------------------------
    def register_replica(self, di: int, replica: str) -> None:
        """A doc's replica set must be registered before its acks count
        — an unregistered replica set means 'unknown readers', which
        pins the doc's stability floor at 0 (never compact)."""
        self.acks[di].setdefault(replica, 0)

    def ack(self, di: int, replica: str, epoch: int) -> None:
        """Record that `replica` integrated everything the server sent
        up to `epoch` (monotone; stale acks are ignored).  The replica
        must have been registered: silently admitting an unknown name
        would let a PARTIAL replica set define the stability floor and
        reclaim rows an unregistered reader still references."""
        if replica not in self.acks[di]:
            raise ValueError(
                f"doc {di}: ack from unregistered replica {replica!r} — "
                "call register_replica first (the full replica set "
                "defines the compaction floor)"
            )
        if epoch > self.acks[di][replica]:
            self.acks[di][replica] = epoch

    def drop_replica(self, di: int, replica: str) -> None:
        """Forget a departed replica so it stops pinning the floor.
        Only do this once the replica is PERMANENTLY gone — a returning
        replica that missed deletes may reference reclaimed rows."""
        self.acks[di].pop(replica, None)

    def stable_epoch(self, di: int) -> int:
        """The doc's compaction floor: the newest epoch every
        registered replica has acked (0 = no floor)."""
        a = self.acks[di]
        return min(a.values()) if a else 0

    # -- lifecycle -----------------------------------------------------
    def compact(self) -> int:
        """Reclaim what the ack floors allow (no-op for map/counter —
        their resident state is already a fold — and while degraded:
        the host mirror holds no device rows to reclaim).  Returns rows
        reclaimed."""
        self._drain_pipeline()  # never compact under a staged group
        if self.family not in _COMPACTABLE or self._degraded:
            return 0
        floors: List[Optional[int]] = []
        for di in range(self.n_docs):
            # acks live on the VISIBLE epoch scale; the batch compares
            # floors against its INTERNAL epochs — translate, clamping
            # at 0 (a too-new floor could reclaim a tombstone a replica
            # still references)
            e = max(0, self.stable_epoch(di) - self._epoch_offset)
            # skip docs whose floor hasn't advanced since the last pass
            floors.append(e if e > self._compacted_at[di] else None)
        if all(f is None for f in floors):
            return 0
        with obs.histogram("server.compact_seconds").time(family=self.family):
            n = self.batch.compact(floors)
        obs.counter("server.compact_rows_reclaimed_total").inc(
            n, family=self.family
        )
        for di, f in enumerate(floors):
            if f is not None:
                self._compacted_at[di] = f
        return n

    # -- checkpoint/resume --------------------------------------------
    def checkpoint(self) -> bytes:
        """Batch state + ack floors (+ v3: construction caps and the
        mirror anchor) as one LTKV store.  Also the journal bound:
        the anchor folds every journaled round in, the in-memory
        journal drops to rounds AFTER this epoch, and with
        ``durable_dir`` the blob lands on the checkpoint ladder while
        the WAL rotates and prunes covered segments.  Unavailable
        while degraded (the device state is gone — ``recover()``
        first, or restore the pre-failure ``last_checkpoint``).  An
        attached pipeline is DRAINED first: a checkpoint must cover
        every submitted round, never split a staged group."""
        self._drain_pipeline()
        if self._degraded:
            raise ResilienceError(
                "cannot checkpoint a degraded server (device state lost); "
                "recover() first or restore() the last_checkpoint"
            )
        from ..codec.binary import Writer
        from ..storage import MemKvStore

        rh = getattr(self.batch, "rehydrate_anchor", None)
        if rh is not None:
            # tiered residency: cold docs' blobs come back into the
            # anchor first — the rung this checkpoint writes must carry
            # EVERY doc (it becomes the cold tier's new backing rung)
            rh()
        if self._anchor is not None:
            # fold the journal tail into the shallow-snapshot anchor
            # BEFORE trimming: the mirror oracle re-anchors here
            self._anchor.advance(self._history, self._cid)
        kv = MemKvStore()
        meta = Writer()
        meta.u8(3)  # server-state version (v3: + caps/flags/anchor)
        meta.str_(self.family)
        meta.varint(self.n_docs)
        meta.varint(len(self._compacted_at))
        for e in self._compacted_at:
            meta.varint(e)
        # acks are visible-scale; the batch state is internal-scale —
        # the offset must survive restore or floors skew (see epoch)
        meta.varint(self._epoch_offset)
        # v3: construction caps + lifecycle flags, so a restore()d
        # server can degrade (anchor) and recover() (caps)
        flags = (
            (1 if self._auto_grow else 0)
            | (2 if self._host_fallback else 0)
            | (4 if self._anchor is not None else 0)
        )
        meta.u8(flags)
        from ..persist.wal import write_caps

        write_caps(meta, self._caps or {})
        kv.set(b"server", bytes(meta.buf))
        w = Writer()
        w.varint(len(self.acks))
        for a in self.acks:
            w.varint(len(a))
            for rep, e in a.items():
                w.str_(rep)
                w.varint(e)
        kv.set(b"acks", bytes(w.buf))
        kv.set(b"batch", self.batch.export_state())
        if self._anchor is not None:
            kv.set(b"anchor", self._anchor.encode())
        blob = kv.export_all()
        # re-anchor recovery + bound the journal (satellite: journal
        # length stays O(rounds since checkpoint)).  last_checkpoint
        # stays the auto-checkpoint blob (the documented pre-first-
        # launch restore point); _replay_base is the recovery anchor.
        self._replay_base = blob
        self._ckpt_epoch = self.epoch
        if self._anchor is not None:
            # trim ONLY when the anchor holds the folded history: a
            # mirror_anchor=False server's host mirror still needs the
            # journal from birth (recover() is bounded either way — it
            # filters the tail against _ckpt_epoch)
            self._history = [r for r in self._history if r[0] > self._ckpt_epoch]
        ckpt_name = None
        if self._durable is not None:
            ckpt_name = self._durable.record_checkpoint(self._ckpt_epoch, blob)
            # the rotation inside record_checkpoint fsyncs any pending
            # group-commit tail: everything JOURNALED is now durable
            # (self.epoch may already include concurrently-staged
            # rounds that are not — the pipeline was drained above,
            # but stay on the journaled clock for consistency)
            self._unsynced_rounds = 0
            self._durable_epoch = max(
                self._durable_epoch, self._journaled_epoch
            )
            obs.gauge(
                "persist.checkpoint_age_rounds",
                "journaled rounds since the last checkpoint",
            ).set(0, family=self.family)
        ac = getattr(self.batch, "after_checkpoint", None)
        if ac is not None:
            # tiered residency: re-back the cold tier on the fresh rung
            # (and re-drop its blobs), run the warm-budget demotions,
            # refresh residency.json
            ac(ckpt_name)
        return blob

    @classmethod
    def restore(cls, data: bytes, mesh=None) -> "ResidentServer":
        from ..codec.binary import Reader
        from ..errors import DecodeError
        from ..storage import MemKvStore

        kv = MemKvStore()
        kv.import_all(data)
        meta_b, acks_b, batch_b = kv.get(b"server"), kv.get(b"acks"), kv.get(b"batch")
        if meta_b is None or acks_b is None or batch_b is None:
            raise DecodeError("ResidentServer state: missing sections")
        try:
            r = Reader(meta_b)
            version = r.u8()
            if version > 3:
                raise DecodeError(f"ResidentServer state v{version} too new")
            family = r.str_()
            n_docs = r.varint()
            n_comp = r.varint()
            compacted_at = [r.varint() for _ in range(n_comp)]
            epoch_offset = r.varint() if version >= 2 else 0
            # v3: construction caps + lifecycle flags (v1/v2 blobs keep
            # the old semantics: no caps -> no in-place recover, no
            # anchor -> typed failure instead of degradation)
            auto_grow, host_fallback, has_anchor, caps = True, False, False, None
            if version >= 3:
                from ..persist.wal import read_caps

                flags = r.u8()
                auto_grow = bool(flags & 1)
                host_fallback = bool(flags & 2)
                has_anchor = bool(flags & 4)
                caps = read_caps(r)
            if family not in _FAMILIES or n_comp != n_docs:
                raise DecodeError("ResidentServer state: malformed meta")
            r = Reader(acks_b)
            n_acks = r.varint()
            if n_acks != n_docs:
                raise DecodeError("ResidentServer state: ack table width")
            acks: List[Dict[str, int]] = []
            for _ in range(n_acks):
                a: Dict[str, int] = {}
                for _ in range(r.varint()):
                    rep = r.str_()
                    a[rep] = r.varint()
                acks.append(a)
        except (IndexError, ValueError, UnicodeDecodeError) as e:
            raise DecodeError(f"ResidentServer state: malformed ({e})") from None
        anchor = None
        if has_anchor:
            from ..persist import MirrorAnchor

            anchor_b = kv.get(b"anchor")
            if anchor_b is None:
                raise DecodeError("ResidentServer state: anchor flag without section")
            anchor = MirrorAnchor.decode(anchor_b)
            if anchor.family != family or anchor.n_docs != n_docs:
                raise DecodeError("ResidentServer state: anchor shape mismatch")
        srv = cls.__new__(cls)
        srv.family = family
        srv.n_docs = n_docs
        srv.acks = acks
        srv._compacted_at = compacted_at
        srv.batch = cls._import_batch(family, batch_b, caps, mesh)
        if srv.batch.n_docs < n_docs:
            raise DecodeError(
                "ResidentServer state: batch narrower than the ack table"
            )
        # a v3 restore carries everything the resilience machinery
        # needs: caps (in-place recover()), the mirror anchor (host
        # degradation without birth history — the journal resumes from
        # the restore point) and the blob itself as the bounded-replay
        # base.  Pre-v3 blobs restore with host_fallback OFF and a
        # later device failure surfaces as a typed DeviceFailure.
        srv._init_resilience(
            mesh=mesh, auto_grow=auto_grow, caps=caps, supervisor=None,
            host_fallback=host_fallback and anchor is not None,
            auto_checkpoint=False, history_complete=False,
            anchor=anchor, replay_base=data,
        )
        srv._bind_batch(srv.batch)
        srv._epoch_offset = epoch_offset
        srv.last_checkpoint = data
        srv._ckpt_epoch = srv.epoch
        if anchor is not None and anchor.cid is not None:
            srv._cid = anchor.cid
        return srv
