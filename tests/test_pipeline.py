"""Pipelined resident ingest (ISSUE 5): the PipelinedIngest executor,
round coalescing, WAL group commit, and the deterministic COUNT-based
perf guards (obs launch/fsync counters, not wall clock — the ADVICE
de-flaking pattern: scaling shape is asserted on counted device
launches and fsyncs, which load noise cannot move)."""
import os

import pytest

from loro_tpu import LoroDoc
from loro_tpu.codec.binary import encode_changes
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.parallel.server import ResidentServer


def _text_rounds(n_rounds, peer=31, rows=24):
    """n_rounds frozen payload-bytes rounds of text edits (every round
    inserts, so each serial round costs exactly one block scatter)."""
    import random

    rng = random.Random(peer * 7 + 1)
    d = LoroDoc(peer=peer)
    t = d.get_text("t")
    t.insert(0, "pipeline base text")
    d.commit()
    mark = d.oplog_vv()
    rounds = [[strip_envelope(d.export_updates({}))]]
    for r in range(n_rounds - 1):
        made = 0
        while made < rows:
            L = len(t)
            if L > 10 and rng.random() < 0.2:
                p0 = rng.randrange(L - 2)
                t.delete(p0, 2)
                made += 2
            else:
                run = rng.randint(1, 6)
                t.insert(rng.randint(0, L), "abcdef"[:run])
                made += run
        d.commit()
        rounds.append([strip_envelope(d.export_updates(mark))])
        mark = d.oplog_vv()
    return d, rounds


class TestPipelinedIngest:
    def test_pipeline_matches_serial_byte_for_byte(self):
        d, rounds = _text_rounds(10)
        cid = d.get_text("t").id
        serial = ResidentServer("text", 1, capacity=1 << 12)
        for r in rounds:
            serial.ingest(list(r), cid)
        piped = ResidentServer("text", 1, capacity=1 << 12)
        ex = piped.pipeline(cid=cid, coalesce=4, depth=2)
        prs = [ex.submit(list(r)) for r in rounds]
        ex.flush()
        # per-round ack epochs identical to the serial numbering
        assert [p.epoch() for p in prs] == [
            e for e in _serial_epochs(rounds, cid)
        ]
        assert piped.batch.export_state() == serial.batch.export_state()
        assert piped.texts() == [d.get_text("t").to_string()]
        rep = ex.report()
        assert rep["rounds"] == 10
        assert rep["max_group"] <= 4
        assert rep["max_depth_seen"] <= rep["queue_bound"]
        ex.close()

    def test_submit_after_close_raises(self):
        d, rounds = _text_rounds(2)
        cid = d.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        ex = srv.pipeline(cid=cid)
        ex.submit(list(rounds[0]))
        ex.close()
        with pytest.raises(RuntimeError, match="closed"):
            ex.submit(list(rounds[1]))
        # a closed pipeline does not block a fresh one
        ex2 = srv.pipeline(cid=cid)
        ex2.submit(list(rounds[1]))
        ex2.flush()
        assert srv.texts() == [d.get_text("t").to_string()]
        ex2.close()

    def test_live_change_lists_freeze_at_submit(self):
        """Queued live Change lists are aliased with the producing
        oplog (change RLE): submit() must freeze them so later commits
        cannot leak ops into an earlier queued round."""
        d = LoroDoc(peer=44)
        t = d.get_text("t")
        t.insert(0, "frozen")
        d.commit()
        cid = t.id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        ex = srv.pipeline(cid=cid, coalesce=8)
        mark = d.oplog_vv()
        ex.submit([d.oplog.changes_in_causal_order()])
        # the same change object extends NOW (RLE) — round 2 carries
        # the delta; without freezing, round 1 would double-apply it
        t.insert(len(t), " more")
        d.commit()
        ex.submit([list(d.oplog.changes_between(mark, d.oplog_vv()))])
        ex.flush()
        assert srv.texts() == [t.to_string()]
        ex.close()

    def test_checkpoint_drains_pipeline(self):
        """Satellite: checkpoint() must cover every submitted round —
        it drains the attached pipeline before exporting state."""
        d, rounds = _text_rounds(6)
        cid = d.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        ex = srv.pipeline(cid=cid, coalesce=3)
        prs = [ex.submit(list(r)) for r in rounds]
        blob = srv.checkpoint()  # no explicit flush
        assert all(p.done for p in prs)
        back = ResidentServer.restore(blob)
        assert back.texts() == [d.get_text("t").to_string()]
        ex.close()

    def test_close_drains_pipeline_durable(self, tmp_path):
        """Satellite: server.close() drains the pipeline and fsyncs the
        group-commit tail, so recovery sees every submitted round."""
        from loro_tpu.persist import recover_server

        d, rounds = _text_rounds(7)
        cid = d.get_text("t").id
        srv = ResidentServer(
            "text", 1, capacity=1 << 12, durable_dir=str(tmp_path),
            durable_fsync="group", fsync_window=4,
        )
        ex = srv.pipeline(cid=cid, coalesce=3)
        for r in rounds:
            ex.submit(list(r))
        srv.close()  # drains the pipeline, syncs, closes the WAL
        assert srv.durable_epoch == srv.epoch
        back = recover_server(str(tmp_path))
        assert back.epoch == srv.epoch
        assert back.texts() == [d.get_text("t").to_string()]
        back.close()

    def test_group_commit_watermark(self, tmp_path):
        """durable_epoch only advances at fsync points: mid-window
        journaled rounds are not yet acked durable."""
        d, rounds = _text_rounds(6)
        cid = d.get_text("t").id
        srv = ResidentServer(
            "text", 1, capacity=1 << 12, durable_dir=str(tmp_path),
            durable_fsync="group", fsync_window=100,  # never auto-syncs
            auto_checkpoint=False,
        )
        for r in rounds[:4]:
            srv.ingest(list(r), cid)
        assert srv.durable_epoch < srv.epoch  # window still open
        # one fsync covers the 4 journaled rounds (the meta control
        # record synced immediately at construction — control records
        # never ride the group-commit window)
        assert srv.flush_durable() == 4
        assert srv.durable_epoch == srv.epoch
        # coalesced groups sync at group end: epochs returned are acked
        eps = srv.ingest_coalesced([list(r) for r in rounds[4:]], cid)
        assert srv.durable_epoch == eps[-1] == srv.epoch
        srv.close()


class TestWatermarkInvariant:
    def test_watermark_never_exceeds_journaled(self, tmp_path):
        """Review regression: a coalesced group larger than the fsync
        window triggers a MID-JOURNAL window flush — the watermark must
        advance to the newest JOURNALED epoch, never ``self.epoch``
        (which staging already pushed past what is on disk)."""
        d, rounds = _text_rounds(8)
        cid = d.get_text("t").id
        srv = ResidentServer(
            "text", 1, capacity=1 << 12, auto_checkpoint=False,
            durable_dir=str(tmp_path), durable_fsync="group",
            fsync_window=3,  # < the group size below
        )
        journaled = []
        orig = srv._record_round

        def spy(ups, cid2, epoch=None):
            orig(ups, cid2, epoch=epoch)
            journaled.append(epoch if epoch is not None else srv.epoch)
            assert srv.durable_epoch <= max(journaled), (
                "watermark overstates what is on disk"
            )

        srv._record_round = spy
        eps = srv.ingest_coalesced([list(r) for r in rounds], cid)
        # group-end flush: every returned (ackable) epoch is durable
        assert srv.durable_epoch == eps[-1] == srv.epoch
        assert len(journaled) == 8
        srv.close()


class TestCountBasedPerfGuards:
    """Deterministic launch/fsync count guards (never wall-clock)."""

    def test_coalescing_cuts_device_launches(self):
        d, rounds = _text_rounds(8)
        cid = d.get_text("t").id
        c = obs.counter("fleet.device_launches_total")
        serial = ResidentServer("text", 1, capacity=1 << 12)
        n0 = c.get(family="resident_seq")
        for r in rounds:
            serial.ingest(list(r), cid)
        serial_launches = c.get(family="resident_seq") - n0
        piped = ResidentServer("text", 1, capacity=1 << 12)
        n0 = c.get(family="resident_seq")
        piped.ingest_coalesced([list(r) for r in rounds[:4]], cid)
        piped.ingest_coalesced([list(r) for r in rounds[4:]], cid)
        coalesced_launches = c.get(family="resident_seq") - n0
        assert serial_launches == 8  # one block scatter per round
        assert coalesced_launches == 2  # one per coalesced group
        assert 2 * coalesced_launches <= serial_launches
        # and the states still match byte-for-byte
        assert piped.batch.export_state() == serial.batch.export_state()

    def test_group_commit_cuts_fsyncs(self, tmp_path):
        d, rounds = _text_rounds(8)
        cid = d.get_text("t").id
        c = obs.counter("persist.wal_fsyncs_total")
        n0 = c.get(mode="per_round")
        pr = ResidentServer(
            "text", 1, capacity=1 << 12, auto_checkpoint=False,
            durable_dir=str(tmp_path / "per_round"),
        )
        for r in rounds:
            pr.ingest(list(r), cid)
        pr.close()
        per_round_fsyncs = c.get(mode="per_round") - n0
        n0 = c.get(mode="group")
        gr = ResidentServer(
            "text", 1, capacity=1 << 12, auto_checkpoint=False,
            durable_dir=str(tmp_path / "group"),
            durable_fsync="group", fsync_window=4,
        )
        for r in rounds:
            gr.ingest(list(r), cid)
        gr.close()
        group_fsyncs = c.get(mode="group") - n0
        # per-round: 1 meta + 8 rounds; group: meta (control records
        # sync immediately) + window at 4 + window at 8
        assert per_round_fsyncs == 9
        assert group_fsyncs == 3
        assert 2 * group_fsyncs <= per_round_fsyncs
        # equal round count, identical recovered state
        from loro_tpu.persist import recover_server

        a = recover_server(str(tmp_path / "per_round"))
        b = recover_server(str(tmp_path / "group"))
        assert a.texts() == b.texts() == [d.get_text("t").to_string()]
        a.close()
        b.close()


class TestWalGroupSync:
    def test_sync_defers_and_counts(self, tmp_path):
        from loro_tpu.persist.wal import WalMeta, WriteAheadLog

        wal = WriteAheadLog(str(tmp_path), fsync="group")
        wal.write_meta(WalMeta("text", 1, fsync_mode="group"))
        for e in range(1, 5):
            wal.append_round(e, None, [b"x"])
        # the meta control record synced at write_meta; the window
        # flush covers exactly the 4 deferred round appends
        assert wal.sync() == 4
        assert wal.sync() == 0  # nothing pending
        wal.append_round(5, None, [b"y"])
        wal.rotate()  # rotation syncs the tail before sealing
        assert wal.sync() == 0
        wal.close()
        # reopen sees every round (nothing stranded)
        back = WriteAheadLog(str(tmp_path), fsync="group")
        assert [e for e, _c, _u in back.rounds_after(0)] == [1, 2, 3, 4, 5]
        assert back.meta.fsync_mode == "group"
        back.close()

    def test_unknown_mode_refused(self, tmp_path):
        from loro_tpu.errors import PersistError
        from loro_tpu.persist.wal import WriteAheadLog

        with pytest.raises(PersistError, match="fsync mode"):
            WriteAheadLog(str(tmp_path), fsync="sometimes")

    def test_inspect_reports_group_mode(self, tmp_path, capsys):
        from loro_tpu.persist.inspect import inspect_dir

        d, rounds = _text_rounds(3)
        cid = d.get_text("t").id
        srv = ResidentServer(
            "text", 1, capacity=1 << 12, auto_checkpoint=False,
            durable_dir=str(tmp_path), durable_fsync="group",
        )
        for r in rounds:
            srv.ingest(list(r), cid)
        srv.close()
        rc = inspect_dir(str(tmp_path))
        out = capsys.readouterr().out
        assert rc == 0
        assert "fsync=group" in out


def _serial_epochs(rounds, cid):
    """The epoch sequence a fresh serial server hands out for these
    rounds (the ack-parity oracle for the pipelined path)."""
    srv = ResidentServer("text", 1, capacity=1 << 12)
    return [srv.ingest(list(r), cid) for r in rounds]


class TestCoalescedNamedBlocks:
    """ISSUE 36: a coalesced group's merged block is over the UNION of
    the documents its rounds name, and its over-capacity fallback ships
    each round's own named block.  Both against the serial path, array
    for array."""

    SLOTS = 8
    # (slot, characters) a round: disjoint sets, then overlapping ones
    # (slot 1 three times, slot 3 twice), then a slot alone.  Slot 1's
    # 35 rows merge into a window of 64 at offset 5: over a capacity of
    # 64, where each round's own window of 32 fits
    ROUNDS = [
        [(0, 20), (1, 3)],
        [(4, 7), (6, 2)],
        [(1, 20), (3, 5), (4, 1)],
        [(1, 12), (3, 20)],
        [(7, 9)],
    ]

    def _rounds(self, docs, marks):
        out = []
        for rnd in self.ROUNDS:
            changes = [None] * self.SLOTS
            for di, n in rnd:
                t = docs[di].get_text("t")
                t.insert(len(t) // 2, "abcdefghijklmnopqrst"[:n])
                if len(t) > 6:
                    t.delete(1, 2)
                docs[di].commit()
                # payload bytes: a live change list would grow with the
                # document's later edits
                changes[di] = strip_envelope(docs[di].export_updates(marks[di]))
                marks[di] = docs[di].oplog_vv()
            out.append(changes)
        return out

    @pytest.mark.parametrize("capacity,launches", [(256, 1), (64, 5)],
                             ids=["merged", "per_round_fallback"])
    @pytest.mark.parametrize("mesh", ["one_device", "mesh8"])
    def test_group_over_disjoint_and_overlapping_documents(
            self, mesh, capacity, launches):
        import jax
        import numpy as np

        from loro_tpu.parallel.fleet import DeviceDocBatch
        from loro_tpu.parallel.mesh import make_mesh

        m = make_mesh(jax.devices()[:1] if mesh == "one_device" else None)
        docs = [LoroDoc(peer=61 + i) for i in range(self.SLOTS)]
        cid = docs[0].get_text("t").id
        for di, d in enumerate(docs):
            d.get_text("t").insert(0, "0123456789"[: 4 + di])
            d.commit()
        base = [strip_envelope(d.export_updates({})) for d in docs]
        marks = [d.oplog_vv() for d in docs]
        rounds = self._rounds(docs, marks)
        serial = DeviceDocBatch(self.SLOTS, capacity, mesh=m)
        grouped = DeviceDocBatch(self.SLOTS, capacity, mesh=m)
        for b in (serial, grouped):
            b.append_payloads(base, cid)
        for r in rounds:
            serial.append_payloads(r, cid)
        c = obs.counter("fleet.device_launches_total")
        n0 = c.get(family="resident_seq")
        grouped.begin_coalesce()
        for r in rounds:
            grouped.append_payloads(r, cid)
        pending = grouped.detach_coalesce()
        assert [list(r[3]) for r in pending.rounds] == [
            [di for di, _n in r] for r in self.ROUNDS]
        grouped.commit_detached(pending)
        assert c.get(family="resident_seq") - n0 == launches
        assert grouped.texts() == serial.texts() == [
            d.get_text("t").to_string() for d in docs]
        for f in serial.cols._fields:
            assert np.array_equal(np.asarray(getattr(grouped.cols, f)),
                                  np.asarray(getattr(serial.cols, f))), f
        assert np.array_equal(np.asarray(grouped.key_hi), np.asarray(serial.key_hi))
        assert np.array_equal(np.asarray(grouped.key_lo), np.asarray(serial.key_lo))
        assert grouped.export_state() == serial.export_state()
