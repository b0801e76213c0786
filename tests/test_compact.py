"""Tombstone compaction (r4 verdict #6, second half): causally-stable
dead subtrees are reclaimed; materialization is unchanged; future
appends — including ops whose Fugue parents are old surviving elements
— still converge with the host oracle after row renumbering.
"""
import random

import numpy as np
import pytest

from loro_tpu import LoroDoc
from loro_tpu.doc import strip_envelope
from loro_tpu.parallel.fleet import DeviceDocBatch


def _stable(batch):
    """Every epoch ingested so far is acked by all replicas."""
    return batch.epoch


class TestCompact:
    def test_reclaims_and_preserves_text(self):
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "hello cruel world")
        doc.commit()
        t.delete(5, 6)  # "hello world"
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        before = int(batch.counts[0])
        n = batch.compact([_stable(batch)])
        assert n > 0 and int(batch.counts[0]) == before - n
        assert batch.texts() == ["hello world"]

    def test_keeps_tombstones_with_unstable_delete(self):
        """A tombstone whose DELETE epoch is not yet acked everywhere
        must stay: a replica that hasn't seen the delete can still
        parent on the char."""
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "abcdef")
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        stable = batch.epoch  # acked BEFORE the delete is ingested
        vv = doc.oplog_vv()
        t.delete(1, 3)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], t.id)
        before = int(batch.counts[0])
        assert batch.compact([stable]) == 0  # the delete is not yet stable
        assert int(batch.counts[0]) == before
        assert batch.texts() == ["aef"]
        # once the delete epoch is acked, it reclaims
        assert batch.compact([batch.epoch]) > 0
        assert batch.texts() == ["aef"]

    def test_keeps_dead_rows_with_live_descendants(self):
        """A tombstoned char that a surviving char parents on must stay
        (the survivor's placement references it)."""
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "ab")
        t.insert(1, "XY")  # X parents on 'a'; Y parents on X (run)
        doc.commit()
        t.delete(1, 1)  # delete X; Y survives and parents on X
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        batch.compact([_stable(batch)])
        assert batch.texts() == ["aYb"]

    def test_append_after_compact_converges(self):
        """Continued concurrent editing after compaction — new ops
        reference surviving (renumbered) elements via the rebuilt id
        map and order engine."""
        rng = random.Random(42)
        a, b = LoroDoc(peer=1), LoroDoc(peer=2)
        ta = a.get_text("t")
        ta.insert(0, "the quick brown fox jumps over the lazy dog")
        a.commit()
        b.import_(a.export_snapshot())
        cid = ta.id
        batch = DeviceDocBatch(n_docs=1, capacity=512)
        batch.append_changes([a.oplog.changes_in_causal_order()], cid)
        mark = a.oplog_vv()
        # epoch 1: edits + deletes, fully synced -> stable
        for d in (a, b):
            t = d.get_text("t")
            for _ in range(6):
                L = len(t)
                if L > 6 and rng.random() < 0.4:
                    t.delete(rng.randrange(L - 2), rng.randint(1, 2))
                else:
                    t.insert(rng.randint(0, L), rng.choice(["zig", "zag"]))
            d.commit()
        a.import_(b.export_updates(a.oplog_vv()))
        b.import_(a.export_updates(b.oplog_vv()))
        batch.append_payloads([strip_envelope(a.export_updates(mark))], cid)
        mark = a.oplog_vv()
        assert batch.texts()[0] == ta.to_string()
        # everything so far is at every peer: stable floor
        n = batch.compact([_stable(batch)])
        assert n > 0
        assert batch.texts()[0] == ta.to_string()
        # epoch 2: more concurrent edits parenting on surviving elements
        for d in (a, b):
            t = d.get_text("t")
            for _ in range(6):
                L = len(t)
                if L > 6 and rng.random() < 0.3:
                    t.delete(rng.randrange(L - 2), 1)
                else:
                    t.insert(rng.randint(0, L), rng.choice(["AB", "c"]))
            t.mark(0, min(4, len(t)), "bold", True)
            d.commit()
        a.import_(b.export_updates(a.oplog_vv()))
        b.import_(a.export_updates(b.oplog_vv()))
        batch.append_payloads([strip_envelope(a.export_updates(mark))], cid)
        assert batch.texts()[0] == ta.to_string()
        assert batch.richtexts()[0] == ta.get_richtext_value()

    def test_compact_with_styles_preserves_richtext(self):
        doc = LoroDoc(peer=7)
        t = doc.get_text("t")
        t.insert(0, "styled region here")
        t.mark(0, 6, "bold", True)
        doc.commit()
        t.delete(7, 7)  # "styled  here" area shrinks
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=128)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        want = t.get_richtext_value()
        assert batch.compact([_stable(batch)]) > 0
        assert batch.richtexts()[0] == want
        assert batch.texts()[0] == t.to_string()

    def test_checkpoint_roundtrip_after_compact(self):
        doc = LoroDoc(peer=3)
        t = doc.get_text("t")
        t.insert(0, "persisted after gc")
        doc.commit()
        t.delete(0, 4)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        batch.compact([_stable(batch)])
        restored = DeviceDocBatch.import_state(batch.export_state())
        assert restored.texts() == [t.to_string()]

    def test_dead_end_anchor_survives_compaction(self):
        """Review r5: a tombstoned END anchor whose start anchor is live
        means "style runs to EOF" — compaction must keep the dead anchor
        row (and its metadata) or the style silently deactivates."""
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "abcdef tail")
        t.mark(0, 6, "bold", True)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=128)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        # tombstone the END anchor row directly (the anchor-death path
        # mark_deleted supports), dating it via a follow-up append
        end_rows = [
            a["row"] for a in batch.anchor_meta[0].values() if not a["start"]
        ]
        assert end_rows
        batch.mark_deleted([(0, end_rows[0])])
        vv = doc.oplog_vv()
        t.insert(len(t), "!")
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], t.id)
        before_rt = batch.richtexts()[0]
        # the dead end anchor must produce a run-to-EOF bold region
        assert any("bold" in (seg.get("attributes") or {}) for seg in before_rt)
        batch.compact([batch.epoch])
        assert batch.richtexts()[0] == before_rt

    @pytest.mark.parametrize("seed", range(8))
    def test_compact_fuzz_concurrent(self, seed):
        """Randomized soak: concurrent edits from two peers, full syncs
        (every ingested epoch becomes stable), compaction every other
        epoch, materialization checked against the host oracle each
        round.  Exercises chain collapse, attach-target protection and
        post-compaction ingest together."""
        rng = random.Random(0xC0117AC7 + seed)
        a, b = LoroDoc(peer=1), LoroDoc(peer=2)
        ta = a.get_text("t")
        ta.insert(0, "seed text for compaction fuzz")
        a.commit()
        b.import_(a.export_snapshot())
        cid = ta.id
        batch = DeviceDocBatch(n_docs=1, capacity=4096)
        batch.append_changes([a.oplog.changes_in_causal_order()], cid)
        mark = a.oplog_vv()
        total_reclaimed = 0
        for epoch in range(8):
            for d in (a, b):
                t = d.get_text("t")
                for _ in range(rng.randint(3, 10)):
                    L = len(t)
                    r = rng.random()
                    if L > 6 and r < 0.45:
                        pos = rng.randrange(L - 1)
                        t.delete(pos, min(rng.randint(1, 4), L - pos))
                    else:
                        t.insert(rng.randint(0, L), rng.choice(
                            ["x", "yz", "hello", "qrs tuv"]
                        ))
                if rng.random() < 0.3 and len(t) > 2:
                    s = rng.randrange(len(t) - 1)
                    t.mark(s, rng.randint(s + 1, len(t)), "bold", True)
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
            batch.append_payloads([strip_envelope(a.export_updates(mark))], cid)
            mark = a.oplog_vv()
            assert batch.texts()[0] == ta.to_string(), f"seed {seed} epoch {epoch}"
            if epoch % 2 == 1:
                total_reclaimed += batch.compact([batch.epoch])
                assert batch.texts()[0] == ta.to_string(), (
                    f"seed {seed} epoch {epoch}: compaction changed the text"
                )
                assert batch.richtexts()[0] == ta.get_richtext_value(), (
                    f"seed {seed} epoch {epoch}: compaction changed styles"
                )
        assert total_reclaimed > 0, f"seed {seed}: fuzz never reclaimed a row"

    def test_list_batch_value_store_shrinks(self):
        """Review r5: as_text=False compaction must also drop stranded
        values and rewrite content ordinals, or host memory grows
        unboundedly with historical inserts."""
        doc = LoroDoc(peer=1)
        lst = doc.get_list("l")
        for i in range(12):
            lst.push(f"item-{i}")
        doc.commit()
        for _ in range(8):  # delete a run of 8 interior items
            lst.delete(2, 1)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64, as_text=False)
        batch.append_changes([doc.oplog.changes_in_causal_order()], lst.id)
        want = lst.get_value()
        n_vals_before = len(batch.value_store[0])
        assert batch.compact([batch.epoch]) > 0
        assert len(batch.value_store[0]) < n_vals_before
        # what the store holds, not only what it counts: the survivors
        assert len(batch.value_store[0].decoded) == len(batch.value_store[0])
        assert batch.value_store[0].payloads == []
        assert batch.values() == [want]
        # the compacted batch keeps ingesting
        vv = doc.oplog_vv()
        lst.push("after-gc")
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], lst.id)
        assert batch.values() == [lst.get_value()]

    def test_direct_mark_deleted_gets_fresh_epoch(self):
        """Review r5: a public mark_deleted call advances the epoch
        clock, so its tombstones are never dated with an epoch replicas
        already acked."""
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "abc")
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=32)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        acked = batch.epoch
        batch.mark_deleted([(0, 1)])  # out-of-band delete
        assert batch.epoch > acked
        assert batch.compact([acked]) == 0  # not reclaimable at old ack

    def test_multi_doc_selective(self):
        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        cid = docs[0].get_text("t").id
        for d in docs:
            t = d.get_text("t")
            t.insert(0, f"doc {d.peer} payload")
            d.commit()
            t.delete(0, 4)
            d.commit()
        batch = DeviceDocBatch(n_docs=3, capacity=64)
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        # compact only doc 1
        n = batch.compact([None, batch.epoch, None])
        assert n > 0
        assert batch.texts() == [d.get_text("t").to_string() for d in docs]


class TestListCompact:
    """as_text=False compaction under CONCURRENT replicas: the expand-
    walk protection is text-only (lists never grow style anchors), so
    isolated list tombstones reclaim — this fuzz gates that narrowing
    against the host oracle."""

    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_concurrent(self, seed):
        rng = random.Random(0x115 + seed)
        a, b = LoroDoc(peer=1), LoroDoc(peer=2)
        la = a.get_list("l")
        for i in range(6):
            la.push(f"base{i}")
        a.commit()
        b.import_(a.export_snapshot())
        cid = la.id
        batch = DeviceDocBatch(n_docs=1, capacity=4096, as_text=False,
                               auto_grow=True)
        batch.append_changes([a.oplog.changes_in_causal_order()], cid)
        mark = a.oplog_vv()
        reclaimed = 0
        for epoch in range(8):
            for d in (a, b):
                lst = d.get_list("l")
                for _ in range(rng.randint(2, 8)):
                    L = len(lst.get_value())
                    r = rng.random()
                    if L > 2 and r < 0.45:
                        p0 = rng.randrange(L - 1)
                        lst.delete(p0, min(rng.randint(1, 3), L - p0))
                    else:
                        lst.insert(rng.randint(0, L), rng.choice(
                            [f"x{epoch}", 1.5, None, {"k": epoch}]
                        ))
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
            batch.append_changes([a.oplog.changes_between(mark, a.oplog_vv())], cid)
            mark = a.oplog_vv()
            assert batch.values() == [la.get_value()], f"seed {seed} ep {epoch}"
            if epoch % 2 == 1:
                reclaimed += batch.compact([batch.epoch])
                assert batch.values() == [la.get_value()], (
                    f"seed {seed} ep {epoch} post-compact"
                )
        assert reclaimed > 0, f"seed {seed}: list fuzz never reclaimed"


class TestMovableCompact:
    """Slot-row compaction: the moves fold's device row references are
    protected and rewritten through the remap."""

    def test_churn_reclaims_and_preserves(self):
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        doc = LoroDoc(peer=1)
        ml = doc.get_movable_list("m")
        ml.push(*[f"v{i}" for i in range(6)])
        doc.commit()
        batch = DeviceMovableBatch(n_docs=1, capacity=512, elem_capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], ml.id)
        vv = doc.oplog_vv()
        for i in range(10):  # move churn: each move tombstones a slot
            ml.move(i % len(ml.get_value()), (i * 3) % len(ml.get_value()))
            ml.set(i % len(ml.get_value()), f"set{i}")
        ml.delete(1, 2)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], ml.id)
        want = ml.get_value()
        assert batch.value_lists() == [want]
        before = int(batch.seq.counts[0])
        n = batch.compact([batch.epoch])
        assert n > 0 and int(batch.seq.counts[0]) == before - n
        assert batch.value_lists() == [want]
        # continued ingest after the remap
        vv = doc.oplog_vv()
        ml.push("post-gc")
        ml.move(0, len(ml.get_value()) - 1)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], ml.id)
        assert batch.value_lists() == [ml.get_value()]

    def test_corrupt_winner_row_rejected_at_import(self):
        """Review r5: a checkpoint whose moves fold references a slot
        row beyond the seq buffer must raise DecodeError, not IndexError
        in a later compact()."""
        from loro_tpu.errors import DecodeError
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        doc = LoroDoc(peer=1)
        ml = doc.get_movable_list("m")
        ml.push("a", "b")
        doc.commit()
        batch = DeviceMovableBatch(n_docs=1, capacity=64, elem_capacity=8)
        batch.append_changes([doc.oplog.changes_in_causal_order()], ml.id)
        batch.moves = batch.moves._replace(
            value=batch.moves.value.at[0, 0].set(1 << 20),  # >> seq.cap
            lamport=batch.moves.lamport.at[0, 0].set(5),  # folded slot
        )
        with pytest.raises(DecodeError, match="winner row"):
            DeviceMovableBatch.import_state(batch.export_state())

    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_concurrent(self, seed):
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        rng = random.Random(0x30AB + seed)
        a, b = LoroDoc(peer=1), LoroDoc(peer=2)
        ma = a.get_movable_list("m")
        ma.push(*[f"s{i}" for i in range(4)])
        a.commit()
        b.import_(a.export_snapshot())
        cid = ma.id
        batch = DeviceMovableBatch(n_docs=1, capacity=4096, elem_capacity=512,
                                   auto_grow=True)
        batch.append_changes([a.oplog.changes_in_causal_order()], cid)
        mark = a.oplog_vv()
        for epoch in range(6):
            for d in (a, b):
                m = d.get_movable_list("m")
                for _ in range(rng.randint(1, 6)):
                    L = len(m.get_value())
                    r = rng.random()
                    if L and r < 0.3:
                        m.move(rng.randrange(L), rng.randrange(L))
                    elif L and r < 0.5:
                        m.set(rng.randrange(L), rng.random())
                    elif L > 2 and r < 0.65:
                        m.delete(rng.randrange(L - 1), 1)
                    else:
                        m.insert(rng.randint(0, L), f"e{epoch}{rng.random():.3f}")
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
            batch.append_changes([a.oplog.changes_between(mark, a.oplog_vv())], cid)
            mark = a.oplog_vv()
            assert batch.value_lists() == [ma.get_value()], f"seed {seed} epoch {epoch}"
            if epoch % 2 == 1:
                batch.compact([batch.epoch])
                assert batch.value_lists() == [ma.get_value()], (
                    f"seed {seed} epoch {epoch} post-compact"
                )


class TestTreeCompact:
    """Move-log compaction: superseded/rejected stable moves drop, the
    materialized tree (parents AND child order) is unchanged, and
    post-compaction ingest still converges."""

    def _mk(self, cap=256, nodes=64):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        return DeviceTreeBatch(n_docs=1, move_capacity=cap, node_capacity=nodes)

    def test_superseded_moves_drop(self):
        doc = LoroDoc(peer=1)
        tr = doc.get_tree("tr")
        root = tr.create()
        kids = [tr.create(root) for _ in range(3)]
        doc.commit()
        batch = self._mk()
        batch.append_changes([doc.oplog.changes_in_causal_order()], tr.id)
        vv = doc.oplog_vv()
        for _ in range(5):  # churn: each move supersedes the previous
            tr.move(kids[0], root, 0)
            tr.move(kids[0], kids[1])
            tr.move(kids[0], root)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], tr.id)
        before_parents = batch.parent_maps()
        before_children = batch.children_maps()
        before = int(batch.counts[0])
        n = batch.compact([batch.epoch])
        assert n > 0 and int(batch.counts[0]) == before - n
        assert batch.parent_maps() == before_parents
        assert batch.children_maps() == before_children

    def test_unstable_moves_kept(self):
        doc = LoroDoc(peer=1)
        tr = doc.get_tree("tr")
        root = tr.create()
        kid = tr.create(root)
        doc.commit()
        batch = self._mk()
        batch.append_changes([doc.oplog.changes_in_causal_order()], tr.id)
        acked = batch.epoch
        vv = doc.oplog_vv()
        tr.move(kid, root, 0)
        tr.move(kid, root, 0)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(vv, doc.oplog_vv())], tr.id)
        before = int(batch.counts[0])
        assert batch.compact([acked]) == 0  # churn is not yet stable
        assert int(batch.counts[0]) == before

    def test_append_after_compact_converges(self):
        rng = random.Random(3)
        a, b = LoroDoc(peer=1), LoroDoc(peer=2)
        ta = a.get_tree("tr")
        root = ta.create()
        for _ in range(4):
            ta.create(root)
        a.commit()
        b.import_(a.export_snapshot())
        cid = ta.id
        batch = self._mk(cap=2048, nodes=128)
        batch.append_changes([a.oplog.changes_in_causal_order()], cid)
        mark = a.oplog_vv()
        for epoch in range(5):
            for d in (a, b):
                t = d.get_tree("tr")
                for _ in range(rng.randint(1, 6)):
                    alive = [x for x in t.nodes()]
                    r = rng.random()
                    if alive and r < 0.3:
                        t.create(rng.choice(alive))
                    elif len(alive) > 2 and r < 0.8:
                        x, y = rng.sample(alive, 2)
                        try:
                            t.move(x, y)
                        except Exception:
                            pass  # cycle rejected locally
                    elif alive and rng.random() < 0.2:
                        try:
                            t.delete(rng.choice(alive))
                        except Exception:
                            pass
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
            batch.append_changes([a.oplog.changes_between(mark, a.oplog_vv())], cid)
            mark = a.oplog_vv()
            host = {t_: ta.parent(t_) for t_ in ta.nodes()}
            assert batch.parent_maps() == [host], f"epoch {epoch}"
            if epoch % 2 == 1:
                batch.compact([batch.epoch])
                assert batch.parent_maps() == [host], f"epoch {epoch} post-compact"

    def test_checkpoint_roundtrip_after_compact(self):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        doc = LoroDoc(peer=5)
        tr = doc.get_tree("tr")
        root = tr.create()
        kid = tr.create(root)
        tr.move(kid, root, 0)
        tr.move(kid, root)
        tr.delete(kid)
        doc.commit()
        batch = self._mk()
        batch.append_changes([doc.oplog.changes_in_causal_order()], tr.id)
        batch.compact([batch.epoch])
        restored = DeviceTreeBatch.import_state(batch.export_state())
        assert restored.parent_maps() == batch.parent_maps()
        assert restored.epoch == batch.epoch


