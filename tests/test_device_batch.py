"""Device-resident batches (text + map): incremental merge vs host."""
import random

import numpy as np
import pytest

from loro_tpu import LoroDoc
from loro_tpu.parallel.fleet import DeviceDocBatch, DeviceMapBatch


class TestDeviceMapBatch:
    @pytest.mark.parametrize("seed", range(4))
    def test_incremental_lww_fuzz(self, seed):
        rng = random.Random(seed)
        pairs = []
        for i in range(3):
            a = LoroDoc(peer=i + 1)
            b = LoroDoc(peer=(1 << 34) + i)  # u64-hi peers exercise halves
            pairs.append((a, b))
        batch = DeviceMapBatch(n_docs=3, slot_capacity=64)
        marks = [a.oplog_vv() for a, _ in pairs]
        for epoch in range(4):
            for a, b in pairs:
                for d in (a, b):
                    m = d.get_map("m")
                    for _ in range(rng.randint(1, 8)):
                        if rng.random() < 0.2:
                            m.delete(rng.choice("abcd"))
                        else:
                            m.set(rng.choice("abcd"), rng.randint(0, 99))
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(a.oplog.changes_between(marks[i], a.oplog_vv()))
                marks[i] = a.oplog_vv()
            batch.append_changes(ups)
            got = batch.root_value_maps("m")
            for i, (a, _) in enumerate(pairs):
                assert got[i] == a.get_map("m").get_value(), f"seed {seed} epoch {epoch} doc {i}"

    def test_empty_append(self):
        batch = DeviceMapBatch(n_docs=2, slot_capacity=8)
        batch.append_changes([None, None])
        assert batch.value_maps() == [{}, {}]

    @pytest.mark.parametrize("seed", range(3))
    def test_native_payload_ingest_lazy_values(self, seed):
        """Payload ingest: native columns fold; only LWW winners decode
        (lazy value cells)."""
        from loro_tpu import ExportMode
        from loro_tpu.native import available

        if not available():
            pytest.skip("native codec unavailable")
        rng = random.Random(seed)
        pairs = []
        for i in range(2):
            a = LoroDoc(peer=i + 1)
            b = LoroDoc(peer=(1 << 35) + i)
            pairs.append((a, b))
        batch = DeviceMapBatch(n_docs=2, slot_capacity=32)
        marks = [a.oplog_vv() for a, _ in pairs]
        for epoch in range(3):
            payloads = []
            for i, (a, b) in enumerate(pairs):
                for d in (a, b):
                    m = d.get_map("m")
                    for _ in range(rng.randint(1, 6)):
                        if rng.random() < 0.2:
                            m.delete(rng.choice("ab"))
                        else:
                            m.set(rng.choice("ab"), {"v": rng.randint(0, 99)})
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
                payloads.append(
                    a.export(ExportMode.UpdatesInRange(marks[i], a.oplog_vv()))[10:]
                )
                marks[i] = a.oplog_vv()
            batch.append_payloads(payloads)
            got = batch.root_value_maps("m")
            for i, (a, _) in enumerate(pairs):
                assert got[i] == a.get_map("m").get_value(), f"seed {seed} epoch {epoch}"

    def test_same_key_two_containers_no_collision(self):
        """Advisor finding: the same key name in two map containers of
        one doc must not collide in value_maps()."""
        a = LoroDoc(peer=1)
        a.get_map("m1").set("k", "v1")
        a.get_map("m2").set("k", "v2")
        a.commit()
        batch = DeviceMapBatch(n_docs=1, slot_capacity=8)
        batch.append_changes([a.oplog.changes_in_causal_order()])
        full = batch.value_maps()[0]
        assert len(full) == 2
        assert {v for v in full.values()} == {"v1", "v2"}
        assert batch.root_value_maps("m1")[0] == {"k": "v1"}
        assert batch.root_value_maps("m2")[0] == {"k": "v2"}

    def test_capacity_overflow_raises(self):
        """Advisor finding: capacity overflow must raise (not a bare
        assert that vanishes under python -O)."""
        a = LoroDoc(peer=1)
        m = a.get_map("m")
        for i in range(5):
            m.set(f"k{i}", i)
        a.commit()
        batch = DeviceMapBatch(n_docs=1, slot_capacity=2)
        with pytest.raises(ValueError, match="slot capacity"):
            batch.append_changes([a.oplog.changes_in_causal_order()])
        # failed append must not poison the batch: state unchanged,
        # and a fitting append still works
        assert batch.slot_of[0] == {} and batch.values[0] == []
        b = LoroDoc(peer=2)
        b.get_map("m").set("k0", "fits")
        b.commit()
        batch.append_changes([b.oplog.changes_in_causal_order()])
        assert batch.root_value_maps("m")[0] == {"k0": "fits"}

    def test_high_bit_peer_tiebreak(self):
        """u32 halves must compare unsigned: peer 2^63-ish beats a small
        peer at equal lamport (would flip under int32 truncation)."""
        big = (1 << 63) - 5
        a, b = LoroDoc(peer=big), LoroDoc(peer=1)
        a.get_map("m").set("k", "from_big")
        a.commit()
        b.get_map("m").set("k", "from_small")
        b.commit()
        a.import_(b.export_updates(a.oplog_vv()))
        batch = DeviceMapBatch(n_docs=1, slot_capacity=8)
        batch.append_changes([a.oplog.changes_in_causal_order()])
        assert batch.root_value_maps("m")[0] == a.get_map("m").get_value() == {"k": "from_big"}


def _changes_between(doc, from_vv):
    doc.commit()
    return doc.oplog.changes_between(from_vv, doc.oplog_vv())


class TestDeviceDocBatch:
    def test_initial_plus_incremental(self):
        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=3, capacity=1024)
        # epoch 1
        marks = []
        for d in docs:
            d.get_text("t").insert(0, f"doc{d.peer} ")
            d.commit()
            marks.append(d.oplog_vv())
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        assert batch.texts() == [d.get_text("t").to_string() for d in docs]
        # epoch 2: edits referencing epoch-1 elements (incl. deletes)
        for d in docs:
            t = d.get_text("t")
            t.insert(4, "-mid-")
            t.delete(0, 2)
        batch.append_changes(
            [_changes_between(d, mv) for d, mv in zip(docs, marks)], cid
        )
        assert batch.texts() == [d.get_text("t").to_string() for d in docs]

    def test_sparse_updates(self):
        docs = [LoroDoc(peer=10 + i) for i in range(4)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=4, capacity=512)
        for d in docs:
            d.get_text("t").insert(0, "base")
            d.commit()
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        marks = [d.oplog_vv() for d in docs]
        docs[1].get_text("t").insert(4, "!")
        docs[3].get_text("t").delete(0, 1)
        updates = [None, _changes_between(docs[1], marks[1]), None, _changes_between(docs[3], marks[3])]
        batch.append_changes(updates, cid)
        assert batch.texts() == [d.get_text("t").to_string() for d in docs]

    @pytest.mark.parametrize("seed", range(4))
    def test_incremental_fuzz_multi_peer(self, seed):
        """Each resident doc is a 2-replica pair with concurrent edits —
        exercises the u64 (peer_hi, peer_lo) sibling lexsort, which
        single-peer docs never touch (review finding).  Peer ids span
        both u32 halves."""
        rng = random.Random(seed)
        n_docs = 3
        pairs = []
        for i in range(n_docs):
            # one small peer id, one > 2^32 (hi half nonzero)
            a = LoroDoc(peer=i + 1)
            b = LoroDoc(peer=(1 << 33) + rng.getrandbits(20) + i)
            pairs.append((a, b))
        cid = pairs[0][0].get_text("t").id
        batch = DeviceDocBatch(n_docs=n_docs, capacity=2048)
        marks = [a.oplog_vv() for a, _ in pairs]
        for epoch in range(5):
            for a, b in pairs:
                for d in (a, b):
                    t = d.get_text("t")
                    for _ in range(rng.randint(1, 6)):
                        if len(t) and rng.random() < 0.35:
                            pos = rng.randint(0, len(t) - 1)
                            t.delete(pos, min(rng.randint(1, 3), len(t) - pos))
                        else:
                            t.insert(rng.randint(0, len(t)), rng.choice(["ab", "z", "qrs"]))
                # merge the pair: concurrent sibling runs now coexist
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
            updates = []
            for i, (a, _) in enumerate(pairs):
                chs = _changes_between(a, marks[i])
                marks[i] = a.oplog_vv()
                updates.append(chs)
            batch.append_changes(updates, cid)
            assert batch.texts() == [
                a.get_text("t").to_string() for a, _ in pairs
            ], f"seed {seed} epoch {epoch}"

    def test_chain_budget_overflow_retry(self):
        """The static chain budget must double-and-retry on overflow
        (review finding: path was uncovered).  Alternating-position
        inserts defeat run merging, forcing many chains."""
        import random

        rng = random.Random(0)
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        for i in range(120):
            t.insert(rng.randint(0, len(t)), "ab")
        doc.commit()
        cid = t.id
        batch = DeviceDocBatch(n_docs=1, capacity=1024)
        batch._c_pad = 16  # force overflow
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        assert batch.texts(use_solver=True) == [t.to_string()]
        assert batch._c_pad > 16  # budget grew
        # incremental key path agrees with the solver
        assert batch.texts() == [t.to_string()]

    def test_uncontracted_solver_agrees(self):
        """merge_docs_u (no contraction) is the differential oracle for
        the chain-contracted resident solver."""
        import random

        import numpy as np

        from loro_tpu.ops.fugue_batch import chain_merge_docs_u, merge_docs_u

        rng = random.Random(3)
        docs = [LoroDoc(peer=i + 1) for i in range(2)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=2, capacity=512)
        for d in docs:
            t = d.get_text("t")
            for _ in range(60):
                if len(t) and rng.random() < 0.3:
                    pos = rng.randint(0, len(t) - 1)
                    t.delete(pos, min(2, len(t) - pos))
                else:
                    t.insert(rng.randint(0, len(t)), rng.choice(["x", "yz"]))
            d.commit()
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        full_codes, full_counts = merge_docs_u(batch.cols)
        chain_codes, chain_counts, _ = chain_merge_docs_u(batch.cols, batch._c_pad)
        np.testing.assert_array_equal(np.asarray(full_counts), np.asarray(chain_counts))
        np.testing.assert_array_equal(np.asarray(full_codes), np.asarray(chain_codes))

    @pytest.mark.parametrize("seed", range(4))
    def test_native_payload_appends(self, seed):
        """Incremental ingest straight from binary payloads (native C++
        delta decode; cross-epoch parents and deletes resolved through
        the id maps; anchor payloads fall back per-payload)."""
        from loro_tpu.native import available

        if not available():
            pytest.skip("native codec unavailable")
        rng = random.Random(seed)
        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=3, capacity=2048)
        marks = [d.oplog_vv() for d in docs]
        for epoch in range(4):
            payloads = []
            for i, d in enumerate(docs):
                t = d.get_text("t")
                for _ in range(rng.randint(1, 10)):
                    r = rng.random()
                    if len(t) and r < 0.3:
                        pos = rng.randint(0, len(t) - 1)
                        t.delete(pos, min(rng.randint(1, 3), len(t) - pos))
                    elif len(t) >= 2 and r < 0.4 and seed % 2:
                        s = rng.randint(0, len(t) - 2)
                        t.mark(s, rng.randint(s + 1, len(t)), "bold", True)
                    else:
                        t.insert(rng.randint(0, len(t)), rng.choice(["ab", "z", "qrs"]))
                d.commit()
                blob = d.export(
                    __import__("loro_tpu").ExportMode.UpdatesInRange(marks[i], d.oplog_vv())
                )
                marks[i] = d.oplog_vv()
                payloads.append(blob[10:])  # strip envelope
            batch.append_payloads(payloads, cid)
            assert batch.texts() == [
                d.get_text("t").to_string() for d in docs
            ], f"seed {seed} epoch {epoch}"

    def test_native_cross_epoch_anchor_parent(self):
        """Regression (review repro): epoch-2 insert parenting on an
        epoch-1 mark anchor must resolve natively (anchors enter the id
        map)."""
        from loro_tpu import ExportMode
        from loro_tpu.native import available

        if not available():
            pytest.skip("native codec unavailable")
        doc = LoroDoc(peer=1)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "abcd")
        t.mark(1, 3, "bold", True)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256)
        batch.append_payloads([doc.export_updates()[10:]], cid)
        mark = doc.oplog_vv()
        t.insert(1, "X")  # parents near the start anchor
        t.insert(4, "Y")
        doc.commit()
        batch.append_payloads(
            [doc.export(ExportMode.UpdatesInRange(mark, doc.oplog_vv()))[10:]], cid
        )
        assert batch.texts() == [t.to_string()]

    def test_payloads_on_value_batch_falls_back(self):
        """as_text=False + payloads routes through the python decoder
        (review finding: used to assert)."""
        doc = LoroDoc(peer=1)
        cid = doc.get_list("l").id
        doc.get_list("l").push(1, {"k": 2})
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64, as_text=False)
        batch.append_payloads([doc.export_updates()[10:]], cid)
        assert batch.values() == [doc.get_list("l").get_value()]

    @pytest.mark.parametrize("seed", range(3))
    def test_list_value_batch(self, seed):
        """as_text=False batches hold List containers (value payloads
        incl. nested structures)."""
        rng = random.Random(seed)
        docs = [LoroDoc(peer=i + 1) for i in range(2)]
        cid = docs[0].get_list("l").id
        batch = DeviceDocBatch(n_docs=2, capacity=512, as_text=False)
        marks = [d.oplog_vv() for d in docs]
        for epoch in range(3):
            for d in docs:
                l = d.get_list("l")
                for _ in range(rng.randint(1, 8)):
                    if len(l) and rng.random() < 0.3:
                        l.delete(rng.randint(0, len(l) - 1), 1)
                    else:
                        l.insert(
                            rng.randint(0, len(l)),
                            rng.choice([1, "s", None, 2.5, {"n": [1]}]),
                        )
                d.commit()
            ups = []
            for i, d in enumerate(docs):
                ups.append(d.oplog.changes_between(marks[i], d.oplog_vv()))
                marks[i] = d.oplog_vv()
            batch.append_changes(ups, cid)
            assert batch.values() == [d.get_list("l").get_value() for d in docs]

    def test_capacity_guard(self):
        doc = LoroDoc(peer=1)
        cid = doc.get_text("t").id
        doc.get_text("t").insert(0, "x" * 100)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=64)
        with pytest.raises(RuntimeError):
            batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        # failed append leaves the batch untouched (review finding)
        assert batch.counts[0] == 0 and not batch.id2row[0]

    def test_anchor_parent_resolution(self):
        """Inserts adjacent to mark boundaries parent on anchor elements
        (review finding: anchors must register in the id map)."""
        doc = LoroDoc(peer=1)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "bold text")
        t.mark(0, 4, "bold", True)
        t.insert(4, "er")  # lands adjacent to the end anchor
        t.insert(0, ">")  # adjacent to the start anchor
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256)
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        assert batch.texts() == [t.to_string()]

    def test_incremental_after_marks(self):
        doc = LoroDoc(peer=1)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "abc")
        t.mark(0, 3, "bold", True)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256)
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        mark = doc.oplog_vv()
        t.insert(3, "d")  # parents on the end-anchor region
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], cid)
        assert batch.texts() == [t.to_string()]


class TestIncrementalOrder:
    @pytest.mark.parametrize("seed", range(3))
    def test_key_path_matches_solver(self, seed):
        """The ShadowOrder key materialization must agree with the full
        chain-contracted rank solve after every sync epoch."""
        rng = random.Random(40 + seed)
        docs = [LoroDoc(peer=i + 1) for i in range(2)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=2, capacity=4096)
        marks = [d.oplog_vv() for d in docs]
        for epoch in range(5):
            for d in docs:
                t = d.get_text("t")
                for _ in range(rng.randint(1, 12)):
                    if len(t) and rng.random() < 0.3:
                        pos = rng.randrange(len(t))
                        t.delete(pos, min(2, len(t) - pos))
                    else:
                        t.insert(rng.randint(0, len(t)), rng.choice(["a", "bc "]))
                d.commit()
            docs[0].import_(docs[1].export_updates(docs[0].oplog_vv()))
            docs[1].import_(docs[0].export_updates(docs[1].oplog_vv()))
            ups = []
            for i, d in enumerate(docs):
                ups.append(d.oplog.changes_between(marks[i], d.oplog_vv()))
                marks[i] = d.oplog_vv()
            batch.append_changes(ups, cid)
            want = [d.get_text("t").to_string() for d in docs]
            assert batch.texts() == want, f"key path diverged epoch {epoch}"
            assert batch.texts(use_solver=True) == want

    def test_append_soak_sublinear(self):
        """Append-heavy steady state: per-sync ingest cost must not grow
        with the standing table (the old design re-ranked everything).
        Deterministic check: zero renumbers + O(1) fast-path placement;
        plus a loose wall-clock ratio guard."""
        import time

        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        cid = t.id
        batch = DeviceDocBatch(n_docs=1, capacity=1 << 15)
        mark = doc.oplog_vv()

        def sync(n_chars):
            nonlocal mark
            t.insert(len(t), "x" * n_chars)
            doc.commit()
            ups = doc.oplog.changes_between(mark, doc.oplog_vv())
            mark = doc.oplog_vv()
            t0 = time.perf_counter()
            batch.append_changes([ups], cid)
            return time.perf_counter() - t0

        times = [sync(200) for _ in range(40)]
        assert batch.order[0].renumbers == 0
        early = sorted(times[2:10])[:4]
        late = sorted(times[-8:])[:4]
        assert sum(late) < 6 * sum(early), (
            f"per-sync ingest grew: early {sum(early):.4f}s late {sum(late):.4f}s"
        )
        assert batch.texts() == [t.to_string()]


class TestResidentRichtext:
    """richtexts(): resident style resolution on device vs the host
    oracle (the incremental sibling of the one-shot richtext kernels)."""

    def test_basic_marks(self):
        doc = LoroDoc(peer=1)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "hello world")
        t.mark(0, 5, "bold", True)
        t.mark(3, 8, "color", "red")
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256)
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        assert batch.richtexts() == [t.get_richtext_value()]

    def test_incremental_marks_and_unmark(self):
        doc = LoroDoc(peer=1)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "abcdefgh")
        t.mark(0, 6, "bold", True)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=512)
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        mark = doc.oplog_vv()
        t.unmark(2, 4, "bold")
        t.insert(3, "XY")  # inside the formerly-bold range
        t.delete(0, 1)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], cid)
        assert batch.richtexts() == [t.get_richtext_value()]
        assert batch.texts() == [t.to_string()]

    def test_concurrent_multi_doc_epochs(self):
        pairs = []
        for i in range(3):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=2 * i + 2)
            a.get_text("t").insert(0, "the quick brown fox")
            b.import_(a.export_updates(b.oplog_vv()))
            pairs.append((a, b))
        cid = pairs[0][0].get_text("t").id
        batch = DeviceDocBatch(n_docs=3, capacity=1024)
        marks = [a.oplog_vv() for a, _ in pairs]
        # epoch 0: initial import of the shared base
        batch.append_changes(
            [a.oplog.changes_in_causal_order() for a, _ in pairs], cid
        )
        rng = random.Random(5)
        for epoch in range(3):
            for a, b in pairs:
                for d in (a, b):
                    t = d.get_text("t")
                    L = len(t)
                    r = rng.random()
                    if L >= 2 and r < 0.5:
                        s = rng.randrange(L - 1)
                        e = rng.randint(s + 1, L)
                        k = rng.choice(["bold", "color"])
                        if rng.random() < 0.3:
                            t.unmark(s, e, k)
                        else:
                            t.mark(s, e, k, rng.choice([True, "red", 7]))
                    elif L > 4 and r < 0.7:
                        p = rng.randrange(L - 1)
                        t.delete(p, min(2, L - p))
                    else:
                        t.insert(rng.randint(0, L), rng.choice(["zz", "q"]))
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(a.oplog.changes_between(marks[i], a.oplog_vv()))
                marks[i] = a.oplog_vv()
            batch.append_changes(ups, cid)
            got = batch.richtexts()
            for i, (a, _) in enumerate(pairs):
                want = a.get_text("t").get_richtext_value()
                assert got[i] == want, f"epoch {epoch} doc {i}:\n{got[i]}\nvs\n{want}"

    def test_payload_ingest_with_marks(self):
        from loro_tpu.doc import strip_envelope

        doc = LoroDoc(peer=3)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "styled text here")
        t.mark(0, 6, "bold", True)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256)
        batch.append_payloads([strip_envelope(doc.export_updates(None))], cid)
        assert batch.richtexts() == [t.get_richtext_value()]


class TestDeviceTreeBatch:
    """Resident movable-tree logs: incremental appends + device replay
    vs host TreeState and the one-shot fleet path."""

    def test_initial_plus_incremental(self):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        doc = LoroDoc(peer=1)
        tr = doc.get_tree("tr")
        a = tr.create()
        b = tr.create(a)
        c = tr.create(b)
        doc.commit()
        cid = tr.id
        batch = DeviceTreeBatch(n_docs=1, move_capacity=256, node_capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        mark = doc.oplog_vv()
        tr.move(c, a)
        tr.delete(b)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], cid)
        host = {t: tr.parent(t) for t in tr.nodes()}
        assert batch.parent_maps() == [host]

    @pytest.mark.parametrize("seed", range(4))
    def test_incremental_fuzz_concurrent(self, seed):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        rng = random.Random(seed)
        pairs = []
        for i in range(3):
            a = LoroDoc(peer=2 * i + 1)
            b = LoroDoc(peer=2 * i + 2)
            tr = a.get_tree("tr")
            root = tr.create()
            for _ in range(3):
                tr.create(root)
            b.import_(a.export_snapshot())
            pairs.append((a, b))
        cid = pairs[0][0].get_tree("tr").id
        batch = DeviceTreeBatch(n_docs=3, move_capacity=1024, node_capacity=128)
        marks = [a.oplog_vv() for a, _ in pairs]
        batch.append_changes(
            [a.oplog.changes_in_causal_order() for a, _ in pairs], cid
        )
        for epoch in range(4):
            for a, b in pairs:
                for d in (a, b):
                    tr = d.get_tree("tr")
                    nodes = [t for t in tr.nodes()]
                    r = rng.random()
                    if not nodes or r < 0.3:
                        tr.create(rng.choice(nodes) if nodes and rng.random() < 0.7 else None)
                    elif r < 0.6 and len(nodes) >= 2:
                        t1, t2 = rng.sample(nodes, 2)
                        try:
                            tr.move(t1, t2, rng.randint(0, 1))
                        except Exception:
                            pass  # cycle rejected locally
                    elif r < 0.75:
                        tr.delete(rng.choice(nodes))
                    else:
                        tr.create(rng.choice(nodes), index=0)
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
                assert a.get_deep_value() == b.get_deep_value()
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(a.oplog.changes_between(marks[i], a.oplog_vv()))
                marks[i] = a.oplog_vv()
            batch.append_changes(ups, cid)
            got = batch.parent_maps()
            for i, (a, _) in enumerate(pairs):
                tr = a.get_tree("tr")
                host = {t: tr.parent(t) for t in tr.nodes()}
                assert got[i] == host, f"seed {seed} epoch {epoch} doc {i}"

    def test_children_order_matches_host(self):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        docs = []
        for i in range(2):
            a, b = LoroDoc(peer=700 + 2 * i), LoroDoc(peer=701 + 2 * i)
            tr = a.get_tree("tr")
            root = tr.create()
            kids = [tr.create(root) for _ in range(3)]
            b.import_(a.export_snapshot())
            a.get_tree("tr").move(kids[2], root, 0)
            b.get_tree("tr").create(root, index=1)
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
            a.commit()
            docs.append(a)
        cid = docs[0].get_tree("tr").id
        batch = DeviceTreeBatch(n_docs=2, move_capacity=256, node_capacity=64)
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        got = batch.children_maps()
        for i, d in enumerate(docs):
            tr = d.get_tree("tr")
            host = {}
            for t in [None] + tr.nodes():
                ch = tr.children(t)
                if ch:
                    host[t] = ch
            assert got[i] == host, f"doc {i}"

    def test_capacity_guards(self):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        doc = LoroDoc(peer=1)
        tr = doc.get_tree("tr")
        for _ in range(10):
            tr.create()
        doc.commit()
        batch = DeviceTreeBatch(n_docs=1, move_capacity=8, node_capacity=64)
        with pytest.raises(RuntimeError, match="move capacity"):
            batch.append_changes([doc.oplog.changes_in_causal_order()], tr.id)
        batch2 = DeviceTreeBatch(n_docs=1, move_capacity=64, node_capacity=4)
        with pytest.raises(RuntimeError, match="node capacity"):
            batch2.append_changes([doc.oplog.changes_in_causal_order()], tr.id)

    def test_failed_append_leaves_batch_untouched(self):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        doc = LoroDoc(peer=1)
        tr = doc.get_tree("tr")
        r = tr.create()
        tr.create(r)
        doc.commit()
        batch = DeviceTreeBatch(n_docs=1, move_capacity=64, node_capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], tr.id)
        before_nodes = list(batch.nodes[0])
        before_counts = batch.counts.copy()
        # an over-capacity epoch must not leak phantom node registrations
        doc2 = LoroDoc(peer=2)
        tr2 = doc2.get_tree("tr")
        for _ in range(80):
            tr2.create()
        doc2.commit()
        with pytest.raises(RuntimeError):
            batch.append_changes([doc2.oplog.changes_in_causal_order()], tr.id)
        assert batch.nodes[0] == before_nodes
        assert (batch.counts == before_counts).all()
        # the batch stays fully usable
        mark = doc.oplog_vv()
        tr.delete(r)
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], tr.id)
        host = {t: tr.parent(t) for t in tr.nodes()}
        assert batch.parent_maps() == [host]


class TestDeviceCounterBatch:
    def test_incremental_sums(self):
        from loro_tpu.parallel.fleet import DeviceCounterBatch

        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        batch = DeviceCounterBatch(n_docs=3, slot_capacity=8)
        marks = []
        for d in docs:
            d.get_counter("hits").increment(2.5)
            d.get_counter("views").increment(1)
            d.commit()
            marks.append(d.oplog_vv())
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs])
        for d, mv in zip(docs, marks):
            d.get_counter("hits").increment(-1)
            d.commit()
        batch.append_changes(
            [_changes_between(d, mv) for d, mv in zip(docs, marks)]
        )
        got = batch.value_maps()
        for i, d in enumerate(docs):
            want = {
                d.get_counter("hits").id: d.get_counter("hits").get_value(),
                d.get_counter("views").id: d.get_counter("views").get_value(),
            }
            assert got[i] == want, f"doc {i}"

    def test_concurrent_replicas(self):
        from loro_tpu.parallel.fleet import DeviceCounterBatch

        a, b = LoroDoc(peer=1), LoroDoc(peer=2)
        a.get_counter("c").increment(10)
        b.get_counter("c").increment(-3)
        a.commit(); b.commit()
        a.import_(b.export_updates(a.oplog_vv()))
        b.import_(a.export_updates(b.oplog_vv()))
        assert a.get_counter("c").get_value() == b.get_counter("c").get_value() == 7
        batch = DeviceCounterBatch(n_docs=1, slot_capacity=4)
        batch.append_changes([a.oplog.changes_in_causal_order()])
        assert batch.value_maps()[0][a.get_counter("c").id] == 7

    def test_slot_capacity_guard(self):
        from loro_tpu.parallel.fleet import DeviceCounterBatch

        d = LoroDoc(peer=1)
        for i in range(5):
            d.get_counter(f"c{i}").increment(1)
        d.commit()
        batch = DeviceCounterBatch(n_docs=1, slot_capacity=2)
        with pytest.raises(RuntimeError):
            batch.append_changes([d.oplog.changes_in_causal_order()])
        assert batch.slot_of[0] == {}  # nothing leaked

    @pytest.mark.parametrize("seed", range(4))
    def test_incremental_fuzz_vs_host(self, seed):
        """Differential fuzz vs host CounterState (kernel-test invariant):
        integer deltas < 2^24 are exact in the f32 device fold."""
        from loro_tpu.parallel.fleet import DeviceCounterBatch

        rng = random.Random(seed)
        pairs = []
        for i in range(3):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=2 * i + 2)
            pairs.append((a, b))
        batch = DeviceCounterBatch(n_docs=3, slot_capacity=16)
        marks = [a.oplog_vv() for a, _ in pairs]
        names = ["hits", "views", "errs"]
        for epoch in range(4):
            for a, b in pairs:
                for d in (a, b):
                    for _ in range(rng.randint(1, 5)):
                        d.get_counter(rng.choice(names)).increment(
                            rng.randint(-1000, 1000)
                        )
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(a.oplog.changes_between(marks[i], a.oplog_vv()))
                marks[i] = a.oplog_vv()
            batch.append_changes(ups)
            got = batch.value_maps()
            for i, (a, _) in enumerate(pairs):
                for nm in names:
                    c = a.get_counter(nm)
                    assert got[i].get(c.id, 0.0) == c.get_value(), (
                        f"seed {seed} epoch {epoch} doc {i} {nm}"
                    )

    def test_fractional_deltas_f32_contract(self):
        """Fractional deltas match to f32 rounding (documented contract:
        x64 is disabled on the TPU path)."""
        from loro_tpu.parallel.fleet import DeviceCounterBatch

        d = LoroDoc(peer=1)
        for _ in range(10):
            d.get_counter("c").increment(0.1)
        d.commit()
        batch = DeviceCounterBatch(n_docs=1, slot_capacity=4)
        batch.append_changes([d.oplog.changes_in_causal_order()])
        got = batch.value_maps()[0][d.get_counter("c").id]
        assert got == pytest.approx(d.get_counter("c").get_value(), rel=1e-6)


class TestDeviceMovableBatch:
    """Resident MovableList: incremental slots + element LWW folds vs
    the host MovableListState."""

    def test_initial_plus_incremental(self):
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        doc = LoroDoc(peer=1)
        ml = doc.get_movable_list("m")
        ml.push("a", "b", "c")
        doc.commit()
        cid = ml.id
        batch = DeviceMovableBatch(n_docs=1, capacity=256, elem_capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], cid)
        assert batch.value_lists() == [ml.get_value()]
        mark = doc.oplog_vv()
        ml.move(2, 0)
        ml.set(1, "B")
        ml.delete(2, 1)
        ml.insert(1, "x")
        doc.commit()
        batch.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], cid)
        assert batch.value_lists() == [ml.get_value()]

    @pytest.mark.parametrize("seed", range(4))
    def test_incremental_fuzz_concurrent(self, seed):
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        rng = random.Random(seed)
        pairs = []
        for i in range(3):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=2 * i + 2)
            a.get_movable_list("m").push(*[f"s{j}" for j in range(3)])
            b.import_(a.export_snapshot())
            pairs.append((a, b))
        cid = pairs[0][0].get_movable_list("m").id
        batch = DeviceMovableBatch(n_docs=3, capacity=2048, elem_capacity=256)
        marks = [a.oplog_vv() for a, _ in pairs]
        batch.append_changes(
            [a.oplog.changes_in_causal_order() for a, _ in pairs], cid
        )
        for epoch in range(4):
            for a, b in pairs:
                for d in (a, b):
                    ml = d.get_movable_list("m")
                    L = len(ml)
                    r = rng.random()
                    if L == 0 or r < 0.3:
                        ml.insert(rng.randint(0, L), f"v{rng.randrange(100)}")
                    elif r < 0.5 and L >= 2:
                        ml.move(rng.randrange(L), rng.randrange(L))
                    elif r < 0.7:
                        ml.set(rng.randrange(L), f"w{rng.randrange(100)}")
                    elif r < 0.85:
                        ml.delete(rng.randrange(L), 1)
                    else:
                        ml.push(f"p{rng.randrange(100)}")
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
                assert a.get_deep_value() == b.get_deep_value()
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(a.oplog.changes_between(marks[i], a.oplog_vv()))
                marks[i] = a.oplog_vv()
            batch.append_changes(ups, cid)
            got = batch.value_lists()
            for i, (a, _) in enumerate(pairs):
                want = a.get_movable_list("m").get_value()
                assert got[i] == want, f"seed {seed} epoch {epoch} doc {i}"

    def test_elem_capacity_guard_atomic(self):
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        doc = LoroDoc(peer=1)
        ml = doc.get_movable_list("m")
        ml.push(*[str(i) for i in range(10)])
        doc.commit()
        batch = DeviceMovableBatch(n_docs=1, capacity=256, elem_capacity=4)
        with pytest.raises(RuntimeError, match="element capacity"):
            batch.append_changes([doc.oplog.changes_in_causal_order()], ml.id)
        assert batch.elem_ids[0] == {} and batch.values[0] == []


class TestResidentCheckpoint:
    """Fleet-scale checkpoint/resume: export_state/import_state round-
    trips a live DeviceDocBatch through the LTKV store and the restored
    batch keeps working (materialization AND further appends)."""

    def test_text_roundtrip_and_continue(self):
        from loro_tpu.parallel.fleet import DeviceDocBatch

        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=3, capacity=1024)
        for d in docs:
            d.get_text("t").insert(0, f"doc{d.peer} base ")
            d.get_text("t").mark(0, 4, "bold", True)
            d.commit()
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        marks = [d.oplog_vv() for d in docs]
        for d in docs:
            d.get_text("t").insert(5, "-mid-")
            d.get_text("t").delete(0, 2)
            d.commit()
        batch.append_changes(
            [_changes_between(d, mv) for d, mv in zip(docs, marks)], cid
        )
        blob = batch.export_state()
        restored = DeviceDocBatch.import_state(blob)
        assert restored.texts() == [d.get_text("t").to_string() for d in docs]
        assert restored.richtexts() == [
            d.get_text("t").get_richtext_value() for d in docs
        ]
        # the restored batch must accept FURTHER appends (order engine
        # rebuilt by replay)
        marks = [d.oplog_vv() for d in docs]
        for d in docs:
            d.get_text("t").insert(0, "x")
            d.get_text("t").mark(1, 3, "color", "red")
            d.commit()
        restored.append_changes(
            [_changes_between(d, mv) for d, mv in zip(docs, marks)], cid
        )
        assert restored.texts() == [d.get_text("t").to_string() for d in docs]
        assert restored.richtexts() == [
            d.get_text("t").get_richtext_value() for d in docs
        ]

    def test_list_value_batch_roundtrip(self):
        from loro_tpu.parallel.fleet import DeviceDocBatch

        doc = LoroDoc(peer=5)
        lst = doc.get_list("l")
        for v in [1, "two", None, 2.5, {"k": [1, 2]}, b"bytes"]:
            lst.push(v)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256, as_text=False)
        batch.append_changes([doc.oplog.changes_in_causal_order()], lst.id)
        restored = DeviceDocBatch.import_state(batch.export_state())
        assert restored.values() == [lst.get_value()]

    def test_corrupt_state_raises(self):
        from loro_tpu.errors import DecodeError
        from loro_tpu.parallel.fleet import DeviceDocBatch

        doc = LoroDoc(peer=1)
        doc.get_text("t").insert(0, "hello")
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=128)
        batch.append_changes([doc.oplog.changes_in_causal_order()], doc.get_text("t").id)
        blob = bytearray(batch.export_state())
        blob[25] ^= 0xFF
        with pytest.raises(DecodeError):
            DeviceDocBatch.import_state(bytes(blob))

    def test_corrupt_anchor_row_raises(self):
        """Advisor r4: an anchor whose row ordinal exceeds the doc's row
        count must raise DecodeError, not silently clip style positions."""
        from loro_tpu.codec.binary import Reader
        from loro_tpu.errors import DecodeError
        from loro_tpu.parallel.fleet import DeviceDocBatch
        from loro_tpu.storage import MemKvStore

        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "styled")
        t.mark(0, 3, "bold", True)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=128)
        batch.append_changes([doc.oplog.changes_in_causal_order()], t.id)
        kv = MemKvStore()
        kv.import_all(batch.export_state())
        anch = bytearray(kv.get(b"doc/00000000/anchors"))
        r = Reader(bytes(anch))
        assert r.varint() >= 1  # at least one anchor present
        r.varint()  # peer index
        r.zigzag()  # counter
        row_off = r.i
        assert anch[row_off] < 0x80  # single-byte varint, patchable in place
        anch[row_off] = 0x7F  # row 127 >= count
        kv.set(b"doc/00000000/anchors", bytes(anch))
        with pytest.raises(DecodeError, match="anchor row"):
            DeviceDocBatch.import_state(kv.export_all())

    def test_nested_container_values_roundtrip(self):
        """Regression (review finding): values holding non-root
        ContainerIDs must round-trip — the cid table's peers register
        BEFORE the peer table is emitted."""
        from loro_tpu.parallel.fleet import DeviceDocBatch

        doc = LoroDoc(peer=99)
        lst = doc.get_list("l")
        lst.push("plain")
        from loro_tpu import ContainerType

        child = lst.push_container(ContainerType.Map)
        child.set("k", 1)
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256, as_text=False)
        batch.append_changes([doc.oplog.changes_in_causal_order()], lst.id)
        restored = DeviceDocBatch.import_state(batch.export_state())
        # the restored value list carries the same (plain, ContainerID)
        assert restored.value_store[0] == batch.value_store[0]

    def test_cross_mesh_restore(self):
        """Export on a narrower mesh, import on the full 8-device mesh."""
        import jax as _jax

        from loro_tpu.parallel.fleet import DeviceDocBatch
        from loro_tpu.parallel.mesh import make_mesh

        small = make_mesh(_jax.devices("cpu")[:2])
        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        cid = docs[0].get_text("t").id
        batch = DeviceDocBatch(n_docs=3, capacity=256, mesh=small)
        for d in docs:
            d.get_text("t").insert(0, f"cross {d.peer}")
            d.commit()
        batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], cid)
        restored = DeviceDocBatch.import_state(batch.export_state())  # 8-dev mesh
        assert restored.texts() == [d.get_text("t").to_string() for d in docs]

    def test_map_batch_roundtrip(self):
        from loro_tpu.parallel.fleet import DeviceMapBatch

        pairs = []
        for i in range(2):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=(1 << 33) + i)
            for d in (a, b):
                m = d.get_map("m")
                m.set("k1", d.peer)
                m.set("k2", {"nested": [1, 2]})
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
            pairs.append((a, b))
        batch = DeviceMapBatch(n_docs=2, slot_capacity=16)
        batch.append_changes([a.oplog.changes_in_causal_order() for a, _ in pairs])
        restored = DeviceMapBatch.import_state(batch.export_state())
        assert restored.root_value_maps("m") == [
            a.get_map("m").get_value() for a, _ in pairs
        ]
        # continues folding
        marks = [a.oplog_vv() for a, _ in pairs]
        for a, _ in pairs:
            a.get_map("m").set("k3", "post")
            a.commit()
        restored.append_changes(
            [a.oplog.changes_between(m, a.oplog_vv()) for (a, _), m in zip(pairs, marks)]
        )
        assert restored.root_value_maps("m") == [
            a.get_map("m").get_value() for a, _ in pairs
        ]

    def test_tree_batch_roundtrip(self):
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        doc = LoroDoc(peer=1)
        tr = doc.get_tree("tr")
        root = tr.create()
        kids = [tr.create(root) for _ in range(3)]
        tr.move(kids[2], root, 0)
        tr.delete(kids[0])
        doc.commit()
        batch = DeviceTreeBatch(n_docs=1, move_capacity=128, node_capacity=32)
        batch.append_changes([doc.oplog.changes_in_causal_order()], tr.id)
        restored = DeviceTreeBatch.import_state(batch.export_state())
        assert restored.parent_maps() == [{t: tr.parent(t) for t in tr.nodes()}]
        host_kids = {}
        for t in [None] + tr.nodes():
            ch = tr.children(t)
            if ch:
                host_kids[t] = ch
        assert restored.children_maps() == [host_kids]
        # continues appending
        mark = doc.oplog_vv()
        tr.create(kids[1])
        doc.commit()
        restored.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], tr.id)
        assert restored.parent_maps() == [{t: tr.parent(t) for t in tr.nodes()}]

    def test_counter_batch_roundtrip(self):
        from loro_tpu.parallel.fleet import DeviceCounterBatch

        doc = LoroDoc(peer=1)
        doc.get_counter("c").increment(41)
        doc.commit()
        batch = DeviceCounterBatch(n_docs=1, slot_capacity=8)
        batch.append_changes([doc.oplog.changes_in_causal_order()])
        restored = DeviceCounterBatch.import_state(batch.export_state())
        mark = doc.oplog_vv()
        doc.get_counter("c").increment(1)
        doc.commit()
        restored.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())])
        assert restored.value_maps()[0][doc.get_counter("c").id] == 42

    def test_movable_batch_roundtrip(self):
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        doc = LoroDoc(peer=1)
        ml = doc.get_movable_list("ml")
        ml.push("a", "b", "c")
        ml.move(2, 0)
        ml.set(1, "B")
        doc.commit()
        batch = DeviceMovableBatch(n_docs=1, capacity=256, elem_capacity=64)
        batch.append_changes([doc.oplog.changes_in_causal_order()], ml.id)
        restored = DeviceMovableBatch.import_state(batch.export_state())
        assert restored.value_lists() == [ml.get_value()]
        # continues: move + set + delete after restore
        mark = doc.oplog_vv()
        ml.move(0, 2)
        ml.set(0, "zz")
        ml.delete(1, 1)
        doc.commit()
        restored.append_changes([doc.oplog.changes_between(mark, doc.oplog_vv())], ml.id)
        assert restored.value_lists() == [ml.get_value()]

    def test_checkpoint_mutation_fuzz(self):
        """random_import analog for the checkpoint formats: mutated
        blobs either import (and materialize) or raise DecodeError —
        never crash or hang."""
        from loro_tpu.errors import DecodeError
        from loro_tpu.parallel.fleet import (
            DeviceCounterBatch,
            DeviceDocBatch,
            DeviceMapBatch,
            DeviceMovableBatch,
            DeviceTreeBatch,
        )

        doc = LoroDoc(peer=1)
        doc.get_text("t").insert(0, "fuzz base text")
        doc.get_text("t").mark(0, 4, "bold", True)
        doc.get_map("m").set("k", 1)
        tr = doc.get_tree("tr")
        r_ = tr.create()
        tr.create(r_)
        doc.get_counter("c").increment(3)
        doc.get_movable_list("ml").push("a", "b")
        doc.commit()
        chs = doc.oplog.changes_in_causal_order()

        cases = []
        b1 = DeviceDocBatch(1, 256)
        b1.append_changes([chs], doc.get_text("t").id)
        cases.append((DeviceDocBatch, b1.export_state(), lambda b: (b.texts(), b.richtexts())))
        b2 = DeviceMapBatch(1, 16)
        b2.append_changes([chs])
        cases.append((DeviceMapBatch, b2.export_state(), lambda b: b.value_maps()))
        b3 = DeviceTreeBatch(1, 64, 16)
        b3.append_changes([chs], tr.id)
        cases.append((DeviceTreeBatch, b3.export_state(), lambda b: (b.parent_maps(), b.children_maps())))
        b4 = DeviceCounterBatch(1, 8)
        b4.append_changes([chs])
        cases.append((DeviceCounterBatch, b4.export_state(), lambda b: b.value_maps()))
        b5 = DeviceMovableBatch(1, 128, 32)
        b5.append_changes([chs], doc.get_movable_list("ml").id)
        cases.append((DeviceMovableBatch, b5.export_state(), lambda b: b.value_lists()))

        rng = random.Random(13)
        for cls, blob, materialize in cases:
            # pristine must import + materialize
            materialize(cls.import_state(blob))
            for _ in range(40):
                bad = bytearray(blob)
                for _ in range(rng.randrange(1, 4)):
                    bad[rng.randrange(len(bad))] = rng.randrange(256)
                try:
                    restored = cls.import_state(bytes(bad))
                    materialize(restored)
                except DecodeError:
                    pass
                # NOTHING else is acceptable: import validates size
                # fields, slot/elem/value ordinals and content codes, so
                # a corrupt blob either imports (and materializes) or
                # raises DecodeError — a raw IndexError here is a bug


class TestNativeAnchorIngest:
    """Anchor-bearing payloads must ingest NATIVELY (round-4: the C++
    explode now surfaces anchor metadata; no python fallback)."""

    def _no_fallback(self, monkeypatch, batch):
        def boom(*a, **k):
            raise AssertionError("python fallback must not run for anchor payloads")

        monkeypatch.setattr(batch, "_python_rows", boom)

    def test_marks_payload_native(self, monkeypatch):
        from loro_tpu.doc import strip_envelope
        from loro_tpu.native import available
        from loro_tpu.parallel.fleet import DeviceDocBatch

        if not available():
            pytest.skip("native codec unavailable")
        doc = LoroDoc(peer=3)
        cid = doc.get_text("t").id
        t = doc.get_text("t")
        t.insert(0, "styled text here")
        t.mark(0, 6, "bold", True)
        t.mark(3, 10, "color", "red")
        t.unmark(4, 6, "bold")
        doc.commit()
        batch = DeviceDocBatch(n_docs=1, capacity=256)
        self._no_fallback(monkeypatch, batch)
        batch.append_payloads([strip_envelope(doc.export_updates(None))], cid)
        assert batch.richtexts() == [t.get_richtext_value()]
        assert batch.texts() == [t.to_string()]

    @pytest.mark.parametrize("seed", range(3))
    def test_multi_epoch_payload_richtext_fuzz(self, seed, monkeypatch):
        from loro_tpu.doc import strip_envelope
        from loro_tpu.native import available
        from loro_tpu.parallel.fleet import DeviceDocBatch

        if not available():
            pytest.skip("native codec unavailable")
        rng = random.Random(50 + seed)
        pairs = []
        for i in range(2):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=2 * i + 2)
            a.get_text("t").insert(0, "the quick brown fox")
            b.import_(a.export_updates(b.oplog_vv()))
            pairs.append((a, b))
        cid = pairs[0][0].get_text("t").id
        batch = DeviceDocBatch(n_docs=2, capacity=2048)
        self._no_fallback(monkeypatch, batch)
        marks = [a.oplog_vv() for a, _ in pairs]
        batch.append_payloads(
            [strip_envelope(a.export_updates(None)) for a, _ in pairs], cid
        )
        for epoch in range(3):
            for a, b in pairs:
                for d in (a, b):
                    t = d.get_text("t")
                    L = len(t)
                    r = rng.random()
                    if L >= 3 and r < 0.4:
                        s = rng.randrange(L - 2)
                        k = rng.choice(["bold", "color"])
                        if rng.random() < 0.3:
                            t.unmark(s, rng.randint(s + 1, L), k)
                        else:
                            t.mark(s, rng.randint(s + 1, L), k, rng.choice([True, "red"]))
                    elif L > 4 and r < 0.6:
                        t.delete(rng.randrange(L - 2), 2)
                    else:
                        t.insert(rng.randint(0, L), rng.choice(["zz", "q"]))
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(strip_envelope(a.export_updates(marks[i])))
                marks[i] = a.oplog_vv()
            batch.append_payloads(ups, cid)
            got = batch.richtexts()
            for i, (a, _) in enumerate(pairs):
                want = a.get_text("t").get_richtext_value()
                assert got[i] == want, f"seed {seed} epoch {epoch} doc {i}"


class TestTreePayloadIngest:
    """DeviceTreeBatch.append_payloads: native C++ tree explode feeding
    the resident log (wire order; the device replay sorts anyway)."""

    @pytest.mark.parametrize("seed", range(3))
    def test_payload_epochs_match_host(self, seed, monkeypatch):
        from loro_tpu.doc import strip_envelope
        from loro_tpu.native import available
        from loro_tpu.parallel.fleet import DeviceTreeBatch

        if not available():
            pytest.skip("native codec unavailable")
        rng = random.Random(70 + seed)
        pairs = []
        for i in range(2):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=2 * i + 2)
            tr = a.get_tree("tr")
            root = tr.create()
            tr.create(root)
            b.import_(a.export_snapshot())
            pairs.append((a, b))
        cid = pairs[0][0].get_tree("tr").id
        batch = DeviceTreeBatch(n_docs=2, move_capacity=1024, node_capacity=128)

        def boom(*a, **k):
            raise AssertionError("python fallback must not run")

        monkeypatch.setattr(batch, "_explode_changes_into", boom)
        marks = [a.oplog_vv() for a, _ in pairs]
        batch.append_payloads(
            [strip_envelope(a.export_updates(None)) for a, _ in pairs], cid
        )
        for epoch in range(3):
            for a, b in pairs:
                for d in (a, b):
                    tr = d.get_tree("tr")
                    nodes = tr.nodes()
                    r = rng.random()
                    if not nodes or r < 0.4:
                        tr.create(rng.choice(nodes) if nodes else None, index=0)
                    elif r < 0.7 and len(nodes) >= 2:
                        n1, n2 = rng.sample(nodes, 2)
                        try:
                            tr.move(n1, n2, rng.randint(0, 1))
                        except Exception:
                            pass  # local cycle rejection
                    else:
                        tr.delete(rng.choice(nodes))
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
                assert a.get_deep_value() == b.get_deep_value()
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(strip_envelope(a.export_updates(marks[i])))
                marks[i] = a.oplog_vv()
            batch.append_payloads(ups, cid)
            parents = batch.parent_maps()
            kids = batch.children_maps()
            for i, (a, _) in enumerate(pairs):
                tr = a.get_tree("tr")
                assert parents[i] == {t: tr.parent(t) for t in tr.nodes()}, (
                    f"seed {seed} epoch {epoch} doc {i}"
                )
                host_kids = {}
                for t in [None] + tr.nodes():
                    ch = tr.children(t)
                    if ch:
                        host_kids[t] = ch
                assert kids[i] == host_kids, f"seed {seed} epoch {epoch} doc {i}"


class TestMovablePayloadIngest:
    """DeviceMovableBatch.append_payloads: native C++ movable delta
    explode (ext-ref protocol for cross-epoch slot parents)."""

    @pytest.mark.parametrize("seed", range(3))
    def test_payload_epochs_match_host(self, seed, monkeypatch):
        from loro_tpu.doc import strip_envelope
        from loro_tpu.native import available
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        if not available():
            pytest.skip("native codec unavailable")
        rng = random.Random(80 + seed)
        pairs = []
        for i in range(2):
            a, b = LoroDoc(peer=2 * i + 1), LoroDoc(peer=2 * i + 2)
            a.get_movable_list("ml").push("s0", "s1", "s2")
            b.import_(a.export_snapshot())
            pairs.append((a, b))
        cid = pairs[0][0].get_movable_list("ml").id
        batch = DeviceMovableBatch(n_docs=2, capacity=2048, elem_capacity=256)

        def boom(*a, **k):
            raise AssertionError("python fallback must not run")

        monkeypatch.setattr(batch, "_walk_movable_changes", boom)
        marks = [a.oplog_vv() for a, _ in pairs]
        batch.append_payloads(
            [strip_envelope(a.export_updates(None)) for a, _ in pairs], cid
        )
        for epoch in range(3):
            for a, b in pairs:
                for d in (a, b):
                    ml = d.get_movable_list("ml")
                    L = len(ml)
                    r = rng.random()
                    if L == 0 or r < 0.3:
                        ml.insert(rng.randint(0, L), f"v{rng.randrange(99)}")
                    elif r < 0.5 and L >= 2:
                        ml.move(rng.randrange(L), rng.randrange(L))
                    elif r < 0.7:
                        ml.set(rng.randrange(L), {"w": rng.randrange(99)})
                    else:
                        ml.delete(rng.randrange(L), 1)
                    d.commit()
                a.import_(b.export_updates(a.oplog_vv()))
                b.import_(a.export_updates(b.oplog_vv()))
                assert a.get_deep_value() == b.get_deep_value()
            ups = []
            for i, (a, _) in enumerate(pairs):
                ups.append(strip_envelope(a.export_updates(marks[i])))
                marks[i] = a.oplog_vv()
            batch.append_payloads(ups, cid)
            got = batch.value_lists()
            for i, (a, _) in enumerate(pairs):
                want = a.get_movable_list("ml").get_value()
                assert got[i] == want, f"seed {seed} epoch {epoch} doc {i}"

    def test_checkpoint_after_payload_ingest(self):
        """export/import after NATIVE payload ingest (all decoded state
        must serialize; the restored batch keeps appending payloads)."""
        from loro_tpu.doc import strip_envelope
        from loro_tpu.native import available
        from loro_tpu.parallel.fleet import DeviceMovableBatch

        if not available():
            pytest.skip("native codec unavailable")
        doc = LoroDoc(peer=1)
        ml = doc.get_movable_list("ml")
        ml.push("a", {"b": 1}, "c")
        ml.move(2, 0)
        doc.commit()
        cid = ml.id
        batch = DeviceMovableBatch(n_docs=1, capacity=256, elem_capacity=64)
        batch.append_payloads([strip_envelope(doc.export_updates(None))], cid)
        restored = DeviceMovableBatch.import_state(batch.export_state())
        assert restored.value_lists() == [ml.get_value()]
        mark = doc.oplog_vv()
        ml.set(0, "Z")
        ml.delete(2, 1)
        doc.commit()
        restored.append_payloads(
            [strip_envelope(doc.export_updates(mark))], cid
        )
        assert restored.value_lists() == [ml.get_value()]


class TestResidentErrorSurface:
    def test_missing_base_raises_typed_error(self):
        """Feeding a delta without the base import raises LoroError with
        an actionable message (was a raw KeyError), and the failed walk
        leaks no staged values (list batches)."""
        from loro_tpu import LoroError
        from loro_tpu.parallel.fleet import DeviceDocBatch

        a = LoroDoc(peer=1)
        a.get_list("l").push("v0")
        a.commit()
        mark = a.oplog_vv()
        a.get_list("l").push("v1")
        a.commit()
        batch = DeviceDocBatch(1, 256, as_text=False)
        with pytest.raises(LoroError, match="FULL history"):
            batch.append_changes(
                [a.oplog.changes_between(mark, a.oplog_vv())], a.get_list("l").id
            )
        assert batch.value_store[0] == []  # no orphan values leaked
        # the batch stays usable with the correct feeding order
        batch.append_changes([a.oplog.changes_in_causal_order()], a.get_list("l").id)
        assert batch.values() == [a.get_list("l").get_value()]


def _device_state(batch):
    """Every device array of a DeviceDocBatch on the host: the eight
    columns and both key words."""
    out = {f: np.asarray(getattr(batch.cols, f)) for f in batch.cols._fields}
    out["key_hi"], out["key_lo"] = np.asarray(batch.key_hi), np.asarray(batch.key_lo)
    return out


def _one_device():
    import jax

    from loro_tpu.parallel.mesh import make_mesh

    return make_mesh(jax.devices()[:1])


def _all_devices():
    from loro_tpu.parallel.mesh import make_mesh

    return make_mesh()  # conftest: eight CPU devices, the table doc-sharded


MESHES = {"one_device": _one_device, "mesh8": _all_devices}


class TestNamedBlock:
    """ISSUE 36: the resident round's scatter block holds the documents
    the round NAMES (``[pad_bucket(named), pad_bucket(longest)]``), row
    ``j`` being document ``d_idx[j]``'s — not a row a slot of the table.
    Counts and equalities only."""

    SLOTS = 8

    def _docs(self):
        docs = [LoroDoc(peer=101 + i) for i in range(self.SLOTS)]
        return docs, docs[0].get_text("t").id

    def _payload_round(self, docs, marks, named, edit):
        from loro_tpu.doc import strip_envelope

        updates = [None] * self.SLOTS
        for j, di in enumerate(named):
            edit(docs[di].get_text("t"), j)
            docs[di].commit()
            updates[di] = strip_envelope(docs[di].export_updates(marks[di]))
            marks[di] = docs[di].oplog_vv()
        return updates

    @pytest.mark.parametrize("mesh", list(MESHES))
    @pytest.mark.parametrize(
        "named", [(2,), (0, 3, 6), (1, 2, 4, 5, 7), tuple(range(8))],
        ids=["one", "three", "five_off_bucket", "all"])
    def test_named_rounds_read_right_and_leave_unnamed_slots_bit_identical(
            self, named, mesh):
        docs, cid = self._docs()
        batch = DeviceDocBatch(self.SLOTS, 256, mesh=MESHES[mesh]())
        marks = [{} for _ in docs]
        # every slot holds rows before the named rounds, so an unnamed
        # slot has something to lose
        batch.append_payloads(self._payload_round(
            docs, marks, range(self.SLOTS),
            lambda t, j: t.insert(0, "base%d " % j * (1 + j % 3))), cid)
        unnamed = [di for di in range(self.SLOTS) if di not in named]
        # twice: the second round lands at non-zero offsets of rows the
        # first one wrote
        for rnd in range(2):
            before = _device_state(batch)
            counts = batch.counts.copy()
            batch.append_payloads(self._payload_round(
                docs, marks, named,
                lambda t, j: (t.insert(len(t) // 2, "xyz"[: 1 + j % 3] * (2 + j + rnd)),
                              t.delete(0, 1))), cid)
            after = _device_state(batch)
            assert batch.texts() == [d.get_text("t").to_string() for d in docs]
            for f in before:
                assert np.array_equal(before[f][unnamed], after[f][unnamed]), f
            # a named slot keeps every row it had (deletes only mark)
            for di in named:
                k = int(counts[di])
                assert int(batch.counts[di]) > k
                for f in before:
                    if f not in ("deleted", "key_hi", "key_lo"):
                        assert np.array_equal(before[f][di, :k], after[f][di, :k]), f

    @pytest.mark.parametrize("mesh", list(MESHES))
    def test_pad_rows_never_put_an_old_window_back(self, mesh):
        """Three named documents make a block of four rows: one pad row.
        The FIRST named document gets the longest update (the whole
        window is its new rows) at a non-zero offset — a pad row that
        named it again with ``valid`` all false would write the window
        as it was before the round over them."""
        docs, cid = self._docs()
        batch = DeviceDocBatch(self.SLOTS, 256, mesh=MESHES[mesh]())
        marks = [d.oplog_vv() for d in docs]

        def changes(named, edit):
            out = [None] * self.SLOTS
            for j, di in enumerate(named):
                edit(docs[di].get_text("t"), j)
                docs[di].commit()
                out[di] = _changes_between(docs[di], marks[di])
                marks[di] = docs[di].oplog_vv()
            return out

        batch.append_changes(
            changes(range(self.SLOTS), lambda t, j: t.insert(0, "seed")), cid)
        named = (1, 4, 6)
        batch.append_changes(changes(
            named, lambda t, j: t.insert(2, "L" * 64 if j == 0 else "s")), cid)
        assert batch.texts() == [d.get_text("t").to_string() for d in docs]
        assert batch.texts()[1] == "se" + "L" * 64 + "ed"

    def test_the_block_is_as_large_as_what_the_round_names(self):
        """A 2-of-8 round: ``resident.stage``'s ``bytes`` and the pad
        waste counter are the block's — 34 B a row of
        ``pad_bucket(named) x pad_bucket(longest)`` — and both spans say
        how many documents were named."""
        from loro_tpu.obs import metrics as obs
        from loro_tpu.ops.fugue_batch import pad_bucket
        from loro_tpu.parallel.fleet import _named_bucket
        from loro_tpu.utils import tracing

        docs, cid = self._docs()
        batch = DeviceDocBatch(self.SLOTS, 256, mesh=_one_device())
        marks = [{} for _ in docs]
        batch.append_payloads(self._payload_round(
            docs, marks, range(self.SLOTS), lambda t, j: t.insert(0, "base")), cid)
        waste = obs.counter("fleet.pad_waste_rows_total")
        rows = obs.counter("fleet.resident_rows_total")
        w0, r0 = waste.get(family="resident_seq"), rows.get(family="text")
        updates = self._payload_round(
            docs, marks, (2, 5), lambda t, j: t.insert(1, "q" * (20 if j else 3)))
        tracing.clear()
        tracing.enable()
        try:
            batch.append_payloads(updates, cid)
            spans = {e["name"]: e["args"] for e in tracing.events()}
        finally:
            tracing.disable()
            tracing.clear()
        k_pad = _named_bucket(2)
        width = pad_bucket(20, floor=16)
        assert (k_pad, width) == (4, 32)
        assert spans["resident.stage"]["bytes"] == 34 * k_pad * width
        assert spans["resident.stage"]["docs"] == 2
        assert spans["resident.upload"]["docs"] == 2
        assert rows.get(family="text") - r0 == 23
        assert waste.get(family="resident_seq") - w0 == k_pad * width - 23
        assert batch.texts() == [d.get_text("t").to_string() for d in docs]

    @pytest.mark.parametrize("mesh", list(MESHES))
    def test_scatter_rows_against_a_numpy_walk(self, mesh):
        """``_scatter_rows`` alone, held bit for bit to the plain
        statement of what it does: for each named block row, the window
        at the row's offset of that document, replaced under ``valid``;
        nothing else moves.  The block has pad rows (document -1) whose
        contents would be seen if they were written."""
        import jax

        from loro_tpu.ops.fugue_batch import SeqColumnsU
        from loro_tpu.parallel.fleet import _named_bucket, _scatter_rows
        from loro_tpu.parallel.mesh import doc_sharding, replicated

        m = MESHES[mesh]()
        rng = np.random.default_rng(36)
        d, cap, width = 16, 128, 32
        fields = SeqColumnsU._fields + ("key_hi", "key_lo")
        table = {}
        for f in fields:
            dt = bool if f in ("deleted", "valid") else (
                np.int32 if f in ("parent", "side", "counter", "content") else np.uint32)
            table[f] = rng.integers(0, 2 if dt is bool else 1 << 20, (d, cap)).astype(dt)
        named = [13, 2, 7, 8, 3]  # not at a bucket, not sorted: 3 pad rows
        d_idx = np.full(_named_bucket(len(named)), -1, np.int32)
        d_idx[: len(named)] = named
        blk = {f: rng.integers(0, 2 if a.dtype == bool else 1 << 20,
                               (len(d_idx), width)).astype(a.dtype)
               for f, a in table.items()}
        n_rows = [32, 1, 17, 5, 30]
        blk["valid"][:] = True  # pad rows too: they must be dropped by index
        for j, k in enumerate(n_rows):
            blk["valid"][j, k:] = False
        offsets = np.zeros(len(d_idx), np.int32)
        offsets[: len(named)] = [cap - width, 0, 40, 96, 11]
        want = {f: a.copy() for f, a in table.items()}
        for j, di in enumerate(named):
            sl = slice(int(offsets[j]), int(offsets[j]) + n_rows[j])
            for f in fields:
                want[f][di, sl] = blk[f][j, : n_rows[j]]
        sh, rep = doc_sharding(m), replicated(m)
        put = lambda a, s: jax.device_put(a, s)
        cols = SeqColumnsU(**{f: put(table[f], sh) for f in SeqColumnsU._fields})
        out_cols, hi, lo = _scatter_rows(
            (cols, put(table["key_hi"], sh), put(table["key_lo"], sh)),
            {f: put(a, rep) for f, a in blk.items()},
            put(d_idx, rep), put(offsets, rep), m)
        got = {f: np.asarray(getattr(out_cols, f)) for f in SeqColumnsU._fields}
        got["key_hi"], got["key_lo"] = np.asarray(hi), np.asarray(lo)
        for f in fields:
            assert np.array_equal(got[f], want[f]), f
        assert out_cols.valid.sharding.is_equivalent_to(sh, 2)
