"""Native (C++) wire->SoA decoder vs pure-Python extraction."""
import random

import numpy as np
import pytest

from loro_tpu import EncodeMode, LoroDoc
from loro_tpu.native import available
from loro_tpu.ops.columnar import extract_seq_container, extract_seq_from_payload

pytestmark = pytest.mark.skipif(not available(), reason="native codec unavailable")


def _payload(doc) -> bytes:
    doc.commit()
    blob = doc.export_updates()
    assert blob[5] == EncodeMode.ColumnarUpdates.value
    return blob[10:]  # strip envelope


def _assert_same(ex_py, ex_nat):
    assert ex_nat.n == ex_py.n
    np.testing.assert_array_equal(ex_nat.parent, ex_py.parent)
    np.testing.assert_array_equal(ex_nat.side, ex_py.side)
    np.testing.assert_array_equal(ex_nat.peer, ex_py.peer)
    np.testing.assert_array_equal(ex_nat.counter, ex_py.counter)
    np.testing.assert_array_equal(ex_nat.deleted, ex_py.deleted)


class TestNativeDecoder:
    def test_simple_text(self):
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "hello world")
        t.delete(2, 3)
        t.insert(4, "résumé ☃")  # multibyte utf8
        cid = t.id
        ex_nat = extract_seq_from_payload(_payload(doc), cid)
        ex_py = extract_seq_container(doc.oplog.changes_in_causal_order(), cid)
        _assert_same(ex_py, ex_nat)
        np.testing.assert_array_equal(ex_nat.content, ex_py.content)

    def test_multi_container_interleaved(self):
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        l = doc.get_list("l")
        m = doc.get_map("m")
        tr = doc.get_tree("tree")
        ml = doc.get_movable_list("ml")
        t.insert(0, "abc")
        l.push(1, 2)
        m.set("k", {"nested": [1, 2]})
        r = tr.create()
        ml.push("x", "y")
        ml.move(0, 1)
        t.insert(1, "XY")
        t.mark(0, 3, "bold", True)
        doc.get_counter("c").increment(3)
        t.delete(0, 2)
        cid = t.id
        ex_nat = extract_seq_from_payload(_payload(doc), cid)
        ex_py = extract_seq_container(doc.oplog.changes_in_causal_order(), cid)
        _assert_same(ex_py, ex_nat)

    @pytest.mark.parametrize("seed", range(6))
    def test_random_multi_peer(self, seed):
        rng = random.Random(seed)
        docs = [LoroDoc(peer=rng.getrandbits(50) + 1) for _ in range(3)]
        for _ in range(70):
            d = rng.choice(docs)
            t = d.get_text("t")
            if len(t) and rng.random() < 0.35:
                pos = rng.randint(0, len(t) - 1)
                t.delete(pos, min(rng.randint(1, 3), len(t) - pos))
            else:
                t.insert(rng.randint(0, len(t)), rng.choice(["ab", "ç", "1234", "☃"]))
            if rng.random() < 0.3:
                src, dst = rng.sample(docs, 2)
                dst.import_(src.export_updates(dst.oplog_vv()))
        for _ in range(2):
            for s in docs:
                for t2 in docs:
                    if s is not t2:
                        t2.import_(s.export_updates(t2.oplog_vv()))
        doc = docs[0]
        cid = doc.get_text("t").id
        ex_nat = extract_seq_from_payload(_payload(doc), cid)
        ex_py = extract_seq_container(doc.oplog.changes_in_causal_order(), cid)
        _assert_same(ex_py, ex_nat)
        np.testing.assert_array_equal(ex_nat.content, ex_py.content)

    def test_absent_container(self):
        doc = LoroDoc(peer=1)
        doc.get_text("t").insert(0, "x")
        from loro_tpu import ContainerID, ContainerType

        other = ContainerID.root("nope", ContainerType.Text)
        ex = extract_seq_from_payload(_payload(doc), other)
        assert ex.n == 0

    def test_map_explode_matches_python(self):
        import numpy as np

        from loro_tpu.native import explode_map_payload
        from loro_tpu.ops.columnar import extract_map_ops

        docs = [LoroDoc(peer=1), LoroDoc(peer=2)]
        a, b = docs
        a.get_map("m").set("x", 1)
        a.get_map("m2").set("y", {"n": [1, 2]})
        b.import_(a.export_updates())
        b.get_map("m").set("x", 2)
        b.get_map("m").delete("x")
        b.get_text("t").insert(0, "noise")  # interleaved non-map ops
        a.import_(b.export_updates(a.oplog_vv()))
        payload = _payload(a)
        out = explode_map_payload(payload)
        assert out is not None
        ex = extract_map_ops(a.oplog.changes_in_causal_order())
        assert len(out["cid_idx"]) == len(ex.slot)
        np.testing.assert_array_equal(out["lamport"], ex.lamport)
        np.testing.assert_array_equal(out["peer_rank"], ex.peer)  # rank contract
        assert out["peers"] == ex.peers
        # deletes carry ordinal -1
        assert (out["value_ordinal"] == -1).sum() == 1

    def test_map_explode_peer_rank_tiebreak(self):
        """Regression (review finding): wire registration order must not
        leak into peer ranks — peer 9 registered first still ranks after
        peer 1 in the LWW tie-break ordering."""
        import numpy as np

        from loro_tpu.native import explode_map_payload
        from loro_tpu.ops.columnar import extract_map_ops

        a, b = LoroDoc(peer=9), LoroDoc(peer=1)
        a.get_map("m").set("x", "from9")
        a.commit()
        b.get_map("m").set("x", "from1")
        b.commit()
        a.import_(b.export_updates(a.oplog_vv()))
        payload = _payload(a)
        out = explode_map_payload(payload)
        ex = extract_map_ops(a.oplog.changes_in_causal_order())
        np.testing.assert_array_equal(out["peer_rank"], ex.peer)
        assert out["peers"] == [1, 9]

    def test_malformed_payload_raises(self):
        doc = LoroDoc(peer=1)
        doc.get_text("t").insert(0, "abcdef")
        payload = bytearray(_payload(doc))
        cid = doc.get_text("t").id
        for cut in (len(payload) // 2, len(payload) - 2):
            with pytest.raises(ValueError):
                extract_seq_from_payload(bytes(payload[:cut]), cid)

    def test_bad_peer_index_rejected(self):
        """A CRC-valid payload whose change header references a peer
        index beyond the peer table must fail native decode (advisor
        finding: it used to wrap negative and mis-attribute ops)."""
        from loro_tpu.native import explode_map_payload

        doc = LoroDoc(peer=1)
        doc.get_map("m").set("k", 1)
        payload = bytearray(_payload(doc))
        # Mutate every byte position in turn: the native decoder must
        # either decode, raise ValueError, or fall back (None) — never
        # crash, and (checked below for the explicit case) never accept
        # an out-of-table peer index.
        for pos in range(len(payload)):
            mut = bytearray(payload)
            mut[pos] = (mut[pos] + 0x81) & 0xFF
            try:
                explode_map_payload(bytes(mut))
            except ValueError:
                pass
        # Explicit case: bump the change-meta peer_idx varint past the
        # peer table (layout: binary.py module docstring).  Walk the
        # prelude to find it.
        buf = bytes(payload)

        def rvarint(b, i):
            sh = v = 0
            while True:
                v |= (b[i] & 0x7F) << sh
                sh += 7
                i += 1
                if not b[i - 1] & 0x80:
                    return v, i

        n_peers, i = rvarint(buf, 0)
        assert n_peers == 1
        i += 8 * n_peers
        n_keys, i = rvarint(buf, i)
        for _ in range(n_keys):
            ln, i = rvarint(buf, i)
            i += ln
        n_cids, i = rvarint(buf, i)
        for _ in range(n_cids):
            b0 = buf[i]
            i += 1
            if b0 & 0x80:
                ln, i = rvarint(buf, i)
                i += ln
            else:
                _, i = rvarint(buf, i)  # peer idx
                _, i = rvarint(buf, i)  # zigzag counter
        n_changes, i = rvarint(buf, i)
        assert n_changes >= 1
        assert buf[i] == 0  # peer_idx 0: the only peer
        mut = bytearray(buf)
        mut[i] = 1  # index 1 >= n_peers(1): must be rejected
        with pytest.raises(ValueError):
            explode_map_payload(bytes(mut))

    def test_overlong_utf8_rejected(self):
        """Overlong/invalid UTF-8 in an insert-text op must fail decode,
        not silently produce wrong codepoints."""
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        t.insert(0, "ABCDEF")
        payload = bytearray(_payload(doc))
        cid = t.id
        idx = bytes(payload).find(b"ABCDEF")
        assert idx >= 0
        # overlong encoding of 'A' (0xC1 0x81 is always invalid UTF-8)
        payload[idx] = 0xC1
        payload[idx + 1] = 0x81
        with pytest.raises(ValueError):
            extract_seq_from_payload(bytes(payload), cid)
        # bare continuation byte
        payload2 = bytearray(_payload(doc))
        payload2[idx] = 0x80
        with pytest.raises(ValueError):
            extract_seq_from_payload(bytes(payload2), cid)
        # truncated 2-byte sequence: lead byte followed by ASCII
        payload3 = bytearray(_payload(doc))
        payload3[idx] = 0xC3
        # next byte 'B' (0x42) lacks the 0x80 continuation prefix
        with pytest.raises(ValueError):
            extract_seq_from_payload(bytes(payload3), cid)

    def test_speed_vs_python(self):
        import time

        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        rng = random.Random(0)
        for _ in range(3000):
            if len(t) and rng.random() < 0.3:
                pos = rng.randint(0, len(t) - 1)
                t.delete(pos, min(2, len(t) - pos))
            else:
                t.insert(rng.randint(0, len(t)), "word")
        payload = _payload(doc)
        cid = t.id
        t0 = time.perf_counter()
        ex_nat = extract_seq_from_payload(payload, cid)
        t_nat = time.perf_counter() - t0
        t0 = time.perf_counter()
        ex_py = extract_seq_container(doc.oplog.changes_in_causal_order(), cid)
        t_py = time.perf_counter() - t0
        _assert_same(ex_py, ex_nat)
        assert t_nat < t_py, f"native {t_nat*1e3:.1f}ms not faster than python {t_py*1e3:.1f}ms"


class TestNativeTreeMovable:
    @pytest.mark.parametrize("seed", range(4))
    def test_tree_payload_matches_python(self, seed):
        """Native tree explode vs Python extraction vs host state."""
        from loro_tpu.parallel.fleet import Fleet

        rng = random.Random(200 + seed)
        docs = [LoroDoc(peer=i + 1) for i in range(2)]
        for epoch in range(4):
            for d in docs:
                tr = d.get_tree("tr")
                ns = tr.nodes()
                r = rng.random()
                if not ns or r < 0.4:
                    tr.create(rng.choice(ns) if ns and rng.random() < 0.5 else None)
                elif r < 0.6:
                    try:
                        tr.move(rng.choice(ns), rng.choice(ns + [None]))
                    except Exception:
                        pass
                elif r < 0.8:
                    tr.delete(rng.choice(ns))
                else:
                    try:
                        tr.move(rng.choice(ns), rng.choice(ns + [None]), index=0)
                    except Exception:
                        pass
                d.commit()
            docs[0].import_(docs[1].export_updates(docs[0].oplog_vv()))
            docs[1].import_(docs[0].export_updates(docs[1].oplog_vv()))
        cid = docs[0].get_tree("tr").id
        fleet = Fleet()
        payloads = [_payload(d) for d in docs]
        got_native = fleet.merge_tree_payloads(payloads, cid)
        got_python = fleet.merge_tree_changes(
            [d.oplog.changes_in_causal_order() for d in docs], cid
        )
        assert got_native == got_python
        # host oracle
        for i, d in enumerate(docs):
            st = d.state.get(cid)
            want = {
                t: (None if st.nodes[t].parent is None else st.nodes[t].parent)
                for t in st.nodes
                if not st._is_deleted(t)
            }
            assert got_native[i] == want, f"seed {seed} doc {i}"

    @pytest.mark.parametrize("seed", range(4))
    def test_movable_payload_matches_python(self, seed):
        """Native movable explode (lazy values) vs Python vs host."""
        from loro_tpu.parallel.fleet import Fleet

        rng = random.Random(300 + seed)
        docs = [LoroDoc(peer=i + 1) for i in range(2)]
        for d in docs:
            d.get_movable_list("ml").push("seed0", "seed1")
            d.commit()
        docs[0].import_(docs[1].export_updates(docs[0].oplog_vv()))
        docs[1].import_(docs[0].export_updates(docs[1].oplog_vv()))
        for epoch in range(4):
            for d in docs:
                ml = d.get_movable_list("ml")
                n = len(ml)
                r = rng.random()
                if n == 0 or r < 0.35:
                    ml.insert(rng.randint(0, n), {"v": rng.randint(0, 99)})
                elif r < 0.55:
                    ml.move(rng.randint(0, n - 1), rng.randint(0, n - 1))
                elif r < 0.75:
                    ml.set(rng.randint(0, n - 1), rng.randint(100, 199))
                else:
                    ml.delete(rng.randint(0, n - 1), 1)
                d.commit()
            docs[0].import_(docs[1].export_updates(docs[0].oplog_vv()))
            docs[1].import_(docs[0].export_updates(docs[1].oplog_vv()))
        cid = docs[0].get_movable_list("ml").id
        fleet = Fleet()
        payloads = [_payload(d) for d in docs]
        got_native = fleet.merge_movable_payloads(payloads, cid)
        got_python = fleet.merge_movable_changes(
            [d.oplog.changes_in_causal_order() for d in docs], cid
        )
        assert got_native == got_python
        for i, d in enumerate(docs):
            want = d.get_movable_list("ml").get_value()
            assert got_native[i] == want, f"seed {seed} doc {i}"


class TestChainEntries:
    """The contraction and the row pack on raw columns (their callers'
    differential cases: tests/test_packed_transport.py)."""

    @pytest.mark.parametrize("seed", range(4))
    def test_decoded_payloads_contract_and_pack_as_numpy_does(self, seed):
        from loro_tpu import native
        from loro_tpu.ops.columnar import _contract_chains_numpy, chain_columns
        from loro_tpu.ops.fugue_batch import pack_chain_doc_into, packed_row_bytes

        rng = random.Random(100 + seed)
        docs = [LoroDoc(peer=rng.getrandbits(50) + 1) for _ in range(4)]
        for _ in range(90):
            d = rng.choice(docs)
            t = d.get_text("t")
            if len(t) and rng.random() < 0.3:
                pos = rng.randint(0, len(t) - 1)
                t.delete(pos, min(rng.randint(1, 4), len(t) - pos))
            else:
                t.insert(rng.randint(0, len(t)), rng.choice(["typed run ", "ç", "☃x"]))
            if rng.random() < 0.25:
                src, dst = rng.sample(docs, 2)
                dst.import_(src.export_updates(dst.oplog_vv()))
        for src in docs:
            for dst in docs:
                if src is not dst:
                    dst.import_(src.export_updates(dst.oplog_vv()))
        ex = extract_seq_from_payload(_payload(docs[0]), docs[0].get_text("t").id)
        ref = _contract_chains_numpy(ex)
        chain_id, head_row, c_parent, c_side = native.contract_chains(ex.parent, ex.side)
        for got, want in ((chain_id, ref.chain_id), (head_row, ref.head_row),
                          (c_parent, ref.parent), (c_side, ref.side)):
            assert got.dtype == want.dtype == np.int32
            np.testing.assert_array_equal(got, want)
        assert 1 < ref.n_chains < ex.n  # some runs contracted, not all one
        pad_c, pad_n = ref.n_chains + 11, ex.n + 6
        row, want = np.empty((2, packed_row_bytes(pad_c, pad_n)), np.uint8)
        assert native.pack_chain_row(
            c_parent, c_side, ref.valid, head_row, chain_id, ex.content,
            ex.deleted, ex.valid, pad_c, pad_n, row)
        pack_chain_doc_into(chain_columns(ex, pad_n=pad_n, pad_c=pad_c, chains=ref), want)
        assert row.tobytes() == want.tobytes()


class TestRowTableFallback:
    """The direct-address RowTable fast path falls back to the
    open-addressing IdMap when counters are too sparse for its budget;
    force a tiny budget so that (otherwise dead in dense tests) path
    runs against the Python oracle."""

    def test_forced_fallback_matches(self):
        from loro_tpu.native import _load

        lib = _load()
        rng = random.Random(7)
        docs = [LoroDoc(peer=i + 1) for i in range(3)]
        for _ in range(60):
            d = rng.choice(docs)
            t = d.get_text("t")
            if len(t) and rng.random() < 0.35:
                pos = rng.randint(0, len(t) - 1)
                t.delete(pos, min(rng.randint(1, 3), len(t) - pos))
            else:
                t.insert(rng.randint(0, len(t)), rng.choice(["ab", "ç", "☃x"]))
            if rng.random() < 0.3:
                src, dst = rng.sample(docs, 2)
                dst.import_(src.export_updates(dst.oplog_vv()))
        for src in docs:
            for dst in docs:
                if src is not dst:
                    dst.import_(src.export_updates(dst.oplog_vv()))
        doc = docs[0]
        cid = doc.get_text("t").id
        pl = _payload(doc)
        ex_py = extract_seq_container(doc.oplog.changes_in_causal_order(), cid)
        lib.loro_set_rowtable_budget(1)  # every put overflows -> IdMap rerun
        try:
            ex_forced = extract_seq_from_payload(pl, cid)
        finally:
            lib.loro_set_rowtable_budget(0)
        ex_fast = extract_seq_from_payload(pl, cid)
        _assert_same(ex_py, ex_forced)
        _assert_same(ex_py, ex_fast)
        np.testing.assert_array_equal(ex_forced.content, ex_fast.content)
