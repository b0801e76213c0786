"""Packed u8 single-buffer transport (pack_chain_doc_into /
chain_merge_docs_packed) must be bit-identical to the ChainColumns
path — it is the e2e ingest wire onto the device.  And the native
contraction and row pack (native/codec.cpp, behind
``contract_chains`` / ``pack_chain_row``) must answer as their numpy
references do: column for column, byte for byte."""
import numpy as np
import pytest

import loro_tpu as lt
from loro_tpu import native
from loro_tpu.core.ids import ContainerID, ContainerType
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.ops.columnar import (
    SeqExtract,
    _contract_chains_numpy,
    chain_columns,
    contract_chains,
    extract_seq_container,
    extract_seq_from_payload,
    pack_chain_row,
)
from loro_tpu.ops.fugue_batch import (
    ChainColumns,
    chain_merge_docs,
    chain_merge_docs_checksum,
    chain_merge_docs_packed,
    chain_merge_docs_packed_checksum,
    pack_chain_doc_into,
    packed_row_bytes,
)

CID = ContainerID.root("t", ContainerType.Text)


def _fuzz_docs(seed: int, n_docs: int = 4, steps: int = 150):
    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(n_docs):
        a, b = lt.LoroDoc(peer=1), lt.LoroDoc(peer=2)
        for i in range(steps):
            for d in (a, b):
                t = d.get_text("t")
                pos = int(rng.integers(0, len(t) + 1))
                if len(t) > 2 and rng.random() < 0.3:
                    t.delete(min(pos, len(t) - 1), 1)
                else:
                    t.insert(pos, chr(97 + int(rng.integers(0, 26))))
            if rng.random() < 0.2:
                b.import_(a.export_updates(b.oplog_vv()))
        b.import_(a.export_updates(b.oplog_vv()))
        a.import_(b.export_updates(a.oplog_vv()))
        docs.append(a)
    return docs


def _batch(docs, pad_n, pad_c):
    exs = [extract_seq_container(d.oplog.changes_in_causal_order(), CID) for d in docs]
    cols = [chain_columns(e, pad_n=pad_n, pad_c=pad_c) for e in exs]
    batched = ChainColumns(
        *[np.stack([getattr(c, f) for c in cols]) for f in ChainColumns._fields]
    )
    packed = np.empty((len(docs), packed_row_bytes(pad_c, pad_n)), np.uint8)
    for i, c in enumerate(cols):
        pack_chain_doc_into(c, packed[i])
    return batched, packed


def test_packed_matches_chain_columns_path():
    docs = _fuzz_docs(0)
    exs = [extract_seq_container(d.oplog.changes_in_causal_order(), CID) for d in docs]
    pad_n = max(e.n for e in exs) + 7  # deliberately unaligned pads
    pad_c = max(contract_chains(e).n_chains for e in exs) + 3
    batched, packed = _batch(docs, pad_n, pad_c)

    codes_a, counts_a = map(np.asarray, chain_merge_docs(batched))
    codes_b, counts_b = map(np.asarray, chain_merge_docs_packed(packed, pad_c, pad_n))
    assert (counts_a == counts_b).all()
    assert (codes_a == codes_b).all()

    cs_a, cnt_a = map(np.asarray, chain_merge_docs_checksum(batched))
    cs_b, cnt_b = map(np.asarray, chain_merge_docs_packed_checksum(packed, pad_c, pad_n))
    assert (cs_a == cs_b).all() and (cnt_a == cnt_b).all()

    # and the merged text matches the host engine
    for i, d in enumerate(docs):
        got = "".join(map(chr, codes_b[i][: counts_b[i]]))
        assert got == d.get_text("t").to_string()


def test_packed_u16_sentinels_roundtrip():
    """-1 c_parent (0xFFFF on the wire) survives the u16 packing, with
    generous pads so pad rows (chain_id 0, valid False) are exercised;
    the dump remap to pad_c happens on-device via the valid mask."""
    docs = _fuzz_docs(1, n_docs=2, steps=40)
    exs = [extract_seq_container(d.oplog.changes_in_causal_order(), CID) for d in docs]
    pad_n = max(e.n for e in exs) + 64
    pad_c = max(contract_chains(e).n_chains for e in exs) + 64
    batched, packed = _batch(docs, pad_n, pad_c)
    codes_a, counts_a = map(np.asarray, chain_merge_docs(batched))
    codes_b, counts_b = map(np.asarray, chain_merge_docs_packed(packed, pad_c, pad_n))
    assert (codes_a == codes_b).all() and (counts_a == counts_b).all()


def test_packed_rejects_oversized_pad_c():
    with pytest.raises(AssertionError):
        packed_row_bytes(0xFFFF, 16)


# ---------------------------------------------------------------------------
# the native contraction and row pack against their numpy references
# ---------------------------------------------------------------------------

def _extract(parent, side, seed=0):
    """A SeqExtract over the given tree columns, the element columns drawn."""
    parent, side = np.asarray(parent, np.int32), np.asarray(side, np.int32)
    n = parent.shape[0]
    rng = np.random.default_rng(seed)
    return SeqExtract(
        parent=parent, side=side, peer=np.zeros(n, np.int32),
        counter=np.arange(n, dtype=np.int32), deleted=rng.random(n) < 0.3,
        content=rng.integers(-1, 0x10FFFF, n).astype(np.int32),
        valid=np.ones(n, bool), peers=[1],
    )


def _long_chain():
    n = 700
    return _extract(np.arange(-1, n - 1), np.ones(n))


def _left_children():
    # 0 <- 1 (right) <- 2 (right); 3 a LEFT child of 1: 1 may not take 2 in
    # (it has two children) nor be taken in by 0 (it has a left child);
    # 4 a left child of the root; 5 right of 4, which has no left child
    return _extract([-1, 0, 1, 1, -1, 4], [1, 1, 1, 0, 0, 1])


def _two_right_children():
    # rows 1 and 2 both right children of 0; 3 continues 2
    return _extract([-1, 0, 0, 2, 3], [1, 1, 1, 1, 1])


def _parent_below_child():
    """Peer 1 types after what peer 2 typed: in (peer, counter) row order
    its parent rows lie BELOW it, so a chain's parent is known only once
    every row has its chain."""
    b = lt.LoroDoc(peer=2)
    b.get_text("t").insert(0, "typed by the higher peer")
    b.commit()
    a = lt.LoroDoc(peer=1)
    a.import_(b.export_snapshot())
    a.get_text("t").insert(9, "LOW")
    a.get_text("t").insert(2, "er")
    a.commit()
    ex = extract_seq_from_payload(strip_envelope(a.export_updates({})), CID)
    heads = _contract_chains_numpy(ex).head_row
    assert (ex.parent[heads] > heads).any()
    return ex


def _several_peers(seed):
    doc = _fuzz_docs(seed, n_docs=1, steps=120)[0]
    return extract_seq_container(doc.oplog.changes_in_causal_order(), CID)


def _any_forest(seed):
    """Parents and sides drawn freely (a row's parent any other row or the
    root, runs of typing between): more shapes than documents make."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 400))
    parent = np.arange(-1, n - 1)
    side = np.ones(n, np.int64)
    for i in rng.choice(n, n // 3, replace=False):
        parent[i] = rng.integers(-1, n)
        side[i] = rng.integers(0, 2)
    return _extract(parent, side, seed)


def _loose_columns():
    """The same document in columns that are neither C-contiguous nor of
    the declared dtypes: strided views of wider arrays."""
    ex = _several_peers(3)

    def strided(a, dtype):
        wide = np.zeros((a.shape[0], 3), dtype)
        wide[:, 1] = a
        return wide[:, 1]

    loose = SeqExtract(
        parent=strided(ex.parent, np.int64), side=strided(ex.side, np.int8),
        peer=ex.peer, counter=ex.counter, deleted=strided(ex.deleted, np.uint8),
        content=strided(ex.content, np.int64), valid=strided(ex.valid, bool),
        peers=ex.peers,
    )
    assert not loose.parent.flags.c_contiguous and loose.parent.dtype != np.int32
    return loose


CHAIN_CASES = {
    "empty": lambda: _extract([], []),
    "one_row": lambda: _extract([-1], [1]),
    "one_long_chain": _long_chain,
    "every_row_a_root": lambda: _extract(np.full(50, -1), np.arange(50) % 2),
    "left_children": _left_children,
    "two_right_children": _two_right_children,
    "parent_below_child": _parent_below_child,
    "loose_columns": _loose_columns,
    **{f"several_peers_{k}": (lambda k=k: _several_peers(k)) for k in range(3)},
    **{f"any_forest_{k}": (lambda k=k: _any_forest(k)) for k in range(6)},
}
CHAIN_FIELDS = ("parent", "side", "valid", "head_row", "chain_id")


def _same_chains(got, want):
    for f in CHAIN_FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype and a.shape == b.shape and (a == b).all(), f


def _reference_row(ex, chains, pad_c, pad_n):
    row = np.full(packed_row_bytes(pad_c, pad_n), 0xA5, np.uint8)
    pack_chain_doc_into(chain_columns(ex, pad_n=pad_n, pad_c=pad_c, chains=chains), row)
    return row


def _drop_native(monkeypatch):
    """The library absent, as after a failed build: every entry says so."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", True)
    assert not native.available()


def _chain_calls():
    c = obs.counter("codec.native_chain_calls_total")
    return c.get(fn="contract"), c.get(fn="pack")


@pytest.mark.parametrize("case", list(CHAIN_CASES))
def test_native_contraction_equals_numpys(case):
    assert native.available()
    ex = CHAIN_CASES[case]()
    c0, _p0 = _chain_calls()
    got = contract_chains(ex)
    assert _chain_calls()[0] - c0 == 1  # the native entry answered
    _same_chains(got, _contract_chains_numpy(ex))
    assert got.n_chains == got.head_row.shape[0] <= ex.n
    if case == "left_children":
        assert got.chain_id.tolist() == [0, 1, 2, 3, 4, 4]
        assert got.parent.tolist() == [-1, 0, 1, 1, -1]
    if case == "two_right_children":
        assert got.chain_id.tolist() == [0, 1, 2, 2, 2]
    if case == "one_long_chain":
        assert got.n_chains == 1


# the pads: snug on both (n == pad_n, n_chains == pad_c), roomy and odd (the
# 32-bit sections then start off a 4-byte boundary)
@pytest.mark.parametrize("room", [(0, 0), (5, 9)], ids=["snug", "roomy"])
@pytest.mark.parametrize("case", list(CHAIN_CASES))
def test_native_row_is_byte_equal_to_numpys(case, room):
    assert native.available()
    ex = CHAIN_CASES[case]()
    chains = contract_chains(ex)
    pad_c, pad_n = chains.n_chains + room[0], ex.n + room[1]
    want = _reference_row(ex, chains, pad_c, pad_n)
    # a row of a batch, and one at an odd address
    batch = np.full((3, want.shape[0]), 0x5A, np.uint8)
    odd = np.full(want.shape[0] + 1, 0x5A, np.uint8)[1:]
    _c0, p0 = _chain_calls()
    pack_chain_row(ex, chains, pad_c, pad_n, batch[1])
    pack_chain_row(ex, chains, pad_c, pad_n, odd)
    assert _chain_calls()[1] - p0 == 2
    assert batch[1].tobytes() == want.tobytes() == odd.tobytes()
    assert (batch[0] == 0x5A).all() and (batch[2] == 0x5A).all()  # its row alone


@pytest.mark.parametrize("short", ["chains", "elements"])
def test_a_document_past_its_pads_is_refused_on_both_paths(short, monkeypatch):
    ex = _several_peers(2)
    chains = contract_chains(ex)
    pad_c = chains.n_chains - (short == "chains")
    pad_n = ex.n - (short == "elements")
    row = np.zeros(packed_row_bytes(pad_c, pad_n), np.uint8)
    with pytest.raises(ValueError):
        pack_chain_row(ex, chains, pad_c, pad_n, row)
    _drop_native(monkeypatch)
    with pytest.raises(ValueError):
        pack_chain_row(ex, chains, pad_c, pad_n, row)


def test_a_parent_past_the_table_is_left_to_numpy():
    """The native entry refuses it (no read out of bounds); numpy answers
    as it always did: no link to a row that is not there."""
    ex = _extract([-1, 0, 7, 2], [1, 1, 1, 1])
    assert native.contract_chains(ex.parent, ex.side) is None
    with pytest.raises(IndexError):
        contract_chains(ex)


@pytest.mark.parametrize("case", ["empty", "left_children", "parent_below_child",
                                  "several_peers_0", "any_forest_0"])
def test_without_the_library_numpy_answers_the_same(case, monkeypatch):
    ex = CHAIN_CASES[case]()
    _drop_native(monkeypatch)
    before = _chain_calls()
    got = contract_chains(ex)
    pad_c, pad_n = got.n_chains + 3, ex.n + 4
    row = np.zeros(packed_row_bytes(pad_c, pad_n), np.uint8)
    pack_chain_row(ex, got, pad_c, pad_n, row)
    assert _chain_calls() == before  # no native call
    _same_chains(got, _contract_chains_numpy(ex))
    assert row.tobytes() == _reference_row(ex, got, pad_c, pad_n).tobytes()
