"""List documents in residency on the native path: ``ResidentServer
("list")`` over ``DeviceDocBatch(as_text=False)``, whose payloads take the
C++ delta explode and whose values are held as their positions in the
payloads (``_ValueColumns``), decoded only where they are read.  Held at
small sizes on the CPU against two sides — the plain reference
(``benchmarks/list_reference.py``: the edit script in, nothing of the
program) and the Python walk (``append_changes`` over the Python
decoder's changes), which must leave the same batch, byte for byte —
through every path that changes a table: full histories into empty
slots, deltas into loaded documents, deletes, payloads without the
container, values of every tag, coalesced rounds, ``compact``,
``release_doc``, export / import of state, checkpoint / restore, WAL
recovery, and the fallback of a payload the explode cannot resolve."""
from __future__ import annotations

import gc
import json
import os
import random
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import list_gen  # noqa: E402
import list_reference  # noqa: E402

from loro_tpu import ContainerType, LoroDoc, native  # noqa: E402
from loro_tpu.codec.binary import decode_changes  # noqa: E402
from loro_tpu.core.ids import ContainerID  # noqa: E402
from loro_tpu.doc import strip_envelope  # noqa: E402
from loro_tpu.obs import metrics as obs  # noqa: E402
from loro_tpu.parallel import fleet  # noqa: E402
from loro_tpu.parallel.fleet import DeviceDocBatch  # noqa: E402
from loro_tpu.parallel.mesh import make_mesh  # noqa: E402
from loro_tpu.parallel.server import ResidentServer  # noqa: E402
from loro_tpu.persist import recover_server  # noqa: E402
from loro_tpu.utils import tracing  # noqa: E402

with open(os.path.join(BENCH, "configs", "list_resident.json")) as _f:
    CONFIG = json.load(_f)
TINY = {**CONFIG, **CONFIG["rehearsal"]}
CID = ContainerID.root(list_gen.CONTAINER, ContainerType.List)
SLOTS, K = TINY["resident_documents"], 2
SEED = 2147483659


@pytest.fixture(scope="module")
def documents():
    """The three fed documents of the rehearsal configuration and what
    each must read, by the plain reference."""
    fed = [list_gen.make_payload(SEED, TINY, v) for v in range(TINY["fleet_documents"])]
    refs = [list_reference.replay(SEED, TINY, v) for v in range(len(fed))]
    return fed, refs


@pytest.fixture(params=["one_device", "eight_devices"])
def mesh(request):
    devs = jax.devices()
    return make_mesh(devs[:1] if request.param == "one_device" else devs)


def list_server(mesh, **kw) -> ResidentServer:
    return ResidentServer("list", SLOTS, mesh=mesh, capacity=TINY["capacity"], **kw)


def round_of(fed, slots) -> list:
    updates = [None] * SLOTS
    for k in slots:
        updates[k] = fed[k % len(fed)]["payload"]
    return updates


def cold_start(server, fed, rounds: int, first: int = 0) -> None:
    for r in range(first, first + rounds):
        server.ingest(round_of(fed, range(r * K, (r + 1) * K)), CID)


def assert_reads_the_reference(batch, refs, loaded) -> None:
    values = batch.values()
    assert len(values) == SLOTS
    for k in range(SLOTS):
        assert values[k] == (refs[k % len(refs)]["values"] if k in loaded else []), k


def _payload(doc, since=None) -> bytes:
    return strip_envelope(doc.export_updates(since) if since is not None
                          else doc.export_updates())


def _walked(payloads) -> list:
    """The same round for the Python change walk (``append_changes``)."""
    return [decode_changes(p) if p else None for p in payloads]


def _twins(mesh, n_docs, cap=TINY["capacity"]):
    return (DeviceDocBatch(n_docs, cap, mesh=mesh, as_text=False),
            DeviceDocBatch(n_docs, cap, mesh=mesh, as_text=False))


def assert_twins(native_b, walk) -> None:
    """The native path and the Python walk left the same batch."""
    assert native_b.values() == walk.values()
    np.testing.assert_array_equal(native_b.counts, walk.counts)
    for f in native_b.cols._fields:
        np.testing.assert_array_equal(np.asarray(getattr(native_b.cols, f)),
                                      np.asarray(getattr(walk.cols, f)), f)
    assert native_b.export_state() == walk.export_state()


def _feed(native_b, walk, ups, cid=CID) -> None:
    native_b.append_payloads(ups, cid)
    walk.append_changes(_walked(ups), cid)


# ---------------------------------------------------------------------------
# the cell's own path: full histories into empty slots
# ---------------------------------------------------------------------------

def test_full_histories_into_empty_slots_read_the_reference(documents, mesh):
    fed, refs = documents
    srv = list_server(mesh)
    calls = obs.counter("codec.native_decode_calls_total")
    before = calls.get(fn="seq_delta")
    fell = obs.counter("fleet.host_fallback_total").total()
    values0 = obs.counter("fleet.resident_values_total").get(family="list")
    for r in range(SLOTS // K):
        cold_start(srv, fed, 1, first=r)
        assert_reads_the_reference(srv.batch, refs, set(range((r + 1) * K)))
    assert calls.get(fn="seq_delta") - before == SLOTS  # one explode a payload
    assert obs.counter("fleet.host_fallback_total").total() == fell
    assert obs.counter("fleet.resident_values_total").get(family="list") - values0 \
        == SLOTS * TINY["insert_patches"]
    # each document's values are positions in its own payload: no copy
    for k in range(SLOTS):
        (payload, _cids), = srv.batch.value_store[k].payloads
        assert payload is fed[k % len(fed)]["payload"]
        assert srv.batch.value_store[k].decoded == []


def test_a_round_decodes_no_change_and_makes_no_object_a_value(monkeypatch):
    """One document of 20,000 values: its round runs neither the Python
    decoder nor the walk, and keeps a few objects, never one a value."""
    a = LoroDoc(peer=7)
    lst = a.get_list("l")
    rng = random.Random(3)
    for i in range(20_000):
        lst.insert(rng.randrange(i + 1), "abc"[i % 3])
    a.commit()
    payload, cid = _payload(a), lst.id
    batch = DeviceDocBatch(2, 32_768, mesh=make_mesh(jax.devices()[:1]), as_text=False)
    batch.append_payloads([payload, None], cid)  # every shape compiled here

    def refused(*_a, **_k):
        raise AssertionError("the Python decoder or walk ran")

    monkeypatch.setattr(fleet.DeviceDocBatch, "_python_rows", refused)
    monkeypatch.setattr("loro_tpu.codec.binary.decode_changes", refused)
    gc.collect()
    before = sys.getallocatedblocks()
    batch.append_payloads([None, payload], cid)
    gc.collect()
    grew = sys.getallocatedblocks() - before
    assert len(batch.value_store[1]) == 20_000
    assert grew < 2_000, grew
    assert batch.values() == [lst.get_value()] * 2


# ---------------------------------------------------------------------------
# the native path and the Python walk leave the same batch
# ---------------------------------------------------------------------------

def test_full_histories_leave_the_walks_batch(documents, mesh):
    fed, _refs = documents
    native_b, walk = _twins(mesh, SLOTS)
    for r in range(2):
        _feed(native_b, walk, round_of(fed, range(r * K, (r + 1) * K)))
        assert_twins(native_b, walk)


def _concurrent_rounds(seed, rounds):
    """Per round, three documents' deltas made by two replicas each and
    exchanged: values inserted next to elements of earlier payloads, and
    deletes of those elements."""
    rng = random.Random(seed)
    pairs = []
    for i in range(3):
        a, b = LoroDoc(peer=10 * i + 1), LoroDoc(peer=10 * i + 2)
        a.get_list("l").insert(0, "seed")
        a.commit()
        b.import_(a.export_updates())
        pairs.append((a, b))
    marks = [None] * 3
    for _r in range(rounds):
        for a, b in pairs:
            for d in (a, b):
                lst = d.get_list("l")
                for _ in range(6):
                    n = len(lst)
                    if n and rng.random() < 0.3:
                        lst.delete(rng.randrange(n), 1)
                    else:
                        lst.insert(rng.randrange(n + 1), rng.choice(
                            ["x", "yz", 7, -2, 2.5, None, True, b"\x00\xff", "é"]))
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
        ups = []
        for i, (a, _b) in enumerate(pairs):
            ups.append(_payload(a, marks[i]))
            marks[i] = a.oplog_vv()
        yield ups, [a for a, _b in pairs]


def test_deltas_into_loaded_documents_leave_the_walks_batch(mesh):
    native_b, walk = _twins(mesh, 4, cap=512)
    cid = LoroDoc(peer=1).get_list("l").id
    for ups, docs in _concurrent_rounds(11, 5):
        _feed(native_b, walk, ups + [None], cid)
        assert_twins(native_b, walk)
        assert native_b.values()[:3] == [d.get_list("l").get_value() for d in docs]
    assert int(np.asarray(native_b.cols.deleted)[:3].sum()) > 0
    # a round whose payload holds no list container names nothing
    other = LoroDoc(peer=99)
    other.get_map("m").set("k", 1)
    other.commit()
    before = [len(s) for s in native_b.value_store]
    _feed(native_b, walk, [_payload(other), None, None, None], cid)
    assert [len(s) for s in native_b.value_store] == before
    assert_twins(native_b, walk)


def _every_tag_doc():
    a = LoroDoc(peer=5)
    lst = a.get_list("l")
    for v in (None, True, False, 0, -(1 << 40), 3.25, "", "plain", "ü€𝄞",
              b"", b"\x00\x80", [1, "two", [None]], {"k": 1, "ü": [2.5]}):
        lst.push(v)
    child = lst.push_container(ContainerType.Map)
    child.set("inner", 1)
    lst.push("x" * 200)  # a length that takes two varint bytes
    a.commit()
    return a, lst


def test_a_value_of_every_tag_reads_back(mesh):
    a, lst = _every_tag_doc()
    native_b, walk = _twins(mesh, 2, cap=64)
    _feed(native_b, walk, [_payload(a), None], lst.id)
    assert native_b.values()[0] == lst.get_value()
    assert isinstance(native_b.values()[0][13], ContainerID)
    assert_twins(native_b, walk)
    mark = a.oplog_vv()
    lst.delete(1, 3)
    lst.insert(2, {"late": None})
    a.commit()
    _feed(native_b, walk, [_payload(a, mark), None], lst.id)
    assert native_b.values()[0] == lst.get_value()
    assert_twins(native_b, walk)


@pytest.mark.parametrize("words", [
    ["a", "b", "c"],  # one ASCII character each: the cell's values
    ["to", "", "do list", "x" * 127],  # ASCII of any length under 128 bytes
    ["ü", "日本", "ok"],  # not ASCII: the codec's reader
    ["x" * 128, "y"],  # a two-byte length: the codec's reader
], ids=["characters", "ascii_words", "utf8_words", "long_word"])
def test_strings_read_back_from_their_positions(words):
    a = LoroDoc(peer=9)
    lst = a.get_list("l")
    for i in range(300):
        lst.insert(i // 2, words[i % len(words)])
    a.commit()
    native_b, walk = _twins(make_mesh(jax.devices()[:1]), 2, cap=512)
    _feed(native_b, walk, [_payload(a), None], lst.id)
    assert native_b.values()[0] == lst.get_value()
    (payload, _cids), = native_b.value_store[0].payloads
    assert native_b.value_store[0].take([299, 0, 7]) == [
        walk.value_store[0].tolist()[i] for i in (299, 0, 7)]
    assert_twins(native_b, walk)


def test_a_coalesced_group_leaves_the_serial_batch(documents, mesh):
    fed, refs = documents
    serial, grouped = list_server(mesh), list_server(mesh)
    rounds = [round_of(fed, range(r * K, (r + 1) * K)) for r in range(3)]
    for r in rounds:
        serial.ingest(r, CID)
    grouped.ingest_coalesced(rounds, CID)
    assert grouped.batch.export_state() == serial.batch.export_state()
    assert_reads_the_reference(grouped.batch, refs, set(range(3 * K)))
    native_b, walk = _twins(mesh, 4, cap=512)
    cid = LoroDoc(peer=1).get_list("l").id
    for b in (native_b, walk):
        b.begin_coalesce()
    for ups, _docs in _concurrent_rounds(5, 3):
        _feed(native_b, walk, ups + [None], cid)
    for b in (native_b, walk):
        b.flush_coalesce()
    assert_twins(native_b, walk)


def test_compact_remaps_the_value_columns(mesh):
    native_b, walk = _twins(mesh, 4, cap=512)
    cid = LoroDoc(peer=1).get_list("l").id
    docs = None
    for ups, docs in _concurrent_rounds(17, 4):
        _feed(native_b, walk, ups + [None], cid)
    n_before = [len(s) for s in native_b.value_store]
    assert native_b.compact([native_b.epoch] * 3) == walk.compact([walk.epoch] * 3) > 0
    assert sum(len(s) for s in native_b.value_store) < sum(n_before)
    assert_twins(native_b, walk)
    assert native_b.values()[:3] == [d.get_list("l").get_value() for d in docs]
    # what a store holds is its surviving values: the decoded list no
    # longer, each payload kept only for survivors enough to be worth it
    for s in native_b.value_store:
        src, _off = s._columns()
        assert len(s.decoded) == int((src == 0).sum())
        used = np.bincount(src, minlength=len(s.payloads) + 1)[1:]
        for (payload, _cids), n in zip(s.payloads, used.tolist()):
            assert fleet._keeps_positions(payload, n)
    # the compacted batch keeps ingesting
    for ups, _docs in _concurrent_rounds(18, 1):
        _feed(native_b, walk, ups + [None], cid)
    assert_twins(native_b, walk)


def _a_payload_then_its_deletes(n, keep):
    a = LoroDoc(peer=21)
    lst = a.get_list("l")
    for i in range(n):
        lst.push("abcdefgh"[i % 8])
    a.commit()
    first, mark = _payload(a), a.oplog_vv()
    lst.delete(keep, n - keep)
    a.commit()
    return lst, first, _payload(a, mark)


@pytest.mark.parametrize("keep", [10, 900], ids=["few_survive", "most_survive"])
def test_compaction_holds_what_survives_not_the_history(keep):
    """A payload of 1,000 values of which ``keep`` live: compaction keeps
    it while enough of its values live, and otherwise decodes the
    survivors and lets it go; the walk's decoded list keeps survivors
    alone."""
    lst, first, deletes = _a_payload_then_its_deletes(1_000, keep)
    native_b, walk = _twins(make_mesh(jax.devices()[:1]), 2, cap=2_048)
    _feed(native_b, walk, [first, None], lst.id)
    _feed(native_b, walk, [deletes, None], lst.id)
    assert native_b.compact([native_b.epoch]) == walk.compact([walk.epoch]) > 0
    assert_twins(native_b, walk)
    assert native_b.values()[0] == lst.get_value()
    store, walked = native_b.value_store[0], walk.value_store[0]
    assert keep <= len(store) < 1_000
    assert len(walked.decoded) == len(walked) == len(store)
    if keep < fleet._KEEP_MIN_VALUES:
        assert len(store.decoded) == len(store) and store.payloads == []
    else:
        assert store.decoded == [] and [p for p, _c in store.payloads] == [first]
        assert store.payloads[0][0] is first


def test_many_small_pushes_read_from_one_list():
    """A document fed one value a round: a round keeps its payload as
    positions, and the first read settles them into one decoded list,
    not one payload a push."""
    a = LoroDoc(peer=22)
    lst = a.get_list("l")
    batch = DeviceDocBatch(2, 512, mesh=make_mesh(jax.devices()[:1]), as_text=False)
    batch.begin_coalesce()
    for i in range(150):
        mark = a.oplog_vv()
        lst.push(i)
        a.commit()
        batch.append_payloads([_payload(a, mark), None], lst.id)
    batch.flush_coalesce()
    store = batch.value_store[0]
    assert len(store) == 150 and store.decoded == []
    assert batch.values()[0] == lst.get_value() == list(range(150))
    assert len(store.decoded) == 150 and store.payloads == []


def test_release_doc_empties_the_slot_and_nothing_else(documents, mesh):
    fed, refs = documents
    srv = list_server(mesh)
    cold_start(srv, fed, 2)
    srv.batch.release_doc(1)
    assert_reads_the_reference(srv.batch, refs, {0, 2, 3})
    assert len(srv.batch.value_store[1]) == 0
    srv.ingest(round_of(fed, [1]), CID)  # the slot takes a document again
    assert_reads_the_reference(srv.batch, refs, {0, 1, 2, 3})


def test_grow_keeps_the_values(documents, mesh):
    fed, refs = documents
    srv = list_server(mesh)
    cold_start(srv, fed, 1)
    srv.batch.grow(2 * TINY["capacity"])
    assert_reads_the_reference(srv.batch, refs, set(range(K)))
    cold_start(srv, fed, 1, first=1)
    assert_reads_the_reference(srv.batch, refs, set(range(2 * K)))


def test_a_state_blob_imports_and_exports_the_same_bytes(documents, mesh):
    """The walk's batch holds decoded values, as every batch did before
    values were positions: its v2 blob is the native batch's, byte for
    byte, and imports into a batch that exports it again unchanged."""
    fed, refs = documents
    native_b, walk = _twins(mesh, SLOTS)
    _feed(native_b, walk, round_of(fed, range(3)))
    a, lst = _every_tag_doc()
    _feed(native_b, walk, [None] * 3 + [_payload(a)] + [None] * (SLOTS - 4), lst.id)
    blob = walk.export_state()
    assert DeviceDocBatch.STATE_VERSION == 2
    assert native_b.export_state() == blob
    back = DeviceDocBatch.import_state(blob, mesh=mesh)
    assert back.export_state() == blob
    assert back.values() == native_b.values()
    assert back.values()[:3] == [refs[k]["values"] for k in range(3)]
    assert back.values()[3] == lst.get_value()
    # the restored batch keeps ingesting, natively
    mark = a.oplog_vv()
    lst.push("after")
    a.commit()
    for b in (back, native_b):
        b.append_payloads([None] * 3 + [_payload(a, mark)], lst.id)
    assert back.values() == native_b.values()


def test_checkpoint_and_restore_keep_the_values(documents, mesh):
    fed, refs = documents
    srv = list_server(mesh)
    cold_start(srv, fed, 2)
    back = ResidentServer.restore(srv.checkpoint(), mesh=mesh)
    assert_reads_the_reference(back.batch, refs, set(range(2 * K)))
    cold_start(back, fed, 1, first=2)
    assert_reads_the_reference(back.batch, refs, set(range(3 * K)))


def test_wal_recovery_replays_the_values(documents, mesh, tmp_path):
    fed, refs = documents
    srv = list_server(mesh, durable_dir=str(tmp_path), durable_fsync="group")
    cold_start(srv, fed, 3)
    srv.close()
    back = recover_server(str(tmp_path), mesh=mesh)
    try:
        assert back.last_recovery.rounds_replayed > 0
        assert_reads_the_reference(back.batch, refs, set(range(3 * K)))
    finally:
        back.close()


def test_a_payload_the_native_path_cannot_resolve_falls_back_and_counts(documents, monkeypatch):
    fed, _refs = documents
    mesh = make_mesh(jax.devices()[:1])
    native_b, walk = _twins(mesh, SLOTS)
    ups = round_of(fed, range(4))
    real = native.explode_seq_delta_payload

    def refuse_one(payload, target):
        if payload is ups[2]:
            raise ValueError("unresolvable")
        return real(payload, target)

    monkeypatch.setattr(native, "explode_seq_delta_payload", refuse_one)
    fell = obs.counter("fleet.host_fallback_total")
    f0, t0 = fell.get(kind="list_payload"), fell.get(kind="payload_decode")
    _feed(native_b, walk, ups)
    assert fell.get(kind="list_payload") - f0 == 1
    assert fell.get(kind="payload_decode") == t0
    assert_twins(native_b, walk)
    # the walked payload's values are held decoded, the others as positions
    assert native_b.value_store[2].decoded and not native_b.value_store[2].payloads
    assert native_b.value_store[1].payloads and not native_b.value_store[1].decoded


def test_a_round_records_its_values_span():
    a, lst = _every_tag_doc()
    batch = DeviceDocBatch(2, 64, mesh=make_mesh(jax.devices()[:1]), as_text=False)
    tracing.enable()
    try:
        tracing.clear()
        batch.append_payloads([_payload(a), _payload(a)], lst.id)
        spans = [(e["name"], e.get("args")) for e in tracing.events()
                 if e["name"] in ("resident.decode", "resident.values")]
    finally:
        tracing.disable()
    n = len(lst.get_value())
    assert spans == [("resident.decode", {}), ("resident.values", {"docs": 2, "values": 2 * n})]


_FAULTS = """
import resource
import numpy as np
from loro_tpu.parallel.server import ResidentServer

def faults():
    f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    a = np.ones(48 << 20, np.uint8)
    del a
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0

default = [faults(), faults()]
ResidentServer("list", 2)
held = [faults(), faults()]
print(default[1], held[1])
"""


@pytest.mark.skipif(not sys.platform.startswith("linux"), reason="glibc mallopt")
def test_a_resident_server_keeps_freed_pages_for_the_next_round():
    """A 48 MiB column freed and allocated again is faulted in afresh under
    glibc's own thresholds (above 32 MiB it is always a new ``mmap``); once
    a resident server is built its pages come back from the heap."""
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "GLIBC_TUNABLES"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = ROOT
    out = subprocess.run([sys.executable, "-c", _FAULTS], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120, check=True)
    default, held = map(int, out.stdout.split())
    assert default > 0
    assert held * 4 < default
