"""The resident tree's standing state: ``DeviceTreeBatch.parents``
and ``.refused``, settled on the device at every commit, held against two
sides at small sizes on the CPU — the plain reference
(``benchmarks/tree_reference.py``: the move script in, nothing of the
program) and ONE replay of the whole table (``tree_replay_log_batch``) —
and, for hand-made concurrent histories, the host engine (``LoroDoc``).
Through every path that changes a log: full histories into empty slots,
deltas whose concurrent moves carry LOWER lamports than moves already
applied, cycles and deletes, ``compact``, ``release_doc``, ``grow``,
export / import of state, checkpoint / restore, WAL recovery and coalesced
rounds; and the settle's block follows the documents a round names."""
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

import tree_gen  # noqa: E402
import tree_reference  # noqa: E402

from loro_tpu import LoroDoc, native  # noqa: E402
from loro_tpu.codec.binary import decode_changes  # noqa: E402
from loro_tpu.core.ids import ContainerID, ContainerType, TreeID  # noqa: E402
from loro_tpu.doc import strip_envelope  # noqa: E402
from loro_tpu.obs import metrics as obs  # noqa: E402
from loro_tpu.ops import tree_batch  # noqa: E402
from loro_tpu.ops.fugue_batch import pad_bucket  # noqa: E402
from loro_tpu.parallel import fleet  # noqa: E402
from loro_tpu.ops.tree_batch import ABSENT, tree_replay_log_batch  # noqa: E402
from loro_tpu.parallel.fleet import DeviceTreeBatch  # noqa: E402
from loro_tpu.parallel.mesh import make_mesh  # noqa: E402
from loro_tpu.parallel.server import ResidentServer  # noqa: E402
from loro_tpu.persist import recover_server  # noqa: E402
from loro_tpu.utils import tracing  # noqa: E402

with open(os.path.join(BENCH, "configs", "tree_resident.json")) as _f:
    CONFIG = json.load(_f)
TINY = {**CONFIG, **CONFIG["rehearsal"]}
CID = ContainerID.root(tree_gen.CONTAINER, ContainerType.Tree)
SLOTS, K = TINY["resident_documents"], 2
SEED = 2147483659


@pytest.fixture(scope="module")
def documents():
    """The three fed documents of the rehearsal configuration and what
    each must read, by the plain reference."""
    fed = [tree_gen.make_payload(SEED, TINY, v) for v in range(TINY["fleet_documents"])]
    refs = [tree_reference.replay(SEED, TINY, v) for v in range(len(fed))]
    return fed, refs


@pytest.fixture(params=["one_device", "eight_devices"])
def mesh(request):
    devs = jax.devices()
    return make_mesh(devs[:1] if request.param == "one_device" else devs)


def tree_server(mesh, **kw) -> ResidentServer:
    return ResidentServer("tree", SLOTS, mesh=mesh,
                          move_capacity=TINY["move_capacity"],
                          node_capacity=TINY["node_capacity"], **kw)


def round_of(fed, slots) -> list:
    updates = [None] * SLOTS
    for k in slots:
        updates[k] = fed[k % len(fed)]["payload"]
    return updates


def cold_start(server, fed, rounds: int, first: int = 0) -> None:
    for r in range(first, first + rounds):
        server.ingest(round_of(fed, range(r * K, (r + 1) * K)), CID)


def by_create_index(tree_map: dict) -> list:
    """A parent map as the reference states a tree (the creates' peer is
    the configuration's first)."""
    out = [-9] * TINY["nodes"]
    for node, parent in tree_map.items():
        if node.peer == TINY["peer_ids"][0]:
            out[node.counter] = -1 if parent is None else parent.counter
    return out


def assert_settled(batch) -> None:
    """The standing state equals one replay of the whole table."""
    parents, eff = tree_replay_log_batch(batch.cols, batch.node_cap)
    refused = np.sum(np.asarray(batch.cols.valid) & ~np.asarray(eff), axis=1)
    np.testing.assert_array_equal(np.asarray(batch.parents), np.asarray(parents))
    np.testing.assert_array_equal(np.asarray(batch.refused), refused)


def assert_reads_the_reference(batch, refs, loaded) -> None:
    maps, refused = batch.parent_maps(), batch.refusals()
    for k in range(SLOTS):
        if k in loaded:
            ref = refs[k % len(refs)]
            assert by_create_index(maps[k]) == ref["parents"], k
            assert refused[k] == ref["refused"] > 0, k
        else:
            assert maps[k] == {} and refused[k] == 0, k


# ---------------------------------------------------------------------------
# the cell's own path: full histories into empty slots
# ---------------------------------------------------------------------------

def test_full_histories_into_empty_slots_read_the_reference(documents, mesh):
    fed, refs = documents
    srv = tree_server(mesh)
    before = obs.counter("tree.settle_docs_total").total()
    for r in range(SLOTS // K):
        cold_start(srv, fed, 1, first=r)
        assert_reads_the_reference(srv.batch, refs, set(range((r + 1) * K)))
        assert_settled(srv.batch)
    assert obs.counter("tree.settle_docs_total").total() - before == SLOTS
    moves = obs.counter("fleet.resident_tree_moves_total").total()
    assert moves >= sum(fed[k % len(fed)]["n_ops"] for k in range(SLOTS))


def test_the_settle_block_follows_the_named_documents(documents, monkeypatch):
    fed, _refs = documents
    launches = []
    real = tree_batch.tree_settle_docs

    def watched(cols, standing, d_idx, n_nodes, mesh):
        launches.append(np.asarray(d_idx).tolist())
        return real(cols, standing, d_idx, n_nodes, mesh)

    monkeypatch.setattr(tree_batch, "tree_settle_docs", watched)
    srv = tree_server(make_mesh(jax.devices()[:1]))
    srv.ingest(round_of(fed, [3]), CID)
    srv.ingest(round_of(fed, [0, 1, 2, 4, 5]), CID)
    srv.ingest([None] * SLOTS, CID)  # a round that names nothing settles nothing
    # a block of pad_bucket(named, floor 4) rows; pads carry document -1
    assert launches == [[3, -1, -1, -1], [0, 1, 2, 4, 5, -1, -1, -1]]
    tracing.enable()
    try:
        tracing.clear()
        srv.ingest(round_of(fed, [6, 7]), CID)
        settle = [e for e in tracing.events() if e["name"] == "resident.settle"]
    finally:
        tracing.disable()
    assert [e["args"] for e in settle] == [
        {"docs": 2, "rows": 4 * TINY["move_capacity"]}]
    assert_settled(srv.batch)


# ---------------------------------------------------------------------------
# concurrent deltas, cycles and deletes: the host engine is the oracle
# ---------------------------------------------------------------------------

def _payload(doc, since=None) -> bytes:
    return strip_envelope(doc.export_updates(since) if since is not None
                          else doc.export_updates())


def _host_parents(doc) -> dict:
    tr = doc.get_tree("tr")
    return {t: tr.parent(t) for t in tr.nodes()}


def test_a_delta_of_lower_lamports_replays_before_the_applied_moves():
    # x and y under the root on both replicas; b moves x under y first
    # (lamport 2), a creates four more nodes and THEN moves y under x
    # (lamport 6).  a's history is applied first; b's delta arrives later
    # and sorts BEFORE a's move, which then closes a cycle: refused.
    a, b = LoroDoc(peer=1), LoroDoc(peer=2)
    ta = a.get_tree("tr")
    x, y = ta.create(), ta.create()
    a.commit()
    b.import_(a.export_updates())
    base = b.oplog_vv()
    b.get_tree("tr").move(x, y)
    b.commit()
    for _ in range(4):
        ta.create()
    ta.move(y, x)
    a.commit()
    cid = ta.id
    srv = ResidentServer("tree", 2, mesh=make_mesh(jax.devices()[:1]),
                         move_capacity=64, node_capacity=16)
    srv.ingest([_payload(a), None], cid)
    assert srv.parent_maps()[0] == _host_parents(a)  # y under x
    assert srv.batch.refusals()[0] == 0
    srv.ingest([_payload(b, base), None], cid)
    a.import_(b.export_updates(a.oplog_vv()))
    host = _host_parents(a)
    assert host[x] == y and host[y] is None  # b's earlier move won
    assert srv.parent_maps()[0] == host
    assert srv.batch.refusals()[0] == 1
    assert_settled(srv.batch)


@pytest.mark.parametrize("seed", [3, 11])
def test_concurrent_rounds_with_cycles_and_deletes_track_the_host_engine(seed, mesh):
    rng = random.Random(seed)
    pairs = []
    for i in range(3):
        a, b = LoroDoc(peer=10 * i + 1), LoroDoc(peer=10 * i + 2)
        tr = a.get_tree("tr")
        root = tr.create()
        for _ in range(4):
            tr.create(root)
        a.commit()
        b.import_(a.export_updates())
        pairs.append((a, b))
    cid = pairs[0][0].get_tree("tr").id
    srv = ResidentServer("tree", 3, mesh=mesh, move_capacity=512, node_capacity=64)
    marks = [None] * 3
    for _epoch in range(5):
        for a, b in pairs:
            for d in (a, b):  # each replica edits alone, then they exchange
                tr = d.get_tree("tr")
                for _ in range(3):
                    nodes = list(tr.nodes())
                    r = rng.random()
                    if r < 0.25 or len(nodes) < 2:
                        tr.create(rng.choice(nodes) if nodes else None)
                    elif r < 0.8:
                        t1, t2 = rng.sample(nodes, 2)
                        try:
                            tr.move(t1, t2)
                        except Exception:  # noqa: BLE001 — a cycle refused where it is made
                            pass
                    else:
                        tr.delete(rng.choice(nodes))
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
            b.import_(a.export_updates(b.oplog_vv()))
        ups = []
        for i, (a, _b) in enumerate(pairs):
            ups.append(_payload(a, marks[i]))
            marks[i] = a.oplog_vv()
        srv.ingest(ups, cid)
        got = srv.parent_maps()
        for i, (a, _b) in enumerate(pairs):
            assert got[i] == _host_parents(a)
        assert_settled(srv.batch)
    assert srv.batch.refusals().sum() > 0  # concurrent moves made cycles


# ---------------------------------------------------------------------------
# every lifecycle path keeps the standing state right
# ---------------------------------------------------------------------------

def test_compact_keeps_the_parents_and_recounts_the_refusals(documents, mesh):
    fed, refs = documents
    srv = tree_server(mesh)
    cold_start(srv, fed, 2)
    batch = srv.batch
    before = np.asarray(batch.parents).copy()
    reclaimed = batch.compact([batch.epoch] * 2 * K)
    assert reclaimed > 0
    np.testing.assert_array_equal(np.asarray(batch.parents), before)
    assert_settled(batch)
    # the refused rows were stable and went with the compaction
    assert (batch.refusals()[: 2 * K] == 0).all()
    cold_start(srv, fed, 1, first=2)  # later rounds settle over the compacted logs
    loaded = set(range(3 * K))
    maps = batch.parent_maps()
    for k in loaded:
        assert by_create_index(maps[k]) == refs[k % len(refs)]["parents"]
    assert_settled(batch)


def test_release_doc_empties_the_slot_and_nothing_else(documents, mesh):
    fed, refs = documents
    srv = tree_server(mesh)
    cold_start(srv, fed, 2)
    srv.batch.release_doc(1)
    assert_reads_the_reference(srv.batch, refs, {0, 2, 3})
    assert_settled(srv.batch)
    srv.ingest(round_of(fed, [1]), CID)  # the slot takes a document again
    assert_reads_the_reference(srv.batch, refs, {0, 1, 2, 3})


def test_grow_widens_the_standing_parents(documents, mesh):
    fed, refs = documents
    srv = tree_server(mesh)
    cold_start(srv, fed, 1)
    batch = srv.batch
    batch.grow(move_capacity=2 * batch.cap, node_capacity=2 * batch.node_cap)
    assert np.asarray(batch.parents).shape[1] == 2 * TINY["node_capacity"]
    assert (np.asarray(batch.parents)[:, TINY["node_capacity"]:] == ABSENT).all()
    assert_reads_the_reference(batch, refs, set(range(K)))
    assert_settled(batch)
    cold_start(srv, fed, 1, first=1)
    assert_reads_the_reference(batch, refs, set(range(2 * K)))
    assert_settled(batch)


def test_export_and_import_of_state_derive_the_standing_state(documents, mesh):
    fed, refs = documents
    srv = tree_server(mesh)
    cold_start(srv, fed, 2)
    back = DeviceTreeBatch.import_state(srv.batch.export_state(), mesh=mesh)
    np.testing.assert_array_equal(np.asarray(back.parents), np.asarray(srv.batch.parents))
    np.testing.assert_array_equal(back.refusals(), srv.batch.refusals())
    assert_reads_the_reference(back, refs, set(range(2 * K)))


def test_checkpoint_and_restore_keep_the_standing_state(documents, mesh):
    fed, refs = documents
    srv = tree_server(mesh)
    cold_start(srv, fed, 2)
    back = ResidentServer.restore(srv.checkpoint(), mesh=mesh)
    assert_reads_the_reference(back.batch, refs, set(range(2 * K)))
    cold_start(back, fed, 1, first=2)  # the restored server settles its commits
    assert_reads_the_reference(back.batch, refs, set(range(3 * K)))
    assert_settled(back.batch)


def test_wal_recovery_replays_into_a_settled_table(documents, mesh, tmp_path):
    fed, refs = documents
    srv = tree_server(mesh, durable_dir=str(tmp_path), durable_fsync="group")
    cold_start(srv, fed, 3)
    srv.close()
    back = recover_server(str(tmp_path), mesh=mesh)
    try:
        assert back.last_recovery.rounds_replayed > 0
        assert_reads_the_reference(back.batch, refs, set(range(3 * K)))
        assert_settled(back.batch)
    finally:
        back.close()


def test_a_coalesced_group_settles_the_union_of_its_rounds_once(documents, mesh):
    fed, refs = documents
    serial, grouped = tree_server(mesh), tree_server(mesh)
    rounds = [round_of(fed, range(r * K, (r + 1) * K)) for r in range(3)]
    for r in rounds:
        serial.ingest(r, CID)
    before = obs.counter("tree.settle_docs_total").total()
    grouped.ingest_coalesced(rounds, CID)
    assert obs.counter("tree.settle_docs_total").total() - before == 3 * K
    np.testing.assert_array_equal(np.asarray(grouped.batch.parents),
                                  np.asarray(serial.batch.parents))
    assert_reads_the_reference(grouped.batch, refs, set(range(3 * K)))
    assert_settled(grouped.batch)


# ---------------------------------------------------------------------------
# the staging: a round as columns of the documents it names
# ---------------------------------------------------------------------------

def _walked(payloads) -> list:
    """The same round for the Python change walk (``append_changes``)."""
    return [decode_changes(p) if p else None for p in payloads]


def _twins(mesh, n_docs, cap=TINY["move_capacity"], nodes=TINY["node_capacity"]):
    return (DeviceTreeBatch(n_docs, cap, nodes, mesh=mesh),
            DeviceTreeBatch(n_docs, cap, nodes, mesh=mesh))


def assert_twins(columns, walk) -> None:
    """The columnar staging and the Python walk left the same batch."""
    assert columns.nodes == walk.nodes
    assert columns.node_ids == walk.node_ids
    np.testing.assert_array_equal(np.asarray(columns.parents), np.asarray(walk.parents))
    np.testing.assert_array_equal(columns.refusals(), walk.refusals())
    assert columns.children_maps() == walk.children_maps()
    assert columns.export_state() == walk.export_state()
    assert_settled(columns)


def _concurrent_rounds(seed, rounds, positions=True):
    """Per round, three documents' deltas: creates, moves, deletes made by
    two replicas each and exchanged, so a delta names nodes the slot
    knows and nodes it does not.  Without ``positions`` the second
    replica's moves ship no fractional index."""
    rng = random.Random(seed)
    pairs = []
    for i in range(3):
        a, b = LoroDoc(peer=10 * i + 1), LoroDoc(peer=10 * i + 2)
        root = a.get_tree("tr").create()
        for _ in range(3):
            a.get_tree("tr").create(root)
        a.commit()
        b.import_(a.export_updates())
        if not positions:
            b.get_tree("tr").disable_fractional_index()
        pairs.append((a, b))
    marks = [None] * 3
    for _r in range(rounds):
        for a, b in pairs:
            for d in (a, b):
                tr = d.get_tree("tr")
                for _ in range(4):
                    nodes = list(tr.nodes())
                    x = rng.random()
                    if x < 0.3 or len(nodes) < 2:
                        tr.create(rng.choice(nodes) if nodes else None)
                    elif x < 0.85:
                        t1, t2 = rng.sample(nodes, 2)
                        try:
                            tr.move(t1, t2)
                        except Exception:  # noqa: BLE001 — a cycle refused where it is made
                            pass
                    else:
                        tr.delete(rng.choice(nodes))
                d.commit()
            a.import_(b.export_updates(a.oplog_vv()))
        ups = []
        for i, (a, _b) in enumerate(pairs):
            ups.append(_payload(a, marks[i]))
            marks[i] = a.oplog_vv()
        yield ups, pairs[0][0].get_tree("tr").id


def test_columns_and_the_python_walk_stage_full_histories_alike(documents, mesh):
    fed, _refs = documents
    columns, walk = _twins(mesh, SLOTS)
    for r in range(2):
        ups = round_of(fed, range(r * K, (r + 1) * K))
        columns.append_payloads(ups, CID)
        walk.append_changes(_walked(ups), CID)
        assert_twins(columns, walk)
    # compaction and a release after columnar rounds
    assert columns.compact([columns.epoch] * 2) == walk.compact([walk.epoch] * 2) > 0
    assert_twins(columns, walk)
    for b in (columns, walk):
        b.release_doc(3)
    assert_twins(columns, walk)
    ups = round_of(fed, [3, 4])
    columns.append_payloads(ups, CID)
    walk.append_changes(_walked(ups), CID)
    assert_twins(columns, walk)


@pytest.mark.parametrize("positions", [True, False], ids=["positions", "no_positions"])
def test_columns_and_the_python_walk_stage_deltas_alike(positions, mesh):
    columns, walk = _twins(mesh, 4, cap=512, nodes=64)
    for ups, cid in _concurrent_rounds(5, 4, positions):
        columns.append_payloads(ups + [None], cid)
        walk.append_changes(_walked(ups), cid)
        assert_twins(columns, walk)
    metas = [columns._move_meta(di) for di in range(3)]
    assert sum(int(m["is_del"].sum()) for m in metas) > 0
    without = sum(int(((m["pos_len"] < 0) & ~m["is_del"]).sum()) for m in metas)
    assert (without > 0) == (not positions)
    assert walk.refusals().sum() > 0
    # a round whose payload holds no tree container names nothing
    other = LoroDoc(peer=99)
    other.get_map("m").set("k", 1)
    other.commit()
    before = columns.export_state()
    columns.append_payloads([_payload(other)] + [None] * 3, cid)
    walk.append_changes(_walked([_payload(other)]), cid)
    assert columns.export_state() != before  # the epoch ticked, nothing else
    assert_twins(columns, walk)


@pytest.mark.parametrize("gap", [0, 50_000], ids=["dense", "sparse"])
def test_new_nodes_are_numbered_in_order_of_first_appearance(gap, mesh):
    """A delta into a slot that knows one node, whose changes alternate
    between two authors: the new nodes follow the dictionary in the
    order the moves name them (w, z, x), which is not the order of their
    ids, whichever author the payload's peer table puts first.  With a
    ``gap`` of text between two creates the node counters lie too far
    apart for the dense first-appearance table, and one sort numbers
    them."""
    a, b = LoroDoc(peer=1), LoroDoc(peer=2)
    tb = b.get_tree("tr")
    y = tb.create()
    b.commit()
    first = _payload(b)  # the slot knows y
    mark = b.oplog_vv()
    if gap:
        b.get_text("t").insert(0, "x" * gap)
    w = tb.create(y)
    b.commit()
    a.import_(b.export_updates())
    z = a.get_tree("tr").create(w)
    a.commit()
    b.import_(a.export_updates())
    x = tb.create(z)
    b.commit()
    columns, walk = _twins(mesh, 2, cap=64, nodes=16)
    for ups in ([first, None], [_payload(b, mark), None]):
        columns.append_payloads(ups, tb.id)
        walk.append_changes(_walked(ups), tb.id)
        assert_twins(columns, walk)
    assert columns.nodes[0] == [y, w, z, x]


def test_a_coalesced_group_of_columns_is_the_walks(mesh, monkeypatch):
    columns, walk = _twins(mesh, 4, cap=512, nodes=64)
    blocks = []
    real = fleet._scatter_tree_rows

    def watched(cols, blk, d_idx, offsets, mesh_):
        blocks.append(np.asarray(d_idx).tolist())
        return real(cols, blk, d_idx, offsets, mesh_)

    monkeypatch.setattr(fleet, "_scatter_tree_rows", watched)
    for b in (columns, walk):
        b.begin_coalesce()
    for ups, cid in _concurrent_rounds(7, 3):  # three deltas of each document
        columns.append_payloads(ups + [None], cid)
        walk.append_changes(_walked(ups), cid)
    for b in (columns, walk):
        b.flush_coalesce()
    # ONE block a group: a row a document of the union, its three
    # segments concatenated
    assert blocks == [[0, 1, 2, -1]] * 2
    assert_twins(columns, walk)


def test_a_round_stages_a_block_of_the_documents_it_names(documents, mesh, monkeypatch):
    fed, _refs = documents
    batch = DeviceTreeBatch(64, 2 * TINY["move_capacity"], TINY["node_capacity"], mesh=mesh)
    batch.append_payloads([fed[k % len(fed)]["payload"] for k in range(64)], CID)
    before = {f: np.asarray(getattr(batch.cols, f)).copy() for f in batch.cols._fields}
    blocks = []
    real = fleet._scatter_tree_rows

    def watched(cols, blk, d_idx, offsets, mesh_):
        blocks.append(({f: v.shape for f, v in blk.items()}, np.asarray(d_idx).tolist()))
        return real(cols, blk, d_idx, offsets, mesh_)

    monkeypatch.setattr(fleet, "_scatter_tree_rows", watched)
    named = [5, 40, 63]
    ups = [None] * 64
    for k in named:
        ups[k] = fed[k % len(fed)]["payload"]
    tracing.enable()
    try:
        tracing.clear()
        batch.append_payloads(ups, CID)
        stage = [e["args"] for e in tracing.events() if e["name"] == "resident.stage"]
    finally:
        tracing.disable()
    width = max(fed[k % len(fed)]["n_ops"] for k in named)
    max_new = pad_bucket(width, floor=16)
    assert blocks == [({f: (fleet._named_bucket(3), max_new) for f in before},
                       named + [-1])]
    assert stage == [{"docs": 3, "bytes": 25 * 4 * max_new}]
    after = {f: np.asarray(getattr(batch.cols, f)) for f in before}
    others = [k for k in range(64) if k not in named]  # slot 0 is the pad row's
    for f in before:
        np.testing.assert_array_equal(after[f][others], before[f][others], f)
        for k in named:  # the window past the old rows took the new ones
            n = fed[k % len(fed)]["n_ops"]
            assert after["valid"][k, : 2 * n].all() and not after["valid"][k, 2 * n:].any()
    assert_settled(batch)


@pytest.mark.parametrize("short", ["nodes", "moves"])
def test_a_capacity_error_leaves_the_batch_untouched(documents, short):
    fed, _refs = documents
    width = pad_bucket(max(d["n_ops"] for d in fed), floor=16)
    if short == "nodes":  # room for every move, 8 nodes past the document's
        batch = DeviceTreeBatch(4, 2 * width, TINY["nodes"] + 8,
                                mesh=make_mesh(jax.devices()[:1]))
    else:  # room for one document's log
        batch = DeviceTreeBatch(4, width, TINY["node_capacity"],
                                mesh=make_mesh(jax.devices()[:1]))
    batch.append_payloads([fed[0]["payload"], None, None, None], CID)
    a = LoroDoc(peer=77)  # slot 0's delta: 16 new nodes, or one more move
    tr = a.get_tree(tree_gen.CONTAINER)
    for _ in range(16 if short == "nodes" else 1):
        tr.create()
    a.commit()
    ups = [_payload(a), fed[1]["payload"], None, None]
    state = batch.export_state()
    cols = {f: np.asarray(getattr(batch.cols, f)).copy() for f in batch.cols._fields}
    nodes = [list(n) for n in batch.nodes]
    with pytest.raises(RuntimeError, match=f"{short[:-1]} capacity exceeded"):
        batch.append_payloads(ups, CID)
    # counts, epoch, node dictionaries, metadata and log, as they were
    assert batch.export_state() == state
    assert batch.nodes == nodes and len(batch.node_ids[1]) == 0
    for f, v in cols.items():
        np.testing.assert_array_equal(np.asarray(getattr(batch.cols, f)), v)


def test_a_payload_the_native_path_cannot_resolve_falls_back_and_counts(documents, monkeypatch):
    fed, _refs = documents
    mesh = make_mesh(jax.devices()[:1])
    columns, walk = _twins(mesh, SLOTS)
    ups = round_of(fed, range(4))
    real = native.explode_tree_payload

    def refuse_one(payload, target):
        if payload is ups[2]:
            raise ValueError("unresolvable")
        return real(payload, target)

    monkeypatch.setattr(native, "explode_tree_payload", refuse_one)
    fell = obs.counter("fleet.host_fallback_total")
    cols = obs.counter("fleet.resident_tree_column_docs_total")
    f0, c0 = fell.get(kind="tree_payload"), cols.total()
    columns.append_payloads(ups, CID)
    assert fell.get(kind="tree_payload") - f0 == 1
    assert cols.total() - c0 == 3
    walk.append_changes(_walked(ups), CID)
    assert_twins(columns, walk)


def test_an_old_blob_imports_and_exports_the_same_bytes():
    """A v2 blob written field by field in the layout of the tuple era:
    one document of four moves (two with a position, a move without
    one, a delete), one empty."""
    from loro_tpu.codec.binary import Writer
    from loro_tpu.storage import MemKvStore

    assert DeviceTreeBatch.STATE_VERSION == 2
    kv = MemKvStore()
    w = Writer()
    w.u8(2)  # version
    for v in (2, 2, 16, 8):  # n_docs, d, move capacity, node capacity
        w.varint(v)
    for k in (4, 0):  # counts
        w.varint(k)
    w.varint(7)  # epoch
    w.u8(0)
    kv.set(b"meta", bytes(w.buf))
    peer = (1 << 63) + 5
    rows = [  # lamport, peer, counter, target, parent, is_del, position
        (0, peer, 0, 0, -1, False, b"\x80"),
        (1, peer, 9, 1, 0, False, b"\x7f\x10"),
        (2, peer, 10, 0, -1, False, None),
        (3, peer, 11, 1, -2, True, None),
    ]
    cols = list(zip(*rows))
    w = Writer()
    for (_f, dt), vals in zip(DeviceTreeBatch._STATE_SCHEMA,
                              [cols[0], [p >> 32 for p in cols[1]],
                               [p & 0xFFFFFFFF for p in cols[1]]] + cols[2:5]):
        w.bytes_(np.asarray(vals, dt).tobytes())
    kv.set(b"doc/%08d/log" % 0, bytes(w.buf))
    kv.set(b"doc/%08d/moveepoch" % 0, np.asarray([5, 6, 6, 7], np.int64).tobytes())
    w = Writer()
    w.varint(2)
    for c in (0, 9):
        w.u64le(peer)
        w.zigzag(c)
    kv.set(b"doc/%08d/nodes" % 0, bytes(w.buf))
    w = Writer()
    w.varint(len(rows))
    for lam, pr, ctr, t, _p, is_del, pos in rows:
        w.varint(lam)
        w.u64le(pr)
        w.zigzag(ctr)
        w.varint(t)
        w.u8((1 if is_del else 0) | (2 if pos is not None else 0))
        if pos is not None:
            w.bytes_(pos)
    kv.set(b"doc/%08d/meta" % 0, bytes(w.buf))
    w = Writer()
    for _f, dt in DeviceTreeBatch._STATE_SCHEMA:
        w.bytes_(b"")
    kv.set(b"doc/%08d/log" % 1, bytes(w.buf))
    w = Writer()
    w.varint(0)
    kv.set(b"doc/%08d/nodes" % 1, bytes(w.buf))
    kv.set(b"doc/%08d/meta" % 1, bytes(w.buf))
    blob = kv.export_all()
    back = DeviceTreeBatch.import_state(blob, mesh=make_mesh(jax.devices()[:1]))
    assert back.export_state() == blob
    assert back.children_maps()[0] == {None: [TreeID(peer, 0)]}
    assert back.parent_maps() == [{TreeID(peer, 0): None}, {}]


def test_staging_a_document_makes_no_object_a_move():
    """One document of 20,000 moves over 1,000 nodes: what staging it
    keeps is a few objects a NODE (its TreeID, its counter), never one a
    move."""
    a = LoroDoc(peer=7)
    tr = a.get_tree("tr")
    ids = [tr.create() for _ in range(1000)]
    rng = random.Random(5)
    made = 1000
    while made < 20_000:
        try:
            tr.move(ids[rng.randrange(1000)], ids[rng.randrange(1000)])
            made += 1
        except Exception:  # noqa: BLE001 — a cycle refused where it is made
            pass
    a.commit()
    payload, cid = _payload(a), tr.id
    batch = DeviceTreeBatch(2, 32_768, 1_024, mesh=make_mesh(jax.devices()[:1]))
    batch.append_payloads([payload, None], cid)  # every shape compiled here
    gc.collect()
    before = sys.getallocatedblocks()
    batch.append_payloads([None, payload], cid)
    gc.collect()
    grew = sys.getallocatedblocks() - before
    assert int(batch.counts[1]) == made
    assert grew < 5_000, grew
