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

from loro_tpu import LoroDoc  # noqa: E402
from loro_tpu.core.ids import ContainerID, ContainerType  # noqa: E402
from loro_tpu.doc import strip_envelope  # noqa: E402
from loro_tpu.obs import metrics as obs  # noqa: E402
from loro_tpu.ops import tree_batch  # noqa: E402
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
