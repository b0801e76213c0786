"""Cold start INTO residency (PR 35): rounds of full-history payloads into
successive empty slots of a durable ``ResidentServer`` — the path the cell
``b4_resident.coldstart16`` drives on the chip — on the CPU at tiny sizes.
The documents are the benchmark's own (``benchmarks/gen.py`` types the
seeded script of ``benchmarks/script.py``); what they must read comes from
three sides: ``Fleet.merge_text_payloads``, the host engine (``HostEngine``)
and the plain reference (``benchmarks/fugue_reference.py``)."""
from __future__ import annotations

import json
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import fugue_reference  # noqa: E402
import gen  # noqa: E402

from loro_tpu import native  # noqa: E402
from loro_tpu.codec.binary import decode_changes  # noqa: E402
from loro_tpu.core.ids import ContainerID, ContainerType  # noqa: E402
from loro_tpu.obs import metrics as obs  # noqa: E402
from loro_tpu.obs import trace as obs_trace  # noqa: E402
from loro_tpu.parallel.fleet import Fleet  # noqa: E402
from loro_tpu.parallel.mesh import make_mesh  # noqa: E402
from loro_tpu.parallel.server import ResidentServer  # noqa: E402
from loro_tpu.persist import recover_server  # noqa: E402
from loro_tpu.resilience.hostpath import HostEngine  # noqa: E402
from loro_tpu.resilience.supervisor import get_supervisor  # noqa: E402
from loro_tpu.utils import tracing  # noqa: E402

with open(os.path.join(BENCH, "configs", "b4_resident.json")) as _f:
    CONFIG = json.load(_f)
TINY = {**CONFIG, **CONFIG["rehearsal"]}
CID = ContainerID.root("text", ContainerType.Text)
SLOTS, CAPACITY, K = TINY["resident_documents"], TINY["capacity"], 2
SEEDS = [7, 2147483659]

# item 2 of ISSUE 35: the spans of one ingest round, in the order they end
# (``resident.commit_ids``: ISSUE 37), and the one read-back's
ROUND_SPANS = ["resident.decode", "resident.commit_ids", "resident.stage",
               "resident.order",
               "resident.upload", "resident.scatter", "resident.tombstone",
               "server.journal", "server.ingest", "server.fsync"]
READ_SPANS = ["resident.materialize", "resident.fetch"]


@pytest.fixture(scope="module")
def documents():
    """Per seed: the three fed documents (payload, ops) and what each must
    read, by the plain reference."""
    out = {}
    for seed in SEEDS:
        fed = [gen.make_payload(seed, TINY, v) for v in range(TINY["fleet_documents"])]
        refs = [fugue_reference.replay(seed, TINY, v) for v in range(len(fed))]
        out[seed] = fed, [r["text"] for r in refs]
    return out


@pytest.fixture
def mesh():
    return make_mesh(jax.devices()[:1])


def cold_start(server, fed, rounds: int, first_round: int = 0) -> list:
    """``rounds`` rounds of ``K`` payloads into the next empty slots, each
    acknowledged as the cell acknowledges it; the acknowledgements."""
    acks = []
    for r in range(first_round, first_round + rounds):
        slots = list(range(r * K, (r + 1) * K))
        updates = [None] * SLOTS
        for s in slots:
            updates[s] = fed[s % len(fed)]["payload"]
        epoch = server.ingest(updates, CID)
        server.flush_durable()
        jax.block_until_ready(server.batch.cols)
        acks.append((epoch, server.durable_epoch, slots))
    return acks


def expected(texts: list, loaded: int) -> list:
    return [texts[s % len(texts)] if s < loaded else "" for s in range(SLOTS)]


@pytest.mark.parametrize("seed", SEEDS)
def test_slots_read_what_three_sides_say_and_the_rest_stay_empty(
        tmp_path, mesh, documents, seed):
    fed, want = documents[seed]
    server = ResidentServer("text", SLOTS, mesh=mesh, capacity=CAPACITY,
                            durable_dir=str(tmp_path), durable_fsync="group")
    try:
        acks = cold_start(server, fed, rounds=3)
        got = server.texts()
    finally:
        server.close()
    assert got == expected(want, loaded=3 * K)
    # the same payloads through the bulk entry, and through the host engine
    assert Fleet(mesh).merge_text_payloads(
        [d["payload"] for d in fed], CID).texts == want
    host = HostEngine("text", len(fed))
    host.apply([decode_changes(d["payload"]) for d in fed], CID)
    assert host.texts() == want
    # a round is counted only once the log's fsync has covered its epoch
    assert all(durable >= epoch for epoch, durable, _slots in acks)
    assert [e for e, _d, _s in acks] == sorted({e for e, _d, _s in acks})


@pytest.mark.parametrize("seed", SEEDS)
def test_a_fresh_server_recovered_from_the_directory_reads_the_same(
        tmp_path, mesh, documents, seed):
    fed, want = documents[seed]
    server = ResidentServer("text", SLOTS, mesh=mesh, capacity=CAPACITY,
                            durable_dir=str(tmp_path), durable_fsync="group")
    cold_start(server, fed, rounds=2)
    before, epoch = server.texts(), server.epoch
    server.close()
    again = recover_server(str(tmp_path), mesh=mesh)
    try:
        assert again.texts() == before == expected(want, loaded=2 * K)
        assert again.epoch == epoch and again.durable_epoch == epoch
        # and goes on where the first left off: the next empty slots
        updates = [None] * SLOTS
        updates[2 * K] = fed[(2 * K) % len(fed)]["payload"]
        assert again.ingest(updates, CID) > epoch
        assert again.texts() == expected(want, loaded=2 * K + 1)
    finally:
        again.close()


def test_every_span_once_a_round_under_one_trace_id_and_the_counters_tick(
        tmp_path, mesh, documents, capsys):
    fed, want = documents[SEEDS[0]]
    server = ResidentServer("text", SLOTS, mesh=mesh, capacity=CAPACITY,
                            durable_dir=str(tmp_path), durable_fsync="group")
    cold_start(server, fed, rounds=1)  # the first round also checkpoints
    # the supervisor is the process's: whatever ran before, no drain (a
    # ``sup.drain`` span, one launch in eight) falls into these two rounds
    get_supervisor().drain()
    names = ("fleet.resident_rows_total", "fleet.resident_tombstones_total",
             "server.ingest_rounds_total", "persist.wal_bytes_appended_total",
             "persist.wal_fsyncs_total")
    c0 = [obs.counter(n).total() for n in names]
    idmap0 = id_map_counts()
    tracing.clear()
    tracing.enable()
    try:
        cold_start(server, fed, rounds=2, first_round=1)
        spans = tracing.events()
        dump = tracing.dump(str(tmp_path / "rounds.json"))
        tracing.clear()
        texts = server.texts()
        read = tracing.events()
    finally:
        tracing.disable()
        tracing.clear()
        server.close()
    assert texts == expected(want, loaded=3 * K)
    assert [e["name"] for e in spans] == ROUND_SPANS * 2
    assert [e["name"] for e in read] == READ_SPANS
    by_id = {e["span_id"]: e for e in spans}
    rounds = [e for e in spans if e["name"] == "server.ingest"]
    assert len({e["trace_id"] for e in rounds}) == 2  # an id a round
    for e in spans:
        if e["name"] in ("server.ingest", "server.fsync"):
            assert e["parent_id"] == 0  # the round; the caller's flush
        else:  # every stage under its round, carrying the round's id
            up = by_id[e["parent_id"]]
            assert up["name"] == "server.ingest" and e["trace_id"] == up["trace_id"]
    assert all(e["args"]["docs"] == K for e in rounds)
    ops = sum(fed[s % len(fed)]["n_ops"] for s in range(K, 3 * K))
    payload_bytes = sum(len(fed[s % len(fed)]["payload"]) for s in range(K, 3 * K))
    assert sum(e["args"]["bytes"] for e in spans
               if e["name"] == "server.journal") == payload_bytes
    rows, tombs, n_rounds, wal_bytes, fsyncs = (
        obs.counter(n).total() - c for n, c in zip(names, c0))
    # single-character patches: a row an insert, a tombstone a delete
    assert rows + tombs == ops and rows == 2 * K * TINY["insert_patches"]
    assert n_rounds == 2 and fsyncs == 2 and wal_bytes > payload_bytes
    # ISSUE 37: the id maps' commit has a span of its own, a sibling of the
    # stages, and the id map's boundary counts what it took and how long
    commits = [e["args"] for e in spans if e["name"] == "resident.commit_ids"]
    assert commits == [{"docs": K, "ids": K * TINY["insert_patches"]}] * 2
    moved = {k: v - idmap0[k] for k, v in id_map_counts().items()}
    assert moved["ids.commit"] == moved["ids.stage"] == rows
    assert moved["ids.lookup"] > 0  # cross-epoch parents, delete targets
    assert all(moved[k] > 0 for k in ("ns.stage", "ns.lookup", "ns.commit",
                                      "decode_ns"))
    # the account of the same two rounds, from the dump alone
    table = obs_trace.round_rows(obs_trace.load_artifact(dump))
    assert [(r["trace"], r["docs"]) for r in table] == [
        (e["trace_id"], K) for e in rounds]
    for r, e in zip(table, rounds):
        assert r["ms"] == pytest.approx((e["end_ns"] - e["start_ns"]) / 1e6)
        assert sum(r["cols"].values()) == pytest.approx(r["ms"])
        assert set(r["cols"]) == {"unnamed", *ROUND_SPANS[:-2]}
        assert all(v >= 0 for v in r["cols"].values())
    assert obs_trace.main(["rounds", dump]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[0] for ln in lines] == [
        "trace", *(e["trace_id"] for e in rounds), "median", "max", "longest:"]
    assert all(name in lines[0] for name in ("resident.commit_ids", "unnamed",
                                             "gc.pause"))


def id_map_counts() -> dict:
    """The counters at the id map's and the decoder's boundary (ISSUE 37)."""
    ids, ns = obs.counter("fleet.idmap_ids_total"), obs.counter("fleet.idmap_ns_total")
    out = {f"ids.{op}": ids.get(op=op) for op in ("stage", "lookup", "commit")}
    out.update({f"ns.{op}": ns.get(op=op) for op in ("stage", "lookup", "commit")})
    out["decode_ns"] = obs.counter("codec.native_decode_ns_total").get(fn="seq_delta")
    return out


def test_a_servers_first_round_records_its_checkpoint_and_no_later_one_does(
        tmp_path, mesh, documents):
    """ISSUE 37: the auto-checkpoint before a server's first launch is a
    span of its own under the round, so that round is not one opaque span."""
    fed, _want = documents[SEEDS[0]]
    server = ResidentServer("text", SLOTS, mesh=mesh, capacity=CAPACITY,
                            durable_dir=str(tmp_path), durable_fsync="group")
    tracing.clear()
    tracing.enable()
    try:
        cold_start(server, fed, rounds=2)
        spans = tracing.events()
    finally:
        tracing.disable()
        tracing.clear()
        server.close()
    first, second = [e for e in spans if e["name"] == "server.ingest"]
    taken = [e for e in spans if e["name"] == "server.checkpoint"]
    assert [e["parent_id"] for e in taken] == [first["span_id"]]
    assert taken[0]["trace_id"] == first["trace_id"] != second["trace_id"]
    # it ends before the round's first stage starts: a sibling of them
    decode = next(e for e in spans if e["name"] == "resident.decode")
    assert taken[0]["end_ns"] <= decode["start_ns"]


@pytest.mark.parametrize("slots", [SLOTS, 3 * SLOTS])
def test_a_rounds_block_follows_the_documents_it_names_not_the_table(
        tmp_path, mesh, documents, slots):
    """ISSUE 36: what a round stages, uploads and scatters is
    ``pad_bucket(named) x pad_bucket(longest)`` rows of 34 B whatever the
    table holds — the same count in a table three times as large."""
    from loro_tpu.ops.fugue_batch import pad_bucket
    from loro_tpu.parallel.fleet import _named_bucket

    fed, want = documents[SEEDS[0]]
    server = ResidentServer("text", slots, mesh=mesh, capacity=CAPACITY,
                            durable_dir=str(tmp_path), durable_fsync="group")
    waste = obs.counter("fleet.pad_waste_rows_total")
    tracing.clear()
    tracing.enable()
    try:
        w0 = waste.get(family="resident_seq")
        updates = [None] * slots
        for s in range(K):
            updates[s] = fed[s % len(fed)]["payload"]
        server.ingest(updates, CID)
        args = {e["name"]: e["args"] for e in tracing.events()}
        texts = server.texts()
    finally:
        tracing.disable()
        tracing.clear()
        server.close()
    assert texts[:K] == [want[s % len(want)] for s in range(K)]
    assert texts[K:] == [""] * (slots - K)
    k_pad = _named_bucket(K)
    width = pad_bucket(TINY["insert_patches"], floor=16)
    assert args["resident.stage"] == {"docs": K, "bytes": 34 * k_pad * width}
    assert args["resident.upload"] == {"docs": K}
    assert waste.get(family="resident_seq") - w0 == (
        k_pad * width - K * TINY["insert_patches"])


def test_without_the_library_the_answers_are_the_same_and_the_fallbacks_tick(
        tmp_path, mesh, documents, monkeypatch):
    fed, want = documents[SEEDS[1]]
    monkeypatch.setattr(native, "_load", lambda: None)
    fallbacks = obs.counter("fleet.host_fallback_total")
    before = {k: fallbacks.get(kind=k) for k in ("idmap", "order", "payload_decode")}
    server = ResidentServer("text", SLOTS, mesh=mesh, capacity=CAPACITY,
                            durable_dir=str(tmp_path), durable_fsync="group")
    try:
        cold_start(server, fed, rounds=2)
        assert server.texts() == expected(want, loaded=2 * K)
    finally:
        server.close()
    moved = {k: fallbacks.get(kind=k) - v for k, v in before.items()}
    # a Python id map and a Python order engine a slot, a Python decode a round
    assert moved == {"idmap": SLOTS, "order": SLOTS, "payload_decode": 2}
