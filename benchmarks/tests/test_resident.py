"""The resident cell's own pieces, on the CPU (PR 35): the configuration's
documents are ``b4_import``'s, key for key; a window that fills every slot
ends and still reports; the two reads of the WAL (at the last
acknowledgement, after close) find every acknowledged round; the control
and each planted fault move the number they are meant to move;
the span metrics read a traced run, and read nothing from a program that
has no such spans (the parent commit); the byte count's arithmetic.  The
cell end to end, its control and its faults as every cell's (``correct``
false) run in ``test_benchmark.py``, which finds it in the manifest.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import os

import pytest

import bytes_model
import run as bench
from drivers import ingest_resident
from faults import ingest_resident as faults
from readers import span_self_ms
from test_benchmark import fresh_counters, run_cell  # noqa: F401  (autouse there, so here)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "b4_resident.coldstart16"
CONFIG = bench.load_json(HERE, "configs", "b4_resident.json")
TRAFFIC = bench.load_json(HERE, "traffic", "coldstart16.json")
TINY = {**CONFIG, **CONFIG["rehearsal"]}
ROUNDS = TINY["resident_documents"] // TRAFFIC["rehearsal"]["docs_per_round"]
SPAN_METRICS = ["resident_host_decode_ms", "resident_host_order_ms",
                "resident_host_stage_ms", "resident_host_upload_ms",
                "resident_host_journal_ms", "resident_device_wait_ms"]
# the one number each fault is there to move (others may move with it)
MOVES = {"text_altered": "texts_differing",
         "half_a_round_left_out": "texts_differing",
         "two_slots_swapped": "slots_wrong",
         "wal_record_dropped": "rounds_not_durable",
         "counted_before_flush": "acknowledged_before_durable",
         "fallback_counter_moved": "counters_moved"}


def test_the_documents_are_b4_imports_key_for_key():
    src = bench.load_json(HERE, "configs", "b4_import.json")
    for key in ("insert_patches", "delete_patches", "chains_after_contraction",
                "chains_tolerance", "insert_run", "delete_run",
                "peers_per_document", "peer_window", "sync_every_patches",
                "fleet_documents", "assumed"):
        assert CONFIG[key] == src[key], key
    # the rehearsal's documents too; what it adds is the deployment's
    assert {k: v for k, v in CONFIG["rehearsal"].items() if k in src["rehearsal"]} \
        == src["rehearsal"]
    assert set(CONFIG["reduced"]) == {"fleet_documents", "resident_documents"}
    assert (CONFIG["resident_documents"], CONFIG["capacity"],
            CONFIG["durable_fsync"]) == (144, 262144, "group")
    # the table is what ONE window loads: a whole number of rounds fills it
    assert CONFIG["resident_documents"] % TRAFFIC["docs_per_round"] == 0
    assert len(CONFIG["source"]) <= 200 and set(CONFIG["guarantees"]) == {
        "read_back", "acknowledged", "durable", "no_fallback"}


def test_a_window_that_fills_every_slot_ends_and_still_reports(capsys):
    # a window far longer than the slots last: it ends at the last slot's
    # acknowledgement, and the rate is over the time to it
    rc, res = run_cell(capsys, CELL, seconds=600.0)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] == ROUNDS - 1 and res["failed"] == 0  # one was the warm-up
    assert res["metrics"]["cpu_rehearsal.import_ops_per_s"]["value"] > 0


def test_both_reads_of_the_wal_find_every_acknowledged_round(capsys, monkeypatch):
    seen = {}
    real = ingest_resident.not_durable
    real_close = ingest_resident.wal_rounds

    def watched(run, *reads):
        seen.update(acks=list(run.acks), reads=[dict(r) for r in reads],
                    sha=list(run.sha), variants=len(run.variants))
        return real(run, *reads)

    def read(wal_dir):
        # the first read is made of a copy, with the server still open
        seen.setdefault("dirs", []).append(os.path.basename(wal_dir))
        return real_close(wal_dir)

    monkeypatch.setattr(ingest_resident, "not_durable", watched)
    monkeypatch.setattr(ingest_resident, "wal_rounds", read)
    rc, res = run_cell(capsys, CELL, seconds=600.0)
    assert rc == 0 and res["compared"]["rounds_not_durable"]["value"] == 0
    assert seen["dirs"] == ["wal_at_ack", "wal"]
    assert len(seen["acks"]) == ROUNDS  # the warm-up's round is held too
    per = TRAFFIC["rehearsal"]["docs_per_round"]
    at_ack, after_close = seen["reads"]
    assert at_ack == after_close and len(at_ack) == ROUNDS
    for r, ack in enumerate(seen["acks"]):
        assert ack["slots"] == list(range(r * per, (r + 1) * per))
        assert sorted(at_ack[ack["epoch"]]) == ack["slots"]
        assert ack["durable_epoch"] >= ack["epoch"]
        # its group commit; the first round also checkpoints and rotates
        assert ack["fsyncs"] == (1 if r else 3)
    assert len(set(seen["sha"])) == seen["variants"] == TINY["fleet_documents"]


def test_a_round_the_log_lacked_at_its_acknowledgement_is_not_durable():
    class Run:
        sha = ["a", "b", "c"]
        variants = [None] * 3
        acks = [{"epoch": 1, "slots": [0, 1]}, {"epoch": 2, "slots": [2, 3]}]

    whole = {1: {0: "a", 1: "b"}, 2: {2: "c", 3: "a"}}
    assert ingest_resident.not_durable(Run, whole, whole) == 0
    # the record reached the file only with close()'s sync
    assert ingest_resident.not_durable(Run, {1: whole[1]}, whole) == 1
    # another payload under the slot; a slot the round did not name
    assert ingest_resident.not_durable(Run, whole, {**whole, 2: {2: "c", 3: "b"}}) == 1
    assert ingest_resident.not_durable(Run, {**whole, 1: {0: "a"}}, whole) == 1


def test_the_control_differs_in_every_loaded_slot_and_in_nothing_else(capsys):
    rc, res = run_cell(capsys, CELL, "--control", seconds=600.0)
    assert rc == 1 and res["correct"] is False
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert over == {"texts_differing"}
    assert res["compared"]["texts_differing"]["value"] == TINY["resident_documents"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_planted_fault_moves_the_number_it_is_there_to_move(
        capsys, monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    rc, res = run_cell(capsys, CELL)
    assert rc == 1 and res["correct"] is False
    moved = res["compared"][MOVES[fault]]
    assert moved["value"] > moved["limit"]
    assert set(MOVES) == set(faults.FAULTS)


def test_span_metrics_read_a_traced_run_and_nothing_from_a_program_without_spans(
        capsys, monkeypatch):
    rc, res = run_cell(capsys, CELL, "--trace", "1")
    assert rc == 0
    read = {k.split(".", 1)[1]: v["value"] for k, v in res["metrics"].items()}
    assert set(read) == set(SPAN_METRICS) and all(v > 0 for v in read.values())
    # the parent commit: the driver's own span is there, the program's are not
    spans = [{"name": "bench.columns_ready", "span_id": 1, "parent_id": 0,
              "start_ns": 0, "end_ns": 5_000_000}]
    monkeypatch.setattr(span_self_ms, "spans_of_window", lambda: spans)
    for name in SPAN_METRICS:
        spec = bench.load_json(HERE, "layer_metrics", name + ".json")
        assert spec["params"]["per"] == "server.ingest"
        assert span_self_ms.read(spec["params"], None) is None
    spans.append({"name": "server.ingest", "span_id": 2, "parent_id": 0,
                  "start_ns": 0, "end_ns": 1})
    wait = bench.load_json(HERE, "layer_metrics", "resident_device_wait_ms.json")
    assert span_self_ms.read(wait["params"], None) == 5.0


def test_the_byte_count_is_the_traffic_files_arithmetic():
    c = CONFIG
    ops = c["insert_patches"] + c["delete_patches"]
    # a 26 B row and 8 B of key an insert, a 1 B tombstone write a delete
    exact = (c["insert_patches"] * (26 + 8) + c["delete_patches"]) / ops
    assert exact <= TRAFFIC["bytes_in_per_element"] < exact + 0.1
    assert TRAFFIC["bytes_out_per_element"] == 0
    assert bytes_model.import_bytes(ops, TRAFFIC["bytes_in_per_element"], 0) \
        == pytest.approx(ops * TRAFFIC["bytes_in_per_element"])
    manifest = bench.load_json(bench.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m["workloads"]}
    assert listed == {*SPAN_METRICS, "device_idle_pct.import",
                      "import_roofline_pct", "import_launch_gap_ms"}
