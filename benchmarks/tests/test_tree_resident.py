"""The resident tree cell's own pieces, on the CPU: the configuration's
documents are ``tree_import``'s, key for key; a window that fills every
slot ends and still reports; the control and each planted fault move the
number they are meant to move; a program whose resident tree keeps no
standing state fails at once; the settle's block follows the documents a
round names; the span metrics read a traced run, and read nothing from a
program that has no such spans; the byte count's arithmetic and the cell's
per-layer set.  The cell end to end, its control and its faults as every
cell's (``correct`` false) run in ``test_benchmark.py`` too, which finds it
in the manifest.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import os

import pytest

import bytes_model
import run as bench
from faults import ingest_tree_resident as faults
from readers import span_self_ms
from test_benchmark import fresh_counters, run_cell  # noqa: F401  (autouse there, so here)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tree_resident.coldstart16"
CONFIG = bench.load_json(HERE, "configs", "tree_resident.json")
TRAFFIC = bench.load_json(HERE, "traffic", "tree_coldstart16.json")
TINY = {**CONFIG, **CONFIG["rehearsal"]}
PER_ROUND = TRAFFIC["rehearsal"]["docs_per_round"]
ROUNDS = TINY["resident_documents"] // PER_ROUND
SPAN_METRICS = ["resident_host_decode_ms", "resident_host_stage_ms",
                "resident_host_upload_ms", "resident_host_settle_ms",
                "resident_host_journal_ms", "resident_device_wait_ms"]
# the one number each fault is there to move (others may move with it)
MOVES = {"parent_altered": "parent_maps_differing",
         "half_a_round_left_out": "parent_maps_differing",
         "named_document_unsettled": "documents_unsettled",
         "two_slots_swapped": "parent_maps_differing",
         "wal_record_dropped": "rounds_not_durable",
         "counted_before_flush": "acknowledged_before_durable",
         "fallback_counter_moved": "counters_moved"}


def test_the_documents_are_tree_imports_key_for_key():
    src = bench.load_json(HERE, "configs", "tree_import.json")
    for key in ("nodes", "move_draws", "draw", "deletes", "peers_per_document",
                "peer_ids", "peer_window", "fleet_documents", "assumed"):
        assert CONFIG[key] == src[key], key
    # the rehearsal's documents too; what it adds is the deployment's
    assert {k: v for k, v in CONFIG["rehearsal"].items() if k in src["rehearsal"]} \
        == src["rehearsal"]
    assert set(CONFIG["reduced"]) == {"fleet_documents", "resident_documents"}
    assert (CONFIG["resident_documents"], CONFIG["move_capacity"],
            CONFIG["node_capacity"], CONFIG["durable_fsync"]) == (144, 131072, 1024, "group")
    # a fresh slot takes a whole document's window: pad_bucket of its ops
    from loro_tpu.ops.fugue_batch import pad_bucket

    assert pad_bucket(CONFIG["nodes"] + CONFIG["move_draws"], floor=16) \
        == CONFIG["move_capacity"]
    assert CONFIG["nodes"] <= CONFIG["node_capacity"] < 0xFFFF - 3  # the kernel's 16 bits
    assert len(CONFIG["source"]) <= 200 and set(CONFIG["guarantees"]) == {
        "read_back", "refusals", "settled", "acknowledged", "durable", "no_fallback"}


def test_a_window_that_fills_every_slot_ends_and_still_reports(capsys):
    rc, res = run_cell(capsys, CELL, seconds=600.0)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] == ROUNDS - 1 and res["failed"] == 0  # one was the warm-up
    assert res["metrics"]["cpu_rehearsal.import_ops_per_s"]["value"] > 0


def test_the_control_differs_in_every_loaded_slot_and_in_nothing_else(capsys):
    rc, res = run_cell(capsys, CELL, "--control", seconds=600.0)
    assert rc == 1 and res["correct"] is False
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert over == {"parent_maps_differing"}
    assert res["compared"]["parent_maps_differing"]["value"] == TINY["resident_documents"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_planted_fault_moves_the_number_it_is_there_to_move(
        capsys, monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    rc, res = run_cell(capsys, CELL)
    assert rc == 1 and res["correct"] is False
    moved = res["compared"][MOVES[fault]]
    assert moved["value"] > moved["limit"]
    assert set(MOVES) == set(faults.FAULTS)


def test_a_program_without_standing_state_fails_at_once(capsys, monkeypatch):
    # the parent commit: its DeviceTreeBatch replays the whole table at
    # every read and keeps no answer of its own
    from loro_tpu.parallel.fleet import DeviceTreeBatch

    monkeypatch.delattr(DeviceTreeBatch, "refusals")
    with pytest.raises(SystemExit) as e:
        run_cell(capsys, CELL)
    assert e.value.code == 2
    assert "keeps no standing parents" in capsys.readouterr().err


def test_the_settle_block_is_the_named_documents(capsys):
    from loro_tpu.parallel.fleet import _named_bucket
    from loro_tpu.utils import tracing

    rc, res = run_cell(capsys, CELL, "--trace", "1")
    assert rc == 0
    settles = [e for e in tracing.events() if e["name"] == "resident.settle"]
    assert len(settles) == res["attempted"]
    k_pad = _named_bucket(PER_ROUND)
    for e in settles:
        assert e["args"]["docs"] == PER_ROUND
        assert e["args"]["rows"] == k_pad * TINY["move_capacity"]
    # the cell's own launch: [16, 131,072], not the table's 144 documents
    assert _named_bucket(TRAFFIC["docs_per_round"]) == TRAFFIC["docs_per_round"] == 16


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
    settle = bench.load_json(HERE, "layer_metrics", "resident_host_settle_ms.json")
    assert settle["params"] == {"spans": ["resident.settle"], "per": "server.ingest"}
    for name in SPAN_METRICS:
        spec = bench.load_json(HERE, "layer_metrics", name + ".json")
        assert spec["params"]["per"] == "server.ingest"
        assert span_self_ms.read(spec["params"], None) is None


def test_the_byte_count_is_the_traffic_files_arithmetic():
    b_in, b_out = TRAFFIC["bytes_in_per_element"], TRAFFIC["bytes_out_per_element"]
    # a 25 B log row written by the scatter and read again by the settle
    assert b_in == 2 * (6 * 4 + 1)
    # the 1,000 int32 standing parents of a document over its ops, rounded up
    ops = CONFIG["nodes"] + 0.966 * CONFIG["move_draws"]
    assert 4 * CONFIG["nodes"] / ops <= b_out <= 1.25 * 4 * CONFIG["nodes"] / ops
    assert b_out == bench.load_json(HERE, "traffic", "fleet256.json")["bytes_out_per_element"]
    assert bytes_model.import_bytes(97600, b_in, b_out) == pytest.approx(97600 * 50.05)
    manifest = bench.load_json(bench.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m["workloads"]}
    assert listed == {*SPAN_METRICS, "device_idle_pct.import", "import_roofline_pct",
                      "import_launch_gap_ms", "tree_replay_device_ms"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "tree_coldstart16"
