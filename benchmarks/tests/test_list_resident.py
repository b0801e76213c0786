"""The resident List cell's own pieces, on the CPU: the configuration's
documents are ``b4_resident``'s, key for key; the generator and the plain
reference read the same lists; a window that fills every slot ends and
still reports; the control and each planted fault move the number they
are meant to move; a program whose List payloads take the Python decoder
exits 2 before the warm-up round; the span metrics read a traced run, and
read nothing from a program that has no such spans; the byte count's
arithmetic and the cell's per-layer set.  The cell end to end, its
control and its faults as every cell's (``correct`` false) run in
``test_benchmark.py`` too, which finds it in the manifest.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import os

import pytest

import bytes_model
import list_gen
import list_reference
import run as bench
from faults import ingest_list_resident as faults
from readers import span_self_ms
from test_benchmark import fresh_counters, run_cell  # noqa: F401  (autouse there, so here)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "list_resident.coldstart16"
CONFIG = bench.load_json(HERE, "configs", "list_resident.json")
TRAFFIC = bench.load_json(HERE, "traffic", "list_coldstart16.json")
TINY = {**CONFIG, **CONFIG["rehearsal"]}
ROUNDS = TINY["resident_documents"] // TRAFFIC["rehearsal"]["docs_per_round"]
SPAN_METRICS = ["resident_host_decode_ms", "resident_host_order_ms",
                "resident_host_stage_ms", "resident_host_upload_ms",
                "resident_host_journal_ms", "resident_device_wait_ms",
                "resident_host_values_ms"]
# the one number each fault is there to move (others may move with it)
MOVES = {"value_altered": "values_differing",
         "half_a_round_left_out": "values_differing",
         "two_slots_swapped": "slots_wrong",
         "wal_record_dropped": "rounds_not_durable",
         "counted_before_flush": "acknowledged_before_durable",
         "fallback_counter_moved": "counters_moved",
         "decoded_by_the_walk": "native_explodes_missing"}


def test_the_documents_are_b4_residents_key_for_key():
    src = bench.load_json(HERE, "configs", "b4_resident.json")
    for key in ("insert_patches", "delete_patches", "chains_after_contraction",
                "chains_tolerance", "insert_run", "delete_run",
                "peers_per_document", "peer_window", "sync_every_patches",
                "fleet_documents", "capacity", "durable_fsync"):
        assert CONFIG[key] == src[key], key
    assert {k: v for k, v in CONFIG["rehearsal"].items()} == src["rehearsal"]
    assert set(CONFIG["reduced"]) == {"fleet_documents", "resident_documents"}
    # the warm-up and 30 timed rounds of 16
    assert CONFIG["resident_documents"] == 496 == TRAFFIC["docs_per_round"] * 31
    assert len(CONFIG["source"]) <= 200 and set(CONFIG["guarantees"]) == {
        "read_back", "acknowledged", "durable", "no_fallback"}


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_the_generator_and_the_reference_read_the_same_lists(seed):
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.fleet import DeviceDocBatch

    fed = [list_gen.make_payload(seed, TINY, v) for v in range(2)]
    refs = [list_reference.replay(seed, TINY, v) for v in range(2)]
    for d, r in zip(fed, refs):
        assert d["elements"] == r["inserts"] == TINY["insert_patches"]
        assert r["deletes"] == TINY["delete_patches"]
        assert abs(d["chains"] - r["chains"]) <= 1
        assert all(isinstance(x, str) and len(x) == 1 for x in r["values"])
    batch = DeviceDocBatch(2, TINY["capacity"], as_text=False)
    batch.append_payloads([d["payload"] for d in fed],
                          ContainerID.root(list_gen.CONTAINER, ContainerType.List))
    assert batch.values() == [r["values"] for r in refs]


def test_a_window_that_fills_every_slot_ends_and_still_reports(capsys):
    rc, res = run_cell(capsys, CELL, seconds=600.0)
    assert rc == 0 and res["correct"] is True
    assert res["attempted"] == ROUNDS - 1 and res["failed"] == 0  # one was the warm-up
    assert res["metrics"]["cpu_rehearsal.import_ops_per_s"]["value"] > 0


def test_the_control_differs_in_every_loaded_slot_and_in_nothing_else(capsys):
    rc, res = run_cell(capsys, CELL, "--control", seconds=600.0)
    assert rc == 1 and res["correct"] is False
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert over == {"values_differing"}
    assert res["compared"]["values_differing"]["value"] == TINY["resident_documents"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_planted_fault_moves_the_number_it_is_there_to_move(
        capsys, monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    rc, res = run_cell(capsys, CELL)
    assert rc == 1 and res["correct"] is False
    moved = res["compared"][MOVES[fault]]
    assert moved["value"] > moved["limit"]
    assert set(MOVES) == set(faults.FAULTS)


def test_a_program_without_value_positions_fails_before_the_warm_up(
        capsys, monkeypatch):
    # the parent commit: its DeviceDocBatch sends a List payload to the
    # Python decoder and walk, and the native explode reports no values
    from loro_tpu.parallel.server import ResidentServer

    faults.walk_after_probe(monkeypatch, calls_before=0)
    ingests = []
    real = ResidentServer.ingest
    monkeypatch.setattr(ResidentServer, "ingest",
                        lambda self, u, cid=None: ingests.append(self.n_docs)
                        or real(self, u, cid))
    with pytest.raises(SystemExit) as e:
        run_cell(capsys, CELL)
    assert e.value.code == 2
    assert "does not take the native delta explode" in capsys.readouterr().err
    assert ingests == [4]  # the probe's scratch server, no round of the cell


def test_span_metrics_read_a_traced_run_and_nothing_from_a_program_without_spans(
        capsys, monkeypatch):
    from loro_tpu.utils import tracing

    rc, res = run_cell(capsys, CELL, "--trace", "1")
    assert rc == 0
    read = {k.split(".", 1)[1]: v["value"] for k, v in res["metrics"].items()}
    assert set(read) == set(SPAN_METRICS) and all(v > 0 for v in read.values())
    values = [e["args"] for e in tracing.events() if e["name"] == "resident.values"]
    assert len(values) == res["attempted"]
    per_round = TRAFFIC["rehearsal"]["docs_per_round"]
    assert all(a["docs"] == per_round and a["values"] == per_round * TINY["insert_patches"]
               for a in values)
    # the parent commit: the driver's own span is there, the program's are not
    spans = [{"name": "bench.columns_ready", "span_id": 1, "parent_id": 0,
              "start_ns": 0, "end_ns": 5_000_000}]
    monkeypatch.setattr(span_self_ms, "spans_of_window", lambda: spans)
    spec = bench.load_json(HERE, "layer_metrics", "resident_host_values_ms.json")
    assert spec["params"] == {"spans": ["resident.values"], "per": "server.ingest"}
    for name in SPAN_METRICS:
        spec = bench.load_json(HERE, "layer_metrics", name + ".json")
        assert spec["params"]["per"] == "server.ingest"
        assert span_self_ms.read(spec["params"], None) is None


def test_the_byte_count_is_the_traffic_files_arithmetic():
    b_in, b_out = TRAFFIC["bytes_in_per_element"], TRAFFIC["bytes_out_per_element"]
    ins, dels = CONFIG["insert_patches"], CONFIG["delete_patches"]
    # a 34 B resident row an inserted value, a 1 B tombstone a delete
    assert round((ins * 34 + dels) / (ins + dels), 1) == b_in
    assert b_out == 0
    assert (b_in, b_out) == tuple(bench.load_json(HERE, "traffic", "coldstart16.json")[k]
                                  for k in ("bytes_in_per_element", "bytes_out_per_element"))
    assert bytes_model.import_bytes(ins + dels, b_in, b_out) == pytest.approx(
        (ins + dels) * 24.2)
    manifest = bench.load_json(bench.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m["workloads"]}
    assert listed == {*SPAN_METRICS, "device_idle_pct.import", "import_roofline_pct",
                      "import_launch_gap_ms"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "list_coldstart16"
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "import_ops_per_s")
    assert CELL in rate["workloads"]
