"""CPU rehearsals of the benchmark at tiny sizes (``--rehearsal``): the
manifest and its data files, every cell end to end, each cell's control
and planted faults (``correct`` must come out false), the trace
reduction on a recorded v5e trace, the byte counts from shapes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import importlib
import json
import os

import pytest

import bytes_model
import run as bench
import trace_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = bench.load_json(bench.ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.fixture(autouse=True)
def fresh_counters():
    """The obs registry is the process's: a counter that one test moved
    must not fail the next."""
    from loro_tpu.obs import metrics as obs

    obs.reset()


def run_cell(capsys, cell: str, *extra: str, seed: int = 2147483659,
             seconds: float = 0.5):
    rc = bench.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--rehearsal", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])


def test_manifest_names_only_what_exists():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    assert "setup_s" in e2e
    for c in configs.values():
        body = bench.load_json(bench.ROOT, c["file"])
        assert set(c["reduced"]) == set(body["reduced"])
        assert body["guarantees"]
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        traffic = bench.load_json(HERE, "traffic", w["traffic"] + ".json")
        importlib.import_module(f"drivers.{traffic['driver']}")
    for m in MANIFEST["per_layer"]:
        spec = bench.load_json(HERE, "layer_metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
        importlib.import_module(f"readers.{spec['reader']}")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_line(capsys, cell, trace):
    rc, res = run_cell(capsys, cell, "--trace", str(trace))
    assert rc == 0 and res["correct"] is True and res["rehearsal"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in res["compared"].values())
    assert res["device"]["platform"] == "cpu"
    # a CPU number never stands under a device metric's name
    assert all(k.startswith("cpu_rehearsal.") for k in res["metrics"])
    if trace:  # no device plane in a CPU trace: its readers return nothing
        assert "busy_s" not in res["device"]
        assert not any("roofline" in k or "idle" in k for k in res["metrics"])
    else:
        names = {k.split(".", 1)[1] for k in res["metrics"]}
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(capsys, cell):
    rc, res = run_cell(capsys, cell, "--control")
    assert rc == 1 and res["correct"] is False


def _fleet_fault(monkeypatch, fault: str):
    from loro_tpu.obs import metrics as obs
    from loro_tpu.parallel.fleet import Fleet

    real = Fleet.merge_text_payloads
    calls = {"n": 0}

    def broken(self, payloads, cid):
        calls["n"] += 1
        if fault == "half_of_the_batch_left_out":
            return real(self, payloads[: len(payloads) // 2], cid)
        out = real(self, payloads, cid)
        if calls["n"] < 2:  # the warm-up call stays sound
            return out
        if fault == "answer_altered":
            out.texts[-1] = out.texts[-1][:-1] + "☃"
        elif fault == "fallback_counter_moved":
            obs.counter("fleet.host_fallback_total").inc(where="test")
        return out

    monkeypatch.setattr(Fleet, "merge_text_payloads", broken)


def _packed_fault(monkeypatch, fault: str):
    import numpy as np

    from loro_tpu.obs import metrics as obs
    from loro_tpu.ops import fugue_batch

    real = fugue_batch.merge_text_payloads_packed

    def broken(pairs, cid, pad_c, pad_n, chunk, n_docs, budget_s=float("inf")):
        outs, done, ops, dt, nw = real(pairs, cid, pad_c, pad_n, chunk, n_docs,
                                       budget_s)
        if fault == "answer_altered":
            sums, counts = outs[-1]
            outs[-1] = (np.asarray(sums) + np.uint32(1), counts)
        elif fault == "half_of_the_batch_left_out":
            outs = outs[: max(1, len(outs) // 2)]  # launches counted, not made
        elif fault == "fallback_counter_moved":
            obs.counter("fleet.host_fallback_total").inc(where="test")
        return outs, done, ops, dt, nw

    monkeypatch.setattr(fugue_batch, "merge_text_payloads_packed", broken)


# driver -> (how to plant a fault under the timed path, the faults that
# cell can have).  One chip and no carried state: no exchange between
# chips, no step that returns its state unchanged.
FAULTS = {
    "import_packed": (_packed_fault, ["answer_altered",
                                      "half_of_the_batch_left_out",
                                      "fallback_counter_moved"]),
    "import_fleet": (_fleet_fault, ["answer_altered",
                                    "half_of_the_batch_left_out",
                                    "fallback_counter_moved"]),
}


def _fault_cases():
    for w in MANIFEST["workloads"]:
        driver = bench.load_json(HERE, "traffic", w["traffic"] + ".json")["driver"]
        for fault in FAULTS[driver][1]:
            yield pytest.param(w["name"], driver, fault,
                               id=f"{w['name']}-{fault}")


@pytest.mark.parametrize("cell,driver,fault", list(_fault_cases()))
def test_planted_fault_makes_correct_false(capsys, monkeypatch, cell, driver,
                                           fault):
    FAULTS[driver][0](monkeypatch, fault)
    rc, res = run_cell(capsys, cell)
    assert rc == 1 and res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


def test_no_chip_means_no_result(capsys):
    # without --rehearsal the CPU backend is refused before any result
    with pytest.raises(bench.BenchError):
        bench.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "0.2"])
    assert not capsys.readouterr().out.strip().endswith("}")
    with pytest.raises(bench.BenchError):
        bench.main(["--workload", "no.such_cell", "--seed", "1",
                    "--seconds", "0.2"])


EXPECT = {"busy_s": 0.003527159, "window_s": 0.159400225}


def test_trace_reduction_on_the_recorded_trace():
    """``testdata/tiny_v5e.xplane.pb``: three calls of one jitted program
    on a v5e, a 50 ms host sleep after each (probe, PR 24)."""
    t = trace_reduce.reduce_trace(os.path.join(HERE, "testdata",
                                               "tiny_v5e.xplane.pb"))
    assert t["devices"] == 1 and len(t["launches"]) == 3
    assert t["busy_s"] == pytest.approx(EXPECT["busy_s"], rel=1e-6)
    assert t["window_s"] == pytest.approx(EXPECT["window_s"], rel=1e-6)
    gaps = trace_reduce.launch_gaps(t["launches"])
    assert len(gaps) == 2 and all(0.050 < g < 0.056 for g in gaps)
    assert [n for n, _s in t["idle_gaps"][:2]] == ["bench.host_gap"] * 2
    assert t["device_ops"][0][1] >= t["device_ops"][-1][1] > 0


def test_trace_reduction_arithmetic():
    ev = {"devices": {"/device:TPU:0": {
        "ops": [("%a = f32[] x()", 1.0, 2.0), ("%b = f32[] y()", 1.5, 3.0),
                ("%a = f32[] x()", 6.0, 7.0)],
        "modules": [("jit_f", 1.0, 3.0), ("jit_f", 6.0, 7.0)]}},
        "spans": [("bench.window", 0.0, 10.0), ("bench.call", 0.5, 3.5),
                  ("bench.decode", 3.5, 6.5)]}
    t = trace_reduce.reduce_events(ev)
    assert t["window_s"] == 10.0 and t["busy_s"] == 3.0
    assert t["device_ops"] == [["a", 2.0], ["b", 1.5]]
    assert sorted(map(tuple, t["idle_gaps"])) == [
        ("bench.call", 1.0), ("bench.decode", 3.0), ("outside bench spans", 3.0)]
    assert trace_reduce.launch_gaps(t["launches"]) == [3.0]
    empty = trace_reduce.reduce_events({"devices": {"/device:TPU:0": {
        "ops": [], "modules": []}}, "spans": [("bench.window", 0.0, 1.0)]})
    assert empty["busy_s"] is None  # nothing ran: no share of anything


def test_byte_counts_from_shapes():
    assert bytes_model.import_bytes(1000, 22, 4) == 26000.0
    assert bytes_model.roofline_pct(819e9, 819e9, 4.0) == 25.0
    peaks = bench.load_json(HERE, "peaks.json")
    assert bytes_model.load_peak("TPU v5 lite", "hbm_bytes_per_s", peaks) == 819e9
    with pytest.raises(KeyError):
        bytes_model.load_peak("TPU v9 heavy", "hbm_bytes_per_s", peaks)


# ---------------------------------------------------------------------------
# the edit script and the plain reference
# ---------------------------------------------------------------------------

CONFIG = bench.load_json(bench.ROOT, MANIFEST["configs"][0]["file"])
TINY = {**CONFIG, **CONFIG["rehearsal"]}


@pytest.mark.parametrize("seed", [0, 7, 2147483659, 4294967311])
def test_script_meets_the_configurations_counts_exactly(seed):
    import script

    patches = script.routed_patches(seed, TINY, 1)
    assert sum(1 for _p, _pos, ch in patches if ch) == TINY["insert_patches"]
    assert sum(1 for _p, _pos, ch in patches if not ch) == TINY["delete_patches"]
    assert all(len(ch) <= 1 for _p, _pos, ch in patches)  # single characters
    quiet = patches[:TINY["sync_every_patches"]]
    assert all(ch for _p, _pos, ch in quiet)  # nothing deleted before the first exchange
    assert {p for p, _pos, _ch in patches} == set(range(TINY["peers_per_document"]))
    assert patches == script.routed_patches(seed, TINY, 1)  # the seed alone decides
    assert patches != script.routed_patches(seed, TINY, 2)


def _merged(edits, n_peers=2):
    """Replicas type ``edits`` (``(peer, pos, char)``, no exchange in
    between), then exchange: the text all of them read."""
    import fugue_reference

    m = fugue_reference.Merge(n_peers)
    for peer, pos, ch in edits:
        m.apply(peer, pos, ch)
    m.exchange()
    return "".join(m.ch[e] for e in m.visible())


def test_reference_orders_concurrent_runs_by_peer_without_interleaving():
    # both replicas type a word into the empty text: root children, by peer
    assert _merged([(1, 0, "x"), (1, 1, "y"), (0, 0, "a"), (0, 1, "b")]) == "abxy"


def test_reference_places_by_the_fugue_rule():
    import fugue_reference

    m = fugue_reference.Merge(2)
    for i, ch in enumerate("abc"):
        m.apply(0, i, ch)
    m.exchange()
    # after "a", which has the right child "b": the LEFT child of "b"
    m.apply(0, 1, "X")
    assert (m.parent[3], m.side[3]) == (1, 0)
    # replica 1 does not see X: after "a" it makes a left child of "b" too
    m.apply(1, 1, "Y")
    assert (m.parent[4], m.side[4]) == (1, 0)
    # after "c", which has no child: its RIGHT child; at 0: left of the first
    m.apply(1, 4, "Z")
    assert (m.parent[5], m.side[5]) == (2, 1)
    m.apply(0, 0, "S")
    assert (m.parent[6], m.side[6]) == (0, 0)
    m.apply(1, 2, "")  # replica 1 reads "aYbcZ": deletes "b"
    stale = m.views[0].text()
    m.exchange()
    assert stale == "SaXbc"
    assert "".join(m.ch[e] for e in m.visible()) == "SaXYcZ"  # siblings X, Y by peer
    assert m.chains() == 7  # nothing folds: two children, another peer, a left child
    m.apply(0, 1, "T")  # the next id of S's peer, its only child, on the right
    m.exchange()
    assert "".join(m.ch[e] for e in m.visible()) == "STaXYcZ" and m.chains() == 7


def test_reference_delete_of_one_character_by_two_replicas_deletes_one():
    edits = [(0, 0, "a"), (0, 1, "b"), (0, 2, "c")]
    import fugue_reference

    m = fugue_reference.Merge(2)
    for e in edits:
        m.apply(*e)
    m.exchange()
    m.apply(0, 1, "")
    m.apply(1, 1, "")
    m.exchange()
    assert "".join(m.ch[e] for e in m.visible()) == "ac"


def test_documents_off_the_sources_shape_fail_set_up(capsys, monkeypatch):
    real = bench.load_json

    def off(*parts):
        body = real(*parts)
        if parts[-1].endswith("b4_import.json"):
            body["rehearsal"]["chains_after_contraction"] = 1000
        return body

    monkeypatch.setattr(bench, "load_json", off)
    with pytest.raises(RuntimeError, match="the configuration states"):
        run_cell(capsys, CELLS[0])
