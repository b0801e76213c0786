"""The readers of the program's own spans (``readers/span_self_ms.py``,
``readers/span_cpu_pct.py``): their arithmetic on hand-made span records,
and each cell's rehearsed ``--trace 1`` run printing every metric they
feed, the stages within the span that holds them.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import json

import pytest

import run as bench
from readers import span_cpu_pct, span_self_ms

MANIFEST = bench.load_json(bench.ROOT, "BENCHMARK.json")
MS = 1_000_000


@pytest.fixture(autouse=True)
def fresh_counters():
    from loro_tpu.obs import metrics as obs

    obs.reset()


def sp(name, span_id, parent_id, start_ms, end_ms, tid=1, cpu_ms=None):
    cpu = (end_ms - start_ms) if cpu_ms is None else cpu_ms
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "trace_id": None, "tid": tid, "start_ns": start_ms * MS,
            "end_ns": end_ms * MS, "cpu_ns": cpu * MS, "args": {}}


# a call of 100 ms: two stages (20 and 30 ms), the second with a child of
# its own (10 ms), and 50 ms that no child covers
ONE_CALL = [sp("call", 1, 0, 0, 100), sp("stage.a", 2, 1, 10, 30),
            sp("stage.b", 3, 1, 50, 80), sp("inner", 4, 3, 55, 65)]
# two threads, a call each; the second call's stage takes twice as long
TWO_THREADS = ONE_CALL + [sp("call", 5, 0, 0, 100, tid=2),
                          sp("stage.a", 6, 5, 0, 40, tid=2)]

SELF_CASES = {
    "the_parents_gap": (ONE_CALL, ["call"], "call", 50.0),
    "two_children": (ONE_CALL, ["stage.a", "stage.b"], "call", 20.0 + 20.0),
    "a_child_and_its_parent_sum_to_the_parent": (
        ONE_CALL, ["stage.b", "inner"], "call", 30.0),
    "two_threads": (TWO_THREADS, ["stage.a"], "call", (20.0 + 40.0) / 2),
    "per_document_not_per_call": (TWO_THREADS, ["call"], "stage.a", (50 + 60) / 2),
    "no_per_span": (ONE_CALL, ["stage.a"], "no.such.root", None),
    "no_spans_at_all": ([], ["stage.a"], "call", None),
    # a parent commit from before the stage had a span: nothing, never 0.0
    "the_per_span_but_none_of_the_named": (
        ONE_CALL, ["stage.c", "stage.d"], "call", None),
    # what the parent commit's tracing returns: chrome events, not spans
    "a_program_without_the_record": (
        [{"name": "call", "ph": "X", "ts": 0.0, "dur": 5.0}], ["call"], "call", None),
}


@pytest.mark.parametrize("case", list(SELF_CASES))
def test_self_time_per_root_span(monkeypatch, case):
    from loro_tpu.utils import tracing

    spans, names, per, want = SELF_CASES[case]
    monkeypatch.setattr(tracing, "events", lambda: list(spans))
    got = span_self_ms.read({"spans": names, "per": per}, None)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("reader,params", [
    (span_self_ms, {"spans": ["stage.a"], "per": "call"}),
    (span_cpu_pct, {"spans": ["call"]})])
def test_a_dropped_span_leaves_the_metric_out(monkeypatch, reader, params):
    from loro_tpu.obs import metrics as obs
    from loro_tpu.utils import tracing

    monkeypatch.setattr(tracing, "events", lambda: list(ONE_CALL))
    assert reader.read(params, None) is not None
    obs.counter("trace.spans_dropped_total").inc()
    assert reader.read(params, None) is None


def test_cpu_share_of_the_named_spans(monkeypatch):
    from loro_tpu.utils import tracing

    spans = [sp("doc", 1, 0, 0, 40, cpu_ms=30), sp("doc", 2, 0, 0, 60, tid=2, cpu_ms=20),
             sp("other", 3, 0, 0, 500, cpu_ms=1)]
    monkeypatch.setattr(tracing, "events", lambda: list(spans))
    assert span_cpu_pct.read({"spans": ["doc"]}, None) == pytest.approx(50.0)
    assert span_cpu_pct.read({"spans": ["absent"]}, None) is None


# cell -> the span that holds the stages, the one whose count is
# `attempted`, and the groups of metrics that must fit inside one holder
CELLS = {
    "b4_import.fleet16": ("fleet.merge_text_payloads", [(
        "fleet.merge_text_payloads",
        ["import_host_decode_ms", "import_host_stack_ms", "import_host_upload_ms",
         "import_host_fetch_ms", "import_host_join_ms"])]),
    "b4_import.packed64": ("packed.round", [
        ("packed.decode_one", ["stream_decode_extract_ms", "stream_decode_contract_ms",
                               "stream_decode_pack_ms"]),
        ("packed.round", ["stream_launcher_wait_ms", "stream_launcher_put_ms"])]),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_rehearsed_traced_run_prints_the_span_metrics(capsys, cell):
    from loro_tpu.utils import tracing

    rc = bench.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                     "0.5", "--trace", "1", "--rehearsal"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    mine = [m["name"] for m in MANIFEST["per_layer"]
            if m["source"] == "program_span" and cell in m["workloads"]]
    assert len(mine) >= 5
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert all(got[f"cpu_rehearsal.{name}"] >= 0 for name in mine)
    spans = tracing.events()  # the traced window's record outlives the run
    counted, groups = CELLS[cell]
    assert sum(1 for e in spans if e["name"] == counted) == res["attempted"]
    assert {m for _holder, ms in groups for m in ms} <= set(mine)
    for holder, metrics in groups:
        held = [e["end_ns"] - e["start_ns"] for e in spans if e["name"] == holder]
        mean_ms = sum(held) / len(held) / 1e6
        assert 0 < sum(got[f"cpu_rehearsal.{m}"] for m in metrics) <= mean_ms
