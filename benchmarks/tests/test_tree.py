"""The movable-tree family's own pieces, on the CPU: the move script's
counts, the plain reference on hand-made cases, the kernel-time reader on
the recorded v5e trace, the byte counts of the tree cell's constants.  The
cell itself (end to end, control, planted faults) runs in
``test_benchmark.py``, which finds it in the manifest.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import os
import types

import pytest

import bytes_model
import run as bench
import trace_reduce
import tree_reference
import tree_script
from readers import trace_op_ms

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = bench.load_json(HERE, "configs", "tree_import.json")
TINY = {**CONFIG, **CONFIG["rehearsal"]}


@pytest.mark.parametrize("seed", [0, 7, 2147483659, 4294967311])
def test_script_keeps_the_sources_shape(seed):
    moves = tree_script.routed_moves(seed, TINY, 1)
    n, draws = TINY["nodes"], TINY["move_draws"]
    # a draw that is cyclic where it is made leaves no op: some, not many
    assert 0.7 * draws < len(moves) < draws
    assert all(0 <= i < n and 0 <= j < n and i != j for _p, i, j in moves)
    assert {p for p, _i, _j in moves} == set(range(TINY["peers_per_document"]))
    # every replica's own moves keep its own tree a tree
    for peer in range(TINY["peers_per_document"]):
        parent, refused, _reads = tree_reference.apply_moves(
            n, [(i, j) for p, i, j in moves if p == peer])
        assert refused == 0
    assert moves == tree_script.routed_moves(seed, TINY, 1)  # the seed alone decides
    assert moves != tree_script.routed_moves(seed, TINY, 2)
    ref = tree_reference.replay(seed, TINY, 1)
    assert ref["n_ops"] == n + len(moves) and ref["refused"] > 0
    assert ref["parents"] != ref["stale_parents"]


def test_reference_refuses_the_later_of_two_concurrent_moves_that_make_a_cycle():
    # replica 0 moves a under b, replica 1 moves b under a; each is sound
    # where it is made.  Same lamport: the lower peer id applies first.
    parent, refused, _reads = tree_reference.apply_moves(3, [(0, 1), (1, 0)])
    assert parent == [1, -1, -1] and refused == 1
    parent, refused, _reads = tree_reference.apply_moves(3, [(1, 0), (0, 1)])
    assert parent == [-1, 0, -1] and refused == 1


def test_reference_breaks_a_lamport_tie_by_peer_id(monkeypatch):
    c = {"nodes": 3, "peers_per_document": 2, "peer_ids": [1, 2]}
    script = [(1, 1, 0), (0, 0, 1)]  # drawn in this order; both first moves
    monkeypatch.setattr(tree_script, "routed_moves", lambda *_a: script)
    assert tree_reference.ordered_moves(0, c, 0) == [(0, 1), (1, 0)]
    c["peer_ids"] = [9, 2]  # the other replica now has the lower id
    assert tree_reference.ordered_moves(0, c, 0) == [(1, 0), (0, 1)]
    # a replica's second move comes after every first move
    script.append((1, 2, 0))
    assert tree_reference.ordered_moves(0, c, 0)[-1] == (2, 0)
    assert tree_reference.ordered_moves(0, c, 0, without_peer=1) == [(0, 1)]


def test_reference_walks_any_depth_and_counts_its_reads():
    n = 50  # a chain as deep as the node count, then its head under its tail
    chain = [(i + 1, i) for i in range(n - 1)]
    walks = []
    parent, refused, reads = tree_reference.apply_moves(n, chain + [(0, n - 1)], walks)
    assert refused == 1 and parent[0] == -1 and walks[-1] == n - 1
    assert reads == sum(walks)
    assert tree_reference.lockstep_factor([[1, 5, 2], [4, 1]]) == (4 + 5 + 2) / 3


def test_kernel_time_reader_on_the_recorded_trace():
    t = trace_reduce.reduce_trace(os.path.join(HERE, "testdata", "tiny_v5e.xplane.pb"))
    run = types.SimpleNamespace(trace_numbers=t)
    name, seconds = t["device_ops"][0]
    launches = len(t["launches"])  # three calls of one program
    assert trace_op_ms.read({"match": name}, run) == pytest.approx(
        1e3 * seconds / launches)
    both = sum(s for n, s in t["device_ops"] if "fusion" in n)
    assert both and trace_op_ms.read({"match": "fusion"}, run) == pytest.approx(
        1e3 * both / launches)
    assert trace_op_ms.read({"match": "no_such_kernel"}, run) is None
    assert trace_op_ms.read({"match": name}, types.SimpleNamespace(trace_numbers=None)) is None


def test_byte_counts_from_shapes():
    traffic = bench.load_json(HERE, "traffic", "fleet256.json")
    b_in, b_out = traffic["bytes_in_per_element"], traffic["bytes_out_per_element"]
    assert b_in == 8  # target and parent as int32
    # the 1,000 int32 parents of a document over its ops, rounded up
    ops = CONFIG["nodes"] + 0.9665 * CONFIG["move_draws"]
    assert 4 * CONFIG["nodes"] / ops <= b_out <= 1.25 * 4 * CONFIG["nodes"] / ops
    assert bytes_model.import_bytes(97650, b_in, b_out) == pytest.approx(786082.5)


def test_rehearsed_traced_run_prints_the_trees_span_metrics(capsys):
    import json

    from loro_tpu.obs import metrics as obs
    from loro_tpu.utils import tracing

    obs.reset()
    rc = bench.main(["--workload", "tree_import.fleet256", "--seed", "2147483659",
                     "--seconds", "0.5", "--trace", "1", "--rehearsal"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    manifest = bench.load_json(bench.ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in manifest["per_layer"]
            if m["source"] == "program_span" and "tree_import.fleet256" in m["workloads"]]
    assert len(mine) == 5
    got = {k: v["value"] for k, v in res["metrics"].items()}
    spans = tracing.events()  # the traced window's record outlives the run
    calls = [e["end_ns"] - e["start_ns"] for e in spans
             if e["name"] == "fleet.merge_tree_payloads"]
    assert len(calls) == res["attempted"]
    # the stages of a call, by self time: none is lost, none counted twice
    assert 0 < sum(got[f"cpu_rehearsal.{n}"] for n in mine) <= sum(calls) / len(calls) / 1e6
    assert got["cpu_rehearsal.tree_host_decode_ms"] > 0
    # no device plane in a CPU trace: the device's readers leave their metrics out
    assert not any("roofline" in k or "idle" in k or "device_ms" in k for k in got)
