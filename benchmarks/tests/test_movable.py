"""The movable-list family's own pieces, on the CPU: the edit script's
shape, the plain reference on hand-made cases and against the host
engine (a cross-check only), the program's public entry against the
reference on seeded documents, the cell's span metrics and byte counts.
The cell itself (end to end, control, planted faults) runs in
``test_benchmark.py``, which finds it in the manifest.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import json
import os

import pytest

import bytes_model
import movable_gen
import movable_reference
import movable_script
import run as bench

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = bench.load_json(HERE, "configs", "movable_import.json")
TINY = {**CONFIG, **CONFIG["rehearsal"]}
SEEDS = [0, 7, 2147483659, 4294967311]


@pytest.mark.parametrize("seed", SEEDS)
def test_script_keeps_the_configurations_shape(seed):
    rows = movable_script.routed_draws(seed, TINY, 1)
    n, total, every = TINY["items"], TINY["draws"], TINY["sync_every_draws"]
    draws = [r for r in rows if r[1] != movable_script.EXCHANGE]
    sets = [d for d in draws if d[3] == movable_script.SET]
    moves = [d for d in draws if d[3] != movable_script.SET]
    # one draw in five sets; a move(i, i) leaves no op: a few, not many
    assert 0.12 * total < len(sets) < 0.28 * total
    assert 0.9 * (total - len(sets)) < len(moves) <= total - len(sets)
    assert all(0 <= i < n and 0 <= j < n and i != j for _k, _p, i, j in moves)
    assert all(0 <= i < n for _k, _p, i, _j in sets)
    assert [k for k, *_ in draws] == sorted({k for k, *_ in draws})  # in draw order
    assert {p for _k, p, _i, _j in draws} == set(range(TINY["peers_per_document"]))
    # an exchange after every ``every`` draws, kept or not, before the next
    # one; none after the last: the control's ground
    assert [k for k, p, _i, _j in rows if p == movable_script.EXCHANGE] \
        == list(range(every, total, every))
    assert [k for k, *_ in rows] == sorted(k for k, *_ in rows)
    assert rows == movable_script.routed_draws(seed, TINY, 1)  # the seed alone decides
    assert rows != movable_script.routed_draws(seed, TINY, 2)
    assert draws == movable_script.routed_draws(seed, {**TINY, "sync_every_draws": 0}, 1)
    ref = movable_reference.replay(seed, TINY, 1)
    assert ref["n_ops"] == n + len(draws) and ref["slots"] == n + len(moves)
    assert len(ref["values"]) == n
    assert all(v.startswith(("item ", "edit ")) for v in ref["values"])
    assert ref["values"] != ref["stale_values"]


def _board(peer_ids=(1, 2), items=3):
    b = movable_reference.Board(list(peer_ids))
    for _ in range(items):
        b.push(0)
    b.exchange()
    return b


def test_reference_moves_an_item_to_where_the_handler_says():
    b = _board(items=4)  # item 0..3
    b.move(0, 0, 2)  # the item at 0 ends at 2
    assert b.read(0) == ["item 1", "item 2", "item 0", "item 3"]
    b.move(0, 3, 0)  # and a move to the front
    assert b.read(0) == ["item 3", "item 1", "item 2", "item 0"]
    assert b.read(1) == ["item 0", "item 1", "item 2", "item 3"]  # not yet told
    b.exchange()
    assert b.read(1) == ["item 3", "item 1", "item 2", "item 0"]
    assert b.ops == 4 + 2 and len(b.item) == 4 + 2  # a slot a move, none dropped


def test_reference_last_move_wins_by_lamport_then_peer():
    b = _board()
    b.move(0, 0, 2)  # both replicas move item 0, lamport 3 each:
    b.move(1, 0, 1)  # the higher peer id wins the tie
    b.exchange()
    assert b.read(0) == b.read(1) == ["item 1", "item 0", "item 2"]
    b.move(1, 2, 0)  # replica 1 makes two ops, replica 0 one, on item 2:
    b.move(1, 0, 1)  # the later lamport wins whatever the peer
    b.move(0, 2, 0)
    b.exchange()
    assert b.read(0) == b.read(1) == ["item 1", "item 2", "item 0"]


def test_reference_last_set_wins_and_a_move_keeps_the_value():
    b = _board()
    b.set(0, 1, "low peer")
    b.set(1, 1, "high peer")  # same lamport: the higher peer id
    assert b.read(0)[1] == "low peer" and b.read(1)[1] == "high peer"
    b.move(0, 1, 0)  # the item moves; its value is its own
    b.exchange()
    assert b.read(0) == b.read(1) == ["high peer", "item 0", "item 2"]
    b.set(0, 0, "later")  # replica 0 has seen everything: a later lamport
    b.exchange()
    assert b.read(1)[0] == "later"


def test_reference_exchange_lifts_every_replicas_lamport():
    b = _board()
    for _ in range(5):
        b.set(0, 0, "busy replica")
    b.exchange()
    b.set(1, 0, "quiet replica, after the exchange")  # lamport 8, not 4
    b.exchange()
    assert b.read(0)[0] == "quiet replica, after the exchange"


@pytest.mark.parametrize("every", [TINY["sync_every_draws"], 0])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_public_entry_and_the_host_engine_read_what_the_reference_reads(seed, every):
    from loro_tpu import LoroDoc
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.fleet import Fleet

    c = {**TINY, "sync_every_draws": every}
    fed = [movable_gen.make_payload(seed, c, v) for v in range(2)]
    refs = [movable_reference.replay(seed, c, v) for v in range(2)]
    for f, r in zip(fed, refs):
        assert (f["n_ops"], f["slots"], f["items"]) == (r["n_ops"], r["slots"], c["items"])
    cid = ContainerID.root(movable_gen.CONTAINER, ContainerType.MovableList)
    got = Fleet().merge_movable_payloads([f["payload"] for f in fed], cid)
    assert got == [r["values"] for r in refs]
    # the host engine, typed the same script: a cross-check of the
    # reference only, never what decides ``correct``
    docs = [LoroDoc(peer=p) for p in c["peer_ids"]]
    lists = [d.get_movable_list(movable_gen.CONTAINER) for d in docs]

    def gather():
        for d in docs[1:]:
            docs[0].import_(d.export_updates(docs[0].oplog_vv()))

    for n in range(c["items"]):
        lists[0].push(movable_script.created(n))
    for d in docs[1:]:
        d.import_(docs[0].export_updates())
    for k, peer, i, j in movable_script.routed_draws(seed, c, 0):
        if peer == movable_script.EXCHANGE:
            gather()
            for d in docs[1:]:
                d.import_(docs[0].export_updates(d.oplog_vv()))
        elif j == movable_script.SET:
            lists[peer].set(i, movable_script.edited(k))
        else:
            lists[peer].move(i, j)
    stale = lists[0].get_value()
    gather()
    assert lists[0].get_value() == refs[0]["values"]
    assert stale == refs[0]["stale_values"]  # the control's list too


def test_byte_counts_from_shapes():
    traffic = bench.load_json(HERE, "traffic", "fleet64.json")
    b_in, b_out = traffic["bytes_in_per_element"], traffic["bytes_out_per_element"]
    # a document at the configuration's shares: 0.8 of the draws move (a
    # slot row each, 26 B), 0.2 set (a set row each, 17 B), every item has
    # a slot and a creation value; 1,000 int32 value indexes come back
    items, draws, share = CONFIG["items"], CONFIG["draws"], CONFIG["set_share"]
    slots, set_rows = items + (1 - share) * draws, items + share * draws
    ops = slots + set_rows - items
    need = (26 * slots + 17 * set_rows) / ops
    assert need <= b_in <= 1.02 * need
    assert 4 * items / ops <= b_out <= 1.25 * 4 * items / ops
    assert bytes_model.import_bytes(100900, b_in, b_out) == pytest.approx(2465996.0)


@pytest.mark.parametrize("device_ops,want", [
    ([["while.50", 16.6], ["fusion.144", 15.9], ["fusion.16", 0.75]], 0),  # the chip's
    ([["vmap_rank_.1", 2.9], ["fusion.10", 1.1], ["sort.10", 0.9]], 1),  # another rank's
    ([], None),  # no device plane (a CPU rehearsal): nothing to hold
])
def test_the_traces_half_of_the_rank_check_reads_the_devices_own_names(device_ops, want):
    from types import SimpleNamespace

    from drivers import import_movable

    traffic = bench.load_json(HERE, "traffic", "fleet64.json")
    run = SimpleNamespace(traffic=traffic, trace_numbers={"device_ops": device_ops})
    assert import_movable.rank_untraced(run) == want
    assert import_movable.rank_untraced(SimpleNamespace(traffic=traffic, trace_numbers=None)) is None


def test_rehearsed_traced_run_prints_the_lists_span_metrics(capsys):
    from loro_tpu.obs import metrics as obs
    from loro_tpu.utils import tracing

    obs.reset()
    rc = bench.main(["--workload", "movable_import.fleet64", "--seed", "2147483659",
                     "--seconds", "0.5", "--trace", "1", "--rehearsal"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    manifest = bench.load_json(bench.ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in manifest["per_layer"]
            if m["source"] == "program_span" and "movable_import.fleet64" in m["workloads"]]
    assert sorted(mine) == ["movable_decode_cpu_pct", "movable_device_wait_ms"] + [
        f"movable_host_{stage}_ms" for stage in ("decode", "fetch", "stack", "upload", "values")]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # the pool threads' CPU over their wall: a share, read from the tasks
    assert 0 < got["cpu_rehearsal.movable_decode_cpu_pct"] <= 100.5
    mine.remove("movable_decode_cpu_pct")  # the rest are the call's stages
    spans = tracing.events()  # the traced window's record outlives the run
    calls = [e["end_ns"] - e["start_ns"] for e in spans
             if e["name"] == "fleet.merge_movable_payloads"]
    assert len(calls) == res["attempted"]
    assert len({e["trace_id"] for e in spans if e["name"].startswith("fleet.movable_")}) \
        == len(calls)  # one trace id a call, its stages under it
    # the stages of a call, by self time: none is lost, none counted twice,
    # and with the device's wait they are the call
    staged = sum(got[f"cpu_rehearsal.{n}"] for n in mine)
    assert all(got[f"cpu_rehearsal.{n}"] > 0 for n in mine)
    assert 0.9 * sum(calls) / len(calls) / 1e6 < staged <= sum(calls) / len(calls) / 1e6
    # no device plane in a CPU trace: the device's readers leave their
    # metrics out, and the trace's half of the rank check has nothing to hold
    assert not any("roofline" in k or "idle" in k or "device_ms" in k or "gap" in k
                   for k in got)
    assert "rank_op_not_in_trace" not in res["compared"]
    assert res["compared"]["rank_tokens_off"] == {"value": 0, "limit": 0}
