"""Tracing subsystem tests (reference: dev-utils chrome-trace setup):
what a span records, the one switch, and the span trees and device
scopes of the two import paths (docs/OBSERVABILITY.md "Spans")."""
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from loro_tpu import LoroDoc
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.utils import tracing


@pytest.fixture
def traced():
    """Spans recorded by the explicit half of the switch, in a new record."""
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.clear()


def test_spans_recorded_and_dumped(tmp_path, traced):
    a, b = LoroDoc(peer=1), LoroDoc(peer=2)
    a.get_text("t").insert(0, "traced")
    b.import_(a.export_updates())
    by_name = {e["name"]: e for e in tracing.events()}
    assert {"doc.import", "oplog.import", "state.apply"} <= set(by_name)
    path = tracing.dump(str(tmp_path / "trace.json"))
    with open(path) as f:
        data = json.load(f)
    dumped = {e["name"]: e for e in data["traceEvents"]}
    # the chrome args carry the tree and the request
    imp, oplog = dumped["doc.import"], dumped["oplog.import"]
    assert oplog["args"]["parent"] == imp["args"]["span"]
    assert imp["args"]["parent"] == 0 and "trace" in imp["args"]
    assert oplog["ts"] >= imp["ts"] and oplog["dur"] <= imp["dur"]


def test_zero_overhead_when_disabled():
    tracing.clear()
    tracing.disable()
    assert not tracing.is_enabled()
    a = LoroDoc(peer=1)
    a.get_text("t").insert(0, "x")
    a.export_updates()
    assert tracing.events() == []


def _nested():
    with tracing.span("outer", docs=2):
        with tracing.span("first"):
            pass
        with tracing.span("second"):
            with tracing.span("leaf"):
                pass
    return {e["name"]: e for e in tracing.events()}


def _check_parents():
    by = _nested()
    assert by["outer"]["parent_id"] == 0 and by["outer"]["args"] == {"docs": 2}
    assert by["first"]["parent_id"] == by["second"]["parent_id"] == by["outer"]["span_id"]
    assert by["leaf"]["parent_id"] == by["second"]["span_id"]
    assert len({e["span_id"] for e in by.values()}) == 4


def _check_threads():
    """A thread's first span is a root of that thread, whatever span is
    open on the thread that started it; the thread id is the real one."""
    seen = {}

    def work():
        with tracing.span("worker"):
            seen["tid"] = threading.get_ident()

    with tracing.span("starter"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tracing.span("after"):
            pass
    by = {e["name"]: e for e in tracing.events()}
    assert by["worker"]["parent_id"] == 0 and by["worker"]["tid"] == seen["tid"]
    assert by["starter"]["tid"] == threading.get_ident() != seen["tid"]
    assert by["after"]["parent_id"] == by["starter"]["span_id"]


def _check_trace_id():
    with tracing.span("no_request"):
        pass
    with tracing.ambient("req-7"):
        with tracing.span("in_request"):
            pass
        tracing.instant("point", n=1)
    # a span that stands for a request names it: its children carry the id
    with tracing.ambient("outer-req"):
        with tracing.span("call", trace_id="call-1"):
            with tracing.span("stage"):
                assert tracing.current() == "call-1"
        assert tracing.current() == "outer-req"
    by = {e["name"]: e for e in tracing.events()}
    assert by["call"]["trace_id"] == by["stage"]["trace_id"] == "call-1"
    assert by["no_request"]["trace_id"] is None
    assert by["in_request"]["trace_id"] == by["point"]["trace_id"] == "req-7"
    assert by["point"]["cpu_ns"] is None and by["point"]["end_ns"] == by["point"]["start_ns"]


def _check_cpu_within_wall():
    with tracing.span("sleeps"):
        with tracing.span("child"):  # the thread clock is read on roots only
            time.sleep(0.05)
    # the spin ends when the THREAD's clock has advanced 50 ms, and is made
    # again when its wall time was mostly another process's (six xdist
    # workers share the cores): what is guarded is the clocks, not the host
    for attempt in range(8):
        with tracing.span(f"spins{attempt}"):
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < 50_000_000:
                pass
        e = tracing.events()[-1]
        if e["cpu_ns"] > 0.5 * (e["end_ns"] - e["start_ns"]):
            break
    by = {e["name"]: e for e in tracing.events()}
    assert by.pop("child")["cpu_ns"] is None
    for e in by.values():
        wall = e["end_ns"] - e["start_ns"]
        assert 0 <= e["cpu_ns"] <= wall + 2_000_000  # the two clocks' grain
        assert e["name"] == "sleeps" or e["cpu_ns"] >= 48_000_000
    assert by["sleeps"]["cpu_ns"] < 0.5 * (by["sleeps"]["end_ns"] - by["sleeps"]["start_ns"])
    last = by[f"spins{attempt}"]
    assert last["cpu_ns"] > 0.5 * (last["end_ns"] - last["start_ns"])


def _check_observer_bridge():
    fired = []
    fn = lambda name, dur: fired.append((name, dur))  # noqa: E731
    tracing.add_span_observer(fn)
    try:
        with tracing.span("watched"):
            pass
        tracing.instant("point")
        tracing.disable()  # the bridge works with the record off, too
        with tracing.span("watched_off"):
            pass
    finally:
        tracing.remove_span_observer(fn)
    assert [n for n, _d in fired] == ["watched", "point", "watched_off"]
    assert fired[0][1] > 0 and fired[1][1] == 0.0
    assert [e["name"] for e in tracing.events()] == ["watched", "point"]


def _check_many_threads_lose_nothing():
    """The record is appended without a lock: more writers than cores,
    switching often, lose no span and cross no parent."""
    n_threads, n_each = 24, 300

    def work(k):
        with tracing.ambient(f"w{k}"):
            for i in range(n_each):
                with tracing.span("doc", i=i):
                    with tracing.span("stage"):
                        pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    spans = tracing.events()
    by_id = {e["span_id"]: e for e in spans}
    assert len(spans) == len(by_id) == 2 * n_threads * n_each
    for e in spans:
        if e["name"] == "stage":
            up = by_id[e["parent_id"]]
            assert (up["name"], up["tid"], up["trace_id"]) == ("doc", e["tid"], e["trace_id"])
        else:
            assert e["parent_id"] == 0


def _check_a_leaf_records_nothing_under_it():
    """A ``leaf=True`` span silences its own thread's spans under it, for
    as long as it is open, and no other thread's; observers still hear."""
    fired = []
    fn = lambda name, dur: fired.append(name)  # noqa: E731
    other = threading.Thread(target=lambda: tracing.span("elsewhere").__enter__().__exit__())
    tracing.add_span_observer(fn)
    try:
        with tracing.span("task", leaf=True, bytes=3):
            with tracing.span("inner"):
                with tracing.span("leaf_again", leaf=True):
                    tracing.instant("point")
            other.start()
            other.join()
        with tracing.span("after"):
            pass
    finally:
        tracing.remove_span_observer(fn)
    spans = tracing.events()
    assert [e["name"] for e in spans] == ["elsewhere", "task", "after"]
    assert spans[1]["args"] == {"bytes": 3} and spans[1]["cpu_ns"] is not None
    assert all(e["parent_id"] == 0 for e in spans)
    assert fired == ["point", "leaf_again", "inner", "elsewhere", "task", "after"]


@pytest.mark.parametrize("check", [
    _check_parents, _check_threads, _check_trace_id, _check_cpu_within_wall,
    _check_observer_bridge, _check_many_threads_lose_nothing,
    _check_a_leaf_records_nothing_under_it],
    ids=lambda f: f.__name__.lstrip("_"))
def test_what_a_span_records(traced, check):
    check()


def _new_session_by_enable():
    tracing.enable()
    with tracing.span("first_session"):
        pass
    tracing.disable()
    assert [e["name"] for e in tracing.events()] == ["first_session"]  # kept when off
    tracing.enable()
    with tracing.span("second_session"):
        pass
    tracing.disable()


def _new_session_by_profiler(tmp_path):
    """The other half of the switch: a profiler session records, with no
    enable(), and its spans are in the profiler's own trace."""
    import jax.profiler as P

    for name in ("first_session", "second_session"):
        P.start_trace(str(tmp_path / name))
        try:
            assert tracing.is_enabled()
            with tracing.span(name, docs=3):
                pass
        finally:
            P.stop_trace()
        assert not tracing.is_enabled()
    import glob

    path = glob.glob(str(tmp_path / "second_session" / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    names = {ev.name for plane in P.ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "second_session" in names and "first_session" not in names


@pytest.mark.parametrize("how", ["enable", "profiler"])
def test_a_new_session_starts_a_new_record(tmp_path, how):
    tracing.clear()
    if how == "enable":
        _new_session_by_enable()
    else:
        _new_session_by_profiler(tmp_path)
    assert [e["name"] for e in tracing.events()] == ["second_session"]
    tracing.clear()


def test_the_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(tracing, "RING_SPANS", 8)
    before = obs.counter("trace.spans_dropped_total").total()
    tracing.enable()  # the new record takes the bound
    try:
        for i in range(8):
            with tracing.span("kept", i=i):
                pass
        assert obs.counter("trace.spans_dropped_total").total() == before
        for i in range(8, 11):
            with tracing.span("kept", i=i):
                pass
    finally:
        tracing.disable()
    assert [e["args"]["i"] for e in tracing.events()] == list(range(3, 11))
    assert obs.counter("trace.spans_dropped_total").total() == before + 3
    tracing.clear()


def test_off_records_nothing_and_builds_no_annotation(monkeypatch):
    import jax.profiler as P

    built = []

    class Spy(P.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    tracing.clear()
    assert tracing._profiling() is False  # binds the annotation class
    monkeypatch.setattr(tracing, "_annotation", Spy)
    with tracing.span("off", docs=1) as s:
        assert s._id == 0
    tracing.instant("off.point")
    assert tracing.events() == [] and built == []
    # enable() without a profiler session: the record, still no annotation
    tracing.enable()
    try:
        with tracing.span("on"):
            pass
    finally:
        tracing.disable()
    assert [e["name"] for e in tracing.events()] == ["on"] and built == []
    tracing.clear()


def test_a_collection_is_kept_beside_the_spans_and_never_among_them(tmp_path):
    """ISSUE 37: while a record is kept every collection is a pause of its
    own list, counted in ns and dumped as an instant on its thread; the
    span record, which readers count, holds none of it; off, nothing moves."""
    import gc

    paused = obs.counter("trace.gc_pause_ns_total")
    tracing.disable()
    tracing.clear()
    before = paused.total()
    gc.disable()  # the collections of this test are the ones it asks for
    try:
        gc.collect()
        assert tracing.pauses() == [] and paused.total() == before
        tracing.enable()
        try:
            with tracing.span("open"):
                gc.collect()
            kept, spans = tracing.pauses(), tracing.events()
            path = tracing.dump(str(tmp_path / "trace.json"))
        finally:
            tracing.disable()
        (pause,) = kept
        assert pause["gen"] == 2 and pause["collected"] >= 0
        assert pause["tid"] == threading.get_ident()
        assert paused.total() - before == pause["end_ns"] - pause["start_ns"] > 0
        (span,) = spans  # the pause fell inside the open span, on its clock
        assert span["name"] == "open"
        assert span["start_ns"] <= pause["start_ns"] and pause["end_ns"] <= span["end_ns"]
        with open(path) as f:
            dumped = [e for e in json.load(f)["traceEvents"] if e["name"] != "open"]
        assert dumped == [{
            "name": "gc.pause", "ph": "i", "s": "t", "ts": pause["start_ns"] / 1e3,
            "pid": os.getpid(), "tid": pause["tid"],
            "args": {"gen": 2, "collected": pause["collected"],
                     "ns": pause["end_ns"] - pause["start_ns"]}}]
        gc.collect()  # off again: the list stands as the session left it
        assert tracing.pauses() == kept
        tracing.enable()  # a new session, a new list
        try:
            assert tracing.pauses() == []
        finally:
            tracing.disable()
    finally:
        gc.enable()
        tracing.clear()


# ---------------------------------------------------------------------------
# the two import paths: span trees and device scopes
# ---------------------------------------------------------------------------

def _payload(i):
    """A full-history payload of one two-peer document, concurrent edits."""
    a, b = LoroDoc(peer=900 + 2 * i), LoroDoc(peer=901 + 2 * i)
    a.get_text("text").insert(0, f"document {i}: " + "abc" * (5 + i))
    a.commit()
    b.import_(a.export_snapshot())
    a.get_text("text").insert(3, "AAA")
    b.get_text("text").insert(5, "bbb")
    b.get_text("text").delete(0, 2)
    a.import_(b.export_updates(a.oplog_vv()))
    return strip_envelope(a.export_updates({})), a.get_text("text").to_string()


def _tree(spans):
    """{span name: set of its parents' names} and the spans by name."""
    by_id = {e["span_id"]: e for e in spans}
    parents, by_name = {}, {}
    for e in spans:
        up = by_id[e["parent_id"]]["name"] if e["parent_id"] else None
        parents.setdefault(e["name"], set()).add(up)
        by_name.setdefault(e["name"], []).append(e)
    return parents, by_name


FLEET_TREE = {
    "fleet.merge_text_payloads": {None},
    # the caller's ONE wait a call; a payload's decode is a root of a pool
    # thread and a leaf: its ``native.explode`` is not recorded
    "fleet.decode": {"fleet.merge_text_payloads"},
    "fleet.decode_one": {None},
    "fleet.merge_text_docs": {"fleet.merge_text_payloads"},
    **{f"fleet.{s}": {"fleet.merge_text_docs"}
       for s in ("contract", "stack", "pack", "upload", "launch", "device_wait",
                 "fetch", "join")},
}
TREE_TREE = {
    "fleet.merge_tree_payloads": {None},
    "fleet.tree_decode_one": {None},
    **{f"fleet.tree_{s}": {"fleet.merge_tree_payloads"}
       for s in ("decode", "stack", "upload", "launch", "device_wait",
                 "fetch", "maps")},
}
PACKED_TREE = {
    "packed.decode_one": {None},
    **{f"packed.{s}": {"packed.decode_one"}
       for s in ("extract", "contract", "pack")},
    "native.explode": {"packed.extract"},
    "packed.round": {None},
    **{f"packed.{s}": {"packed.round"}
       for s in ("wait_decoded", "submit", "stack", "put", "dispatch")},
    "packed.drain": {None},
}


def _check_pool_spans(spans, by_name, tree, payloads, decode):
    """The caller / pool split of a ``Fleet`` payload entry: ONE ``decode``
    span a call on the caller's thread, one ``<decode>_one`` a payload on
    pool threads and nothing under it, one trace id on all."""
    one = decode + "_one"
    assert all(len(by_name[n]) == 1 for n in tree if n != one)
    assert by_name[decode][0]["args"] == {"docs": len(payloads), "workers": min(8, os.cpu_count())}
    # one task a payload (their lengths differ); pool threads start them in any order
    ones = by_name[one]
    assert sorted(e["args"]["bytes"] for e in ones) == sorted(map(len, payloads))
    assert len({e["trace_id"] for e in spans}) == 1  # one id a call, on every thread
    caller = {e["tid"] for e in spans if e["name"] != one}
    assert caller == {threading.get_ident()}
    pool = {e["tid"] for e in ones}
    assert not pool & caller and len(pool) <= len(payloads)
    assert all(e["cpu_ns"] is not None for e in ones)  # roots of their threads
    # leaves: a pool thread records its tasks and nothing else
    assert {e["name"] for e in spans if e["tid"] in pool} == {one}
    # the wait holds every task, so the stage's spans by SELF time are the wait
    wait = by_name[decode][0]
    assert all(wait["start_ns"] <= e["start_ns"] and e["end_ns"] <= wait["end_ns"]
               for e in ones)


def test_import_paths_give_their_span_trees_and_the_same_answers():
    """Sizes of the benchmark cells' ``rehearsal`` groups: four documents
    a Fleet call, two a packed launch."""
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.fugue_batch import merge_text_payloads_packed
    from loro_tpu.parallel.fleet import Fleet
    from loro_tpu.parallel.mesh import make_mesh

    cid = ContainerID.root("text", ContainerType.Text)
    docs = [_payload(i) for i in range(4)]
    payloads = [p for p, _t in docs]
    fleet = Fleet(make_mesh(jax.devices()[:1]))
    pairs = [(p, 1) for p in payloads]

    def packed():
        outs, done, _ops, _dt, _nw = merge_text_payloads_packed(
            pairs, cid, 128, 512, 2, 4)
        return done, [np.asarray(x).tolist() for out in outs for x in out]

    tracing.clear()
    untraced = fleet.merge_text_payloads(payloads, cid).texts, packed()
    assert untraced[0] == [t for _p, t in docs] and tracing.events() == []

    tracing.enable()
    try:
        fleet_texts = fleet.merge_text_payloads(payloads, cid).texts
        fleet_spans = tracing.events()
        tracing.clear()
        packed_out = packed()
        spans = tracing.events()
    finally:
        tracing.disable()
        tracing.clear()

    parents, by_name = _tree(fleet_spans)
    assert fleet_texts == untraced[0]
    assert parents == FLEET_TREE
    _check_pool_spans(fleet_spans, by_name, FLEET_TREE, payloads, "fleet.decode")

    assert packed_out == untraced[1]
    parents, by_name = _tree(spans)
    assert parents == PACKED_TREE
    assert sorted(e["args"]["doc"] for e in by_name["packed.decode_one"]) == [0, 1, 2, 3]
    assert len(by_name["packed.round"]) == 2 and len(by_name["packed.drain"]) == 1
    launcher = {e["tid"] for e in by_name["packed.round"]}
    assert launcher == {threading.get_ident()}
    assert not launcher & {e["tid"] for e in by_name["packed.decode_one"]}
    # a document's decode carries the id of the round that takes it
    rounds = [e["trace_id"] for e in sorted(by_name["packed.round"],
                                            key=lambda e: e["start_ns"])]
    assert len(set(rounds)) == 2 and None not in rounds
    for e in by_name["packed.decode_one"]:
        assert e["trace_id"] == rounds[e["args"]["doc"] // 2]
    by_id = {e["span_id"]: e for e in spans}
    for e in spans:  # every child has its parent's id and thread
        if e["parent_id"]:
            up = by_id[e["parent_id"]]
            assert (e["trace_id"], e["tid"]) == (up["trace_id"], up["tid"])
    assert obs.counter("packed.launches_total").total() >= 2


def test_contract_and_pack_keep_their_spans_and_count_their_native_calls():
    """``import_host_contract_ms``, ``stream_decode_contract_ms`` and
    ``stream_decode_pack_ms`` read these four spans by name: one
    ``fleet.contract`` and one ``fleet.pack`` a call (around every
    document's), one ``packed.contract`` and one ``packed.pack`` a
    document; under them one native call a document and a stage."""
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.fugue_batch import merge_text_payloads_packed
    from loro_tpu.parallel.fleet import Fleet
    from loro_tpu.parallel.mesh import make_mesh

    cid = ContainerID.root("text", ContainerType.Text)
    payloads = [_payload(i)[0] for i in range(4)]
    fleet = Fleet(make_mesh(jax.devices()[:1]))
    calls = obs.counter("codec.native_chain_calls_total")
    fallbacks = obs.counter("fleet.host_fallback_total")

    def counts():
        return calls.get(fn="contract"), calls.get(fn="pack"), fallbacks.total()

    def names(spans):
        return [e["name"] for e in spans]

    tracing.clear()
    tracing.enable()
    try:
        c0 = counts()
        for _ in range(2):
            fleet.merge_text_payloads(payloads, cid)
        fleet_names, c1 = names(tracing.events()), counts()
        tracing.clear()
        merge_text_payloads_packed([(p, 1) for p in payloads], cid, 128, 512, 2, 4)
        packed, c2 = tracing.events(), counts()
    finally:
        tracing.disable()
        tracing.clear()
    assert fleet_names.count("fleet.contract") == fleet_names.count("fleet.pack") == 2
    assert (c1[0] - c0[0], c1[1] - c0[1]) == (8, 8) and c1[2] == c0[2]
    packed_names = names(packed)
    assert packed_names.count("packed.contract") == packed_names.count("packed.pack") == 4
    assert (c2[0] - c1[0], c2[1] - c1[1]) == (4, 4) and c2[2] == c0[2]
    # each a child of its own document's decode, on that document's thread
    by_id = {e["span_id"]: e for e in packed}
    for stage in ("packed.contract", "packed.pack"):
        ups = [by_id[e["parent_id"]] for e in packed if e["name"] == stage]
        assert sorted(u["args"]["doc"] for u in ups) == [0, 1, 2, 3]
        assert all(u["name"] == "packed.decode_one" for u in ups)


def _tree_payload(i: int):
    """A full-history payload of two replicas that move concurrently (one
    pair of moves makes a cycle), and the tree they converge on."""
    a, b = LoroDoc(peer=700 + 2 * i), LoroDoc(peer=701 + 2 * i)
    ta = a.get_tree("tree")
    nodes = [ta.create() for _ in range(4 + i)]
    a.commit()
    b.import_(a.export_snapshot())
    ta.move(nodes[0], nodes[1])
    b.get_tree("tree").move(nodes[1], nodes[0])
    b.get_tree("tree").move(nodes[3], nodes[2])
    a.import_(b.export_updates(a.oplog_vv()))
    return strip_envelope(a.export_updates({})), {n: ta.parent(n) for n in ta.nodes()}


def test_the_tree_entry_gives_its_span_tree_and_the_same_answers():
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.fleet import Fleet
    from loro_tpu.parallel.mesh import make_mesh

    cid = ContainerID.root("tree", ContainerType.Tree)
    docs = [_tree_payload(i) for i in range(4)]
    payloads = [p for p, _t in docs]
    fleet = Fleet(make_mesh(jax.devices()[:1]))
    tracing.clear()
    refused = obs.counter("tree.moves_refused_total").total()
    untraced = fleet.merge_tree_payloads(payloads, cid)
    assert untraced == [t for _p, t in docs] and tracing.events() == []
    # one of each concurrent pair of moves is refused, in every document
    assert obs.counter("tree.moves_refused_total").total() - refused == 4
    tracing.enable()
    try:
        traced_maps = fleet.merge_tree_payloads(payloads, cid)
        spans = tracing.events()
    finally:
        tracing.disable()
        tracing.clear()
    parents, by_name = _tree(spans)
    assert traced_maps == untraced
    assert parents == TREE_TREE
    _check_pool_spans(spans, by_name, TREE_TREE, payloads, "fleet.tree_decode")
    assert obs.counter("fleet.tree_docs_total").total() >= 8
    assert obs.counter("tree.replay_steps").total() > 0


def test_device_stage_scopes_are_in_the_lowered_programs():
    """``jax.named_scope`` at the single dispatch points: the packed
    stream's step has unpack, ring, rank, place, checksum; the step
    ``Fleet``'s text entry launches unpack, ring, rank, place; the
    uncontracted reference ring, rank, compact — six names between them."""
    from loro_tpu.ops.fugue_batch import (
        SeqColumns,
        _merge_docs_jit,
        chain_merge_docs_packed,
        chain_merge_docs_packed_checksum,
        packed_row_bytes,
    )
    from loro_tpu.parallel.fleet import text_pads

    def scopes(lowered):
        text = lowered.as_text(debug_info=True)
        return {s for s in ("ring", "rank", "compact", "place", "unpack", "checksum")
                if f"({s})/" in text or f"/{s}/" in text}

    packed = chain_merge_docs_packed_checksum.lower(
        jax.ShapeDtypeStruct((2, packed_row_bytes(128, 512)), np.uint8), 128, 512)
    assert scopes(packed) == {"unpack", "ring", "rank", "place", "checksum"}
    pad_c, pad_n = text_pads(40, 500)
    fleet = chain_merge_docs_packed.lower(
        jax.ShapeDtypeStruct((1, packed_row_bytes(pad_c, pad_n)), np.uint8), pad_c, pad_n)
    assert scopes(fleet) == {"unpack", "ring", "rank", "place"}
    shape = lambda dt: jax.ShapeDtypeStruct((1, 64), dt)  # noqa: E731
    cols = SeqColumns(*[shape(bool if f in ("deleted", "valid") else np.int32)
                        for f in SeqColumns._fields])
    assert scopes(_merge_docs_jit.lower(cols)) == {"ring", "rank", "compact"}
    # the tree import's one launch: the replay, then the deleted nodes
    from loro_tpu.ops.tree_batch import tree_import_batch, tree_pads

    text = tree_import_batch.lower(
        jax.ShapeDtypeStruct((2, 1 + tree_pads(40)), np.uint32), 16, False
    ).as_text(debug_info=True)
    assert all(f"({s})/" in text or f"/{s}/" in text
               for s in ("tree_replay", "tree_deleted"))
