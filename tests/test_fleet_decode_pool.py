"""The decode pool behind ``Fleet``'s payload entries.

``merge_text_payloads``, ``merge_tree_payloads`` and
``merge_movable_payloads`` decode their payloads as tasks of one thread
pool (``parallel/fleet.py`` ``_decode_payloads``).  What a
caller sees must be what the serial loop gave: answers in payload order, the
Python fallback per payload, the same errors, the same degradation —
and no thread left behind."""
import sys
import threading

import jax
import pytest

from loro_tpu import LoroDoc
from loro_tpu.core.ids import ContainerID, ContainerType
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.ops.columnar import extract_seq_from_payload
from loro_tpu.ops.movable_batch import extract_movable_from_payload
from loro_tpu.ops.tree_batch import extract_tree_from_payload
from loro_tpu.parallel import fleet as fleet_mod
from loro_tpu.parallel.fleet import Fleet
from loro_tpu.parallel.mesh import make_mesh
from loro_tpu.resilience import DeviceSupervisor, faultinject, set_supervisor
from loro_tpu.utils import tracing


def _two_replicas(i, seed, edit):
    """Replica a of a two-peer document: ``seed`` typed by a, then ``edit``
    on both concurrently, then synced."""
    a, b = LoroDoc(peer=500 + 2 * i), LoroDoc(peer=501 + 2 * i)
    seed(a)
    a.commit()
    b.import_(a.export_snapshot())
    edit(a, b)
    a.commit()
    b.commit()
    a.import_(b.export_updates(a.oplog_vv()))
    return a


def _text_doc(i):
    def edit(a, b):
        a.get_text("text").insert(3, "AAA")
        b.get_text("text").insert(5, "bbb")
        b.get_text("text").delete(0, 2)

    a = _two_replicas(
        i, lambda d: d.get_text("text").insert(0, f"document {i}: " + "abc" * (3 + i)), edit)
    return a, a.get_text("text").to_string()


def _tree_doc(i):
    nodes = []

    def edit(a, b):
        a.get_tree("tree").move(nodes[0], nodes[1])
        b.get_tree("tree").move(nodes[1], nodes[0])  # one of the pair is a cycle
        b.get_tree("tree").move(nodes[3], nodes[2 + i % 2])

    a = _two_replicas(
        i, lambda d: nodes.extend(d.get_tree("tree").create() for _ in range(4 + i % 5)), edit)
    ta = a.get_tree("tree")
    return a, {n: ta.parent(n) for n in ta.nodes()}


def _movable_doc(i):
    def edit(a, b):
        a.get_movable_list("ml").move(0, 2)
        b.get_movable_list("ml").set(1, 100 + i)
        b.get_movable_list("ml").insert(0, {"doc": i})

    a = _two_replicas(
        i, lambda d: d.get_movable_list("ml").push(*[f"v{i}.{j}" for j in range(3 + i % 4)]), edit)
    return a, a.get_movable_list("ml").get_value()


# family -> (document maker, container id, the entry's answers, the answers
# of a serial native decode handed to the same device half)
FAMILIES = {
    "text": (
        _text_doc, ContainerID.root("text", ContainerType.Text),
        lambda f, ps, cid: f.merge_text_payloads(ps, cid).texts,
        lambda f, ps, cid: f.merge_text_docs(
            [extract_seq_from_payload(p, cid) for p in ps]).texts,
    ),
    "tree": (
        _tree_doc, ContainerID.root("tree", ContainerType.Tree),
        lambda f, ps, cid: f.merge_tree_payloads(ps, cid),
        lambda f, ps, cid: f._merge_tree_extracted(
            [extract_tree_from_payload(p, cid) for p in ps]),
    ),
    "movable": (
        _movable_doc, ContainerID.root("ml", ContainerType.MovableList),
        lambda f, ps, cid: f.merge_movable_payloads(ps, cid),
        lambda f, ps, cid: f._merge_movable_extracted(
            [extract_movable_from_payload(p, cid) for p in ps]),
    ),
}
_built = {}


def documents(family, n):
    """(payloads, the host engine's answers) of ``n`` documents, built once."""
    if (family, n) not in _built:
        docs = [FAMILIES[family][0](i) for i in range(n)]
        _built[family, n] = (
            [strip_envelope(d.export_updates({})) for d, _want in docs],
            [want for _d, want in docs],
        )
    return _built[family, n]


def pool_threads():
    return [t for t in threading.enumerate() if t.name.startswith("fleet-pool")]


@pytest.fixture
def fleet():
    return Fleet(make_mesh(jax.devices()[:1]))


@pytest.fixture
def no_sleep_supervisor():
    set_supervisor(DeviceSupervisor(sleep=lambda s: None))
    yield
    set_supervisor(None)


@pytest.fixture
def threads_switch_often():
    """A lost update of what the pool's tasks share (the counters) shows."""
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        yield
    finally:
        sys.setswitchinterval(was)


@pytest.mark.parametrize("n", [1, 2, 17])  # one task; fewer than threads; more
@pytest.mark.parametrize("family", list(FAMILIES))
def test_answers_in_payload_order_equal_a_serial_decodes(
        fleet, family, n, threads_switch_often):
    _make, cid, entry, serial = FAMILIES[family]
    payloads, want = documents(family, n)
    tasks = obs.counter("fleet.decode_tasks_total")
    fallbacks = obs.counter("fleet.host_fallback_total")
    t0, f0 = tasks.get(family=family), fallbacks.get(kind="payload_extract")
    others = {k: tasks.get(family=k) for k in FAMILIES if k != family}
    got = entry(fleet, payloads, cid)
    assert got == want  # the host engine's, document for document
    assert tasks.get(family=family) - t0 == n  # one pool task a payload
    assert {k: tasks.get(family=k) for k in others} == others
    assert fallbacks.get(kind="payload_extract") == f0
    assert pool_threads() == []  # no thread outlives its call
    assert serial(fleet, payloads, cid) == got
    # the payloads reversed: the answers follow them
    assert entry(fleet, payloads[::-1], cid) == want[::-1]


@pytest.mark.faultinject
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_payload_the_native_path_refuses_takes_the_python_fallback(fleet, family):
    """The ``decode`` fault site truncates the bytes ONE native explode
    sees (whichever pool thread gets there first): that payload alone goes
    through the Python decoder, and every answer is still the host's."""
    _make, cid, entry, _serial = FAMILIES[family]
    payloads, want = documents(family, 17)
    fallbacks = obs.counter("fleet.host_fallback_total")
    f0 = fallbacks.get(kind="payload_extract")
    faultinject.inject("decode", action="truncate", keep_bytes=3, times=1)
    try:
        got = entry(fleet, payloads, cid)
    finally:
        faultinject.clear()
    assert got == want
    assert fallbacks.get(kind="payload_extract") == f0 + 1
    assert pool_threads() == []


@pytest.mark.parametrize("k", [0, 8, 16])
def test_a_payload_that_is_not_self_contained_raises_whatever_the_others_are(fleet, k):
    _make, cid, entry, _serial = FAMILIES["text"]
    payloads, _want = documents("text", 17)
    doc, _text = _text_doc(40)
    seen = doc.oplog_vv()
    doc.get_text("text").insert(4, "typed after the first sync")
    doc.commit()
    delta = strip_envelope(doc.export_updates(seen))  # its parents are outside it
    mixed = payloads[:k] + [delta] + payloads[k + 1:]
    for batch in (mixed, [delta], [delta] * 17):
        with pytest.raises(ValueError, match="payload is not self-contained"):
            entry(fleet, batch, cid)
        assert pool_threads() == []


@pytest.mark.faultinject
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_device_failure_still_degrades_to_the_host_engine(
        fleet, family, no_sleep_supervisor):
    _make, cid, entry, _serial = FAMILIES[family]
    payloads, want = documents(family, 2)
    degraded = obs.counter("fleet.degraded_merges_total")
    n0 = degraded.get(family=family)
    faultinject.inject("launch", exc=RuntimeError("INTERNAL: injected device death"),
                       times=1)
    try:
        got = entry(fleet, payloads, cid)
    finally:
        faultinject.clear()
    assert got == want
    assert degraded.get(family=family) == n0 + 1
    assert pool_threads() == []


def test_the_pool_keeps_order_raises_the_first_failure_and_carries_the_trace_id():
    seen = []

    def native(x, cid):
        seen.append((x, cid, tracing.current(), threading.get_ident()))
        if x in (b"\x05", b"\x0b"):
            raise KeyError(x)
        return x[0] ** 2

    def decode(payloads):
        return fleet_mod._decode_payloads(
            "text", "fleet.decode", payloads, "cid", native, None)

    items = [bytes([i]) for i in range(64)]
    assert decode([]) == []
    with tracing.ambient("call-1"):
        assert decode(items[:5]) == [0, 1, 4, 9, 16]
        assert {(c, t) for _x, c, t, _tid in seen} == {("cid", "call-1")}  # the caller's id
        assert threading.get_ident() not in {tid for *_x, tid in seen}
        with pytest.raises(KeyError) as err:  # the first in payload order, as a loop raises
            decode(items)
        assert err.value.args == (b"\x05",)
    assert tracing.current() is None
    assert pool_threads() == []


def test_a_total_fallbacks_key_error_is_not_called_a_payload_fault(fleet, monkeypatch):
    """The tree's Python fallback is total: a ``KeyError`` out of it is a
    fault of the program and reaches the caller as it is."""
    from loro_tpu.ops import tree_batch

    def broken(changes, cid):
        raise KeyError("a bug")

    monkeypatch.setattr(tree_batch, "extract_tree_from_payload", lambda p, cid: None)
    monkeypatch.setattr(tree_batch, "extract_tree_ops", broken)
    _make, cid, entry, _serial = FAMILIES["tree"]
    with pytest.raises(KeyError, match="a bug"):
        entry(fleet, documents("tree", 2)[0], cid)
    assert pool_threads() == []


SPANS = {"text": "fleet.decode", "tree": "fleet.tree_decode", "movable": "fleet.movable_decode"}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_traced_calls_decode_spans_read_the_callers_wait(fleet, family):
    """What the benchmark's stage metrics rest on: summed by name, the
    decode's spans are the ONE wait on the caller's thread — the pool's
    tasks are leaves of other threads, no ``native.*`` span beside them."""
    _make, cid, entry, _serial = FAMILIES[family]
    payloads, want = documents(family, 17)
    tracing.clear()
    tracing.enable()
    try:
        got = entry(fleet, payloads, cid)
        spans = tracing.events()
    finally:
        tracing.disable()
        tracing.clear()
    assert got == want
    name = SPANS[family]
    (wait,) = [e for e in spans if e["name"] == name]
    ones = [e for e in spans if e["name"] == name + "_one"]
    assert wait["tid"] == threading.get_ident()
    assert wait["args"] == {"docs": 17, "workers": fleet_mod._POOL_WIDTH}
    assert sorted(e["args"]["bytes"] for e in ones) == sorted(map(len, payloads))
    assert all(e["parent_id"] == 0 and e["tid"] != wait["tid"] and e["cpu_ns"] is not None
               and e["trace_id"] == wait["trace_id"] for e in ones)
    assert not [e["name"] for e in spans if e["name"].startswith("native.")]
    assert not [e for e in spans if e["parent_id"] == wait["span_id"]]
