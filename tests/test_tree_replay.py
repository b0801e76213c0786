"""The movable-tree replay, whichever runs (``ops/tree_batch.tree_replay``):
the replay ``tree_merge_batch`` selects here (off the chip the scan) and
the fused lock-step kernel in interpret mode both equal ``tree_merge_doc``,
the host ``LoroDoc`` and the benchmark's plain reference
(``benchmarks/tree_reference.py``, which imports nothing of the program)
on seeded concurrent logs; and the ``Fleet`` tree entry around them: one
packed upload, one launch, one fetch, degradation, no ``[D, M]`` fetch."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from loro_tpu import LoroDoc
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.ops import tree_batch as tb
from loro_tpu.ops.tree_batch import ABSENT, ROOT, TRASH, TreeOpCols
from loro_tpu.parallel.fleet import Fleet
from loro_tpu.parallel.mesh import doc_sharding, make_mesh
from loro_tpu.resilience import DeviceSupervisor, faultinject, set_supervisor

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import tree_reference  # noqa: E402  (standard library only)

ALGOS = ["selected", "pallas:lockstep"]


@functools.lru_cache(maxsize=None)
def _jitted(algo: str, n_nodes: int, d_max):
    if algo == "selected":  # what the callers get
        return lambda cols: tb.tree_replay(cols, n_nodes, d_max)
    return jax.jit(lambda cols: tb.tree_replay(
        cols, n_nodes, d_max, True, algo, interpret=True))


def replay(algo: str, logs: list, n_nodes: int, d_max=None):
    """``logs``: per document a list of ``(target, parent)``; returns
    (parents, eff, stats) as numpy, documents padded to one length."""
    m = max(8, max(len(g) for g in logs))
    cols = TreeOpCols(*[np.zeros((len(logs), m), dt) for dt in (np.int32, np.int32, bool)])
    cols.parent[:] = ROOT
    for d, log in enumerate(logs):
        for k, (t, p) in enumerate(log):
            cols.target[d, k], cols.parent[d, k], cols.valid[d, k] = t, p, True
    out = _jitted(algo, n_nodes, d_max)(TreeOpCols(*map(jnp.asarray, cols)))
    return [np.asarray(x) for x in out], cols


def by_doc(cols: TreeOpCols, d: int, n_nodes: int):
    """The differential reference: ``tree_merge_doc`` on one document."""
    one = TreeOpCols(*[jnp.asarray(a[d]) for a in cols])
    parents, eff = tb.tree_merge_doc(one, n_nodes)
    return np.asarray(parents), np.asarray(eff)


def plain(n_nodes: int, log: list):
    """The plain reference on a log whose nodes are created first."""
    creates = [(t, p) for t, p in log[:n_nodes]]
    assert creates == [(i, ROOT) for i in range(n_nodes)]
    return tree_reference.apply_moves(n_nodes, log[n_nodes:])


@pytest.mark.parametrize("algo", ALGOS)
def test_mutually_cyclic_concurrent_moves(algo):
    # a under b and b under a, each sound where it was made: the later
    # in (lamport, peer) order is refused; then a three-cycle
    n = 4
    log = [(i, ROOT) for i in range(n)] + [(0, 1), (1, 0), (1, 2), (2, 3), (3, 0), (2, 2)]
    (parents, eff, stats), cols = replay(algo, [log], n)
    want, refused, _reads = plain(n, log)
    assert parents[0].tolist() == want == [1, 2, 3, -1]
    assert eff[0, : len(log)].tolist() == [True] * 5 + [False, True, True, False, False]
    assert stats[0, 0] == refused == 3
    ref_p, ref_e = by_doc(cols, 0, n)
    assert (parents[0] == ref_p).all() and (eff[0] == ref_e).all()


@pytest.mark.parametrize("algo", ALGOS)
def test_a_chain_as_deep_as_the_node_count(algo):
    n = 70  # deeper than a vreg is tall, than any fixed cap of a walk
    log = [(i, ROOT) for i in range(n)] + [(i + 1, i) for i in range(n - 1)]
    log += [(0, n - 1), (n // 2, n - 1), (n - 1, ROOT), (0, n - 1)]
    (parents, eff, stats), cols = replay(algo, [log], n)
    want, refused, reads = plain(n, log)
    assert parents[0].tolist() == want and stats[0, 0] == refused == 2
    assert eff[0, len(log) - 4 : len(log)].tolist() == [False, False, True, True]
    # the walk steps it took itself: the reference's parent reads, and the
    # step on which a walk finds its target
    assert stats[0, 1] == reads + refused
    assert (parents[0] == by_doc(cols, 0, n)[0]).all()


@pytest.mark.parametrize("algo", ALGOS)
def test_deletes_moves_under_a_deleted_node_and_a_node_never_created(algo):
    n = 6  # node 5 is never created: it is moved, and moved under
    log = [(i, ROOT) for i in range(5)]
    log += [(1, 0), (2, 1), (1, TRASH), (3, 2), (0, 2), (4, 5), (5, 4), (5, 3), (2, TRASH)]
    (parents, eff, stats), cols = replay(algo, [log], n)
    ref_p, ref_e = by_doc(cols, 0, n)
    assert (parents[0] == ref_p).all() and (eff[0] == ref_e).all()
    assert parents[0].tolist() == [2, TRASH, TRASH, 2, 5, 3]
    assert stats[0, 0] == 1  # (5, 4): 4 already sits under 5
    deleted = np.asarray(tb.is_deleted_batch(jnp.asarray(parents)))[0]
    assert deleted.tolist() == [True, True, True, True, True, True]
    (capped, _e, _s), _c = replay(algo, [log], n, d_max=1)
    assert (capped[0] == np.asarray(tb.tree_merge_doc(
        TreeOpCols(*[jnp.asarray(a[0]) for a in cols]), n, 1)[0])).all()


def _host_parents(doc, nodes):
    from loro_tpu.models.tree_state import TRASH as HOST_TRASH

    st = doc.state.get_or_create(doc.get_tree("tr").id)
    out = []
    for t in nodes:
        node = st.nodes.get(t)
        out.append(ABSENT if node is None else TRASH if node.parent == HOST_TRASH
                   else ROOT if node.parent is None else nodes.index(node.parent))
    return out


def _random_docs(seed: int, steps: int):
    import random

    rng = random.Random(seed)
    docs = [LoroDoc(peer=i + 1) for i in range(3)]
    for _ in range(steps):
        d = rng.choice(docs)
        tr = d.get_tree("tr")
        nodes = tr.nodes()
        r = rng.random()
        if not nodes or r < 0.3:
            tr.create(rng.choice(nodes) if nodes and rng.random() < 0.5 else None)
        elif r < 0.8 and len(nodes) >= 2:
            x, y = rng.sample(nodes, 2)
            try:
                tr.move(x, y)
            except ValueError:
                pass
        elif r < 0.9:
            tr.delete(rng.choice(nodes))
        if rng.random() < 0.25:
            src, dst = rng.sample(docs, 2)
            dst.import_(src.export_updates(dst.oplog_vv()))
    for _ in range(2):
        for s in docs:
            for t in docs:
                if s is not t:
                    t.import_(s.export_updates(t.oplog_vv()))
    return docs[0]


@pytest.mark.parametrize("algo", ALGOS)
def test_an_empty_document_and_very_different_sizes_against_the_host_engine(algo):
    docs = [_random_docs(seed, steps) for seed, steps in ((1, 150), (2, 8), (3, 60))]
    extracted = []
    for d in docs:
        d.commit()
        extracted.append(tb.extract_tree_ops(
            d.oplog.changes_in_causal_order(), d.get_tree("tr").id))
    logs = [list(zip(c.target.tolist(), c.parent.tolist())) for c, _n, _p in extracted]
    logs.insert(1, [])  # a document with no op at all
    n = max(len(nodes) for _c, nodes, _p in extracted)
    (parents, eff, stats), cols = replay(algo, logs, n)
    assert (parents[1] == ABSENT).all() and not eff[1].any() and stats[1, 0] == 0
    for d, (i, (_c, nodes, _p)) in zip(docs, zip((0, 2, 3), extracted)):
        assert parents[i, : len(nodes)].tolist() == _host_parents(d, nodes)
        ref_p, ref_e = by_doc(cols, i, n)
        assert (parents[i] == ref_p).all() and (eff[i] == ref_e).all()
        assert stats[i, 0] == (cols.valid[i] & ~ref_e).sum()


TINY = {"nodes": 48, "move_draws": 600, "peers_per_document": 4,
        "peer_ids": [1, 2, 3, 4], "peer_window": [4, 32]}


def _script_log(seed: int, v: int) -> list:
    n = TINY["nodes"]
    return [(i, ROOT) for i in range(n)] + [
        (i, j) for i, j in tree_reference.ordered_moves(seed, TINY, v)]


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("seed", [11, 2147483659])
def test_seeded_concurrent_logs_against_the_plain_reference(algo, seed):
    logs = [_script_log(seed, v) for v in range(5)]
    (parents, _eff, stats), _cols = replay(algo, logs, TINY["nodes"])
    for v in range(5):
        ref = tree_reference.replay(seed, TINY, v)
        assert parents[v].tolist() == ref["parents"]
        assert stats[v, 0] == ref["refused"] > 0


def test_both_replays_take_the_same_steps():
    logs = [_script_log(5, v) for v in range(3)]
    (_p, _e, scan), _c = replay("selected", logs, TINY["nodes"])
    (_p, _e, fused), _c = replay("pallas:lockstep", logs, TINY["nodes"])
    assert (scan[:, :2] == fused[:, :2]).all()
    # side by side the slowest document paces a move: the fused kernel tests
    # its `while` every `_WALK_UNROLL` steps, so it counts whole rounds
    assert (scan[:, 2] == scan[0, 2]).all() and (fused[:, 2] == fused[0, 2]).all()
    assert scan[0, 2] <= fused[0, 2] <= scan[0, 2] + tb._WALK_UNROLL * len(logs[0])
    assert scan[:, 1].max() <= scan[0, 2] <= scan[:, 1].sum()


def test_the_replay_is_chosen_from_what_the_code_can_observe(monkeypatch):
    assert tb.replay_algo(1000) == "xla:scan"  # off the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tb.replay_algo(1000) == tb.replay_algo(1) == "pallas:lockstep"
    assert tb.replay_algo(70_000) == "xla:scan"  # node indexes past 16 bits
    assert tb.replay_algo(40_000) == "xla:scan"  # a table VMEM does not hold
    assert [tb.tree_pads(m) for m in (0, 1, 17, 8192, 8193, 97_700)] == [
        16, 16, 32, 8192, 16384, 98_304]


# ---------------------------------------------------------------------
# the Fleet entry
# ---------------------------------------------------------------------

def _payload_doc(i: int):
    """Two replicas, concurrent moves (one pair makes a cycle), a delete."""
    a, b = LoroDoc(peer=800 + 2 * i), LoroDoc(peer=801 + 2 * i)
    ta = a.get_tree("tr")
    nodes = [ta.create() for _ in range(5 + 3 * i)]
    a.commit()
    b.import_(a.export_snapshot())
    ta.move(nodes[0], nodes[1])
    tb_ = b.get_tree("tr")
    tb_.move(nodes[1], nodes[0])
    tb_.move(nodes[3], nodes[2])
    tb_.delete(nodes[4])
    a.import_(b.export_updates(a.oplog_vv()))
    return strip_envelope(a.export_updates({})), {n: ta.parent(n) for n in ta.nodes()}, a


@pytest.fixture(params=[1, 8], ids=["one_device", "mesh_of_8"])
def fleet(request):
    return Fleet(make_mesh(jax.devices()[: request.param]))


def test_payload_entry_equals_the_host_engine_and_pads_the_doc_axis(fleet):
    docs = [_payload_doc(i) for i in range(3)]  # 3 documents on 8 devices: padded
    cid = docs[0][2].get_tree("tr").id
    refused = obs.counter("tree.moves_refused_total").total()
    got = fleet.merge_tree_payloads([p for p, _w, _d in docs], cid)
    assert got == [w for _p, w, _d in docs]
    assert obs.counter("tree.moves_refused_total").total() - refused == 3
    assert fleet.tree_refused.tolist() == [1, 1, 1]  # per document, no padding row
    changes = [d.oplog.changes_in_causal_order() for _p, _w, d in docs]
    assert fleet.merge_tree_changes(changes, cid) == got
    kids = fleet.merge_tree_children(changes, cid)
    for (_p, _w, d), k in zip(docs, kids):
        tr = d.get_tree("tr")
        assert k == {p: tr.children(p) for p in [None, *tr.nodes()] if tr.children(p)}


def test_a_second_identical_call_compiles_nothing_and_fetches_no_move_array(
        fleet, monkeypatch):
    from loro_tpu.resilience import supervisor as sup_mod

    docs = [_payload_doc(i) for i in range(3)]
    cid = docs[0][2].get_tree("tr").id
    payloads = [p for p, _w, _d in docs]
    first = fleet.merge_tree_payloads(payloads, cid)
    compiled, fetched = [], []

    def on_compile(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    real_fetch = sup_mod.DeviceSupervisor.fetch

    def fetch(self, value, label=None):
        fetched.append(tuple(value.shape))
        return real_fetch(self, value, label=label)

    monkeypatch.setattr(sup_mod.DeviceSupervisor, "fetch", fetch)
    try:
        assert fleet.merge_tree_payloads(payloads, cid) == first
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiled == []
    n = 5 + 3 * 2  # the largest document's nodes, the deleted one too
    d_pad = len(jax.devices()) if fleet.mesh.size > 1 else 3
    # one fetch: parents of alive nodes + two counts a document; no [D, M]
    assert fetched == [(d_pad, n + 2)]


@pytest.fixture
def fake_sleep_supervisor():
    set_supervisor(DeviceSupervisor(sleep=lambda _s: None))
    yield
    set_supervisor(None)


@pytest.mark.faultinject
@pytest.mark.parametrize("site", ["launch", "fetch"])
def test_device_failure_still_degrades_with_its_counter_ticked(
        site, fake_sleep_supervisor):
    payload, want, doc = _payload_doc(1)
    cid = doc.get_tree("tr").id
    fleet = Fleet()
    n0 = obs.counter("fleet.degraded_merges_total").get(family="tree")
    faultinject.inject(site, exc=RuntimeError("INTERNAL: injected device death"),
                       times=1)
    try:
        got = fleet.merge_tree_payloads([payload], cid)
    finally:
        faultinject.clear()
    assert got == [want] and fleet.tree_refused is None
    assert obs.counter("fleet.degraded_merges_total").get(family="tree") == n0 + 1


def test_sharded_batch_replays_each_devices_own_documents():
    mesh = make_mesh(jax.devices()[:8])
    logs = [_script_log(3, v) for v in range(8)]
    m = tb.tree_pads(max(len(g) for g in logs))
    cols = [TreeOpCols(np.asarray([t for t, _p in g], np.int32),
                       np.asarray([p for _t, p in g], np.int32),
                       np.ones(len(g), bool)) for g in logs]
    rows = jax.device_put(tb.pack_tree_rows(cols, 8), doc_sharding(mesh))
    assert rows.shape == (8, 1 + m)
    out = np.asarray(tb.tree_import_batch(rows, TINY["nodes"], False))
    for v in range(8):
        ref = tree_reference.replay(3, TINY, v)
        assert out[v, : TINY["nodes"]].tolist() == ref["parents"]
        assert out[v, TINY["nodes"]] == ref["refused"]
