"""Anti-quadratic perf guards (reference: crates/loro/tests/
perf_import_quadratic.rs + perf_text_insert_quadratic.rs — asserting
scaling shape, not absolute numbers).

The two scaling guards count what the work costs — function calls under
``sys.setprofile``, Python and C alike — for n and 4 n, so they read the
same under any load (a wall-clock ratio read 2.6-4.8x alone and over 11x
under six xdist workers).  The structural guards below count too;
``test_checkout_bounded`` and the two ``*_floor`` guards still read the
clock, against generous ceilings (ROADMAP D15)."""
import sys
import time

import pytest

from loro_tpu import LoroDoc

# quadratic would be ~16x for 4x work; n log n reads ~4.3x
RATIO_BOUND = 11


def _calls(fn) -> int:
    """The function calls ``fn()`` makes on this thread."""
    n = 0

    def on_event(_frame, event, _arg):
        nonlocal n
        if event in ("call", "c_call"):
            n += 1

    sys.setprofile(on_event)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return n


def _scaling(work, n: int) -> float:
    """Calls of 4x the work over calls of the work; ``work(n)`` sets up
    outside the count and returns the function to count."""
    return _calls(work(4 * n)) / _calls(work(n))


def _text_insert(n: int):
    def run():
        doc = LoroDoc(peer=1)
        t = doc.get_text("t")
        for i in range(n):
            t.insert(i, "x")
        doc.commit()

    return run


def _import(n_updates: int):
    a = LoroDoc(peer=1)
    blobs = []
    t = a.get_text("t")
    for i in range(n_updates):
        vv = a.oplog_vv()
        t.insert(len(t), f"w{i} ")
        a.commit()
        blobs.append(a.export_updates(vv))

    def run():
        b = LoroDoc(peer=2)
        for blob in blobs:
            b.import_(blob)

    return run


def _quadratic_stand_in(n: int):
    """What the guards are there to catch: every step walks all before it."""
    def run():
        seen = []
        for i in range(n):
            for _ in seen:
                len(seen)
            seen.append(i)

    return run


def test_text_insert_not_quadratic():
    ratio = _scaling(_text_insert, 4000)
    assert ratio < RATIO_BOUND, f"text insert: {ratio:.1f}x the calls for 4x work"


def test_import_not_quadratic():
    ratio = _scaling(_import, 100)
    assert ratio < RATIO_BOUND, f"import: {ratio:.1f}x the calls for 4x work"


def test_scaling_guard_bites_on_quadratic_work():
    assert _scaling(_quadratic_stand_in, 100) > RATIO_BOUND


def test_checkout_bounded():
    """Checkout cost stays proportional to history, not history^2."""
    doc = LoroDoc(peer=1)
    t = doc.get_text("t")
    fs = []
    for i in range(300):
        t.insert(len(t), "ab")
        doc.commit()
        fs.append(doc.oplog_frontiers())
    t0 = time.perf_counter()
    doc.checkout(fs[10])
    doc.checkout_to_latest()
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"checkout round-trip took {dt:.2f}s"


def _count_replayed(doc):
    """Wrap oplog.changes_between to record how many changes each
    state materialization replays (deterministic, not timing-based)."""
    counts = []
    orig = doc.oplog.changes_between

    def wrapper(a, b):
        out = orig(a, b)
        counts.append(len(out))
        return out

    doc.oplog.changes_between = wrapper
    return counts


def test_recheckout_sublinear():
    """History cache (history_cache.py): after one retreat, further
    checkouts in the same region replay only the delta between
    versions, not history-from-floor (reference: history_cache.rs)."""
    doc = LoroDoc(peer=1)
    t = doc.get_text("t")
    fs = []
    n = 400
    for i in range(n):
        t.insert(len(t), "word ")
        doc.commit(message=f"c{i}")  # distinct messages: no RLE merge
        fs.append(doc.oplog_frontiers())
    counts = _count_replayed(doc)
    doc.checkout(fs[200])  # cold retreat: replays ~200 changes
    cold = sum(counts)
    assert cold >= 150, f"expected a full replay on first retreat, got {cold}"
    counts.clear()
    doc.checkout(fs[210])  # warm: nearest checkpoint is fs[200]
    warm = sum(counts)
    assert warm <= 15, f"re-checkout replayed {warm} changes (want O(delta))"
    counts.clear()
    doc.checkout(fs[205])  # retreat within the cached region
    warm2 = sum(counts)
    assert warm2 <= 15, f"retreat near checkpoint replayed {warm2} changes"
    doc.checkout_to_latest()
    assert t.to_string().count("word") == n


def test_undo_deep_history_soak():
    """Undo on a doc with deep history must not replay from the floor
    on every step (each inverse diff uses the checkpoint cache)."""
    from loro_tpu.undo import UndoManager

    doc = LoroDoc(peer=1)
    um = UndoManager(doc)
    t = doc.get_text("t")
    n = 300
    for i in range(n):
        t.insert(len(t), f"w{i} ")
        doc.commit(message=f"c{i}")
    counts = _count_replayed(doc)
    t0 = time.perf_counter()
    for _ in range(20):
        assert um.undo()
    dt = time.perf_counter() - t0
    # one cold replay (~n) plus small ladder-gap replays per undo —
    # far below the 20 undos x n changes the floor-replay design cost
    assert sum(counts) < 3 * n, f"undo soak replayed {sum(counts)} changes"
    assert dt < 5.0, f"20 undos on deep history took {dt:.2f}s"
    assert t.to_string().count("w") == n - 20


def test_diff_cost_scales_with_delta():
    """delta_between is O(delta), not O(doc): on a large doc, a 1-commit
    diff near the tip must touch a bounded number of elements, however
    long the history (reference: changed-subtree-only diff walk,
    crdt_rope.rs:383-451).  Counted structurally via visible_rank calls,
    not timing."""
    doc = LoroDoc(peer=1)
    t = doc.get_text("t")
    n = 3000
    fs = []
    for i in range(n):
        t.insert(len(t), "word ")
        doc.commit(message=f"c{i}")
        fs.append(doc.oplog_frontiers())
    from loro_tpu.utils.treap import Treap

    calls = []
    orig = Treap.visible_rank

    def wrapper(self, e):
        calls.append(1)
        return orig(self, e)

    Treap.visible_rank = wrapper
    try:
        d = doc.diff(fs[-2], fs[-1])
    finally:
        Treap.visible_rank = orig
    assert sum(calls) <= 64, f"1-commit diff did {sum(calls)} rank queries on a {n}-commit doc"
    assert t.cid in d and d[t.cid].insert_len() == 5


def test_diff_delta_vs_fullscan_equivalence():
    """Randomized oracle: the ranged O(delta) path must produce the
    exact delta of the legacy full-table scan for random version pairs
    on a multi-peer doc with deletes."""
    import random as _random

    from loro_tpu import LoroDoc as _Doc

    rng = _random.Random(7)
    doc = _Doc(peer=1)
    t = doc.get_text("t")
    fs = []
    for i in range(120):
        L = len(t)
        if L and rng.random() < 0.35:
            p = rng.randrange(L)
            t.delete(p, min(3, L - p))
        else:
            t.insert(rng.randrange(L + 1) if L else 0, f"x{i}")
        doc.commit()
        fs.append(doc.oplog_frontiers())
    dag = doc.oplog.dag
    st = doc.state.states[t.cid]
    vc = doc.state.vv
    for _ in range(40):
        va = dag.frontiers_to_vv(fs[rng.randrange(len(fs))])
        vb = dag.frontiers_to_vv(fs[rng.randrange(len(fs))])
        fast = st.seq.delta_between(va, vb, as_text=True, vc=vc)
        slow = st.seq.delta_between(va, vb, as_text=True)
        assert fast.items == slow.items, (
            f"ranged diff mismatch: {fast.items} vs {slow.items}"
        )


def test_native_order_engine_floor():
    """Resident-fleet host ceiling guard (tests/soak_fleet.py measures
    ~3M rows/s/core isolated): the native order engine must stay above
    a conservative floor so a regression in the C++ splice path can't
    silently starve thousands-of-docs resident fleets."""
    import random as _random

    from loro_tpu.native import native_order

    eng_factory = native_order
    if eng_factory() is None:
        pytest.skip("native library unavailable")
    rng = _random.Random(1)
    k = 4096
    rows = []
    for i in range(k):
        if i and rng.random() < 0.7:
            rows.append((i - 1, 1, 7, i))
        else:
            rows.append((rng.randrange(i) if i else -1, rng.choice([0, 1]), 7, i))

    def one():
        eng = eng_factory()
        t0 = time.perf_counter()
        eng.append_rows(rows, 0)
        return time.perf_counter() - t0

    # the minimum: the least load-sensitive statistic of a CPU-bound loop
    best = min(one() for _ in range(5))
    rate = k / best
    assert rate > 500_000, f"native order engine at {rate/1e6:.2f}M rows/s (< 0.5M floor)"


def test_resident_ingest_floor():
    """Full resident ingest floor (r5 host-funnel rebuild measured
    ~1.1M rows/s/core steady at 768-row epochs): order maintenance +
    native id maps + columnar staging + block scatter must stay above a
    conservative floor, so per-row Python can't silently creep back
    into the hot path.  Generous vs the measured rate — this guards
    order-of-magnitude regressions, not session load variance."""
    import random as _random

    from loro_tpu import LoroDoc
    from loro_tpu.doc import strip_envelope
    from loro_tpu.parallel.fleet import DeviceDocBatch

    rng = _random.Random(0xF100D)
    doc = LoroDoc(peer=1)
    t = doc.get_text("t")
    eps = []
    for _ in range(4):
        vv = doc.oplog_vv()
        made = 0
        while made < 768:
            L = len(t)
            if L > 8 and rng.random() < 0.15:
                p = rng.randrange(L - 1)
                d = min(rng.randint(1, 3), L - p)
                t.delete(p, d)
                made += d
            else:
                run = rng.randint(1, 12)
                t.insert(rng.randint(0, L), "abcdefghijkl"[:run])
                made += run
        doc.commit()
        eps.append(strip_envelope(doc.export_updates(vv)))
    batch = DeviceDocBatch(16, capacity=1 << 13)
    rates = []
    for pl in eps:
        t0 = time.perf_counter()
        batch.append_payloads([pl] * 16, doc.get_text("t").id)
        rates.append(16 * 768 / (time.perf_counter() - t0))
    best = max(rates)  # best epoch: least load/compile confounded
    assert best > 150_000, (
        f"resident ingest at {best/1e3:.0f}k rows/s best-epoch "
        "(< 150k floor; steady-state measured ~1.1M on an idle core)"
    )
    assert batch.texts()[0] == t.to_string()
