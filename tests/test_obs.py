"""loro_tpu.obs: registry semantics, exposition formats, the tracing
bridge, and counters observed ticking through the real fleet/server
paths — all on the CPU mesh, no device access."""
import json
import os
import sys
import threading

import pytest

from loro_tpu import LoroDoc, obs
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as m
from loro_tpu.obs.report import render
from loro_tpu.utils import tracing


@pytest.fixture
def reg():
    """Isolated registry (the default registry is process-global and
    other tests tick it)."""
    return m.Registry()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_labels_and_totals(reg):
    c = reg.counter("x.a_total", "help text")
    c.inc()
    c.inc(4, family="text")
    c.inc(2, family="map")
    assert c.get() == 1
    assert c.get(family="text") == 4
    assert c.total() == 7
    # label order is normalized
    c.inc(1, b="2", a="1")
    assert c.get(a="1", b="2") == 1


def test_gauge_set_inc_dec(reg):
    g = reg.gauge("x.depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.get() == 6
    g.set(1.5, family="tree")
    assert g.get(family="tree") == 1.5


def test_histogram_buckets_and_quantiles(reg):
    h = reg.histogram("x.seconds", buckets=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(6.05)
    assert 0.1 <= s["p50"] <= 1.0  # two obs in the (0.1, 1] bucket
    assert 1.0 <= s["p99"] <= 10.0
    rows = h.snapshot()["values"]
    assert rows[0]["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4], ["+Inf", 4]]
    # overflow bucket: beyond the last bound
    h.observe(99.0)
    assert h.snapshot()["values"][0]["buckets"][-1] == ["+Inf", 5]


def test_unique_cardinality(reg):
    u = reg.unique("x.shapes")
    u.add(("text", 64, 8))
    u.add(("text", 64, 8))
    u.add(("text", 128, 8))
    assert u.get() == 2
    assert u.total() == 2


def test_kind_conflict_raises(reg):
    reg.counter("x.n")
    with pytest.raises(TypeError):
        reg.gauge("x.n")


def test_histogram_time_context(reg):
    h = reg.histogram("x.t_seconds")
    with h.time(family="text"):
        pass
    assert h.summary()["count"] == 1


# ---------------------------------------------------------------------------
# exposition: prometheus text + JSON snapshot round trip + sidecar
# ---------------------------------------------------------------------------


def test_prometheus_exposition_format(reg):
    from loro_tpu.obs.exposition import prometheus_text

    reg.counter("fleet.ops_merged_total", "rows merged").inc(10, family="text")
    reg.histogram("server.epoch_seconds", buckets=[1.0]).observe(0.5, family="t")
    reg.unique("fleet.padded_shapes_distinct").add((64, 8))
    text = prometheus_text(reg)
    assert "# HELP fleet_ops_merged_total rows merged" in text
    assert "# TYPE fleet_ops_merged_total counter" in text
    assert 'fleet_ops_merged_total{family="text"} 10' in text
    # histogram: cumulative buckets + sum + count, le label merged in
    assert 'server_epoch_seconds_bucket{family="t",le="1.0"} 1' in text
    assert 'server_epoch_seconds_bucket{family="t",le="+Inf"} 1' in text
    assert 'server_epoch_seconds_sum{family="t"} 0.5' in text
    assert 'server_epoch_seconds_count{family="t"} 1' in text
    # unique exports as a gauge
    assert "# TYPE fleet_padded_shapes_distinct gauge" in text
    assert "fleet_padded_shapes_distinct 1" in text


def test_json_snapshot_round_trip(reg):
    from loro_tpu.obs.exposition import snapshot_json

    reg.counter("a.b_total").inc(3, k="v")
    reg.histogram("a.h", buckets=[1.0]).observe(0.2)
    snap = reg.snapshot()
    assert json.loads(snapshot_json(reg)) == snap
    # render accepts the decoded snapshot (the report CLI path)
    out = render(json.loads(snapshot_json(reg)))
    assert "a.b_total" in out and "a.h" in out


def test_sidecar_shape(reg):
    from loro_tpu.obs.exposition import sidecar

    reg.counter("fleet.ops_merged_total").inc(7, family="text")
    reg.gauge("resilience.in_flight").set(3.0)
    reg.histogram("server.epoch_seconds").observe(0.25)
    side = sidecar(reg)
    assert side["fleet.ops_merged_total"] == 7
    assert side["fleet.ops_merged_total{family=text}"] == 7
    assert side["resilience.in_flight"] == 3
    hs = side["server.epoch_seconds"]
    assert hs["count"] == 1 and hs["p50"] is not None


def test_report_renders_live_registry():
    # the module entry (python -m loro_tpu.obs.report) renders the
    # process-global registry; make sure it never throws on real state
    obs.counter("fleet.ops_merged_total").inc(0, family="text")
    out = render()
    assert "loro_tpu.obs" in out


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------


def test_thread_safety_smoke(reg):
    c = reg.counter("x.threads_total")
    h = reg.histogram("x.threads_seconds", buckets=[0.5])
    u = reg.unique("x.threads_shapes")

    def work(tid):
        for i in range(1000):
            c.inc()
            h.observe(0.1)
            u.add((tid, i % 10))

    ts = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.get() == 8000
    assert h.summary()["count"] == 8000
    assert u.get() == 80


# ---------------------------------------------------------------------------
# tracing bridge + overhead
# ---------------------------------------------------------------------------


def test_span_bridge_feeds_histogram():
    obs.enable_span_metrics()
    try:
        with tracing.span("obs.bridge.probe"):
            pass
        h = obs.histogram("trace.span_seconds")
        rows = {tuple(sorted(r["labels"].items())): r for r in h.snapshot()["values"]}
        assert (("span", "obs.bridge.probe"),) in rows
        # chrome-trace collection stays off: the bridge alone must not
        # start recording events
        assert not tracing.is_enabled()
        assert tracing.events() == []
    finally:
        obs.disable_span_metrics()


def test_zero_overhead_when_bridge_disabled():
    """Mirror of test_zero_overhead_when_disabled (tracing): with the
    bridge off and tracing off, span() must not record events, call
    observers, or grow the span histogram."""
    obs.disable_span_metrics()
    tracing.disable()
    tracing.clear()
    h = obs.histogram("trace.span_seconds")
    before = h.summary()["count"]
    with tracing.span("obs.overhead.probe"):
        pass
    assert tracing.events() == []
    assert h.summary()["count"] == before
    # and the always-on registry itself is cheap: a counter hot loop
    # stays far from pathological (structural smoke, generous bound)
    import time

    c = obs.counter("x.overhead_probe_total")
    t0 = time.perf_counter()
    for _ in range(10_000):
        c.inc()
    assert time.perf_counter() - t0 < 2.0
    assert c.get() >= 10_000


# ---------------------------------------------------------------------------
# counters tick through the real merge/ingest paths (CPU mesh)
# ---------------------------------------------------------------------------


def _two_docs():
    a, b = LoroDoc(peer=11), LoroDoc(peer=12)
    a.get_text("t").insert(0, "observable text")
    a.commit()
    b.import_(a.export_snapshot())
    b.get_text("t").insert(5, "XYZ")
    a.import_(b.export_updates(a.oplog_vv()))
    a.commit()
    b.commit()
    return a, b


def test_fleet_merge_ticks_counters():
    from loro_tpu.parallel.fleet import Fleet

    a, b = _two_docs()
    cid = a.get_text("t").id
    ops0 = obs.counter("fleet.ops_merged_total").get(family="text")
    calls0 = obs.counter("fleet.merge_calls_total").get(family="text")
    launches0 = obs.counter("fleet.device_launches_total").get(family="text")
    waste0 = obs.counter("fleet.pad_waste_rows_total").get(family="text")
    fleet = Fleet()
    res = fleet.merge_text_changes(
        [a.oplog.changes_in_causal_order(), b.oplog.changes_in_causal_order()], cid
    )
    assert res.texts[0] == a.get_text("t").to_string()
    assert obs.counter("fleet.merge_calls_total").get(family="text") == calls0 + 1
    assert obs.counter("fleet.device_launches_total").get(family="text") == launches0 + 1
    assert obs.counter("fleet.ops_merged_total").get(family="text") > ops0
    assert obs.counter("fleet.pad_waste_rows_total").get(family="text") > waste0
    assert obs.unique("fleet.padded_shapes_distinct").total() >= 1


def test_resident_server_epoch_ticks_counters():
    from loro_tpu.parallel.server import ResidentServer

    a, _ = _two_docs()
    cid = a.get_text("t").id
    h = obs.histogram("server.epoch_seconds")
    n0 = h.summary()["count"]
    rounds0 = obs.counter("server.ingest_rounds_total").get(
        family="text", route="payloads"
    )
    srv = ResidentServer("text", 2, capacity=1 << 10)
    srv.ingest([strip_envelope(a.export_updates({})), None], cid)
    assert srv.batch.texts()[0] == a.get_text("t").to_string()
    assert h.summary()["count"] == n0 + 1
    assert (
        obs.counter("server.ingest_rounds_total").get(family="text", route="payloads")
        == rounds0 + 1
    )
    assert obs.gauge("server.queue_depth").get(family="text") == 1
    assert obs.counter("server.ingest_docs_total").get(family="text") >= 1


def test_doc_io_and_codec_counters_tick():
    imp0 = obs.counter("doc.import_calls_total").get()
    impb0 = obs.counter("doc.import_bytes_total").get()
    exp0 = obs.counter("doc.export_calls_total").get(mode="Updates")
    ops0 = obs.counter("oplog.ops_applied_total").get()
    a, b = LoroDoc(peer=21), LoroDoc(peer=22)
    a.get_text("t").insert(0, "wire")
    blob = a.export_updates()
    b.import_(blob)
    assert obs.counter("doc.import_calls_total").get() == imp0 + 1
    assert obs.counter("doc.import_bytes_total").get() == impb0 + len(blob)
    assert obs.counter("doc.export_calls_total").get(mode="Updates") == exp0 + 1
    assert obs.counter("oplog.ops_applied_total").get() > ops0


def test_native_decode_counters_tick():
    from loro_tpu import native
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import extract_seq_from_payload

    if not native.available():
        pytest.skip("native library unavailable")
    a = LoroDoc(peer=31)
    a.get_text("t").insert(0, "native bytes")
    a.commit()
    pl = strip_envelope(a.export_updates())
    calls0 = obs.counter("codec.native_decode_calls_total").total()
    bytes0 = obs.counter("codec.native_decode_bytes_total").total()
    cid = ContainerID.root("t", ContainerType.Text)
    assert extract_seq_from_payload(pl, cid) is not None
    assert obs.counter("codec.native_decode_calls_total").total() > calls0
    assert obs.counter("codec.native_decode_bytes_total").total() >= bytes0 + len(pl)


def test_host_fallback_counter_ticks(monkeypatch):
    from loro_tpu.parallel.idmap import PyIdMap, make_idmap

    monkeypatch.setenv("LORO_PY_IDMAP", "1")
    n0 = obs.counter("fleet.host_fallback_total").get(kind="idmap")
    assert isinstance(make_idmap(), PyIdMap)
    assert obs.counter("fleet.host_fallback_total").get(kind="idmap") == n0 + 1


# ---------------------------------------------------------------------------
# tracing satellites (ISSUE 14): observer COW race, instant observers,
# dump collision guard
# ---------------------------------------------------------------------------


def test_observer_cow_survives_mid_span_unregister():
    """The ISSUE 14 race: removing an observer while span() iterates
    must neither skip other observers nor raise.  COW means the span
    that started with N observers fires all N; registrations landing
    mid-span apply to the NEXT span."""
    fired = []

    def self_removing(name, dur):
        fired.append("a")
        tracing.remove_span_observer(self_removing)

    def stable(name, dur):
        fired.append("b")

    tracing.add_span_observer(self_removing)
    tracing.add_span_observer(stable)
    try:
        with tracing.span("obs.cow.probe"):
            pass
        assert fired == ["a", "b"]  # removal mid-iteration skipped nothing
        fired.clear()
        with tracing.span("obs.cow.probe2"):
            pass
        assert fired == ["b"]  # the removal took effect for later spans
    finally:
        tracing.remove_span_observer(stable)
        tracing.remove_span_observer(self_removing)


def test_observer_registration_concurrent_with_spans():
    """Hammer add/remove against concurrent span() iterations — the
    pre-fix list mutation raced the unlocked iteration."""
    stop = []

    def obs_fn(name, dur):
        pass

    def churn():
        for _ in range(300):
            tracing.add_span_observer(obs_fn)
            tracing.remove_span_observer(obs_fn)

    def spans():
        while not stop:
            with tracing.span("obs.race.probe"):
                pass

    ts = [threading.Thread(target=churn) for _ in range(4)]
    sp = threading.Thread(target=spans)
    sp.start()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.append(True)
    sp.join()
    tracing.remove_span_observer(obs_fn)


def test_instant_fires_observers():
    seen = []
    tracing.add_span_observer(lambda n, d: seen.append((n, d)))
    fn = tracing._span_observers[-1]
    try:
        tracing.instant("obs.instant.probe", k=1)
        assert ("obs.instant.probe", 0.0) in seen
    finally:
        tracing.remove_span_observer(fn)


def test_dump_paths_never_collide(tmp_path, monkeypatch):
    """Two dumps in the same wall second used to overwrite each other
    — the default filename now carries pid + a monotonic counter."""
    monkeypatch.chdir(tmp_path)
    tracing.enable()
    try:
        with tracing.span("dump.probe"):
            pass
        p1 = tracing.dump()
        p2 = tracing.dump()
        assert p1 != p2
        assert os.path.exists(p1) and os.path.exists(p2)
        assert str(os.getpid()) in os.path.basename(p1)
    finally:
        tracing.disable()
        tracing.clear()


# ---------------------------------------------------------------------------
# histogram exemplars (ISSUE 14)
# ---------------------------------------------------------------------------


def test_histogram_exemplars_per_bucket(reg):
    h = reg.histogram("x.ex_seconds", buckets=[0.1, 1.0])
    h.observe(0.05, exemplar="fast-1", family="text")
    h.observe(0.5, exemplar="mid-1", family="text")
    h.observe(0.5, exemplar="mid-2", family="text")  # last-writer-wins
    h.observe(5.0, family="text")  # no exemplar: slot stays empty
    ex = h.exemplars(family="text")
    assert ex == {"le_0.1": "fast-1", "le_1.0": "mid-2"}
    # snapshot carries them (the dashboard read path)
    row = h.snapshot()["values"][0]
    assert row["exemplars"]["1.0"] == "mid-2"
    # label sets that never carried one stay exemplar-free
    h.observe(0.5, family="map")
    assert h.exemplars(family="map") == {}


# ---------------------------------------------------------------------------
# flight recorder (ISSUE 14): bounded ring + the count-based perf guards
# ---------------------------------------------------------------------------


def _fresh_flight(cap=16):
    from loro_tpu.obs.flight import FlightRecorder

    return FlightRecorder(capacity=cap)


def test_flight_ring_bounded_and_ordered():
    fr = _fresh_flight(cap=8)
    for i in range(20):
        fr.record("probe", n=i)
    evs = fr.events()
    assert len(evs) == 8  # bounded by capacity, oldest overwritten
    assert [e["n"] for e in evs] == list(range(12, 20))
    assert [e["i"] for e in evs] == list(range(12, 20))
    assert fr.recorded_total == 20
    assert fr.tail(3) == evs[-3:]


def test_flight_disabled_path_zero_net_allocations():
    """The count-based perf guard: with the recorder disabled, a
    record() call allocates nothing that survives the call — the ring
    must be leavable ON in production with a literal no-op off switch."""
    import gc

    fr = _fresh_flight(cap=64)
    fr.disable()
    fr.record("warm", a=1)  # warm any lazy state
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(10_000):
        fr.record("probe", a=1, b="x")
    gc.collect()
    grew = sys.getallocatedblocks() - before
    assert grew <= 16, f"disabled flight path leaked {grew} blocks"
    assert fr.events() == [] and fr.recorded_total == 0


def test_flight_enabled_path_bounded_by_capacity():
    """Enabled-path guard: memory is bounded by the ring — 50x the
    capacity in events retains exactly `capacity` and the block count
    plateaus instead of growing with the event count."""
    import gc

    fr = _fresh_flight(cap=32)
    for i in range(64):  # fill + wrap once: steady state
        fr.record("probe", n=i)
    gc.collect()
    before = sys.getallocatedblocks()
    for i in range(32 * 50):
        fr.record("probe", n=i)
    gc.collect()
    grew = sys.getallocatedblocks() - before
    assert grew <= 64, f"flight ring grew {grew} blocks past capacity"
    assert len(fr.events()) == 32


def test_flight_reentrant_record_is_dropped():
    fr = _fresh_flight(cap=8)
    fr._guard.held = True
    try:
        fr.record("nested")
    finally:
        fr._guard.held = False
    assert fr.recorded_total == 0


def test_flight_snapshot_and_dump(tmp_path):
    fr = _fresh_flight(cap=8)
    fr.record("alpha", x=1)
    snap = fr.snapshot()
    assert snap["flight"] == 1 and snap["capacity"] == 8
    assert snap["events"][0]["kind"] == "alpha"
    path = fr.dump(str(tmp_path / "f.json"))
    assert json.load(open(path))["events"][0]["x"] == 1


def test_flight_cap_knob_typed_at_first_use(monkeypatch):
    """LORO_FLIGHT_CAP=abc must raise typed ConfigError at the first
    recorder() use (the knob convention) — and importing the package
    must never crash on it (the default recorder builds lazily)."""
    from loro_tpu import obs as obs_pkg  # import survives a bad knob
    from loro_tpu.errors import ConfigError
    from loro_tpu.obs import flight

    assert obs_pkg.flight is flight
    monkeypatch.setenv("LORO_FLIGHT_CAP", "abc")
    monkeypatch.setattr(flight, "_default", None)
    with pytest.raises(ConfigError, match="LORO_FLIGHT_CAP"):
        flight.recorder()
    monkeypatch.setenv("LORO_FLIGHT_CAP", "64")
    assert flight.recorder().capacity == 64
    monkeypatch.setattr(flight, "_default", None)  # next test rebuilds


def test_flight_dump_on_gated_by_auto_dir(tmp_path):
    from loro_tpu.obs import flight

    flight.set_auto_dump(None)
    try:
        assert flight.dump_on("test_disarmed") is None
        flight.set_auto_dump(str(tmp_path / "bb"))
        p = flight.dump_on("test_armed")
        assert p is not None and os.path.exists(p)
        art = json.load(open(p))
        assert any(e.get("kind") == "flight.trigger" and
                   e.get("reason") == "test_armed"
                   for e in art["events"])
    finally:
        flight.set_auto_dump(None)


def test_degradation_records_flight_event():
    from loro_tpu.obs import flight
    from loro_tpu.resilience.supervisor import DeviceSupervisor

    sup = DeviceSupervisor()
    n0 = len([e for e in flight.events() if e["kind"] == "sup.degrade"])
    sup.note_degradation("test.site")
    evs = [e for e in flight.events() if e["kind"] == "sup.degrade"]
    assert len(evs) == n0 + 1
    assert evs[-1]["where"] == "test.site"


# ---------------------------------------------------------------------------
# CLI coverage (ISSUE 14 satellite): obs.report and obs.trace
# ---------------------------------------------------------------------------


class TestReportCli:
    def test_live_registry_mode(self, capsys):
        from loro_tpu.obs import report

        obs.counter("fleet.ops_merged_total").inc(5, family="text")
        rc = report.main([])
        out = capsys.readouterr().out
        assert rc == 0
        assert "loro_tpu.obs" in out and "fleet.ops_merged_total" in out

    def test_snapshot_file_mode(self, tmp_path, capsys):
        from loro_tpu.obs import report
        from loro_tpu.obs.exposition import snapshot_json

        reg = m.Registry()
        reg.counter("fleet.ops_merged_total", "rows").inc(7, family="map")
        reg.histogram("server.epoch_seconds", buckets=[1.0]).observe(0.2)
        p = tmp_path / "snap.json"
        p.write_text(snapshot_json(reg))
        rc = report.main([str(p)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fleet.ops_merged_total" in out
        assert "server.epoch_seconds" in out
        # JSON mode round-trip: the written snapshot is schema-stable
        snap = json.loads(p.read_text())
        e = snap["fleet.ops_merged_total"]
        assert e["type"] == "counter"
        assert e["values"][0]["labels"] == {"family": "map"}
        assert e["values"][0]["value"] == 7


class TestTraceCli:
    def _flight_file(self, tmp_path, name="f.json"):
        from loro_tpu.obs.flight import FlightRecorder

        fr = FlightRecorder(capacity=16)
        fr.record("server.epoch", family="text", epoch=3, trace="t-x")
        fr.record("repl.apply", epoch=3, trace="t-x", lag_ms=4.2)
        return fr.dump(str(tmp_path / name))

    def test_inspect_flight(self, tmp_path, capsys):
        from loro_tpu.obs import trace as tcli

        p = self._flight_file(tmp_path)
        rc = tcli.main(["inspect", p])
        out = capsys.readouterr().out
        assert rc == 0
        assert "flight" in out and "repl.apply" in out

    def test_inspect_chrome(self, tmp_path, capsys):
        from loro_tpu.obs import trace as tcli

        tracing.enable()
        try:
            with tracing.span("cli.probe"):
                pass
            p = tracing.dump(str(tmp_path / "t.json"))
        finally:
            tracing.disable()
            tracing.clear()
        rc = tcli.main(["inspect", p])
        out = capsys.readouterr().out
        assert rc == 0 and "cli.probe" in out

    def test_merge_lag_attribution(self, tmp_path, capsys):
        from loro_tpu.obs import trace as tcli

        p = self._flight_file(tmp_path)
        out_path = str(tmp_path / "merged.json")
        rc = tcli.main(["merge", p, p, "-o", out_path])
        out = capsys.readouterr().out
        assert rc == 0
        assert "replication-lag attribution" in out
        assert "epoch 3" in out
        merged = json.load(open(out_path))
        assert {e["pid"] for e in merged["traceEvents"]} == {1, 2}

    def test_rounds_accounts_for_each_round_by_self_time(self, tmp_path, capsys):
        """ISSUE 37: a hand-made dump of three rounds (ms): a plain one, one
        whose journal has a child and whose supervisor drained, and one that
        holds a round of its own (a column, not a line)."""
        from loro_tpu.obs import trace as tcli

        def x(name, span, parent, ts_ms, dur_ms, tid=7, **args):
            return {"name": name, "ph": "X", "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
                    "pid": 1, "tid": tid,
                    "args": {"span": span, "parent": parent, "trace": None, **args}}

        def pause(ts_ms, ms, tid=7):
            return {"name": "gc.pause", "ph": "i", "s": "t", "ts": ts_ms * 1e3,
                    "pid": 1, "tid": tid,
                    "args": {"gen": 2, "collected": 0, "ns": int(ms * 1e6)}}

        events = [
            x("server.ingest", 1, 0, 0, 100, docs=16), x("resident.decode", 2, 1, 10, 50),
            x("resident.commit_ids", 3, 1, 60, 30),
            x("server.ingest", 4, 0, 200, 160, docs=16), x("resident.decode", 5, 4, 200, 60),
            x("server.journal", 6, 4, 270, 40), x("wal.write", 7, 6, 280, 25),
            x("sup.drain", 8, 4, 320, 35, label="server.ingest.text"),
            x("server.fsync", 9, 0, 365, 5),  # the caller's flush: no round's
            x("server.ingest", 10, 0, 400, 50, docs=1), x("server.ingest", 11, 10, 410, 30, docs=1),
            x("resident.decode", 12, 11, 415, 20),
            {"name": "server.epoch", "ph": "i", "s": "t", "ts": 5e3, "pid": 1, "tid": 7,
             "args": {"span": 13, "parent": 1, "trace": None}},
            pause(20, 4), pause(90, 1), pause(95, 2, tid=8),  # another thread's
            pause(150, 9), pause(330, 3),
        ]
        for e, trace in zip((events[0], events[3], events[9]), "abc"):
            e["args"]["trace"] = trace
        path = tmp_path / "rounds.json"
        path.write_text(json.dumps({"traceEvents": events}))
        rows = tcli.round_rows(tcli.load_artifact(str(path)))
        assert [(r["trace"], r["docs"], r["ms"], r["gc_ms"]) for r in rows] == [
            ("a", 16, 100.0, 5.0), ("b", 16, 160.0, 3.0), ("c", 1, 50.0, 0.0)]
        assert rows[0]["cols"] == {"unnamed": 20.0, "resident.decode": 50.0,
                                   "resident.commit_ids": 30.0}
        assert rows[1]["cols"] == {"unnamed": 25.0, "resident.decode": 60.0,
                                   "server.journal": 15.0, "wal.write": 25.0,
                                   "sup.drain": 35.0}
        assert rows[2]["cols"] == {"unnamed": 20.0, "server.ingest": 10.0,
                                   "resident.decode": 20.0}
        assert tcli.main(["rounds", str(path)]) == 0
        out = capsys.readouterr().out.splitlines()
        assert [ln.split()[0] for ln in out] == ["trace", "a", "b", "c", "median",
                                                 "max", "longest:"]
        # the costliest column first (median, then worst round), `unnamed` last
        assert out[0].split()[2:] == [
            "ms", "resident.decode", "sup.drain", "resident.commit_ids", "wal.write",
            "server.journal", "server.ingest", "unnamed", "|", "gc.pause"]
        assert out[-1] == ("longest: b 160.00 ms, +60.00 over the median; "
                           "sup.drain holds +35.00 of it")
        # a flight snapshot has no spans; neither has a trace of no round
        assert tcli.main(["rounds", self._flight_file(tmp_path)]) == 2
        path.write_text(json.dumps({"traceEvents": events[1:3]}))
        assert tcli.main(["rounds", str(path)]) == 2
        assert tcli.main(["rounds"]) == 2
        assert capsys.readouterr().err.count("obs.trace:") == 3

    def test_malformed_artifact_rc2(self, tmp_path, capsys):
        from loro_tpu.obs import trace as tcli

        bad = tmp_path / "bad.json"
        bad.write_text('{"neither": 1}')
        rc = tcli.main(["inspect", str(bad)])
        err = capsys.readouterr().err
        assert rc == 2 and "obs.trace:" in err
        rc = tcli.main(["inspect", str(tmp_path / "missing.json")])
        assert rc == 2

    def test_help_and_unknown(self, capsys):
        from loro_tpu.obs import trace as tcli

        assert tcli.main([]) == 0
        assert "Subcommands" in capsys.readouterr().out
        assert tcli.main(["wat"]) == 2

    def test_dump_subcommand(self, tmp_path, capsys):
        from loro_tpu.obs import trace as tcli

        p = str(tmp_path / "proc.json")
        rc = tcli.main(["dump", p])
        out = capsys.readouterr().out
        assert rc == 0 and p in out
        assert json.load(open(p))["flight"] == 1
