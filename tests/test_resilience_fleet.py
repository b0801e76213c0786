"""Integration: fault-injected device failures on the 8-device CPU mesh.

Acceptance contract (ISSUE 3): every injected fault class — launch
raise (transient and fatal), slow fetch, truncated codec bytes, poison
doc — ends in either a host-fallback result byte-identical to the host
oracle or a typed error; never a hang, never an uncaught exception."""
import pytest

from loro_tpu import LoroDoc
from loro_tpu.doc import strip_envelope
from loro_tpu.errors import DeviceFailure
from loro_tpu.obs import metrics as obs
from loro_tpu.parallel.fleet import Fleet
from loro_tpu.parallel.server import ResidentServer
from loro_tpu.resilience import DeviceSupervisor, faultinject, set_supervisor


@pytest.fixture
def fake_sleep_supervisor():
    """Process supervisor with a recording no-wall-clock sleeper (the
    injected transient retries must not wall-sleep in tier-1)."""
    sleeps = []
    sup = DeviceSupervisor(sleep=sleeps.append)
    set_supervisor(sup)
    yield sup, sleeps
    set_supervisor(None)


def _fatal(site="launch", times=1):
    return faultinject.inject(
        site, exc=RuntimeError("INTERNAL: injected device death"), times=times
    )


def _mk_pair(family, i=0):
    """One two-peer doc pair seeded + concurrently edited on `family`'s
    container, fully synced (a is the host oracle)."""
    a, b = LoroDoc(peer=700 + 2 * i), LoroDoc(peer=701 + 2 * i)
    if family in ("text", "richtext"):
        a.get_text("t").insert(0, "base text")
    elif family == "map":
        a.get_map("m").set("k", 1)
    elif family == "tree":
        a.get_tree("tr").create()
    elif family == "movable":
        a.get_movable_list("ml").push("a", "b")
    elif family == "counter":
        a.get_counter("c").increment(3)
    a.commit()
    b.import_(a.export_snapshot())
    _edit(family, a, salt=1)
    _edit(family, b, salt=2)
    a.import_(b.export_updates(a.oplog_vv()))
    b.import_(a.export_updates(b.oplog_vv()))
    assert a.get_deep_value() == b.get_deep_value()
    return a, b


def _edit(family, d, salt):
    if family == "text":
        d.get_text("t").insert(salt, f"p{salt}")
    elif family == "richtext":
        t = d.get_text("t")
        t.insert(salt, f"p{salt}")
        t.mark(0, 4 + salt, "bold", True if salt % 2 else None)
    elif family == "map":
        d.get_map("m").set(f"k{salt}", salt * 10)
    elif family == "tree":
        tr = d.get_tree("tr")
        n = tr.create(tr.nodes()[0] if tr.nodes() else None)
        if len(tr.nodes()) >= 2:
            tr.move(n, tr.nodes()[0])
    elif family == "movable":
        ml = d.get_movable_list("ml")
        ml.insert(salt % (len(ml) + 1), f"v{salt}")
        if len(ml) >= 2:
            ml.set(0, f"w{salt}")
    elif family == "counter":
        d.get_counter("c").increment(salt * 7)
    d.commit()


def _oracle(family, a):
    if family == "text":
        return a.get_text("t").to_string()
    if family == "richtext":
        return a.get_text("t").get_richtext_value()
    if family == "map":
        return a.get_map("m").get_value()
    if family == "tree":
        tr = a.get_tree("tr")
        return {x: tr.parent(x) for x in tr.nodes()}
    if family == "movable":
        return a.get_movable_list("ml").get_value()
    if family == "counter":
        c = a.get_counter("c")
        return {c.id: float(c.get_value())}
    raise AssertionError(family)


def _fleet_merge(fleet, family, changes, a):
    if family == "text":
        cid = a.get_text("t").id
        return fleet.merge_text_changes([changes], cid).texts[0]
    if family == "richtext":
        return fleet.merge_richtext_changes([changes], a.get_text("t").id)[0]
    if family == "tree":
        return fleet.merge_tree_changes([changes], a.get_tree("tr").id)[0]
    if family == "movable":
        return fleet.merge_movable_changes([changes], a.get_movable_list("ml").id)[0]
    if family == "counter":
        return fleet.merge_counter_changes([changes])[0]
    raise AssertionError(family)


FLEET_FAMILIES = ["text", "richtext", "tree", "movable", "counter"]


@pytest.mark.faultinject
class TestFleetDegradation:
    @pytest.mark.parametrize("family", FLEET_FAMILIES)
    def test_fatal_launch_degrades_to_host_oracle(self, family,
                                                  fake_sleep_supervisor):
        a, _ = _mk_pair(family)
        changes = a.oplog.changes_in_causal_order()
        fleet = Fleet()
        want = _oracle(family, a)
        # clean run first: device result IS the oracle
        assert _fleet_merge(fleet, family, changes, a) == want
        n0 = obs.counter("fleet.degraded_merges_total").get(family=family)
        _fatal(times=1)
        try:
            got = _fleet_merge(fleet, family, changes, a)
        finally:
            faultinject.clear()
        assert got == want  # host fallback, byte-identical
        assert obs.counter("fleet.degraded_merges_total").get(family=family) == n0 + 1

    def test_transient_launch_retries_on_device(self, fake_sleep_supervisor):
        sup, sleeps = fake_sleep_supervisor
        a, _ = _mk_pair("text", i=3)
        changes = a.oplog.changes_in_causal_order()
        fleet = Fleet()
        n0 = obs.counter("fleet.degraded_merges_total").get(family="text")
        faultinject.inject("launch", times=2)  # default transient UNAVAILABLE
        try:
            got = fleet.merge_text_changes([changes], a.get_text("t").id)
        finally:
            faultinject.clear()
        assert got.texts[0] == a.get_text("t").to_string()
        assert len(sleeps) == 2  # backoff rode the fake sleeper
        assert sup.report()["retries"] == 2
        # retried on DEVICE — no degradation
        assert obs.counter("fleet.degraded_merges_total").get(family="text") == n0

    def test_device_error_at_fetch_degrades(self, fake_sleep_supervisor):
        """A failure surfacing at the result fetch (the realistic async
        failure mode) takes the same host-degradation path as a launch
        failure."""
        a, _ = _mk_pair("text", i=14)
        fleet = Fleet()
        n0 = obs.counter("fleet.degraded_merges_total").get(family="text")
        faultinject.inject("fetch", exc=OSError("link dropped at fetch"),
                           times=1)
        try:
            got = fleet.merge_text_changes(
                [a.oplog.changes_in_causal_order()], a.get_text("t").id
            )
        finally:
            faultinject.clear()
        assert got.texts[0] == a.get_text("t").to_string()
        assert obs.counter("fleet.degraded_merges_total").get(family="text") == n0 + 1

    def test_slow_fetch_delays_but_completes(self, fake_sleep_supervisor):
        slept = []
        faultinject.set_sleep(slept.append)
        faultinject.inject("fetch", action="delay", delay_s=2.0, times=1)
        a, _ = _mk_pair("text", i=4)
        fleet = Fleet()
        try:
            got = fleet.merge_text_changes(
                [a.oplog.changes_in_causal_order()], a.get_text("t").id
            )
        finally:
            faultinject.clear()
            faultinject.set_sleep(None)
        assert got.texts[0] == a.get_text("t").to_string()
        assert slept == [2.0]

    def test_payload_merge_degrades_via_decoded_changes(self,
                                                        fake_sleep_supervisor):
        a, _ = _mk_pair("text", i=5)
        payload = strip_envelope(a.export_updates({}))
        fleet = Fleet()
        _fatal(times=1)
        try:
            got = fleet.merge_text_payloads([payload], a.get_text("t").id)
        finally:
            faultinject.clear()
        assert got.texts[0] == a.get_text("t").to_string()


@pytest.mark.faultinject
class TestResidentPoisonIsolation:
    def test_one_poison_doc_isolates(self, fake_sleep_supervisor):
        """A round where doc 1's payload is corrupt: doc 0 commits,
        doc 1 is skipped with a typed record + obs counter — the epoch
        never raises and never poisons doc 0's state."""
        a0, _ = _mk_pair("text", i=6)
        a1, _ = _mk_pair("text", i=7)
        cid = a0.get_text("t").id
        srv = ResidentServer("text", 2, capacity=1 << 12)
        n0 = obs.counter("server.poison_docs_total").get(family="text")
        faultinject.inject("poison_doc", action="truncate", keep_bytes=3,
                           docs=[1], times=1)
        try:
            srv.ingest(
                [strip_envelope(a0.export_updates({})),
                 strip_envelope(a1.export_updates({}))],
                cid,
            )
        finally:
            faultinject.clear()
        assert srv.texts()[0] == a0.get_text("t").to_string()
        assert srv.last_poison_docs == [1]
        assert obs.counter("server.poison_docs_total").get(family="text") == n0 + 1
        assert not srv.degraded

    def test_all_poison_round_is_typed_not_raised(self, fake_sleep_supervisor):
        a, _ = _mk_pair("text", i=8)
        srv = ResidentServer("text", 1, capacity=1 << 12)
        payload = strip_envelope(a.export_updates({}))
        srv.ingest([payload[:3]], a.get_text("t").id)  # corrupt: no raise
        assert srv.last_poison_docs == [0]
        assert srv.texts() == [""]  # state untouched

    def test_mixed_round_poison_bytes_isolates(self, fake_sleep_supervisor):
        """Regression (review finding): poison bytes in a MIXED
        bytes+changes round must isolate to that doc during the
        normalization decode, not raise CodecDecodeError for the whole
        round."""
        a0, _ = _mk_pair("text", i=9)
        a1, _ = _mk_pair("text", i=12)
        cid = a0.get_text("t").id
        srv = ResidentServer("text", 2, capacity=1 << 12)
        n0 = obs.counter("server.poison_docs_total").get(family="text")
        srv.ingest(
            [a0.oplog.changes_in_causal_order(),
             strip_envelope(a1.export_updates({}))[:5]],  # poison bytes
            cid,
        )
        assert srv.texts()[0] == a0.get_text("t").to_string()
        assert srv.last_poison_docs == [1]
        assert obs.counter("server.poison_docs_total").get(family="text") == n0 + 1

    def test_capacity_config_error_surfaces(self, fake_sleep_supervisor):
        """Review finding: a host-side config error (capacity exceeded,
        auto_grow=False) must raise verbatim — not degrade, not be
        misread as poison."""
        a, _ = _mk_pair("text", i=13)
        srv = ResidentServer("text", 1, capacity=8, auto_grow=False)
        with pytest.raises(RuntimeError, match="auto_grow"):
            srv.ingest([a.oplog.changes_in_causal_order()], a.get_text("t").id)
        assert not srv.degraded
        assert srv.last_poison_docs == []


SERVER_FAMILIES = ["text", "map", "tree", "movable", "counter"]

_SRV_KW = {
    "text": dict(capacity=1 << 12),
    "map": dict(slot_capacity=128),
    "tree": dict(move_capacity=1 << 10, node_capacity=256),
    "movable": dict(capacity=1 << 10, elem_capacity=256),
    "counter": dict(slot_capacity=32),
}


def _srv_cid(family, a):
    if family == "text":
        return a.get_text("t").id
    if family == "tree":
        return a.get_tree("tr").id
    if family == "movable":
        return a.get_movable_list("ml").id
    return None  # map / counter fold every container


def _srv_read(srv, family, a):
    if family == "text":
        return srv.texts()[0]
    if family == "map":
        return srv.root_value_maps("m")[0]
    if family == "tree":
        return srv.parent_maps()[0]
    if family == "movable":
        return srv.value_lists()[0]
    c = a.get_counter("c")
    return {c.id: srv.value_maps()[0].get(c.id, 0.0)}


@pytest.mark.faultinject
class TestResidentDegradationAndRecovery:
    @pytest.mark.parametrize("family", SERVER_FAMILIES)
    def test_checkpoint_restore_roundtrip_under_midepoch_failure(
        self, family, fake_sleep_supervisor
    ):
        """Satellite 3: epoch 1 on device, checkpoint, injected device
        failure in epoch 2 -> transparent host degradation (reads match
        the host oracle), then restore()+replay of epoch 2 on a fresh
        device batch matches the same oracle."""
        a, b = _mk_pair(family, i=10)
        cid = _srv_cid(family, a)
        srv = ResidentServer(family, 1, **_SRV_KW[family])
        mark = a.oplog_vv()
        srv.ingest([a.oplog.changes_in_causal_order()], cid)
        assert _srv_read(srv, family, a) == _oracle(
            "text" if family == "text" else family, a
        )
        ckpt = srv.checkpoint()
        # epoch 2: fresh concurrent edits, synced
        _edit(family, a, salt=3)
        _edit(family, b, salt=4)
        a.import_(b.export_updates(a.oplog_vv()))
        b.import_(a.export_updates(b.oplog_vv()))
        ups2 = a.oplog.changes_between(mark, a.oplog_vv())
        want2 = _oracle(family, a)
        epoch_before = srv.epoch
        _fatal(times=1)
        try:
            srv.ingest([ups2], cid)
        finally:
            faultinject.clear()
        # degraded: host mirror serves the epoch, byte-identical
        assert srv.degraded
        assert _srv_read(srv, family, a) == want2
        assert srv.epoch > epoch_before  # clients keep acking
        # recovery path A: restore the pre-failure checkpoint and
        # replay epoch 2 on a fresh device batch
        srv2 = ResidentServer.restore(ckpt)
        srv2.ingest([ups2], cid)
        assert not srv2.degraded
        assert _srv_read(srv2, family, a) == want2
        # recovery path B: recover() in place (journal replay)
        assert srv.recover()
        assert not srv.degraded
        assert _srv_read(srv, family, a) == want2

    def test_degraded_server_keeps_ingesting(self, fake_sleep_supervisor):
        a, b = _mk_pair("text", i=20)
        cid = a.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        mark = a.oplog_vv()
        srv.ingest([a.oplog.changes_in_causal_order()], cid)
        n0 = obs.counter("server.degraded_rounds_total").get(family="text")
        _edit("text", a, salt=5)
        a.commit()
        ups2 = a.oplog.changes_between(mark, a.oplog_vv())
        mark = a.oplog_vv()
        _fatal(times=1)
        try:
            srv.ingest([ups2], cid)
        finally:
            faultinject.clear()
        assert srv.degraded
        # subsequent epochs ride the host engine transparently
        _edit("text", a, salt=6)
        a.commit()
        ups3 = a.oplog.changes_between(mark, a.oplog_vv())
        srv.ingest([ups3], cid)
        assert srv.texts()[0] == a.get_text("t").to_string()
        assert obs.counter("server.degraded_rounds_total").get(
            family="text") == n0 + 2
        # regression (journal aliasing): the producing doc's oplog
        # extends live Change objects in place (change RLE), so the
        # journal must freeze rounds at record time — recover() replay
        # must NOT double-apply the delta epochs
        epoch_degraded = srv.epoch
        assert srv.recover()
        assert not srv.degraded
        # visible epoch never regresses across recovery (clients acked
        # the degraded epochs; compact() translates via the offset)
        assert srv.epoch >= epoch_degraded
        assert srv.texts()[0] == a.get_text("t").to_string()
        assert srv.batch.texts()[0] == a.get_text("t").to_string()
        # the offset survives checkpoint()/restore() (state v2)
        srv2 = ResidentServer.restore(srv.checkpoint())
        assert srv2.epoch == srv.epoch
        # auto-checkpoint was taken before the first (risky) launch
        assert srv.last_checkpoint is not None
        restored = ResidentServer.restore(srv.last_checkpoint)
        assert restored.texts() == [""]  # pre-first-epoch state

    def test_restored_server_degrades_via_anchor(self, fake_sleep_supervisor):
        """A v3 checkpoint embeds the shallow-snapshot mirror anchor
        (persist.MirrorAnchor), so a restore()d server degrades to a
        CORRECT host mirror — anchor state + post-restore journal —
        and recover()s in place (the checkpoint also carries the
        construction caps)."""
        a, _ = _mk_pair("text", i=21)
        cid = a.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        srv.ingest([a.oplog.changes_in_causal_order()], cid)
        mark = a.oplog_vv()
        srv2 = ResidentServer.restore(srv.checkpoint())
        _edit("text", a, salt=7)
        a.commit()
        _fatal(times=1)
        try:
            srv2.ingest([a.oplog.changes_between(mark, a.oplog_vv())], cid)
        finally:
            faultinject.clear()
        assert srv2.degraded
        assert srv2.texts()[0] == a.get_text("t").to_string()
        # bounded recover(): checkpoint batch state + journal tail
        assert srv2.recover()
        assert not srv2.degraded
        assert srv2.texts()[0] == a.get_text("t").to_string()

    def test_coalesced_group_failure_degrades_with_staged_rounds(
        self, fake_sleep_supervisor
    ):
        """Satellite (ISSUE 5): a device failure on coalesced group N
        while group N+1 is already staged degrades cleanly — the host
        mirror answers, and BOTH groups' rounds replay in order (group
        N via the degradation mirror seed, group N+1 via the
        degraded-replay commit), byte-identical to the oracle."""
        a, _ = _mk_pair("text", i=30)
        cid = a.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        mark = a.oplog_vv()
        rounds = [[strip_envelope(a.export_updates({}))]]
        for s in range(5):
            a.get_text("t").insert(0, f"g{s} ")
            a.commit()
            rounds.append([strip_envelope(a.export_updates(mark))])
            mark = a.oplog_vv()
        want = a.get_text("t").to_string()
        n0 = obs.counter("server.degraded_rounds_total").get(family="text")
        ex = srv.pipeline(cid=cid, coalesce=3, depth=2)
        _fatal(times=1)  # first supervised launch = group 1's commit
        try:
            prs = [ex.submit(list(r)) for r in rounds]
            ex.flush()
        finally:
            faultinject.clear()
        epochs = [p.epoch() for p in prs]
        assert epochs == sorted(epochs)  # per-round acks stay monotone
        assert srv.degraded
        assert srv.texts()[0] == want  # every staged round replayed
        assert obs.counter("server.degraded_rounds_total").get(
            family="text") == n0 + len(rounds)
        ex.close()
        # in-place recovery replays the journal back onto a device batch
        assert srv.recover()
        assert not srv.degraded
        assert srv.batch.texts()[0] == want

    def test_coalesced_poison_round_isolates(self, fake_sleep_supervisor):
        """A poison round INSIDE a coalesced group: earlier rounds
        commit as one group, the poison round isolates per doc (typed
        record, no raise), later rounds still apply — and the device
        never degrades."""
        a, _ = _mk_pair("text", i=31)
        cid = a.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12)
        mark = a.oplog_vv()
        good1 = [strip_envelope(a.export_updates({}))]
        poison = [b"\x07garbage-not-a-payload"]  # undecodable round
        a.get_text("t").insert(0, "kept ")
        a.commit()
        good2 = [strip_envelope(a.export_updates(mark))]
        n0 = obs.counter("server.poison_docs_total").get(family="text")
        epochs = srv.ingest_coalesced([good1, poison, good2], cid)
        assert len(epochs) == 3
        assert not srv.degraded
        assert srv.last_poison_docs == [0]
        assert obs.counter("server.poison_docs_total").get(
            family="text") == n0 + 1
        # the poison round's delta (salt=40) is lost with its bytes;
        # good1 + good2 applied — mirror that on a fresh oracle server
        oracle = ResidentServer("text", 1, capacity=1 << 12)
        oracle.ingest(good1, cid)
        oracle.ingest(good2, cid)
        assert srv.texts() == oracle.texts()

    def test_restored_server_without_anchor_is_typed(self,
                                                     fake_sleep_supervisor):
        """host_fallback=False servers embed no anchor: their restored
        form keeps the old contract — a device failure surfaces as a
        typed DeviceFailure, never a wrong host mirror."""
        a, _ = _mk_pair("text", i=22)
        cid = a.get_text("t").id
        srv = ResidentServer("text", 1, capacity=1 << 12, host_fallback=False)
        srv.ingest([a.oplog.changes_in_causal_order()], cid)
        srv2 = ResidentServer.restore(srv.checkpoint())
        _edit("text", a, salt=7)
        a.commit()
        _fatal(times=1)
        try:
            with pytest.raises(DeviceFailure):
                srv2.ingest([a.oplog.changes_in_causal_order()], cid)
        finally:
            faultinject.clear()
