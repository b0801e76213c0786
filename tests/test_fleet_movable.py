"""``Fleet``'s movable-list entries (PR 32): the batch's pads are powers
of two (the parent's: the ring is two tokens past one); a call whose
rings the device ranked ticks ``rank.ring_tokens`` under the rank the
one rule gives for that ring, a call that fails over to the host none; a
payload call records its stages as ``fleet.movable_*`` spans under one
trace id."""
import jax
import numpy as np
import pytest

from loro_tpu import LoroDoc
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.ops import fugue_batch as fb
from loro_tpu.ops import movable_batch, pallas_rank
from loro_tpu.parallel.fleet import Fleet, movable_pads
from loro_tpu.parallel.mesh import make_mesh
from loro_tpu.utils import tracing

# slots of the longest document -> (pad_s, the ring, the rank on a TPU)
BOUNDARIES = [
    (32_767, 32_768, 65_538, "pallas:ruling"),    # two past the packed kernels' last ring
    (32_768, 32_768, 65_538, "pallas:ruling"),
    (65_535, 65_536, 131_074, "xla:wyllie"),      # two past the wide kernel's last
    (65_536, 65_536, 131_074, "xla:wyllie"),
    (80_894, 131_072, 262_146, "xla:wyllie"),     # movable_import's documents
]


def _one_item_moved(n):
    """One item and ``n - 1`` moves of it by one peer, each slot the right
    child of the one before: ``(MovableCols, items, values)`` as the
    extractors give them."""
    rows = np.arange(n, dtype=np.int32)
    seq = fb.SeqColumns(
        parent=rows - 1, side=np.ones(n, np.int32), peer=np.zeros(n, np.int32),
        counter=rows, deleted=np.zeros(n, bool), content=np.zeros(n, np.int32),
        valid=np.ones(n, bool))
    one = np.zeros(1, np.int32)
    cols = movable_batch.MovableCols(
        seq=seq, lamport=rows, set_elem=one, set_lamport=one, set_peer=one,
        set_value=one, set_valid=np.ones(1, bool))
    return cols, [(1, 0)], ["the item"]


@pytest.mark.parametrize("slots,pad_s,ring,on_tpu", BOUNDARIES)
def test_movable_pads_are_powers_of_two_and_the_entry_ticks_its_rank(
        monkeypatch, slots, pad_s, ring, on_tpu):
    assert movable_pads(slots, 21_000, 1_000) == (pad_s, 32_768, 1_024)
    assert fb.rank_bound(pad_s) == ring and pad_s & (pad_s - 1) == 0
    # the rule, from the platform and the ring alone
    assert ":".join(fb._resolve_rank_spec(None, ring)) == "xla:wyllie"  # off the chip
    monkeypatch.setattr(pallas_rank, "use_pallas_rank", lambda: True)
    assert ":".join(fb._resolve_rank_spec(None, ring)) == on_tpu
    # the entry's accounting at that size, the launch itself stood in for
    # (a compile a boundary is not this test's to pay)
    launched = []

    def stand_in(cols, n_elems):
        launched.append(cols.seq.parent.shape)
        d = cols.seq.parent.shape[0]
        return np.zeros((d, pad_s), np.int32), np.ones(d, np.int32)

    monkeypatch.setattr(movable_batch, "movable_merge_batch", stand_in)
    ranked = obs.counter("rank.ring_tokens")
    before, total = ranked.get(algo=on_tpu), ranked.total()
    got = Fleet(make_mesh(jax.devices()[:1]))._merge_movable_extracted(
        [_one_item_moved(slots), _one_item_moved(5)])
    assert got == [["the item"], ["the item"]] and launched == [(2, pad_s)]
    assert ranked.get(algo=on_tpu) - before == ranked.total() - total == 2 * ring


def test_small_batches_keep_the_floor():
    assert movable_pads(0, 0, 0) == (64, 16, 16)
    assert movable_pads(64, 16, 16) == (64, 16, 16)
    assert movable_pads(65, 17, 17) == (128, 32, 32)


@pytest.mark.faultinject
def test_a_call_that_fails_over_to_the_host_ticks_no_ring():
    from loro_tpu.resilience import DeviceSupervisor, faultinject, set_supervisor

    doc, want = _board(0)
    changes = doc.oplog.changes_in_causal_order()
    cid = doc.get_movable_list("ml").id
    fleet = Fleet(make_mesh(jax.devices()[:1]))
    ranked = obs.counter("rank.ring_tokens")
    assert fleet.merge_movable_changes([changes], cid) == [want]  # warm, sound
    before = ranked.total()
    set_supervisor(DeviceSupervisor(sleep=lambda s: None))
    try:
        faultinject.inject("launch", exc=RuntimeError("INTERNAL: injected device death"),
                           times=1)
        assert fleet.merge_movable_changes([changes], cid) == [want]  # the host's answer
    finally:
        faultinject.clear()
        set_supervisor(None)
    assert ranked.total() == before
    assert fleet.merge_movable_changes([changes], cid) == [want]
    assert ranked.total() - before == 130  # one document, 64 slots: 2 * (64 + 1)


def _board(i):
    doc = LoroDoc(peer=i + 1)
    ml = doc.get_movable_list("ml")
    ml.push(*[f"card {j}" for j in range(6 + i)])
    ml.move(0, 3)
    ml.set(1, "edited")
    ml.move(4, 0)
    doc.commit()
    return doc, ml.get_value()


def test_a_payload_call_ticks_its_ring_and_records_its_stages_under_one_trace_id():
    docs = [_board(i) for i in range(3)]
    payloads = [strip_envelope(d.export_updates({})) for d, _want in docs]
    cid = docs[0][0].get_movable_list("ml").id
    fleet = Fleet(make_mesh(jax.devices()[:1]))
    want = [w for _d, w in docs]
    assert fleet.merge_movable_payloads(payloads, cid) == want  # warm
    ranked, tasks = obs.counter("rank.ring_tokens"), obs.counter("fleet.decode_tasks_total")
    r0, t0 = ranked.get(algo="xla:wyllie"), tasks.get(family="movable")
    tracing.clear()
    tracing.enable()
    try:
        assert fleet.merge_movable_payloads(payloads, cid) == want
    finally:
        tracing.disable()
    # 6-8 items + 2 moves pad to 64 slots: a ring of 130 a document
    assert ranked.get(algo="xla:wyllie") - r0 == 3 * 130
    assert tasks.get(family="movable") - t0 == 3
    spans = [e for e in tracing.events() if "span_id" in e]
    tracing.clear()
    per = [e for e in spans if e["name"] == "fleet.merge_movable_payloads"]
    assert len(per) == 1 and per[0]["trace_id"]
    stages = [e for e in spans if e["parent_id"] == per[0]["span_id"]]
    assert [e["name"] for e in stages] == [
        "fleet.movable_decode", "fleet.movable_stack", "fleet.movable_upload",
        "fleet.movable_launch", "fleet.movable_device_wait", "fleet.movable_fetch",
        "fleet.movable_values"]
    assert {e["trace_id"] for e in spans} == {per[0]["trace_id"]}
    assert sum(e["name"] == "fleet.movable_decode_one" for e in spans) == 3
    # the stages are the call: what lies between them is bookkeeping
    staged = sum(e["end_ns"] - e["start_ns"] for e in stages)
    assert staged <= per[0]["end_ns"] - per[0]["start_ns"]


def test_the_launch_names_its_stages():
    cols = jax.tree_util.tree_map(lambda a: a[None], _one_item_moved(63)[0])
    text = movable_batch.movable_merge_batch.lower(cols, 16).as_text(debug_info=True)
    for scope in ("movable_winners", "ring", "rank", "movable_place"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
