"""The public text entry launches the chain-contracted merge.

``Fleet.merge_text_payloads`` / ``merge_text_docs`` contract every
document's chains on the host, ship packed u8 rows (or plain
``ChainColumns`` when a chain bucket outgrows 16-bit chain ids) and
launch ``chain_merge_docs_packed``.  The answers must be those of the
uncontracted program (``ops.fugue_batch.merge_docs``, which left
``Fleet`` but stays as the differential reference) and of the host
engine (``LoroDoc``), character for character."""
import jax
import jax.monitoring
import numpy as np
import pytest

from loro_tpu import LoroDoc, native
from loro_tpu.core.ids import ContainerID, ContainerType
from loro_tpu.doc import strip_envelope
from loro_tpu.obs import metrics as obs
from loro_tpu.ops import fugue_batch as fb
from loro_tpu.ops.columnar import (
    SeqExtract,
    _contract_chains_numpy,
    chain_columns,
    contract_chains,
    extract_seq_from_payload,
    pack_chain_row,
)
from loro_tpu.parallel import fleet as fleet_mod
from loro_tpu.parallel.fleet import Fleet, text_pads, text_transport
from loro_tpu.parallel.mesh import make_mesh
from loro_tpu.resilience import DeviceSupervisor, faultinject, set_supervisor

CID = ContainerID.root("text", ContainerType.Text)


def _synced(*docs):
    for a in docs:
        a.commit()
    for a in docs:
        for b in docs:
            if a is not b:
                b.import_(a.export_updates(b.oplog_vv()))
    texts = {d.get_text("text").to_string() for d in docs}
    assert len(texts) == 1
    return docs[0]


def _typed(peer, text):
    d = LoroDoc(peer=peer)
    d.get_text("text").insert(0, text)
    d.commit()
    return d


def _fork(d, peer):
    f = LoroDoc(peer=peer)
    f.import_(d.export_snapshot())
    return f


def _empty_in_a_batch():
    return [_typed(11, "first document"), LoroDoc(peer=12), _typed(13, "third")]


def _no_contraction():
    """Every insert concurrent at one position: 24 replicas each put one
    character at position 0 of the empty text — every element a child of
    the root, no two of them a chain."""
    reps = [LoroDoc(peer=100 + i) for i in range(24)]
    for i, r in enumerate(reps):
        r.get_text("text").insert(0, chr(ord("a") + i))
    doc = _synced(*reps)
    ex = extract_seq_from_payload(strip_envelope(doc.export_updates({})), CID)
    assert contract_chains(ex).n_chains == ex.n == 24
    return [doc]


def _deletes_in_and_at_the_ends_of_chains():
    a = _typed(21, "0123456789" * 6)
    b = _fork(a, 22)
    t = a.get_text("text")
    t.delete(0, 3)  # the head of the chain
    t.delete(20, 5)  # inside it
    t.delete(len(t.to_string()) - 4, 4)  # its tail
    b.get_text("text").delete(10, 30)  # concurrent, overlapping
    b.get_text("text").insert(10, "kept")
    c = _typed(23, "x")
    c.get_text("text").delete(0, 1)  # a chain of one, all deleted
    return [_synced(a, b), c]


def _children_of_a_chain_middle():
    """Left- and right-side children hanging off the middle of a typed
    run: a replica that saw only "abc" types on after the c (a right
    child of c, beside the d that continues the run), another inserts
    between c and d once d is there (a left child of d), twice over."""
    a = _typed(31, "abc")
    b = _fork(a, 32)
    a.get_text("text").insert(3, "defghi")
    b.get_text("text").insert(3, "XY")
    doc = _synced(a, b)
    c, d = _fork(doc, 33), _fork(doc, 34)
    c.get_text("text").insert(3, "left")
    d.get_text("text").insert(3, "LEFT")
    d.get_text("text").insert(1, "!")
    return [_synced(doc, c, d)]


def _very_different_sizes():
    big = _typed(41, "lorem ipsum dolor sit amet " * 40)
    for i in range(0, 900, 90):
        big.get_text("text").insert(i, f"<{i}>")
    big.commit()
    return [_typed(42, "ab"), big, LoroDoc(peer=43), _typed(44, "c" * 70)]


SCENARIOS = {
    "empty_in_a_batch": _empty_in_a_batch,
    "no_contraction": _no_contraction,
    "deletes_in_and_at_ends": _deletes_in_and_at_the_ends_of_chains,
    "children_of_a_middle": _children_of_a_chain_middle,
    "very_different_sizes": _very_different_sizes,
}
_built = {}


def scenario(name):
    """(payloads, extracts, the host engine's texts), built once."""
    if name not in _built:
        docs = SCENARIOS[name]()
        payloads = [strip_envelope(d.export_updates({})) for d in docs]
        _built[name] = (
            payloads,
            [extract_seq_from_payload(p, CID) for p in payloads],
            [d.get_text("text").to_string() for d in docs],
        )
    return _built[name]


def uncontracted_texts(extracts):
    """``merge_docs``: the program ``Fleet`` launched before, every
    element ranked."""
    n = fb.pad_bucket(max(e.n for e in extracts))
    cols = [e.to_seq_columns(pad_to=n) for e in extracts]
    codes, counts = fb.merge_docs(fb.SeqColumns(
        *[np.stack([getattr(c, f) for c in cols]) for f in fb.SeqColumns._fields]))
    codes, counts = np.asarray(codes), np.asarray(counts)
    return ["".join(map(chr, codes[i, : counts[i]])) for i in range(len(extracts))]


MESHES = {"one_device": 1, "mesh_pads_the_doc_axis": 8}


@pytest.mark.parametrize("devices", MESHES.values(), ids=MESHES.keys())
@pytest.mark.parametrize("entry", ["merge_text_payloads", "merge_text_docs"])
@pytest.mark.parametrize("name", SCENARIOS)
def test_entry_equals_uncontracted_program_and_host(name, entry, devices):
    payloads, extracts, want = scenario(name)
    fleet = Fleet(make_mesh(jax.devices()[:devices]))
    assert len(payloads) % devices or devices == 1  # eight devices: padded
    if entry == "merge_text_payloads":
        got = fleet.merge_text_payloads(payloads, CID).texts
    else:
        got = fleet.merge_text_docs(extracts).texts
    assert got == want
    assert got == uncontracted_texts(extracts)


@pytest.mark.parametrize("pad_c,pad_n,want", [
    (63, 64, "packed"),
    (32_767, 262_144, "packed"),  # a B4-sized batch
    (0xFFFE, 64, "packed"),
    (0xFFFF, 64, "chains"),  # 0xFFFF is the root's parent in a packed row
    (0xFFFF, 1 << 20, "chains"),
    ((1 << 17) - 1, 1 << 17, "chains"),
])
def test_transport_is_a_pure_function_of_the_padded_sizes(pad_c, pad_n, want):
    assert text_transport(pad_c, pad_n) == want
    if want == "packed":
        assert fb.packed_row_bytes(pad_c, pad_n) == 8 * (pad_c + pad_n)


@pytest.mark.parametrize("chains,elements,want", [
    (0, 0, (63, 64)),
    (63, 64, (63, 64)),
    (64, 65, (127, 128)),
    (17_500, 182_315, (32_767, 262_144)),  # B4: ring 65,536, the packed kernels' last
    (32_767, 40_000, (32_767, 65_536)),
    (32_768, 40_000, (65_535, 65_536)),  # the first chain bucket past 16-bit ids
])
def test_pads_keep_the_ring_a_power_of_two(chains, elements, want):
    pad_c, pad_n = text_pads(chains, elements)
    assert (pad_c, pad_n) == want
    ring = fb.rank_bound(pad_c)
    assert ring & (ring - 1) == 0 and pad_c >= chains and pad_n >= elements


def _prepended(n):
    """One peer typing ``n`` characters, each at position 0: every
    element the left child of the one before (the first a right child
    of the root, as the wire has it), so nothing contracts."""
    rows = np.arange(n, dtype=np.int32)
    return SeqExtract(
        parent=rows - 1, side=(rows == 0).astype(np.int32), peer=np.zeros(n, np.int32),
        counter=rows, deleted=rows % 7 == 3, content=65 + rows % 26,
        valid=np.ones(n, bool), peers=[5])


def test_a_batch_past_sixteen_bit_chain_ids_travels_as_chain_columns():
    """40,000 chains pad to 65,535: the packed row cannot name them, so
    the same contraction ships plain ``ChainColumns`` — once, for real,
    beside a small document."""
    big, small = _prepended(40_000), scenario("children_of_a_middle")[1][0]
    assert contract_chains(big).n_chains == 40_000
    assert text_transport(*text_pads(40_000, 40_000)) == "chains"
    by_transport = obs.counter("fleet.text_docs_total")
    n0 = by_transport.get(transport="chains")
    got = Fleet(make_mesh(jax.devices()[:1])).merge_text_docs([big, small]).texts
    assert by_transport.get(transport="chains") == n0 + 2
    keep = ~big.deleted
    assert got[0] == "".join(map(chr, big.content[keep][::-1]))
    assert got == uncontracted_texts([big, small])


def test_entry_counts_its_transport_and_the_ring_it_ranked():
    payloads, extracts, want = scenario("very_different_sizes")
    pad_c, _pad_n = text_pads(
        max(contract_chains(e).n_chains for e in extracts), max(e.n for e in extracts))
    spec = ":".join(fb._resolve_rank_spec(None, fb.rank_bound(pad_c)))
    docs, ring = obs.counter("fleet.text_docs_total"), obs.counter("rank.ring_tokens")
    d0, r0 = docs.get(transport="packed"), ring.get(algo=spec)
    assert Fleet().merge_text_payloads(payloads, CID).texts == want
    assert docs.get(transport="packed") == d0 + len(payloads)
    # eight devices: the four documents are padded to eight
    assert ring.get(algo=spec) == r0 + 8 * fb.rank_bound(pad_c)


@pytest.fixture(scope="module")
def compiled():
    """The names of the executables this process asks its backend for,
    from here on (one listener for the module: jax.monitoring has no
    public way to take one off)."""
    names = []

    def on(event, _secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(on)
    return names


@pytest.mark.parametrize("devices", MESHES.values(), ids=MESHES.keys())
def test_a_second_identical_call_compiles_nothing(devices, compiled):
    payloads, _extracts, want = scenario("deletes_in_and_at_ends")
    fleet = Fleet(make_mesh(jax.devices()[:devices]))
    assert fleet.merge_text_payloads(payloads, CID).texts == want
    before = len(compiled)
    # another Fleet on the same mesh: the program is the module's, not the object's
    again = Fleet(make_mesh(jax.devices()[:devices])).merge_text_payloads(payloads, CID)
    assert again.texts == want and compiled[before:] == []


@pytest.fixture
def no_sleep_supervisor():
    set_supervisor(DeviceSupervisor(sleep=lambda s: None))
    yield
    set_supervisor(None)


@pytest.mark.faultinject
@pytest.mark.parametrize("site", ["launch", "fetch"])
@pytest.mark.parametrize("entry", ["merge_text_payloads", "merge_text_changes"])
def test_a_device_failure_still_degrades_to_the_host_engine(
        entry, site, no_sleep_supervisor):
    doc = _children_of_a_chain_middle()[0]
    want = doc.get_text("text").to_string()
    degraded = obs.counter("fleet.degraded_merges_total")
    n0 = degraded.get(family="text")
    faultinject.inject(site, exc=RuntimeError("INTERNAL: injected device death"),
                       times=1)
    try:
        if entry == "merge_text_payloads":
            got = Fleet().merge_text_payloads(
                [strip_envelope(doc.export_updates({}))], CID)
        else:
            got = Fleet().merge_text_changes(
                [doc.oplog.changes_in_causal_order()], CID)
    finally:
        faultinject.clear()
    assert got.texts == [want]
    assert degraded.get(family="text") == n0 + 1


@pytest.mark.parametrize("name", SCENARIOS)
def test_native_contraction_and_row_equal_numpys_on_every_scenario(name):
    """What ``Fleet`` ships is what it shipped: per document the native
    chains are numpy's and the packed row, at the batch's pads, is
    ``pack_chain_doc_into(chain_columns(...))``'s byte for byte."""
    assert native.available()
    _payloads, extracts, _want = scenario(name)
    chains = [contract_chains(e) for e in extracts]
    pad_c, pad_n = text_pads(max(c.n_chains for c in chains), max(e.n for e in extracts))
    for e, got in zip(extracts, chains):
        ref = _contract_chains_numpy(e)
        for f in ("parent", "side", "valid", "head_row", "chain_id"):
            a, b = getattr(got, f), getattr(ref, f)
            assert a.dtype == b.dtype and np.array_equal(a, b), f
        row, want = np.empty((2, fb.packed_row_bytes(pad_c, pad_n)), np.uint8)
        pack_chain_row(e, got, pad_c, pad_n, row)
        fb.pack_chain_doc_into(chain_columns(e, pad_n=pad_n, pad_c=pad_c, chains=got), want)
        assert row.tobytes() == want.tobytes()


@pytest.mark.parametrize("entry", ["merge_text_payloads", "merge_text_docs"])
def test_without_the_library_fleet_contracts_in_numpy_and_says_so(entry, monkeypatch):
    payloads, extracts, want = scenario("children_of_a_middle")
    fallbacks = obs.counter("fleet.host_fallback_total")
    native_calls = obs.counter("codec.native_chain_calls_total")
    fleet = Fleet(make_mesh(jax.devices()[:1]))

    def call():
        if entry == "merge_text_payloads":
            return fleet.merge_text_payloads(payloads, CID).texts
        return fleet.merge_text_docs(extracts).texts

    f0, n0 = fallbacks.get(kind="chain_contract"), native_calls.total()
    assert call() == want
    # the library there: a native call a document and a stage, no fallback
    assert fallbacks.get(kind="chain_contract") == f0
    assert native_calls.total() - n0 == 2 * len(extracts)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", True)
    assert call() == want and call() == want
    assert fallbacks.get(kind="chain_contract") == f0 + 2  # one a call
    assert native_calls.total() - n0 == 2 * len(extracts)


def test_fleet_holds_no_uncontracted_text_program():
    assert not hasattr(Fleet, "_build_text_fn")
    assert not hasattr(fleet_mod, "materialize_content_batch")
