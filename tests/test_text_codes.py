"""``ops/text_codes.text_from_codes``: a text kernel's code points become
a ``str`` by one bulk codec call.  It must answer what the per-character
``chr`` join answered — lone surrogates, U+0000 and astral code points
included — or raise a ``ValueError``; and the two entries a user reads a
text through (``Fleet.merge_text_payloads``, ``DeviceDocBatch.texts``)
must equal the host engine's text on such a document."""
import jax
import numpy as np
import pytest

from loro_tpu import LoroDoc
from loro_tpu.core.ids import ContainerID, ContainerType
from loro_tpu.doc import strip_envelope
from loro_tpu.ops.text_codes import text_from_codes
from loro_tpu.parallel.fleet import DeviceDocBatch, Fleet
from loro_tpu.parallel.mesh import make_mesh
from loro_tpu.utils import tracing

GARBAGE = [0x110000, -1, 0x7FFFFFFF]  # never read: it lies past ``count``


def chr_join(row, count):
    return "".join(map(chr, row[:count]))


def _row(text, tail=(), dtype=np.int32):
    return np.array([ord(c) for c in text] + list(tail), dtype=dtype)


CASES = {
    "empty_row": (np.zeros(0, np.int32), 0),
    "count_0": (_row("abc"), 0),
    "ascii": (_row("hello, world"), 12),
    "latin1_with_nul": (_row("a\x00\xe9\xff"), 4),
    "bmp": (_row("aé☃z"), 4),
    "astral": (_row("\U0001F600\U0001D11E"), 2),
    "lone_high_surrogate": (_row("a\ud800b"), 3),
    "lone_low_surrogate": (_row("\udc00z"), 2),
    "garbage_past_count": (_row("aé☃", GARBAGE), 3),
    "non_contiguous": (np.repeat(_row("aé☃\U0001F600z", [0x110000]), 2)[::2], 5),
    "int64": (_row("aé☃\U0001F600z", GARBAGE, np.int64), 5),
    "uint32": (_row("aé☃\U0001F600z", dtype=np.uint32), 5),
    "numpy_count": (_row("abcdef"), np.int32(4)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_equals_the_per_character_chr_join(case):
    row, count = CASES[case]
    got = text_from_codes(row, count)
    assert isinstance(got, str)
    assert got == chr_join(np.asarray(row).tolist(), int(count))


def test_a_device_array_is_read_like_a_numpy_row():
    row = jax.numpy.asarray(_row("aé☃\U0001F600z", [0, 0, 0]))
    assert text_from_codes(row, 5) == "aé☃\U0001F600z"


@pytest.mark.parametrize(
    "bad, dtype",
    [(0x110000, np.int32), (-1, np.int32), (0x7FFFFFFF, np.int32),
     (0x110000, np.int64), (-1, np.int64), (2**32 + 0x41, np.int64)],
    ids=["0x110000", "minus_1", "int32_max",
         "0x110000_int64", "minus_1_int64", "int64_that_wraps_to_A"],
)
def test_a_code_chr_refuses_is_a_value_error(bad, dtype):
    row = np.array([0x61, bad, 0x62], dtype=dtype)
    with pytest.raises((ValueError, OverflowError)):
        chr_join(row.tolist(), 3)
    with pytest.raises(ValueError):
        text_from_codes(row, 3)
    assert text_from_codes(row, 1) == "a"  # and is not read past ``count``


# one code point of each width class, typed by two replicas concurrently
TEXT = "aé☃𝄞z\U0001F600"


def _document(i):
    a, b = LoroDoc(peer=900 + 2 * i), LoroDoc(peer=901 + 2 * i)
    a.get_text("text").insert(0, TEXT)
    a.commit()
    b.import_(a.export_snapshot())
    a.get_text("text").insert(3, "𝄞" * (i + 1))
    b.get_text("text").insert(5, "☃\x00é")
    b.get_text("text").delete(0, 1)
    a.commit()
    b.commit()
    a.import_(b.export_updates(a.oplog_vv()))
    return a


@pytest.fixture(scope="module")
def documents():
    docs = [_document(i) for i in range(3)]
    return docs, [d.get_text("text").to_string() for d in docs]


CID = ContainerID.root("text", ContainerType.Text)


@pytest.fixture(scope="module")
def fleet_call(documents):
    """ONE ``merge_text_payloads`` call under tracing: its texts and spans."""
    docs, _want = documents
    payloads = [strip_envelope(d.export_updates({})) for d in docs]
    tracing.clear()
    tracing.enable()
    try:
        got = Fleet(make_mesh(jax.devices()[:1])).merge_text_payloads(payloads, CID)
        return got.texts, tracing.events()
    finally:
        tracing.disable()
        tracing.clear()


def test_fleet_merge_text_payloads_equals_the_host_text(documents, fleet_call):
    _docs, want = documents
    assert all(set(TEXT[1:]) <= set(w) for w in want)
    assert fleet_call[0] == want


def test_the_span_fleet_join_is_one_a_call_and_counts_its_characters(documents, fleet_call):
    _docs, want = documents
    spans = fleet_call[1]
    (join,) = [e for e in spans if e["name"] == "fleet.join"]
    (docs_span,) = [e for e in spans if e["name"] == "fleet.merge_text_docs"]
    assert join["parent_id"] == docs_span["span_id"]
    assert join["args"] == {"chars": sum(map(len, want))}


def test_device_doc_batch_texts_equal_the_host_text(documents):
    docs, want = documents
    batch = DeviceDocBatch(n_docs=len(docs), capacity=64)
    batch.append_changes([d.oplog.changes_in_causal_order() for d in docs], CID)
    assert batch.texts() == want
    assert batch.texts(use_solver=True) == want
