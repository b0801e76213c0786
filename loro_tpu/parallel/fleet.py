"""Fleet merge engine: reconcile batches of documents in one XLA launch.

The north-star path (BASELINE.json): a server holds thousands of docs;
incoming update blobs are decoded host-side into columnar element
tables (ops/columnar.py), the doc axis is sharded over the device mesh,
and one jit launch resolves every document's final sequence order /
LWW winners.  This replaces the reference's per-doc sequential
`OpLog::import -> DiffCalculator` replay (loro.rs:568 -> diff_calc.rs)
with data-parallel kernels.

Shapes are bucket-padded (pad_bucket) so the jit cache stays small
across varying doc sizes.
"""
from __future__ import annotations

import functools
import os
import struct
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import native
from ..core.change import Change
from ..core.ids import ContainerID
from ..errors import DeviceFailure
from ..obs import metrics as obs
from ..analysis.lockwitness import named_rlock
from ..codec.binary import read_values_at
from ..resilience import get_supervisor
from ..resilience.faultinject import register_site

register_site(
    "export_launch", "batched delta-export selection launch (fleet "
    "export_select thunk, inside the supervisor): transient retries, "
    "terminal -> DeviceFailure degrades ONLY that window")
from ..utils import tracing
from ..ops.columnar import (
    MapExtract,
    SeqExtract,
    chain_columns,
    contract_chains,
    extract_seq_container,
    pack_chain_row,
    pack_wire_ids,
)
from ..ops.fugue_batch import (
    ChainColumns,
    _chain_merge_docs_jit,
    _tick_rank_obs,
    chain_merge_docs_packed,
    packed_row_bytes,
    pad_bucket,
)
from ..ops.lww import MapOpCols, lww_merge_doc
from ..ops.text_codes import text_from_codes
from .mesh import DOC_AXIS, OP_AXIS, doc_sharding, make_mesh, replicated


@dataclass
class TextMergeResult:
    texts: List[str]


def _mesh_pad(mesh, d: int) -> int:
    """Doc count padded up to a multiple of the mesh's doc dimension."""
    dm = mesh.shape[DOC_AXIS]
    return ((d + dm - 1) // dm) * dm


def _obs_merge(family: str, docs: int, real_rows: int, padded_rows: int,
               shape: Tuple[int, ...]) -> None:
    """One accounting point per device merge launch (docs/OBSERVABILITY
    .md): real vs padded rows quantify pad_bucket waste, the shape set
    cardinality proxies the jit cache size."""
    obs.counter("fleet.merge_calls_total").inc(family=family)
    obs.counter("fleet.docs_merged_total").inc(docs, family=family)
    obs.counter("fleet.ops_merged_total").inc(real_rows, family=family)
    obs.counter("fleet.pad_waste_rows_total").inc(
        max(0, padded_rows - real_rows), family=family
    )
    obs.counter("fleet.device_launches_total").inc(family=family)
    obs.unique("fleet.padded_shapes_distinct").add((family,) + tuple(shape))


def _obs_fallback(kind: str) -> None:
    """Host-fallback hits: forced Python engines (LORO_PY_ORDER /
    LORO_PY_IDMAP or missing native lib) and per-payload decode
    fallbacks."""
    obs.counter("fleet.host_fallback_total").inc(kind=kind)


# the host's decode width, merge_text_payloads_packed's own
_POOL_WIDTH = min(8, os.cpu_count() or 1)


def _self_contained(extract):
    """A sequence's Python extractor whose ``KeyError`` (a row that
    references an element outside the payload) is said as the entry's
    ``ValueError``."""

    def checked(changes, cid):
        try:
            return extract(changes, cid)
        except KeyError as e:
            raise ValueError(
                "payload is not self-contained (references elements "
                f"outside it: {e}); one-shot fleet merges need full-"
                "history payloads — use DeviceDocBatch for deltas"
            ) from e

    return checked


def _decode_payloads(family: str, span: str, payloads, cid, native, python) -> list:
    """The per-payload host stage of a payload entry, one task a payload
    on a pool of ``_POOL_WIDTH`` threads that lives for this call:
    ``native(payload, cid)``, and where that refuses (an incremental
    payload that references elements outside it) or has no library,
    ``python(decode_changes(payload), cid)``.  Answers in the payloads'
    order, the first exception in payload order raised as a loop would
    raise it (tasks not yet started are cancelled), every task under the
    caller's trace id.  The tasks are large-array numpy and the native
    explode, which give the interpreter lock up.  A caller that is itself
    one of many threads shares the machine's cores with its siblings'
    pools.

    The caller's thread holds ONE span ``span`` around submit-and-wait:
    the stage's time on the call's critical path.  A task's is
    ``<span>_one``, a root of its pool thread and a leaf: the spans under
    it (``native.explode*``) are not recorded, or readers that sum a
    stage by its spans' names would count the pool's threads on top of
    the wait that holds them."""
    from ..codec.binary import decode_changes

    tasks = obs.counter("fleet.decode_tasks_total")

    def one(p):
        with tracing.span(span + "_one", leaf=True, bytes=len(p)):
            tasks.inc(family=family)
            try:
                ex = native(p, cid)
            except ValueError:
                ex = None
            if ex is None:
                _obs_fallback("payload_extract")
                ex = python(decode_changes(p), cid)
            return ex

    with tracing.span(span, docs=len(payloads), workers=_POOL_WIDTH):
        pool = ThreadPoolExecutor(
            _POOL_WIDTH, "fleet-pool", tracing.set_current, (tracing.current(),)
        )
        try:
            futures = [pool.submit(one, p) for p in payloads]
            return [f.result() for f in futures]
        finally:
            pool.shutdown(wait=True, cancel_futures=True)


def _sup_launch(label: str, thunk):
    """Route one merge launch through the process DeviceSupervisor:
    bounded retry on transient UNAVAILABLE errors, typed DeviceFailure
    on anything terminal, in-flight accounting (docs/RESILIENCE.md).
    Fleet merge thunks are pure (fresh device_put inputs, no donated
    buffers) so retry is safe."""
    return get_supervisor().launch(thunk, label=label)


def _sup_fetch(label: str, value):
    """Supervised host fetch: the merge's sync point (drains the
    in-flight queue through it)."""
    return get_supervisor().fetch(value, label=label)


def _host_degrade(family: str, docs_changes, cid=None):
    """Graceful degradation: re-run a failed device merge on the host
    ``models/`` engine (byte-identical by the differential-fuzz
    contract).  One obs counter per degraded merge."""
    from ..resilience import hostpath

    get_supervisor().note_degradation(f"fleet.{family}")
    obs.counter("fleet.degraded_merges_total").inc(family=family)
    return hostpath.host_merge_changes(family, docs_changes, cid)


def _batch_export_select(batch, family: str, index, requests, sup=None):
    """Shared read-plane selection entry (docs/SYNC.md "Read plane"):
    ONE supervised launch answers a window of ``(doc, frontier)`` pull
    requests against the change-span index (ops/export_batch.py).
    Runs under the batch device lock — selection never mutates batch
    state, but the supervisor's drain fetch must not interleave with a
    buffer-donating grow/evict on the same device queue.  The
    ``export_launch`` fault site fires inside the supervised thunk, so
    an armed failure classifies exactly like a real device error
    (DeviceFailure -> the read batcher degrades that window to the
    oracle)."""
    from ..resilience import faultinject

    sup = sup if sup is not None else get_supervisor()

    def thunk():
        faultinject.check("export_launch")
        return index.select(requests)

    with batch._dev_lock:
        # selection is a pure read of the index grid: retry-safe
        return sup.launch(thunk, label=f"fleet.export.{family}")


def text_pads(n_chains: int, n_elements: int) -> Tuple[int, int]:
    """``(pad_c, pad_n)`` of a text batch whose largest document has
    ``n_chains`` chains and ``n_elements`` elements.  Elements pad to a
    power of two; chains pad so that the RING the rank walks,
    2 * (pad_c + 1) tokens, is a power of two — the rank's cost follows
    the ring, and a B4-sized document (about 17,500 chains) then ranks
    65,536 tokens, the last ring the packed Pallas kernels hold (one
    more chain slot would be 65,538 and the dual-table kernel)."""
    return pad_bucket(n_chains + 1) - 1, pad_bucket(n_elements)


def movable_pads(n_slots: int, n_sets: int, n_elems: int) -> Tuple[int, int, int]:
    """``(pad_s, pad_k, pad_e)`` of a movable-list batch whose largest
    document has ``n_slots`` position slots, ``n_sets`` set rows and
    ``n_elems`` elements: each to a power of two, so the RING the rank
    walks, 2 * (pad_s + 1) tokens, is two past one (32,768 slots rank
    65,538 tokens, 65,536 rank 131,074).  ``text_pads`` buckets its ring
    instead; here that is a ``perf_opt``'s to measure size by size, not
    this rule's to assume: on the chip the XLA loop ranks 64 documents of
    ring 262,146 in 218 ms a round and of ring 262,144 in 270, and a ring
    bucket pays at 30,000 slots and costs at 50,000 (PERF.md, PR 32)."""
    return (pad_bucket(n_slots), pad_bucket(n_sets, floor=16),
            pad_bucket(n_elems, floor=16))


def text_transport(pad_c: int, pad_n: int) -> str:
    """How a chain-contracted text batch of these padded sizes reaches
    the device: ``"packed"``, one u8 row a document
    (``pack_chain_doc_into``: 8 * (pad_c + pad_n) bytes, one put), when
    its 16-bit chain ids hold ``pad_c`` — 0xFFFF is the root's parent
    there; else ``"chains"``, plain ``ChainColumns``.  Rows and contents
    are 32-bit in both, so ``pad_n`` bounds neither.  Either way the
    rank is chosen from the ring size (``_resolve_rank_spec``)."""
    return "packed" if pad_c < 0xFFFF else "chains"


def _empty_text_batch(transport: str, d_pad: int, pad_c: int, pad_n: int):
    """The host buffer of a text batch, every document all-invalid (no
    valid chain, no valid element: zeros say that in both layouts):
    ``[d_pad, packed_row_bytes]`` u8 rows, or ``ChainColumns`` of
    ``[d_pad, pad_c]`` / ``[d_pad, pad_n]`` arrays."""
    if transport == "packed":
        return np.zeros((d_pad, packed_row_bytes(pad_c, pad_n)), np.uint8)
    c, n = (d_pad, pad_c), (d_pad, pad_n)
    return ChainColumns(
        c_parent=np.zeros(c, np.int32), c_side=np.zeros(c, np.int32),
        c_valid=np.zeros(c, bool), head_row=np.zeros(c, np.int32),
        chain_id=np.zeros(n, np.int32), deleted=np.zeros(n, bool),
        content=np.zeros(n, np.int32), valid=np.zeros(n, bool),
    )


def _empty_movable_batch(d_pad: int, pad_s: int, pad_k: int):
    """The host buffers of a movable-list batch, every document
    all-invalid: ``MovableCols`` of ``[d_pad, pad_s]`` slot columns and
    ``[d_pad, pad_k]`` set columns."""
    from ..ops.fugue_batch import SeqColumns
    from ..ops.movable_batch import MovableCols

    s, k = (d_pad, pad_s), (d_pad, pad_k)
    return MovableCols(
        seq=SeqColumns(
            parent=np.full(s, -1, np.int32), side=np.zeros(s, np.int32),
            peer=np.zeros(s, np.int32), counter=np.zeros(s, np.int32),
            deleted=np.ones(s, bool), content=np.full(s, -1, np.int32),
            valid=np.zeros(s, bool),
        ),
        lamport=np.zeros(s, np.int32), set_elem=np.zeros(k, np.int32),
        set_lamport=np.zeros(k, np.int32), set_peer=np.zeros(k, np.int32),
        set_value=np.zeros(k, np.int32), set_valid=np.zeros(k, bool),
    )


class Fleet:
    """Batched merge front-end bound to a device mesh.  ``tree_refused``:
    per document of this Fleet's last tree merge on the device, the moves
    its replay refused as cycles (None before one, and after a degraded
    call)."""

    def __init__(self, mesh=None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.tree_refused: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    # text / list sequence merge
    # ------------------------------------------------------------------
    def merge_text_docs(
        self, extracts: Sequence[SeqExtract], pad_docs: Optional[int] = None
    ) -> TextMergeResult:
        """Resolve final text for a batch of documents (one launch of
        the chain-contracted merge: the device ranks each document's
        chains, not its elements).  Documents are padded to common
        bucketed chain and element counts (``text_pads``) and the doc
        axis to a multiple of the mesh's doc dimension; doc-axis padding
        rows are all-invalid documents."""
        with tracing.span("fleet.merge_text_docs", docs=len(extracts)):
            # a loop on the caller's thread: one native call a document
            # (about a millisecond at B4's size, PERF.md PR 33); without
            # the library, numpy's twenty passes, and a counted fallback
            if not native.available():
                _obs_fallback("chain_contract")
            with tracing.span("fleet.contract"):
                chains = [contract_chains(e) for e in extracts]
            pad_c, pad_n = text_pads(
                max(c.n_chains for c in chains), max(e.n for e in extracts)
            )
            transport = text_transport(pad_c, pad_n)
            d = len(extracts)
            d_pad = pad_docs or _mesh_pad(self.mesh, d)
            _obs_merge(
                "text", d, sum(e.n for e in extracts), pad_n * d_pad,
                (pad_n, pad_c, d_pad),
            )
            obs.counter("fleet.text_docs_total").inc(d, transport=transport)
            _tick_rank_obs(d_pad, pad_c)
            with tracing.span("fleet.stack"):
                rows = _empty_text_batch(transport, d_pad, pad_c, pad_n)
            with tracing.span("fleet.pack"):
                for i, (e, ch) in enumerate(zip(extracts, chains)):
                    if transport == "packed":
                        pack_chain_row(e, ch, pad_c, pad_n, rows[i])
                    else:
                        cols = chain_columns(e, pad_n=pad_n, pad_c=pad_c, chains=ch)
                        for column, of_doc in zip(rows, cols):
                            column[i] = of_doc
            sh = doc_sharding(self.mesh)
            # the upload is supervised too: a device that is gone raises
            # synchronously at device_put, and that must be a typed
            # DeviceFailure for the degradation handlers, not a raw crash
            with tracing.span("fleet.upload"):
                batched = _sup_launch("fleet.text", lambda: jax.device_put(rows, sh))
            with tracing.span("fleet.launch"):
                codes, counts = _sup_launch(
                    "fleet.text",
                    lambda: chain_merge_docs_packed(batched, pad_c, pad_n)
                    if transport == "packed" else _chain_merge_docs_jit(batched),
                )
            # the wait is the device's time, the fetch the host's copy:
            # kept apart, under the same guard (a device that fails
            # mid-merge surfaces at the first sync point)
            with tracing.span("fleet.device_wait"):
                get_supervisor().guard(
                    lambda: jax.block_until_ready((codes, counts)), label="fleet.text"
                )
            with tracing.span("fleet.fetch"):
                codes = _sup_fetch("fleet.text", codes)
                counts = _sup_fetch("fleet.text", counts)
            with tracing.span("fleet.join", chars=int(counts[:d].sum())):
                texts = [text_from_codes(codes[i], counts[i]) for i in range(d)]
        return TextMergeResult(texts)

    def merge_text_changes(
        self, docs_changes: Sequence[Sequence[Change]], cid: ContainerID
    ) -> TextMergeResult:
        """Convenience: decode + merge each doc's change list.  On a
        supervisor-declared device failure the merge transparently
        re-runs on the host engine (same bytes out, typed counters)."""
        extracts = [extract_seq_container(chs, cid) for chs in docs_changes]
        try:
            return self.merge_text_docs(extracts)
        except DeviceFailure:
            return TextMergeResult(_host_degrade("text", docs_changes, cid))

    def merge_text_payloads(
        self, payloads: Sequence[bytes], cid: ContainerID
    ) -> TextMergeResult:
        """Full ingest pipeline: binary update payloads -> native C++
        wire->SoA decode -> one sharded device launch.  This is the
        server-side bulk-sync path the north star describes: the decode
        stage never materializes Python op objects.

        Payloads are envelope-stripped bytes; integrity (CRC) is the
        envelope layer's job (LoroDoc._parse_envelope) — a corrupted
        payload here decodes to garbage-but-safe output, never a crash.
        """
        from ..codec.binary import decode_changes
        from ..ops.columnar import extract_seq_from_payload

        # one trace id per call (a request's own, when it made the call)
        with tracing.span(
            "fleet.merge_text_payloads",
            trace_id=tracing.current() or tracing.new_trace_id("f"),
            docs=len(payloads),
        ):
            extracts = _decode_payloads(
                "text", "fleet.decode", payloads, cid,
                extract_seq_from_payload, _self_contained(extract_seq_container),
            )
            try:
                return self.merge_text_docs(extracts)
            except DeviceFailure:
                return TextMergeResult(
                    _host_degrade("text", [decode_changes(p) for p in payloads], cid)
                )

    # ------------------------------------------------------------------
    # rich text merge
    # ------------------------------------------------------------------
    def merge_richtext_changes(self, docs_changes: Sequence[Sequence[Change]], cid) -> List[list]:
        """Batched rich-text merge: per-doc change lists -> Quill-style
        segment lists with resolved styles (one vmapped launch)."""
        from ..ops.fugue_batch import ChainColumns, pad_bucket
        from ..ops.richtext_batch import (
            RichtextChainCols,
            extract_richtext_chain,
            pad_richtext_chain_cols,
            richtext_chain_merge_batch,
        )

        extracts = [extract_richtext_chain(chs, cid) for chs in docs_changes]
        n = pad_bucket(max(1, max(c.chain.chain_id.shape[0] for c, _, _ in extracts)))
        cpad = pad_bucket(max(1, max(c.chain.c_parent.shape[0] for c, _, _ in extracts)))
        p = pad_bucket(max(1, max(c.pair_start.shape[0] for c, _, _ in extracts)), floor=16)
        n_keys = pad_bucket(max(1, max(len(k) for _, k, _ in extracts)), floor=4)
        d = len(extracts)
        d_pad = _mesh_pad(self.mesh, d)
        _obs_merge(
            "richtext",
            d,
            sum(c.chain.chain_id.shape[0] for c, _, _ in extracts),
            n * d_pad,
            (n, cpad, p, n_keys, d_pad),
        )

        padded = [
            pad_richtext_chain_cols(c, pad_n=n, pad_c=cpad, pad_p=p)
            for c, _, _ in extracts
        ]
        if len(padded) < d_pad:  # doc-axis pad: one shared all-pad doc
            empty = pad_richtext_chain_cols(
                RichtextChainCols(
                    chain=ChainColumns(
                        c_parent=np.zeros(0, np.int32),
                        c_side=np.zeros(0, np.int32),
                        c_valid=np.zeros(0, bool),
                        head_row=np.zeros(0, np.int32),
                        chain_id=np.zeros(0, np.int32),
                        deleted=np.zeros(0, bool),
                        content=np.zeros(0, np.int32),
                        valid=np.zeros(0, bool),
                    ),
                    pair_start=np.zeros(0, np.int32),
                    pair_end=np.zeros(0, np.int32),
                    pair_key=np.zeros(0, np.int32),
                    pair_value=np.zeros(0, np.int32),
                    pair_lamport=np.zeros(0, np.int32),
                    pair_peer=np.zeros(0, np.int32),
                    pair_valid=np.zeros(0, bool),
                ),
                pad_n=n,
                pad_c=cpad,
                pad_p=p,
            )
            padded.extend([empty] * (d_pad - len(padded)))
        sh = doc_sharding(self.mesh)

        def upload():
            return RichtextChainCols(
                chain=ChainColumns(
                    *[
                        jax.device_put(np.stack([getattr(q.chain, f) for q in padded]), sh)
                        for f in ChainColumns._fields
                    ]
                ),
                **{
                    f: jax.device_put(np.stack([getattr(q, f) for q in padded]), sh)
                    for f in RichtextChainCols._fields
                    if f != "chain"
                },
            )

        try:
            cols = _sup_launch("fleet.richtext", upload)
            codes, counts, bounds, win = _sup_launch(
                "fleet.richtext", lambda: richtext_chain_merge_batch(cols, n_keys)
            )
            codes = _sup_fetch("fleet.richtext", codes)
            counts = _sup_fetch("fleet.richtext", counts)
            bounds = _sup_fetch("fleet.richtext", bounds)
            win = _sup_fetch("fleet.richtext", win)
        except DeviceFailure:
            return _host_degrade("richtext", docs_changes, cid)
        results = []
        for i, (_, keys, values) in enumerate(extracts):
            text = text_from_codes(codes[i], counts[i])
            segs: List[dict] = []
            for r in range(bounds.shape[1] - 1):
                lo, hi = int(bounds[i, r]), int(bounds[i, r + 1])
                if lo >= hi:
                    continue
                attrs = {}
                for ki in range(len(keys)):
                    vi = int(win[i, r, ki])
                    if vi >= 0:
                        attrs[keys[ki]] = values[vi]
                seg: dict = {"insert": text[lo:hi]}
                if attrs:
                    seg["attributes"] = attrs
                if segs and segs[-1].get("attributes") == seg.get("attributes"):
                    segs[-1]["insert"] += seg["insert"]
                else:
                    segs.append(seg)
            results.append(segs)
        return results

    # ------------------------------------------------------------------
    # movable list merge
    # ------------------------------------------------------------------
    def merge_movable_changes(self, docs_changes: Sequence[Sequence[Change]], cid) -> List[list]:
        """Batched movable-list merge: per-doc change lists -> final
        value lists (one vmapped launch)."""
        from ..ops.movable_batch import extract_movable

        try:
            return self._merge_movable_extracted(
                [extract_movable(chs, cid) for chs in docs_changes]
            )
        except DeviceFailure:
            return _host_degrade("movable", docs_changes, cid)

    def merge_movable_payloads(self, payloads: Sequence[bytes], cid) -> List[list]:
        """Native ingest: envelope-stripped update payloads -> C++
        movable explode -> one launch.  Values decode lazily (winners
        only); unresolvable payloads fall back to the Python decoder."""
        from ..codec.binary import decode_changes
        from ..ops.movable_batch import extract_movable, extract_movable_from_payload

        # one trace id per call (a request's own, when it made the call)
        with tracing.span(
            "fleet.merge_movable_payloads",
            trace_id=tracing.current() or tracing.new_trace_id("f"),
            docs=len(payloads),
        ):
            extracts = _decode_payloads(
                "movable", "fleet.movable_decode", payloads, cid,
                extract_movable_from_payload, _self_contained(extract_movable),
            )
            try:
                return self._merge_movable_extracted(extracts)
            except DeviceFailure:
                return _host_degrade(
                    "movable", [decode_changes(p) for p in payloads], cid
                )

    def _merge_movable_extracted(self, extracts) -> List[list]:
        """The device half of both movable-list entries: the documents'
        columns written into one all-invalid batch (``movable_pads``), one
        upload, one launch of ``movable_merge_batch`` (the Fugue order of
        the slots — the rank ``_resolve_rank_spec`` gives for the ring —
        and the two LWW folds), one fetch, the value lists."""
        from ..ops.movable_batch import LazyPayloadValue, movable_merge_batch

        label = "fleet.movable"
        s, k, n_elems = movable_pads(
            max(c.seq.parent.shape[0] for c, _, _ in extracts),
            max(c.set_elem.shape[0] for c, _, _ in extracts),
            max(len(e) for _, e, _ in extracts),
        )
        d = len(extracts)
        d_pad = _mesh_pad(self.mesh, d)
        _obs_merge(
            "movable",
            d,
            sum(c.seq.parent.shape[0] + c.set_elem.shape[0] for c, _, _ in extracts),
            (s + k) * d_pad,
            (s, k, n_elems, d_pad),
        )
        with tracing.span("fleet.movable_stack"):
            rows = _empty_movable_batch(d_pad, s, k)
            columns = jax.tree_util.tree_leaves(rows)
            for i, (c, _, _) in enumerate(extracts):
                for column, of_doc in zip(columns, jax.tree_util.tree_leaves(c)):
                    column[i, : of_doc.shape[0]] = of_doc
        sh = doc_sharding(self.mesh)
        with tracing.span("fleet.movable_upload"):
            cols = _sup_launch(label, lambda: jax.device_put(rows, sh))
        with tracing.span("fleet.movable_launch"):
            out = _sup_launch(label, lambda: movable_merge_batch(cols, n_elems))
        # the wait is the device's time, the fetch the host's copy: kept
        # apart, under the same guard (merge_text_docs)
        with tracing.span("fleet.movable_device_wait"):
            get_supervisor().guard(lambda: jax.block_until_ready(out), label=label)
        # ticked once the device has ranked the rings: a call that fails
        # over to the host engine on the way here counts none
        _tick_rank_obs(d_pad, s)
        with tracing.span("fleet.movable_fetch"):
            out, counts = (_sup_fetch(label, x) for x in out)
        with tracing.span("fleet.movable_values"):
            results = []
            for i, (_, _, values) in enumerate(extracts):
                row = []
                for j in out[i, : counts[i]].tolist():
                    v = values[j] if j >= 0 else None
                    if isinstance(v, LazyPayloadValue):
                        v = v.get()  # winners only ever decode
                    row.append(v)
                results.append(row)
        return results

    # ------------------------------------------------------------------
    # tree merge
    # ------------------------------------------------------------------
    def merge_tree_changes(self, docs_changes: Sequence[Sequence[Change]], cid) -> List[dict]:
        """Batched movable-tree merge: per-doc change lists -> parent
        maps {TreeID: parent TreeID | None} of alive nodes."""
        from ..ops.tree_batch import extract_tree_ops

        try:
            return self._merge_tree_extracted(
                [extract_tree_ops(chs, cid) for chs in docs_changes]
            )
        except DeviceFailure:
            return _host_degrade("tree", docs_changes, cid)

    def merge_tree_payloads(self, payloads: Sequence[bytes], cid) -> List[dict]:
        """Native ingest: envelope-stripped update payloads -> C++ tree
        explode -> one launch (no per-op Python objects).  Falls back to
        the Python decoder per payload on unresolvable input."""
        from ..codec.binary import decode_changes
        from ..ops.tree_batch import extract_tree_from_payload, extract_tree_ops

        # one trace id per call (a request's own, when it made the call)
        trace_id = tracing.current() or tracing.new_trace_id("f")
        with tracing.span(
            "fleet.merge_tree_payloads", trace_id=trace_id, docs=len(payloads)
        ):
            extracted = _decode_payloads(
                "tree", "fleet.tree_decode", payloads, cid,
                extract_tree_from_payload, extract_tree_ops,
            )
            try:
                return self._merge_tree_extracted(extracted)
            except DeviceFailure:
                return _host_degrade(
                    "tree", [decode_changes(p) for p in payloads], cid
                )

    def _tree_device_merge(self, extracted, family: str, want_eff: bool):
        """The device half of every tree merge: one packed upload
        (`pack_tree_rows`), one launch (`tree_import_batch`: the replay
        `replay_algo` selects, then `is_deleted_batch`), one fetch.
        Returns i32[d_pad, n + 2] — per document the parent of every
        alive node (TRASH: deleted, ABSENT: never created), the moves
        refused, the walk steps — and, ``want_eff``, the moves effected
        bool[d_pad, m]."""
        from ..ops.tree_batch import (
            pack_tree_rows,
            replay_algo,
            tree_import_batch,
            tree_pads,
        )

        label = f"fleet.{family}"
        self.tree_refused = None
        cols = [c for c, _, _ in extracted]
        moves = sum(int(c.valid.sum()) for c in cols)
        longest = max([c.target.shape[0] for c in cols] + [1])
        m = tree_pads(longest)
        n = max(1, max(len(nodes) for _, nodes, _ in extracted))
        d = len(extracted)
        d_pad = _mesh_pad(self.mesh, d)
        _obs_merge(
            family, d, sum(c.target.shape[0] for c in cols), m * d_pad,
            (m, n, d_pad),
        )
        algo = replay_algo(n)
        obs.counter("fleet.tree_docs_total").inc(d)
        obs.counter("tree.moves_total").inc(moves)
        # the sequential steps of the launch: the scan replays its padding
        obs.counter("tree.replay_steps").inc(
            longest if algo == "pallas:lockstep" else m, algo=algo
        )
        with tracing.span("fleet.tree_stack"):
            rows = pack_tree_rows(cols, d_pad)
        sh = doc_sharding(self.mesh)
        with tracing.span("fleet.tree_upload"):
            batched = _sup_launch(label, lambda: jax.device_put(rows, sh))
        with tracing.span("fleet.tree_launch"):
            out = _sup_launch(
                label, lambda: tree_import_batch(batched, n, want_eff)
            )
        with tracing.span("fleet.tree_device_wait"):
            get_supervisor().guard(
                lambda: jax.block_until_ready(out), label=label
            )
        with tracing.span("fleet.tree_fetch"):
            if want_eff:
                out, eff = (_sup_fetch(label, x) for x in out)
            else:
                out, eff = _sup_fetch(label, out), None
        self.tree_refused = out[:d, n].copy()
        obs.counter("tree.moves_refused_total").inc(int(self.tree_refused.sum()))
        return out, eff

    def _merge_tree_extracted(self, extracted) -> List[dict]:
        from ..ops.tree_batch import ROOT

        out, _eff = self._tree_device_merge(extracted, "tree", want_eff=False)
        with tracing.span("fleet.tree_maps"):
            maps = []
            for i, (_c, nodes, _pos) in enumerate(extracted):
                row = out[i, : len(nodes)].tolist()
                # alive nodes only: TRASH and ABSENT are below ROOT
                maps.append({
                    nodes[j]: (None if p == ROOT else nodes[p])
                    for j, p in enumerate(row) if p >= ROOT
                })
        return maps

    def merge_tree_children(self, docs_changes: Sequence[Sequence[Change]], cid) -> List[dict]:
        """Like merge_tree_changes but returns ordered children maps
        {parent|None: [child TreeIDs in (fractional-index, move-key)
        order]} — the full materialized tree shape."""
        from ..ops.tree_batch import ROOT, extract_tree_ops, positions_of

        extracted = [extract_tree_ops(chs, cid) for chs in docs_changes]
        try:
            # a family of its own: this launch also returns the moves
            # effected, so its shapes must not alias the import's in the
            # jit-cache proxy
            alive, eff = self._tree_device_merge(
                extracted, "tree_children", want_eff=True
            )
        except DeviceFailure:
            return _host_degrade("tree_children", docs_changes, cid)
        out = []
        for i, (c, nodes, row_pos) in enumerate(extracted):
            n_rows = c.target.shape[0]
            e_i = eff[i, :n_rows]
            pos = positions_of(c, row_pos, e_i)
            # sibling tiebreak = the winning move's key; rows are sorted
            # by (lamport, peer, counter) so the row index is that order
            last_eff_row: Dict[int, int] = {}
            for j in range(n_rows):
                if e_i[j]:
                    last_eff_row[int(c.target[j])] = j
            kids: Dict = {}
            for j, tid in enumerate(nodes):
                p = int(alive[i, j])
                if p < ROOT:
                    continue
                parent_t = None if p == ROOT else nodes[p]
                kids.setdefault(parent_t, []).append(
                    (pos.get(j) or b"", last_eff_row.get(j, 0), tid)
                )
            out.append(
                {k: [t for _, _, t in sorted(v, key=lambda x: (x[0], x[1]))] for k, v in kids.items()}
            )
        return out

    # ------------------------------------------------------------------
    # counter merge
    # ------------------------------------------------------------------
    def merge_counter_changes(self, docs_changes: Sequence[Sequence[Change]]) -> List[Dict]:
        """Batched counter merge: per-doc change lists -> {container:
        sum} (order-independent segment sums, one launch)."""
        from ..core.change import CounterIncr
        from ..ops.fugue_batch import pad_bucket
        from ..ops.lww import counter_merge_batch

        rows_per_doc = []
        cids_per_doc = []
        for changes in docs_changes:
            rows = []
            cid_of: Dict[ContainerID, int] = {}
            cids: List[ContainerID] = []
            for ch in changes:
                for op in ch.ops:
                    if not isinstance(op.content, CounterIncr):
                        continue
                    if op.container not in cid_of:
                        cid_of[op.container] = len(cids)
                        cids.append(op.container)
                    rows.append((cid_of[op.container], op.content.delta))
            rows_per_doc.append(rows)
            cids_per_doc.append(cids)
        m = pad_bucket(max(1, max(len(r) for r in rows_per_doc)), floor=16)
        s = max(1, max(len(c) for c in cids_per_doc))
        d = len(docs_changes)
        d_pad = _mesh_pad(self.mesh, d)
        _obs_merge(
            "counter", d, sum(len(r) for r in rows_per_doc),
            m * d_pad, (m, s, d_pad),
        )
        slot = np.zeros((d_pad, m), np.int32)
        delta = np.zeros((d_pad, m), np.float32)
        valid = np.zeros((d_pad, m), bool)
        for di, rows in enumerate(rows_per_doc):
            for j, (s_, dv) in enumerate(rows):
                slot[di, j] = s_
                delta[di, j] = dv
                valid[di, j] = True
        sh = doc_sharding(self.mesh)
        try:
            sums = _sup_fetch(
                "fleet.counter",
                _sup_launch(
                    "fleet.counter",
                    lambda: counter_merge_batch(
                        jax.device_put(slot, sh), jax.device_put(delta, sh),
                        jax.device_put(valid, sh), s,
                    ),
                ),
            )
        except DeviceFailure:
            return _host_degrade("counter", docs_changes)
        return [
            {cid: float(sums[di, j]) for j, cid in enumerate(cids_per_doc[di])}
            for di in range(d)
        ]

    # ------------------------------------------------------------------
    # LWW map merge
    # ------------------------------------------------------------------
    def _batch_map_cols(self, extracts: Sequence[MapExtract], m: int) -> MapOpCols:
        """Stack per-doc MapExtract rows into padded [D, M] columns."""
        d_pad = _mesh_pad(self.mesh, len(extracts))

        def col(rows_list, fill, dtype):
            out = np.full((d_pad, m), fill, dtype)
            for i, r in enumerate(rows_list):
                out[i, : len(r)] = r
            return out

        return MapOpCols(
            slot=col([e.slot for e in extracts], 0, np.int32),
            lamport=col([e.lamport for e in extracts], 0, np.int32),
            peer=col([e.peer for e in extracts], 0, np.int32),
            value_idx=col([e.value_idx for e in extracts], 0, np.int32),
            valid=col([e.valid for e in extracts], False, bool),
        )

    def merge_map_docs(self, extracts: Sequence[MapExtract]) -> List[Dict[str, object]]:
        """Resolve LWW winners for a batch of docs; returns per-doc
        {key: value} for root map containers."""
        m = pad_bucket(max(1, max(len(e.slot) for e in extracts)))
        s = max(1, max(len(e.slots) for e in extracts))
        d_pad = _mesh_pad(self.mesh, len(extracts))
        _obs_merge(
            "map", len(extracts), sum(len(e.slot) for e in extracts),
            m * d_pad, (m, s, d_pad),
        )
        batched = self._batch_map_cols(extracts, m)
        sh = doc_sharding(self.mesh)
        batched = _sup_launch("fleet.map", lambda: MapOpCols(
            *[jax.device_put(np.asarray(a), sh) for a in batched]
        ))
        fn = _lww_batch_fn(self.mesh, s)
        vi, _, _ = _sup_launch("fleet.map", lambda: fn(batched))
        return self._map_winner_values(_sup_fetch("fleet.map", vi), extracts)

    def _map_winner_values(self, vi: np.ndarray, extracts) -> List[Dict[str, object]]:
        out: List[Dict[str, object]] = []
        for i, e in enumerate(extracts):
            got: Dict[str, object] = {}
            for si, (cid, key) in enumerate(e.slots):
                idx = int(vi[i, si])
                if idx >= 0:
                    got[key] = e.values[idx]
            out.append(got)
        return out

    def merge_map_docs_sharded(self, extracts: Sequence[MapExtract]) -> List[Dict[str, object]]:
        """Op-axis-sharded LWW merge for very large imports (SURVEY.md
        §2.4 "sp"): op rows shard over the mesh's ops axis; per-shard
        scatter-max partials combine with pmax collectives.  Requires a
        Fleet built on a 2D mesh (make_mesh(op_parallel=k)).  Same
        output contract as merge_map_docs."""
        op_dim = self.mesh.shape[OP_AXIS]
        if op_dim <= 1:
            return self.merge_map_docs(extracts)
        m = pad_bucket(max(1, max(len(e.slot) for e in extracts)))
        m = ((m + op_dim - 1) // op_dim) * op_dim  # divisible by the op axis
        s = max(1, max(len(e.slots) for e in extracts))
        d_pad = _mesh_pad(self.mesh, len(extracts))
        _obs_merge(
            "map_sharded", len(extracts), sum(len(e.slot) for e in extracts),
            m * d_pad, (m, s, d_pad, op_dim),
        )
        batched = self._batch_map_cols(extracts, m)
        sh = NamedSharding(self.mesh, P(DOC_AXIS, OP_AXIS))
        batched = _sup_launch("fleet.map_sharded", lambda: MapOpCols(
            *[jax.device_put(np.asarray(a), sh) for a in batched]
        ))
        fn = _lww_sharded_fn(self.mesh, s)
        vi, _, _ = _sup_launch("fleet.map_sharded", lambda: fn(batched))
        return self._map_winner_values(_sup_fetch("fleet.map_sharded", vi), extracts)


def _pad_axis1(arrays: Dict[str, "jax.Array"], new_n: int, fills: Dict[str, object], sh) -> Dict[str, "jax.Array"]:
    """Re-pad (d, n) device arrays to (d, new_n) with per-field fills —
    the repack half of the resident grow path.  Host round trip: growth
    is rare (power-of-two buckets) and the simple path is shape-safe."""
    out = {}
    for f, a in arrays.items():
        h = np.asarray(a)
        nh = np.full((h.shape[0], new_n), fills[f], h.dtype)
        nh[:, : h.shape[1]] = h
        out[f] = jax.device_put(nh, sh)
    return out


def _grow_target(required: int, current: int) -> int:
    """Next power-of-two-style bucket >= required, at least 2x current
    (avoids repeated small regrows)."""
    from ..ops.fugue_batch import pad_bucket

    return pad_bucket(required, floor=max(16, 2 * current))


def _lww_fills(value_fill: int) -> Dict[str, object]:
    """Fill values for LwwResident columns — ONE table shared by the
    grow()/import paths of the map and movable batches so they cannot
    drift from each other (the value fill is the only per-use field)."""
    from ..ops.lww import NEG

    return dict(lamport=int(NEG), peer_hi=0, peer_lo=0, value=value_fill)


def _resolve_row(overlay, idmap, key, di, what):
    """Overlay-then-idmap row lookup that raises a typed, actionable
    error for unknown ids (shared by every resident ingest walk)."""
    r = overlay.get(key)
    if r is not None:
        return r
    try:
        return idmap[key]
    except KeyError:
        from ..errors import LoroError

        raise LoroError(
            f"doc {di}: {what} references unknown element {key} — resident "
            "batches need every doc's FULL history from its first epoch "
            "(feed the base import before deltas)"
        ) from None


_KEEP_MIN_VALUES = 64
_KEEP_BYTES_PER_VALUE = 256


def _keeps_positions(payload: bytes, n: int) -> bool:
    """Whether ``n`` values a store names in ``payload`` are worth holding
    the payload for.  Fewer values are decoded when first read, so a
    document fed by many small pushes reads from one list and not from
    thousands of payloads; more than 256 payload bytes a value are
    decoded at compaction, so the bytes a store holds follow the values
    that live, not the history that made them."""
    return n >= _KEEP_MIN_VALUES and len(payload) <= _KEEP_BYTES_PER_VALUE * n


def _by_chunk(s: np.ndarray):
    """``(chunk, positions in s)`` for each chunk ``s`` names, the
    positions in their order in ``s``."""
    if (s == s[0]).all():
        yield int(s[0]), np.arange(len(s))
        return
    order = np.argsort(s, kind="stable")
    for idx in np.split(order, np.flatnonzero(np.diff(s[order])) + 1):
        yield int(s[idx[0]]), idx


class _ValueColumns:
    """One document's List values as columns, in content-ordinal order:
    value ``i`` is in chunk ``src[i]`` at ``off[i]``.  Chunk 0 is
    ``decoded``, a list of values (the Python walk's, a restored state's,
    a small payload's), the offset an index into it; chunk ``c > 0`` is
    ``payloads[c - 1]``, a payload the native explode read, ``(bytes,
    cids)``, the offset that of the tagged value in the bytes.  A payload
    is the object the round and its WAL record already hold: a round
    copies and decodes nothing and makes no Python object a value; what
    it adds is settled into the columns when next read
    (``_keeps_positions``)."""

    __slots__ = ("decoded", "payloads", "_src", "_off", "_parts", "_n")

    def __init__(self, values=()):
        self.decoded = list(values)
        self.payloads: list = []
        self._src = np.zeros(len(self.decoded), np.int32)
        self._off = np.arange(len(self.decoded), dtype=np.int64)
        self._parts: list = []  # (payload or None, cids, offsets) since the last read
        self._n = len(self.decoded)

    def __len__(self) -> int:
        return self._n

    def __eq__(self, other) -> bool:
        if isinstance(other, _ValueColumns):
            other = other.tolist()
        return isinstance(other, list) and self.tolist() == other

    __hash__ = None

    def add_positions(self, payload: bytes, cids, offsets: np.ndarray) -> None:
        if len(offsets):
            self._parts.append((payload, cids, offsets))
            self._n += len(offsets)

    def add_values(self, values: list) -> None:
        if values:
            at = len(self.decoded)
            self.decoded.extend(values)
            self._parts.append(
                (None, None, np.arange(at, len(self.decoded), dtype=np.int64)))
            self._n += len(values)

    def _columns(self):
        if self._parts:
            src, off = [self._src], [self._off]
            for payload, cids, o in self._parts:
                c = 0
                if payload is not None:
                    if _keeps_positions(payload, len(o)):
                        self.payloads.append((payload, cids))
                        c = len(self.payloads)
                    else:
                        at = len(self.decoded)
                        self.decoded.extend(read_values_at(payload, o, cids))
                        o = np.arange(at, len(self.decoded), dtype=np.int64)
                src.append(np.full(len(o), c, np.int32))
                off.append(o)
            self._src, self._off = np.concatenate(src), np.concatenate(off)
            self._parts = []
        return self._src, self._off

    def _read(self, c: int, offsets: np.ndarray) -> list:
        if c == 0:
            return [self.decoded[i] for i in offsets.tolist()]
        payload, cids = self.payloads[c - 1]
        return read_values_at(payload, offsets, cids)

    def take(self, ordinals) -> list:
        """The values at ``ordinals``, decoded, in that order."""
        from ..errors import DecodeError

        ordinals = np.asarray(ordinals, np.int64)
        if not len(ordinals):
            return []
        if ordinals.min() < 0 or ordinals.max() >= self._n:
            raise DecodeError(
                "resident batch: content ordinal outside the value store "
                "(corrupt restored state?)")
        src, off = self._columns()
        s, o = src[ordinals], off[ordinals]
        out = [None] * len(ordinals)
        for c, idx in _by_chunk(s):
            if len(idx) == len(out):
                return self._read(c, o)
            for i, v in zip(idx.tolist(), self._read(c, o[idx])):
                out[i] = v
        return out

    def tolist(self) -> list:
        return self.take(np.arange(self._n))

    def select(self, ordinals: np.ndarray) -> "_ValueColumns":
        """A store of the values at ``ordinals`` alone (compaction): the
        decoded values that survive go into a new list, a payload is kept
        while ``_keeps_positions`` holds for its survivors and is decoded
        into that list otherwise."""
        src, off = self._columns()
        s, o = src[ordinals], off[ordinals]
        out = _ValueColumns()
        out._src = np.zeros(len(s), np.int32)
        out._off = np.empty(len(s), np.int64)
        out._n = len(s)
        for c, idx in (_by_chunk(s) if len(s) else ()):
            if c and _keeps_positions(self.payloads[c - 1][0], len(idx)):
                out.payloads.append(self.payloads[c - 1])
                out._src[idx] = len(out.payloads)
                out._off[idx] = o[idx]
            else:
                at = len(out.decoded)
                out.decoded.extend(self._read(c, o[idx]))
                out._off[idx] = np.arange(at, len(out.decoded))
        return out


class DeviceDocBatch:
    """Device-resident document batch with incremental ingest.

    SURVEY.md §7 step 9: "state lives on device for bulk workloads".
    The element tables stay on device between syncs; each `append` ships
    only the new rows/tombstones, and `texts()` re-resolves order in one
    launch.  Uses the row-order-free kernel (SeqColumnsU) because
    appended rows land in the buffer tail, not in (peer, counter) order.
    """

    def __init__(self, n_docs: int, capacity: int, mesh=None, as_text: bool = True,
                 auto_grow: bool = False):
        """as_text=False holds List containers: contents become per-doc
        value ordinals (host keeps the value stores) and values() is the
        materializer instead of texts().  auto_grow=True repacks the
        batch to the next capacity bucket instead of raising when an
        append overflows (long-lived server lifecycle)."""
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_docs = n_docs
        d_mesh = self.mesh.shape[DOC_AXIS]
        self.d = ((n_docs + d_mesh - 1) // d_mesh) * d_mesh  # mesh-padded
        n_docs = self.d
        self.cap = capacity
        self.as_text = as_text
        self.auto_grow = auto_grow
        self._c_pad = 256  # chain budget (doubles on overflow)
        self.counts = np.zeros(n_docs, np.int64)  # used rows per doc
        # ingest epochs date rows + tombstones for compaction: a
        # tombstone may be reclaimed once every replica has acked the
        # epoch that ingested its delete; row dates let layered batches
        # (DeviceMovableBatch) date supersessions by their winner's
        # ingest epoch (see compact())
        self.epoch = 0
        self.tomb_epoch = np.full((n_docs, capacity), -1, np.int64)
        self.row_epoch = np.full((n_docs, capacity), -1, np.int64)
        # host-side id -> row resolution per doc (C++ map of runs when the
        # native lib is available; batch stage/lookup/commit contract —
        # see parallel/idmap.py)
        from .idmap import make_idmap

        self.id2row = [make_idmap() for _ in range(n_docs)]
        # List batches: each document's values as columns of payload
        # positions (_ValueColumns), decoded only where they are read
        self.value_store: List[_ValueColumns] = [_ValueColumns() for _ in range(n_docs)]
        # richtext: per-doc style-anchor metadata ((peer, ctr) -> dict)
        # + device-row backmap so delete tombstones deactivate pairs
        self.anchor_meta: List[Dict[Tuple[int, int], dict]] = [dict() for _ in range(n_docs)]
        self.anchor_by_row: List[Dict[int, Tuple[int, int]]] = [dict() for _ in range(n_docs)]
        # incremental order: per-doc host ShadowOrder assigns standing
        # 64-bit order keys in O(delta); materialization sorts by key
        # instead of re-ranking the table (VERDICT round-1 item 4).
        # The C++ engine (native/codec.cpp loro_order_*) is used when
        # available — bit-identical keys; LORO_PY_ORDER=1 forces the
        # Python engine (the differential oracle).
        self.order = [self._fresh_order() for _ in range(n_docs)]
        from ..ops.fugue_batch import SeqColumnsU

        sh = doc_sharding(self.mesh)
        z = lambda dt, fill: jax.device_put(
            np.full((n_docs, capacity), fill, dt), sh
        )
        self.cols = SeqColumnsU(
            parent=z(np.int32, -1),
            side=z(np.int32, 0),
            peer_hi=z(np.uint32, 0),
            peer_lo=z(np.uint32, 0),
            counter=z(np.int32, 0),
            deleted=z(bool, True),
            content=z(np.int32, -1),
            valid=z(bool, False),
        )
        self.key_hi = z(np.uint32, 0xFFFFFFFF)
        self.key_lo = z(np.uint32, 0xFFFFFFFF)
        # coalesced-ingest accumulator (None = every append launches its
        # own device scatter; see begin_coalesce)
        self._defer: Optional[_DeferredSeqDevice] = None
        # serializes device-array writers: a detached commit (pipeline
        # commit thread) vs a grow() triggered by the NEXT group's host
        # staging — the only two that can ever overlap
        self._dev_lock = named_rlock("fleet.dev")

    # column fill values shared by __init__, grow() and compact() —
    # one table so the three cannot drift
    _COL_FILLS = dict(
        parent=-1, side=0, peer_hi=0, peer_lo=0, counter=0,
        deleted=True, content=-1, valid=False,
    )

    # -- round coalescing ----------------------------------------------
    # Contract (docs/RESILIENCE.md "round coalescing"): between
    # begin_coalesce() and flush_coalesce(), every append commits its
    # HOST state per round exactly as before — epoch clock, row/tomb
    # epoch stamps, order engines, id maps, counts — so the final state
    # is byte-for-byte what the serial path produces; only the DEVICE
    # block scatters/tombstone launches accumulate, and flush ships
    # them as ONE scatter (+ one tombstone launch) for the whole group.
    # Reads between begin and flush would see stale device columns:
    # the caller (ResidentServer.ingest_coalesced) never reads inside a
    # group.
    def begin_coalesce(self) -> None:
        if self._defer is not None:
            raise RuntimeError("coalesce group already open")
        self._defer = _DeferredSeqDevice(self.counts.copy())

    def detach_coalesce(self):
        """Close the group and hand back its pending device work for a
        later ``commit_detached`` — the two-phase form the pipeline
        executor uses to overlap group N's device commit with group
        N+1's host staging.  Everything the commit needs from host
        state is SNAPSHOTTED here (renumbered key rows, group-start
        offsets), so the commit thread never reads order engines / id
        maps / epoch arrays the next group is already mutating."""
        d, self._defer = self._defer, None
        if d is not None and d.renumbered:
            d.key_snap = {
                di: np.asarray(self.order[di].all_keys(), np.int64).copy()
                for di in sorted(d.renumbered)
            }
        return d

    def commit_detached(self, d) -> None:
        """Ship a detached group's blocks as one merged scatter + one
        tombstone launch.  Per-doc row segments across rounds are
        contiguous (appends only ever extend the tail), so the merged
        block is each doc's concatenated segments at its group-start
        offset — one block row per document of the UNION of the
        documents the group's rounds name (``_scatter_rows``' layout).
        Never grows: a grow here would race the next group's host
        staging (epoch arrays repack) — a merged window that outgrew
        capacity by bucket rounding falls back to per-round scatters,
        each already validated at stage time."""
        from ..ops.fugue_batch import pad_bucket

        if d is None:
            return
        with self._dev_lock:
            if d.rounds:
                total = np.zeros(self.d, np.int64)
                for _blk, _kh, _kl, docs, r_new in d.rounds:
                    total[docs] += r_new
                named = np.flatnonzero(total)
                width = pad_bucket(int(total.max()), floor=16)
                need = int((d.base0[named] + width).max())
                if need > self.cap:
                    off = d.base0.astype(np.int64).copy()
                    for blk, kh, kl, docs, r_new in d.rounds:
                        self._device_commit_block(
                            blk, kh, kl, docs, off[docs], int(r_new.sum()),
                            renumbered=(),
                        )
                        off[docs] += r_new
                    if d.renumbered:
                        self._upload_renumbered_keys(
                            sorted(d.renumbered), d.key_snap
                        )
                else:
                    blk_shape = (_named_bucket(len(named)), width)
                    blk = {
                        f: np.full(blk_shape, fill,
                                   dtype=d.rounds[0][0][f].dtype)
                        for f, fill in self._COL_FILLS.items()
                    }
                    khc = np.full(blk_shape, 0xFFFFFFFF, np.uint32)
                    klc = np.full(blk_shape, 0xFFFFFFFF, np.uint32)
                    row_of = np.zeros(self.d, np.int64)
                    row_of[named] = np.arange(len(named))
                    pos = np.zeros(len(named), np.int64)
                    for rblk, rkh, rkl, docs, r_new in d.rounds:
                        for j, k in enumerate(r_new):
                            i = int(row_of[docs[j]])
                            p = int(pos[i])
                            for f in blk:
                                blk[f][i, p : p + k] = rblk[f][j, :k]
                            khc[i, p : p + k] = rkh[j, :k]
                            klc[i, p : p + k] = rkl[j, :k]
                            pos[i] += k
                    self._device_commit_block(
                        blk, khc, klc, named, d.base0[named],
                        int(total.sum()), sorted(d.renumbered), d.key_snap,
                    )
                obs.counter("pipeline.coalesced_rounds_total").inc(
                    len(d.rounds), family="text" if self.as_text else "list"
                )
            elif d.renumbered:
                # delete-only / no-op rounds can still renumber docs
                self._upload_renumbered_keys(sorted(d.renumbered), d.key_snap)
            if d.del_d:
                self._device_mark_deleted(
                    np.concatenate(d.del_d), np.concatenate(d.del_r)
                )

    def flush_coalesce(self) -> None:
        """Synchronous close-and-commit of the open group."""
        self.commit_detached(self.detach_coalesce())

    def _device_commit_block(self, blk, key_blk_hi, key_blk_lo, named,
                             offsets, n_rows, renumbered, key_snap=None) -> None:
        """The device tail of an append: one scatter of the block of the
        documents the round names (+ whole-row key re-uploads for
        renumbered docs).  Block row ``j`` holds document ``named[j]``'s
        new rows for ``offsets[j]``; the block's rows past ``len(named)``
        are padding, which goes up under the document index -1 and is
        dropped by ``_scatter_rows``; ``n_rows`` is the block's live
        rows.  Shared by the immediate path and commit_detached.  On a
        mesh of several devices the block goes up replicated — the named
        documents lie on any of them — and each device writes those it
        holds."""
        k_pad, width = blk["valid"].shape
        d_idx = np.full(k_pad, -1, np.int32)
        d_idx[: len(named)] = named
        off = np.zeros(k_pad, np.int32)
        off[: len(named)] = offsets
        obs.counter("fleet.pad_waste_rows_total").inc(
            int(k_pad * width - n_rows), family="resident_seq"
        )
        obs.counter("fleet.device_launches_total").inc(family="resident_seq")
        obs.unique("fleet.padded_shapes_distinct").add(
            ("resident_seq", k_pad, width, self.cap)
        )
        with self._dev_lock:
            rep = replicated(self.mesh)
            with tracing.span("resident.upload", docs=len(named)):
                blk_dev = {f: jax.device_put(v, rep) for f, v in blk.items()}
                blk_dev["key_hi"] = jax.device_put(key_blk_hi, rep)
                blk_dev["key_lo"] = jax.device_put(key_blk_lo, rep)
            with tracing.span("resident.scatter", renumbered=len(renumbered)):
                packed = _scatter_rows(
                    (self.cols, self.key_hi, self.key_lo),
                    blk_dev,
                    jax.device_put(d_idx, rep),
                    jax.device_put(off, rep),
                    self.mesh,
                )
                self.cols, self.key_hi, self.key_lo = packed
                if renumbered:
                    self._upload_renumbered_keys(list(renumbered), key_snap)

    def _upload_renumbered_keys(self, renumbered, key_snap=None) -> None:
        """Renumbered docs: re-upload whole key rows in ONE jitted
        scatter (the per-doc eager .at[di].set dispatch was ~half of
        warm epoch time — r5 profile).  Fixed [cap]-wide rows + bucket-
        padded doc count bound retraces; pad entries repeat doc
        renumbered[0]'s row (idempotent writes).  ``key_snap`` (doc ->
        key array) is the detach-time snapshot a pipelined commit uses
        — the live engines belong to the group being staged."""
        from ..ops.fugue_batch import pad_bucket

        from .order_maintenance import split_keys

        nb = pad_bucket(len(renumbered), floor=4)
        kh_rows = np.empty((nb, self.cap), np.uint32)
        kl_rows = np.empty((nb, self.cap), np.uint32)
        d_idx = np.empty(nb, np.int32)
        for i in range(nb):
            di = renumbered[i] if i < len(renumbered) else renumbered[0]
            d_idx[i] = di
            if i < len(renumbered):
                keys = (
                    key_snap[di] if key_snap is not None
                    else self.order[di].all_keys()
                )
                kh, kl = split_keys(np.asarray(keys, np.int64))
                kh_rows[i, : len(kh)] = kh
                kl_rows[i, : len(kl)] = kl
                kh_rows[i, len(kh):] = 0xFFFFFFFF
                kl_rows[i, len(kl):] = 0xFFFFFFFF
            else:
                kh_rows[i] = kh_rows[0]
                kl_rows[i] = kl_rows[0]
        with self._dev_lock:
            self.key_hi, self.key_lo = _set_key_rows(
                (self.key_hi, self.key_lo),
                jnp.asarray(d_idx),
                jnp.asarray(kh_rows),
                jnp.asarray(kl_rows),
            )

    def _device_mark_deleted(self, d_all: np.ndarray, r_all: np.ndarray) -> None:
        """The device tail of mark_deleted (padded tombstone scatter)."""
        from ..ops.fugue_batch import pad_bucket

        n = len(d_all)
        k = pad_bucket(n, floor=16)
        d_idx = np.empty(k, np.int32)
        r_idx = np.empty(k, np.int32)
        d_idx[:n], r_idx[:n] = d_all, r_all
        d_idx[n:], r_idx[n:] = d_all[0], r_all[0]
        with self._dev_lock:
            deleted = _set_deleted(
                self.cols.deleted, jnp.asarray(d_idx), jnp.asarray(r_idx)
            )
            self.cols = self.cols._replace(deleted=deleted)

    # ------------------------------------------------------------------
    def grow(self, new_capacity: int) -> None:
        """Repack the resident columns to a larger row capacity (device
        re-pad; order engines, id maps, counts and host metadata are
        capacity-independent).  Part of the resident lifecycle: a
        long-lived server grows instead of dying at the initial bucket
        (r4 verdict #6).  Reference analog: the reference re-allocates
        its tracker arenas as docs grow (crates/loro-internal/src/
        container/richtext/tracker.rs)."""
        if new_capacity <= self.cap:
            return
        # under the device lock: a pipelined commit in flight is
        # scattering into the SAME buffers this repack replaces
        with self._dev_lock:
            sh = doc_sharding(self.mesh)
            cols = _pad_axis1(
                {f: getattr(self.cols, f) for f in self.cols._fields},
                new_capacity, self._COL_FILLS, sh,
            )
            from ..ops.fugue_batch import SeqColumnsU

            self.cols = SeqColumnsU(**cols)
            keys = _pad_axis1(
                {"key_hi": self.key_hi, "key_lo": self.key_lo},
                new_capacity,
                {"key_hi": 0xFFFFFFFF, "key_lo": 0xFFFFFFFF},
                sh,
            )
            self.key_hi, self.key_lo = keys["key_hi"], keys["key_lo"]
            for name in ("tomb_epoch", "row_epoch"):
                ne = np.full((self.d, new_capacity), -1, np.int64)
                ne[:, : self.cap] = getattr(self, name)
                setattr(self, name, ne)
            self.cap = new_capacity

    def release_doc(self, di: int) -> None:
        """Reset doc ``di`` to a never-used slot (tiered-residency
        eviction, parallel/residency.py): every host structure back to
        its construction value, every device row back to its fill.  The
        CALLER owns the safety argument — the doc's state must already
        be preserved elsewhere (deep mirror anchor + journal) and no
        staged/in-flight device work may reference the doc (the
        residency manager only releases journal-stable docs).  Inside
        an open coalesce group the deferred base offset for the doc
        resets too, so a later round in the same group can land a new
        doc at row 0."""
        from .idmap import make_idmap

        self.counts[di] = 0
        self.tomb_epoch[di, :] = -1
        self.row_epoch[di, :] = -1
        self.id2row[di] = make_idmap()
        self.value_store[di] = _ValueColumns()
        self.anchor_meta[di] = {}
        self.anchor_by_row[di] = {}
        self.order[di] = self._fresh_order()
        if self._defer is not None:
            self._defer.base0[di] = 0
            self._defer.renumbered.discard(di)
        with self._dev_lock:
            fields = list(self.cols._fields)
            arrays = tuple(getattr(self.cols, f) for f in fields) + (
                self.key_hi, self.key_lo,
            )
            fills = tuple(self._COL_FILLS[f] for f in fields) + (
                0xFFFFFFFF, 0xFFFFFFFF,
            )
            out = _release_rows(arrays, jnp.int32(di), fills)
            from ..ops.fugue_batch import SeqColumnsU

            self.cols = SeqColumnsU(**dict(zip(fields, out[: len(fields)])))
            self.key_hi, self.key_lo = out[len(fields):]
        obs.counter("fleet.doc_releases_total").inc(
            family="text" if self.as_text else "list"
        )

    def compact(
        self,
        stable_epochs: Sequence[Optional[int]],
        extra_protect: Optional[Sequence[Optional[np.ndarray]]] = None,
        extra_dead: Optional[Sequence[Optional[np.ndarray]]] = None,
        return_remaps: bool = False,
    ):
        """Reclaim causally-stable tombstones (resident lifecycle, r4
        verdict #6; the reference analog is the shallow-snapshot floor,
        crates/loro-internal/src/encoding/shallow_snapshot.rs:16-40).

        ``extra_protect[di]`` (optional row arrays) marks rows a caller
        layers external references onto (DeviceMovableBatch's winning
        slot rows); ``extra_dead[di]`` marks rows the caller asserts
        are invisible AND stably so (superseded movable slots whose
        winner's ingest epoch every replica acked) — they join the
        droppable set under the same protection/subtree rules;
        ``return_remaps=True`` additionally returns {di: old-row ->
        new-row int array, -1 = dropped} so such callers can rewrite
        their references.

        ``stable_epochs[di]`` is the newest ingest epoch (``self.epoch``
        after an append) that EVERY replica of doc di has acknowledged
        integrating; None skips the doc.  A tombstone whose delete was
        ingested at epoch <= that is invisible at every replica, so no
        future op can treat it as visible.  Three keep-rules still
        apply, because Fugue ops CAN reference invisible rows:

        - attach-target protection: a future insert at the gap after a
          visible row `a` with R-children parents (side=L) on `a`'s
          total-order SUCCESSOR, tombstone or not; an insert at
          position 0 parents on the total-order FIRST row; and the
          anchor-aware expand walk (models/handlers._placement_with_
          expand) can end on the LAST tombstone of an invisible window,
          so every tombstone whose immediate successor is non-deleted
          is targetable too — all three classes stay (mirrors
          models/seq_crdt.placement_for_visible_pos + the expand walk);
        - live subtrees: a row with a surviving child stays (children's
          placements reference the parent chain) — EXCEPT a run-interior
          tombstone whose single live R-child is its run continuation,
          which drops by promoting that child into its place (safe: the
          only siblings the child could re-order against are same-peer
          counters inside the collapsed interval — the dropped chain
          itself; future same-peer ops carry higher counters);
        - undated tombstones (imported from pre-epoch checkpoints)
          never drop.

        Rebuilds the order engine, id map, anchors and device columns
        for compacted docs; returns rows reclaimed.  O(table) host pass
        — a rare maintenance op, not the hot path."""
        from .idmap import make_idmap
        from .order_maintenance import split_keys

        if len(stable_epochs) > self.d:
            raise ValueError(
                f"compact: {len(stable_epochs)} stable_epochs for a "
                f"{self.d}-doc batch"
            )
        stable_epochs = list(stable_epochs) + [None] * (self.d - len(stable_epochs))
        host = None  # fetched lazily on the first doc that compacts
        key_hi = key_lo = None
        reclaimed = 0
        remaps: Dict[int, np.ndarray] = {}
        for di, stable_e in enumerate(stable_epochs):
            if stable_e is None or not int(self.counts[di]):
                continue
            if host is None:
                host = {f: np.asarray(getattr(self.cols, f)).copy()
                        for f in self.cols._fields}
                key_hi = np.asarray(self.key_hi).copy()
                key_lo = np.asarray(self.key_lo).copy()
            k = int(self.counts[di])
            peer = (host["peer_hi"][di, :k].astype(np.uint64) << np.uint64(32)) | \
                host["peer_lo"][di, :k].astype(np.uint64)
            ctr = host["counter"][di, :k].astype(np.int64)
            parent = host["parent"][di, :k].astype(np.int64)
            deleted = host["deleted"][di, :k]
            side = host["side"][di, :k].astype(np.int64)
            te = self.tomb_epoch[di, :k]
            dead = deleted.copy()  # invisible rows: tombstones + caller's
            if extra_dead is not None and extra_dead[di] is not None:
                rows_d = np.asarray(extra_dead[di], np.int64)
                dead[rows_d[rows_d < k]] = True
            # attach-target protection from the standing total order
            order = np.lexsort((key_lo[di, :k], key_hi[di, :k]))
            protected = np.zeros(k, bool)
            protected[order[0]] = True  # global first (position-0 inserts)
            succ_of = np.full(k, -1, np.int64)
            succ_of[order[:-1]] = order[1:]
            has_r = np.zeros(k, bool)
            rmask = side == 1
            has_r[parent[rmask][parent[rmask] >= 0]] = True
            tgt = np.flatnonzero((~dead) & has_r & (succ_of >= 0))
            protected[succ_of[tgt]] = True
            if self.as_text:
                # expand-walk targets (TEXT only — style anchors can
                # appear at any future time and the anchor-aware walk
                # steps over tombstones, attaching to the LAST one of an
                # invisible window; list containers never grow anchors,
                # so their isolated slot tombstones stay reclaimable):
                # the last tombstone before any non-deleted row...
                nd_succ = np.flatnonzero(
                    (succ_of >= 0) & dead & ~dead[np.clip(succ_of, 0, k - 1)]
                )
                protected[nd_succ] = True
                # ...and the end-of-document window's final tombstone,
                # which has no successor
                protected[order[-1]] = True
            # anchor rows never drop, live OR dead: a dead END anchor
            # with a live start means "style runs to EOF" (richtexts'
            # dead-end-never-pops rule) — dropping the row would discard
            # its metadata and silently deactivate the style
            if self.anchor_by_row[di]:
                rows_a = np.fromiter(
                    self.anchor_by_row[di], np.int64, len(self.anchor_by_row[di])
                )
                protected[rows_a[rows_a < k]] = True
            if extra_protect is not None and extra_protect[di] is not None:
                rows_x = np.asarray(extra_protect[di], np.int64)
                protected[rows_x[rows_x < k]] = True
            stable_dead = deleted & (te >= 0) & (te <= int(stable_e))
            if extra_dead is not None and extra_dead[di] is not None:
                # caller-asserted stability: superseded rows join as-is
                stable_dead |= dead & ~deleted
            stable_dead &= ~protected
            # Reverse pass (children have higher indices than parents):
            # a stable tombstone drops when it anchors no live subtree —
            # either no live children at all (dead subtree), or exactly
            # one live R-child that is its run continuation, which then
            # PROMOTES into its place (chain collapse).  Promotion is
            # sibling-sort-safe: the promoted child keeps its identity
            # (peer, ctr); the only siblings it could re-order against
            # are same-peer rows with counters inside the collapsed
            # (T.ctr, C.ctr] interval — all of which are the dropped
            # chain rows themselves, and future same-peer ops always
            # carry higher counters.
            dparent = parent.copy()
            dside = side.copy()
            prom = ctr.copy()  # promoted placement counter (check only)
            live_l = np.zeros(k, np.int64)
            live_r = np.zeros(k, np.int64)
            only_r = np.full(k, -1, np.int64)  # valid when live_r == 1
            keep = np.zeros(k, bool)

            def credit(child: int, p: int, s: int) -> None:
                if p < 0:
                    return
                if s == 1:
                    live_r[p] += 1
                    only_r[p] = child if live_r[p] == 1 else -1
                else:
                    live_l[p] += 1

            for r in range(k - 1, -1, -1):
                if stable_dead[r] and live_l[r] == 0:
                    if live_r[r] == 0:
                        continue  # whole subtree dead: drop
                    if live_r[r] == 1:
                        c = int(only_r[r])
                        if peer[c] == peer[r] and prom[c] == ctr[r] + 1:
                            dparent[c] = parent[r]
                            dside[c] = side[r]
                            prom[c] = ctr[r]
                            credit(c, int(parent[r]), int(side[r]))
                            continue  # r drops, c takes its place
                keep[r] = True
                credit(r, int(dparent[r]), int(dside[r]))
            n_keep = int(keep.sum())
            if n_keep == k:
                continue
            reclaimed += k - n_keep
            old_rows = np.flatnonzero(keep)
            remap = np.full(k, -1, np.int64)
            remap[old_rows] = np.arange(n_keep)
            remaps[di] = remap
            new_parent = dparent[old_rows]
            pos = new_parent >= 0
            new_parent[pos] = remap[new_parent[pos]]
            new_side = dside[old_rows]
            # rebuild columns for this doc (tail restored to fills)
            for f in self.cols._fields:
                row = host[f][di]
                vals = row[:k][old_rows].copy()
                row[:] = self._COL_FILLS[f]
                row[:n_keep] = vals
            # list batches: drop stranded values and rewrite the content
            # ordinals over survivors (an empty store with content rows
            # is the externally-indexed movable-slot use — those
            # ordinals are NOT ours to rewrite, and there is no store
            # to shrink)
            if not self.as_text and self.value_store[di]:
                cvals = host["content"][di, :n_keep].astype(np.int64)
                uniq = np.unique(cvals[cvals >= 0])
                vmap = np.full(len(self.value_store[di]), -1, np.int64)
                vmap[uniq] = np.arange(len(uniq))
                host["content"][di, :n_keep] = np.where(
                    cvals >= 0, vmap[np.clip(cvals, 0, None)], cvals
                ).astype(host["content"].dtype)
                self.value_store[di] = self.value_store[di].select(uniq)
            host["parent"][di, :n_keep] = new_parent
            host["side"][di, :n_keep] = new_side  # promoted rows inherit
            te_new = te[old_rows].copy()
            self.tomb_epoch[di, :] = -1
            self.tomb_epoch[di, :n_keep] = te_new
            re_new = self.row_epoch[di, :k][old_rows]
            self.row_epoch[di, :] = -1
            self.row_epoch[di, :n_keep] = re_new
            # rebuild the order engine + standing keys by replay
            self.order[di] = self._fresh_order()
            keys = self.order[di].append_arrays(
                new_parent.astype(np.int32),
                host["side"][di, :n_keep],
                peer[old_rows],
                ctr[old_rows],
                0,
            )
            if keys is None:
                keys = self.order[di].all_keys()
            kh, kl = split_keys(np.asarray(keys, np.int64))
            key_hi[di] = 0xFFFFFFFF
            key_lo[di] = 0xFFFFFFFF
            key_hi[di, :n_keep] = kh
            key_lo[di, :n_keep] = kl
            # rebuild the id map over survivors only
            m = make_idmap()
            m.insert_arrays(
                peer[old_rows], ctr[old_rows], np.arange(n_keep, dtype=np.int32)
            )
            self.id2row[di] = m
            # anchors: drop dead rows' metadata, remap the survivors
            if self.anchor_meta[di]:
                new_meta = {}
                for pc, a in self.anchor_meta[di].items():
                    nr = remap[a["row"]] if a["row"] < k else -1
                    if nr >= 0:
                        new_meta[pc] = dict(a, row=int(nr))
                self.anchor_meta[di] = new_meta
                self.anchor_by_row[di] = {a["row"]: pc for pc, a in new_meta.items()}
            self.counts[di] = n_keep
        if host is not None and reclaimed:
            from ..ops.fugue_batch import SeqColumnsU

            sh = doc_sharding(self.mesh)
            self.cols = SeqColumnsU(
                **{f: jax.device_put(v, sh) for f, v in host.items()}
            )
            self.key_hi = jax.device_put(key_hi, sh)
            self.key_lo = jax.device_put(key_lo, sh)
        return (reclaimed, remaps) if return_remaps else reclaimed

    def _fresh_order(self):
        """A new order engine of the configured kind (compaction
        rebuild)."""
        import os as _os

        if _os.environ.get("LORO_PY_ORDER", "0") not in ("1", "true", "yes"):
            from ..native import native_order

            nat = native_order()
            if nat is not None:
                return nat
        from .order_maintenance import ShadowOrder

        _obs_fallback("order")
        return ShadowOrder()

    def append_changes(self, per_doc_changes: Sequence[Optional[Sequence[Change]]], cid) -> None:
        """Incremental ingest: each doc's new causally-ordered changes
        (None = no update).  Inserts (chars AND style anchors — anchors
        are real Fugue nodes other inserts may parent on) become new
        rows; deletes tombstone rows from any epoch.  All validation and
        id-map staging happens before any state mutates, so a capacity
        error leaves the batch untouched.  One device scatter per call."""
        per_doc_changes = list(per_doc_changes) + [None] * (self.d - len(per_doc_changes))
        rows_per_doc: List[List[Tuple[int, int, int, int, int]]] = []
        overlays: List[Dict[Tuple[int, int], int]] = []
        anchor_stages: List[Dict[Tuple[int, int], dict]] = []
        value_stages: List[list] = []
        del_pairs: List[Tuple[int, int]] = []
        for di, changes in enumerate(per_doc_changes):
            rows: List[Tuple[int, int, int, int, int]] = []
            overlay: Dict[Tuple[int, int], int] = {}
            stage: Dict[Tuple[int, int], dict] = {}
            vstage: list = []
            rows_per_doc.append(rows)
            overlays.append(overlay)
            anchor_stages.append(stage)
            value_stages.append(vstage)
            if changes:
                self._python_rows(di, changes, cid, rows, overlay, del_pairs, stage, vstage)
        self._commit_rows(rows_per_doc, overlays, del_pairs, anchor_stages, value_stages)

    def _python_rows(self, di, changes, cid, rows, overlay, del_pairs, anchor_stage, value_stage) -> None:
        """Pure-Python op walk producing (parent,side,counter,content,
        peer) rows + delete pairs + staged anchor metadata for one doc
        (also the fallback for the native delta path)."""
        from ..core.change import SeqDelete, SeqInsert, StyleAnchor
        from ..oplog.oplog import _RunCont

        base = int(self.counts[di])
        idmap = self.id2row[di]
        n_vals = len(self.value_store[di])

        def resolve(key):
            return _resolve_row(overlay, idmap, key, di, "op parent")

        for ch in changes:
            for op in ch.ops:
                if op.container != cid:
                    continue
                c = op.content
                if isinstance(c, SeqInsert):
                    body = [c.content] if isinstance(c.content, StyleAnchor) else c.content
                    for j in range(len(body)):
                        if j == 0:
                            if isinstance(c.parent, _RunCont):
                                prow = resolve((ch.peer, op.counter - 1))
                            elif c.parent is None:
                                prow = -1
                            else:
                                prow = resolve((c.parent.peer, c.parent.counter))
                            side = int(c.side)
                        else:
                            prow = base + len(rows) - 1
                            side = 1
                        row = base + len(rows)
                        overlay[(ch.peer, op.counter + j)] = row
                        if isinstance(body[j], StyleAnchor):
                            content = -1
                            a = body[j]
                            anchor_stage[(ch.peer, op.counter + j)] = {
                                "row": row,
                                "key": a.key,
                                "value": a.value,
                                "lamport": ch.lamport + (op.counter + j - ch.ctr_start),
                                "peer": ch.peer,
                                "start": a.is_start,
                                "deleted": False,
                            }
                        elif self.as_text:
                            content = ord(body[j])
                        else:
                            content = n_vals + len(value_stage)
                            value_stage.append(body[j])
                        rows.append((prow, side, op.counter + j, content, ch.peer))
                elif isinstance(c, SeqDelete):
                    # deletes tolerate unknown targets (same as the
                    # native paths): a missing target means the insert
                    # is missing too, which the parent resolution flags
                    for sp in c.spans:
                        for ctr in range(sp.start, sp.end):
                            row_d = overlay.get((sp.peer, ctr))
                            if row_d is None:
                                row_d = idmap.get((sp.peer, ctr))
                            if row_d is not None:
                                del_pairs.append((di, row_d))

    def _commit_rows(self, rows_per_doc, overlays, del_pairs, anchor_stages=None, value_stages=None) -> None:
        """Shared tail: validate capacity, commit staged id maps +
        anchor metadata, block-scatter new rows, tombstone deletes
        (append_changes and append_payloads both end here).  Per-doc
        entries are either tuple lists (Python walks) or column dicts
        (the native fast path, ids staged in the idmap: overlays[di] is
        None and commit/abort goes through the map's staging)."""
        from ..ops.fugue_batch import pad_bucket

        def n_of(r) -> int:
            return len(r["parent"]) if isinstance(r, dict) else len(r)

        n_new = [n_of(r) for r in rows_per_doc]
        max_new = pad_bucket(max(n_new, default=0), floor=16) if any(n_new) else 0
        # validate BEFORE mutating: the scatter window is max_new wide,
        # so every updated doc needs base + max_new <= capacity
        # (dynamic_update_slice would silently clamp otherwise)
        required = max(
            (int(self.counts[di]) + max_new for di, k in enumerate(n_new) if k),
            default=0,
        )
        if required > self.cap:
            if self.auto_grow:
                self.grow(_grow_target(required, self.cap))
            else:
                for dj, ov in enumerate(overlays):
                    if ov is None:
                        self.id2row[dj].abort()
                raise RuntimeError(
                    f"DeviceDocBatch capacity exceeded: a doc needs "
                    f"{required} rows > {self.cap} (pass auto_grow=True "
                    "or call grow())"
                )
        self.epoch += 1  # post-validation: dates this append's rows
        # commit staged id maps + anchor metadata: a row registers an id,
        # so the round's new rows are the ids committed
        told = {}
        if tracing.is_enabled():
            told = {"docs": sum(1 for k in n_new if k), "ids": sum(n_new)}
        with tracing.span("resident.commit_ids", **told):
            for di, overlay in enumerate(overlays):
                if overlay is None:
                    self.id2row[di].commit()
                elif overlay:
                    self.id2row[di].update(overlay)
            for di, stage in enumerate(anchor_stages or ()):
                if stage:
                    self.anchor_meta[di].update(stage)
                    self.anchor_by_row[di].update(
                        {a["row"]: pc for pc, a in stage.items()}
                    )
        if any(value_stages or ()):
            self._commit_values(value_stages)
        if max_new:
            from .order_maintenance import split_keys

            obs.counter("fleet.resident_rows_total").inc(
                sum(n_new), family="text" if self.as_text else "list"
            )
            # the block holds the documents the round NAMES: row j is
            # document active[j]'s, the rows past them keep the fills
            active = np.flatnonzero(n_new)
            blk_new = np.asarray(n_new, np.int64)[active]
            blk_shape = (_named_bucket(len(active)), max_new)
            # 34 B a row: the eight columns (26 B) and the two key words
            with tracing.span("resident.stage", docs=len(active),
                              bytes=34 * blk_shape[0] * max_new):
                blk = {
                    "parent": np.full(blk_shape, -1, np.int32),
                    "side": np.zeros(blk_shape, np.int32),
                    "peer_hi": np.zeros(blk_shape, np.uint32),
                    "peer_lo": np.zeros(blk_shape, np.uint32),
                    "counter": np.zeros(blk_shape, np.int32),
                    "deleted": np.ones(blk_shape, bool),
                    "content": np.full(blk_shape, -1, np.int32),
                    "valid": np.zeros(blk_shape, bool),
                }
                key_blk_hi = np.full(blk_shape, 0xFFFFFFFF, np.uint32)
                key_blk_lo = np.full(blk_shape, 0xFFFFFFFF, np.uint32)
            offsets = np.zeros(len(active), np.int32)

            def _ingest_doc(j: int) -> bool:
                """Per-doc host work (block fill + order append) for
                block row ``j``: writes touch doc-disjoint slices/state
                only, and the native order engine's ctypes call releases
                the GIL, so docs shard across threads.  Returns True
                when the doc's keys were renumbered (caller re-uploads
                the whole key row)."""
                di = int(active[j])
                rows = rows_per_doc[di]
                base = int(self.counts[di])
                if isinstance(rows, dict):
                    k = len(rows["parent"])
                    parent, side_a = rows["parent"], rows["side"]
                    ctr_a, content_a = rows["counter"], rows["content"]
                    pu = rows["peer"]
                else:
                    k = len(rows)
                    arr = np.asarray(
                        [(r[0], r[1], r[2], r[3]) for r in rows], np.int64
                    )
                    pu = np.asarray([r[4] for r in rows], np.uint64)
                    parent, side_a = arr[:, 0], arr[:, 1]
                    ctr_a, content_a = arr[:, 2], arr[:, 3]
                blk["parent"][j, :k] = parent
                blk["side"][j, :k] = side_a
                blk["peer_hi"][j, :k] = (pu >> np.uint64(32)).astype(np.uint32)
                blk["peer_lo"][j, :k] = (pu & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                blk["counter"][j, :k] = ctr_a
                blk["deleted"][j, :k] = False
                blk["content"][j, :k] = content_a
                blk["valid"][j, :k] = True
                self.row_epoch[di, base : base + k] = self.epoch
                keys = self.order[di].append_arrays(
                    parent, side_a, pu, ctr_a, base
                )
                renum = keys is None
                if not renum:
                    kh, kl = split_keys(np.asarray(keys, np.int64))
                    key_blk_hi[j, :k] = kh
                    key_blk_lo[j, :k] = kl
                offsets[j] = base
                self.counts[di] += k
                return renum

            # thread fan-out only pays when the order engine is the
            # native one (ctypes releases the GIL); the Python
            # ShadowOrder fallback would serialize through the GIL and
            # eat pool-spawn overhead on the hot path
            from ..native import NativeShadowOrder

            native_engine = bool(self.order) and isinstance(
                self.order[0], NativeShadowOrder
            )
            n_threads = min(
                int(os.environ.get("LORO_ORDER_THREADS") or (os.cpu_count() or 1))
                if native_engine
                else 1,
                max(1, len(active)),
            )
            with tracing.span("resident.order", docs=len(active),
                              workers=n_threads):
                if n_threads > 1:
                    from concurrent.futures import ThreadPoolExecutor

                    with ThreadPoolExecutor(max_workers=n_threads) as pool:
                        renums = list(pool.map(_ingest_doc, range(len(active))))
                else:
                    renums = [_ingest_doc(j) for j in range(len(active))]
                renumbered = [int(di) for di, renum in zip(active, renums) if renum]
            if self._defer is not None:
                # coalesced group: stash the block; flush_coalesce ships
                # every round's segments in one merged scatter
                self._defer.rounds.append(
                    (blk, key_blk_hi, key_blk_lo, active, blk_new)
                )
                self._defer.renumbered.update(renumbered)
            else:
                self._device_commit_block(
                    blk, key_blk_hi, key_blk_lo, active, offsets,
                    int(blk_new.sum()), renumbered,
                )
        self.mark_deleted(del_pairs)

    def _commit_values(self, value_stages) -> None:
        """The round's values into each document's columns: a stage is
        the Python walk's list of decoded values, or the native explode's
        ``(payload, cids, value offsets)``, kept as positions."""
        docs = sum(1 for vs in value_stages if vs)
        n = sum(len(vs[2]) if isinstance(vs, tuple) else len(vs)
                for vs in value_stages if vs)
        with tracing.span("resident.values", docs=docs, values=n):
            for di, vs in enumerate(value_stages):
                if isinstance(vs, tuple):
                    self.value_store[di].add_positions(*vs)
                elif vs:
                    self.value_store[di].add_values(vs)
        obs.counter("fleet.resident_values_total").inc(n, family="list")

    def append_payloads(self, per_doc_payloads: Sequence[Optional[bytes]], cid) -> None:
        """Incremental NATIVE ingest: envelope-stripped binary payloads
        -> C++ delta explode (cross-epoch parents/deletes resolved
        through the per-doc id maps; a List's values kept as their
        positions in the payload) -> one block scatter.  Falls back to
        append_changes via the Python decoder per payload when the
        native library is unavailable."""
        from ..codec.binary import decode_changes
        from ..native import available

        if not available():
            _obs_fallback("payload_decode")
            self.append_changes(
                [decode_changes(p) if p else None for p in per_doc_payloads], cid
            )
            return
        per_doc_payloads = list(per_doc_payloads) + [None] * (self.d - len(per_doc_payloads))
        try:
            self._append_payloads_staged(per_doc_payloads, cid)
        except BaseException:
            # ANY escaping error must roll back native-staged ids: the
            # C++ maps are long-lived, and a later commit would publish
            # phantom (peer, ctr) -> row mappings for rows that were
            # never scattered (post-commit aborts are no-ops)
            for di in range(self.d):
                self.id2row[di].abort()
            raise

    def _append_payloads_staged(self, per_doc_payloads, cid) -> None:
        with tracing.span("resident.decode"):
            staged = self._decode_payloads_staged(per_doc_payloads, cid)
        self._commit_rows(*staged)

    def _decode_payloads_staged(self, per_doc_payloads, cid) -> tuple:
        """The per-document half of the native ingest: tables, the C++
        delta explode, id-map staging and lookups.  Returns
        ``_commit_rows``' arguments."""
        from ..codec.binary import decode_changes, read_tables
        from ..native import (
            decode_value_at,
            explode_seq_anchor_meta,
            explode_seq_delta_payload,
        )

        rows_per_doc: List[list] = []
        overlays: List[Dict[Tuple[int, int], int]] = []
        anchor_stages: List[Dict[Tuple[int, int], dict]] = []
        value_stages: List[list] = []
        del_pairs: List[Tuple[int, int]] = []
        for di, payload in enumerate(per_doc_payloads):
            rows: list = []
            overlay: Dict[Tuple[int, int], int] = {}
            stage: Dict[Tuple[int, int], dict] = {}
            vstage: list = []
            rows_per_doc.append(rows)
            overlays.append(overlay)
            anchor_stages.append(stage)
            value_stages.append(vstage)
            if not payload:
                continue
            n_dels_start = len(del_pairs)
            try:
                peers_wire, _keys, cids, _r = read_tables(payload)
                try:
                    target = cids.index(cid)
                except ValueError:
                    continue  # no ops for this container
                out = explode_seq_delta_payload(payload, target)
                anchor_cols = None
                if self.as_text and (out["content"] == -1).any():
                    # style anchors: fetch their metadata natively (same
                    # row numbering as the main explode) so richtexts()
                    # keeps its pair table without the python walk
                    anchor_cols = explode_seq_anchor_meta(payload, target)
                base = int(self.counts[di])
                idmap = self.id2row[di]
                # columnar end-to-end: the id registrations ride the
                # native map's staging (committed in _commit_rows), ext
                # parents and delete spans resolve in TWO batch lookups
                # — no per-row Python dict/tuple traffic (r4 verdict #5)
                peers_np = np.asarray(peers_wire, np.uint64)
                peer_u64 = peers_np[out["peer_idx"]]
                ctr64 = out["counter"].astype(np.int64)
                idmap.stage_base(peer_u64, ctr64, base)
                prow_arr = np.where(
                    out["parent"] >= 0, base + out["parent"], out["parent"]
                ).astype(np.int32)
                ext_rows = np.flatnonzero(out["parent"] == -2)
                if len(ext_rows):
                    res = idmap.lookup(
                        peers_np[out["ext_peer_idx"][ext_rows]],
                        out["ext_counter"][ext_rows],
                    )
                    if (res < 0).any():
                        raise KeyError("unresolved cross-epoch parent")
                    prow_arr[ext_rows] = res
                content = out["content"]
                if not self.as_text:
                    voff = out["value_off"]
                    if voff is None or (voff < 0).any():
                        raise ValueError("a List batch names a text container")
                    # the payload numbers its values from 0: they follow
                    # the document's own, and stay positions until read
                    content = content + len(self.value_store[di])
                    if len(voff):
                        value_stages[di] = (payload, cids, voff)
                rows_per_doc[di] = {
                    "parent": prow_arr,
                    "side": out["side"],
                    "counter": out["counter"],
                    "content": content,
                    "peer": peer_u64,
                }
                overlays[di] = None  # marker: ids staged in the idmap
                if anchor_cols is not None:
                    for ai in range(len(anchor_cols["row"])):
                        rrow = int(anchor_cols["row"][ai])
                        a_peer = int(peer_u64[rrow])
                        stage[(a_peer, int(out["counter"][rrow]))] = {
                            "row": base + rrow,
                            "key": _keys[int(anchor_cols["key_idx"][ai])],
                            "value": decode_value_at(
                                payload, int(anchor_cols["voffset"][ai]), cids
                            ),
                            "lamport": int(anchor_cols["lamport"][ai]),
                            "peer": a_peer,
                            "start": bool(anchor_cols["flags"][ai] & 1),
                            "deleted": False,
                        }
                lens = (out["del_end"] - out["del_start"]).astype(np.int64)
                tot = int(lens.sum())
                if tot:
                    dp = np.repeat(peers_np[out["del_peer_idx"]], lens)
                    offs = np.repeat(np.cumsum(lens) - lens, lens)
                    dctr = np.arange(tot, dtype=np.int64) - offs + np.repeat(
                        out["del_start"], lens
                    )
                    drows = idmap.lookup(dp, dctr)
                    # deletes tolerate unknown targets (as the walks do)
                    drows = drows[drows >= 0]
                    if len(drows):
                        del_pairs.append((di, drows))
            except (KeyError, ValueError):
                # unresolvable refs or malformed input for the native
                # path: python fallback for this payload only
                _obs_fallback("payload_decode" if self.as_text else "list_payload")
                self.id2row[di].abort()
                rows.clear()
                rows_per_doc[di] = rows
                overlay.clear()
                overlays[di] = overlay
                stage.clear()
                vstage.clear()
                value_stages[di] = vstage
                del del_pairs[n_dels_start:]
                self._python_rows(
                    di, decode_changes(payload), cid, rows, overlay, del_pairs,
                    stage, vstage,
                )
        return rows_per_doc, overlays, del_pairs, anchor_stages, value_stages

    def mark_deleted(self, pairs) -> None:
        """Tombstone (doc, rows) entries (delete ops referencing earlier
        appends).  Each entry is (doc, row) or (doc, row_ndarray) — the
        columnar ingest path ships whole per-doc delete chunks.  Padded
        to buckets (idempotent repeats of the first pair) to bound
        retraces.

        Advances the epoch clock and dates the new tombstones with the
        fresh epoch — including direct public calls, so an out-of-band
        delete can never be stamped with an epoch replicas already
        acked (which would let compact() reclaim a never-propagated
        delete).  Runs after all ingest validation, so a failed append
        leaves the clock untouched."""
        if not pairs:
            return
        with tracing.span("resident.tombstone"):
            self._mark_deleted(pairs)

    def _mark_deleted(self, pairs) -> None:
        self.epoch += 1
        d_parts: List[np.ndarray] = []
        r_parts: List[np.ndarray] = []
        for di, row in pairs:  # deactivate style pairs whose anchor died
            abr = self.anchor_by_row[di]
            if isinstance(row, np.ndarray):
                if abr:  # anchors are rare; skip the loop when none
                    for rr in row.tolist():
                        pc = abr.get(rr)
                        if pc is not None:
                            self.anchor_meta[di][pc]["deleted"] = True
                d_parts.append(np.full(len(row), di, np.int32))
                r_parts.append(row.astype(np.int32))
            else:
                pc = abr.get(row)
                if pc is not None:
                    self.anchor_meta[di][pc]["deleted"] = True
                d_parts.append(np.full(1, di, np.int32))
                r_parts.append(np.full(1, row, np.int32))
        d_all = np.concatenate(d_parts)
        r_all = np.concatenate(r_parts)
        n = len(d_all)
        if not n:
            return
        obs.counter("fleet.resident_tombstones_total").inc(
            n, family="text" if self.as_text else "list"
        )
        # date the tombstones: compact() may reclaim them once every
        # replica has acked this epoch
        self.tomb_epoch[d_all, r_all] = self.epoch
        if self._defer is not None:
            # coalesced group: tombstones launch once at flush (after
            # the merged row scatter, which only writes NEW rows — it
            # cannot resurrect a row an earlier round tombstoned)
            self._defer.del_d.append(d_all)
            self._defer.del_r.append(r_all)
            return
        self._device_mark_deleted(d_all, r_all)

    def export_select(self, index, requests, sup=None):
        """Batched read-plane selection for the sync pull path: one
        launch per request window (see ``_batch_export_select``)."""
        return _batch_export_select(self, "seq", index, requests, sup)

    def resolve_row(self, doc: int, peer: int, counter: int) -> Optional[int]:
        return self.id2row[doc].get((peer, counter))

    def _materialize(self, use_solver: bool = False):
        """(codes, counts) for the whole batch in one launch.

        Default path: sort by the standing ShadowOrder keys — the
        per-sync order work already happened incrementally on ingest
        (O(delta)); the launch is one multi-key sort, no rank solve.
        use_solver=True runs the full chain-contracted rank solve
        instead (bulk path; also the differential check in tests)."""
        from ..ops.fugue_batch import chain_merge_docs_u, materialize_by_key

        obs.counter("fleet.device_launches_total").inc(family="resident_materialize")
        if not use_solver:
            with tracing.span("resident.materialize", docs=self.d, rows=self.cap):
                codes, counts = jax.block_until_ready(
                    materialize_by_key(self.cols, self.key_hi, self.key_lo)
                )
            with tracing.span("resident.fetch", bytes=codes.nbytes):
                return np.asarray(codes), np.asarray(counts)
        while True:
            codes, counts, n_chains = chain_merge_docs_u(self.cols, self._c_pad)
            max_chains = int(np.asarray(n_chains).max()) if self.d else 0
            if max_chains <= self._c_pad:
                break
            while self._c_pad < max_chains:
                self._c_pad *= 2
        return np.asarray(codes), np.asarray(counts)

    def texts(self, use_solver: bool = False) -> List[str]:
        """Materialize every doc (one launch)."""
        codes, counts = self._materialize(use_solver)
        return [text_from_codes(codes[i], counts[i]) for i in range(self.n_docs)]

    def values(self, use_solver: bool = False) -> List[list]:
        """Materialize value lists (as_text=False batches): the device's
        order of each document's visible content ordinals, gathered from
        its value columns and decoded."""
        assert not self.as_text, "values() is for as_text=False batches"
        codes, counts = self._materialize(use_solver)
        return [self.value_store[i].take(codes[i, : counts[i]])
                for i in range(self.n_docs)]

    # -- checkpoint/resume (fleet-scale; SURVEY §5) --------------------
    STATE_VERSION = 2  # v2: + ingest epoch in meta, tomb-epoch columns
    # serialized row columns (valid is derivable from counts): ONE
    # schema shared by export and import so they cannot drift
    _STATE_SCHEMA = (
        ("parent", np.int32),
        ("side", np.int32),
        ("peer_hi", np.uint32),
        ("peer_lo", np.uint32),
        ("counter", np.int32),
        ("deleted", np.uint8),
        ("content", np.int32),
    )

    def export_state(self) -> bytes:
        """Serialize the resident batch into an LTKV store (storage/kv
        SSTable): per-doc committed row columns, value stores, anchor
        metadata.  id2row and the order engine are NOT serialized —
        both rebuild deterministically from the row table on import
        (keys are re-assigned by replay; any valid assignment orders
        identically).  One server restart = export_state -> bytes ->
        import_state."""
        from ..codec.binary import Writer, _Dicts, _write_value
        from ..storage import MemKvStore

        cols = {f: np.asarray(getattr(self.cols, f)) for f, _ in self._STATE_SCHEMA}
        kv = MemKvStore()
        d = _Dicts()
        meta = Writer()
        meta.u8(self.STATE_VERSION)
        meta.varint(self.n_docs)
        meta.varint(self.d)  # exporter's mesh-padded width
        meta.varint(self.cap)
        meta.u8(1 if self.as_text else 0)
        meta.varint(self._c_pad)
        for di in range(self.d):
            meta.varint(int(self.counts[di]))
        meta.varint(self.epoch)  # v2: compaction epoch clock
        meta.u8(1 if self.auto_grow else 0)  # v2: lifecycle flag
        kv.set(b"meta", bytes(meta.buf))
        for di in range(self.d):
            k = int(self.counts[di])
            w = Writer()
            for f, dt in self._STATE_SCHEMA:
                w.bytes_(cols[f][di, :k].astype(dt).tobytes())
            kv.set(b"doc/%08d/rows" % di, bytes(w.buf))
            if k:
                # v2: tombstone + row ingest epochs (compaction dating)
                kv.set(
                    b"doc/%08d/tombepoch" % di,
                    self.tomb_epoch[di, :k].astype(np.int64).tobytes(),
                )
                kv.set(
                    b"doc/%08d/rowepoch" % di,
                    self.row_epoch[di, :k].astype(np.int64).tobytes(),
                )
            w = Writer()
            _state_write_values(w, d, self.value_store[di].tolist())
            kv.set(b"doc/%08d/values" % di, bytes(w.buf))
            w = Writer()
            w.varint(len(self.anchor_meta[di]))
            for (peer, ctr), a in self.anchor_meta[di].items():
                w.varint(d.peer(peer))
                w.zigzag(ctr)
                w.varint(a["row"])
                w.str_(a["key"])
                if a["value"] is None:
                    w.u8(0)
                else:
                    w.u8(1)
                    _write_value(w, d, a["value"])
                w.varint(a["lamport"])
                w.u8((1 if a["start"] else 0) | (2 if a["deleted"] else 0))
            kv.set(b"doc/%08d/anchors" % di, bytes(w.buf))
        kv.set(b"dicts", _state_dicts_blob(d))
        return kv.export_all()

    @classmethod
    def import_state(cls, data: bytes, mesh=None) -> "DeviceDocBatch":
        """Restore a resident batch from export_state bytes: upload the
        row table, rebuild id maps + the incremental order engine by
        deterministic replay, re-derive standing keys."""
        from ..codec.binary import Reader, _read_value
        from ..errors import DecodeError
        from ..storage import MemKvStore

        kv = MemKvStore()
        kv.import_all(data)
        meta_b = kv.get(b"meta")
        if meta_b is None:
            raise DecodeError("DeviceDocBatch state: missing meta")
        try:
            r = Reader(meta_b)
            version = r.u8()
            if version > cls.STATE_VERSION:
                raise DecodeError(f"DeviceDocBatch state v{version} too new")
            n_docs = r.varint()
            d_saved = r.varint()  # exporter's mesh-padded width
            cap = r.varint()
            as_text = r.u8() == 1
            c_pad = r.varint()
            if c_pad <= 0:  # the chain-budget doubling loop needs > 0
                raise DecodeError("DeviceDocBatch state: bad chain budget")
            counts = [r.varint() for _ in range(d_saved)]
            epoch = r.varint() if version >= 2 else 0
            auto_grow = (r.u8() == 1) if version >= 2 else False
        except (IndexError, ValueError, struct.error) as e:
            raise DecodeError(f"DeviceDocBatch state: malformed meta ({e})") from None
        _state_sane_sizes("DeviceDocBatch", d_saved, capacity=cap)
        if not 0 < n_docs <= d_saved:
            raise DecodeError("DeviceDocBatch state: implausible n_docs")
        batch = cls(n_docs, cap, mesh=mesh, as_text=as_text, auto_grow=auto_grow)
        batch._c_pad = c_pad
        batch.epoch = epoch
        # mesh-pad docs beyond the importer's width must be empty (they
        # only ever receive None updates on the export side)
        for di in range(batch.d, d_saved):
            if counts[di]:
                raise DecodeError(
                    "DeviceDocBatch state: exporter pad doc carries rows but "
                    "importer mesh is narrower"
                )
        dicts_b = kv.get(b"dicts")
        if dicts_b is None:
            raise DecodeError("DeviceDocBatch state: missing dicts")
        peers, cids = _state_read_dicts(dicts_b)
        host = {
            f: np.asarray(getattr(batch.cols, f)).copy() for f in batch.cols._fields
        }
        key_hi = np.asarray(batch.key_hi).copy()
        key_lo = np.asarray(batch.key_lo).copy()
        from .order_maintenance import split_keys

        for di in range(min(batch.d, d_saved)):
            k = counts[di]
            if k > cap:
                raise DecodeError("DeviceDocBatch state: count exceeds capacity")
            rows_b = kv.get(b"doc/%08d/rows" % di)
            if k and rows_b is None:
                raise DecodeError(f"DeviceDocBatch state: missing rows for doc {di}")
            if rows_b is not None:
                r = Reader(rows_b)
                arrs = {}
                try:
                    for f, dt in cls._STATE_SCHEMA:
                        buf = np.frombuffer(r.bytes_(), dt)
                        if len(buf) != k:
                            raise DecodeError("DeviceDocBatch state: row column length")
                        arrs[f] = buf
                except (IndexError, ValueError) as e:
                    raise DecodeError(
                        f"DeviceDocBatch state: malformed rows ({e})"
                    ) from None
                for f in arrs:
                    tgt = host[f]
                    tgt[di, :k] = arrs[f].astype(tgt.dtype)
                host["valid"][di, :k] = True
                batch.counts[di] = k
                for key, attr in (
                    (b"doc/%08d/tombepoch" % di, "tomb_epoch"),
                    (b"doc/%08d/rowepoch" % di, "row_epoch"),
                ):
                    e_b = kv.get(key)
                    if e_b is not None:
                        ecol = np.frombuffer(e_b, np.int64)
                        if len(ecol) != k:
                            raise DecodeError(
                                "DeviceDocBatch state: epoch column length"
                            )
                        getattr(batch, attr)[di, :k] = ecol
                peer_full = (arrs["peer_hi"].astype(np.uint64) << np.uint64(32)) | arrs[
                    "peer_lo"
                ].astype(np.uint64)
                ctr = arrs["counter"]
                batch.id2row[di].insert_arrays(
                    peer_full, ctr.astype(np.int64), np.arange(k, dtype=np.int32)
                )
                # deterministic order-engine rebuild by replay
                if k:
                    keys = batch.order[di].append_arrays(
                        arrs["parent"], arrs["side"], peer_full,
                        ctr.astype(np.int64), 0,
                    )
                    if keys is None:
                        keys = batch.order[di].all_keys()
                    kh, kl = split_keys(np.asarray(keys, np.int64))
                    key_hi[di, :k] = kh
                    key_lo[di, :k] = kl
            try:
                vals_b = kv.get(b"doc/%08d/values" % di)
                if vals_b is not None:
                    batch.value_store[di] = _ValueColumns(
                        _state_read_values(vals_b, cids))
                if k:
                    c_col = host["content"][di, :k].astype(np.int64)
                    if as_text:
                        if c_col.min() < -1 or c_col.max() >= 0x110000:
                            raise DecodeError("DeviceDocBatch state: content code")
                    elif batch.value_store[di] and (
                        c_col.min() < -1
                        or c_col.max() >= len(batch.value_store[di])
                    ):
                        # (an empty store with content rows is the
                        # externally-indexed nested use — DeviceMovable-
                        # Batch slots; values() re-checks at read time)
                        raise DecodeError("DeviceDocBatch state: value ordinal")
                anch_b = kv.get(b"doc/%08d/anchors" % di)
                if anch_b is not None:
                    r = Reader(anch_b)
                    meta_d: Dict[Tuple[int, int], dict] = {}
                    for _ in range(r.varint()):
                        pi = r.varint()
                        if pi >= len(peers):
                            raise DecodeError("DeviceDocBatch state: anchor peer index")
                        peer = peers[pi]
                        ctr_ = r.zigzag()
                        row = r.varint()
                        if row >= k:
                            # an out-of-range anchor row would silently
                            # clip into wrong style positions in
                            # richtexts(); reject like value ordinals
                            raise DecodeError(
                                "DeviceDocBatch state: anchor row out of range"
                            )
                        key = r.str_()
                        val = _read_value(r, cids) if r.u8() == 1 else None
                        lam = r.varint()
                        flags = r.u8()
                        meta_d[(peer, ctr_)] = {
                            "row": row,
                            "key": key,
                            "value": val,
                            "lamport": lam,
                            "peer": peer,
                            "start": bool(flags & 1),
                            "deleted": bool(flags & 2),
                        }
                    batch.anchor_meta[di] = meta_d
                    batch.anchor_by_row[di] = {
                        a["row"]: pc for pc, a in meta_d.items()
                    }
            except (IndexError, ValueError, struct.error, UnicodeDecodeError) as e:
                raise DecodeError(
                    f"DeviceDocBatch state: malformed doc {di} ({e})"
                ) from None
        sh = doc_sharding(batch.mesh)
        from ..ops.fugue_batch import SeqColumnsU

        batch.cols = SeqColumnsU(**{f: jax.device_put(v, sh) for f, v in host.items()})
        batch.key_hi = jax.device_put(key_hi, sh)
        batch.key_lo = jax.device_put(key_lo, sh)
        return batch

    def richtexts(self) -> List[list]:
        """Materialize every doc as Quill-style [{insert, attributes?}]
        segments with styles resolved ON DEVICE (one launch): the
        standing-key sort yields char-positions for every row (anchors
        are zero-width rows), then winners resolve on the segment
        forest (ops/richtext_batch.richtext_by_key_batch).  The
        incremental sibling of Fleet.merge_richtext_changes for
        long-lived resident batches."""
        from ..ops.fugue_batch import pad_bucket
        from ..ops.richtext_batch import (
            RichtextPairs,
            richtext_by_key_batch,
            segments_from_device,
        )

        assert self.as_text, "richtexts() is for as_text=True batches"
        # batch-uniform key dictionary; per-doc value stores
        keys: List[str] = []
        key_idx: Dict[str, int] = {}
        doc_pairs: List[list] = []
        doc_values: List[list] = []
        for di in range(self.d):
            meta = self.anchor_meta[di]
            values: List = []
            pairs = []
            peers = sorted({a["peer"] for a in meta.values()})
            prank = {p: i for i, p in enumerate(peers)}
            for (peer, ctr), a in meta.items():
                if not a["start"]:
                    continue
                end = meta.get((peer, ctr + 1))
                if end is None or end["start"]:
                    continue  # unpaired (mid-transfer); inactive
                if a["deleted"]:
                    continue  # dead start = inactive pair (host walk)
                ki = key_idx.setdefault(a["key"], len(keys))
                if ki == len(keys):
                    keys.append(a["key"])
                if a["value"] is None:
                    vi = -1
                else:
                    vi = len(values)
                    values.append(a["value"])
                pairs.append(
                    (
                        a["row"],
                        # dead end anchor never pops: style runs to EOF
                        -1 if end["deleted"] else end["row"],
                        ki,
                        vi,
                        a["lamport"],
                        prank[a["peer"]],
                    )
                )
            doc_pairs.append(pairs)
            doc_values.append(values)
        n_keys = pad_bucket(max(1, len(keys)), floor=4)
        p = pad_bucket(max(1, max(len(x) for x in doc_pairs)), floor=16)

        def col(j, fill):
            out = np.full((self.d, p), fill, np.int32)
            for di, pairs in enumerate(doc_pairs):
                for i, row in enumerate(pairs):
                    out[di, i] = row[j]
            return out

        pv = np.zeros((self.d, p), bool)
        for di, pairs in enumerate(doc_pairs):
            pv[di, : len(pairs)] = True
        pairs_dev = RichtextPairs(
            start=jnp.asarray(col(0, 0)),
            end=jnp.asarray(col(1, 0)),
            key=jnp.asarray(col(2, 0)),
            value=jnp.asarray(col(3, -1)),
            lamport=jnp.asarray(col(4, 0)),
            peer=jnp.asarray(col(5, 0)),
            valid=jnp.asarray(pv),
        )
        codes, counts, bounds, win = richtext_by_key_batch(
            self.cols, self.key_hi, self.key_lo, pairs_dev, n_keys
        )
        codes = np.asarray(codes)
        counts = np.asarray(counts)
        bounds = np.asarray(bounds)
        win = np.asarray(win)
        return [
            segments_from_device(
                codes[i], counts[i], bounds[i], win[i], keys, doc_values[i]
            )
            for i in range(self.n_docs)
        ]


class _LazyValue:
    """Undecoded map value: payload bytes + native-reported offset.
    Decoded only if it wins the LWW (value_maps)."""

    __slots__ = ("payload", "offset", "cids")

    def __init__(self, payload: bytes, offset: int, cids):
        self.payload = payload
        self.offset = offset
        self.cids = cids

    def decode(self):
        from ..native import decode_value_at

        return decode_value_at(self.payload, self.offset, self.cids)


class DeviceMapBatch:
    """Device-resident LWW-map winners for a doc batch (the map analog
    of DeviceDocBatch).  Appends fold into per-(doc, slot) winners in
    one donated launch; values live host-side as per-doc ordinal lists.
    """

    def __init__(self, n_docs: int, slot_capacity: int, mesh=None,
                 auto_grow: bool = False):
        from ..ops.lww import NEG, LwwResident

        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_docs = n_docs
        self.d = _mesh_pad(self.mesh, n_docs)
        self.s = slot_capacity
        self.auto_grow = auto_grow
        sh = doc_sharding(self.mesh)
        z = lambda dt, fill: jax.device_put(np.full((self.d, self.s), fill, dt), sh)
        self.res = LwwResident(
            lamport=z(np.int32, int(NEG)),
            peer_hi=z(np.uint32, 0),
            peer_lo=z(np.uint32, 0),
            value=z(np.int32, -2),
        )
        self.slot_of: List[Dict[Tuple[ContainerID, str], int]] = [dict() for _ in range(self.d)]
        self.values: List[List] = [[] for _ in range(self.d)]
        # ingest-epoch clock (parity with the seq/tree batches: the
        # server journals rounds against it; folds have no rows to
        # reclaim, so unlike theirs it never gates a compact())
        self.epoch = 0
        self._defer = None  # coalesced-ingest accumulator
        self._dev_lock = named_rlock("fleet.dev")

    # -- round coalescing (LWW fold is associative: one merged fold of
    # the group's rows lands the same winners as one fold per round;
    # the epoch clock still bumps per round in _fold_rows) -------------
    def begin_coalesce(self) -> None:
        if self._defer is not None:
            raise RuntimeError("coalesce group already open")
        self._defer = _DeferredFold(self.d)

    def detach_coalesce(self):
        d, self._defer = self._defer, None
        return d

    def commit_detached(self, d) -> None:
        if d is None or not any(d.rows):
            return
        self._device_fold(d.rows)
        obs.counter("pipeline.coalesced_rounds_total").inc(
            d.n_rounds, family="map"
        )

    def flush_coalesce(self) -> None:
        self.commit_detached(self.detach_coalesce())

    def grow(self, new_slot_capacity: int) -> None:
        """Repack the LWW winner columns to a larger slot capacity
        (resident lifecycle, r4 verdict #6)."""
        from ..ops.lww import LwwResident

        if new_slot_capacity <= self.s:
            return
        with self._dev_lock:  # vs an in-flight pipelined commit
            fills = _lww_fills(-2)
            res = _pad_axis1(
                {f: getattr(self.res, f) for f in self.res._fields},
                new_slot_capacity, fills, doc_sharding(self.mesh),
            )
            self.res = LwwResident(**res)
            self.s = new_slot_capacity

    def _require_slots(self, required: int) -> None:
        """Grow (auto_grow) or raise when a staged append needs more
        slots than the current capacity."""
        if required <= self.s:
            return
        if self.auto_grow:
            self.grow(_grow_target(required, self.s))
        else:
            raise ValueError(
                f"DeviceMapBatch slot capacity exceeded ({required} > "
                f"{self.s}); grow slot_capacity or pass auto_grow=True"
            )

    def release_doc(self, di: int) -> None:
        """Reset doc ``di`` to a never-used slot (tiered-residency
        eviction; see DeviceDocBatch.release_doc for the contract)."""
        from ..ops.lww import NEG, LwwResident

        self.slot_of[di] = {}
        self.values[di] = []
        with self._dev_lock:
            out = _release_rows(
                tuple(self.res), jnp.int32(di),
                (int(NEG), 0, 0, -2),
            )
            self.res = LwwResident(*out)
        obs.counter("fleet.doc_releases_total").inc(family="map")

    def append_changes(self, per_doc_changes: Sequence[Optional[Sequence[Change]]]) -> None:
        from ..core.change import MapSet
        from ..ops.fugue_batch import pad_bucket
        from ..ops.lww import lww_update_resident

        per_doc_changes = list(per_doc_changes) + [None] * (self.d - len(per_doc_changes))
        # stage all mutations; commit only after every doc ingests clean
        # (a capacity error must leave the batch state untouched)
        rows_per_doc, new_slots, new_vals = [], [], []
        for di, changes in enumerate(per_doc_changes):
            rows = []
            rows_per_doc.append(rows)
            staged_slots: Dict = {}
            staged_vals: List = []
            new_slots.append(staged_slots)
            new_vals.append(staged_vals)
            if not changes:
                continue
            slot_of = self.slot_of[di]
            n_vals0 = len(self.values[di])
            for ch in changes:
                for op in ch.ops:
                    c = op.content
                    if not isinstance(c, MapSet):
                        continue
                    key = (op.container, c.key)
                    slot = slot_of.get(key)
                    if slot is None:
                        slot = staged_slots.get(key)
                    if slot is None:
                        slot = len(slot_of) + len(staged_slots)
                        staged_slots[key] = slot
                    lam = ch.lamport + (op.counter - ch.ctr_start)
                    if c.deleted:
                        vi = -1
                    else:
                        vi = n_vals0 + len(staged_vals)
                        staged_vals.append(c.value)
                    rows.append((slot, lam, ch.peer, vi))
        self._require_slots(
            max(
                (len(self.slot_of[di]) + len(new_slots[di]) for di in range(self.d)),
                default=0,
            )
        )
        for di in range(self.d):
            self.slot_of[di].update(new_slots[di])
            self.values[di].extend(new_vals[di])
        self._fold_rows(rows_per_doc)

    def append_payloads(self, per_doc_payloads: Sequence[Optional[bytes]]) -> None:
        """Native ingest: binary payloads -> C++ map explode -> one
        donated fold.  Values are NOT decoded here — the native decoder
        reports byte offsets and value_maps() decodes only the LWW
        winners (loser values never touch Python)."""
        from ..codec.binary import decode_changes
        from ..native import available, explode_map_payload
        from ..ops.fugue_batch import pad_bucket
        from ..ops.lww import lww_update_resident

        if not available():
            self.append_changes(
                [decode_changes(p) if p else None for p in per_doc_payloads]
            )
            return
        per_doc_payloads = list(per_doc_payloads) + [None] * (self.d - len(per_doc_payloads))
        # staged exactly like append_changes: no state mutation until
        # every payload decodes and fits capacity
        rows_per_doc, new_slots, new_vals = [], [], []
        for di, payload in enumerate(per_doc_payloads):
            rows = []
            rows_per_doc.append(rows)
            staged_slots: Dict = {}
            staged_vals: List = []
            new_slots.append(staged_slots)
            new_vals.append(staged_vals)
            if not payload:
                continue
            out = explode_map_payload(payload)
            slot_of = self.slot_of[di]
            n_vals0 = len(self.values[di])
            n = len(out["cid_idx"])
            for j in range(n):
                key = (out["cids"][out["cid_idx"][j]], out["keys"][out["key_idx"][j]])
                slot = slot_of.get(key)
                if slot is None:
                    slot = staged_slots.get(key)
                if slot is None:
                    slot = len(slot_of) + len(staged_slots)
                    staged_slots[key] = slot
                off = int(out["value_offset"][j])
                if off < 0:
                    vi = -1
                else:
                    vi = n_vals0 + len(staged_vals)
                    # lazy cell: decoded on demand in value_maps()
                    staged_vals.append(_LazyValue(payload, off, out["cids"]))
                rows.append(
                    (slot, int(out["lamport"][j]), out["peer_u64"][j], vi)
                )
        self._require_slots(
            max(
                (len(self.slot_of[di]) + len(new_slots[di]) for di in range(self.d)),
                default=0,
            )
        )
        for di in range(self.d):
            self.slot_of[di].update(new_slots[di])
            self.values[di].extend(new_vals[di])
        self._fold_rows(rows_per_doc)

    def _fold_rows(self, rows_per_doc) -> None:
        self.epoch += 1  # post-validation: dates this append (journal clock)
        if not any(rows_per_doc):
            return
        if self._defer is not None:
            self._defer.extend(rows_per_doc)
            return
        self._device_fold(rows_per_doc)

    def _device_fold(self, rows_per_doc) -> None:
        from ..ops.fugue_batch import pad_bucket
        from ..ops.lww import lww_update_resident

        obs.counter("fleet.device_launches_total").inc(family="resident_map")
        m = pad_bucket(max((len(r) for r in rows_per_doc), default=0), floor=16)
        slot = np.zeros((self.d, m), np.int32)
        lam = np.zeros((self.d, m), np.int32)
        hi = np.zeros((self.d, m), np.uint32)
        lo = np.zeros((self.d, m), np.uint32)
        val = np.full((self.d, m), -2, np.int32)
        valid = np.zeros((self.d, m), bool)
        for di, rows in enumerate(rows_per_doc):
            for j, (s_, l_, p_, v_) in enumerate(rows):
                slot[di, j] = s_
                lam[di, j] = l_
                hi[di, j] = p_ >> 32
                lo[di, j] = p_ & 0xFFFFFFFF
                val[di, j] = v_
                valid[di, j] = True
        with self._dev_lock:
            sh = doc_sharding(self.mesh)
            put = lambda a: jax.device_put(a, sh)
            self.res = lww_update_resident(
                self.res, put(slot), put(lam), put(hi), put(lo), put(valid),
                self.s, value=put(val),
            )

    def export_select(self, index, requests, sup=None):
        """Batched read-plane selection for the sync pull path (the
        LWW fold holds no op history — delta framing rides the
        change-span index, like every family)."""
        return _batch_export_select(self, "map", index, requests, sup)

    def value_maps(self) -> List[Dict[Tuple[ContainerID, str], object]]:
        """Materialize {(container, key): value} per doc.  Keys carry
        the container id so the same key name in two map containers of
        one doc cannot collide.  Lazy cells (native ingest) decode here
        — winners only."""
        win = np.asarray(self.res.value)
        out = []
        for di in range(self.n_docs):
            m: Dict[Tuple[ContainerID, str], object] = {}
            for (cid, key), s_ in self.slot_of[di].items():
                vi = int(win[di, s_])
                if vi >= 0:
                    v = self.values[di][vi]
                    if isinstance(v, _LazyValue):
                        v = v.decode()
                        self.values[di][vi] = v
                    m[(cid, key)] = v
            out.append(m)
        return out

    def root_value_maps(self, name: str) -> List[Dict[str, object]]:
        """Flat {key: value} per doc for one root map container."""
        out = []
        for full in self.value_maps():
            out.append(
                {
                    key: v
                    for (cid, key), v in full.items()
                    if cid.is_root and cid.name == name
                }
            )
        return out

    # -- checkpoint/resume --------------------------------------------
    STATE_VERSION = 3  # v3: + ingest epoch clock

    def export_state(self) -> bytes:
        """Serialize the resident winners + slot/value dictionaries into
        an LTKV store (lazy values decode here — winners only live on)."""
        from ..codec.binary import Writer, _Dicts
        from ..storage import MemKvStore

        kv = MemKvStore()
        d = _Dicts()
        meta = Writer()
        meta.u8(self.STATE_VERSION)
        meta.varint(self.n_docs)
        meta.varint(self.d)
        meta.varint(self.s)
        meta.u8(1 if self.auto_grow else 0)  # v2
        meta.varint(self.epoch)  # v3
        kv.set(b"meta", bytes(meta.buf))
        _state_write_grid(kv, b"res", [np.asarray(a) for a in self.res])
        for di in range(self.d):
            w = Writer()
            w.varint(len(self.slot_of[di]))
            for (cid, key), s_ in self.slot_of[di].items():
                w.varint(d.cid(cid))
                w.str_(key)
                w.varint(s_)
            kv.set(b"doc/%08d/slots" % di, bytes(w.buf))
            w = Writer()
            _state_write_values(w, d, self.values[di])
            kv.set(b"doc/%08d/values" % di, bytes(w.buf))
        kv.set(b"dicts", _state_dicts_blob(d))
        return kv.export_all()

    @classmethod
    def import_state(cls, data: bytes, mesh=None) -> "DeviceMapBatch":
        from ..codec.binary import Reader
        from ..errors import DecodeError
        from ..ops.lww import LwwResident
        from ..storage import MemKvStore

        kv = MemKvStore()
        kv.import_all(data)
        meta_b, dicts_b = kv.get(b"meta"), kv.get(b"dicts")
        if meta_b is None or dicts_b is None:
            raise DecodeError("DeviceMapBatch state: missing meta/dicts")
        try:
            r = Reader(meta_b)
            version = r.u8()
            if version > cls.STATE_VERSION:
                raise DecodeError(f"DeviceMapBatch state v{version} too new")
            n_docs, d_saved, s = r.varint(), r.varint(), r.varint()
            auto_grow = (r.u8() == 1) if version >= 2 else False
            epoch = r.varint() if version >= 3 else 0
        except (IndexError, ValueError) as e:
            raise DecodeError(f"DeviceMapBatch state: malformed meta ({e})") from None
        _state_sane_sizes("DeviceMapBatch", d_saved, slot_capacity=s)
        if not 0 < n_docs <= d_saved:
            raise DecodeError("DeviceMapBatch state: implausible n_docs")
        peers, cids = _state_read_dicts(dicts_b)
        batch = cls(n_docs, s, mesh=mesh, auto_grow=auto_grow)
        batch.epoch = epoch
        res_b = kv.get(b"res")
        if res_b is None:
            raise DecodeError("DeviceMapBatch state: missing res")
        grids = _state_read_grid(
            res_b,
            [((d_saved, s), dt) for dt in (np.int32, np.uint32, np.uint32, np.int32)],
        )
        host = [np.asarray(a).copy() for a in batch.res]
        lim = min(batch.d, d_saved)
        for h, g in zip(host, grids):
            h[:lim] = g[:lim]
        sh = doc_sharding(batch.mesh)
        batch.res = LwwResident(*[jax.device_put(h, sh) for h in host])
        for di in range(lim):
            slots_b = kv.get(b"doc/%08d/slots" % di)
            if slots_b is not None:
                try:
                    r = Reader(slots_b)
                    so: Dict[Tuple[ContainerID, str], int] = {}
                    for _ in range(r.varint()):
                        ci = r.varint()
                        if ci >= len(cids):
                            raise DecodeError("DeviceMapBatch state: cid index")
                        key = r.str_()
                        s_ = r.varint()
                        if s_ >= s:
                            raise DecodeError("DeviceMapBatch state: slot index")
                        so[(cids[ci], key)] = s_
                    batch.slot_of[di] = so
                except (IndexError, ValueError, UnicodeDecodeError) as e:
                    raise DecodeError(
                        f"DeviceMapBatch state: malformed slots ({e})"
                    ) from None
            vals_b = kv.get(b"doc/%08d/values" % di)
            if vals_b is not None:
                batch.values[di] = _state_read_values(vals_b, cids)
            # registered slots must reference in-range value ordinals
            # (value_maps would IndexError otherwise)
            for _ck, s_ in batch.slot_of[di].items():
                if int(host[3][di, s_]) >= len(batch.values[di]):
                    raise DecodeError("DeviceMapBatch state: value ordinal")
        return batch


class DeviceTreeBatch:
    """Device-resident movable-tree move logs for a doc batch (the tree
    member of the resident family next to DeviceDocBatch/DeviceMapBatch).

    Appends ship only NEW moves (one block scatter).  Unlike LWW folds,
    tree moves do not commute — a late-arriving concurrent move with a
    lower lamport must replay BEFORE already-applied moves — so the
    resident state is the log (the reference's TreeCacheForDiff keeps
    the same per-node move sets and re-walks them,
    diff_calc/tree.rs:230-396) PLUS its answer, settled at every
    commit: ``parents`` i32[d, node_cap] (ABSENT for a node never
    created) and ``refused`` i32[d], the moves each document's log
    refuses as cycles.  The commit settles the documents it names
    (ops/tree_batch.tree_settle_docs: their whole logs gathered, sorted
    by the global move key (lamport, peer, counter) and replayed, the
    answers scattered back), so the device work of a round follows the
    documents it names, and ``parent_maps`` reads the standing state.
    Every path that changes a log keeps it settled: ``commit_detached``,
    ``release_doc``, ``grow``, ``compact``, ``import_state`` (one whole
    replay) and, through the commit, journal replay."""

    # a never-written log row, field by field (parent: tree_batch.ROOT)
    _COL_FILLS = dict(
        lamport=0, peer_hi=0, peer_lo=0, counter=0, target=0,
        parent=-1, valid=False,
    )

    def __init__(self, n_docs: int, move_capacity: int, node_capacity: int, mesh=None,
                 auto_grow: bool = False):
        from ..ops.tree_batch import ABSENT, TreeLogCols

        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_docs = n_docs
        self.d = _mesh_pad(self.mesh, n_docs)
        self.cap = move_capacity
        self.node_cap = node_capacity
        self.auto_grow = auto_grow
        self.counts = np.zeros(self.d, np.int64)
        # ingest epochs date move rows for compaction (see compact())
        self.epoch = 0
        self.move_epoch = np.full((self.d, move_capacity), -1, np.int64)
        # per-doc node dictionaries + host move metadata for sibling
        # positions, row-aligned with the log: a list of column chunks a
        # doc (``_tree_columns_of``' fields, a round's chunk appended at
        # each commit, joined by ``_move_meta`` when read)
        self.node_ids: List[Dict] = [dict() for _ in range(self.d)]
        self.nodes: List[list] = [[] for _ in range(self.d)]
        self.move_meta: List[list] = [[] for _ in range(self.d)]
        sh = doc_sharding(self.mesh)
        self.cols = TreeLogCols(**{
            f: jax.device_put(v, sh)
            for f, v in self._block(self.d, move_capacity).items()
        })
        # the standing answer of every document's whole log
        self.parents = jax.device_put(
            np.full((self.d, node_capacity), ABSENT, np.int32), sh)
        self.refused = jax.device_put(np.zeros(self.d, np.int32), sh)
        self._defer = None  # coalesced-ingest accumulator
        self._dev_lock = named_rlock("fleet.dev")

    def _settle(self, named) -> None:
        """Settle the documents ``named`` (slot indexes with new or
        rewritten log rows): ONE launch of ``tree_settle_docs`` over a
        block of ``_named_bucket(len(named))`` documents."""
        from ..ops.tree_batch import replay_algo, tree_settle_docs

        named = np.asarray(named, np.int32)
        if not len(named):
            return
        k_pad = _named_bucket(len(named))
        d_idx = np.full(k_pad, -1, np.int32)
        d_idx[: len(named)] = named
        algo = replay_algo(self.node_cap)
        obs.counter("tree.settle_docs_total").inc(len(named))
        # the sequential steps of the launch: the scan replays its padding
        obs.counter("tree.replay_steps").inc(
            int(self.counts[named].max()) if algo == "pallas:lockstep" else self.cap,
            algo=algo,
        )
        with self._dev_lock, tracing.span(
            "resident.settle", docs=len(named), rows=k_pad * self.cap
        ):
            self.parents, self.refused = tree_settle_docs(
                self.cols, (self.parents, self.refused),
                jax.device_put(d_idx, replicated(self.mesh)),
                self.node_cap, self.mesh,
            )

    def _settle_whole(self) -> None:
        """The standing state from ONE replay of the whole table
        (``tree_replay_log_batch``): a batch built from saved logs."""
        from ..ops.tree_batch import tree_replay_log_batch

        with self._dev_lock:
            parents, eff = tree_replay_log_batch(self.cols, self.node_cap)
            self.parents = parents
            self.refused = jnp.sum(
                self.cols.valid & ~eff, axis=1, dtype=jnp.int32
            )

    def refusals(self) -> np.ndarray:
        """The moves each document's standing log refuses as cycles,
        read from the device: int64[n_docs]."""
        with self._dev_lock:
            return np.asarray(self.refused)[: self.n_docs].astype(np.int64)

    # -- round coalescing (same contract as DeviceDocBatch) ------------
    def begin_coalesce(self) -> None:
        if self._defer is not None:
            raise RuntimeError("coalesce group already open")
        self._defer = _DeferredSeqDevice(self.counts.copy())

    def detach_coalesce(self):
        d, self._defer = self._defer, None
        return d

    def commit_detached(self, d) -> None:
        """Ship a detached group's round blocks as ONE scatter of the
        UNION of the documents its rounds name, then settle that union
        once.  A document's segments across rounds are contiguous
        (appends only extend the tail), so its merged row is their
        concatenation at its group-start offset (``DeviceDocBatch.
        commit_detached``' layout).  Never grows: a merged window that
        outgrew capacity by bucket rounding falls back to per-round
        scatters, each validated at stage time."""
        from ..ops.fugue_batch import pad_bucket

        if d is None or not d.rounds:
            return
        with self._dev_lock:
            total = np.zeros(self.d, np.int64)
            for _blk, docs, r_new in d.rounds:
                total[docs] += r_new
            named = np.flatnonzero(total)
            width = pad_bucket(int(total.max()), floor=16)
            if int((d.base0[named] + width).max()) > self.cap:
                off = d.base0.astype(np.int64).copy()
                for blk, docs, r_new in d.rounds:
                    self._device_commit_moves(blk, docs, off[docs], int(r_new.sum()))
                    off[docs] += r_new
            else:
                blk = self._block(_named_bucket(len(named)), width)
                row_of = np.zeros(self.d, np.int64)
                row_of[named] = np.arange(len(named))
                pos = np.zeros(len(named), np.int64)
                for rblk, docs, r_new in d.rounds:
                    for j, k in enumerate(r_new.tolist()):
                        i = int(row_of[docs[j]])
                        p = int(pos[i])
                        for f in blk:
                            blk[f][i, p : p + k] = rblk[f][j, :k]
                        pos[i] += k
                self._device_commit_moves(blk, named, d.base0[named], int(total.sum()))
            self._settle(named)
            obs.counter("pipeline.coalesced_rounds_total").inc(
                len(d.rounds), family="tree"
            )

    def flush_coalesce(self) -> None:
        self.commit_detached(self.detach_coalesce())

    def _block(self, k_pad: int, width: int) -> dict:
        """A scatter block of ``k_pad`` document rows, every field at its
        never-written fill."""
        dtypes = dict(self._STATE_SCHEMA, valid=np.bool_)
        return {
            f: np.full((k_pad, width), fill, dtypes[f]) if fill
            else np.zeros((k_pad, width), dtypes[f])
            for f, fill in self._COL_FILLS.items()
        }

    def _device_commit_moves(self, blk, named, offsets, n_rows) -> None:
        """The device tail of an append: ONE ``_scatter_tree_rows`` of the
        block of the documents a round names.  Block row ``j`` holds
        document ``named[j]``'s new rows for ``offsets[j]``; rows past
        ``len(named)`` go up under the document index -1 and are dropped;
        ``n_rows`` is the block's live rows.  On a mesh of several devices
        the block goes up replicated and each device writes the documents
        it holds."""
        k_pad = blk["valid"].shape[0]
        d_idx = np.full(k_pad, -1, np.int32)
        d_idx[: len(named)] = named
        off = np.zeros(k_pad, np.int32)
        off[: len(named)] = offsets
        obs.counter("fleet.device_launches_total").inc(family="resident_tree")
        obs.counter("fleet.pad_waste_rows_total").inc(
            int(blk["valid"].size - n_rows), family="resident_tree"
        )
        with self._dev_lock, tracing.span("resident.upload", docs=len(named)):
            rep = replicated(self.mesh)
            self.cols = _scatter_tree_rows(
                self.cols,
                {f: jax.device_put(v, rep) for f, v in blk.items()},
                jax.device_put(d_idx, rep),
                jax.device_put(off, rep),
                self.mesh,
            )

    def release_doc(self, di: int) -> None:
        """Reset doc ``di`` to a never-used slot (tiered-residency
        eviction; see DeviceDocBatch.release_doc for the contract)."""
        from ..ops.tree_batch import ABSENT, TreeLogCols

        self.counts[di] = 0
        self.move_epoch[di, :] = -1
        self.node_ids[di] = {}
        self.nodes[di] = []
        self.move_meta[di] = []
        if self._defer is not None:
            self._defer.base0[di] = 0
        with self._dev_lock:
            fields = list(self.cols._fields)
            out = _release_rows(
                tuple(getattr(self.cols, f) for f in fields),
                jnp.int32(di),
                tuple(self._COL_FILLS[f] for f in fields),
            )
            self.cols = TreeLogCols(**dict(zip(fields, out)))
            # an empty log's answer: no node, nothing refused
            (self.parents,) = _release_rows(
                (self.parents,), jnp.int32(di), (ABSENT,))
            self.refused = _release_count(self.refused, jnp.int32(di))
        obs.counter("fleet.doc_releases_total").inc(family="tree")

    def append_changes(self, per_doc_changes: Sequence[Optional[Sequence[Change]]], cid) -> None:
        """Incremental ingest: each doc's new causally-ordered changes
        (None = no update); TreeMove ops become appended log rows.  All
        node registration and rows are STAGED before any validation, so
        a capacity error leaves the batch untouched (the DeviceDocBatch
        atomicity contract)."""
        per_doc_changes = list(per_doc_changes) + [None] * (self.d - len(per_doc_changes))
        staged: List[Optional[dict]] = [None] * self.d
        new_nodes: List[list] = [[] for _ in range(self.d)]
        with tracing.span("resident.decode"):
            for di, changes in enumerate(per_doc_changes):
                if changes:
                    staged[di] = self._walk_changes(di, changes, cid, new_nodes[di])
        self._commit_moves(staged, new_nodes)

    def append_payloads(self, per_doc_payloads: Sequence[Optional[bytes]], cid) -> None:
        """Incremental NATIVE ingest: envelope-stripped binary payloads
        -> C++ tree explode (wire order — the device replay sorts by the
        global move key anyway) -> each document's moves as columns
        (``_stage_payload``: no Python object a move) -> one block of the
        documents the round names.  A payload the native path cannot
        resolve falls back to the Python walk
        (``fleet.host_fallback_total{kind="tree_payload"}``); every other
        one counts in ``fleet.resident_tree_column_docs_total``."""
        from ..codec.binary import decode_changes
        from ..native import available

        if not available():
            self.append_changes(
                [decode_changes(p) if p else None for p in per_doc_payloads], cid
            )
            return
        per_doc_payloads = list(per_doc_payloads) + [None] * (
            self.d - len(per_doc_payloads)
        )
        staged: List[Optional[dict]] = [None] * self.d
        new_nodes: List[list] = [[] for _ in range(self.d)]
        column_docs = 0
        with tracing.span("resident.decode"):
            for di, payload in enumerate(per_doc_payloads):
                if not payload:
                    continue
                try:
                    staged[di] = self._stage_payload(di, payload, cid, new_nodes[di])
                    column_docs += 1
                except (ValueError, IndexError):
                    new_nodes[di].clear()
                    _obs_fallback("tree_payload")
                    staged[di] = self._walk_changes(
                        di, decode_changes(payload), cid, new_nodes[di])
        obs.counter("fleet.resident_tree_column_docs_total").inc(column_docs)
        self._commit_moves(staged, new_nodes)

    def _stage_payload(self, di, payload, cid, new_nodes) -> Optional[dict]:
        """One payload's moves of ``cid`` as columns, built from
        ``native.explode_tree``'s arrays by array operations (the form
        ``_commit_moves`` takes: ``_tree_columns_of``'s fields), or None
        when it holds none.  Node references are numbered by
        ``_node_numbers``; ``new_nodes`` gets the new ones.  Raises
        ValueError (or IndexError) on input the native path cannot
        resolve."""
        from ..codec.binary import read_tables
        from ..native import explode_tree_payload
        from ..ops.tree_batch import ROOT, TRASH

        peers_wire, _keys, cids, _r = read_tables(payload)
        try:
            target = cids.index(cid)
        except ValueError:
            return None  # no ops for this container
        out = explode_tree_payload(payload, target)
        n = len(out["lamport"])
        if not n:
            return None
        fl = out["flags"]
        is_del = (fl & 2) != 0
        has_parent = ((fl & 4) != 0) & ~is_del
        if out["target_ctr"].min() < 0 or (out["parent_ctr"][has_parent] < 0).any():
            raise ValueError("tree node counter below 0")
        # the node references move by move, the target before the parent
        refs = np.empty((n, 2), np.int64)
        refs[:, 0] = pack_wire_ids(out["target_peer_idx"], out["target_ctr"])
        refs[:, 1] = pack_wire_ids(out["parent_peer_idx"], out["parent_ctr"])
        referenced = np.empty((n, 2), bool)
        referenced[:, 0] = True
        referenced[:, 1] = has_parent
        node = np.empty((n, 2), np.int32)
        node[referenced] = self._node_numbers(
            di, refs[referenced], peers_wire, new_nodes)
        parent = np.where(has_parent, node[:, 1], ROOT).astype(np.int32)
        parent[is_del] = TRASH
        arena, pos_off, pos_len = _payload_positions(
            payload, out["pos_off"], np.where((fl & 8) != 0, out["pos_len"], -1))
        return {
            "lamport": out["lamport"].astype(np.int64),
            "peer": np.asarray(peers_wire, np.uint64)[out["peer_idx"]],
            "counter": out["counter"].astype(np.int64),
            "target": node[:, 0].copy(),
            "parent": parent,
            "is_del": is_del,
            "pos_off": pos_off,
            "pos_len": pos_len,
            "arena": arena,
        }

    def _node_numbers(self, di, refs, peers_wire, new_nodes) -> np.ndarray:
        """The node index of each packed (wire peer, counter) reference
        of document ``di``, exactly as the Python walk numbers them:
        committed nodes keep theirs, new ones follow the dictionary in
        order of first appearance (their TreeIDs appended to
        ``new_nodes``).  Python runs once a distinct NODE, never a
        reference: first appearances come from a dense table over
        (peer, counter) when that is small, else from one sort."""
        from ..core.ids import TreeID

        m = len(refs)
        pidx, ctr = refs >> 32, refs & 0xFFFFFFFF
        p0, c0 = int(pidx.min()), int(ctr.min())
        span = int(ctr.max()) - c0 + 1
        size = (int(pidx.max()) - p0 + 1) * span
        if size <= 4 * m + 4096:
            code = (pidx - p0) * span + (ctr - c0)
        else:
            uniq, code = np.unique(refs, return_inverse=True)
            size = len(uniq)
        first = np.full(size, m, np.int64)
        np.minimum.at(first, code, np.arange(m))
        seen = np.flatnonzero(first < m)
        seen = seen[np.argsort(first[seen], kind="stable")]
        lut = np.empty(size, np.int32)
        ids, base = self.node_ids[di], len(self.nodes[di])
        for u, key in zip(seen.tolist(), refs[first[seen]].tolist()):
            tid = TreeID(peers_wire[key >> 32], key & 0xFFFFFFFF)
            i = ids.get(tid)
            if i is None:
                i = base + len(new_nodes)
                new_nodes.append(tid)
            lut[u] = i
        return lut[code]

    def _walk_changes(self, di, changes, cid, new_nodes) -> Optional[dict]:
        """The Python change walk (``append_changes``, and
        ``append_payloads``' fallback): the moves of ``cid`` in
        ``_stage_payload``'s columns, nodes numbered as it numbers them,
        or None when there are none."""
        from ..core.change import TreeMove
        from ..ops.tree_batch import ROOT, TRASH

        ids = self.node_ids[di]
        base = len(self.nodes[di])
        staged: Dict = {}

        def node_idx(tid):
            i = ids.get(tid)
            if i is None:
                i = staged.get(tid)
            if i is None:
                i = staged[tid] = base + len(new_nodes)
                new_nodes.append(tid)
            return i

        rows = []
        for ch in changes:
            for op in ch.ops:
                if op.container != cid or not isinstance(op.content, TreeMove):
                    continue
                c = op.content
                lam = ch.lamport + (op.counter - ch.ctr_start)
                t = node_idx(c.target)
                if c.is_delete:
                    p = TRASH
                elif c.parent is None:
                    p = ROOT
                else:
                    p = node_idx(c.parent)
                rows.append((lam, ch.peer, op.counter, t, p, c.is_delete, c.position))
        return _tree_columns_of(*zip(*rows)) if rows else None

    def grow(self, move_capacity: int = None, node_capacity: int = None) -> None:
        """Repack move-log columns and/or raise the node ceiling
        (resident lifecycle, r4 verdict #6).  The node ceiling widens
        the standing parents (new nodes read ABSENT): the answers of the
        logs do not change."""
        from ..ops.tree_batch import ABSENT, TreeLogCols

        if move_capacity is not None and move_capacity > self.cap:
            with self._dev_lock:  # vs an in-flight pipelined commit
                cols = _pad_axis1(
                    {f: getattr(self.cols, f) for f in self.cols._fields},
                    move_capacity, self._COL_FILLS, doc_sharding(self.mesh),
                )
                self.cols = TreeLogCols(**cols)
                me = np.full((self.d, move_capacity), -1, np.int64)
                me[:, : self.cap] = self.move_epoch
                self.move_epoch = me
                self.cap = move_capacity
        if node_capacity is not None and node_capacity > self.node_cap:
            with self._dev_lock:
                self.parents = _pad_axis1(
                    {"parents": self.parents}, node_capacity,
                    {"parents": ABSENT}, doc_sharding(self.mesh),
                )["parents"]
                self.node_cap = node_capacity

    def _commit_moves(self, staged, new_nodes) -> None:
        """Shared tail: validate capacities, commit the new nodes, write
        each named document's move columns into ONE block of the
        documents the round names (``[_named_bucket(named), max_new]``,
        row ``j`` document ``named[j]``), scatter it, settle them.
        ``staged`` holds a slot's columns (``_tree_columns_of``'s fields
        + ``parent``) or None; ``new_nodes`` the TreeIDs its columns
        number past the slot's dictionary."""
        from ..ops.fugue_batch import pad_bucket

        named = np.asarray(
            [di for di, c in enumerate(staged) if c is not None and len(c["target"])],
            np.int64,
        )
        n_new = np.asarray([len(staged[di]["target"]) for di in named], np.int64)
        max_new = pad_bucket(int(n_new.max()), floor=16) if len(named) else 0
        # validate BEFORE mutating anything
        req_moves = int((self.counts[named] + max_new).max()) if len(named) else 0
        req_nodes = max(
            (len(self.nodes[di]) + len(new) for di, new in enumerate(new_nodes)),
            default=0,
        )
        if req_moves > self.cap:
            if self.auto_grow:
                self.grow(move_capacity=_grow_target(req_moves, self.cap))
            else:
                raise RuntimeError(
                    f"DeviceTreeBatch move capacity exceeded: a doc needs "
                    f"{req_moves} rows > {self.cap}"
                )
        if req_nodes > self.node_cap:
            if self.auto_grow:
                self.grow(node_capacity=_grow_target(req_nodes, self.node_cap))
            else:
                raise RuntimeError(
                    f"DeviceTreeBatch node capacity exceeded: a doc needs "
                    f"{req_nodes} nodes > {self.node_cap}"
                )
        # the clock ticks for EVERY appended round — including rounds
        # that stage no move rows (a tree server fed a map-only edit).
        # Every family batch shares this contract (journal epochs are
        # strictly monotone per round): a lazy bump here stamped those
        # rounds' journal records with epoch 0 / duplicate epochs,
        # which recovery replay skips and which un-pin WAL retention
        # under a live follower (chaos seed 4).
        self.epoch += 1
        if not len(named):
            return
        obs.counter("fleet.resident_tree_moves_total").inc(int(n_new.sum()))
        k_pad = _named_bucket(len(named))
        with tracing.span("resident.stage", docs=len(named),
                          bytes=25 * k_pad * max_new):
            for di, new in enumerate(new_nodes):
                ids, nodes = self.node_ids[di], self.nodes[di]
                for tid in new:
                    ids[tid] = len(nodes)
                    nodes.append(tid)
            blk = self._block(k_pad, max_new)
            offsets = self.counts[named].copy()
            for j, (di, k) in enumerate(zip(named.tolist(), n_new.tolist())):
                c = staged[di]
                blk["lamport"][j, :k] = c["lamport"]
                blk["peer_hi"][j, :k] = c["peer"] >> np.uint64(32)
                blk["peer_lo"][j, :k] = c["peer"] & np.uint64(0xFFFFFFFF)
                blk["counter"][j, :k] = c["counter"]
                blk["target"][j, :k] = c["target"]
                blk["parent"][j, :k] = c["parent"]
                blk["valid"][j, :k] = True
                base = int(self.counts[di])
                self.move_epoch[di, base : base + k] = self.epoch
                self.counts[di] += k
                self.move_meta[di].append(
                    {f: c[f] for f in _TREE_META_FIELDS + ("arena",)})
        if self._defer is not None:
            self._defer.rounds.append((blk, named, n_new))
        else:
            self._device_commit_moves(blk, named, offsets, int(n_new.sum()))
            self._settle(named)

    def _move_meta(self, di: int) -> dict:
        """Document ``di``'s move metadata as ONE set of columns, row-
        aligned with its log (the rounds' chunks joined on first read)."""
        m = _tree_meta_join(self.move_meta[di])
        self.move_meta[di] = [m] if len(m["target"]) else []
        return m

    def _replay(self):
        from ..ops.tree_batch import tree_replay_log_batch

        return tree_replay_log_batch(self.cols, self.node_cap)

    def compact(self, stable_epochs: Sequence[Optional[int]]) -> int:
        """Collapse the move log over its causally-stable prefix: per
        node, keep only the WINNING stable move (the last effected one
        in global key order); drop every superseded or cycle-rejected
        stable row.  Rows newer than the doc's stable epoch all stay.

        Sound because (a) every future move's lamport exceeds every
        stable move's lamport (its author's frontier dominates the
        stable set), so future rows sort strictly after the stable
        prefix, and (b) replaying only winners reproduces the stable
        tree state: at any winner's position the reduced state is a
        sub-chain of the full state per node (ABSENT where a superseded
        move once pointed), and the ancestor cycle-walk over sub-chains
        can only stop earlier — a move accepted in full replay is never
        spuriously rejected in the reduced one.  move_meta stays
        row-aligned (children_maps' sibling tiebreak uses relative key
        order, which filtering preserves).  Node dictionaries are not
        reclaimed (targets keep their ordinals).  Returns rows dropped.
        Reference analog: loro's tree uses the same last-writer state
        under its shallow-snapshot floor (shallow_snapshot.rs:16-40)."""
        from ..ops.tree_batch import TreeLogCols

        if len(stable_epochs) > self.d:
            raise ValueError(
                f"compact: {len(stable_epochs)} stable_epochs for a "
                f"{self.d}-doc batch"
            )
        stable_epochs = list(stable_epochs) + [None] * (self.d - len(stable_epochs))
        host = None
        eff = None
        reclaimed = 0
        rewritten: List[int] = []
        for di, stable_e in enumerate(stable_epochs):
            if stable_e is None or not int(self.counts[di]):
                continue
            if host is None:
                _parents, eff_dev = self._replay()
                eff = np.asarray(eff_dev)
                host = {f: np.asarray(getattr(self.cols, f)).copy()
                        for f in self.cols._fields}
            k = int(self.counts[di])
            stable = self.move_epoch[di, :k] <= int(stable_e)
            stable &= self.move_epoch[di, :k] >= 0  # undated rows stay
            if not stable.any():
                continue
            lam = host["lamport"][di, :k]
            phi = host["peer_hi"][di, :k]
            plo = host["peer_lo"][di, :k]
            ctr = host["counter"][di, :k]
            tgt = host["target"][di, :k]
            order = np.lexsort((ctr, plo, phi, lam))
            winner: Dict[int, int] = {}
            for r in order:
                if stable[r] and eff[di, r]:
                    winner[int(tgt[r])] = int(r)
            win_rows = set(winner.values())
            keep = ~stable  # unstable rows all stay
            for r in win_rows:
                keep[r] = True
            n_keep = int(keep.sum())
            if n_keep == k:
                continue
            reclaimed += k - n_keep
            rewritten.append(di)
            old_rows = np.flatnonzero(keep)  # original append order
            for f in self.cols._fields:
                row = host[f][di]
                vals = row[:k][old_rows]  # fancy index: already a copy
                row[:] = self._COL_FILLS[f]
                row[:n_keep] = vals
            me = self.move_epoch[di, :k][old_rows]
            self.move_epoch[di, :] = -1
            self.move_epoch[di, :n_keep] = me
            m = self._move_meta(di)
            self.move_meta[di] = [
                {**{f: m[f][old_rows] for f in _TREE_META_FIELDS}, "arena": m["arena"]}]
            self.counts[di] = n_keep
        if host is not None and reclaimed:
            sh = doc_sharding(self.mesh)
            self.cols = TreeLogCols(
                **{f: jax.device_put(v, sh) for f, v in host.items()}
            )
            # the parents stand; the refused rows that went are recounted
            self._settle(rewritten)
        return reclaimed

    def export_select(self, index, requests, sup=None):
        """Batched read-plane selection for the sync pull path."""
        return _batch_export_select(self, "tree", index, requests, sup)

    def parent_maps(self) -> List[dict]:
        """{TreeID: parent TreeID | None} of alive nodes per doc, read
        off the standing parents (same contract as
        Fleet.merge_tree_changes)."""
        from ..ops.tree_batch import ABSENT, ROOT, is_deleted_batch

        with self._dev_lock:  # a commit donates the standing buffers
            deleted = np.asarray(is_deleted_batch(self.parents))
            parents = np.asarray(self.parents)
        out = []
        for di in range(self.n_docs):
            res = {}
            nodes = self.nodes[di]
            for j, tid in enumerate(nodes):
                p = int(parents[di, j])
                if p == ABSENT or deleted[di, j]:
                    continue
                res[tid] = None if p == ROOT else nodes[p]
            out.append(res)
        return out

    # -- checkpoint/resume --------------------------------------------
    STATE_VERSION = 2  # v2: + epoch clock, move-epoch columns
    _STATE_SCHEMA = (
        ("lamport", np.int32),
        ("peer_hi", np.uint32),
        ("peer_lo", np.uint32),
        ("counter", np.int32),
        ("target", np.int32),
        ("parent", np.int32),
    )

    def export_state(self) -> bytes:
        """Serialize the resident move logs + node dictionaries + host
        move metadata (fractional positions) into an LTKV store."""
        from ..codec.binary import Writer
        from ..storage import MemKvStore

        kv = MemKvStore()
        meta = Writer()
        meta.u8(self.STATE_VERSION)
        meta.varint(self.n_docs)
        meta.varint(self.d)
        meta.varint(self.cap)
        meta.varint(self.node_cap)
        for di in range(self.d):
            meta.varint(int(self.counts[di]))
        meta.varint(self.epoch)  # v2
        meta.u8(1 if self.auto_grow else 0)  # v2
        kv.set(b"meta", bytes(meta.buf))
        cols = {f: np.asarray(getattr(self.cols, f)) for f, _ in self._STATE_SCHEMA}
        for di in range(self.d):
            k = int(self.counts[di])
            w = Writer()
            for f, dt in self._STATE_SCHEMA:
                w.bytes_(cols[f][di, :k].astype(dt).tobytes())
            kv.set(b"doc/%08d/log" % di, bytes(w.buf))
            if k:
                kv.set(
                    b"doc/%08d/moveepoch" % di,
                    self.move_epoch[di, :k].astype(np.int64).tobytes(),
                )
            w = Writer()
            w.varint(len(self.nodes[di]))
            for tid in self.nodes[di]:
                w.u64le(tid.peer)
                w.zigzag(tid.counter)
            kv.set(b"doc/%08d/nodes" % di, bytes(w.buf))
            m = self._move_meta(di)
            arena = m["arena"].tobytes()
            w = Writer()
            w.varint(len(m["target"]))
            for lam, peer, ctr, t, is_del, off, n in zip(
                    *(m[f].tolist() for f in _TREE_META_FIELDS)):
                w.varint(lam)
                w.u64le(peer)
                w.zigzag(ctr)
                w.varint(t)
                w.u8((1 if is_del else 0) | (2 if n >= 0 else 0))
                if n >= 0:
                    w.bytes_(arena[off : off + n])
            kv.set(b"doc/%08d/meta" % di, bytes(w.buf))
        return kv.export_all()

    @classmethod
    def import_state(cls, data: bytes, mesh=None) -> "DeviceTreeBatch":
        from ..codec.binary import Reader
        from ..core.ids import TreeID
        from ..errors import DecodeError
        from ..ops.tree_batch import TreeLogCols
        from ..storage import MemKvStore

        kv = MemKvStore()
        kv.import_all(data)
        meta_b = kv.get(b"meta")
        if meta_b is None:
            raise DecodeError("DeviceTreeBatch state: missing meta")
        try:
            r = Reader(meta_b)
            version = r.u8()
            if version > cls.STATE_VERSION:
                raise DecodeError(f"DeviceTreeBatch state v{version} too new")
            n_docs, d_saved = r.varint(), r.varint()
            cap, node_cap = r.varint(), r.varint()
            counts = [r.varint() for _ in range(d_saved)]
            epoch = r.varint() if version >= 2 else 0
            auto_grow = (r.u8() == 1) if version >= 2 else False
        except (IndexError, ValueError) as e:
            raise DecodeError(f"DeviceTreeBatch state: malformed meta ({e})") from None
        _state_sane_sizes("DeviceTreeBatch", d_saved, move_capacity=cap, node_capacity=node_cap)
        if not 0 < n_docs <= d_saved:
            raise DecodeError("DeviceTreeBatch state: implausible n_docs")
        batch = cls(n_docs, cap, node_cap, mesh=mesh, auto_grow=auto_grow)
        batch.epoch = epoch
        for di in range(batch.d, d_saved):
            if counts[di]:
                raise DecodeError("DeviceTreeBatch state: importer mesh too narrow")
        host = {f: np.asarray(getattr(batch.cols, f)).copy() for f in batch.cols._fields}
        try:
            for di in range(min(batch.d, d_saved)):
                k = counts[di]
                if k > cap:
                    raise DecodeError("DeviceTreeBatch state: count exceeds capacity")
                log_b = kv.get(b"doc/%08d/log" % di)
                if k and log_b is None:
                    raise DecodeError(f"DeviceTreeBatch state: missing log for doc {di}")
                if log_b is not None:
                    r = Reader(log_b)
                    for f, dt in cls._STATE_SCHEMA:
                        buf = np.frombuffer(r.bytes_(), dt)
                        if len(buf) != k:
                            raise DecodeError("DeviceTreeBatch state: log column length")
                        host[f][di, :k] = buf.astype(host[f].dtype)
                    host["valid"][di, :k] = True
                    batch.counts[di] = k
                    me_b = kv.get(b"doc/%08d/moveepoch" % di)
                    if me_b is not None:
                        me = np.frombuffer(me_b, np.int64)
                        if len(me) != k:
                            raise DecodeError(
                                "DeviceTreeBatch state: move epoch column length"
                            )
                        batch.move_epoch[di, :k] = me
                nodes_b = kv.get(b"doc/%08d/nodes" % di)
                if nodes_b is not None:
                    r = Reader(nodes_b)
                    nodes = []
                    for _ in range(r.varint()):
                        nodes.append(TreeID(r.u64le(), r.zigzag()))
                    if len(nodes) > node_cap:
                        raise DecodeError("DeviceTreeBatch state: node overflow")
                    batch.nodes[di] = nodes
                    batch.node_ids[di] = {tid: i for i, tid in enumerate(nodes)}
                mm_b = kv.get(b"doc/%08d/meta" % di)
                if mm_b is not None:
                    r = Reader(mm_b)
                    mm = []
                    for _ in range(r.varint()):
                        lam = r.varint()
                        peer = r.u64le()
                        ctr = r.zigzag()
                        t = r.varint()
                        flags = r.u8()
                        pos = r.bytes_() if flags & 2 else None
                        mm.append((lam, peer, ctr, t, bool(flags & 1), pos))
                    if mm:
                        lam, peer, ctr, t, dels, pos = zip(*mm)
                        batch.move_meta[di] = [
                            _tree_columns_of(lam, peer, ctr, t, None, dels, pos)]
                if k:
                    # node ordinals must stay inside the node dict
                    # (parent_maps would IndexError on nodes[p])
                    n_nodes = len(batch.nodes[di])
                    tgt = host["target"][di, :k].astype(np.int64)
                    par = host["parent"][di, :k].astype(np.int64)
                    if tgt.min() < 0 or tgt.max() >= n_nodes:
                        raise DecodeError("DeviceTreeBatch state: target ordinal")
                    if par.min() < -2 or par.max() >= n_nodes:
                        raise DecodeError("DeviceTreeBatch state: parent ordinal")
        except (IndexError, ValueError, OverflowError, struct.error) as e:
            raise DecodeError(f"DeviceTreeBatch state: malformed doc ({e})") from None
        sh = doc_sharding(batch.mesh)
        batch.cols = TreeLogCols(**{f: jax.device_put(v, sh) for f, v in host.items()})
        batch._settle_whole()  # the standing state is derived, not saved
        return batch

    def children_maps(self) -> List[dict]:
        """{parent | None: [children in (fractional-index, move-key)
        order]} per doc — the materialized tree shape (same contract as
        Fleet.merge_tree_children).  Sibling order needs every move's
        effected flag, which the standing state does not keep: this read
        replays the whole table."""
        from ..ops.tree_batch import ABSENT, ROOT, is_deleted_batch

        parents, eff = self._replay()
        deleted = np.asarray(is_deleted_batch(parents))
        parents = np.asarray(parents)
        eff = np.asarray(eff)
        out = []
        for di in range(self.n_docs):
            nodes = self.nodes[di]
            # winning position = last effected non-delete move per node
            # in key order; sibling tiebreak = the winning move's key
            # order (exactly merge_tree_children's host walk)
            m = self._move_meta(di)
            order = np.lexsort((m["counter"], m["peer"], m["lamport"]))
            tgt = m["target"][order]
            eff_o = eff[di, : len(order)][order]
            # per node, the place in key order of its last effected move
            # and of its last effected non-delete move (-1: none)
            last_eff = np.full(len(nodes), -1, np.int64)
            at = np.flatnonzero(eff_o)
            np.maximum.at(last_eff, tgt[at], at)
            won = np.full(len(nodes), -1, np.int64)
            at = np.flatnonzero(eff_o & ~m["is_del"][order])
            np.maximum.at(won, tgt[at], at)
            arena = m["arena"].tobytes()
            kids: Dict = {}
            for j, tid in enumerate(nodes):
                p = int(parents[di, j])
                if p == ABSENT or deleted[di, j]:
                    continue
                key = None if p == ROOT else nodes[p]
                pos = b""
                if won[j] >= 0:
                    r = order[won[j]]
                    off, n = int(m["pos_off"][r]), int(m["pos_len"][r])
                    pos = arena[off : off + n] if n > 0 else b""
                kids.setdefault(key, []).append((pos, max(int(last_eff[j]), 0), tid))
            out.append(
                {
                    k: [t for _, _, t in sorted(v, key=lambda x: (x[0], x[1]))]
                    for k, v in kids.items()
                }
            )
        return out


class _DeferredSeqDevice:
    """Accumulated device work of one coalesced ingest group over a
    DeviceDocBatch/DeviceTreeBatch: per-round host blocks (already
    host-committed — epochs, order engines, id maps, counts) waiting
    for the single merged scatter at flush_coalesce()."""

    __slots__ = ("base0", "rounds", "renumbered", "del_d", "del_r", "key_snap")

    def __init__(self, base0: np.ndarray):
        self.base0 = base0          # per-doc counts at group start
        # DeviceDocBatch: (blk, key_hi, key_lo, docs, rows) a round — the
        # named block, the document and the live rows of each of its
        # rows; DeviceTreeBatch: (blk, docs, rows), the same without keys
        self.rounds: List[tuple] = []
        self.renumbered: set = set()
        self.del_d: List[np.ndarray] = []
        self.del_r: List[np.ndarray] = []
        self.key_snap = None        # detach-time key rows (renumbered docs)


class _DeferredFold:
    """Accumulated fold rows of one coalesced group over an LWW/counter
    resident (per-doc row lists concatenated across rounds; the folds
    are associative — max by (lamport, peer) / float add — so one
    merged fold lands the same winners as one fold per round)."""

    __slots__ = ("rows", "n_rounds")

    def __init__(self, n_docs: int):
        self.rows: List[list] = [[] for _ in range(n_docs)]
        self.n_rounds = 0  # non-empty rounds folded (metric unit parity
        #                    with the seq/tree per-round block counts)

    def extend(self, rows_per_doc) -> None:
        if any(rows_per_doc):
            self.n_rounds += 1
        for di, rows in enumerate(rows_per_doc):
            if rows:
                self.rows[di].extend(rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_key_rows(keys, d_idx, kh_rows, kl_rows):
    """Replace whole key rows for renumbered docs (donated, one launch
    for the whole epoch; duplicate pad indices write identical rows)."""
    key_hi, key_lo = keys
    return key_hi.at[d_idx].set(kh_rows), key_lo.at[d_idx].set(kl_rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _set_deleted(deleted, d_idx, r_idx):
    """Tombstone (doc, row) pairs in one donated launch."""
    return deleted.at[d_idx, r_idx].set(True)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2,))
def _release_rows(arrays, di, fills):
    """Reset doc row ``di`` of every [d, cap] array to its construction
    fill (donated, one launch) — the device half of ``release_doc``:
    the tiered-residency eviction path (parallel/residency.py) recycles
    the slot for a different doc, so the row must be indistinguishable
    from a never-used one.  ``fills`` is a static tuple aligned with
    ``arrays``; shapes are the resident capacities, so there is exactly
    one compile per family per capacity bucket (LT-PAD holds: no
    data-dependent shapes)."""
    return tuple(
        a.at[di].set(jnp.full((a.shape[1],), f, a.dtype))  # tpulint: disable=LT-PAD(in-jit row fill at the array's OWN static capacity — already bucketed at allocation, no new shape can exist)
        for a, f in zip(arrays, fills)
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _release_count(counts, di):
    """Zero document ``di``'s entry of a per-document ``[d]`` count
    (donated, one launch): ``release_doc``'s half for such arrays."""
    return counts.at[di].set(0)


# smallest block of named documents (as the key rows of
# ``_upload_renumbered_keys``): bounds the programs a table compiles
_NAMED_FLOOR = 4


def _named_bucket(n_named: int) -> int:
    """Rows of the scatter block of a round that names ``n_named``
    documents."""
    from ..ops.fugue_batch import pad_bucket

    return pad_bucket(n_named, floor=_NAMED_FLOOR)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(4,))
def _scatter_rows(state, blk, d_idx, offsets, mesh):
    """Write the block of the documents a round NAMES into the resident
    table (donated update — the old buffers are reused).  `state` is
    (SeqColumnsU, key_hi, key_lo), each ``[d, capacity]``; ``blk`` holds
    the same ten arrays as ``[k_pad, max_new]``, row ``j`` being document
    ``d_idx[j]``'s new rows for the window ``[offsets[j], offsets[j] +
    max_new)``, replaced under ``valid``: a window's cells past the
    document's new rows keep what the table held there (``jnp.where``
    over the window read back by ``dynamic_slice``, in ``write_row``).

    One loop over the block's rows, each a ``dynamic_update_slice`` at
    ``(d_idx[j], offsets[j])`` of every array: the bytes moved follow
    ``k_pad * max_new`` — a named row touches its own document's window
    and nothing else, and there is no ``[d, capacity]`` temporary.  One
    compiled program per ``(k_pad, max_new, capacity)`` bucket.

    **Pad entries** (``len(named)`` is not always at a bucket) carry the
    document index -1, a document NO device holds, and are DROPPED: such
    a row keeps nothing of the block (``valid & held`` is all false), so
    what it writes is the window it has just read, at the clamped slot,
    in its own turn of a SEQUENTIAL loop — it can never put an old
    window over a real row's new values (what a pad row that repeated a
    named document with ``valid`` all false would do in a parallel
    scatter).

    On a mesh of several devices the table is doc-sharded and the block,
    ``d_idx`` and ``offsets`` are replicated: under ``shard_map`` each
    device drops, by the same test, the rows of documents it does not
    hold and writes its own."""
    cols, key_hi, key_lo = state
    fields = cols._fields
    arrays = tuple(getattr(cols, f) for f in fields) + (key_hi, key_lo)
    news = tuple(blk[f] for f in fields) + (blk["key_hi"], blk["key_lo"])
    sharded = mesh.size > 1

    def on_device(arrays, news, valid, d_idx, offsets):
        d_local = arrays[0].shape[0]
        lo = jax.lax.axis_index(DOC_AXIS) * d_local if sharded else 0
        width = valid.shape[1]

        def write_row(j, arrays):
            di = d_idx[j] - lo
            held = (di >= 0) & (di < d_local)
            at = (jnp.clip(di, 0, d_local - 1), offsets[j])
            keep = (valid[j] & held)[None]
            out = []
            for a, nb in zip(arrays, news):
                window = jax.lax.dynamic_slice(a, at, (1, width))
                row = jax.lax.dynamic_slice(nb, (j, 0), (1, width))
                out.append(jax.lax.dynamic_update_slice(
                    a, jnp.where(keep, row, window), at))
            return tuple(out)

        return jax.lax.fori_loop(0, d_idx.shape[0], write_row, arrays)

    if sharded:
        docs, rep = P(DOC_AXIS), P()
        on_device = jax.shard_map(
            on_device, mesh=mesh, in_specs=(docs, rep, rep, rep, rep),
            out_specs=docs, check_vma=False,
        )
    out = on_device(arrays, news, blk["valid"], d_idx, offsets)
    n = len(fields)
    return type(cols)(**dict(zip(fields, out[:n]))), out[n], out[n + 1]


class DeviceMovableBatch:
    """Device-resident MovableList state for a doc batch — the last
    member of the resident family.

    Decomposition (reference semantics diff_calc.rs:1669-2020): position
    SLOTS are sequence elements (they ride an internal DeviceDocBatch:
    standing ShadowOrder keys, O(delta) ingest, tombstones); per element
    the winning slot (last move by (lamport, peer)) and winning value
    (last set) are LWW — both kept as RESIDENT folds (LwwResident with
    the slot ROW / value ordinal as the folded value).  Materialization
    is ONE [E]-sized sort: each element gathers its winning slot's
    standing key + tombstone (a tombstoned winner hides the element; a
    newer concurrent move revives it), no slot-level re-rank."""

    def __init__(self, n_docs: int, capacity: int, elem_capacity: int, mesh=None,
                 auto_grow: bool = False):
        from ..ops.lww import NEG, LwwResident

        self.seq = DeviceDocBatch(
            n_docs, capacity, mesh=mesh, as_text=False, auto_grow=auto_grow
        )
        self.mesh = self.seq.mesh
        self.n_docs = n_docs
        self.d = self.seq.d
        self.e_cap = elem_capacity
        self.auto_grow = auto_grow
        self.elem_ids: List[Dict] = [dict() for _ in range(self.d)]
        self.values: List[list] = [[] for _ in range(self.d)]
        sh = doc_sharding(self.mesh)
        z = lambda dt, fill: jax.device_put(np.full((self.d, elem_capacity), fill, dt), sh)
        mk = lambda vfill: LwwResident(
            lamport=z(np.int32, int(NEG)),
            peer_hi=z(np.uint32, 0),
            peer_lo=z(np.uint32, 0),
            value=z(np.int32, vfill),
        )
        self.moves = mk(0)  # value = winning slot ROW in the seq buffer
        self.vals = mk(-2)  # value = winning value ordinal
        self._defer_moves = None  # coalesced-ingest accumulators
        self._defer_vals = None
        self._dev_lock = named_rlock("fleet.dev")

    # -- round coalescing (slots ride the inner seq batch's deferral;
    # the two element folds accumulate here — both associative) --------
    def begin_coalesce(self) -> None:
        if self._defer_moves is not None:
            raise RuntimeError("coalesce group already open")
        self.seq.begin_coalesce()
        self._defer_moves = _DeferredFold(self.d)
        self._defer_vals = _DeferredFold(self.d)

    def detach_coalesce(self):
        dm, self._defer_moves = self._defer_moves, None
        dv, self._defer_vals = self._defer_vals, None
        return (self.seq.detach_coalesce(), dm, dv)

    def commit_detached(self, pending) -> None:
        if pending is None:
            return
        seq_d, dm, dv = pending
        self.seq.commit_detached(seq_d)
        if dm is not None and any(dm.rows):
            self._device_fold_elem(dm.rows, "moves")
        if dv is not None and any(dv.rows):
            self._device_fold_elem(dv.rows, "vals")
        if dm is not None and dm.n_rounds:
            obs.counter("pipeline.coalesced_rounds_total").inc(
                dm.n_rounds, family="movable"
            )

    def flush_coalesce(self) -> None:
        self.commit_detached(self.detach_coalesce())

    def release_doc(self, di: int) -> None:
        """Reset doc ``di`` to a never-used slot (tiered-residency
        eviction; see DeviceDocBatch.release_doc for the contract).
        The inner seq batch releases its slot rows; both element folds
        reset to their construction fills."""
        from ..ops.lww import NEG, LwwResident

        self.seq.release_doc(di)
        self.elem_ids[di] = {}
        self.values[di] = []
        with self._dev_lock:
            self.moves = LwwResident(*_release_rows(
                tuple(self.moves), jnp.int32(di), (int(NEG), 0, 0, 0),
            ))
            self.vals = LwwResident(*_release_rows(
                tuple(self.vals), jnp.int32(di), (int(NEG), 0, 0, -2),
            ))
        obs.counter("fleet.doc_releases_total").inc(family="movable")

    def append_changes(self, per_doc_changes: Sequence[Optional[Sequence[Change]]], cid) -> None:
        """Incremental ingest: slots append into the internal seq batch
        (one block scatter), element winners fold (two donated LWW
        updates).  Staged before validation — capacity errors leave the
        batch untouched."""
        # NOTE: _walk_movable_changes intentionally mirrors
        # DeviceDocBatch._python_rows (same parent-resolution and
        # delete-span contract) but diverges in what it PRODUCES per row
        # (element ordinals + move/set fold rows vs content codes) — a
        # shared walk would need per-row callbacks for every arm; the
        # differential fuzzers pin both walks to the host engine.
        per_doc_changes = list(per_doc_changes) + [None] * (self.d - len(per_doc_changes))
        rows_per_doc: List[list] = []
        overlays: List[Dict[Tuple[int, int], int]] = []
        move_rows: List[list] = []  # (elem, lam, peer, slot_row)
        set_rows: List[list] = []  # (elem, lam, peer, value_ordinal)
        staged_elems: List[list] = []
        staged_vals: List[list] = []
        del_pairs: List[Tuple[int, int]] = []
        for di, changes in enumerate(per_doc_changes):
            rows, overlay, mrows, srows, e_staged, e_order, v_staged = self._stage_doc(
                rows_per_doc, overlays, move_rows, set_rows, staged_elems, staged_vals
            )
            if not changes:
                continue
            self._walk_movable_changes(
                di, changes, cid, rows, overlay, mrows, srows,
                e_staged, e_order, v_staged, del_pairs,
            )
        self._commit_movable(
            rows_per_doc, overlays, move_rows, set_rows,
            staged_elems, staged_vals, del_pairs,
        )

    @staticmethod
    def _stage_doc(rows_per_doc, overlays, move_rows, set_rows, staged_elems, staged_vals):
        """Allocate + register one doc's staging structures (shared by
        both ingest entry points so they commit identical shapes)."""
        rows: list = []
        overlay: Dict[Tuple[int, int], int] = {}
        mrows: list = []
        srows: list = []
        e_staged: Dict = {}
        e_order: list = []
        v_staged: list = []
        rows_per_doc.append(rows)
        overlays.append(overlay)
        move_rows.append(mrows)
        set_rows.append(srows)
        staged_elems.append(e_order)
        staged_vals.append(v_staged)
        return rows, overlay, mrows, srows, e_staged, e_order, v_staged

    def _elem_registrar(self, di, e_staged, e_order):
        """Staged element-ordinal lookup shared by BOTH ingest paths —
        the numbering must stay in lockstep with the commit loop."""
        eids = self.elem_ids[di]

        def eidx(eid):
            i = eids.get(eid)
            if i is None:
                i = e_staged.get(eid)
            if i is None:
                i = len(eids) + len(e_order)
                e_staged[eid] = i
                e_order.append(eid)
            return i

        return eidx

    def _walk_movable_changes(
        self, di, changes, cid, rows, overlay, mrows, srows,
        e_staged, e_order, v_staged, del_pairs,
    ) -> None:
        """Per-doc python change walk (also the append_payloads
        fallback): produces slot rows + move/set fold rows + staged
        element/value registrations."""
        from ..core.change import MovableMove, MovableSet, SeqDelete, SeqInsert
        from ..oplog.oplog import _RunCont

        idmap = self.seq.id2row[di]
        base = int(self.seq.counts[di])
        n_vals = len(self.values[di])
        eidx = self._elem_registrar(di, e_staged, e_order)

        def vidx(v):
            v_staged.append(v)
            return n_vals + len(v_staged) - 1

        def resolve(key):
            return _resolve_row(overlay, idmap, key, di, "movable op parent")

        def resolve_parent(c, peer, counter):
            if isinstance(c.parent, _RunCont):
                return resolve((peer, counter - 1))
            if c.parent is None:
                return -1
            return resolve((c.parent.peer, c.parent.counter))

        for ch in changes:
            for op in ch.ops:
                if op.container != cid:
                    continue
                c = op.content
                lam = ch.lamport + (op.counter - ch.ctr_start)
                if isinstance(c, SeqInsert):
                    body = c.content
                    for j in range(len(body)):
                        if j == 0:
                            prow = resolve_parent(c, ch.peer, op.counter)
                            side = int(c.side)
                        else:
                            prow = base + len(rows) - 1
                            side = 1
                        row = base + len(rows)
                        eid = (ch.peer, op.counter + j)
                        ei = eidx(eid)
                        overlay[eid] = row
                        rows.append((prow, side, op.counter + j, ei, ch.peer))
                        mrows.append((ei, lam + j, ch.peer, row))
                        srows.append((ei, lam + j, ch.peer, vidx(body[j])))
                elif isinstance(c, MovableMove):
                    prow = resolve_parent(c, ch.peer, op.counter)
                    row = base + len(rows)
                    ei = eidx((c.elem.peer, c.elem.counter))
                    overlay[(ch.peer, op.counter)] = row
                    rows.append((prow, int(c.side), op.counter, ei, ch.peer))
                    mrows.append((ei, lam, ch.peer, row))
                elif isinstance(c, MovableSet):
                    ei = eidx((c.elem.peer, c.elem.counter))
                    srows.append((ei, lam, ch.peer, vidx(c.value)))
                elif isinstance(c, SeqDelete):
                    # deletes tolerate unknown targets (same as the
                    # native paths): a missing target means the insert
                    # is missing too, which the parent resolution flags
                    for sp in c.spans:
                        for ctr in range(sp.start, sp.end):
                            row_d = overlay.get((sp.peer, ctr))
                            if row_d is None:
                                row_d = idmap.get((sp.peer, ctr))
                            if row_d is not None:
                                del_pairs.append((di, row_d))

    def append_payloads(self, per_doc_payloads: Sequence[Optional[bytes]], cid) -> None:
        """Incremental NATIVE ingest: envelope-stripped payloads -> C++
        movable delta explode (cross-epoch slot parents resolved through
        the seq batch's id maps via the ext-ref protocol) -> one block
        scatter + two donated folds.  Falls back to the Python walk per
        unresolvable payload."""
        from ..codec.binary import decode_changes, read_tables
        from ..native import available, decode_value_at, explode_movable_delta_payload

        if not available():
            self.append_changes(
                [decode_changes(p) if p else None for p in per_doc_payloads], cid
            )
            return
        per_doc_payloads = list(per_doc_payloads) + [None] * (
            self.d - len(per_doc_payloads)
        )
        rows_per_doc: List[list] = []
        overlays: List[Dict[Tuple[int, int], int]] = []
        move_rows: List[list] = []
        set_rows: List[list] = []
        staged_elems: List[list] = []
        staged_vals: List[list] = []
        del_pairs: List[Tuple[int, int]] = []
        for di, payload in enumerate(per_doc_payloads):
            rows, overlay, mrows, srows, e_staged, e_order, v_staged = self._stage_doc(
                rows_per_doc, overlays, move_rows, set_rows, staged_elems, staged_vals
            )
            if not payload:
                continue
            idmap = self.seq.id2row[di]
            base = int(self.seq.counts[di])
            n_vals = len(self.values[di])
            n_dels_start = len(del_pairs)
            eidx = self._elem_registrar(di, e_staged, e_order)

            # NOTE: per-row python loop (vs the seq analog's vectorized
            # fast path) — movable epochs are move/set-dominated and
            # small; vectorize like DeviceDocBatch.append_payloads if a
            # full-history movable ingest ever shows up hot
            try:
                peers_wire, _keys, cids, _r = read_tables(payload)
                try:
                    target = cids.index(cid)
                except ValueError:
                    continue  # no ops for this container
                out = explode_movable_delta_payload(payload, target)
                sl = out["slots"]
                n = len(sl["parent"])
                for i in range(n):
                    prow = int(sl["parent"][i])
                    if prow >= 0:
                        prow = base + prow
                    elif prow == -2:  # cross-epoch parent: id-map lookup
                        key = (
                            int(peers_wire[int(sl["ext_peer_idx"][i])]),
                            int(sl["ext_counter"][i]),
                        )
                        r_ = overlay.get(key)
                        prow = idmap[key] if r_ is None else r_
                    peer = int(peers_wire[int(sl["peer_idx"][i])])
                    ctr_v = int(sl["counter"][i])
                    ei = eidx(
                        (int(peers_wire[int(sl["elem_peer_idx"][i])]), int(sl["elem_ctr"][i]))
                    )
                    row = base + i
                    overlay[(peer, ctr_v)] = row
                    rows.append((prow, int(sl["side"][i]), ctr_v, ei, peer))
                    mrows.append((ei, int(sl["lamport"][i]), peer, row))
                st = out["sets"]
                for i in range(len(st["lamport"])):
                    ei = eidx(
                        (int(peers_wire[int(st["elem_peer_idx"][i])]), int(st["elem_ctr"][i]))
                    )
                    v_staged.append(
                        decode_value_at(payload, int(st["value_off"][i]), cids)
                    )
                    srows.append(
                        (
                            ei,
                            int(st["lamport"][i]),
                            int(peers_wire[int(st["peer_idx"][i])]),
                            n_vals + len(v_staged) - 1,
                        )
                    )
                dl = out["dels"]
                for i in range(len(dl["peer_idx"])):
                    dp = int(peers_wire[int(dl["peer_idx"][i])])
                    for ctr_v in range(int(dl["start"][i]), int(dl["end"][i])):
                        row = overlay.get((dp, ctr_v))
                        if row is None:
                            row = idmap.get((dp, ctr_v))
                        if row is not None:
                            del_pairs.append((di, row))
            except (KeyError, ValueError):
                rows.clear()
                overlay.clear()
                mrows.clear()
                srows.clear()
                e_staged.clear()
                e_order.clear()
                v_staged.clear()
                del del_pairs[n_dels_start:]
                self._walk_movable_changes(
                    di, decode_changes(payload), cid, rows, overlay, mrows,
                    srows, e_staged, e_order, v_staged, del_pairs,
                )
        self._commit_movable(
            rows_per_doc, overlays, move_rows, set_rows,
            staged_elems, staged_vals, del_pairs,
        )

    @property
    def epoch(self) -> int:
        """Ingest-epoch clock (rides the inner seq batch; snapshot after
        an append, pass back to compact() once every replica acked it)."""
        return self.seq.epoch

    def compact(self, stable_epochs: Sequence[Optional[int]]) -> int:
        """Reclaim stable dead SLOT rows: tombstoned ones (deleted
        elements' history) AND superseded ones — a move's losing slot is
        invisible forever but only droppable once the WINNING slot's
        ingest epoch is acked everywhere (a replica that hasn't seen the
        winner still treats the old slot as visible).  Slots are
        sequence elements, so the seq batch's compaction rules apply;
        every element's winning slot row (the moves fold stores device
        ROW indices) is protected and the fold is rewritten through the
        row remap afterwards.  Element registries and value stores are
        untouched (ordinals, not rows)."""
        from ..ops.lww import NEG

        stable_list = list(stable_epochs) + [None] * (self.d - len(stable_epochs))
        if all(e is None for e in stable_list):
            return 0  # nothing to do: skip the device fetches
        mh = np.asarray(self.moves.value).copy()
        # untouched fold slots carry the value FILL (0) — only slots a
        # move actually folded into (lamport != NEG) reference rows
        folded = np.asarray(self.moves.lamport) != int(NEG)
        mh[~folded] = -1
        content = np.asarray(self.seq.cols.content)
        protect: List[Optional[np.ndarray]] = []
        extra_dead: List[Optional[np.ndarray]] = []
        for di in range(self.d):
            wr = mh[di][mh[di] >= 0].astype(np.int64)
            protect.append(np.unique(wr) if len(wr) else None)
            stable_e = stable_list[di]
            k = int(self.seq.counts[di])
            if stable_e is None or not k or not len(wr):
                extra_dead.append(None)
                continue
            # superseded slot r (element e = content[r], winner w != r)
            # is stable-dead when the winner's ingest epoch is acked
            e_arr = content[di, :k].astype(np.int64)
            valid_e = e_arr >= 0
            w_of_row = np.where(valid_e, mh[di][np.clip(e_arr, 0, None)], -1)
            w_epoch = np.where(
                w_of_row >= 0,
                self.seq.row_epoch[di][np.clip(w_of_row, 0, None)],
                -1,
            )
            sup = (
                valid_e
                & (w_of_row >= 0)
                & (w_of_row != np.arange(k))
                & (w_epoch >= 0)
                & (w_epoch <= int(stable_e))
            )
            rows_s = np.flatnonzero(sup)
            extra_dead.append(rows_s if len(rows_s) else None)
        reclaimed, remaps = self.seq.compact(
            stable_epochs,
            extra_protect=protect,
            extra_dead=extra_dead,
            return_remaps=True,
        )
        if reclaimed and remaps:
            # rewrite on a FRESH copy: mh is the protection scratch with
            # unfolded slots forced to -1, and persisting that would
            # change the documented fill (0) of untouched fold slots
            out = np.asarray(self.moves.value).copy()
            for di, remap in remaps.items():
                row = out[di]
                mask = folded[di] & (row >= 0) & (row < len(remap))
                row[mask] = remap[row[mask]]
            self.moves = self.moves._replace(
                value=jax.device_put(out, doc_sharding(self.mesh))
            )
        return reclaimed

    def grow(self, capacity: int = None, elem_capacity: int = None) -> None:
        """Repack: slot rows grow through the inner seq batch; element
        winner columns re-pad here (resident lifecycle, r4 verdict #6)."""
        from ..ops.lww import LwwResident

        if capacity is not None:
            self.seq.grow(capacity)
        if elem_capacity is not None and elem_capacity > self.e_cap:
            with self._dev_lock:  # vs an in-flight pipelined commit
                sh = doc_sharding(self.mesh)
                for name, vfill in (("moves", 0), ("vals", -2)):
                    res = getattr(self, name)
                    fills = _lww_fills(vfill)
                    setattr(
                        self,
                        name,
                        LwwResident(**_pad_axis1(
                            {f: getattr(res, f) for f in res._fields},
                            elem_capacity, fills, sh,
                        )),
                    )
                self.e_cap = elem_capacity

    def _commit_movable(
        self, rows_per_doc, overlays, move_rows, set_rows,
        staged_elems, staged_vals, del_pairs,
    ) -> None:
        """Shared tail: validate, commit registrations, scatter + folds."""
        from ..ops.fugue_batch import pad_bucket
        from ..ops.lww import lww_update_resident

        # validate BEFORE mutating (element capacity; the seq batch
        # validates row capacity in _commit_rows before ITS mutation)
        req_elems = max(
            (len(self.elem_ids[di]) + len(staged_elems[di]) for di in range(self.d)),
            default=0,
        )
        if req_elems > self.e_cap:
            if self.auto_grow:
                self.grow(elem_capacity=_grow_target(req_elems, self.e_cap))
            else:
                raise RuntimeError(
                    f"DeviceMovableBatch element capacity exceeded: a doc "
                    f"needs {req_elems} elements > {self.e_cap}"
                )
        self.seq._commit_rows(rows_per_doc, overlays, del_pairs)
        # commit staged element/value registrations
        for di in range(self.d):
            for eid in staged_elems[di]:
                self.elem_ids[di][eid] = len(self.elem_ids[di])
            self.values[di].extend(staged_vals[di])
        # fold element winners (moves then values)
        if self._defer_moves is not None:
            set_only = not any(move_rows) and any(set_rows)
            self._defer_moves.extend(move_rows)
            self._defer_vals.extend(set_rows)
            if set_only:
                # a set-only round still shipped device work: count it
                # on the moves accumulator (the group's round tally)
                self._defer_moves.n_rounds += 1
            return
        for rows_set, res_name in ((move_rows, "moves"), (set_rows, "vals")):
            if any(rows_set):
                self._device_fold_elem(rows_set, res_name)

    def _device_fold_elem(self, rows_set, res_name: str) -> None:
        from ..ops.fugue_batch import pad_bucket
        from ..ops.lww import lww_update_resident

        obs.counter("fleet.device_launches_total").inc(family="resident_movable")
        with self._dev_lock:
            sh = doc_sharding(self.mesh)
            put = lambda a: jax.device_put(a, sh)
            m = pad_bucket(max(len(r) for r in rows_set), floor=16)
            shp = (self.d, m)
            elem = np.full(shp, self.e_cap, np.int32)
            lam = np.zeros(shp, np.int32)
            hi = np.zeros(shp, np.uint32)
            lo = np.zeros(shp, np.uint32)
            val = np.full(shp, -2, np.int32)
            valid = np.zeros(shp, bool)
            for di, rws in enumerate(rows_set):
                for i, (ei, lm, peer, v) in enumerate(rws):
                    elem[di, i] = ei
                    lam[di, i] = lm
                    hi[di, i] = peer >> 32
                    lo[di, i] = peer & 0xFFFFFFFF
                    val[di, i] = v
                    valid[di, i] = True
            setattr(
                self,
                res_name,
                lww_update_resident(
                    getattr(self, res_name),
                    put(elem),
                    put(lam),
                    put(hi),
                    put(lo),
                    put(valid),
                    self.e_cap,
                    value=put(val),
                ),
            )

    # -- checkpoint/resume --------------------------------------------
    STATE_VERSION = 2  # v2: + auto_grow lifecycle flag

    def export_state(self) -> bytes:
        """Serialize the movable batch: the nested slot-sequence batch
        rides its own export; element folds, dictionaries and values
        layer on top."""
        from ..codec.binary import Writer, _Dicts
        from ..storage import MemKvStore

        kv = MemKvStore()
        d = _Dicts()
        meta = Writer()
        meta.u8(self.STATE_VERSION)
        meta.varint(self.n_docs)
        meta.varint(self.d)
        meta.varint(self.e_cap)
        meta.u8(1 if self.auto_grow else 0)  # v2
        kv.set(b"meta", bytes(meta.buf))
        kv.set(b"seq", self.seq.export_state())
        _state_write_grid(kv, b"moves", [np.asarray(a) for a in self.moves])
        _state_write_grid(kv, b"vals", [np.asarray(a) for a in self.vals])
        for di in range(self.d):
            w = Writer()
            w.varint(len(self.elem_ids[di]))
            for (peer, ctr), i in self.elem_ids[di].items():
                w.u64le(peer)
                w.zigzag(ctr)
                w.varint(i)
            kv.set(b"doc/%08d/elems" % di, bytes(w.buf))
            w = Writer()
            _state_write_values(w, d, self.values[di])
            kv.set(b"doc/%08d/values" % di, bytes(w.buf))
        kv.set(b"dicts", _state_dicts_blob(d))
        return kv.export_all()

    @classmethod
    def import_state(cls, data: bytes, mesh=None) -> "DeviceMovableBatch":
        from ..codec.binary import Reader
        from ..errors import DecodeError
        from ..ops.lww import LwwResident
        from ..storage import MemKvStore

        kv = MemKvStore()
        kv.import_all(data)
        meta_b, dicts_b, seq_b = kv.get(b"meta"), kv.get(b"dicts"), kv.get(b"seq")
        if meta_b is None or dicts_b is None or seq_b is None:
            raise DecodeError("DeviceMovableBatch state: missing sections")
        try:
            r = Reader(meta_b)
            version = r.u8()
            if version > cls.STATE_VERSION:
                raise DecodeError(f"DeviceMovableBatch state v{version} too new")
            n_docs, d_saved, e_cap = r.varint(), r.varint(), r.varint()
            auto_grow = (r.u8() == 1) if version >= 2 else False
        except (IndexError, ValueError) as e:
            raise DecodeError(
                f"DeviceMovableBatch state: malformed meta ({e})"
            ) from None
        _state_sane_sizes("DeviceMovableBatch", d_saved, elem_capacity=e_cap)
        if not 0 < n_docs <= d_saved:
            raise DecodeError("DeviceMovableBatch state: implausible n_docs")
        _peers, cids = _state_read_dicts(dicts_b)
        seq = DeviceDocBatch.import_state(seq_b, mesh=mesh)
        batch = cls.__new__(cls)
        batch.seq = seq
        batch.mesh = seq.mesh
        batch.n_docs = n_docs
        batch.d = seq.d
        batch.e_cap = e_cap
        batch.auto_grow = auto_grow  # review r5: __new__ skips __init__
        batch._defer_moves = batch._defer_vals = None
        batch._dev_lock = named_rlock("fleet.dev")
        batch.elem_ids = [dict() for _ in range(batch.d)]
        batch.values = [[] for _ in range(batch.d)]
        sh = doc_sharding(batch.mesh)
        lim = min(batch.d, d_saved)
        for name in ("moves", "vals"):
            blob = kv.get(name.encode())
            if blob is None:
                raise DecodeError(f"DeviceMovableBatch state: missing {name}")
            grids = _state_read_grid(
                blob,
                [
                    ((d_saved, e_cap), dt)
                    for dt in (np.int32, np.uint32, np.uint32, np.int32)
                ],
            )
            from ..ops.lww import NEG

            _f = _lww_fills(0 if name == "moves" else -2)
            defaults = (_f["lamport"], _f["peer_hi"], _f["peer_lo"], _f["value"])
            host = [
                np.full((batch.d, e_cap), fill, dt)
                for fill, dt in zip(defaults, (np.int32, np.uint32, np.uint32, np.int32))
            ]
            for h, g in zip(host, grids):
                h[:lim] = g[:lim]
            if name == "vals":
                vals_host_value = host[3]
            elif name == "moves":
                # folded slot-row references must stay inside the seq
                # buffer (compact's winner-epoch lookup and the kernel's
                # row gathers index with them)
                folded = host[0] != int(NEG)
                wrow = host[3][folded].astype(np.int64)
                if wrow.size and (wrow.min() < 0 or wrow.max() >= batch.seq.cap):
                    raise DecodeError("DeviceMovableBatch state: winner row")
            setattr(batch, name, LwwResident(*[jax.device_put(h, sh) for h in host]))
        try:
            for di in range(lim):
                elems_b = kv.get(b"doc/%08d/elems" % di)
                if elems_b is not None:
                    r = Reader(elems_b)
                    eids: Dict = {}
                    for _ in range(r.varint()):
                        peer = r.u64le()
                        ctr = r.zigzag()
                        i = r.varint()
                        if i >= e_cap:
                            raise DecodeError("DeviceMovableBatch state: elem ordinal")
                        eids[(peer, ctr)] = i
                    batch.elem_ids[di] = eids
                vals_b = kv.get(b"doc/%08d/values" % di)
                if vals_b is not None:
                    batch.values[di] = _state_read_values(vals_b, cids)
                # folded value ordinals must stay inside the value store
                # (value_lists would IndexError otherwise)
                vv = vals_host_value[di].astype(np.int64)
                vv = vv[vv >= 0]
                if vv.size and vv.max() >= len(batch.values[di]):
                    raise DecodeError("DeviceMovableBatch state: value ordinal")
        except (IndexError, ValueError, struct.error) as e:
            raise DecodeError(
                f"DeviceMovableBatch state: malformed doc ({e})"
            ) from None
        return batch

    def export_select(self, index, requests, sup=None):
        """Batched read-plane selection for the sync pull path."""
        return _batch_export_select(self, "movable", index, requests, sup)

    def value_lists(self) -> List[list]:
        """Materialize every doc's ordered element values (one launch;
        same contract as Fleet.merge_movable_changes per doc)."""
        from ..ops.movable_batch import movable_by_key_batch

        out_idx, counts = movable_by_key_batch(
            self.seq.cols.valid,
            self.seq.cols.deleted,
            self.seq.key_hi,
            self.seq.key_lo,
            self.moves.value,
            self.moves.lamport,
            self.vals.value,
        )
        out_idx = np.asarray(out_idx)
        counts = np.asarray(counts)
        return [
            [self.values[di][j] for j in out_idx[di, : counts[di]]]
            for di in range(self.n_docs)
        ]


# ---- shared checkpoint helpers (fleet-scale checkpoint/resume) --------


def _state_sane_sizes(cls_name: str, d_saved: int, **fields) -> None:
    """Reject implausible size fields BEFORE allocating host/device
    arrays from them — a few flipped meta bytes must produce
    DecodeError, not a multi-GB allocation (checkpoint fuzz contract).
    Bounds are generous (16M per axis, 128M grid entries)."""
    from ..errors import DecodeError

    if not 0 < d_saved <= 1 << 20:
        raise DecodeError(f"{cls_name} state: implausible doc width {d_saved}")
    for name, v in fields.items():
        if not 0 < v <= 1 << 24:
            raise DecodeError(f"{cls_name} state: implausible {name} {v}")
        if d_saved * v > 1 << 27:
            raise DecodeError(
                f"{cls_name} state: implausible grid {d_saved}x{v} ({name})"
            )


def _state_dicts_blob(d) -> bytes:
    """Serialize the peer/cid dictionaries (cid peers pre-registered —
    the encode_changes guard)."""
    from ..codec.binary import Writer, _write_cid

    for c in d.cids:
        if not c.is_root:
            d.peer(c.peer)
    w = Writer()
    w.varint(len(d.peers))
    for p in d.peers:
        w.u64le(p)
    w.varint(len(d.cids))
    for c in d.cids:
        _write_cid(w, d, c)
    return bytes(w.buf)


def _state_read_dicts(blob: bytes):
    from ..codec.binary import Reader, _read_cid
    from ..errors import DecodeError

    try:
        r = Reader(blob)
        peers = [r.u64le() for _ in range(r.varint())]
        cids: List[ContainerID] = []
        for _ in range(r.varint()):
            cids.append(_read_cid(r, peers))
        return peers, cids
    except (IndexError, ValueError, struct.error) as e:
        raise DecodeError(f"resident state: malformed dicts ({e})") from None


def _state_write_values(w, d, values) -> None:
    from ..codec.binary import _write_value

    w.varint(len(values))
    for i, v in enumerate(values):
        if isinstance(v, _LazyValue):
            v = v.decode()
            values[i] = v  # cache: repeat exports stay O(new values)
        _write_value(w, d, v)


def _state_read_values(blob: bytes, cids) -> list:
    from ..codec.binary import Reader, _read_value
    from ..errors import DecodeError

    try:
        r = Reader(blob)
        return [_read_value(r, cids) for _ in range(r.varint())]
    except (IndexError, ValueError, struct.error, UnicodeDecodeError) as e:
        raise DecodeError(f"resident state: malformed values ({e})") from None


def _state_write_grid(kv, key: bytes, arrays) -> None:
    """One [D, S] array set as raw little-endian buffers."""
    from ..codec.binary import Writer

    w = Writer()
    for a in arrays:
        w.bytes_(np.asarray(a).tobytes())
    kv.set(key, bytes(w.buf))


def _state_read_grid(blob: bytes, shapes_dtypes):
    from ..codec.binary import Reader
    from ..errors import DecodeError

    try:
        r = Reader(blob)
        out = []
        for shape, dt in shapes_dtypes:
            buf = np.frombuffer(r.bytes_(), dt)
            if buf.size != int(np.prod(shape)):
                raise DecodeError("resident state: grid size mismatch")
            out.append(buf.reshape(shape).copy())
        return out
    except (IndexError, ValueError) as e:
        raise DecodeError(f"resident state: malformed grid ({e})") from None


class DeviceCounterBatch:
    """Device-resident counter sums for a doc batch (increments are
    commutative, so the resident state IS the fold — one donated
    scatter-add per append, the cheapest member of the resident
    family).

    Precision contract: device sums are float32 (x64 is disabled on the
    TPU path; same contract as the one-shot merge_counter_changes), so
    values match the host's f64 CounterState exactly for integer-valued
    deltas up to 2^24 and to f32 rounding otherwise."""

    def __init__(self, n_docs: int, slot_capacity: int, mesh=None,
                 auto_grow: bool = False):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_docs = n_docs
        self.d = _mesh_pad(self.mesh, n_docs)
        self.s = slot_capacity
        self.auto_grow = auto_grow
        self.slot_of: List[Dict[ContainerID, int]] = [dict() for _ in range(self.d)]
        self.sums = jax.device_put(
            np.zeros((self.d, self.s), np.float32), doc_sharding(self.mesh)
        )
        # ingest-epoch clock (parity with the seq/tree batches — the
        # server journals rounds against it; folds never compact)
        self.epoch = 0
        self._defer = None  # coalesced-ingest accumulator
        self._dev_lock = named_rlock("fleet.dev")

    # -- round coalescing (float add is associative for the documented
    # integer-delta precision contract; epoch still bumps per round) ---
    def begin_coalesce(self) -> None:
        if self._defer is not None:
            raise RuntimeError("coalesce group already open")
        self._defer = _DeferredFold(self.d)

    def detach_coalesce(self):
        d, self._defer = self._defer, None
        return d

    def commit_detached(self, d) -> None:
        if d is None or not any(d.rows):
            return
        self._device_fold(d.rows)
        obs.counter("pipeline.coalesced_rounds_total").inc(
            d.n_rounds, family="counter"
        )

    def flush_coalesce(self) -> None:
        self.commit_detached(self.detach_coalesce())

    def grow(self, new_slot_capacity: int) -> None:
        """Repack counter sums to a larger slot capacity (resident
        lifecycle, r4 verdict #6)."""
        if new_slot_capacity <= self.s:
            return
        with self._dev_lock:  # vs an in-flight pipelined commit
            self.sums = _pad_axis1(
                {"sums": self.sums}, new_slot_capacity, {"sums": 0.0},
                doc_sharding(self.mesh),
            )["sums"]
            self.s = new_slot_capacity

    def release_doc(self, di: int) -> None:
        """Reset doc ``di`` to a never-used slot (tiered-residency
        eviction; see DeviceDocBatch.release_doc for the contract)."""
        self.slot_of[di] = {}
        with self._dev_lock:
            (self.sums,) = _release_rows(
                (self.sums,), jnp.int32(di), (0.0,)
            )
        obs.counter("fleet.doc_releases_total").inc(family="counter")

    def append_changes(self, per_doc_changes: Sequence[Optional[Sequence[Change]]]) -> None:
        from ..core.change import CounterIncr
        from ..ops.fugue_batch import pad_bucket

        per_doc_changes = list(per_doc_changes) + [None] * (self.d - len(per_doc_changes))
        rows_per_doc: List[list] = []
        staged_slots: List[list] = []
        for di, changes in enumerate(per_doc_changes):
            rows: list = []
            staged: Dict = {}
            order: list = []
            rows_per_doc.append(rows)
            staged_slots.append(order)
            if not changes:
                continue
            slots = self.slot_of[di]

            def slot_idx(cid):
                i = slots.get(cid)
                if i is None:
                    i = staged.get(cid)
                if i is None:
                    i = len(slots) + len(order)
                    staged[cid] = i
                    order.append(cid)
                return i

            for ch in changes:
                for op in ch.ops:
                    if isinstance(op.content, CounterIncr):
                        rows.append((slot_idx(op.container), float(op.content.delta)))
        req = max(
            (len(self.slot_of[di]) + len(staged_slots[di]) for di in range(self.d)),
            default=0,
        )
        if req > self.s:
            if self.auto_grow:
                self.grow(_grow_target(req, self.s))
            else:
                raise RuntimeError(
                    f"DeviceCounterBatch slot capacity exceeded: a doc needs "
                    f"{req} slots > {self.s}"
                )
        self.epoch += 1  # post-validation: dates this append (journal clock)
        if not any(rows_per_doc):
            return
        for di, order in enumerate(staged_slots):
            for cid in order:
                self.slot_of[di][cid] = len(self.slot_of[di])
        if self._defer is not None:
            self._defer.extend(rows_per_doc)
            return
        self._device_fold(rows_per_doc)

    def _device_fold(self, rows_per_doc) -> None:
        from ..ops.fugue_batch import pad_bucket

        obs.counter("fleet.device_launches_total").inc(family="resident_counter")
        with self._dev_lock:
            m = pad_bucket(max(len(r) for r in rows_per_doc), floor=16)
            slot = np.full((self.d, m), self.s, np.int32)  # dump slot
            delta = np.zeros((self.d, m), np.float32)
            for di, rows in enumerate(rows_per_doc):
                for i, (s_, dl) in enumerate(rows):
                    slot[di, i] = s_
                    delta[di, i] = dl
            sh = doc_sharding(self.mesh)
            self.sums = _fold_counter_rows(
                self.sums, jax.device_put(slot, sh), jax.device_put(delta, sh)
            )

    def export_select(self, index, requests, sup=None):
        """Batched read-plane selection for the sync pull path (the
        counter fold keeps no per-op rows — the change-span index is
        the only delta history, same as map)."""
        return _batch_export_select(self, "counter", index, requests, sup)

    def value_maps(self) -> List[Dict[ContainerID, float]]:
        sums = np.asarray(self.sums)
        return [
            {cid: float(sums[di, s_]) for cid, s_ in self.slot_of[di].items()}
            for di in range(self.n_docs)
        ]

    # -- checkpoint/resume --------------------------------------------
    STATE_VERSION = 3  # v3: + ingest epoch clock

    def export_state(self) -> bytes:
        from ..codec.binary import Writer, _Dicts
        from ..storage import MemKvStore

        kv = MemKvStore()
        d = _Dicts()
        meta = Writer()
        meta.u8(self.STATE_VERSION)
        meta.varint(self.n_docs)
        meta.varint(self.d)
        meta.varint(self.s)
        meta.u8(1 if self.auto_grow else 0)  # v2
        meta.varint(self.epoch)  # v3
        kv.set(b"meta", bytes(meta.buf))
        _state_write_grid(kv, b"sums", [np.asarray(self.sums)])
        for di in range(self.d):
            w = Writer()
            w.varint(len(self.slot_of[di]))
            for cid, s_ in self.slot_of[di].items():
                w.varint(d.cid(cid))
                w.varint(s_)
            kv.set(b"doc/%08d/slots" % di, bytes(w.buf))
        kv.set(b"dicts", _state_dicts_blob(d))
        return kv.export_all()

    @classmethod
    def import_state(cls, data: bytes, mesh=None) -> "DeviceCounterBatch":
        from ..codec.binary import Reader
        from ..errors import DecodeError
        from ..storage import MemKvStore

        kv = MemKvStore()
        kv.import_all(data)
        meta_b, dicts_b = kv.get(b"meta"), kv.get(b"dicts")
        if meta_b is None or dicts_b is None:
            raise DecodeError("DeviceCounterBatch state: missing meta/dicts")
        try:
            r = Reader(meta_b)
            version = r.u8()
            if version > cls.STATE_VERSION:
                raise DecodeError(f"DeviceCounterBatch state v{version} too new")
            n_docs, d_saved, s = r.varint(), r.varint(), r.varint()
            auto_grow = (r.u8() == 1) if version >= 2 else False
            epoch = r.varint() if version >= 3 else 0
        except (IndexError, ValueError) as e:
            raise DecodeError(f"DeviceCounterBatch state: malformed meta ({e})") from None
        _state_sane_sizes("DeviceCounterBatch", d_saved, slot_capacity=s)
        if not 0 < n_docs <= d_saved:
            raise DecodeError("DeviceCounterBatch state: implausible n_docs")
        _peers, cids = _state_read_dicts(dicts_b)
        batch = cls(n_docs, s, mesh=mesh, auto_grow=auto_grow)
        batch.epoch = epoch
        sums_b = kv.get(b"sums")
        if sums_b is None:
            raise DecodeError("DeviceCounterBatch state: missing sums")
        (grid,) = _state_read_grid(sums_b, [((d_saved, s), np.float32)])
        host = np.asarray(batch.sums).copy()
        lim = min(batch.d, d_saved)
        host[:lim] = grid[:lim]
        batch.sums = jax.device_put(host, doc_sharding(batch.mesh))
        for di in range(lim):
            slots_b = kv.get(b"doc/%08d/slots" % di)
            if slots_b is not None:
                try:
                    r = Reader(slots_b)
                    so: Dict[ContainerID, int] = {}
                    for _ in range(r.varint()):
                        ci = r.varint()
                        if ci >= len(cids):
                            raise DecodeError("DeviceCounterBatch state: cid index")
                        s_ = r.varint()
                        if s_ >= s:
                            raise DecodeError("DeviceCounterBatch state: slot index")
                        so[cids[ci]] = s_
                    batch.slot_of[di] = so
                except (IndexError, ValueError) as e:
                    raise DecodeError(
                        f"DeviceCounterBatch state: malformed slots ({e})"
                    ) from None
        return batch


@functools.partial(jax.jit, donate_argnums=(0,))
def _fold_counter_rows(sums, slot, delta):
    from ..ops.lww import counter_merge_doc

    def per_doc(acc, s_, dl):
        # one canonical counter-sum kernel (rows with slot >= S are the
        # padding the dump slot swallows)
        return acc + counter_merge_doc(s_, dl, s_ < acc.shape[0], acc.shape[0])

    return jax.vmap(per_doc)(sums, slot, delta)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(4,))
def _scatter_tree_rows(cols, blk, d_idx, offsets, mesh):
    """Write the block of the documents a round NAMES into the resident
    move log (donated update): the tree's copy of ``_scatter_rows``' row
    loop.  ``cols`` is the ``TreeLogCols`` table ``[d, move_cap]``;
    ``blk`` holds its seven fields as ``[k_pad, max_new]``, row ``j``
    being document ``d_idx[j]``'s new rows for the window ``[offsets[j],
    offsets[j] + max_new)``, replaced under ``valid`` (the window's
    other rows keep their values).  One ``dynamic_update_slice`` a
    block row and field, so the bytes moved follow ``k_pad * max_new``;
    one compiled program per ``(k_pad, max_new, move_cap)`` bucket.  Pad
    entries carry the document index -1, which no device holds, and
    write back the window they read.  On a mesh of several devices the
    table is doc-sharded and the block, ``d_idx`` and ``offsets`` are
    replicated: under ``shard_map`` each device writes the rows of the
    documents it holds."""
    fields = cols._fields
    arrays = tuple(getattr(cols, f) for f in fields)
    news = tuple(blk[f] for f in fields)
    sharded = mesh.size > 1

    def on_device(arrays, news, valid, d_idx, offsets):
        d_local = arrays[0].shape[0]
        lo = jax.lax.axis_index(DOC_AXIS) * d_local if sharded else 0
        width = valid.shape[1]

        def write_row(j, arrays):
            di = d_idx[j] - lo
            held = (di >= 0) & (di < d_local)
            at = (jnp.clip(di, 0, d_local - 1), offsets[j])
            keep = (valid[j] & held)[None]
            out = []
            for a, nb in zip(arrays, news):
                window = jax.lax.dynamic_slice(a, at, (1, width))
                row = jax.lax.dynamic_slice(nb, (j, 0), (1, width))
                out.append(jax.lax.dynamic_update_slice(
                    a, jnp.where(keep, row, window), at))
            return tuple(out)

        return jax.lax.fori_loop(0, d_idx.shape[0], write_row, arrays)

    if sharded:
        docs, rep = P(DOC_AXIS), P()
        on_device = jax.shard_map(
            on_device, mesh=mesh, in_specs=(docs, rep, rep, rep, rep),
            out_specs=docs, check_vma=False,
        )
    out = on_device(arrays, news, blk["valid"], d_idx, offsets)
    return type(cols)(**dict(zip(fields, out)))


# the fields of a tree move's host metadata (``DeviceTreeBatch.move_meta``),
# each chunk's positions lying in its own ``arena`` (u8)
_TREE_META_FIELDS = (
    "lamport", "peer", "counter", "target", "is_del", "pos_off", "pos_len",
)


def _tree_columns_of(lamport, peer, counter, target, parent, is_del, positions) -> dict:
    """Move columns from per-move sequences (the Python walk's rows, a
    saved blob's metadata) in ``DeviceTreeBatch._stage_payload``'s form:
    ``_TREE_META_FIELDS`` + ``arena`` (+ ``parent`` unless None);
    ``positions`` are bytes or None, packed into the arena (``pos_len``
    and ``pos_off`` -1 for None)."""
    n = len(lamport)
    pos_len = np.fromiter(
        (-1 if p is None else len(p) for p in positions), np.int32, n)
    lens = np.maximum(pos_len, 0).astype(np.int64)
    cols = {
        "lamport": np.asarray(lamport, np.int64),
        "peer": np.asarray(peer, np.uint64),
        "counter": np.asarray(counter, np.int64),
        "target": np.asarray(target, np.int32),
        "is_del": np.asarray(is_del, bool),
        "pos_off": np.where(pos_len >= 0, np.cumsum(lens) - lens, -1),
        "pos_len": pos_len,
        "arena": np.frombuffer(b"".join(p for p in positions if p), np.uint8),
    }
    if parent is not None:
        cols["parent"] = np.asarray(parent, np.int32)
    return cols


def _payload_positions(payload, pos_off, pos_len):
    """The positions a payload's moves name as (arena, offsets, lengths)
    with the payload itself as the arena (a u8 view, no copy); offset
    and length -1 where a move has none.  Raises ValueError for a
    position outside the payload."""
    if not isinstance(payload, bytes):
        payload = bytes(payload)  # the arena outlives the caller's buffer
    has = pos_len >= 0
    if has.any() and (pos_off[has].min() < 0
                      or (pos_off + pos_len)[has].max() > len(payload)):
        raise ValueError("tree position outside the payload")
    return (np.frombuffer(payload, np.uint8),
            np.where(has, pos_off, -1).astype(np.int64),
            np.where(has, pos_len, -1).astype(np.int32))


def _tree_meta_join(chunks) -> dict:
    """One document's metadata chunks as one set of columns whose arena
    holds their positions alone, in row order (a chunk staged from a
    payload keeps the whole payload as its arena until this read)."""
    if not chunks:
        return _tree_columns_of((), (), (), (), None, (), ())
    lens = [np.maximum(c["pos_len"], 0).astype(np.int64) for c in chunks]
    if len(chunks) == 1 and len(chunks[0]["arena"]) == int(lens[0].sum()):
        return chunks[0]  # one chunk, already packed
    out = {f: np.concatenate([c[f] for c in chunks]) for f in _TREE_META_FIELDS}
    arenas = []
    for c, n in zip(chunks, lens):
        starts = np.cumsum(n) - n
        arenas.append(c["arena"][np.repeat(c["pos_off"] - starts, n)
                                 + np.arange(int(n.sum()))])
    n = np.concatenate(lens)
    out["pos_off"] = np.where(out["pos_len"] >= 0, np.cumsum(n) - n, -1)
    out["arena"] = np.concatenate(arenas)
    return out


@functools.lru_cache(maxsize=32)
def _lww_sharded_fn(mesh, n_slots: int):
    from ..ops.lww import make_lww_sharded

    return make_lww_sharded(mesh, n_slots)


@functools.lru_cache(maxsize=32)
def _lww_batch_fn(mesh, n_slots: int):
    in_sh = NamedSharding(mesh, P(DOC_AXIS))

    @functools.partial(jax.jit, in_shardings=(MapOpCols(*([in_sh] * 5)),))
    def run(cols: MapOpCols):
        return jax.vmap(lambda c: lww_merge_doc(c, n_slots))(cols)

    return run
