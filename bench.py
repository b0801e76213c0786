#!/usr/bin/env python
"""North-star benchmark: batched concurrent import of the automerge-perf
trace across a fleet of documents (BASELINE.md config 3).

Per doc, this performs the work of the reference's
`OpLog::import -> DiffCalculator -> apply` replay of the full trace
(reference harness: crates/loro-internal/benches/text_r.rs B4): resolve
the final Fugue sequence order of every element (insert integration +
tombstones) and materialize the visible document.  The fleet dimension
is the TPU win: all documents merge in one XLA launch per chunk.

Prints the compact flagship JSON line LAST (hard-budgeted under
FLAGSHIP_BUDGET chars so a 2,000-char tail window always captures it):
  {"metric": ..., "value": ops_merged_per_sec, "unit": ..., "vs_baseline": ...}
Verbose notes + the metrics/resilience/pipeline sidecars ride a
separate `sidecars_for` line printed just before it.

`python bench.py` runs main() in the calling process: one process owns
the chip.  Without a TPU the run is an error; an explicit
JAX_PLATFORMS=cpu is honoured as a rehearsal, and its record then says
`platform: cpu` under a metric name that is not a device metric.  Every
record carries platform, device_kind and device count.

The trace is the seeded synthetic source of loro_tpu.bench_utils at the
published length (259,778 patches; the automerge-perf file itself is not
in the repository) and the record names it (`trace_source`).  Its
extracts and concurrent variants cache under git-ignored `.bench_cache_*`
files: a fresh checkout pays the host replay (tens of seconds per
variant) once.  Phases run in ascending cost
order (XLA pilot -> XLA budget -> pallas compile -> pallas budget ->
latency -> e2e -> serving phases), each under the run's cooperative
deadline (BENCH_CHILD_DEADLINE seconds), and every stderr note carries
elapsed seconds.

Baseline denominator: single-threaded reference (Rust) B4 import
throughput.  The reference repo publishes no numbers (BASELINE.md);
Rust is not installed in this image, so we use 2.0e6 ops/s — an
estimate on the generous side for loro's snapshot-import fast path on
this trace (~130ms for 260k ops) — and publish an explicit x2 band
(baseline_band) rather than a bare point estimate.
"""
import json
import os
import sys
import time

import numpy as np

RUST_SINGLE_THREAD_OPS_PER_SEC = 2.0e6  # see module docstring
BASELINE_BAND = [1.0e6, 4.0e6]  # x/2 .. x2 sensitivity band around the estimate
BASELINE_NOTE = (
    "denominator is an ESTIMATE (2.0e6 ops/s single-thread Rust B4; Rust "
    "unavailable in image — BASELINE.md says measure, we cannot); "
    "baseline_band gives the x2 sensitivity band: divide value by band "
    "edges for the conservative/optimistic speedup"
)

T0 = time.time()


def note(msg: str) -> None:
    print(f"bench[{time.time() - T0:6.1f}s]: {msg}", file=sys.stderr, flush=True)


def device_fields() -> dict:
    """platform / device_kind / device_count as JAX reports them — on
    every record.  No TPU is an error unless JAX_PLATFORMS was set
    explicitly (a CPU rehearsal, labelled as such); a TPU that is not
    in the peaks table is an error too."""
    import jax

    from loro_tpu.bench_utils import DEVICE_PEAKS

    dev0 = jax.devices()[0]
    fields = {
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "device_count": len(jax.devices()),
    }
    if dev0.platform != "tpu" and not os.environ.get("JAX_PLATFORMS"):
        raise SystemExit(
            f"bench: no TPU (JAX runs on {dev0.platform!r}); set "
            "JAX_PLATFORMS=cpu explicitly for a CPU rehearsal"
        )
    if dev0.platform == "tpu" and dev0.device_kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench: device kind {dev0.device_kind!r} is not in "
            "loro_tpu.bench_utils.DEVICE_PEAKS"
        )
    return fields


def metric_name(name: str, platform: str) -> str:
    """A device metric keeps its name only on the device: a CPU
    rehearsal's number goes under a name that cannot be mistaken for
    one."""
    return name if platform == "tpu" else f"{platform}_rehearsal ({name}; not a device metric)"


_CKPT: dict = {}


def _metrics_sidecar() -> dict | None:
    """The obs registry as a compact dict (docs/OBSERVABILITY.md
    "bench sidecar"): pad-waste / jit-shape / launch / epoch counters
    ride every record.  None when the obs package is unavailable or
    empty."""
    try:
        from loro_tpu.obs import sidecar

        side = sidecar()
        return side or None
    except Exception:  # tpulint: disable=LT-EXC(sidecars are optional; the flagship JSON line must always emit)
        return None


def _resilience_sidecar() -> dict | None:
    """Supervisor outcome dict (retries, degradations, drain budget):
    a run in which the device path degraded says so in its record."""
    try:
        from loro_tpu.resilience import get_supervisor

        rep = get_supervisor().report()
        return rep if rep.get("launches") else None
    except Exception:  # tpulint: disable=LT-EXC(sidecars are optional; the flagship JSON line must always emit)
        return None


def bank(phase: str, **fields) -> None:
    """Merge a finished phase's fields into the record under assembly
    and refresh the metrics + resilience sidecars."""
    _CKPT.update(fields)
    side = _metrics_sidecar()
    if side:
        _CKPT["metrics"] = side
    res = _resilience_sidecar()
    if res:
        _CKPT["resilience"] = res
    _CKPT["last_phase"] = phase
    _CKPT["elapsed_s"] = round(time.time() - T0, 1)


def _final_record() -> dict:
    """Assemble the ONE output line from the banked state."""
    return assemble_record(dict(_CKPT))


# ---------------------------------------------------------------------------
# flagship-line emission (round-5 verdict: the final JSON line was so
# fat with sidecars + notes that a 2,000-char tail window truncated the
# flagship fields).  The record now splits: verbose prose (*_note),
# dict sidecars (metrics/resilience/pipeline) and per-flight series
# ride a SECONDARY line tagged `sidecars_for`, printed first; the LAST
# line is always the compact flagship record, hard-budgeted under
# FLAGSHIP_BUDGET chars so any tail capture parses it whole.
# ---------------------------------------------------------------------------

FLAGSHIP_BUDGET = 2000

# never dropped from the flagship line, whatever the budget says
_CORE_KEYS = (
    "metric", "value", "unit", "vs_baseline", "platform", "device_kind",
    "device_count", "trace_source", "partial", "last_phase", "sidecars",
)
# always routed to the sidecar line: prose, dict sidecars, series —
# plus the roofline model/measured numerics, which ride with their
# notes (the flagship keeps the serving + kernel headline numbers)
_SIDECAR_KEYS = (
    "metrics", "resilience", "pipeline", "rank", "sync", "shard", "tier",
    "readplane", "repl", "trace", "net", "health",
    "gather_rows_per_sec", "hbm_bytes_per_op_model",
    "achieved_hbm_gbps_model", "hbm_frac_model", "rank_ms_measured",
    "place_ms_measured", "gather_rows_per_sec_measured",
    "achieved_hbm_gbps_measured", "hbm_frac",
    "baseline_note", "latency_note", "roofline_note",
    "roofline_measured_note", "resident_note", "resident_durable_note",
    "resident_pipeline_note", "e2e_note", "e2e_unit", "richtext_unit",
    "latency_series_ms", "xla_flight_ms", "pallas_flight_ms",
)


def split_record(rec: dict):
    """``(flagship, sidecars_or_None)``: flagship keeps the metric /
    value / vs_baseline / device numerics and stays under
    FLAGSHIP_BUDGET chars (over-budget extras spill to the sidecar
    line, largest first, core fields never)."""
    flag = {k: v for k, v in rec.items() if k not in _SIDECAR_KEYS}
    extras = {k: rec[k] for k in _SIDECAR_KEYS if k in rec}
    while len(json.dumps(flag)) > FLAGSHIP_BUDGET - 100:
        droppable = [k for k in flag if k not in _CORE_KEYS]
        if not droppable:
            break
        big = max(droppable, key=lambda k: len(json.dumps(flag[k])))
        extras[big] = flag.pop(big)
    if not extras:
        return flag, None
    side = {"sidecars_for": flag.get("metric", "?")}
    side.update(extras)
    flag["sidecars"] = "previous_line"
    return flag, side


def emit_record(rec: dict) -> None:
    """Print the (optional) sidecar line, then the compact flagship
    line LAST — a tail window keys on the final ``metric`` line."""
    flag, side = split_record(rec)
    if side:
        print(json.dumps(side), flush=True)
    print(json.dumps(flag), flush=True)


def _ambient_fields(rec: dict) -> dict:
    """Attach the ambient load to a record (r4 verdict weak #7: host
    timings are load-confounded)."""
    try:
        rec.setdefault("load_avg_1m", round(os.getloadavg()[0], 2))
    except OSError:
        pass
    return rec


def assemble_record(ck: dict) -> dict:
    """Build the output JSON from the banked fields (possibly partial:
    phases past the run's deadline are skipped)."""
    value = ck.get("value")
    metric = metric_name(
        ck.get("metric", "ops_merged_per_sec_per_chip"),
        ck.get("platform", "unknown"),
    )
    rec = {
        "metric": metric,
        "value": round(value) if value else 0,
        "unit": ck.get("unit", "ops/s"),
        "vs_baseline": round((value or 0) / RUST_SINGLE_THREAD_OPS_PER_SEC, 2),
        "baseline_band": BASELINE_BAND,
        "baseline_note": BASELINE_NOTE,
    }
    for k in (
        "platform",
        "device_kind",
        "device_count",
        "trace_source",
        "phases_done",
        "last_phase",
        "partial",
        "kernel",
        "place_algo",
        "xla_flight_median",
        "pallas_flight_median",
        "merge_latency_ms_p50",
        "merge_latency_ms_p99",
        "merge_latency_ms_max",
        "latency_samples",
        "latency_note",
        "xla_rank_value",
        "ring_tokens_per_doc",
        "rank_rounds",
        "rank_gather_reduction",
        "rank_gather_rows_per_op",
        "rank",
        "gather_rows_per_sec",
        "hbm_bytes_per_op_model",
        "achieved_hbm_gbps_model",
        "hbm_frac_model",
        "roofline_note",
        "rank_ms_measured",
        "place_ms_measured",
        "gather_rows_per_sec_measured",
        "achieved_hbm_gbps_measured",
        "hbm_frac",
        "roofline_measured_note",
        "e2e_value",
        "e2e_unit",
        "e2e_vs_baseline",
        "e2e_note",
        "resident_rows_per_sec",
        "resident_rows_per_sec_best",
        "resident_note",
        "resident_sync_rows_per_sec",
        "resident_pipeline_rows_per_sec",
        "resident_pipeline_speedup",
        "resident_pipeline_note",
        "pipeline",
        "resident_durable_rows_per_sec",
        "resident_durable_replayed_rounds",
        "resident_durable_note",
        "resident_durable_fsyncs",
        "resident_durable_group_fsyncs",
        "resident_durable_group_rows_per_sec",
        "richtext_value",
        "richtext_unit",
        "richtext_vs_baseline",
        "sync_sessions",
        "sync_pushes_per_sec",
        "sync_push_to_visible_ms_p50",
        "sync_push_to_visible_ms_p99",
        "sync",
        "sync_readers",
        "sync_pulls_per_sec",
        "sync_pulls_per_sec_oracle",
        "sync_read_speedup",
        "sync_pull_ms_p50",
        "sync_pull_ms_p99",
        "readplane",
        "repl_readers",
        "repl_pulls_per_sec",
        "repl_pulls_per_sec_leader_only",
        "repl_read_scaling_x",
        "repl_lag_ms_p50",
        "repl_lag_ms_p99",
        "repl_promotion_downtime_ms",
        "repl",
        "net_connections",
        "net_pushes_per_sec",
        "net_push_to_visible_ms_p50",
        "net_push_to_visible_ms_p99",
        "net",
        "shard_count",
        "shard_rows_per_sec",
        "shard_scaling_x",
        "shard",
        "tier_hit_rate",
        "tier_revive_ms_p50",
        "tier_revive_ms_p99",
        "tier_rows_per_sec",
        "tier_all_hot_rows_per_sec",
        "tier_vs_all_hot",
        "tier_hot_path_ratio",
        "tier",
        "health_tick_ns",
        "health_skew_ratio",
        "health",
        "trace",
        "metrics",
        "resilience",
        "elapsed_s",
    ):
        if k in ck and ck[k] is not None:
            rec[k] = ck[k]
    return _ambient_fields(rec)


def _emit_simple(metric: str, ops_per_sec: float, extras: dict | None = None) -> None:
    dev = device_fields()
    rec = {
        "metric": metric_name(metric, dev["platform"]),
        "value": round(ops_per_sec),
        "unit": "ops/s",
        "vs_baseline": round(ops_per_sec / RUST_SINGLE_THREAD_OPS_PER_SEC, 2),
        **dev,
    }
    if extras:
        rec.update(extras)
    side = _metrics_sidecar()
    if side:
        rec["metrics"] = side
    emit_record(_ambient_fields(rec))


# ---------------------------------------------------------------------------
# secondary configs (BENCH_CONFIG=map|tree|movable|richtext|size)
# ---------------------------------------------------------------------------


def bench_map() -> None:
    """BASELINE config 1: batched LWW-map concurrent import."""
    import jax

    from loro_tpu.ops.lww import MapOpCols, lww_merge_batch

    docs = int(os.environ.get("BENCH_DOCS", "1024"))
    m = int(os.environ.get("BENCH_MAP_OPS", "65536"))
    s = int(os.environ.get("BENCH_MAP_SLOTS", "4096"))
    rng = np.random.default_rng(0)
    cols = MapOpCols(
        slot=rng.integers(0, s, (docs, m)).astype(np.int32),
        lamport=rng.integers(0, 1 << 20, (docs, m)).astype(np.int32),
        peer=rng.integers(0, 64, (docs, m)).astype(np.int32),
        value_idx=np.arange(docs * m, dtype=np.int32).reshape(docs, m) % (1 << 20),
        valid=np.ones((docs, m), bool),
    )
    dev = MapOpCols(*[jax.device_put(a) for a in cols])
    out = lww_merge_batch(dev, s)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        out = lww_merge_batch(dev, s)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    _emit_simple(f"lww_map ops merged/sec ({docs}-doc batch, {m} ops/doc)", docs * m / dt)


def bench_tree() -> None:
    """BASELINE config 5: deep hierarchy, concurrent move/reparent."""
    import jax

    from loro_tpu.ops.tree_batch import TreeOpCols, tree_merge_batch

    docs = int(os.environ.get("BENCH_DOCS", "1024"))
    n_nodes = int(os.environ.get("BENCH_TREE_NODES", "512"))
    m = int(os.environ.get("BENCH_TREE_MOVES", "2048"))
    rng = np.random.default_rng(0)
    target = rng.integers(0, n_nodes, (docs, m)).astype(np.int32)
    parent = rng.integers(-2, n_nodes, (docs, m)).astype(np.int32)
    cols = TreeOpCols(target=target, parent=parent, valid=np.ones((docs, m), bool))
    dev = TreeOpCols(*[jax.device_put(a) for a in cols])
    d_max = os.environ.get("BENCH_TREE_DEPTH")
    d_max = int(d_max) if d_max else None
    out = tree_merge_batch(dev, n_nodes, d_max)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = tree_merge_batch(dev, n_nodes, d_max)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    _emit_simple(f"tree moves merged/sec ({docs}-doc batch, {m} moves/doc)", docs * m / dt)


def bench_movable() -> None:
    """BASELINE config ~4/5 hybrid: movable-list concurrent move/set."""
    import jax

    from loro_tpu.ops.fugue_batch import SeqColumns
    from loro_tpu.ops.movable_batch import MovableCols, movable_merge_batch

    docs = int(os.environ.get("BENCH_DOCS", "256"))
    s = int(os.environ.get("BENCH_SLOTS", "8192"))  # slots per doc
    n_elems = s // 2
    rng = np.random.default_rng(0)
    parent = np.concatenate(
        [
            np.arange(-1, n_elems - 1, dtype=np.int32),
            rng.integers(0, n_elems, s - n_elems).astype(np.int32),
        ]
    )
    elem = np.concatenate(
        [np.arange(n_elems, dtype=np.int32), rng.integers(0, n_elems, s - n_elems).astype(np.int32)]
    )
    lam = np.concatenate(
        [
            np.arange(n_elems, dtype=np.int32),
            rng.integers(n_elems, 4 * n_elems, s - n_elems).astype(np.int32),
        ]
    )
    seq = SeqColumns(
        parent=np.broadcast_to(parent, (docs, s)).copy(),
        side=np.ones((docs, s), np.int32),
        peer=np.zeros((docs, s), np.int32),
        counter=np.broadcast_to(np.arange(s, dtype=np.int32), (docs, s)).copy(),
        deleted=np.zeros((docs, s), bool),
        content=np.broadcast_to(elem, (docs, s)).copy(),
        valid=np.ones((docs, s), bool),
    )
    cols = MovableCols(
        seq=SeqColumns(*[jax.device_put(a) for a in seq]),
        lamport=jax.device_put(np.broadcast_to(lam, (docs, s)).copy()),
        set_elem=jax.device_put(
            np.broadcast_to(np.arange(n_elems, dtype=np.int32), (docs, n_elems)).copy()
        ),
        set_lamport=jax.device_put(np.zeros((docs, n_elems), np.int32)),
        set_peer=jax.device_put(np.zeros((docs, n_elems), np.int32)),
        set_value=jax.device_put(
            np.broadcast_to(np.arange(n_elems, dtype=np.int32), (docs, n_elems)).copy()
        ),
        set_valid=jax.device_put(np.ones((docs, n_elems), bool)),
    )
    out = movable_merge_batch(cols, n_elems)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = movable_merge_batch(cols, n_elems)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    _emit_simple(f"movable_list ops merged/sec ({docs}-doc batch, {s} slots/doc)", docs * s / dt)


def bench_size() -> None:
    """Encoded-size harness (reference: examples/benches/mergeable_size
    + encode.rs): bytes per op for updates / snapshot / state-only on
    the automerge trace prefix."""
    from loro_tpu import ExportMode, LoroDoc
    from loro_tpu.bench_utils import TraceSource

    n_txn = int(os.environ.get("BENCH_TXN_LIMIT", "20000"))
    source = TraceSource.synthetic()
    patches = source.load(limit=n_txn)
    doc = LoroDoc(peer=1)
    t = doc.get_text("text")
    for pos, dels, ins in patches:
        if dels:
            t.delete(pos, dels)
        if ins:
            t.insert(pos, ins)
    doc.commit()
    updates = len(doc.export_updates())
    snapshot = len(doc.export(ExportMode.Snapshot))
    state_only = len(doc.export(ExportMode.StateOnly))
    n_ops = len(patches)
    print(
        json.dumps(
            {
                "metric": (
                    f"update bytes/op ({n_ops} ops; snapshot={snapshot}B "
                    f"state_only={state_only}B)"
                ),
                "value": round(updates / n_ops, 2),
                "unit": "bytes/op",
                "vs_baseline": 1.0,
                "trace_source": source.record(),
            }
        ),
        flush=True,
    )


def bench_richtext(emit: bool = True) -> float:
    """BASELINE config 4: concurrent formatting spans + text edits at
    fleet scale — full merge (Fugue order + Peritext style resolution)
    of concurrent multi-peer rich-text docs, correctness-gated against
    the host oracle (reference: text_r.rs richtext analogs + style
    semantics in style_range_map.rs)."""
    import jax

    from loro_tpu.bench_utils import RICHTEXT_KEYS, richtext_bench_docs
    from loro_tpu.ops.richtext_batch import (
        RichtextChainCols,
        richtext_chain_merge_batch,
        segments_from_device,
    )

    docs_total = int(os.environ.get("BENCH_RT_DOCS", "512"))
    chunk = int(os.environ.get("BENCH_RT_CHUNK", "16"))
    n_distinct = int(os.environ.get("BENCH_RT_DISTINCT", "8"))
    distinct, pad_n, pad_p, pad_c = richtext_bench_docs(n_distinct=n_distinct)
    n_keys = len(RICHTEXT_KEYS)
    note(f"richtext: {n_distinct} distinct docs, pad_n={pad_n} pad_p={pad_p} pad_c={pad_c}")
    from loro_tpu.ops.fugue_batch import ChainColumns

    idx0 = [j % n_distinct for j in range(chunk)]
    chunk_cols = [distinct[i]["cols"] for i in idx0]
    batch = RichtextChainCols(
        chain=ChainColumns(
            *[
                jax.device_put(np.stack([getattr(c.chain, f) for c in chunk_cols]))
                for f in ChainColumns._fields
            ]
        ),
        **{
            f: jax.device_put(np.stack([getattr(c, f) for c in chunk_cols]))
            for f in RichtextChainCols._fields
            if f != "chain"
        },
    )
    codes, counts, bounds, win = richtext_chain_merge_batch(batch, n_keys)
    for j in range(min(chunk, n_distinct)):  # one slot per distinct doc
        d = distinct[idx0[j]]
        segs = segments_from_device(
            np.asarray(codes[j]), counts[j], bounds[j], win[j], d["keys"], d["values"]
        )
        assert segs == d["oracle"], f"richtext device merge != host oracle (doc {j})"
    ops_per_chunk = sum(distinct[i]["n_ops"] for i in idx0)
    jax.block_until_ready(counts)
    n_chunks = max(1, docs_total // chunk)
    t0 = time.perf_counter()
    out = None
    for i in range(n_chunks):
        out = richtext_chain_merge_batch(batch, n_keys)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    ops_s = ops_per_chunk * n_chunks / dt
    if emit:
        _emit_simple(
            f"richtext ops merged/sec ({n_chunks * chunk}-doc concurrent import, "
            f"{n_distinct} distinct multi-peer docs, marks+edits)",
            ops_s,
        )
    return ops_s


# ---------------------------------------------------------------------------
# flagship config: phased, banked
# ---------------------------------------------------------------------------


def main() -> None:
    import jax

    from loro_tpu.config import configure_compile_cache

    configure_compile_cache()
    config = os.environ.get("BENCH_CONFIG", "text")
    if config == "map":
        return bench_map()
    if config == "tree":
        return bench_tree()
    if config == "movable":
        return bench_movable()
    if config == "size":
        return bench_size()
    if config == "richtext":
        return bench_richtext()

    from loro_tpu import native
    from loro_tpu.bench_utils import (
        DEVICE_PEAKS,
        TraceSource,
        automerge_final_text,
        automerge_seq_extract,
        concurrent_trace_variants,
    )
    from loro_tpu.ops.columnar import chain_columns, contract_chains
    from loro_tpu.ops.fugue_batch import (
        ChainColumns,
        chain_merge_docs_checksum_v,
        chain_merge_docs_v,
    )

    docs_total = int(os.environ.get("BENCH_DOCS", "10240"))
    chunk = int(os.environ.get("BENCH_CHUNK", "8"))
    budget_s = float(os.environ.get("BENCH_BUDGET", "240"))  # flagship loop
    xla_budget_s = float(os.environ.get("BENCH_XLA_BUDGET", "75"))
    lat_budget_s = float(os.environ.get("BENCH_LAT_BUDGET", "150"))
    e2e_docs_req = int(os.environ.get("BENCH_E2E_DOCS", "64"))
    e2e_budget_s = float(os.environ.get("BENCH_E2E_BUDGET", "90"))
    n_variants = int(os.environ.get("BENCH_VARIANTS", "8"))
    run_deadline = T0 + float(os.environ.get("BENCH_CHILD_DEADLINE", "660"))
    limit = os.environ.get("BENCH_TXN_LIMIT")
    limit = int(limit) if limit else None

    def remaining() -> float:
        return run_deadline - time.time()

    # every device phase below routes through one DeviceSupervisor:
    # bounded in-flight budget (drain_every=8), cooperative deadline at
    # the run deadline minus a drain margin (checked BETWEEN launches —
    # an expiry surfaces as a typed DeadlineExceeded at a launch
    # boundary), and its report() banks as the `resilience` sidecar
    from loro_tpu.resilience import DeviceSupervisor, set_supervisor

    sup = DeviceSupervisor(drain_every=8, deadline_s=max(30.0, remaining() - 15))
    set_supervisor(sup)

    # ---- phase 0: device contact ---------------------------------------
    dev = device_fields()
    platform, device_kind = dev["platform"], dev["device_kind"]
    on_tpu = platform == "tpu"
    native.require()  # the e2e and resident phases measure the native decode
    note(f"device: platform={platform} kind={device_kind} count={dev['device_count']}")
    bank("device_contact", **dev)

    # ---- phase: extraction (cached per checkout) ----------------------
    source = TraceSource.synthetic()
    note(f"extracting trace + concurrent variants ({source.record()})...")
    ex0, n_ops = automerge_seq_extract(source, limit=limit)
    variants = concurrent_trace_variants(source, n_variants=n_variants, limit=limit)
    extracts = [ex0] + [v["extract"] for v in variants]
    per_doc_ops = [n_ops] + [v["n_ops"] for v in variants]
    want0 = automerge_final_text(source, limit=limit)
    note(f"extraction done ({len(extracts)} distinct traces)")
    bank("extraction", trace_source=source.record())

    # the trace set is fixed for the whole run, so pad to the batch max
    # on a fine quantum instead of power-of-two buckets: ranking cost is
    # linear in pad_c (the ring is 2*(pad_c+1) tokens)
    def pad_to(n: int, q: int) -> int:
        return -(-n // q) * q

    pad_n = pad_to(max(e.n for e in extracts), 8192)
    pad_c = pad_to(max(contract_chains(e).n_chains for e in extracts), 1024)
    per_doc_cols = [chain_columns(e, pad_n=pad_n, pad_c=pad_c) for e in extracts]
    per_doc_rows = [e.n for e in extracts]
    n_distinct = len(per_doc_cols)
    n_batches = max(1, -(-n_distinct // chunk))
    host_batches = []
    batch_ops = []
    batch_rows = []
    for b in range(n_batches):
        idxs = [(b * chunk + j) % n_distinct for j in range(chunk)]
        docs = [per_doc_cols[i] for i in idxs]
        batch_ops.append(sum(per_doc_ops[i] for i in idxs))
        batch_rows.append(sum(per_doc_rows[i] for i in idxs))
        host_batches.append(
            ChainColumns(*[np.stack([getattr(c, f) for c in docs]) for f in ChainColumns._fields])
        )
    from loro_tpu.obs import metrics as obs_m

    obs_m.unique("fleet.padded_shapes_distinct").add(
        ("chain_text", pad_n, pad_c, chunk)
    )

    sync = jax.block_until_ready

    # ---- phase: upload pilot batch + XLA compile + correctness -------
    note(f"uploading pilot batch ({chunk} docs, pad_n={pad_n} pad_c={pad_c})...")
    batches = [ChainColumns(*[jax.device_put(a) for a in host_batches[0]])]
    note("compiling XLA merge kernel (first compile ~20-40s)...")
    codes, counts = chain_merge_docs_v(batches[0], rank_impl="xla")
    got = "".join(map(chr, np.asarray(codes[0])[: int(counts[0])]))
    assert got == want0, f"device merge mismatch: {len(got)} vs {len(want0)} chars"
    if variants and chunk >= 2:
        got1 = "".join(map(chr, np.asarray(codes[1])[: int(counts[1])]))
        assert got1 == variants[0]["text"], "variant merge mismatch vs host oracle"
    note("XLA kernel correctness gates passed")

    metric = (
        "ops_merged_per_sec_per_chip (automerge-perf trace, "
        f"{{docs}}-doc concurrent import, {n_distinct} distinct traces cycled)"
    )

    # checksum variant (cheap fetches) for all timed loops
    sync(chain_merge_docs_checksum_v(batches[0], rank_impl="xla"))
    t0 = time.perf_counter()
    sync(chain_merge_docs_checksum_v(batches[0], rank_impl="xla"))
    t_pilot = time.perf_counter() - t0
    pilot_ops_s = batch_ops[0] / max(t_pilot, 1e-9)
    note(f"XLA pilot chunk: {t_pilot * 1e3:.0f}ms ({pilot_ops_s / 1e6:.1f}M ops/s)")
    bank(
        "xla_pilot",
        value=pilot_ops_s,
        kernel="xla",
        metric=metric.format(docs=chunk),
        partial="pilot only (1 chunk)",
    )

    # remaining uploads
    note(f"uploading remaining {n_batches - 1} chunk batches...")
    for hb in host_batches[1:]:
        batches.append(ChainColumns(*[jax.device_put(a) for a in hb]))
    note(f"uploaded {n_batches} batches ({n_distinct} distinct traces)")

    def budget_loop(fn, secs: float, label: str):
        """Timed throughput loop: flights of `drain` launches with a
        sync between flights (bounds the in-device queue; the final
        sync closes the window, so wall-clock spans real work).  Launches route through the DeviceSupervisor, whose
        drain_every matches the flight size — the supervisor's
        auto-drain IS the between-flight sync, so the in-flight queue
        provably never exceeds the budget.  Returns (ops/s, docs_done,
        flight_times)."""
        drain = sup.drain_every
        n_chunks_req = max(1, docs_total // chunk)
        n_chunks = max(1, min(n_chunks_req, int(secs / max(t_pilot / 4, 1e-9))))
        flights = []
        t0 = time.perf_counter()
        out = None
        ops_done = 0
        i = 0
        tf = t0
        while i < n_chunks:
            b = batches[i % n_batches]
            out = sup.launch(lambda b=b: fn(b), label=f"bench.{label}")
            ops_done += batch_ops[i % n_batches]
            i += 1
            if i % drain == 0:
                # the supervisor auto-drained at this boundary (depth
                # hit drain_every on the launch above); flight is timed
                # against that sync
                now = time.perf_counter()
                flights.append(now - tf)
                tf = now
                if (now - t0) > secs or remaining() < 30:
                    note(f"{label}: budget expired after {i}/{n_chunks} chunks")
                    break
        sup.drain(lambda: sync(out))
        dt = time.perf_counter() - t0
        # fleet accounting for the sidecar: the budget loop is the
        # bench's merge front-end, so it ticks the same counters the
        # Fleet API does (family chain_text = direct chain kernel)
        rows_done = sum(batch_rows[j % n_batches] for j in range(i))
        obs_m.counter("fleet.merge_calls_total").inc(i, family="chain_text")
        obs_m.counter("fleet.device_launches_total").inc(i, family="chain_text")
        obs_m.counter("fleet.docs_merged_total").inc(i * chunk, family="chain_text")
        obs_m.counter("fleet.ops_merged_total").inc(rows_done, family="chain_text")
        obs_m.counter("fleet.pad_waste_rows_total").inc(
            i * chunk * pad_n - rows_done, family="chain_text"
        )
        return ops_done / dt, i * chunk, flights

    def flight_median_rate(ops_s: float, flights) -> float | None:
        """Load-robust throughput: ops-per-flight / median flight time.
        The mean rate is confounded by ambient load spikes (r4 verdict
        weak #7: same code measured 0.82x vs 1.53x under different
        session load); the median flight is the stable cross-round
        comparator."""
        if len(flights) < 3:
            return None
        ops_per_flight = ops_s * sum(flights) / len(flights)
        med = sorted(flights)[len(flights) // 2]
        return ops_per_flight / med

    # ---- phase: XLA budget loop (banked device number, low risk) -----
    note(f"XLA budget loop ({xla_budget_s:.0f}s)...")
    xla_ops_s, xla_docs, xla_flights = budget_loop(
        lambda b: chain_merge_docs_checksum_v(b, rank_impl="xla"), xla_budget_s, "xla"
    )
    note(f"XLA kernel: {xla_ops_s / 1e6:.1f}M ops/s over {xla_docs} docs")
    xla_med = flight_median_rate(xla_ops_s, xla_flights)
    bank(
        "xla_budget",
        value=xla_ops_s,
        kernel="xla",
        place_algo=os.environ.get("PLACE_ALGO", "sort"),
        metric=metric.format(docs=xla_docs),
        partial="XLA rank kernel (pallas phase not yet run)",
        xla_rank_value=round(xla_ops_s),
        xla_flight_median=round(xla_med) if xla_med is not None else None,
        # per-flight wall times (8 launches each): postmortem time series
        xla_flight_ms=[round(t * 1e3, 1) for t in xla_flights],
    )

    # ---- phase: rank A/B (gather-count reduction, CPU-mesh-provable) --
    # ISSUE 6: ranking gathers are ~all of merge cost on chip, so the
    # reduction is judged by COUNTS (rank_model is the shared ledger):
    # base = the wyllie default, new = run-coalesced ring + ruling
    # sub-rank at a budget sized from the measured run statistics.
    # Byte-identity gates on the pilot batch; wall-clock rides along as
    # a sanity field only.
    if remaining() > 45 and os.environ.get("BENCH_SKIP_RANK_AB") != "1":
        try:
            from loro_tpu.ops import rank_model as _rm
            from loro_tpu.ops.fugue_batch import chain_rank_checksum_v as _crank_v

            note("rank A/B phase: run-coalesced vs wyllie gather counts...")
            rings = [
                _rm.build_ring(
                    np.asarray(c.c_parent), np.asarray(c.c_side), np.asarray(c.c_valid)
                )
                for c in per_doc_cols
            ]
            stats = [_rm.ring_stats(s) for s in rings]
            n_runs_max = max(st["n_runs"] for st in stats)
            mean_run = float(np.mean([st["mean_run"] for st in stats]))
            ring_budget = _rm.coalesce_budget(n_runs_max)
            # realized (simulated rounds) + analytic cap, once per
            # DISTINCT ring, multiplied by its occurrence count in the
            # pilot chunk (docs cycle j % n_distinct)
            occur = [0] * n_distinct
            for j in range(chunk):
                occur[j % n_distinct] += 1
            base_rows = new_rows = 0
            for s, cnt in zip(rings, occur):
                if not cnt:
                    continue
                base_rows += cnt * _rm.simulate(s, "wyllie")[1]["global_rows"]
                new_rows += cnt * _rm.simulate(
                    s, "coalesced", r_pad=ring_budget
                )[1]["global_rows"]
            m_ring_len = len(rings[0])  # all rings share the padded length
            model_base = chunk * _rm.gather_model(m_ring_len, "wyllie")["global_rows"]
            model_new = chunk * _rm.gather_model(
                m_ring_len, "coalesced", r_pad=ring_budget
            )["global_rows"]
            # correctness gates: byte-identical text + identical rank
            # checksums (every algorithm computes the same distances)
            codes_c, counts_c = chain_merge_docs_v(
                batches[0], rank_impl="xla:coalesced", ring_budget=ring_budget
            )
            got_c = "".join(map(chr, np.asarray(codes_c[0])[: int(counts_c[0])]))
            assert got_c == want0, "coalesced merge mismatch vs ground truth"
            cs_base = np.asarray(_crank_v(batches[0], rank_impl="xla:wyllie"))
            cs_new = np.asarray(
                _crank_v(batches[0], rank_impl="xla:coalesced", ring_budget=ring_budget)
            )
            assert (cs_base == cs_new).all(), "coalesced rank checksum mismatch"
            note("rank A/B correctness gates passed (text + rank checksums)")

            def timed_rank(spec, budget=None, reps=3):
                fn = lambda b: _crank_v(b, rank_impl=spec, ring_budget=budget)  # noqa: E731
                sync(fn(batches[0]))
                ts = []
                for _ in range(reps):
                    t1 = time.perf_counter()
                    sync(fn(batches[0]))
                    ts.append(time.perf_counter() - t1)
                return sorted(ts)[len(ts) // 2]

            t_base = timed_rank("xla:wyllie")
            t_new = timed_rank("xla:coalesced", ring_budget)
            ops_chunk = batch_ops[0]
            reduction = base_rows / max(new_rows, 1)
            note(
                f"rank A/B: {base_rows}->{new_rows} global gather rows/chunk "
                f"(x{reduction:.2f}), wall {t_base * 1e3:.0f}->{t_new * 1e3:.0f}ms"
            )
            bank(
                "rank_ab",
                rank_gather_reduction=round(reduction, 2),
                rank_gather_rows_per_op=round(new_rows / ops_chunk, 2),
                rank={
                    "algo_base": "xla:wyllie",
                    "algo_new": "xla:coalesced",
                    "ring_tokens": 2 * (pad_c + 1),
                    "n_runs_max": n_runs_max,
                    "mean_run": round(mean_run, 2),
                    "ring_budget": ring_budget,
                    "gather_rows_base": int(base_rows),
                    "gather_rows_new": int(new_rows),
                    "gather_rows_base_per_op": round(base_rows / ops_chunk, 2),
                    "gather_rows_new_per_op": round(new_rows / ops_chunk, 2),
                    "model_rows_base": int(model_base),
                    "model_rows_new": int(model_new),
                    "rank_ms_base": round(t_base * 1e3, 1),
                    "rank_ms_new": round(t_new * 1e3, 1),
                    "gather_rows_per_sec_base": round(base_rows / t_base),
                    "gather_rows_per_sec_new": round(new_rows / t_new),
                    "note": (
                        "global random-gather rows per pilot chunk, realized "
                        "(simulated adaptive rounds on the real rings) and "
                        "analytic-cap model; reduction is count-based — wall "
                        "times are rank-only synced medians and only "
                        "sanity-check the counts"
                    ),
                },
            )
        except Exception as e:  # an extra, never the headline — tpulint: disable=LT-EXC(rank-A/B extra, never the headline)
            note(f"rank A/B phase failed ({type(e).__name__}: {e})")
            bank("rank_ab_failed", partial=f"rank A/B failed: {type(e).__name__}")

    # ---- phase: pallas compile + budget loop (the flagship) ----------
    flagship_fn = lambda b: chain_merge_docs_checksum_v(b, rank_impl="xla")  # noqa: E731
    kernel_name = "xla"
    kernel_ops_s = xla_ops_s
    kernel_docs = xla_docs
    from loro_tpu.ops.pallas_rank import PALLAS_RANK_MAX_M

    ring_ok = 2 * (pad_c + 1) <= PALLAS_RANK_MAX_M
    want_pallas = os.environ.get("BENCH_PALLAS", "1") != "0"
    if on_tpu and ring_ok and want_pallas and remaining() > 90:
        note("compiling pallas rank kernel...")
        try:
            codes, counts = chain_merge_docs_v(batches[0], rank_impl="pallas")
            got = "".join(map(chr, np.asarray(codes[0])[: int(counts[0])]))
            assert got == want0, "pallas merge mismatch vs ground truth"
            if variants and chunk >= 2:
                got1 = "".join(map(chr, np.asarray(codes[1])[: int(counts[1])]))
                assert got1 == variants[0]["text"], "pallas variant mismatch vs host oracle"
            note("pallas kernel correctness gates passed")
            sync(chain_merge_docs_checksum_v(batches[0], rank_impl="pallas"))
            t0 = time.perf_counter()
            sync(chain_merge_docs_checksum_v(batches[0], rank_impl="pallas"))
            t_pilot_p = time.perf_counter() - t0
            note(f"pallas pilot chunk: {t_pilot_p * 1e3:.0f}ms")
            bank("pallas_pilot", partial="pallas pilot done, budget loop pending")
            secs = min(budget_s, max(remaining() - 150, 30))
            note(f"pallas budget loop ({secs:.0f}s)...")
            p_ops_s, p_docs, p_flights = budget_loop(
                lambda b: chain_merge_docs_checksum_v(b, rank_impl="pallas"),
                secs,
                "pallas",
            )
            note(f"pallas kernel: {p_ops_s / 1e6:.1f}M ops/s over {p_docs} docs")
            if p_ops_s > kernel_ops_s:
                kernel_ops_s, kernel_docs, kernel_name = p_ops_s, p_docs, "pallas"
                flagship_fn = lambda b: chain_merge_docs_checksum_v(  # noqa: E731
                    b, rank_impl="pallas"
                )
            p_med = flight_median_rate(p_ops_s, p_flights)
            bank(
                "pallas_budget",
                value=kernel_ops_s,
                kernel=kernel_name,
                metric=metric.format(docs=kernel_docs),
                partial=None,
                pallas_flight_median=round(p_med) if p_med is not None else None,
                pallas_flight_ms=[round(t * 1e3, 1) for t in p_flights],
            )
        except Exception as e:  # pallas is an upgrade, never a downgrade — tpulint: disable=LT-EXC(pallas is an upgrade, never a downgrade)
            note(f"pallas phase failed ({type(e).__name__}: {e}); keeping XLA numbers")
            bank("pallas_failed", partial=f"pallas failed: {type(e).__name__}")
    else:
        why = (
            "off-TPU" if not on_tpu else
            "ring too long" if not ring_ok else
            "BENCH_PALLAS=0" if not want_pallas else "deadline"
        )
        note(f"skipping pallas phase ({why})")

    # ---- phase: per-launch latency distribution (true p99) -----------
    if remaining() > 45 and os.environ.get("BENCH_SKIP_LAT") != "1":
        secs = min(lat_budget_s, remaining() - 30)
        n_lat_max = int(os.environ.get("BENCH_LAT_SAMPLES", "1024"))
        note(f"latency phase: synced chunk merges for up to {secs:.0f}s...")
        lat = []
        t0 = time.perf_counter()
        i = 0
        while len(lat) < n_lat_max and (time.perf_counter() - t0) < secs:
            t1 = time.perf_counter()
            sync(flagship_fn(batches[i % n_batches]))
            lat.append(time.perf_counter() - t1)
            i += 1
        lat.sort()
        n_lat = len(lat)
        if n_lat >= 8:
            p50 = lat[n_lat // 2]
            p99 = lat[min(n_lat - 1, (n_lat * 99) // 100)]
            bank(
                "latency",
                merge_latency_ms_p50=round(p50 * 1e3, 1),
                merge_latency_ms_p99=round(p99 * 1e3, 1),
                merge_latency_ms_max=round(lat[-1] * 1e3, 1),
                latency_samples=n_lat,
                latency_note=(
                    f"{chunk}-doc chunk merges, each timed to "
                    f"block_until_ready, full trace per doc, {n_lat} samples"
                ),
                # full sorted series lives in the checkpoint only (the
                # emitted record carries the percentiles)
                latency_series_ms=[round(v * 1e3, 1) for v in lat],
            )
            note(
                f"latency: p50 {p50 * 1e3:.0f}ms p99 {p99 * 1e3:.0f}ms over {n_lat} samples"
            )

    # shared roofline constants (both the measured and the model phase
    # read the SAME byte model — keep them from drifting apart):
    #   ranking ring: m = 2*(pad_c+1) u32 tokens; XLA path gathers the
    #     [m, 2] row table log2(m) times from HBM (8B/row/round);
    #     pallas path loads/stores the ring once (VMEM-resident loop)
    #   placement: rank-delta scatter (C rows) + N-cumsum + one stable
    #     sort of (u32 key, i32 content) — modeled as 3 passes over
    #     8B/row (TPU sort is multi-pass; this is the documented floor)
    #   unpack/stream: content + flags ~ 10B/row read, 4B/row write
    m_ring = 2 * (pad_c + 1)
    rank_rounds = int(np.ceil(np.log2(max(m_ring, 2))))
    place_bytes = 3 * pad_n * 8 + pad_n * 14
    # no peak off the chip: a rehearsal reports no roofline share
    peak = DEVICE_PEAKS[device_kind]["hbm_bytes_per_s"] if on_tpu else None

    # ---- phase: MEASURED roofline (on-chip phase split) --------------
    # synced per-phase timings: rank-only vs full merge on one
    # chunk; placement = difference.  Combined with the byte model this
    # yields achieved HBM GB/s and a non-null hbm_frac with device
    # provenance (VERDICT r3 item 4: a measured number, not a model)
    if remaining() > 30 and os.environ.get("BENCH_SKIP_ROOFLINE") != "1":
        from loro_tpu.ops.fugue_batch import chain_rank_checksum_v

        impl = "pallas" if kernel_name == "pallas" else "xla"

        def timed(fn, reps=5):
            sync(fn(batches[0]))
            ts = []
            for _ in range(reps):
                t1 = time.perf_counter()
                sync(fn(batches[0]))
                ts.append(time.perf_counter() - t1)
            ts.sort()
            return ts[len(ts) // 2]

        try:
            t_rank_m = timed(lambda b: chain_rank_checksum_v(b, rank_impl=impl))
            t_full_m = timed(flagship_fn)
        except Exception as e:  # tpulint: disable=LT-EXC(roofline extra, never the headline)
            note(f"measured-roofline phase failed ({type(e).__name__}: {e})")
        else:
            t_rank_net = t_rank_m
            t_full_net = t_full_m
            t_place_net = max(t_full_net - t_rank_net, 1e-4)
            # the per-round HBM-gather row model only describes the xla
            # ranking path; the pallas ring rides VMEM (no per-round HBM
            # gathers), so a "measured gather rate" would be meaningless
            gather_rows_meas = (
                rank_rounds * m_ring * chunk / t_rank_net if impl == "xla" else None
            )
            ach_gbps = place_bytes * chunk / t_place_net / 1e9
            bank(
                "roofline_measured",
                rank_ms_measured=round(t_rank_net * 1e3, 1),
                place_ms_measured=round(t_place_net * 1e3, 1),
                gather_rows_per_sec_measured=(
                    round(gather_rows_meas) if gather_rows_meas is not None else None
                ),
                achieved_hbm_gbps_measured=round(ach_gbps, 1),
                hbm_frac=round(ach_gbps * 1e9 / peak, 4) if peak else None,
                roofline_measured_note=(
                    f"synced medians on {platform}: rank-only vs "
                    "full merge per chunk; placement bytes from the documented "
                    "floor model (3 sort passes x 8B + 14B stream per row); "
                    "hbm_frac = placement-phase achieved/peak (ranking rides "
                    "VMEM on the pallas path); gather_rows_per_sec_measured vs "
                    "the ~80-100M rows/s v5e random-gather ceiling"
                ),
            )
            note(
                f"measured roofline: rank {t_rank_net*1e3:.0f}ms place "
                f"{t_place_net*1e3:.0f}ms -> {ach_gbps:.1f} GB/s"
                + (f" ({ach_gbps*1e9/peak:.1%} of peak)" if peak else "")
            )

    # ---- phase: roofline / bytes-moved accounting (model) ------------
    # (byte-model constants shared with the measured phase above)
    if kernel_name == "pallas":
        rank_bytes = 2 * m_ring * 4  # HBM load + store; rounds ride VMEM
    else:
        rank_bytes = rank_rounds * m_ring * 8
    ops_per_doc = float(np.mean(per_doc_ops))
    bytes_per_op = (rank_bytes + place_bytes) / ops_per_doc
    achieved = bytes_per_op * kernel_ops_s
    gather_rows = None
    if kernel_ops_s:
        # every ranking round gathers m rows; chunk docs per launch
        t_per_doc = 1.0 / (kernel_ops_s / ops_per_doc)
        gather_rows = rank_rounds * m_ring / t_per_doc
    bank(
        "roofline",
        ring_tokens_per_doc=m_ring,
        rank_rounds=rank_rounds,
        gather_rows_per_sec=round(gather_rows) if gather_rows else None,
        hbm_bytes_per_op_model=round(bytes_per_op, 1),
        achieved_hbm_gbps_model=round(achieved / 1e9, 1),
        hbm_frac_model=round(achieved / peak, 4) if peak else None,
        roofline_note=(
            "analytic lower-bound byte model (rank ring + placement sort floor); "
            f"{kernel_name} ranking is VMEM-resident on the pallas path, so the "
            "HBM fraction covers the streaming phases; gather_rows_per_sec is "
            "the ranking-loop row rate vs the ~80-100M random-gather rows/s "
            "HBM ceiling measured on v5e"
        ),
    )

    # ---- phase: richtext config (BASELINE config 4, banked extra) ----
    if remaining() > 75 and os.environ.get("BENCH_SKIP_RT") != "1":
        try:
            note("richtext phase (BASELINE config 4)...")
            rt_ops_s = bench_richtext(emit=False)
            note(f"richtext: {rt_ops_s / 1e6:.1f}M ops/s")
            bank(
                "richtext",
                richtext_value=round(rt_ops_s),
                richtext_unit="ops/s (concurrent marks+edits merge, correctness-gated)",
                richtext_vs_baseline=round(rt_ops_s / RUST_SINGLE_THREAD_OPS_PER_SEC, 2),
            )
        except Exception as e:  # an extra, never the headline  # tpulint: disable=LT-EXC(richtext extra, never the headline)
            note(f"richtext phase failed ({type(e).__name__}: {e})")

    # ---- phase: end-to-end ingest pipeline ---------------------------
    if (
        variants
        and not os.environ.get("BENCH_SKIP_E2E")
        and e2e_docs_req >= chunk
        and pad_c < 0xFFFF
        and remaining() > 45
    ):
        note("e2e phase: payload decode -> SoA -> upload -> merge, pipelined...")
        from loro_tpu.core.ids import ContainerID, ContainerType
        from loro_tpu.ops.fugue_batch import (
            chain_merge_docs_packed_checksum,
            merge_text_payloads_packed,
            packed_row_bytes,
        )

        cid = ContainerID.root("text", ContainerType.Text)
        sync(
            chain_merge_docs_packed_checksum(
                jax.device_put(np.zeros((chunk, packed_row_bytes(pad_c, pad_n)), np.uint8)),
                pad_c,
                pad_n,
            )
        )
        _outs, e2e_done, e2e_ops, e2e_dt, n_workers = merge_text_payloads_packed(
            [(v["payload"], v["n_ops"]) for v in variants],
            cid,
            pad_c,
            pad_n,
            chunk,
            (min(e2e_docs_req, docs_total) // chunk) * chunk,
            budget_s=min(e2e_budget_s, remaining() - 20),
        )
        if e2e_done:
            e2e_ops_s = e2e_ops / e2e_dt
            note(
                f"e2e: {e2e_done} docs in {e2e_dt:.1f}s "
                f"({n_workers} decode threads overlapping device merges)"
            )
            bank(
                "e2e",
                e2e_value=round(e2e_ops_s),
                e2e_unit="ops/s (payload decode -> SoA -> upload -> merge)",
                e2e_vs_baseline=round(e2e_ops_s / RUST_SINGLE_THREAD_OPS_PER_SEC, 2),
                e2e_note=(
                    f"{n_workers} decode worker(s) on a {os.cpu_count()}-core host"
                ),
            )

    # ---- phase: resident-fleet ingest (host funnel, r4 verdict #5) ----
    # steady-state rows/s through DeviceDocBatch.append_payloads on a
    # FIXED synthetic fleet (seeded, 768-row epochs — the batch size at
    # which the per-epoch dispatch floor is amortized).  Mostly host
    # work.
    if remaining() > 40 and os.environ.get("BENCH_SKIP_RESIDENT") != "1":
        try:
            import random as _random

            from loro_tpu import LoroDoc
            from loro_tpu.doc import strip_envelope
            from loro_tpu.parallel.server import ResidentServer

            note("resident-fleet phase: 32 docs x 6 epochs x ~768 rows...")
            _rng = _random.Random(0x5E51DE17)
            _doc = LoroDoc(peer=1)
            _t = _doc.get_text("t")
            _eps = []
            for _e in range(6):
                _vv = _doc.oplog_vv()
                made = 0
                while made < 768:
                    L = len(_t)
                    if L > 8 and _rng.random() < 0.15:
                        p0 = _rng.randrange(L - 1)
                        dl = min(_rng.randint(1, 3), L - p0)
                        _t.delete(p0, dl)
                        made += dl
                    else:
                        run = _rng.randint(1, 12)
                        _t.insert(_rng.randint(0, L), "abcdefghijkl"[:run])
                        made += run
                _doc.commit()
                _eps.append(strip_envelope(_doc.export_updates(_vv)))
            import jax.numpy as _jnp

            # ResidentServer (not the bare batch): the ingest rounds
            # feed the server.epoch_seconds histogram the sidecar ships
            _srv = ResidentServer("text", 32, capacity=1 << 14)
            _cid = _doc.get_text("t").id
            _rates = []
            _rows_ep = 32 * 768
            for _e, _pl in enumerate(_eps):
                _t0 = time.perf_counter()
                _srv.ingest([_pl] * 32, _cid)
                # the scatter is asynchronous: wait for it, or the timed
                # window excludes the device work
                jax.block_until_ready(_srv.batch.cols)
                _rates.append(_rows_ep / (time.perf_counter() - _t0))
            _rates.sort()
            assert _srv.batch.texts()[0] == _t.to_string()  # correctness gate
            bank(
                "resident",
                resident_rows_per_sec=round(_rates[len(_rates) // 2]),
                resident_rows_per_sec_best=round(_rates[-1]),
                resident_note=(
                    "median per-epoch resident ingest (order maintenance + "
                    "native id maps + block scatter) on a 32-doc fleet, "
                    "768-row epochs, oracle-gated; each epoch is timed to "
                    "block_until_ready on the resident columns"
                ),
            )
            note(
                f"resident ingest: median {_rates[len(_rates)//2]/1e3:.0f}k "
                f"rows/s (best {_rates[-1]/1e3:.0f}k)"
            )

            # -- pipelined A/B (ISSUE 5 tentpole): serving-granularity
            # sync rounds (192 rows — the regime where the per-round
            # launch + drain floor dominates) through (a) serial ingest
            # and (b) PipelinedIngest (round coalescing + stage/commit
            # overlap).  INTERLEAVED blocks: serial and pipelined take
            # turns on the same round blocks, so ambient load hits both
            # paths alike (the r4 load-confounding lesson); the
            # differential gate (byte-identical batch state) makes the
            # A/B apples-to-apples by construction.
            _rng2 = _random.Random(0x5E51DE18)
            _doc2 = LoroDoc(peer=2)
            _t2 = _doc2.get_text("t")
            SYNC_ROWS, N_WARM, BLOCK, NBLK, CO = 192, 8, 16, 3, 8
            _srounds = []
            for _e in range(N_WARM + BLOCK * NBLK):
                _vv = _doc2.oplog_vv()
                made = 0
                while made < SYNC_ROWS:
                    L = len(_t2)
                    if L > 8 and _rng2.random() < 0.15:
                        p0 = _rng2.randrange(L - 1)
                        dl = min(_rng2.randint(1, 3), L - p0)
                        _t2.delete(p0, dl)
                        made += dl
                    else:
                        run = _rng2.randint(1, 12)
                        _t2.insert(_rng2.randint(0, L), "abcdefghijkl"[:run])
                        made += run
                _doc2.commit()
                _srounds.append(strip_envelope(_doc2.export_updates(_vv)))
            _cid2 = _doc2.get_text("t").id
            _rows_sync = 32 * SYNC_ROWS
            note(
                f"resident pipelined A/B: {NBLK} interleaved blocks of "
                f"{BLOCK} {SYNC_ROWS}-row sync rounds, coalesce={CO}..."
            )
            _ss = ResidentServer("text", 32, capacity=1 << 15)
            _ps = ResidentServer("text", 32, capacity=1 << 15)
            _ex = _ps.pipeline(cid=_cid2, coalesce=CO, depth=2)
            for _pl in _srounds[:N_WARM]:  # warm compiles off the clock
                _ss.ingest([_pl] * 32, _cid2)
                np.asarray(_jnp.count_nonzero(_ss.batch.cols.valid))
                _ex.submit([_pl] * 32)
            _ex.flush()
            np.asarray(_jnp.count_nonzero(_ps.batch.cols.valid))
            _sr = []
            _cr = []
            for _b in range(NBLK):
                _blk = _srounds[N_WARM + _b * BLOCK : N_WARM + (_b + 1) * BLOCK]
                for _pl in _blk:  # serial turn: per-round rates
                    _t0 = time.perf_counter()
                    _ss.ingest([_pl] * 32, _cid2)
                    np.asarray(_jnp.count_nonzero(_ss.batch.cols.valid))
                    _sr.append(_rows_sync / (time.perf_counter() - _t0))
                _t0 = time.perf_counter()  # pipelined turn: one stream
                for _pl in _blk:
                    _ex.submit([_pl] * 32)
                _ex.flush()
                np.asarray(_jnp.count_nonzero(_ps.batch.cols.valid))
                _cr.append(BLOCK * _rows_sync / (time.perf_counter() - _t0))
            _sr.sort()
            _cr.sort()
            _ser_med = _sr[len(_sr) // 2]
            _pipe_med = _cr[len(_cr) // 2]
            # differential gate: coalesced state is byte-for-byte the
            # serial state, and both match the host oracle
            assert _ps.batch.export_state() == _ss.batch.export_state(), \
                "pipelined resident state diverged from serial"
            assert _ps.batch.texts()[0] == _t2.to_string()
            bank(
                "resident_pipeline",
                resident_sync_rows_per_sec=round(_ser_med),
                resident_pipeline_rows_per_sec=round(_pipe_med),
                resident_pipeline_speedup=round(_pipe_med / _ser_med, 2),
                pipeline=_ex.report(),
                resident_pipeline_note=(
                    f"same-run INTERLEAVED A/B at serving granularity "
                    f"({SYNC_ROWS}-row sync rounds, 32-doc fleet, {NBLK} "
                    f"alternating blocks of {BLOCK}): serial = per-round "
                    f"ingest + drain fetch (median across rounds); "
                    f"pipelined = PipelinedIngest stream, coalesce={CO}, "
                    "stage/commit overlap (median across blocks); batch "
                    "state asserted byte-identical across paths, "
                    "oracle-gated"
                ),
            )
            note(
                f"resident pipelined: {_pipe_med/1e3:.0f}k rows/s vs serial "
                f"{_ser_med/1e3:.0f}k ({_pipe_med/_ser_med:.2f}x)"
            )
            if os.environ.get("BENCH_DURABLE") == "1":
                # durable sub-phase: same epochs on a smaller fleet
                # through the WAL (fsync'd per round) + one mid-run
                # checkpoint, then a reopen with bounded replay — the
                # `persist` sidecar banks the wal/fsync histograms
                import shutil as _shutil
                import tempfile as _tempfile

                from loro_tpu.persist import recover_server as _recover

                from loro_tpu.obs import metrics as _obsm

                _ddir = _tempfile.mkdtemp(prefix=".durable_bench_")
                _gdir = _tempfile.mkdtemp(prefix=".durable_group_")
                try:
                    _fs = _obsm.counter("persist.wal_fsyncs_total")
                    # the A/B counts INGEST-path fsyncs: the checkpoint
                    # call's control-record syncs (marker/rotation/meta/
                    # prune) are identical in both modes and excluded
                    _n_pr0 = _fs.get(mode="per_round")
                    _ck_pr = 0.0
                    # auto_checkpoint off: its mid-ingest control
                    # syncs would blur the ingest-path fsync count (the
                    # explicit mid-run checkpoint covers the ladder)
                    _dsrv = ResidentServer(
                        "text", 8, capacity=1 << 14, durable_dir=_ddir,
                        auto_checkpoint=False,
                    )
                    _d0 = time.perf_counter()
                    for _e, _pl in enumerate(_eps):
                        _dsrv.ingest([_pl] * 8, _cid)
                        if _e == len(_eps) // 2:
                            _c0 = _fs.get(mode="per_round")
                            _dsrv.checkpoint()
                            _ck_pr = _fs.get(mode="per_round") - _c0
                    np.asarray(_jnp.count_nonzero(_dsrv.batch.cols.valid))
                    _dsec = time.perf_counter() - _d0
                    _dsrv.close()
                    _n_pr = _fs.get(mode="per_round") - _n_pr0 - _ck_pr
                    _rec = _recover(_ddir)
                    assert _rec.batch.texts()[0] == _t.to_string()
                    _rec.close()
                    # group-commit A/B: same rounds + checkpoint through
                    # durable_fsync="group" (fsync_window=4) — equal
                    # round count, a fraction of the fsyncs
                    _n_gr0 = _fs.get(mode="group")
                    _ck_gr = 0.0
                    _gsrv = ResidentServer(
                        "text", 8, capacity=1 << 14, durable_dir=_gdir,
                        durable_fsync="group", fsync_window=4,
                        auto_checkpoint=False,
                    )
                    _g0 = time.perf_counter()
                    for _e, _pl in enumerate(_eps):
                        _gsrv.ingest([_pl] * 8, _cid)
                        if _e == len(_eps) // 2:
                            _c0 = _fs.get(mode="group")
                            _gsrv.checkpoint()
                            _ck_gr = _fs.get(mode="group") - _c0
                    np.asarray(_jnp.count_nonzero(_gsrv.batch.cols.valid))
                    _gsec = time.perf_counter() - _g0
                    _gsrv.close()
                    _n_gr = _fs.get(mode="group") - _n_gr0 - _ck_gr
                    _grec = _recover(_gdir)
                    assert _grec.batch.texts()[0] == _t.to_string()
                    assert _grec.epoch >= _gsrv.durable_epoch
                    _grec.close()
                    bank(
                        "resident_durable",
                        resident_durable_rows_per_sec=round(
                            8 * 768 * len(_eps) / _dsec
                        ),
                        resident_durable_replayed_rounds=(
                            _rec.last_recovery.rounds_replayed
                        ),
                        resident_durable_fsyncs=round(_n_pr),
                        resident_durable_group_fsyncs=round(_n_gr),
                        resident_durable_group_rows_per_sec=round(
                            8 * 768 * len(_eps) / _gsec
                        ),
                        resident_durable_note=(
                            "resident ingest with durable_dir, then "
                            "recover_server reopen gated on the oracle — "
                            "A/B at equal round count: per-round WAL fsync "
                            f"({round(_n_pr)} ingest-path fsyncs) vs "
                            "durable_fsync='group' fsync_window=4 "
                            f"({round(_n_gr)} ingest-path fsyncs, "
                            "acked-epoch watermark honored across the "
                            "reopen); checkpoint-driven control-record "
                            "syncs are identical in both modes and "
                            "excluded; the persist.* entries of the "
                            "metrics sidecar carry the wal/fsync "
                            "histograms"
                        ),
                    )
                    note(
                        f"durable resident ingest: {8*768*len(_eps)/_dsec/1e3:.0f}k "
                        f"rows/s, {round(_n_pr)} fsyncs; group commit "
                        f"{8*768*len(_eps)/_gsec/1e3:.0f}k rows/s, "
                        f"{round(_n_gr)} fsyncs; reopen replayed "
                        f"{_rec.last_recovery.rounds_replayed} rounds"
                    )
                finally:
                    _shutil.rmtree(_ddir, ignore_errors=True)
                    _shutil.rmtree(_gdir, ignore_errors=True)
        except Exception as e:  # tpulint: disable=LT-EXC(resident extra, never the headline)
            note(f"resident phase failed ({type(e).__name__}: {e})")

    # ---- phase: sync front-end (BENCH_SYNC=1, ISSUE 7) ----------------
    # the repo's first end-to-end many-writers-many-readers benchmark:
    # concurrent sessions push client update blobs through the SyncServer
    # fan-in (batched into pipelined resident groups), committed epochs
    # fan out, readers pull deltas.  Banks sessions, pushes/sec and
    # p50/p99 push-to-visible latency into the `sync` sidecar.
    if remaining() > 30 and os.environ.get("BENCH_SYNC") == "1":
        try:
            import random as _random

            from loro_tpu import LoroDoc
            from loro_tpu.obs import metrics as _obsm
            from loro_tpu.sync import SyncServer

            S_DOCS, S_WRITERS, S_EPOCHS = 8, 2, 6
            n_sess = S_DOCS * S_WRITERS
            note(
                f"sync phase: {n_sess} writer sessions x {S_DOCS} docs x "
                f"{S_EPOCHS} epochs through the fan-in..."
            )
            _rng3 = _random.Random(0x5E51DE19)
            _clients = []  # [doc][writer] replicas
            for i in range(S_DOCS):
                b = LoroDoc(peer=3000 + 10 * i)
                b.get_text("t").insert(0, f"sync bench base {i}")
                b.commit()
                reps = [b]
                for w in range(1, S_WRITERS):
                    r = LoroDoc(peer=3000 + 10 * i + w)
                    r.import_(b.export_snapshot())
                    reps.append(r)
                _clients.append(reps)
            _scid = _clients[0][0].get_text("t").id
            _ssrv = SyncServer(
                "text", S_DOCS, cid=_scid, capacity=1 << 14,
                coalesce=8, max_queue=128,
            )
            _sess = [[_ssrv.connect(sid=f"d{i}w{w}")
                      for w in range(S_WRITERS)] for i in range(S_DOCS)]
            _smarks = [[{} for _ in range(S_WRITERS)]
                       for _ in range(S_DOCS)]
            _boot = []
            for i in range(S_DOCS):
                _boot.append(_sess[i][0].push(
                    i, _clients[i][0].export_updates({})
                ))
                _smarks[i][0] = _clients[i][0].oplog_vv()
                for w in range(1, S_WRITERS):
                    _sess[i][w]._vv[i] = _clients[i][w].oplog_vv()
                    _smarks[i][w] = _clients[i][w].oplog_vv()
            for _tk in _boot:
                _tk.epoch(120)
            _p2v = _obsm.histogram("sync.push_to_visible_seconds")
            _pushes = 0
            _s0 = time.perf_counter()
            for _e in range(S_EPOCHS):
                _tks = []
                for i in range(S_DOCS):
                    for w in range(S_WRITERS):
                        d = _clients[i][w]
                        t = d.get_text("t")
                        made = 0
                        while made < 96:
                            L = len(t)
                            if L > 8 and _rng3.random() < 0.15:
                                p0 = _rng3.randrange(L - 1)
                                dl = min(_rng3.randint(1, 3), L - p0)
                                t.delete(p0, dl)
                                made += dl
                            else:
                                run = _rng3.randint(1, 12)
                                t.insert(_rng3.randint(0, L),
                                         "abcdefghijkl"[:run])
                                made += run
                        d.commit()
                        _tks.append(_sess[i][w].push(
                            i, d.export_updates(_smarks[i][w])
                        ))
                        _smarks[i][w] = d.oplog_vv()
                        _pushes += 1
                for _tk in _tks:
                    _tk.epoch(120)
                # the many-readers half: every session pulls the delta
                # and integrates it (cross-writer merge)
                for i in range(S_DOCS):
                    for w in range(S_WRITERS):
                        _clients[i][w].import_(_sess[i][w].pull(i))
                        _smarks[i][w] = _clients[i][w].oplog_vv()
            _ssec = time.perf_counter() - _s0
            _ssrv.flush()
            # convergence gate: replicas agree and match the resident
            _stexts = _ssrv.texts()
            for i in range(S_DOCS):
                want = _clients[i][0].get_text("t").to_string()
                assert _clients[i][1].get_text("t").to_string() == want
                assert _stexts[i] == want, f"sync bench doc {i} diverged"
            _p50 = _p2v.quantile(0.50) or 0.0
            _p99 = _p2v.quantile(0.99) or 0.0
            _pull_b = _obsm.histogram("sync.pull_bytes").summary()
            _srep = _ssrv.report()
            _srep.update(
                docs=S_DOCS, epochs=S_EPOCHS,
                push_to_visible_ms_p50=round(_p50 * 1e3, 2),
                push_to_visible_ms_p99=round(_p99 * 1e3, 2),
                pull_bytes_mean=round(_pull_b["mean"], 1),
                pulls=_pull_b["count"],
                note=(
                    "many-writers-many-readers: 2 writer sessions per doc "
                    "push ~96-row client deltas through the bounded fan-in "
                    "(pipelined resident groups), every session pulls + "
                    "integrates per epoch; p50/p99 = push submit -> "
                    "committed + oracle-visible; convergence gated vs the "
                    "resident reads"
                ),
            )
            _ssrv.close()
            # trace sidecar (ISSUE 14): the stage decomposition of the
            # push-to-visible headline — per-stage mean ms (the stages
            # telescope, so their means sum to the p2v mean over the
            # same tickets), one exemplar trace id per stage, and the
            # flight ring state
            from loro_tpu.obs import flight as _flight

            _stage_h = _obsm.histogram("trace.push_stage_seconds")
            _tstages = {}
            for _row in _stage_h.snapshot()["values"]:
                _stg = _row["labels"].get("stage")
                if _stg is None:
                    continue
                _n = _row["count"]
                _ent = _tstages.setdefault(
                    _stg, {"count": 0, "sum_ms": 0.0}
                )
                _ent["count"] += _n
                _ent["sum_ms"] += _row["sum"] * 1e3
                _ex = _row.get("exemplars") or {}
                if _ex:
                    _ent["exemplar"] = list(_ex.values())[-1]
            for _ent in _tstages.values():
                _ent["mean_ms"] = round(
                    _ent.pop("sum_ms") / max(_ent["count"], 1), 3
                )
            _trace_side = {
                "stages": _tstages,
                "stage_sum_mean_ms": round(
                    sum(s["mean_ms"] for s in _tstages.values()), 3
                ),
                "p2v_mean_ms": round(_p2v.summary()["mean"] * 1e3, 3),
                "flight_recorded": _flight.recorder().recorded_total,
                "flight_capacity": _flight.recorder().capacity,
                "note": (
                    "per-stage push latency attribution "
                    "(trace.push_stage_seconds): queue_wait -> "
                    "coalesce_wait -> stage -> commit -> fsync -> "
                    "fanout telescope to push-to-visible; exemplar = "
                    "a trace id that landed in the stage's slowest "
                    "populated bucket"
                ),
            }
            bank(
                "sync",
                sync_sessions=n_sess,
                sync_pushes_per_sec=round(_pushes / _ssec, 1),
                sync_push_to_visible_ms_p50=round(_p50 * 1e3, 2),
                sync_push_to_visible_ms_p99=round(_p99 * 1e3, 2),
                sync=_srep,
                trace=_trace_side,
            )
            note(
                f"sync: {n_sess} sessions, {_pushes/_ssec:.0f} pushes/s, "
                f"push-to-visible p50 {_p50*1e3:.1f}ms p99 {_p99*1e3:.1f}ms"
            )
        except Exception as e:  # tpulint: disable=LT-EXC(sync extra, never the headline)
            note(f"sync phase failed ({type(e).__name__}: {e})")

    # ---- phase: batched read plane (BENCH_SYNC_READERS=N, ISSUE 11) ---
    # reader-heavy serving A/B: N concurrent reader sessions pull every
    # epoch from two identically-fed text SyncServers — one with the
    # batched device read plane (concurrent pulls coalesce into one
    # export launch per window, identical frames shared), one pinned to
    # the per-doc host oracle (read_batch=False).  Banks the
    # sync_pulls_per_sec flagship pair + p50/p99 pull latency + the
    # `readplane` sidecar, and asserts the count guard: one export
    # launch per coalesced window.  Not measured on the current
    # machine yet.
    if remaining() > 30 and os.environ.get("BENCH_SYNC_READERS"):
        try:
            import random as _random
            from concurrent.futures import ThreadPoolExecutor as _TPE

            from loro_tpu import LoroDoc
            from loro_tpu.sync import SyncServer

            n_readers = int(os.environ["BENCH_SYNC_READERS"])
            R_DOCS, R_EPOCHS, R_EDITS = 4, 6, 192
            note(
                f"read-plane phase: {n_readers} readers x {R_DOCS} docs x "
                f"{R_EPOCHS} epochs, batched-device vs host-oracle..."
            )
            _rng4 = _random.Random(0x4EADB10C)
            _wdocs = []
            for i in range(R_DOCS):
                b = LoroDoc(peer=4000 + i)
                b.get_text("t").insert(0, f"read plane base {i}")
                b.commit()
                _wdocs.append(b)
            _rcid = _wdocs[0].get_text("t").id
            _arms = ("device", "oracle")
            _rsrv = {
                "device": SyncServer("text", R_DOCS, cid=_rcid,
                                     capacity=1 << 14, max_queue=128),
                "oracle": SyncServer("text", R_DOCS, cid=_rcid,
                                     capacity=1 << 14, max_queue=128,
                                     read_batch=False),
            }
            _wsess = {a: [_rsrv[a].connect(sid=f"w{i}")
                          for i in range(R_DOCS)] for a in _arms}
            _marks = [{} for _ in range(R_DOCS)]
            _boot = []
            for i in range(R_DOCS):
                pl = _wdocs[i].export_updates({})
                for a in _arms:
                    _boot.append(_wsess[a][i].push(i, pl))
                _marks[i] = _wdocs[i].oplog_vv()
            for _tk in _boot:
                _tk.epoch(120)
            _rdrs = {a: [_rsrv[a].connect(sid=f"r{k}")
                         for k in range(n_readers)] for a in _arms}
            # persistent reader pools (thread SPAWN cost is common-mode
            # noise that would swamp the serving difference) + a warm
            # round excluded from timing that seeds the reader
            # frontiers (steady-state serving is the thing being
            # measured).  The SERIAL seeding pulls ride the device but
            # only ever form size-1 windows — the 16/32/64 request
            # buckets and the dirty-doc scatter delta stay cold — so
            # warm_read_plane pre-compiles those shapes, or the first
            # timed epoch banks a multi-hundred-ms XLA compile as
            # serving latency
            _pools = {a: _TPE(max_workers=n_readers) for a in _arms}
            for a in _arms:
                for k in range(n_readers):
                    _rdrs[a][k].pull(k % R_DOCS)
            _rsrv["device"].warm_read_plane(n_readers)
            _lat = {a: [] for a in _arms}
            _wall = {a: 0.0 for a in _arms}
            _pull_n = {a: 0 for a in _arms}

            def _mk_pull(a):
                sess, lats = _rdrs[a], _lat[a]

                def _pull_one(k):
                    t0p = time.perf_counter()
                    sess[k].pull(k % R_DOCS)
                    lats.append(time.perf_counter() - t0p)
                return _pull_one

            for _e in range(R_EPOCHS):
                _tks = []
                for i in range(R_DOCS):
                    d = _wdocs[i]
                    t = d.get_text("t")
                    for _ in range(R_EDITS):
                        L = len(t)
                        t.insert(_rng4.randint(0, L), "abcdef"[:_rng4.randint(1, 6)])
                    d.commit()
                    pl = d.export_updates(_marks[i])
                    for a in _arms:
                        _tks.append(_wsess[a][i].push(i, pl))
                    _marks[i] = d.oplog_vv()
                for _tk in _tks:
                    _tk.epoch(120)
                # interleave arm order per epoch (decorrelate ambient load)
                for a in (_arms if _e % 2 == 0 else _arms[::-1]):
                    _fn = _mk_pull(a)
                    _t0a = time.perf_counter()
                    list(_pools[a].map(_fn, range(n_readers)))
                    _wall[a] += time.perf_counter() - _t0a
                    _pull_n[a] += n_readers
            # convergence + count guard
            _dt = _rsrv["device"].texts()
            _ot = _rsrv["oracle"].texts()
            assert _dt == _ot, "read-plane A/B servers diverged"
            _rbrep = _rsrv["device"].report()["readbatch"]
            assert _rbrep["launches"] <= _rbrep["windows"] <= _rbrep["pulls"], \
                "count guard: at most one export launch per pull window"
            if n_readers >= 8:
                # coalescing must actually bite at reader-storm sizes
                # (a solo reader legitimately gets one window per pull)
                assert _rbrep["windows"] < _rbrep["pulls"], \
                    "count guard: windows did not coalesce concurrent pulls"
            def _pctl(xs, q):
                xs = sorted(xs)
                return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0
            _dev_ps = _pull_n["device"] / max(_wall["device"], 1e-9)
            _ora_ps = _pull_n["oracle"] / max(_wall["oracle"], 1e-9)
            _side = {
                "readers": n_readers,
                "docs": R_DOCS,
                "epochs": R_EPOCHS,
                "device_pulls_per_sec": round(_dev_ps, 1),
                "oracle_pulls_per_sec": round(_ora_ps, 1),
                "oracle_pull_ms_p50": round(_pctl(_lat["oracle"], 0.50) * 1e3, 2),
                "oracle_pull_ms_p99": round(_pctl(_lat["oracle"], 0.99) * 1e3, 2),
                "readbatch": _rbrep,
                "note": (
                    "N concurrent reader sessions pull per epoch against "
                    "identically-fed servers; device = batched read plane "
                    "(window coalescing + shared frames, one selection "
                    "launch per window), oracle = per-doc host LoroDoc "
                    "exports under the server lock; pulls/s over the "
                    "concurrent-pull wall time, arm order interleaved"
                ),
            }
            for a in _arms:
                _pools[a].shutdown()
                _rsrv[a].close()
            bank(
                "readplane",
                sync_readers=n_readers,
                sync_pulls_per_sec=round(_dev_ps, 1),
                sync_pulls_per_sec_oracle=round(_ora_ps, 1),
                sync_read_speedup=round(_dev_ps / max(_ora_ps, 1e-9), 2),
                sync_pull_ms_p50=round(_pctl(_lat["device"], 0.50) * 1e3, 2),
                sync_pull_ms_p99=round(_pctl(_lat["device"], 0.99) * 1e3, 2),
                readplane=_side,
            )
            note(
                f"read plane: {n_readers} readers, device {_dev_ps:.0f} "
                f"pulls/s vs oracle {_ora_ps:.0f} pulls/s "
                f"({_dev_ps / max(_ora_ps, 1e-9):.2f}x), "
                f"{_rbrep['windows']} windows / {_rbrep['launches']} launches"
            )
        except Exception as e:  # tpulint: disable=LT-EXC(read-plane extra, never the headline)
            note(f"read-plane phase failed ({type(e).__name__}: {e})")

    # ---- phase: network edge (BENCH_NET=1|N, ISSUE 16) ----------------
    # the socket-fronted serving shape: N real TCP connections (one
    # NetClient + replica LoroDoc thread each) push columnar deltas
    # through the asyncio NetServer into the SyncServer fan-in, block
    # on PUSH_ACK (sent only after commit, carrying the durable
    # watermark + trace id) and pull + integrate the cross-client
    # delta.  Banks connections, pushes/s over the wire and the
    # CLIENT-observed p50/p99 push-to-ack latency — a strict superset
    # of push-to-visible whose net.ack/net.send stage marks telescope
    # into the trace.push_stage_seconds breakdown.  Convergence is
    # gated: after a full-fleet barrier every replica's final pull
    # must land it byte-equal to the resident read.  BENCH_NET=N>1
    # sets the connection count (default 64).
    if remaining() > 30 and os.environ.get("BENCH_NET"):
        try:
            import random as _random
            import threading as _threading

            from loro_tpu import LoroDoc
            from loro_tpu.net import NetClient, NetServer
            from loro_tpu.obs import metrics as _obsm
            from loro_tpu.sync import SyncServer

            _nn = int(os.environ["BENCH_NET"])
            n_conns = _nn if _nn > 1 else 64
            N_DOCS, N_EPOCHS, N_EDITS = 8, 4, 48
            note(
                f"net phase: {n_conns} socket connections x {N_DOCS} "
                f"docs x {N_EPOCHS} epochs through the TCP edge..."
            )
            _nbases = []
            for i in range(N_DOCS):
                b = LoroDoc(peer=6000 + i)
                b.get_text("t").insert(0, f"net bench base {i}")
                b.commit()
                _nbases.append(b)
            _ncid = _nbases[0].get_text("t").id
            _nsrv = SyncServer("text", N_DOCS, cid=_ncid,
                               capacity=1 << 14, coalesce=8,
                               max_queue=256)
            _nseed = _nsrv.connect(sid="net-seed")
            _nboot = [_nseed.push(i, _nbases[i].export_updates({}))
                      for i in range(N_DOCS)]
            for _tk in _nboot:
                _tk.epoch(120)
            _nsrv.warm_read_plane(min(n_conns, 64))
            _net = NetServer(_nsrv, max_connections=n_conns + 8)
            _nlat = [[] for _ in range(n_conns)]
            _npush = [0] * n_conns
            _ntend = [0.0] * n_conns
            _nfinal = [None] * n_conns
            _nerrs = []
            _go = _threading.Barrier(n_conns + 1)
            _acked = _threading.Barrier(n_conns)

            def _conn_worker(k):
                rng = _random.Random(0x0E7B000 + k)
                di = k % N_DOCS
                d = LoroDoc(peer=6100 + k)
                d.import_(_nbases[di].export_snapshot())
                cli = NetClient("127.0.0.1", _net.port, "text",
                                client_id=f"bench-{k}", timeout=120.0)
                try:
                    cli.connect()
                    d.import_(cli.pull(di))  # seed the wire frontier
                    _go.wait(120)
                    mark = d.oplog_vv()
                    for _e in range(N_EPOCHS):
                        t = d.get_text("t")
                        for _ in range(N_EDITS):
                            L = len(t)
                            t.insert(rng.randint(0, L),
                                     "abcdef"[:rng.randint(1, 6)])
                        d.commit()
                        pl = d.export_updates(mark)
                        t0p = time.perf_counter()
                        cli.push(di, pl)
                        _nlat[k].append(time.perf_counter() - t0p)
                        _npush[k] += 1
                        mark = d.oplog_vv()
                        d.import_(cli.pull(di))
                        mark = d.oplog_vv()
                    _ntend[k] = time.perf_counter()
                    # every connection's pushes are acked past here, so
                    # one more pull sees the whole fleet's ops
                    _acked.wait(300)
                    d.import_(cli.pull(di))
                    _nfinal[k] = d.get_text("t").to_string()
                except Exception as e:  # tpulint: disable=LT-EXC(worker failure is re-raised by the phase after join)
                    _nerrs.append(e)
                    _go.abort()
                    _acked.abort()
                finally:
                    cli.close()

            _nthreads = [
                _threading.Thread(target=_conn_worker, args=(k,),
                                  name=f"bench-net-{k}", daemon=True)
                for k in range(n_conns)
            ]
            for _t in _nthreads:
                _t.start()
            _go.wait(120)
            _nt0 = time.perf_counter()
            for _t in _nthreads:
                _t.join(600)
            if _nerrs:
                raise _nerrs[0]
            _nwall = max(_ntend) - _nt0
            _nsrv.flush()
            _ntexts = _nsrv.texts()
            for k in range(n_conns):
                assert _nfinal[k] == _ntexts[k % N_DOCS], \
                    f"net bench conn {k} diverged from the resident read"
            _nall = sorted(x for xs in _nlat for x in xs)

            def _npctl(q):
                return (_nall[min(len(_nall) - 1, int(q * len(_nall)))]
                        if _nall else 0.0)

            _ntotal = sum(_npush)
            _nps = _ntotal / max(_nwall, 1e-9)
            _np50, _np99 = _npctl(0.50), _npctl(0.99)
            # server-side attribution: the socket stages ride the same
            # trace.push_stage_seconds histogram as the fan-in stages
            _nstage_h = _obsm.histogram("trace.push_stage_seconds")
            _nstages = {}
            for _row in _nstage_h.snapshot()["values"]:
                _stg = _row["labels"].get("stage")
                if not (_stg or "").startswith("net."):
                    continue
                _ent = _nstages.setdefault(
                    _stg, {"count": 0, "sum_ms": 0.0})
                _ent["count"] += _row["count"]
                _ent["sum_ms"] += _row["sum"] * 1e3
            for _ent in _nstages.values():
                _ent["mean_ms"] = round(
                    _ent.pop("sum_ms") / max(_ent["count"], 1), 3)
            _nack = _obsm.histogram("net.push_to_ack_seconds")
            _nrep = _net.report()
            _net.close()
            _nsrv.close()
            _nside = {
                "connections": n_conns,
                "docs": N_DOCS,
                "epochs": N_EPOCHS,
                "pushes": _ntotal,
                "pushes_per_sec": round(_nps, 1),
                "push_to_ack_ms_p50_server": round(
                    (_nack.quantile(0.50) or 0.0) * 1e3, 2),
                "push_to_ack_ms_p99_server": round(
                    (_nack.quantile(0.99) or 0.0) * 1e3, 2),
                "net_stages": _nstages,
                "server": _nrep,
                "note": (
                    "N threads each own a REAL TCP connection + replica "
                    "doc; per epoch they push a columnar delta, block on "
                    "PUSH_ACK (commit + durable watermark ride the ack) "
                    "and pull-integrate; p50/p99 = client-side push "
                    "submit -> ack receipt over the socket; net.ack/"
                    "net.send stage marks telescope into the push "
                    "breakdown; convergence gated vs the resident read "
                    "after a full-fleet ack barrier"
                ),
            }
            bank(
                "net",
                net_connections=n_conns,
                net_pushes_per_sec=round(_nps, 1),
                net_push_to_visible_ms_p50=round(_np50 * 1e3, 2),
                net_push_to_visible_ms_p99=round(_np99 * 1e3, 2),
                net=_nside,
            )
            note(
                f"net: {n_conns} connections, {_nps:.0f} pushes/s, "
                f"push-to-ack p50 {_np50*1e3:.1f}ms p99 {_np99*1e3:.1f}ms"
            )
        except Exception as e:  # tpulint: disable=LT-EXC(net extra, never the headline)
            note(f"net phase failed ({type(e).__name__}: {e})")

    # ---- phase: WAL-shipping replication (BENCH_REPL=1|N, ISSUE 12) ---
    # read scale-OUT, measured in the deployment shape: leader A serves
    # ALL N readers alone (the single-leader line); leader B ships its
    # WAL to a follower in a SEPARATE PROCESS (.visible-marker tail
    # visibility, own GIL/core/read plane) and the same N readers split
    # N/2 in-process on B + N/2 in the follower child, both halves
    # serving CONCURRENTLY.  Both leaders are fed identical pushes.
    # Banks aggregate repl_pulls_per_sec vs the single-leader line, the
    # cross-process push-to-follower-visible lag, and the promotion
    # downtime (leader retired -> first durable write on the promoted
    # follower).  BENCH_REPL=N>1 sets the reader count (default 32).
    if remaining() > 60 and os.environ.get("BENCH_REPL"):
        _rctl = None
        _rproc = None
        try:
            import random as _random
            import subprocess as _subprocess
            import tempfile as _tempfile
            from concurrent.futures import ThreadPoolExecutor as _TPE

            from loro_tpu import LoroDoc, replication
            from loro_tpu.replication import Follower
            from loro_tpu.sync import SyncServer

            _rn = int(os.environ["BENCH_REPL"])
            n_readers = _rn if _rn > 1 else 32
            _half = n_readers // 2
            P_DOCS, P_EPOCHS, P_EDITS = 4, 6, 128
            note(
                f"replication phase: {n_readers} readers x {P_DOCS} docs "
                f"x {P_EPOCHS} epochs, single leader vs leader + "
                "cross-process follower..."
            )
            _rng5 = _random.Random(0x4EB11CA)
            _rctl = _tempfile.mkdtemp(prefix="bench_repl_")
            _pdocs = []
            for i in range(P_DOCS):
                b = LoroDoc(peer=5000 + i)
                b.get_text("t").insert(0, f"repl base {i}")
                b.commit()
                _pdocs.append(b)
            _pcid = _pdocs[0].get_text("t").id

            def _mk_lead(tag):
                return SyncServer(
                    "text", P_DOCS, cid=_pcid, capacity=1 << 14,
                    max_queue=128, durable_dir=os.path.join(_rctl, tag),
                    durable_fsync="group", fsync_window=8,
                )

            _leadA, _leadB = _mk_lead("A"), _mk_lead("B")
            replication.enable(_leadB.resident, "bench-leader")
            _pwA = [_leadA.connect(sid=f"w{i}") for i in range(P_DOCS)]
            _pwB = [_leadB.connect(sid=f"w{i}") for i in range(P_DOCS)]
            _pmarks = [{} for _ in range(P_DOCS)]
            _boot = []
            for i in range(P_DOCS):
                pl = _pdocs[i].export_updates({})
                _boot += [_pwA[i].push(i, pl), _pwB[i].push(i, pl)]
                _pmarks[i] = _pdocs[i].oplog_vv()
            for _tk in _boot:
                _tk.epoch(120)
            for _s in (_leadA, _leadB):
                _s.flush()
                _s.resident.flush_durable()
            # spawn the follower child over leader B's directory (its
            # jax import runs while we warm the parent-side planes)
            with open(os.path.join(_rctl, "child.cfg"), "w") as f:
                json.dump({
                    "leader_dir": os.path.join(_rctl, "B"),
                    "follower_dir": os.path.join(_rctl, "F"),
                    "readers": n_readers - _half, "docs": P_DOCS,
                    "epochs": P_EPOCHS,
                }, f)
            # the follower is a host process: pinned to the CPU, it
            # never touches the chip this process holds
            _renv = dict(os.environ, BENCH_REPL_CHILD=_rctl, JAX_PLATFORMS="cpu")
            with open(os.path.join(_rctl, "child.log"), "ab") as _clog:
                _rproc = _subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__)],
                    env=_renv, stdout=_clog, stderr=_clog,
                    start_new_session=True,
                )
            _solo = [_leadA.connect(sid=f"s{k}") for k in range(n_readers)]
            _aggL = [_leadB.connect(sid=f"bl{k}") for k in range(_half)]
            for k, s in enumerate(_solo):
                s.pull(k % P_DOCS)
            for k, s in enumerate(_aggL):
                s.pull(k % P_DOCS)
            _leadA.warm_read_plane(n_readers)
            _leadB.warm_read_plane(n_readers)

            def _wait_file(path, deadline_s, what):
                t0w = time.time()
                while not os.path.exists(path):
                    err = os.path.join(_rctl, "child.err")
                    if os.path.exists(err):
                        with open(err) as f:
                            raise RuntimeError(
                                f"repl child failed: {f.read()[:500]}"
                            )
                    if _rproc.poll() is not None:
                        raise RuntimeError(
                            f"repl child exited rc={_rproc.returncode} "
                            f"before {what}"
                        )
                    if time.time() - t0w > deadline_s:
                        raise RuntimeError(f"repl child: {what} timed out")
                    time.sleep(0.005)

            _wait_file(os.path.join(_rctl, "child.ready"), 180,
                       "bootstrap")
            _pool = _TPE(max_workers=n_readers)
            _wall = {"solo": 0.0, "agg": 0.0}
            _pulls = {"solo": 0, "agg": 0}
            _lags = []

            def _pull_solo(k):
                _solo[k].pull(k % P_DOCS)

            def _pull_aggL(k):
                _aggL[k].pull(k % P_DOCS)

            # epoch 0 is an UNTIMED warm epoch: the child's replay path
            # jit-compiles its real payload shapes on the first shipped
            # round, which would otherwise bank one ~300ms compile as
            # serving lag (the read-plane warm lesson, PR 11)
            _timed = {"on": False}
            for _e in range(P_EPOCHS):
                _tks = []
                for i in range(P_DOCS):
                    d = _pdocs[i]
                    t = d.get_text("t")
                    for _ in range(P_EDITS):
                        L = len(t)
                        t.insert(_rng5.randint(0, L),
                                 "abcdef"[:_rng5.randint(1, 6)])
                    d.commit()
                    pl = d.export_updates(_pmarks[i])
                    _tks += [_pwA[i].push(i, pl), _pwB[i].push(i, pl)]
                    _pmarks[i] = d.oplog_vv()
                for _tk in _tks:
                    _tk.epoch(120)
                for _s in (_leadA, _leadB):
                    _s.flush()
                    _s.resident.flush_durable()  # publishes .visible

                def _run_agg():
                    # child goes first (its catch_up overlaps nothing
                    # timed), then the parent half serves concurrently
                    # with the child's half
                    _gop = os.path.join(_rctl, f"e{_e}.go")
                    with open(_gop + ".tmp", "w") as f:
                        json.dump({"epoch": _leadB.resident.epoch}, f)
                    os.replace(_gop + ".tmp", _gop)  # atomic: child polls
                    _t0a = time.perf_counter()
                    list(_pool.map(_pull_aggL, range(_half)))
                    _pwall = time.perf_counter() - _t0a
                    _wait_file(os.path.join(_rctl, f"e{_e}.done"), 90,
                               f"epoch {_e}")
                    with open(os.path.join(_rctl, "child.out")) as f:
                        rec = json.loads(f.read().splitlines()[_e])
                    if _timed["on"]:
                        _lags.append(rec["lag_s"] * 1e3)
                        _wall["agg"] += max(_pwall, rec["pull_wall_s"])
                        _pulls["agg"] += n_readers

                def _run_solo():
                    _t0a = time.perf_counter()
                    list(_pool.map(_pull_solo, range(n_readers)))
                    if _timed["on"]:
                        _wall["solo"] += time.perf_counter() - _t0a
                        _pulls["solo"] += n_readers

                for _arm in (("solo", "agg") if _e % 2 == 0
                             else ("agg", "solo")):
                    (_run_solo if _arm == "solo" else _run_agg)()
                _timed["on"] = True
            _wait_file(os.path.join(_rctl, "child.final"), 60,
                       "final state")
            with open(os.path.join(_rctl, "child.final")) as f:
                _cfinal = json.load(f)
            _rproc.wait(timeout=60)
            _rproc = None
            assert _cfinal["texts"] == _leadB.resident.texts() \
                == _leadA.resident.texts(), \
                "replication A/B: follower diverged from the leaders"
            # promotion downtime: a second (in-process) follower takes
            # over leader B — retire -> first durable write accepted
            _fol2 = Follower(os.path.join(_rctl, "B"),
                             os.path.join(_rctl, "F2"),
                             leader=_leadB.resident)
            _fol2.catch_up()
            _t0p = time.perf_counter()
            _leadB.close()
            _prom = _fol2.promote("bench-survivor")
            _wd = _pdocs[0]
            _wt = _wd.get_text("t")
            _wt.insert(0, "post-promotion ")
            _wd.commit()
            _ws = _fol2.sync.connect()
            _ws.push(0, _wd.export_updates(_pmarks[0])).epoch(120)
            _down_ms = (time.perf_counter() - _t0p) * 1e3
            assert _prom.texts()[0] == _wt.to_string(), \
                "post-promotion push did not land"
            _fol2.close()
            _leadA.close()
            _pool.shutdown()

            def _pctl5(xs, q):
                xs = sorted(xs)
                return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0
            _solo_ps = _pulls["solo"] / max(_wall["solo"], 1e-9)
            _agg_ps = _pulls["agg"] / max(_wall["agg"], 1e-9)
            _side = {
                "readers": n_readers,
                "docs": P_DOCS,
                "epochs": P_EPOCHS,
                "warm_epochs": 1,
                "leader_pulls_per_sec": round(_solo_ps, 1),
                "aggregate_pulls_per_sec": round(_agg_ps, 1),
                "lag_ms_p50": round(_pctl5(_lags, 0.50), 2),
                "lag_ms_p99": round(_pctl5(_lags, 0.99), 2),
                "promotion_downtime_ms": round(_down_ms, 1),
                "follower": _cfinal.get("report"),
                "note": (
                    "two identically-fed durable group-commit text "
                    "leaders: A serves all N readers (single-leader "
                    "line); B ships WAL to a follower in a separate "
                    "process (.visible marker tail) and N/2 in-process "
                    "+ N/2 follower-process readers serve concurrently "
                    "— per-epoch agg wall = max(parent half, follower "
                    "half); lag = cross-process marker catch_up to the "
                    "pushed epoch; epoch 0 is an untimed warm epoch "
                    "(child replay-path compile); downtime = leader "
                    "close -> promoted follower's first durable write"
                ),
            }
            bank(
                "repl",
                repl_readers=n_readers,
                repl_pulls_per_sec=round(_agg_ps, 1),
                repl_pulls_per_sec_leader_only=round(_solo_ps, 1),
                repl_read_scaling_x=round(_agg_ps / max(_solo_ps, 1e-9), 2),
                repl_lag_ms_p50=round(_pctl5(_lags, 0.50), 2),
                repl_lag_ms_p99=round(_pctl5(_lags, 0.99), 2),
                repl_promotion_downtime_ms=round(_down_ms, 1),
                repl=_side,
            )
            note(
                f"replication: {n_readers} readers, single leader "
                f"{_solo_ps:.0f} pulls/s vs leader+follower "
                f"{_agg_ps:.0f} pulls/s "
                f"({_agg_ps / max(_solo_ps, 1e-9):.2f}x), lag p50 "
                f"{_pctl5(_lags, 0.50):.1f}ms, promotion {_down_ms:.0f}ms"
            )
            import shutil as _shutil

            _shutil.rmtree(_rctl, ignore_errors=True)
        except Exception as e:  # tpulint: disable=LT-EXC(replication extra, never the headline)
            note(f"replication phase failed ({type(e).__name__}: {e})")
            if _rproc is not None and _rctl is not None:
                try:
                    # cooperative stop through the control directory
                    with open(os.path.join(_rctl, "stop"), "w") as f:
                        f.write("stop")
                    _rproc.wait(timeout=30)
                except Exception:  # tpulint: disable=LT-EXC(best-effort child teardown on an already-failed phase)
                    pass
            # best-effort teardown: later phases must never time their
            # runs against this phase's leaked worker threads, and a
            # failed run must not strand its control dir in /tmp
            _rlocals = locals()
            for _rname in ("_pool", "_fol2", "_leadA", "_leadB"):
                _robj = _rlocals.get(_rname)
                if _robj is None:
                    continue
                try:
                    if _rname == "_pool":
                        _robj.shutdown(wait=False)
                    else:
                        _robj.close()
                except Exception:  # tpulint: disable=LT-EXC(best-effort teardown on an already-failed phase)
                    pass
            if _rctl is not None:
                import shutil as _shutil

                _shutil.rmtree(_rctl, ignore_errors=True)

    # ---- phase: sharded resident fleet (BENCH_SHARDS=N, ISSUE 8) ------
    # doc-batch parallelism as the distributed axis: the same serving-
    # granularity rounds through a 1-shard vs an N-shard
    # ShardedResidentServer (per-shard PipelinedIngest executors, so
    # coalesced groups launch concurrently across the mesh's doc rows).
    # Banks shard_scaling_x + the `shard` sidecar.  Needs >= N doc rows
    # (the 8-device CPU mesh in CI, four chips on a v5e host).
    if remaining() > 30 and os.environ.get("BENCH_SHARDS"):
        try:
            import random as _random

            from loro_tpu import LoroDoc
            from loro_tpu.doc import strip_envelope
            from loro_tpu.parallel.mesh import make_mesh as _make_mesh
            from loro_tpu.parallel.sharded import ShardedResidentServer

            n_sh = int(os.environ["BENCH_SHARDS"])
            _smesh = _make_mesh()
            rows_axis = int(np.asarray(_smesh.devices).shape[0])
            if rows_axis < n_sh or rows_axis % n_sh:
                note(
                    f"shard phase skipped: mesh doc axis {rows_axis} "
                    f"cannot host {n_sh} shards (run on the CPU mesh: "
                    "JAX_PLATFORMS=cpu XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)"
                )
            else:
                SH_DOCS, SH_ROWS, SH_WARM, SH_BLOCK, SH_NBLK = 32, 192, 6, 8, 3
                note(
                    f"shard phase: {n_sh} shards vs 1, {SH_DOCS} docs x "
                    f"{SH_BLOCK * SH_NBLK} {SH_ROWS}-row rounds..."
                )
                _rng4 = _random.Random(0x5E51DE20)
                _doc4 = LoroDoc(peer=4)
                _t4 = _doc4.get_text("t")
                _shrounds = []
                for _e in range(SH_WARM + SH_BLOCK * SH_NBLK):
                    _vv = _doc4.oplog_vv()
                    made = 0
                    while made < SH_ROWS:
                        L = len(_t4)
                        if L > 8 and _rng4.random() < 0.15:
                            p0 = _rng4.randrange(L - 1)
                            dl = min(_rng4.randint(1, 3), L - p0)
                            _t4.delete(p0, dl)
                            made += dl
                        else:
                            run = _rng4.randint(1, 12)
                            _t4.insert(_rng4.randint(0, L),
                                       "abcdefghijkl"[:run])
                            made += run
                    _doc4.commit()
                    _shrounds.append(strip_envelope(_doc4.export_updates(_vv)))
                _cid4 = _doc4.get_text("t").id
                _rows_round = SH_DOCS * SH_ROWS
                import jax.numpy as _jnp

                def _mk_fleet(k):
                    f = ShardedResidentServer(
                        "text", SH_DOCS, shards=k, mesh=_smesh,
                        capacity=1 << 15,
                    )
                    return f, f.pipeline(cid=_cid4, coalesce=8, depth=2)

                def _drain_fleet(f):
                    for _s in f.shards:
                        np.asarray(_jnp.count_nonzero(_s.batch.cols.valid))

                _f1, _x1 = _mk_fleet(1)
                _fn, _xn = _mk_fleet(n_sh)
                for _pl in _shrounds[:SH_WARM]:  # compiles off the clock
                    _x1.submit([_pl] * SH_DOCS)
                    _xn.submit([_pl] * SH_DOCS)
                _x1.flush()
                _xn.flush()
                _drain_fleet(_f1)
                _drain_fleet(_fn)
                _r1 = []
                _rn = []
                for _b in range(SH_NBLK):  # interleaved turns (r4 lesson)
                    _blk = _shrounds[
                        SH_WARM + _b * SH_BLOCK : SH_WARM + (_b + 1) * SH_BLOCK
                    ]
                    for _ex, _fl, _acc in ((_x1, _f1, _r1), (_xn, _fn, _rn)):
                        _t0 = time.perf_counter()
                        for _pl in _blk:
                            _ex.submit([_pl] * SH_DOCS)
                        _ex.flush()
                        _drain_fleet(_fl)
                        _acc.append(
                            SH_BLOCK * _rows_round
                            / (time.perf_counter() - _t0)
                        )
                _r1.sort()
                _rn.sort()
                _m1 = _r1[len(_r1) // 2]
                _mn = _rn[len(_rn) // 2]
                # correctness gate: both fleets serve the host text
                assert _f1.texts() == _fn.texts()
                assert _fn.texts()[0] == _t4.to_string()
                _scaling = _mn / _m1
                _srep = _xn.report()
                _srep.update(
                    docs=SH_DOCS, rows_per_round=SH_ROWS,
                    rows_per_sec_1shard=round(_m1),
                    rows_per_sec=round(_mn),
                    scaling_x=round(_scaling, 2),
                    scaling_efficiency=round(_scaling / n_sh, 3),
                    note=(
                        f"interleaved A/B at serving granularity "
                        f"({SH_ROWS}-row rounds, {SH_DOCS} docs, "
                        f"{SH_NBLK} alternating blocks of {SH_BLOCK}): "
                        f"1-shard vs {n_sh}-shard ShardedResidentServer, "
                        "per-shard pipelines (coalesce=8), reads gated "
                        "equal across fleets and vs the host doc"
                    ),
                )
                _f1.close()
                _fn.close()
                bank(
                    "shard",
                    shard_count=n_sh,
                    shard_rows_per_sec=round(_mn),
                    shard_scaling_x=round(_scaling, 2),
                    shard=_srep,
                )
                note(
                    f"sharded: {n_sh} shards {_mn/1e3:.0f}k rows/s vs "
                    f"1 shard {_m1/1e3:.0f}k ({_scaling:.2f}x, "
                    f"eff {_scaling/n_sh:.2f})"
                )
        except Exception as e:  # tpulint: disable=LT-EXC(shard extra, never the headline)
            note(f"shard phase failed ({type(e).__name__}: {e})")

    # ---- phase: tiered doc residency (BENCH_TIER=1, ISSUE 10) ----------
    # the HBM-capacity story: 32 docs over 4 hot device slots under a
    # skewed (90/10) access trace — the tiered server serves almost all
    # traffic from the hot set while warm/cold docs hold no device rows.
    # Banks tier_hit_rate, revive-latency percentiles and the
    # tiered-vs-all-hot ingest A/B (interleaved blocks, r4 lesson) plus
    # an all-hits hot-path block whose ratio gates the <=10% overhead
    # acceptance (docs/RESIDENCY.md).
    if remaining() > 30 and os.environ.get("BENCH_TIER") == "1":
        try:
            import random as _random

            import jax.numpy as _jnp

            from loro_tpu import LoroDoc
            from loro_tpu.doc import strip_envelope
            from loro_tpu.parallel.residency import TieredResidentServer
            from loro_tpu.parallel.server import ResidentServer

            T_DOCS, T_HOT, T_ROWS = 32, 4, 96
            T_BLOCK, T_NBLK, T_HOTBLK = 12, 3, 12
            note(
                f"tier phase: {T_DOCS} docs over {T_HOT} hot slots, "
                f"90/10 skewed {T_ROWS}-row rounds..."
            )
            _rng5 = _random.Random(0x5E51DE21)
            _tdocs = []
            for i in range(T_DOCS):
                d = LoroDoc(peer=5000 + i)
                d.get_text("t").insert(0, f"tier bench doc {i} base")
                d.commit()
                _tdocs.append(d)
            _tcid = _tdocs[0].get_text("t").id
            _tmarks = [{} for _ in range(T_DOCS)]

            def _tier_delta(di):
                d = _tdocs[di]
                t = d.get_text("t")
                made = 0
                while made < T_ROWS:
                    L = len(t)
                    if L > 8 and _rng5.random() < 0.15:
                        p0 = _rng5.randrange(L - 1)
                        dl = min(_rng5.randint(1, 3), L - p0)
                        t.delete(p0, dl)
                        made += dl
                    else:
                        run = _rng5.randint(1, 12)
                        t.insert(_rng5.randint(0, L), "abcdefghijkl"[:run])
                        made += run
                d.commit()
                pl = strip_envelope(d.export_updates(_tmarks[di]))
                _tmarks[di] = d.oplog_vv()
                return pl

            def _round(di, pl):
                ups = [None] * T_DOCS
                ups[di] = pl
                return ups

            _hot_srv = ResidentServer("text", T_DOCS, capacity=1 << 14)
            _tier_srv = TieredResidentServer(
                "text", T_DOCS, hot_slots=T_HOT, capacity=1 << 14
            )

            def _drain(srv):
                dev = getattr(srv.batch, "device_batch", srv.batch)
                np.asarray(_jnp.count_nonzero(dev.cols.valid))

            # base rounds (full history, one doc per round) + compile
            # warm-up ride off the clock for both fleets
            for i in range(T_DOCS):
                pl = strip_envelope(_tdocs[i].export_updates({}))
                _tmarks[i] = _tdocs[i].oplog_vv()
                for srv in (_hot_srv, _tier_srv):
                    srv.ingest(_round(i, pl), _tcid)
                    _drain(srv)
            # core strictly inside the hot budget: LRU keeps it resident
            # across the 10% tail misses (the run-locality premise)
            _skew_core = list(range(T_HOT - 1))

            def _pick():
                if _rng5.random() < 0.90:
                    return _rng5.choice(_skew_core)
                return _rng5.randrange(T_DOCS)

            # warm block OFF the clock: first release/landing compiles
            # + the skew's steady state (bench rule: compiles never ride
            # a timed window)
            for _ in range(T_BLOCK):
                di = _pick()
                pl = _tier_delta(di)
                for srv in (_hot_srv, _tier_srv):
                    srv.ingest(_round(di, pl), _tcid)
                    _drain(srv)
            _rep0 = _tier_srv.residency.report()
            _rev0 = len(_tier_srv.residency.revive_s)
            _rh, _rt = [], []
            for _b in range(T_NBLK):  # interleaved turns (r4 lesson)
                _blk = [(_pick(),) for _ in range(T_BLOCK)]
                _blk = [(di, _tier_delta(di)) for (di,) in _blk]
                for _srv, _acc in ((_hot_srv, _rh), (_tier_srv, _rt)):
                    _t0 = time.perf_counter()
                    for di, pl in _blk:
                        _srv.ingest(_round(di, pl), _tcid)
                        _drain(_srv)
                    _acc.append(
                        T_BLOCK * T_ROWS / (time.perf_counter() - _t0)
                    )
            # all-hits hot-path block: rounds over docs that are hot
            # RIGHT NOW in the tiered fleet — the <=10%-overhead gate
            _hot_now = _tier_srv.residency.tiers()["hot"]
            _hblk = [
                (di, _tier_delta(di))
                for di in (_rng5.choice(_hot_now) for _ in range(T_HOTBLK))
            ]
            _hp = []
            for _srv in (_hot_srv, _tier_srv):
                _t0 = time.perf_counter()
                for di, pl in _hblk:
                    _srv.ingest(_round(di, pl), _tcid)
                    _drain(_srv)
                _hp.append(T_HOTBLK * T_ROWS / (time.perf_counter() - _t0))
            # correctness gate: both fleets serve the host docs
            assert _tier_srv.texts() == _hot_srv.texts() == [
                d.get_text("t").to_string() for d in _tdocs
            ], "tiered fleet diverged"
            _rh.sort()
            _rt.sort()
            _mh = _rh[len(_rh) // 2]
            _mt = _rt[len(_rt) // 2]
            _trep = _tier_srv.residency.report()
            # WINDOWED stats: only the timed skewed blocks (the
            # lifetime counters include the 32 base-round misses and
            # off-clock warm-up, which are not what the trace measures)
            _w_touch = (_trep["hits"] + _trep["misses"]
                        - _rep0["hits"] - _rep0["misses"])
            _w_hits = _trep["hits"] - _rep0["hits"]
            _hit_rate = round(_w_hits / _w_touch, 4) if _w_touch else 1.0
            _w_rev = sorted(_tier_srv.residency.revive_s[_rev0:])
            _p = lambda q: round(
                (_w_rev[min(len(_w_rev) - 1, int(q * len(_w_rev)))]
                 if _w_rev else 0.0) * 1e3, 3)
            _rev_p50, _rev_p99 = _p(0.50), _p(0.99)
            _trep.update(
                rows_per_round=T_ROWS,
                skew="90/10 over a 3-doc core",
                window_hit_rate=_hit_rate,
                window_revive_ms_p50=_rev_p50,
                window_revive_ms_p99=_rev_p99,
                rows_per_sec_all_hot=round(_mh),
                rows_per_sec_tiered=round(_mt),
                hot_path_rows_per_sec_all_hot=round(_hp[0]),
                hot_path_rows_per_sec_tiered=round(_hp[1]),
                note=(
                    f"interleaved A/B at serving granularity ({T_ROWS}-"
                    f"row single-doc rounds, {T_DOCS} docs, {T_NBLK} "
                    f"alternating blocks of {T_BLOCK}): always-hot "
                    f"ResidentServer vs hot_slots={T_HOT} tiered server "
                    "under a 90/10 skewed trace (one off-clock warm "
                    "block takes release/landing compiles + skew "
                    "steady-state); hit rate and revive percentiles are "
                    "WINDOWED to the timed blocks; hot-path block "
                    "touches only currently-hot docs (the <=10% "
                    "overhead gate); reads gated equal across fleets "
                    "and vs host docs"
                ),
            )
            bank(
                "tier",
                tier_hit_rate=_hit_rate,
                tier_revive_ms_p50=_rev_p50,
                tier_revive_ms_p99=_rev_p99,
                tier_rows_per_sec=round(_mt),
                tier_all_hot_rows_per_sec=round(_mh),
                tier_vs_all_hot=round(_mt / _mh, 3),
                tier_hot_path_ratio=round(_hp[1] / _hp[0], 3),
                tier=_trep,
            )
            note(
                f"tiered: {_mt/1e3:.0f}k rows/s vs all-hot "
                f"{_mh/1e3:.0f}k ({_mt/_mh:.2f}x), windowed hit rate "
                f"{_hit_rate:.2f}, revive p50 {_rev_p50:.1f}ms p99 "
                f"{_rev_p99:.1f}ms, hot-path ratio {_hp[1]/_hp[0]:.2f}"
            )
        except Exception as e:  # tpulint: disable=LT-EXC(tier extra, never the headline)
            note(f"tier phase failed ({type(e).__name__}: {e})")

    # ---- phase: fleet health plane (BENCH_HEALTH=1, ISSUE 17) ---------
    # the observability tax, measured: a HealthPlane sampling THIS
    # process's full registry (every phase above left its counters,
    # labeled rows and histograms behind) — mean/p50/p99 ns per tick
    # over ~200 ticks, plus the heat accountant's rebalancer feed
    # (top-K docs, per-shard skew ratio).  When no serving phase fed
    # the accountant, a seeded zipfian stand-in load makes the skew
    # number meaningful.  Count-guarded: the sampled device-launch
    # counters must not move across the ticks (the sampler never
    # touches the device).
    if remaining() > 10 and os.environ.get("BENCH_HEALTH") == "1":
        try:
            from loro_tpu.obs import heat as _heat
            from loro_tpu.obs import metrics as _obsm
            from loro_tpu.obs.health import HealthPlane as _HealthPlane

            def _launch_total() -> float:
                out = 0.0
                for _mm in _obsm.registry().metrics():
                    if _mm.name in ("fleet.device_launches_total",
                                    "resilience.launches_total"):
                        out += sum(r["value"]
                                   for r in _mm.snapshot()["values"])
                return out

            _acct = _heat.accountant()
            if not _acct.report()["docs_top"]:
                import random as _random

                _hrng = _random.Random(17)
                for _ in range(512):
                    _di = min(int(_hrng.paretovariate(1.2)) - 1, 63)
                    _heat.tick_doc(_di, "push")
                    _heat.tick_shard(_di % 4, "ingest", of=4)
            _plane = _HealthPlane(window_s=60.0)
            _plane.tick()  # warm: first sample builds the flatten dicts
            _hl0 = _launch_total()
            _tick_ns = []
            for _ in range(200):
                _t0 = time.perf_counter_ns()
                _plane.tick()
                _tick_ns.append(time.perf_counter_ns() - _t0)
            _hlaunches = _launch_total() - _hl0
            _tick_ns.sort()
            _hst = _plane.status()
            _hrep = _hst["heat"]
            _mean_ns = int(sum(_tick_ns) / len(_tick_ns))
            bank(
                "health",
                health_tick_ns=_mean_ns,
                health_skew_ratio=_hrep["skew_ratio"],
                health={
                    "ticks": _hst["ticks"],
                    "tick_ns_p50": _tick_ns[len(_tick_ns) // 2],
                    "tick_ns_p99": _tick_ns[int(len(_tick_ns) * 0.99)],
                    "verdict": _hst["verdict"],
                    "open_alerts": len(_hst["alerts"]),
                    "tracked_docs": _hrep["tracked_docs"],
                    "n_shards": _hrep["n_shards"],
                    "skew_ratio": _hrep["skew_ratio"],
                    "docs_top": _hrep["docs_top"][:4],
                    "revive_per_s": _hrep["revive_per_s"],
                    "launches_during_ticks": _hlaunches,
                },
            )
            note(
                f"health: {_mean_ns / 1e3:.0f}us/tick mean "
                f"(p99 {_tick_ns[int(len(_tick_ns) * 0.99)] / 1e3:.0f}us "
                f"over {len(_tick_ns)} ticks), skew {_hrep['skew_ratio']}"
                f", launches during ticks {_hlaunches:.0f}"
            )
        except Exception as e:  # tpulint: disable=LT-EXC(health extra, never the headline)
            note(f"health phase failed ({type(e).__name__}: {e})")

    bank("done", partial=None)
    emit_record(_final_record())


# ---------------------------------------------------------------------------
# replication bench: the follower process
# ---------------------------------------------------------------------------


def _repl_child_main() -> None:
    """BENCH_REPL_CHILD=<ctl_dir>: the replication bench's follower
    PROCESS — a cross-process hot standby over the leader's durable
    directory (``.visible``-marker tail visibility, the real deployment
    shape: its own GIL, its own core, its own read plane).  File
    protocol under ctl_dir: ``child.cfg`` in, ``child.ready`` out,
    then per epoch wait ``e<N>.go`` (JSON ``{"epoch": target}``),
    catch up to the target, serve one reader fan-out, append a line to
    ``child.out`` and write ``e<N>.done``; ``child.final`` carries the
    differential texts + follower report.  Always CPU platform (the
    parent starts it with JAX_PLATFORMS=cpu): a chip belongs to one
    process, and that is the leader."""
    ctl = os.environ["BENCH_REPL_CHILD"]

    def _fail(e: BaseException) -> None:
        import traceback

        with open(os.path.join(ctl, "child.err"), "w") as f:
            f.write(f"{type(e).__name__}: {e}\n{traceback.format_exc()}")

    try:
        from concurrent.futures import ThreadPoolExecutor

        from loro_tpu.replication import Follower

        with open(os.path.join(ctl, "child.cfg")) as f:
            cfg = json.load(f)
        n, docs = int(cfg["readers"]), int(cfg["docs"])
        fol = Follower(cfg["leader_dir"], cfg["follower_dir"],
                       follower_id="bench-child", leader=None)
        readers = [fol.sync.connect(sid=f"cr{k}") for k in range(n)]
        for k, s in enumerate(readers):
            s.pull(k % docs)
        fol.warm_read_plane(n)
        pool = ThreadPoolExecutor(max_workers=n)
        with open(os.path.join(ctl, "child.ready"), "w") as f:
            f.write("ready")
        with open(os.path.join(ctl, "child.out"), "a") as out:
            for e in range(int(cfg["epochs"])):
                go = os.path.join(ctl, f"e{e}.go")
                stop = os.path.join(ctl, "stop")
                t0w = time.time()
                while not os.path.exists(go):
                    if os.path.exists(stop) or time.time() - t0w > 300:
                        return  # parent stopped (or died): exit clean
                    time.sleep(0.001)
                with open(go) as f:
                    target = int(json.load(f)["epoch"])
                t0 = time.perf_counter()
                deadline = t0 + 60.0
                while (fol.applied_epoch < target
                       and time.perf_counter() < deadline):
                    fol.catch_up()
                    if fol.applied_epoch < target:
                        time.sleep(0.001)
                lag_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                list(pool.map(lambda k: readers[k].pull(k % docs),
                              range(n)))
                wall = time.perf_counter() - t0
                out.write(json.dumps({
                    "e": e, "applied": fol.applied_epoch,
                    "lag_s": round(lag_s, 6),
                    "pull_wall_s": round(wall, 6), "pulls": n,
                }) + "\n")
                out.flush()
                with open(os.path.join(ctl, f"e{e}.done"), "w") as f:
                    f.write("done")
        final = {"texts": fol.resident.texts(), "report": fol.report()}
        pool.shutdown()
        fol.close()
        fpath = os.path.join(ctl, "child.final")
        with open(fpath + ".tmp", "w") as f:
            json.dump(final, f)
        os.replace(fpath + ".tmp", fpath)  # atomic: the parent polls
    except BaseException as e:  # tpulint: disable=LT-EXC(subprocess boundary: the parent reads child.err, a silent death would hang it)
        _fail(e)
        raise


if __name__ == "__main__":
    if os.environ.get("BENCH_REPL_CHILD"):
        _repl_child_main()
    else:
        main()
