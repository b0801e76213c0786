#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that loro-tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the width of the published deployment, and checks every answer
against the plain host engine (``LoroDoc``):

- ``import`` — the north-star bulk import on documents of 259,778
  single-character patches (the length and shape of B4's automerge
  trace, from the seeded generator): (a) ``public``, the library's
  ``Fleet.merge_text_payloads`` on 16 documents in its one launch of
  the chain-contracted merge (packed u8 rows, or plain ``ChainColumns``
  where the chain bucket outgrows 16-bit chain ids, as the seeded
  trace's ~51,000 chains do; the rank chosen from the chain ring);
  (b) ``flagship``, the decode -> contract -> pack -> merge pipeline
  (``merge_text_payloads_packed``, the benchmark's packed64) on 64 documents
  in 8-document launches, where the Pallas rank must be the one that
  runs;
- ``serve`` — ``NetServer`` -> ``SyncServer`` -> ``ResidentServer`` with
  4096 x 16,384 resident text columns, a group-commit WAL, the pipeline
  and the warmed device read plane; TCP clients push keystroke-sized
  edits to a zipfian hot set, pull, and a fresh client first-syncs;
- ``sync`` — one launch timed with ``block_until_ready``, with a scalar
  fetch, and with both.

One JSON object per phase goes to stdout; the LAST line is
``{"ok": true, "device": {...}}`` and nothing else.  The run fails
(non-zero exit, ``"ok": false`` with the reason) when the platform is not
``tpu``, when the device is not in the peaks table, when any
degradation / host-fallback / retry counter moved, when the native
decoder is missing, when the flagship step resolved to anything but the
Pallas rank, or when any phase raised.

One process owns the chip: this one.  The host-only work (trace replay,
workload generation) runs in worker processes that are started BEFORE
this process initialises a JAX backend and are themselves pinned to the
CPU.  The one-chip phases run on an explicit one-device mesh over
``jax.devices()[0]``, so they behave the same on a four-chip host.

``--chips 4`` (run by hand on a four-chip host; the driver never passes
it) runs only the multi-chip paths and what they are compared with:
``ShardedResidentServer`` over four shards against the one-shard
``ResidentServer`` on the same rounds, and ``Fleet`` /
``DeviceDocBatch`` on a four-device doc-axis mesh against one device.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from multiprocessing import get_context

import numpy as np

from loro_tpu import LoroDoc
from loro_tpu.bench_utils import (
    DEVICE_PEAKS,
    PUBLISHED_PATCHES,
    TraceSource,
    concurrent_trace_variant,
)
from loro_tpu.core.ids import ContainerID, ContainerType
from loro_tpu.doc import strip_envelope
from loro_tpu.errors import LoroError
from loro_tpu.ops.text_codes import text_from_codes

IMPORT_CID = ContainerID.root("text", ContainerType.Text)
SERVE_CID = ContainerID.root("t", ContainerType.Text)

# the sizes of the run (tests/test_chip_smoke.py calls the phases tiny)
N_DOCS, CAPACITY = 4096, 1 << 14  # resident: 34 B/row -> 2.3 GB of columns
HOT_DOCS, ROUNDS, WRITERS = 300, 32, 4  # serve traffic: 128 pushes of 1-50 ops
PUBLIC_DOCS, FLAGSHIP_DOCS, CHUNK = 16, 64, 8  # import: the benchmark's sizes
CHIPS4_PATCHES = (34_000, 34_000, 2_500, 2_500)  # rings where Pallas applies

# counters that must not move: each is a place where a host engine or a
# Python decoder answers instead of the device / native path
ZERO_COUNTERS = (
    "fleet.degraded_merges_total",
    "fleet.host_fallback_total",
    "codec.native_build_failed_total",
    "resilience.retries_total",
    "resilience.launch_failures_total",
    "resilience.degradations_total",
    "server.degraded_rounds_total",
    "server.poison_docs_total",
    "readbatch.degraded_windows_total",
    "readbatch.window_errors_total",
)

_WORDS = (
    "the of and to in is that for it as was with be by on not he this are or "
    "his from at which but have an had they you were their one all we can her "
    "has there been if more when will would who so no"
).split()


class SmokeFailure(LoroError):
    """A check of the smoke did not hold."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# host-only work: runs in worker processes that never load JAX
# ---------------------------------------------------------------------------


def _pin_worker_to_cpu() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"


def replay_variant(seed: int, patches: int, v: int) -> dict:
    """One concurrent multi-peer variant of the seeded trace, with the
    seconds its host replay took."""
    t0 = time.perf_counter()
    out = concurrent_trace_variant(
        TraceSource.synthetic(seed, patches).load(), seed, v
    )
    out["replay_s"] = time.perf_counter() - t0
    return out


def make_serve_workload(seed: int, n_docs: int, hot_docs: int, rounds: int,
                        writers: int) -> dict:
    """The serve traffic, made up front so that every consumer (TCP
    writers, the four-chip replay, the reference) sees the same bytes.

    Every document starts with 200-2000 characters.  In each round each
    writer picks a document from a zipfian hot set and types a burst of
    1-50 single-character inserts/deletes into ITS OWN replica (offline
    editors: a writer's ops depend on the base and on its own earlier
    ops only), exporting the delta.  ``expected`` is the host engine's
    merge of all writers' replicas per touched document."""
    t0 = time.perf_counter()
    rng = random.Random(seed)
    base, base_text = [], []
    for di in range(n_docs):
        d = LoroDoc(peer=(1 << 32) + di)
        n = rng.randint(200, 2000)
        words, length = [], 0
        while length < n:
            w = rng.choice(_WORDS)
            words.append(w)
            length += len(w) + 1
        text = " ".join(words)[:n]
        d.get_text("t").insert(0, text)
        d.commit()
        base.append(d.export_updates({}))
        base_text.append(text)
    hot = rng.sample(range(n_docs), min(hot_docs, n_docs))
    weights = [1.0 / (r + 1) ** 1.1 for r in range(len(hot))]
    replicas = {}
    script = [[] for _ in range(writers)]
    n_ops = 0
    for _ in range(rounds):
        for k in range(writers):
            di = rng.choices(hot, weights)[0]
            doc = replicas.get((k, di))
            if doc is None:
                doc = replicas[(k, di)] = LoroDoc(peer=((k + 1) << 40) + di + 1)
                doc.import_(base[di])
            mark = doc.oplog_vv()
            t = doc.get_text("t")
            pos = rng.randint(0, len(t))
            burst = rng.randint(1, 50)
            for _ in range(burst):
                if len(t) > 8 and rng.random() < 0.15:
                    pos = min(pos, len(t) - 1)
                    t.delete(pos, 1)
                else:
                    pos = min(pos, len(t))
                    t.insert(pos, rng.choice("etaoin shrdlu"))
                    pos += 1
            doc.commit()
            script[k].append((di, doc.export_updates(mark)))
            n_ops += burst
    expected = {}
    for di in sorted({di for _k, di in replicas}):
        ref = LoroDoc(peer=(3 << 48) + di)
        ref.import_(base[di])
        for (k, dj), doc in replicas.items():
            if dj == di:
                ref.import_(doc.export_updates({}))
        expected[di] = ref.get_text("t").to_string()
    return {
        "base": base, "base_text": base_text, "script": script,
        "expected": expected, "ops": n_ops,
        "make_s": time.perf_counter() - t0,
    }


def ingest_rounds(workload: dict, n_docs: int) -> list:
    """The workload as ``ResidentServer.ingest`` rounds (one payload per
    document per round, envelope stripped): the base load, then the
    scripted pushes in round order, a same-document collision spilling
    to a further round — what the sync fan-in does with them."""
    rounds = [[strip_envelope(b) for b in workload["base"]]]
    script = workload["script"]
    for r in range(max(len(s) for s in script)):
        cur = [[None] * n_docs]
        for s in script:
            if r < len(s):
                di, data = s[r]
                slot = next((x for x in cur if x[di] is None), None)
                if slot is None:
                    slot = [None] * n_docs
                    cur.append(slot)
                slot[di] = strip_envelope(data)
        rounds.extend(cur)
    return rounds


# ---------------------------------------------------------------------------
# checks shared by the phases
# ---------------------------------------------------------------------------


def bytes_in_use(dev, key: str = "bytes_in_use") -> int:
    stats = dev.memory_stats()  # None on backends that keep no count
    return int(stats[key]) if stats else -1


def check_clean(servers=()) -> dict:
    """Fail unless nothing fell back, degraded or retried anywhere in
    this process so far; returns what was looked at."""
    from loro_tpu import native
    from loro_tpu.obs import metrics as obs
    from loro_tpu.resilience import get_supervisor

    require(native.available(), "native decoder is not built/loaded")
    counters = {name: obs.counter(name).total() for name in ZERO_COUNTERS}
    moved = {k: v for k, v in counters.items() if v}
    require(not moved, f"fallback/degradation counters moved: {moved}")
    rep = get_supervisor().report()
    bad = {k: rep[k] for k in ("retries", "failures", "degradations") if rep[k]}
    require(not bad, f"supervisor reports {bad}")
    for srv in servers:
        require(not srv.degraded, f"{type(srv).__name__} is degraded")
    return {"counters": counters, "supervisor": rep}


def text_checksum(text: str, pad_n: int) -> int:
    """Host twin of fugue_batch._weighted_checksum for one document:
    what the device must report for ``text`` at row width ``pad_n``."""
    codes = np.zeros(pad_n, np.uint32)
    codes[: len(text)] = np.frombuffer(text.encode("utf-32-le"), np.uint32)
    wgt = (np.arange(pad_n, dtype=np.uint32) * np.uint32(2654435761)) % np.uint32(1 << 30)
    return int(((codes * wgt) % np.uint32(1 << 30)).sum(dtype=np.uint32))


class CompileEvents:
    """Counts, through ``jax.monitoring``, the executables this process
    asked its backend for (one event each, whether XLA compiled it or
    the persistent cache held it) and the persistent cache's hits and
    misses."""

    def __init__(self):
        import jax.monitoring

        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def since(self, mark=(0, 0, 0)) -> dict:
        return {"backend_compiles": self.compiles - mark[0],
                "persistent_cache_hits": self.hits - mark[1],
                "persistent_cache_misses": self.misses - mark[2]}

    def mark(self) -> tuple:
        return (self.compiles, self.hits, self.misses)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_sync(dev) -> dict:
    """One launch (the XLA rank over eight 36,866-token rings) timed
    three ways, and enqueued without waiting."""
    import jax

    from loro_tpu.ops.fugue_batch import _wyllie_dist

    rng = np.random.default_rng(0)
    m = 36866
    rings = np.tile(np.arange(m, dtype=np.int32), (8, 1))
    for r in rings:
        p = rng.permutation(m).astype(np.int32)
        r[p[:-1]] = p[1:]
    succ = jax.device_put(rings, dev)
    fn = jax.jit(jax.vmap(_wyllie_dist))
    jax.block_until_ready(fn(succ))  # compile

    def median_ms(sync) -> float:
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            sync(fn(succ))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2] * 1e3

    rec = {"phase": "sync", "enqueue_only_ms": median_ms(lambda o: None)}
    jax.block_until_ready(fn(succ))  # drain what the line above enqueued
    rec["block_until_ready_ms"] = median_ms(jax.block_until_ready)
    rec["scalar_fetch_ms"] = median_ms(lambda o: np.asarray(o[0, 0]))
    rec["both_ms"] = median_ms(
        lambda o: (jax.block_until_ready(o), np.asarray(o[0, 0])))
    rec["block_until_ready_synchronises"] = (
        rec["block_until_ready_ms"] > 0.5 * rec["scalar_fetch_ms"])
    return rec


def public_entry_plan(variants) -> dict:
    """What ``Fleet.merge_text_payloads`` is to do with these documents,
    from their own sizes: the chain and element buckets, how the batch
    travels, the ring the rank walks and the rank that ring resolves to."""
    from loro_tpu.ops.columnar import contract_chains
    from loro_tpu.ops.fugue_batch import _resolve_rank_spec, rank_bound
    from loro_tpu.parallel.fleet import text_pads, text_transport

    n_chains = max(contract_chains(v["extract"]).n_chains for v in variants)
    pad_c, pad_n = text_pads(n_chains, max(v["extract"].n for v in variants))
    ring = rank_bound(pad_c)
    return {"chains": n_chains, "pad_c": pad_c, "pad_n": pad_n,
            "transport": text_transport(pad_c, pad_n), "ring_tokens": ring,
            "rank_spec": ":".join(_resolve_rank_spec(None, ring))}


def phase_import(variants, mesh, public_docs: int, flagship_docs: int,
                 chunk: int, events: CompileEvents,
                 pipeline_runs: int = 3) -> list:
    """The two import steps on the same payloads; returns their records."""
    import jax

    from loro_tpu.obs import metrics as obs
    from loro_tpu.ops.columnar import contract_chains
    from loro_tpu.ops.fugue_batch import (
        _resolve_rank_spec,
        chain_merge_docs_packed_checksum,
        merge_text_payloads_packed,
        packed_row_bytes,
    )
    from loro_tpu.parallel.fleet import Fleet

    dev = mesh.devices.flat[0]
    on_chip = dev.platform == "tpu"
    launches = obs.counter("fleet.device_launches_total")

    # -- (a) the public entry ------------------------------------------
    payloads = [variants[i % len(variants)]["payload"] for i in range(public_docs)]
    fleet = Fleet(mesh)
    plan = public_entry_plan(variants)
    ring, spec = plan["ring_tokens"], plan["rank_spec"]
    ranked = obs.counter("rank.ring_tokens")
    n0, r0 = launches.total(), ranked.get(algo=spec)
    t0 = time.perf_counter()
    texts = fleet.merge_text_payloads(payloads, IMPORT_CID).texts
    first_s = time.perf_counter() - t0
    mark = events.mark()
    t0 = time.perf_counter()
    fleet.merge_text_payloads(payloads, IMPORT_CID)
    second_s = time.perf_counter() - t0
    require(not events.since(mark)["backend_compiles"],
            "import.public: the second, identical call compiled again")
    for i, text in enumerate(texts):
        require(text == variants[i % len(variants)]["text"],
                f"import.public: document {i} differs from the host replay")
    # ... and what it says it did: both calls ranked the chain ring
    require(ranked.get(algo=spec) - r0 == 2 * public_docs * ring,
            f"import.public: rank.ring_tokens{{algo={spec}}} moved by "
            f"{ranked.get(algo=spec) - r0}, not by two calls of "
            f"{public_docs} rings of {ring} tokens")
    public = {
        "phase": "import.public", "entry": "Fleet.merge_text_payloads",
        "docs": public_docs, "distinct": len(variants),
        "padded_shape": [public_docs, plan.pop("pad_n")], **plan,
        "first_call_s": first_s, "second_call_s": second_s,
        "compile_s": first_s - second_s,
        "launches": int(launches.total() - n0) // 2,
        "texts_equal_host": True, "bytes_in_use": bytes_in_use(dev),
    }

    # -- (b) the flagship pipeline -------------------------------------
    def pad_to(x: int, q: int) -> int:
        return -(-x // q) * q

    pad_n = pad_to(max(v["extract"].n for v in variants), 8192)
    pad_c = pad_to(max(contract_chains(v["extract"]).n_chains for v in variants), 1024)
    ring = 2 * (pad_c + 1)
    spec = ":".join(_resolve_rank_spec(None, ring))
    require(spec == "pallas:ruling",
            f"import.flagship: rank resolved to {spec}, not pallas:ruling "
            f"(ring {ring} tokens)")
    row_w = packed_row_bytes(pad_c, pad_n)
    # Lowered, inspected and warmed exactly as the pipeline dispatches:
    # on ``jax.device_put(host rows)``, an UNCOMMITTED array of the
    # default device (``jax.devices()[0]``, the mesh's one device).  A
    # committed array (``device_put(x, dev)``) is another jit entry and
    # compiles again.
    zeros = jax.device_put(np.zeros((chunk, row_w), np.uint8))
    require(zeros.devices() == {dev}, "the default device is not the mesh's")
    t0 = time.perf_counter()
    compiled = chain_merge_docs_packed_checksum.lower(
        zeros, pad_c, pad_n).compile()
    compile_s = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in compiled.as_text()
    require(has_kernel or not on_chip,
            "import.flagship: no tpu_custom_call in the compiled step")
    mem = compiled.memory_analysis()
    # the launched jit is a second, identical one: it must find the
    # executable that was just inspected, not build another
    mark = events.mark()
    t0 = time.perf_counter()
    jax.block_until_ready(chain_merge_docs_packed_checksum(zeros, pad_c, pad_n))
    warm_s = time.perf_counter() - t0
    second_jit = events.since(mark)
    require(not second_jit["backend_compiles"],
            "import.flagship: the launched jit is not the program that was "
            f"inspected (it compiled again: {second_jit})")
    step_ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(chain_merge_docs_packed_checksum(zeros, pad_c, pad_n))
        step_ms.append((time.perf_counter() - t0) * 1e3)
    pairs = [(v["payload"], v["n_ops"]) for v in variants]
    want = [(text_checksum(v["text"], pad_n), len(v["text"])) for v in variants]
    pipeline_s = []
    for run in range(pipeline_runs):
        mark = events.mark()
        outs, done, ops, seconds, n_workers = merge_text_payloads_packed(
            pairs, IMPORT_CID, pad_c, pad_n, chunk, flagship_docs)
        in_window = events.since(mark)
        require(not in_window["backend_compiles"],
                f"import.flagship: run {run} compiled inside its timed "
                f"pipeline ({in_window}); its seconds are not the pipeline's")
        require(done == flagship_docs,
                f"import.flagship: run {run} merged {done} documents")
        for li, (sums, counts) in enumerate(outs):
            sums, counts = np.asarray(sums), np.asarray(counts)
            for j in range(chunk):
                got = (int(sums[j]), int(counts[j]))
                require(got == want[(li * chunk + j) % len(variants)],
                        f"import.flagship: run {run} launch {li} document {j} "
                        "differs from the host text")
        pipeline_s.append(seconds)
    flagship = {
        "phase": "import.flagship", "entry": "merge_text_payloads_packed",
        "docs": done, "launches": len(outs), "chunk": chunk,
        "padded_shape": [chunk, pad_n], "pad_c": pad_c, "ring_tokens": ring,
        "rank_spec": spec, "tpu_custom_call": has_kernel,
        "compile_s": compile_s, "second_jit_s": warm_s, "second_jit": second_jit,
        "step_ms_on_zero_buffer": step_ms,
        "program_bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                          + mem.temp_size_in_bytes) if mem else None,
        "pipeline_s": pipeline_s, "backend_compiles_in_pipeline": 0,
        "ops": ops, "decode_threads": n_workers,
        "checksums_equal_host": True, "bytes_in_use": bytes_in_use(dev),
    }
    return [public, flagship]


def phase_serve(workload: dict, mesh, n_docs: int, capacity: int,
                durable_dir: str, sample: int = 64, seed: int = 0) -> dict:
    """Load, serve the scripted traffic over TCP, and compare."""
    import jax

    from loro_tpu.net import NetClient, NetServer
    from loro_tpu.parallel.server import ResidentServer
    from loro_tpu.sync import SyncServer

    dev = mesh.devices.flat[0]
    script, expected = workload["script"], workload["expected"]
    rec = {"phase": "serve", "n_docs": n_docs, "capacity": capacity,
           "writers": len(script), "rounds": max(len(s) for s in script),
           "hot_docs_touched": len(expected), "ops_pushed": workload["ops"],
           "workload_make_s": workload["make_s"]}
    t0 = time.perf_counter()
    resident = ResidentServer("text", n_docs, mesh=mesh, capacity=capacity,
                              durable_dir=durable_dir, durable_fsync="group")
    jax.block_until_ready(resident.batch.cols)
    rec["construct_s"] = time.perf_counter() - t0
    rec["bytes_in_use_constructed"] = bytes_in_use(dev)
    rec["device_set"] = sorted(
        d.id for d in resident.batch.cols.parent.sharding.device_set)
    sync = net = None
    try:
        t0 = time.perf_counter()
        resident.ingest([strip_envelope(b) for b in workload["base"]], SERVE_CID)
        jax.block_until_ready(resident.batch.cols)
        rec["load_s"] = time.perf_counter() - t0
        rec["rows_loaded"] = int(resident.batch.counts.sum())
        rec["bytes_in_use_loaded"] = bytes_in_use(dev)
        t0 = time.perf_counter()
        sync = SyncServer.over(resident, cid=SERVE_CID)
        rec["oracle_seed_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        rec["read_plane_shapes_warmed"] = sync.warm_read_plane()
        rec["read_plane_warm_s"] = time.perf_counter() - t0
        net = NetServer(sync)
        all_pushed = threading.Barrier(len(script))

        def writer(k: int) -> dict:
            mirrors, push_ms, paths = {}, [], {}
            with NetClient("127.0.0.1", net.port, "text",
                           client_id=f"w{k}", timeout=120.0) as cli:
                def pull_into_mirror(di: int) -> None:
                    m = mirrors.get(di)
                    if m is None:
                        m = mirrors[di] = LoroDoc(peer=(5 << 48) + (k << 20) + di)
                    m.import_(cli.pull(di))

                for di, data in script[k]:
                    t1 = time.perf_counter()
                    cli.push(di, data)
                    push_ms.append((time.perf_counter() - t1) * 1e3)
                    pull_into_mirror(di)
                    # a pusher holds its own ops: the server never
                    # serves them back to the session that pushed them
                    mirrors[di].import_(data)
                all_pushed.wait(600.0)
                for di in mirrors:  # whatever the others pushed since
                    pull_into_mirror(di)
            return {"texts": {di: m.get_text("t").to_string()
                              for di, m in mirrors.items()},
                    "push_ms": push_ms}

        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(script)) as pool:
            results = [f.result() for f in
                       [pool.submit(writer, k) for k in range(len(script))]]
        rec["traffic_s"] = time.perf_counter() - t0
        push_ms = sorted(ms for r in results for ms in r["push_ms"])
        rec["pushes"] = len(push_ms)
        rec["push_ack_ms_median"] = push_ms[len(push_ms) // 2]
        rec["push_ack_ms_max"] = push_ms[-1]
        for k, r in enumerate(results):
            for di, text in r["texts"].items():
                require(text == expected[di],
                        f"serve: writer {k}'s pulled copy of document {di} "
                        "differs from the host reference")
        # a fresh client first-syncs
        rng = random.Random(seed)
        fresh_docs = rng.sample(sorted(expected), min(8, len(expected)))
        with NetClient("127.0.0.1", net.port, "text", client_id="fresh",
                       timeout=120.0) as cli:
            for di in fresh_docs:
                d = LoroDoc(peer=(6 << 48) + di)
                d.import_(cli.pull(di))
                require(d.get_text("t").to_string() == expected[di],
                        f"serve: first sync of document {di} differs from "
                        "the host reference")
        rec["first_synced_docs"] = len(fresh_docs)
        # the device's own answer: touched documents and a sample of
        # untouched ones, from the one materialise launch
        t0 = time.perf_counter()
        texts = sync.texts()
        rec["materialise_s"] = time.perf_counter() - t0
        for di, text in expected.items():
            require(texts[di] == text,
                    f"serve: device text of document {di} differs from the "
                    "host reference")
        untouched = [di for di in range(n_docs) if di not in expected]
        for di in rng.sample(untouched, min(sample, len(untouched))):
            require(texts[di] == workload["base_text"][di],
                    f"serve: untouched document {di} changed")
        rb = sync.report()["readbatch"]
        require(rb["launches"] > 0, "serve: no pull reached the device read plane")
        require(not rb["degraded_windows"] and not rb["degraded_pulls"],
                f"serve: read plane degraded: {rb}")
        require(resident.durable_epoch == resident.epoch,
                "serve: acknowledged rounds are not all durable")
        rec["read_plane"] = rb
        rec["epoch"] = resident.epoch
        rec["durable_epoch"] = resident.durable_epoch
        rec["net"] = net.report()
        rec["bytes_in_use_served"] = bytes_in_use(dev)
        rec["peak_bytes_in_use"] = bytes_in_use(dev, "peak_bytes_in_use")
        rec["checks"] = check_clean([resident])
        rec["docs_compared"] = (len(expected) + min(sample, len(untouched))
                                + len(fresh_docs))
    finally:
        if net is not None:
            net.close()
        if sync is not None:
            sync.close()
        resident.close()
    return rec


def phase_chips4_sharded(workload: dict, n_docs: int, capacity: int) -> dict:
    """Four one-chip shards against one shard, on the same rounds."""
    import jax

    from loro_tpu.parallel.mesh import make_mesh
    from loro_tpu.parallel.server import ResidentServer
    from loro_tpu.parallel.sharded import ShardedResidentServer

    devs = jax.devices()
    rounds = ingest_rounds(workload, n_docs)
    before = [bytes_in_use(d) for d in devs]
    t0 = time.perf_counter()
    sharded = ShardedResidentServer("text", n_docs, shards=len(devs),
                                    capacity=capacity)
    jax.block_until_ready([s.batch.cols for s in sharded.shards])
    shards_only = [bytes_in_use(d) - b for d, b in zip(devs, before)]
    single = ResidentServer("text", n_docs, mesh=make_mesh(devs[:1]),
                            capacity=capacity)
    try:
        rec = {"phase": "chips4.sharded", "n_docs": n_docs, "capacity": capacity,
               "shards": len(devs), "rounds": len(rounds),
               "construct_s": time.perf_counter() - t0,
               "bytes_in_use_shards_constructed": shards_only}
        t0 = time.perf_counter()
        last = 0
        for i, r in enumerate(rounds):
            # the sharded fleet's global clock counts rounds; a single
            # server's also ticks for a round's tombstones
            ep = sharded.ingest(list(r), SERVE_CID)
            require(ep == i + 1, f"sharded: round {i} got epoch {ep}")
            ep = single.ingest(list(r), SERVE_CID)
            require(ep > last, f"one shard: round {i} epoch {ep} after {last}")
            last = ep
        rec["ingest_s"] = time.perf_counter() - t0
        rec["bytes_in_use_per_device"] = [bytes_in_use(d) for d in devs]
        rec["shard_device_sets"] = [
            sorted(d.id for d in s.batch.cols.parent.sharding.device_set)
            for s in sharded.shards]
        require(len({tuple(s) for s in rec["shard_device_sets"]}) == len(devs),
                f"shards share devices: {rec['shard_device_sets']}")
        t0 = time.perf_counter()
        got, want = sharded.texts(), single.texts()
        rec["materialise_s"] = time.perf_counter() - t0
        require(got == want, "sharded texts differ from the one-shard server")
        for di, text in workload["expected"].items():
            require(want[di] == text,
                    f"document {di} differs from the host reference")
        rec["texts_equal"] = True
        rec["checks"] = check_clean([sharded, single])
    finally:
        sharded.close()
        single.close()
    return rec


def phase_chips4_mesh(fleet_variants, batch_variants) -> dict:
    """``Fleet`` and ``DeviceDocBatch`` on a four-device doc-axis mesh,
    at widths where the Pallas rank applies, against one device."""
    import jax

    from loro_tpu.ops.fugue_batch import _resolve_rank_spec, pad_bucket
    from loro_tpu.parallel.fleet import DeviceDocBatch, Fleet
    from loro_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    mesh_n, mesh_1 = make_mesh(devs), make_mesh(devs[:1])
    rec = {"phase": "chips4.mesh", "devices": len(devs)}
    # Fleet.merge_text_payloads
    payloads = [fleet_variants[i % len(fleet_variants)]["payload"]
                for i in range(2 * len(devs))]
    plan = public_entry_plan(fleet_variants)
    require(plan["rank_spec"].startswith("pallas:"),
            f"chips4.mesh: Fleet rank is {plan['rank_spec']}")
    t0 = time.perf_counter()
    many = Fleet(mesh_n).merge_text_payloads(payloads, IMPORT_CID).texts
    rec["fleet_mesh_s"] = time.perf_counter() - t0
    one = Fleet(mesh_1).merge_text_payloads(payloads, IMPORT_CID).texts
    require(many == one, "Fleet on the mesh differs from one device")
    require(all(t == fleet_variants[i % len(fleet_variants)]["text"]
                for i, t in enumerate(one)),
            "Fleet differs from the host replay")
    rec["fleet"] = {"docs": len(payloads), **plan, "equal_one_device": True}
    # DeviceDocBatch._materialize(use_solver=True)
    payloads = [batch_variants[i % len(batch_variants)]["payload"]
                for i in range(2 * len(devs))]
    cap = pad_bucket(max(v["extract"].n for v in batch_variants))
    texts = {}
    for name, mesh in (("mesh", mesh_n), ("one", mesh_1)):
        batch = DeviceDocBatch(len(payloads), cap, mesh=mesh)
        batch.append_payloads(payloads, IMPORT_CID)
        t0 = time.perf_counter()
        codes, counts = batch._materialize(use_solver=True)
        rec[f"batch_{name}_s"] = time.perf_counter() - t0
        texts[name] = [text_from_codes(codes[i], counts[i])
                       for i in range(len(payloads))]
        if name == "mesh":
            rec["batch"] = {
                "docs": len(payloads), "capacity": cap, "c_pad": batch._c_pad,
                "rank_spec": ":".join(
                    _resolve_rank_spec(None, 2 * (batch._c_pad + 1))),
                "device_set": sorted(
                    d.id for d in batch.cols.parent.sharding.device_set),
                "shard_shapes": [list(s.data.shape) for s in
                                 batch.cols.parent.addressable_shards],
                "bytes_in_use_per_device": [bytes_in_use(d) for d in devs],
            }
            require(len(rec["batch"]["device_set"]) == len(devs),
                    f"batch lives on {rec['batch']['device_set']}")
            require(rec["batch"]["rank_spec"].startswith("pallas:"),
                    f"chips4.mesh: batch rank is {rec['batch']['rank_spec']}")
    require(texts["mesh"] == texts["one"],
            "DeviceDocBatch on the mesh differs from one device")
    require(all(t == batch_variants[i % len(batch_variants)]["text"]
                for i, t in enumerate(texts["one"])),
            "DeviceDocBatch differs from the host replay")
    rec["batch"]["equal_one_device"] = True
    rec["checks"] = check_clean()
    return rec


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths (by hand, on a "
                         "four-chip host)")
    args = ap.parse_args(argv)
    device = None
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    pool = get_context("spawn").Pool(4, initializer=_pin_worker_to_cpu)
    try:
        t_start = time.perf_counter()
        # host-only work first, in workers, BEFORE this process touches JAX
        wl = pool.apply_async(make_serve_workload,
                              (args.seed, N_DOCS, HOT_DOCS, ROUNDS, WRITERS))
        widths = ([PUBLISHED_PATCHES] * 3 if args.chips == 1
                  else CHIPS4_PATCHES)
        var = [pool.apply_async(replay_variant, (args.seed, w, v))
               for v, w in enumerate(widths)]

        import jax

        from loro_tpu import native
        from loro_tpu.config import configure_compile_cache
        from loro_tpu.parallel.mesh import make_mesh

        cache_dir = configure_compile_cache()
        events = CompileEvents()
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
        require(d0.platform == "tpu",
                f"no TPU: JAX runs on {d0.platform!r} ({d0.device_kind})")
        require(d0.device_kind in DEVICE_PEAKS,
                f"device kind {d0.device_kind!r} is not in the peaks table")
        require(len(jax.devices()) >= args.chips,
                f"--chips {args.chips} needs {args.chips} devices")
        native.require()
        emit({"phase": "start", "device": device, "seed": args.seed,
              "compile_cache_dir": cache_dir, "jax": jax.__version__,
              "trace": {"trace": "synthetic", "seed": args.seed,
                        "patches": sorted(set(widths))},
              "memory_stats": {k: int(v) for k, v in (d0.memory_stats() or {}).items()}})
        if args.chips == 1:
            mesh = make_mesh([d0])
            emit(phase_sync(d0))
            emit(phase_serve(wl.get(), mesh, N_DOCS, CAPACITY,
                             os.path.join(tmp, "wal"), seed=args.seed))
            t0 = time.perf_counter()
            variants = [f.get() for f in var]
            emit({"phase": "import.setup", "variants": len(variants),
                  "patches": PUBLISHED_PATCHES,
                  "replay_s_per_variant": [v["replay_s"] for v in variants],
                  "replay_workers": len(variants),
                  "waited_for_replay_s": time.perf_counter() - t0,
                  "ops_applied": [v["n_ops"] for v in variants],
                  "elements": [v["extract"].n for v in variants],
                  "payload_bytes": [len(v["payload"]) for v in variants]})
            for rec in phase_import(variants, mesh, PUBLIC_DOCS,
                                    FLAGSHIP_DOCS, CHUNK, events):
                emit(rec)
        else:
            emit(phase_chips4_sharded(wl.get(), N_DOCS, CAPACITY))
            variants = [f.get() for f in var]
            emit(phase_chips4_mesh(variants[:2], variants[2:]))
        emit({"phase": "end", "checks": check_clean(),
              "compile_cache": {"dir": cache_dir, **events.since()},
              "seconds": time.perf_counter() - t_start})
    except BaseException as e:
        emit({"ok": False, "reason": f"{type(e).__name__}: {e}", "device": device})
        raise
    finally:
        pool.terminate()  # tpulint: disable=LT-CHIP(the workers are host-only: pinned to the CPU, they never load JAX)
        pool.join()
        shutil.rmtree(tmp, ignore_errors=True)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
