"""Lists imported INTO residency through the server's normal path:
``ResidentServer("list", resident_documents, mesh=mesh, capacity=capacity,
durable_dir=<the run's scratch>, durable_fsync="group")`` and
``server.ingest(per_doc_updates, cid)``, a closed loop of one caller, as
``ingest_resident`` drives text: round r carries the full-history payloads
of ``docs_per_round`` documents (the document of slot k is variant k mod
``fleet_documents``, a ``LoroList`` typed from text's edit script by
``list_gen.py``) for the next EMPTY slots and ``None`` for every other
slot, and is ACKNOWLEDGED when ``ingest`` has returned, ``flush_durable()``
has lifted ``server.durable_epoch`` to its epoch and the device columns are
ready.  The rate is every op of an acknowledged payload over the time from
the window's start to the last acknowledgement.

Set-up first holds the program to the configuration's ``no_fallback``
guarantee: one rehearsal-size List payload ingested into a 4-slot scratch
server must take the native delta explode
(``codec.native_decode_ns_total{fn="seq_delta"}`` moves) and no host
fallback.  A program whose List payloads take the Python decoder exits 2
there, before the warm-up round.

After the window, untimed: the log's files read as they stand at the last
acknowledgement (the server still open), ``server.batch.values()`` once
(the DEVICE's order of every slot's values), the WAL re-read after
``close()``, and the plain reference (``list_reference.py``).  The
acknowledgement, the round and the WAL's two reads are ``ingest_resident``'s,
taken from that driver."""
from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import checks
import list_gen
import list_reference
from drivers import import_fleet, ingest_resident

ROW_BYTES = ingest_resident.ROW_BYTES
variant_of = ingest_resident.variant_of


def prepare(run) -> None:
    c = run.config
    run.refs = None
    run.probe_job = run.pool.apply_async(
        list_gen.make_payload, (run.seed, {**c, **c["rehearsal"]}, 0))
    run.variant_jobs = [
        run.pool.apply_async(list_gen.make_payload, (run.seed, c, v))
        for v in range(c["fleet_documents"])]


def native_list_ingest(run) -> float:
    """Ingest the rehearsal-size payload into a 4-slot scratch server and
    exit 2 unless the native delta explode decoded it without a host
    fallback; seconds taken."""
    from loro_tpu.obs import metrics as obs
    from loro_tpu.parallel.server import ResidentServer

    t0 = time.perf_counter()
    probe = run.probe_job.get()
    ns, fell = obs.counter("codec.native_decode_ns_total"), obs.counter("fleet.host_fallback_total")
    ns0, fell0 = ns.get(fn="seq_delta"), fell.total()
    server = ResidentServer("list", 4, mesh=run.mesh,
                            capacity=run.config["rehearsal"]["capacity"])
    try:
        server.ingest([probe["payload"], None, None, None], run.cid)
    finally:
        server.close()
    if ns.get(fn="seq_delta") == ns0 or fell.total() != fell0:
        print("benchmarks/run.py: this program's List ingest does not take the "
              "native delta explode (codec.native_decode_ns_total{fn=\"seq_delta\"} "
              "did not move, or a host fallback did): it cannot run the cell",
              file=sys.stderr)
        sys.exit(2)
    return time.perf_counter() - t0


def native_list_explodes() -> int:
    """Payloads the native delta explode has been given in this process."""
    from loro_tpu.obs import metrics as obs

    return int(obs.counter("codec.native_decode_calls_total").get(fn="seq_delta"))


def setup(run) -> None:
    import jax

    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.server import ResidentServer

    c = run.config
    run.cid = ContainerID.root(list_gen.CONTAINER, ContainerType.List)
    probed = native_list_ingest(run)
    run.explodes_before = native_list_explodes()
    waited = import_fleet.fed_documents(run)
    run.sha = [hashlib.sha256(v["payload"]).hexdigest() for v in run.variants]
    run.slots = c["resident_documents"]
    run.per_round = run.traffic["docs_per_round"]
    run.durable_dir = os.path.join(run.work_dir, "durable")
    t0 = time.perf_counter()
    run.server = ResidentServer(
        "list", run.slots, mesh=run.mesh, capacity=c["capacity"],
        durable_dir=run.durable_dir, durable_fsync=c["durable_fsync"])
    jax.block_until_ready(run.server.batch.cols)
    built = time.perf_counter() - t0
    in_use = ingest_resident.bytes_in_use(run)
    run.acks, run.next_slot = [], 0
    t0 = time.perf_counter()
    ingest_resident.round_entry(run)  # slots 0..: compiles (or fetches) the scatter, the tombstones
    first = time.perf_counter() - t0
    if run.server.degraded:
        raise RuntimeError("the warm-up round degraded the server to the host "
                           "engine: nothing of this cell would be measured")
    t0 = time.perf_counter()
    run.server.batch.values()  # compiles the read-back the end of the run makes
    read = time.perf_counter() - t0
    run.warm_rounds = len(run.acks)
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "native_probe_s": probed, "waited_for_documents_s": waited,
        "server_built_s": built, "first_round_s": first, "first_values_s": read,
        "bytes_in_use_built": in_use,
        "bytes_in_use_warm": ingest_resident.bytes_in_use(run),
        "payload_bytes": [len(v["payload"]) for v in run.variants],
        "elements": [v["elements"] for v in run.variants],
        "chains": [v["chains"] for v in run.variants]}), flush=True)


def window(run) -> dict:
    import jax.profiler as P

    from loro_tpu.utils import tracing

    from loro_tpu.obs import metrics as obs

    round_s = []
    counted = {name: obs.counter(name) for name in (
        "fleet.resident_values_total", "codec.native_decode_ns_total")}
    before = {name: c.total() for name, c in counted.items()}
    run.start_trace()
    mark = run.events.mark()
    with run.window_span():
        t0 = last = time.perf_counter()
        while last - t0 < run.seconds and run.next_slot < run.slots:
            with P.TraceAnnotation("bench.round"):
                ingest_resident.round_entry(run)
            now = time.perf_counter()
            round_s.append(now - last)
            last = now
    compiled = run.events.names_since(mark)
    in_use = ingest_resident.bytes_in_use(run)
    # the window's values committed and the C++ decode's time, traced or not
    window_counts = {name: c.total() - before[name] for name, c in counted.items()}
    run.stop_trace()  # the read-back is not the window's
    run.wal_at_ack = ingest_resident.log_at_acknowledgement(run)
    with tracing.span("bench.read_back"):
        t1 = time.perf_counter()
        run.values = run.server.batch.values()  # the device's order of every slot
        read = time.perf_counter() - t1
    rounds = run.acks[run.warm_rounds:]
    docs = [k for a in rounds for k in a["slots"]]
    ops = sum(run.variants[variant_of(run, k)]["n_ops"] for k in docs)
    live = ROW_BYTES * sum(run.variants[variant_of(run, k)]["elements"]
                           for k in range(run.next_slot))
    return {
        "attempted": len(rounds), "failed": 0, "compiled": compiled,
        "metrics": {"import_ops_per_s": ops / (last - t0)},
        # an element of this cell's byte count is an op (the traffic file)
        "facts": {"documents_merged": len(docs), "elements_merged": ops},
        "log": {"rounds": len(rounds), "round_s": round_s[:64],
                "window_s": last - t0, "ops_in_window": ops,
                "slots_loaded": run.next_slot, "read_back_s": read,
                "slots": run.slots, "bytes_in_use_loaded": in_use,
                "live_row_bytes": live, "window_counts": window_counts,
                "table_bytes": ROW_BYTES * run.slots * run.config["capacity"]},
    }


def reference(run) -> dict:
    """The plain reference's reading of every fed document, in the worker
    processes, once the window has closed; and whether the documents it
    read have the configuration's shape."""
    c = run.config
    if run.refs is None:
        t0 = time.perf_counter()
        run.refs = run.pool.starmap(
            list_reference.replay,
            [(run.seed, c, v) for v in range(c["fleet_documents"])])
        print(json.dumps({
            "reference_s": time.perf_counter() - t0,
            "reference_chains": [r["chains"] for r in run.refs],
            "reference_values": [len(r["values"]) for r in run.refs]}), flush=True)
    off = max(abs(r["chains"] / c["chains_after_contraction"] - 1) for r in run.refs)
    return {
        "reference_patches_off": [sum(
            abs(r["inserts"] - c["insert_patches"])
            + abs(r["deletes"] - c["delete_patches"]) for r in run.refs), 0],
        "reference_chains_off": [off, c["chains_tolerance"]]}


def compare(run) -> dict:
    compared = reference(run)
    if not hasattr(run, "wal_restart"):
        # the WAL as a restart finds it: the server closed, the log re-read
        run.server.close()
        run.wal_restart = ingest_resident.wal_rounds(os.path.join(run.durable_dir, "wal"))
        print(json.dumps({"wal_rounds_at_acknowledgement": len(run.wal_at_ack),
                          "wal_rounds_after_close": len(run.wal_restart),
                          "acknowledged_rounds": len(run.acks)}), flush=True)
    loaded = [k for a in run.acks for k in a["slots"]]
    values = list(run.values)
    # the control: the reference in the program's place with one stated
    # guarantee broken, each loaded slot as a replica reads it that missed
    # the last exchange
    if run.control:
        for k in loaded:
            values[k] = run.refs[variant_of(run, k)]["stale_values"]
    want = [r["values"] for r in run.refs]
    given = {k: variant_of(run, k) for k in loaded}
    compared["values_differing"] = [
        abs(len(values) - run.slots)
        + sum(1 for k, v in given.items() if values[k] != want[v]), 0]
    # a slot that reads ANOTHER variant's values holds another document
    # than its round gave it; a slot no round loaded reads empty
    compared["slots_wrong"] = [
        sum(1 for k, v in given.items()
            if values[k] != want[v] and values[k] in want)
        + sum(1 for k, vs in enumerate(values) if k not in given and vs), 0]
    compared["rounds_not_durable"] = [
        ingest_resident.not_durable(run, run.wal_at_ack, run.wal_restart), 0]
    compared["acknowledged_before_durable"] = [
        sum(1 for a in run.acks
            if a["durable_epoch"] < a["epoch"] or a["fsyncs"] < 1), 0]
    compared["native_explodes_missing"] = [
        max(0, len(loaded) - (native_list_explodes() - run.explodes_before)), 0]
    return compared


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    server = getattr(run, "server", None)
    if server is not None:
        server.close()
    run.server = None
