"""Import INTO residency through the server's normal path:
``ResidentServer("text", resident_documents, mesh=mesh, capacity=capacity,
durable_dir=<the run's scratch>, durable_fsync="group")`` and
``server.ingest(per_doc_updates, cid)``, a closed loop of one caller.
Round r carries the full-history payloads of ``docs_per_round`` documents
(the document of slot k is variant k mod ``fleet_documents``) for the next
EMPTY slots and ``None`` for every other slot.  A round is ACKNOWLEDGED when
``ingest`` has returned, ``flush_durable()`` has lifted
``server.durable_epoch`` to its epoch and the device columns are ready; the
next starts only then, none starts after ``--seconds``, and the window
also ends when every slot is loaded.  The rate is every op (a patch of an
acknowledged payload) over the time from the window's start to the last
acknowledgement.

After the window, untimed: the log's files read as they stand at the last
acknowledgement (the server still open), ``server.texts()`` once (the
DEVICE's reading of every slot), the WAL re-read from disk after
``close()`` as a restart finds it, both by the log's own reader, and the
plain reference (``fugue_reference.py``, as ``import_fleet``'s cells).  The
documents, the generator and the reference are ``b4_import``'s, unchanged:
this module takes them from ``drivers/import_fleet.py``."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import checks
from drivers import import_fleet

prepare = import_fleet.prepare
# a resident row on the device: eight columns (26 B) and two key words
ROW_BYTES = 34


def setup(run) -> None:
    import jax

    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.server import ResidentServer

    c = run.config
    waited = import_fleet.fed_documents(run)
    run.sha = [hashlib.sha256(v["payload"]).hexdigest() for v in run.variants]
    run.cid = ContainerID.root("text", ContainerType.Text)
    run.slots = c["resident_documents"]
    run.per_round = run.traffic["docs_per_round"]
    run.durable_dir = os.path.join(run.work_dir, "durable")
    t0 = time.perf_counter()
    run.server = ResidentServer(
        "text", run.slots, mesh=run.mesh, capacity=c["capacity"],
        durable_dir=run.durable_dir, durable_fsync=c["durable_fsync"])
    jax.block_until_ready(run.server.batch.cols)
    built = time.perf_counter() - t0
    in_use = bytes_in_use(run)
    run.acks, run.next_slot = [], 0
    t0 = time.perf_counter()
    round_entry(run)  # slots 0..: compiles (or fetches) the scatter, the tombstones
    first = time.perf_counter() - t0
    if run.server.degraded:
        raise RuntimeError("the warm-up round degraded the server to the host "
                           "engine: nothing of this cell would be measured")
    t0 = time.perf_counter()
    run.server.texts()  # compiles the read-back the end of the run makes
    read = time.perf_counter() - t0
    run.warm_rounds = len(run.acks)
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "waited_for_documents_s": waited, "server_built_s": built,
        "first_round_s": first, "first_texts_s": read,
        "bytes_in_use_built": in_use, "bytes_in_use_warm": bytes_in_use(run),
        "payload_bytes": [len(v["payload"]) for v in run.variants],
        "elements": [v["elements"] for v in run.variants],
        "chains": [v["chains"] for v in run.variants]}), flush=True)


def bytes_in_use(run) -> int:
    stats = run.devices[0].memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))


def variant_of(run, slot: int) -> int:
    return slot % len(run.variants)


def round_entry(run) -> None:
    """The timed path: one round into the next empty slots, and its
    acknowledgement (returned + durable + columns ready)."""
    import jax

    from loro_tpu.utils import tracing

    srv = run.server
    slots = list(range(run.next_slot, min(run.next_slot + run.per_round, run.slots)))
    updates = [None] * run.slots
    for k in slots:
        updates[k] = run.variants[variant_of(run, k)]["payload"]
    synced = fsyncs()
    epoch = srv.ingest(updates, run.cid)
    srv.flush_durable()
    with tracing.span("bench.columns_ready"):
        jax.block_until_ready((srv.batch.cols, srv.batch.key_hi, srv.batch.key_lo))
    run.acks.append({"epoch": epoch, "slots": slots,
                     "durable_epoch": srv.durable_epoch,
                     "fsyncs": fsyncs() - synced})
    run.next_slot = slots[-1] + 1


def fsyncs() -> int:
    """The data fsyncs the log has issued so far: the witness, beside
    ``durable_epoch``, that a round's group commit ran before its count."""
    from loro_tpu.obs import metrics as obs

    return obs.counter("persist.wal_fsyncs_total").total()


def window(run) -> dict:
    import jax.profiler as P

    from loro_tpu.utils import tracing

    round_s = []
    run.start_trace()
    mark = run.events.mark()
    with run.window_span():
        t0 = last = time.perf_counter()
        while last - t0 < run.seconds and run.next_slot < run.slots:
            with P.TraceAnnotation("bench.round"):
                round_entry(run)
            now = time.perf_counter()
            round_s.append(now - last)
            last = now
    compiled = run.events.names_since(mark)
    in_use = bytes_in_use(run)
    run.wal_at_ack = log_at_acknowledgement(run)
    with tracing.span("bench.read_back"):
        t1 = time.perf_counter()
        run.texts = run.server.texts()  # the device's reading of every slot
        read = time.perf_counter() - t1
    run.stop_trace()
    rounds = run.acks[run.warm_rounds:]
    docs = [k for a in rounds for k in a["slots"]]
    ops = sum(run.variants[variant_of(run, k)]["n_ops"] for k in docs)
    # what the table HOLDS at the window's end beside what it reserves
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
                "live_row_bytes": live,
                "table_bytes": ROW_BYTES * run.slots * run.config["capacity"]},
    }


def wal_rounds(wal_dir: str) -> dict:
    """``{epoch: {slot: SHA-256 of its payload}}`` of every round record
    the log's own reader finds under ``wal_dir`` (frames' CRCs checked)."""
    from loro_tpu.persist.wal import R_ROUND, WriteAheadLog

    log = WriteAheadLog(wal_dir, fsync=False)
    try:
        return {r.epoch: {k: hashlib.sha256(u).hexdigest()
                          for k, u in enumerate(r.updates) if u is not None}
                for r in log.records() if r.rtype == R_ROUND}
    finally:
        log.close()


def log_at_acknowledgement(run) -> dict:
    """The log's files as they stand at the last acknowledgement, the
    server still open: ``close()`` syncs a buffered tail, so only a read
    made before it says what the file held when the round was counted.
    The files are copied aside and read there: a second log opened on the
    live directory would take the active segment for append.  (A file read
    cannot tell a synced byte from one the OS still buffers: that witness
    is ``durable_epoch`` and the fsync count of each acknowledgement.)"""
    aside = os.path.join(run.work_dir, "wal_at_ack")
    shutil.copytree(os.path.join(run.durable_dir, "wal"), aside)
    return wal_rounds(aside)


def not_durable(run, *reads: dict) -> int:
    """Acknowledged rounds whose epoch, documents or payload bytes one of
    the log's ``reads`` lacks."""
    missing = 0
    for a in run.acks:
        given = {k: run.sha[variant_of(run, k)] for k in a["slots"]}
        missing += any(held.get(a["epoch"]) != given for held in reads)
    return missing


def compare(run) -> dict:
    compared = import_fleet.reference(run)
    if not hasattr(run, "wal_restart"):
        # the WAL as a restart finds it: the server closed, the log re-read
        run.server.close()
        run.wal_restart = wal_rounds(os.path.join(run.durable_dir, "wal"))
        print(json.dumps({"wal_rounds_at_acknowledgement": len(run.wal_at_ack),
                          "wal_rounds_after_close": len(run.wal_restart),
                          "acknowledged_rounds": len(run.acks)}), flush=True)
    loaded = [k for a in run.acks for k in a["slots"]]
    texts = list(run.texts)
    # the control: the reference in the program's place with one stated
    # guarantee broken, each loaded slot as a replica reads it that missed
    # the last exchange
    if run.control:
        for k in loaded:
            texts[k] = run.refs[variant_of(run, k)]["stale_text"]
    want = [r["text"] for r in run.refs]
    given = {k: variant_of(run, k) for k in loaded}
    compared["texts_differing"] = [
        abs(len(texts) - run.slots)
        + sum(1 for k, v in given.items() if texts[k] != want[v]), 0]
    # a slot that reads ANOTHER variant's text holds another document than
    # its round gave it; a slot no round loaded reads empty
    compared["slots_wrong"] = [
        sum(1 for k, v in given.items()
            if texts[k] != want[v] and texts[k] in want)
        + sum(1 for k, t in enumerate(texts) if k not in given and t), 0]
    compared["rounds_not_durable"] = [
        not_durable(run, run.wal_at_ack, run.wal_restart), 0]
    compared["acknowledged_before_durable"] = [
        sum(1 for a in run.acks
            if a["durable_epoch"] < a["epoch"] or a["fsyncs"] < 1), 0]
    return compared


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    server = getattr(run, "server", None)
    if server is not None:
        server.close()
    run.server = None
