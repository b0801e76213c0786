"""Movable trees imported INTO residency through the server's normal path:
``ResidentServer("tree", resident_documents, mesh=mesh,
move_capacity=..., node_capacity=..., durable_dir=<the run's scratch>,
durable_fsync="group")`` and ``server.ingest(per_doc_updates, cid)``, a
closed loop of one caller.  Round r carries the full-history payloads of
``docs_per_round`` documents (the document of slot k is variant k mod
``fleet_documents``) for the next EMPTY slots and ``None`` for every other
slot.  The batch's commit settles the documents a round names on the
device (their standing parents and refusal counts).  A round is
ACKNOWLEDGED when ``ingest`` has returned, ``flush_durable()`` has lifted
``server.durable_epoch`` to its epoch and the log columns and the settled
standing state are ready; the next starts only then, none starts after
``--seconds``, and the window also ends when every slot is loaded.  The
rate is every op (a create or a recorded move of an acknowledged payload)
over the time from the window's start to the last acknowledgement.

After the window, untimed: the log's files read as they stand at the last
acknowledgement (the server still open), the DEVICE's standing state read
once (``server.parent_maps()``, ``DeviceTreeBatch.refusals()``), ONE replay
of the whole table (``tree_replay_log_batch``) that the standing state must
equal, the WAL re-read after ``close()`` as a restart finds it, and the
plain reference (``tree_reference.py``, as ``import_tree``'s cell).  The
documents, the generator and the reference are ``tree_import``'s; the
acknowledgement and the WAL's two reads are ``ingest_resident``'s: both are
taken from those drivers."""
from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import numpy as np

import checks
import tree_gen
from drivers import import_tree, ingest_resident

# a log row on the device: lamport, peer_hi, peer_lo, counter, target,
# parent (4 B each) and valid (1 B)
ROW_BYTES = 25
variant_of = ingest_resident.variant_of


def prepare(run) -> None:
    from loro_tpu.parallel.fleet import DeviceTreeBatch

    if not hasattr(DeviceTreeBatch, "refusals"):
        # a program whose resident tree keeps no standing state: it cannot
        # say what a commit settled, nor read it back; fail at once, cleanly
        print("benchmarks/run.py: this program's DeviceTreeBatch keeps no "
              "standing parents (DeviceTreeBatch.refusals): it cannot run "
              "the cell", file=sys.stderr)
        sys.exit(2)
    import_tree.prepare(run)


def setup(run) -> None:
    import jax

    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.server import ResidentServer

    c = run.config
    t0 = time.perf_counter()
    run.variants = [j.get() for j in run.variant_jobs]
    waited = time.perf_counter() - t0
    for v in run.variants:  # the source's shape: its nodes, ~3 % of the draws dropped
        if v["nodes"] != c["nodes"] or not (
                c["nodes"] < v["n_ops"] <= c["nodes"] + c["move_draws"]):
            raise RuntimeError(
                f"a fed document has {v['nodes']} nodes and {v['n_ops']} ops; "
                f"the configuration states {c['nodes']} nodes and at most "
                f"{c['nodes'] + c['move_draws']} ops")
    run.sha = [hashlib.sha256(v["payload"]).hexdigest() for v in run.variants]
    run.cid = ContainerID.root(tree_gen.CONTAINER, ContainerType.Tree)
    run.first_peer = c["peer_ids"][0]  # the creates' peer: a node's counter is its create index
    run.slots = c["resident_documents"]
    run.per_round = run.traffic["docs_per_round"]
    run.durable_dir = os.path.join(run.work_dir, "durable")
    t0 = time.perf_counter()
    run.server = ResidentServer(
        "tree", run.slots, mesh=run.mesh, move_capacity=c["move_capacity"],
        node_capacity=c["node_capacity"], durable_dir=run.durable_dir,
        durable_fsync=c["durable_fsync"])
    jax.block_until_ready(run.server.batch.cols)
    built = time.perf_counter() - t0
    in_use = ingest_resident.bytes_in_use(run)
    run.acks, run.next_slot = [], 0
    t0 = time.perf_counter()
    round_entry(run)  # slots 0..: compiles (or fetches) the scatter and the settle
    first = time.perf_counter() - t0
    if run.server.degraded:
        raise RuntimeError("the warm-up round degraded the server to the host "
                           "engine: nothing of this cell would be measured")
    t0 = time.perf_counter()
    run.server.parent_maps()  # compiles the read-back the end of the run makes
    read = time.perf_counter() - t0
    run.warm_rounds = len(run.acks)
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "waited_for_documents_s": waited, "server_built_s": built,
        "first_round_s": first, "first_parent_maps_s": read,
        "bytes_in_use_built": in_use,
        "bytes_in_use_warm": ingest_resident.bytes_in_use(run),
        "payload_bytes": [len(v["payload"]) for v in run.variants],
        "n_ops": [v["n_ops"] for v in run.variants]}), flush=True)


def round_entry(run) -> None:
    """The timed path: one round into the next empty slots, and its
    acknowledgement (returned + durable + log and settled state ready)."""
    import jax

    from loro_tpu.utils import tracing

    srv = run.server
    slots = list(range(run.next_slot, min(run.next_slot + run.per_round, run.slots)))
    updates = [None] * run.slots
    for k in slots:
        updates[k] = run.variants[variant_of(run, k)]["payload"]
    synced = ingest_resident.fsyncs()
    epoch = srv.ingest(updates, run.cid)
    srv.flush_durable()
    b = srv.batch
    with tracing.span("bench.columns_ready"):
        jax.block_until_ready((b.cols, b.parents, b.refused))
    run.acks.append({"epoch": epoch, "slots": slots,
                     "durable_epoch": srv.durable_epoch,
                     "fsyncs": ingest_resident.fsyncs() - synced})
    run.next_slot = slots[-1] + 1


def window(run) -> dict:
    import jax.profiler as P

    from loro_tpu.ops.tree_batch import tree_replay_log_batch
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
    in_use = ingest_resident.bytes_in_use(run)
    run.stop_trace()  # the read-back and the whole replay are not the window's
    run.wal_at_ack = ingest_resident.log_at_acknowledgement(run)
    b = run.server.batch
    with tracing.span("bench.read_back"):
        t1 = time.perf_counter()
        run.maps = run.server.parent_maps()  # the device's standing parents
        run.refused = b.refusals()
        read = time.perf_counter() - t1
    t1 = time.perf_counter()
    # ONE replay of the whole table, the standing state's yardstick
    parents, eff = tree_replay_log_batch(b.cols, b.node_cap)
    run.whole = {"parents": np.asarray(parents),
                 "refused": np.sum(np.asarray(b.cols.valid) & ~np.asarray(eff), axis=1)}
    run.standing = {"parents": np.asarray(b.parents), "refused": np.asarray(b.refused)}
    whole_s = time.perf_counter() - t1
    rounds = run.acks[run.warm_rounds:]
    docs = [k for a in rounds for k in a["slots"]]
    ops = sum(run.variants[variant_of(run, k)]["n_ops"] for k in docs)
    c = run.config
    return {
        "attempted": len(rounds), "failed": 0, "compiled": compiled,
        "metrics": {"import_ops_per_s": ops / (last - t0)},
        # an element of this cell's byte count is an op (the traffic file)
        "facts": {"documents_merged": len(docs), "elements_merged": ops},
        "log": {"rounds": len(rounds), "round_s": round_s[:64],
                "window_s": last - t0, "ops_in_window": ops,
                "slots_loaded": run.next_slot, "read_back_s": read,
                "whole_replay_s": whole_s, "slots": run.slots,
                "bytes_in_use_loaded": in_use,
                "table_bytes": run.slots * (ROW_BYTES * c["move_capacity"]
                                            + 4 * c["node_capacity"] + 4)},
    }


def compare(run) -> dict:
    compared = import_tree.reference(run)
    if not hasattr(run, "wal_restart"):
        # the WAL as a restart finds it: the server closed, the log re-read
        run.server.close()
        run.wal_restart = ingest_resident.wal_rounds(
            os.path.join(run.durable_dir, "wal"))
        print(json.dumps({"wal_rounds_at_acknowledgement": len(run.wal_at_ack),
                          "wal_rounds_after_close": len(run.wal_restart),
                          "acknowledged_rounds": len(run.acks)}), flush=True)
    given = {k: variant_of(run, k) for a in run.acks for k in a["slots"]}
    trees = [import_tree.parents_by_create_index(m, run) for m in run.maps]
    refused = [int(x) for x in run.refused]
    # the control: the reference in the program's place with one stated
    # guarantee broken, each loaded slot as a replica reads it that missed
    # the last replica's moves
    if run.control:
        for k, v in given.items():
            trees[k] = run.refs[v]["stale_parents"]
    compared["parent_maps_differing"] = [
        abs(len(trees) - run.slots)
        + sum(1 for k, v in given.items() if trees[k] != run.refs[v]["parents"])
        # a slot no round loaded reads empty
        + sum(1 for k, m in enumerate(run.maps) if k not in given and m), 0]
    compared["refusals_differing"] = [
        abs(len(refused) - run.slots)
        + sum(1 for k, n in enumerate(refused)
              if n != (run.refs[given[k]]["refused"] if k in given else 0)), 0]
    compared["documents_unsettled"] = [sum(
        1 for k in range(len(run.standing["refused"]))
        if (run.standing["parents"][k] != run.whole["parents"][k]).any()
        or run.standing["refused"][k] != run.whole["refused"][k]), 0]
    compared["rounds_not_durable"] = [
        ingest_resident.not_durable(run, run.wal_at_ack, run.wal_restart), 0]
    compared["acknowledged_before_durable"] = [
        sum(1 for a in run.acks
            if a["durable_epoch"] < a["epoch"] or a["fsyncs"] < 1), 0]
    compared["native_explodes_missing"] = [
        max(0, len(given) - native_tree_explodes()), 0]
    return compared


def native_tree_explodes() -> int:
    """Payloads the native tree explode has decoded in this process."""
    from loro_tpu.obs import metrics as obs

    return int(obs.counter("codec.native_decode_calls_total").get(fn="tree"))


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    server = getattr(run, "server", None)
    if server is not None:
        server.close()
    run.server = None
