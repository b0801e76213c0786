"""Bulk import of movable trees through the library's public entry:
``Fleet(mesh).merge_tree_payloads(payloads, cid)`` on ``docs_per_call``
full-history payloads, called back to back for the window.  No new call
starts after ``--seconds``; the rate is every op (creates and recorded
moves) of the calls that completed over the time from the window's start
to the end of the last.  Every parent map of every document of every call
and every document's count of refused moves are compared, after the window,
with the plain reference's (``tree_reference.py``) reading of the same
move script."""
from __future__ import annotations

import gc
import json
import resource
import sys
import time

import checks
import tree_gen
import tree_reference

def prepare(run) -> None:
    from loro_tpu.ops import tree_batch  # imports JAX, starts no backend

    if not hasattr(tree_batch, "tree_import_batch"):
        # a program from before PR 28: its scan takes 46 s a call at this
        # shape (PERF.md) and counts no refusals; fail at once, cleanly
        print("benchmarks/run.py: this program has no fused tree import "
              "(ops/tree_batch.tree_import_batch): it cannot run the cell",
              file=sys.stderr)
        sys.exit(2)
    run.refs = run.trees = None
    run.variant_jobs = [
        run.pool.apply_async(tree_gen.make_payload, (run.seed, run.config, v))
        for v in range(run.config["fleet_documents"])]


def setup(run) -> None:
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.fleet import Fleet

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
    n = run.traffic["docs_per_call"]
    k = len(run.variants)
    run.docs = [i % k for i in range(n)]  # the variant each document is
    run.payloads = [run.variants[v]["payload"] for v in run.docs]
    run.cid = ContainerID.root(tree_gen.CONTAINER, ContainerType.Tree)
    run.first_peer = c["peer_ids"][0]  # the creates' peer: a node's counter is its create index
    run.fleet = Fleet(run.mesh)
    t0 = time.perf_counter()
    call_entry(run)  # compiles (or fetches) the one launch of this shape
    first = time.perf_counter() - t0
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "waited_for_documents_s": waited, "first_call_s": first,
        "n_ops": [v["n_ops"] for v in run.variants]}), flush=True)


def call_entry(run) -> tuple:
    """The timed path: one call of the public entry, and nothing of the
    harness's.  Its answer is held as it comes: the parent maps, and the
    moves the replay refused in each document (``Fleet.tree_refused``)."""
    maps = run.fleet.merge_tree_payloads(run.payloads, run.cid)
    return maps, run.fleet.tree_refused


def host_clocks() -> dict:
    """What this process and the machine's CPUs have done so far: their
    change over a window tells a slow window's cause from the log (the
    program's own CPU time, page faults, or time the shared host took:
    PERF.md, the cell's spread)."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    with open("/proc/stat") as f:  # cpu user nice system idle iowait irq softirq steal
        steal = int(f.readline().split()[8])
    return {"user_s": r.ru_utime, "sys_s": r.ru_stime, "minor_faults": r.ru_minflt,
            "switched_out": r.ru_nivcsw, "steal_ticks": steal,
            "collections": sum(g["collections"] for g in gc.get_stats())}


def window(run) -> dict:
    import jax.profiler as P

    ops_per_call = sum(run.variants[v]["n_ops"] for v in run.docs)
    run.answers, call_s = [], []
    # what set-up left, out of the way of the window's collections as the
    # held answers are below: every call meets the same collector
    gc.collect()
    gc.freeze()
    run.start_trace()
    before = host_clocks()
    with run.window_span():
        t0 = last = time.perf_counter()
        while last - t0 < run.seconds:
            with P.TraceAnnotation("bench.call"):
                run.answers.append(call_entry(run))
            # the held answers are the harness's, 0.5 M tracked objects a
            # call: out of the way of the program's collector (no walk)
            gc.freeze()
            now = time.perf_counter()
            call_s.append(now - last)
            last = now
    calls = len(run.answers)
    host = {k: v - before[k] for k, v in host_clocks().items()}
    return {
        "attempted": calls, "failed": 0,
        "metrics": {"import_ops_per_s": calls * ops_per_call / (last - t0)},
        "facts": {"documents_merged": calls * len(run.docs),
                  "elements_merged": calls * ops_per_call},
        "log": {"calls": calls, "call_s": call_s[:64], "window_s": last - t0,
                "ops_per_call": ops_per_call, "host": host},
    }


def reference(run) -> dict:
    """The plain reference's reading of every fed document, in the worker
    processes, once the window has closed; and whether the documents it
    read are the ones that were fed."""
    c = run.config
    if run.refs is None:
        t0 = time.perf_counter()
        run.refs = run.pool.starmap(
            tree_reference.replay,
            [(run.seed, c, v) for v in range(c["fleet_documents"])])
        print(json.dumps({
            "reference_s": time.perf_counter() - t0,
            "reference_refused": [r["refused"] for r in run.refs],
            "reference_reads_per_move": [round(r["reads_per_move"], 2)
                                         for r in run.refs]}), flush=True)
    return {
        "reference_ops_off": [sum(
            abs(r["n_ops"] - v["n_ops"])
            for r, v in zip(run.refs, run.variants)), 0],
        # the mechanism must be in the run
        "reference_refusals_none": [
            sum(1 for r in run.refs if r["refused"] == 0), 0]}


def parents_by_create_index(tree_map: dict, run) -> list:
    """The program's ``{TreeID: parent TreeID | None}`` as the reference
    states a tree: the parent's create index of every node, -1 under the
    root; -9 for a node that is missing (never equal)."""
    out = [-9] * run.config["nodes"]
    for node, parent in tree_map.items():
        if node.peer == run.first_peer and 0 <= node.counter < len(out):
            out[node.counter] = -1 if parent is None else parent.counter
    return out


def compare(run) -> dict:
    compared = reference(run)
    if run.trees is None:  # the window's maps, as the reference states a tree
        run.trees = [([parents_by_create_index(m, run) for m in maps], refused)
                     for maps, refused in run.answers]
        run.answers = None
    differing = refusals = 0
    for trees, refused in run.trees:
        refused = [] if refused is None else refused.tolist()
        differing += abs(len(trees) - len(run.docs))
        refusals += abs(len(refused) - len(run.docs))
        for got, v in zip(trees, run.docs):
            # the control: the reference in the program's place with one
            # stated guarantee broken, the tree as a replica reads it that
            # missed the last replica's moves
            if run.control:
                got = run.refs[v]["stale_parents"]
            differing += got != run.refs[v]["parents"]
        refusals += sum(got != run.refs[v]["refused"]
                        for got, v in zip(refused, run.docs))
    compared["parent_maps_differing"] = [differing, 0]
    compared["refusals_differing"] = [refusals, 0]  # documents, not moves
    return compared


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    run.fleet = None
    gc.unfreeze()
