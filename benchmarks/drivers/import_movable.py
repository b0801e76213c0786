"""Bulk import of movable lists through the library's public entry:
``Fleet(mesh).merge_movable_payloads(payloads, cid)`` on ``docs_per_call``
full-history payloads, called back to back for the window.  No new call
starts after ``--seconds``; the rate is every op (an item created, a
recorded move, a set) of the calls that completed over the time from the
window's start to the end of the last.  Every value list of every
document of every call is compared, after the window, with the plain
reference's (``movable_reference.py``) reading of the same edit script,
and the rank the window ticked, and in a traced run the device's own
record, with the rank the cell's traffic file states."""
from __future__ import annotations

import gc
import json
import time

import checks
import movable_gen
import movable_reference


def prepare(run) -> None:
    run.refs = None
    run.variant_jobs = [
        run.pool.apply_async(movable_gen.make_payload, (run.seed, run.config, v))
        for v in range(run.config["fleet_documents"])]


def setup(run) -> None:
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.fleet import Fleet

    c = run.config
    t0 = time.perf_counter()
    run.variants = [j.get() for j in run.variant_jobs]
    waited = time.perf_counter() - t0
    for v in run.variants:  # the configuration's shape: its items, its draws
        if v["items"] != c["items"] or not (
                c["items"] < v["n_ops"] <= c["items"] + c["draws"]):
            raise RuntimeError(
                f"a fed document has {v['items']} items and {v['n_ops']} ops; "
                f"the configuration states {c['items']} items and at most "
                f"{c['items'] + c['draws']} ops")
    n = run.traffic["docs_per_call"]
    k = len(run.variants)
    run.docs = [i % k for i in range(n)]  # the variant each document is
    run.payloads = [run.variants[v]["payload"] for v in run.docs]
    run.cid = ContainerID.root(movable_gen.CONTAINER, ContainerType.MovableList)
    run.fleet = Fleet(run.mesh)
    t0 = time.perf_counter()
    call_entry(run)  # compiles (or fetches) the one launch of this shape
    first = time.perf_counter() - t0
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "waited_for_documents_s": waited, "first_call_s": first,
        "slots": [v["slots"] for v in run.variants],
        "set_rows": [v["set_rows"] for v in run.variants],
        "payload_bytes": [len(v["payload"]) for v in run.variants],
        "n_ops": [v["n_ops"] for v in run.variants]}), flush=True)


def call_entry(run) -> list:
    """The timed path: one call of the public entry, and nothing of the
    harness's.  Its answer: a value list a document."""
    return run.fleet.merge_movable_payloads(run.payloads, run.cid)


def ranked() -> dict:
    """The tokens the program says it has ranked so far
    (``rank.ring_tokens``), by the rank it says ran (``algo``)."""
    from loro_tpu.obs import metrics as obs

    return {row["labels"].get("algo"): row["value"]
            for row in obs.counter("rank.ring_tokens").snapshot()["values"]}


def window(run) -> dict:
    import jax.profiler as P

    ops_per_call = sum(run.variants[v]["n_ops"] for v in run.docs)
    run.lists, call_s = [], []  # every call's answer, held as it comes
    gc.collect()
    gc.freeze()  # what set-up left: out of the way of the window's collections
    run.start_trace()
    before = ranked()
    with run.window_span():
        t0 = last = time.perf_counter()
        while last - t0 < run.seconds:
            with P.TraceAnnotation("bench.call"):
                run.lists.append(call_entry(run))
            now = time.perf_counter()
            call_s.append(now - last)
            last = now
    calls = len(run.lists)
    run.ranked = {algo: v - before.get(algo, 0) for algo, v in ranked().items()
                  if v > before.get(algo, 0)}
    return {
        "attempted": calls, "failed": 0,
        "metrics": {"import_ops_per_s": calls * ops_per_call / (last - t0)},
        "facts": {"documents_merged": calls * len(run.docs),
                  "elements_merged": calls * ops_per_call},
        "log": {"calls": calls, "call_s": call_s[:64], "window_s": last - t0,
                "ops_per_call": ops_per_call, "ranked_tokens": run.ranked},
    }


def reference(run) -> dict:
    """The plain reference's reading of every fed document, in the worker
    processes, once the window has closed; and whether the documents it
    read are the ones that were fed."""
    c = run.config
    if run.refs is None:
        t0 = time.perf_counter()
        run.refs = run.pool.starmap(
            movable_reference.replay,
            [(run.seed, c, v) for v in range(c["fleet_documents"])])
        print(json.dumps({
            "reference_s": time.perf_counter() - t0,
            "reference_moves": [r["moves"] for r in run.refs],
            "reference_sets": [r["sets"] for r in run.refs]}), flush=True)
    return {
        "reference_ops_off": [sum(
            abs(r["n_ops"] - v["n_ops"]) + abs(r["slots"] - v["slots"])
            for r, v in zip(run.refs, run.variants)), 0],
        # both mechanisms must be in the run
        "reference_moves_or_sets_none": [
            sum(1 for r in run.refs if not (r["moves"] and r["sets"])), 0]}


def rank_off(run) -> int:
    """Tokens the window's launches ranked that are not of ONE ring a
    document of the padded batch — a ring that holds the longest
    document's slots (two tokens a slot and the root's) and wastes under
    half of itself — ticked under the rank the CELL states
    (``rank_algo`` of the traffic file: a literal, not the program's
    rule asked again).  A program from before this entry ticked
    ``rank.ring_tokens`` says nothing of its rank: nothing to hold."""
    total = int(sum(run.ranked.values()))
    if not total:
        return 0
    chips = run.cell["chips"]
    docs = -(-len(run.docs) // chips) * chips  # the doc axis fills the mesh
    ring, rest = divmod(total, len(run.lists) * docs)
    need = 2 * (max(run.variants[v]["slots"] for v in run.docs) + 1)
    if rest or not need <= ring < max(2 * need, 130):
        return total
    return total - int(run.ranked.get(run.traffic["rank_algo"], 0))


def rank_untraced(run) -> int | None:
    """In a traced run whose device left a record: 1 unless an operation
    named as the cell's rank is on the device (``rank_device_op``, the
    trace's own name of the XLA doubling loop) is among the ten with most
    device time.  The device's word, whatever the program ticked."""
    t = run.trace_numbers
    if not t or not t["device_ops"]:
        return None
    return int(not any(run.traffic["rank_device_op"] in name
                       for name, _s in t["device_ops"]))


def compare(run) -> dict:
    compared = reference(run)
    differing = 0
    for lists in run.lists:
        differing += abs(len(lists) - len(run.docs))
        for got, v in zip(lists, run.docs):
            # the control: the reference in the program's place with one
            # stated guarantee broken, the list as a replica reads it that
            # missed the last exchange
            if run.control:
                got = run.refs[v]["stale_values"]
            differing += got != run.refs[v]["values"]
    compared["value_lists_differing"] = [differing, 0]
    compared["rank_tokens_off"] = [rank_off(run), 0]
    untraced = rank_untraced(run)
    if untraced is not None:
        compared["rank_op_not_in_trace"] = [untraced, 0]
    return compared


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    run.fleet = None
    gc.unfreeze()
