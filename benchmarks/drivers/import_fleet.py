"""Bulk import through the library's public entry:
``Fleet(mesh).merge_text_payloads(payloads, cid)`` on ``docs_per_call``
full-history payloads, called back to back for the window.  No new call
starts after ``--seconds``; the rate is every op of the calls that
completed over the time from the window's start to the end of the last.
Every text of every call is compared, after the window, with the plain
reference's (``fugue_reference.py``) reading of the same edit script."""
from __future__ import annotations

import json
import time

import checks
import fugue_reference
import gen


def prepare(run) -> None:
    run.refs = None
    run.variant_jobs = [
        run.pool.apply_async(gen.make_payload, (run.seed, run.config, v))
        for v in range(run.config["fleet_documents"])]


def fed_documents(run) -> float:
    """Wait for the fed documents and hold them to the configuration's
    shape; seconds waited."""
    c = run.config
    t0 = time.perf_counter()
    run.variants = [j.get() for j in run.variant_jobs]
    lo, hi = (c["chains_after_contraction"] * (1 + s * c["chains_tolerance"])
              for s in (-1, 1))
    for v in run.variants:
        if v["elements"] != c["insert_patches"] or not lo <= v["chains"] <= hi:
            raise RuntimeError(
                f"a fed document has {v['elements']} elements in {v['chains']} "
                f"chains; the configuration states {c['insert_patches']} in "
                f"{c['chains_after_contraction']} +- {c['chains_tolerance']:.0%}")
    return time.perf_counter() - t0


def setup(run) -> None:
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.parallel.fleet import Fleet

    waited = fed_documents(run)
    n = run.traffic["docs_per_call"]
    k = len(run.variants)
    run.docs = [i % k for i in range(n)]  # the variant each document is
    run.payloads = [run.variants[v]["payload"] for v in run.docs]
    run.cid = ContainerID.root("text", ContainerType.Text)
    run.fleet = Fleet(run.mesh)
    t0 = time.perf_counter()
    call_entry(run)  # compiles (or fetches) the one launch of this shape
    first = time.perf_counter() - t0
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "waited_for_documents_s": waited, "first_call_s": first,
        "elements": [v["elements"] for v in run.variants],
        "chains": [v["chains"] for v in run.variants]}), flush=True)


def call_entry(run) -> list:
    """The timed path: one call of the public entry, texts out."""
    return run.fleet.merge_text_payloads(run.payloads, run.cid).texts


def window(run) -> dict:
    import jax.profiler as P

    ops_per_call = sum(run.variants[v]["n_ops"] for v in run.docs)
    elements_per_call = sum(run.variants[v]["elements"] for v in run.docs)
    run.answers, call_s = [], []
    run.start_trace()
    with run.window_span():
        t0 = last = time.perf_counter()
        while last - t0 < run.seconds:
            with P.TraceAnnotation("bench.call"):
                run.answers.append(call_entry(run))
            now = time.perf_counter()
            call_s.append(now - last)
            last = now
    calls = len(run.answers)
    return {
        "attempted": calls, "failed": 0,
        "metrics": {"import_ops_per_s": calls * ops_per_call / (last - t0)},
        "facts": {"documents_merged": calls * len(run.docs),
                  "elements_merged": calls * elements_per_call},
        "log": {"calls": calls, "call_s": call_s[:64], "window_s": last - t0,
                "ops_per_call": ops_per_call},
    }


def reference(run) -> dict:
    """The plain reference's reading of every fed document, in the worker
    processes, once the window has closed; and whether the documents it
    read have the configuration's shape."""
    c = run.config
    if run.refs is None:
        t0 = time.perf_counter()
        run.refs = run.pool.starmap(
            fugue_reference.replay,
            [(run.seed, c, v) for v in range(c["fleet_documents"])])
        print(json.dumps({
            "reference_s": time.perf_counter() - t0,
            "reference_chains": [r["chains"] for r in run.refs],
            "reference_text_chars": [len(r["text"]) for r in run.refs]}), flush=True)
    off = max(abs(r["chains"] / c["chains_after_contraction"] - 1) for r in run.refs)
    return {
        "reference_patches_off": [sum(
            abs(r["inserts"] - c["insert_patches"])
            + abs(r["deletes"] - c["delete_patches"]) for r in run.refs), 0],
        "reference_chains_off": [off, c["chains_tolerance"]]}


def compare(run) -> dict:
    compared = reference(run)
    # the control: the reference in the program's place with one stated
    # guarantee broken, each text as a replica reads it that missed the
    # last exchange
    answers = run.answers
    if run.control:
        answers = [[run.refs[v]["stale_text"] for v in run.docs] for _ in answers]
    compared["texts_differing"] = [sum(
        abs(len(texts) - len(run.docs))
        + sum(1 for t, v in zip(texts, run.docs) if t != run.refs[v]["text"])
        for texts in answers), 0]
    return compared


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    run.fleet = None
