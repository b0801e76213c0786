"""Bulk import through the flagship pipeline:
``ops.fugue_batch.merge_text_payloads_packed(pairs, cid, pad_c, pad_n,
chunk, n_docs, budget_s=--seconds)``: decode threads -> chain contraction
-> packed rows -> ``chunk``-document launches of
``chain_merge_docs_packed_checksum`` (the Pallas rank on the chip), three
chunks decoded ahead.  One call is the window: it starts no launch after
``--seconds``.  The benchmark times the call with its own clock, to the
last answer fetched, and counts the ops of the documents whose answers it
holds.  Every launch's per-document checksum and count is compared, after
the window, with the plain reference's text of the same document."""
from __future__ import annotations

import json
import time

import checks
from drivers.import_fleet import fed_documents, prepare, reference  # the same documents  # noqa: F401


def setup(run) -> None:
    import jax
    import numpy as np

    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.fugue_batch import (
        _resolve_rank_spec,
        chain_merge_docs_packed_checksum,
        merge_text_payloads_packed,
        packed_row_bytes,
    )

    p = run.traffic
    waited = fed_documents(run)
    run.cid = ContainerID.root("text", ContainerType.Text)
    # the rows' widths are the cell's, not the seed's: every seed runs the
    # same program
    run.pad_n, run.pad_c = p["pad_n"], p["pad_c"]
    for v in run.variants:
        if v["elements"] > run.pad_n or v["chains"] > run.pad_c:
            raise RuntimeError(f"a document of {v['elements']} elements in "
                               f"{v['chains']} chains does not fit the cell's rows")
    run.pairs = [(v["payload"], v["n_ops"]) for v in run.variants]
    ring = 2 * (run.pad_c + 1)
    spec = ":".join(_resolve_rank_spec(None, ring))
    if not run.rehearsal and spec != p["rank_spec"]:
        raise RuntimeError(f"the rank resolved to {spec}, not {p['rank_spec']} "
                           f"(ring of {ring} tokens)")
    # lowered, inspected and warmed exactly as the pipeline dispatches: on
    # an UNCOMMITTED array of the default device (a committed one is
    # another jit entry and would compile again, inside the window)
    zeros = jax.device_put(np.zeros(
        (p["chunk"], packed_row_bytes(run.pad_c, run.pad_n)), np.uint8))
    t0 = time.perf_counter()
    compiled = chain_merge_docs_packed_checksum.lower(
        zeros, run.pad_c, run.pad_n).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if not run.rehearsal and not has_kernel:
        raise RuntimeError("no tpu_custom_call in the compiled step: the "
                           "Pallas rank is not what this cell would time")
    jax.block_until_ready(
        chain_merge_docs_packed_checksum(zeros, run.pad_c, run.pad_n))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    merge_text_payloads_packed(  # the decode threads and the put, once
        run.pairs, run.cid, run.pad_c, run.pad_n, p["chunk"], p["chunk"])
    print(json.dumps({
        "replay_s": [v["replay_s"] for v in run.variants],
        "waited_for_documents_s": waited, "pad_n": run.pad_n, "pad_c": run.pad_c,
        "ring_tokens": ring, "rank_spec": spec, "tpu_custom_call": has_kernel,
        "compile_and_first_step_s": compile_s,
        "first_pipeline_s": time.perf_counter() - t0,
        "elements": [v["elements"] for v in run.variants],
        "chains": [v["chains"] for v in run.variants]}), flush=True)


def window(run) -> dict:
    import jax.profiler as P
    import numpy as np

    from loro_tpu.ops.fugue_batch import merge_text_payloads_packed

    p = run.traffic
    run.start_trace()
    with run.window_span(), P.TraceAnnotation("bench.call"):
        t0 = time.perf_counter()
        outs, done, _ops, _seconds, n_workers = merge_text_payloads_packed(
            run.pairs, run.cid, run.pad_c, run.pad_n, p["chunk"],
            p["documents_offered"], budget_s=run.seconds)
        run.answers = [(int(s), int(c)) for sums, counts in outs
                       for s, c in zip(np.asarray(sums), np.asarray(counts))]
        seconds = time.perf_counter() - t0
    k = len(run.variants)
    answered = len(run.answers)  # document i is variant i % k
    ops = sum(run.variants[i % k]["n_ops"] for i in range(answered))
    return {
        "attempted": len(outs), "failed": 0,
        # the traffic file names the rate: a host-bound stream and the
        # device-bound Fleet call are held to bounds of their own
        "metrics": {p.get("rate_metric", "import_ops_per_s"): ops / seconds},
        "facts": {"documents_merged": answered, "documents_reported": done,
                  "elements_merged": sum(run.variants[i % k]["elements"]
                                         for i in range(answered))},
        "log": {"launches": len(outs), "documents": answered, "ops": ops,
                "window_s": seconds, "decode_threads": n_workers,
                "ms_per_launch": 1e3 * seconds / max(1, len(outs))},
    }


def compare(run) -> dict:
    compared = reference(run)
    k = len(run.variants)
    want = [(checks.text_checksum(r["text"], run.pad_n), len(r["text"]))
            for r in run.refs]
    answers = run.answers
    if run.control:  # each text as a replica reads it that missed the last exchange
        stale = [(checks.text_checksum(r["stale_text"], run.pad_n),
                  len(r["stale_text"])) for r in run.refs]
        answers = [stale[i % k] for i in range(len(answers))]
    compared["documents_differing"] = [
        sum(1 for i, got in enumerate(answers) if got != want[i % k]), 0]
    compared["documents_missing"] = [
        abs(run.facts["documents_reported"] - len(answers)), 0]
    # the budget has to end the window, never the offer
    compared["offer_ran_out"] = [
        int(len(answers) >= run.traffic["documents_offered"]), 0]
    return compared


def counters_moved(run) -> dict:
    return checks.counters_moved()


def close(run) -> None:
    run.answers = None
