"""Faults planted under the timed path of the ``import_packed`` driver
(``ops.fugue_batch.merge_text_payloads_packed``): ``correct`` must come
out false under each.  One chip and no carried state: no exchange between
chips, no step that returns its state unchanged."""

FAULTS = ["answer_altered", "half_of_the_batch_left_out",
          "fallback_counter_moved"]


def plant(monkeypatch, fault: str) -> None:
    import numpy as np

    from loro_tpu.obs import metrics as obs
    from loro_tpu.ops import fugue_batch

    real = fugue_batch.merge_text_payloads_packed

    def broken(pairs, cid, pad_c, pad_n, chunk, n_docs, budget_s=float("inf")):
        outs, done, ops, dt, nw = real(pairs, cid, pad_c, pad_n, chunk, n_docs,
                                       budget_s)
        if fault == "answer_altered":
            sums, counts = outs[-1]
            outs[-1] = (np.asarray(sums) + np.uint32(1), counts)
        elif fault == "half_of_the_batch_left_out":
            outs = outs[: max(1, len(outs) // 2)]  # launches counted, not made
        elif fault == "fallback_counter_moved":
            obs.counter("fleet.host_fallback_total").inc(where="test")
        return outs, done, ops, dt, nw

    monkeypatch.setattr(fugue_batch, "merge_text_payloads_packed", broken)
