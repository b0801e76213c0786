"""Faults planted under the timed path of the ``import_movable`` driver
(``Fleet.merge_movable_payloads``): ``correct`` must come out false under
each.  One chip and no carried state: no exchange between chips, no step
that returns its state unchanged."""

FAULTS = ["answer_altered", "half_of_the_batch_left_out",
          "fallback_counter_moved", "set_fold_ignored", "ranked_by_another"]


def plant(monkeypatch, fault: str) -> None:
    from loro_tpu.obs import metrics as obs
    from loro_tpu.ops import movable_batch
    from loro_tpu.parallel import fleet
    from loro_tpu.parallel.fleet import Fleet

    if fault == "ranked_by_another":
        # the rings are ticked under a rank the cell does not state: what
        # a rule that sent them to another kernel would say
        def ticked_as_pallas(n_docs, n_nodes):
            obs.counter("rank.ring_tokens").inc(
                n_docs * 2 * (n_nodes + 1), algo="pallas:ruling")

        monkeypatch.setattr(fleet, "_tick_rank_obs", ticked_as_pallas)
        return
    if fault == "set_fold_ignored":
        # the last-set fold left out: every set row but an element's
        # earliest, its creation value, is fed to the launch as invalid
        import numpy as np

        real_extract = movable_batch.extract_movable_from_payload

        def creation_values_only(payload, cid):
            cols, elems, values = real_extract(payload, cid)
            order = np.lexsort((cols.set_lamport, cols.set_elem))
            elem = cols.set_elem[order]
            valid = np.zeros_like(cols.set_valid)
            valid[order[np.r_[True, elem[1:] != elem[:-1]]]] = True
            return cols._replace(set_valid=valid), elems, values

        monkeypatch.setattr(movable_batch, "extract_movable_from_payload",
                            creation_values_only)
        return
    real = Fleet.merge_movable_payloads
    calls = {"n": 0}

    def broken(self, payloads, cid):
        calls["n"] += 1
        if fault == "half_of_the_batch_left_out":
            return real(self, payloads[: len(payloads) // 2], cid)
        out = real(self, payloads, cid)
        if calls["n"] < 2:  # the warm-up call stays sound
            return out
        if fault == "answer_altered":
            out[-1][0], out[-1][1] = out[-1][1], out[-1][0]  # two items swapped
        elif fault == "fallback_counter_moved":
            obs.counter("fleet.host_fallback_total").inc(where="test")
        return out

    monkeypatch.setattr(Fleet, "merge_movable_payloads", broken)
