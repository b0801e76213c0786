"""Faults planted under the timed path of the ``import_fleet`` driver
(``Fleet.merge_text_payloads``): ``correct`` must come out false under
each.  One chip and no carried state: no exchange between chips, no step
that returns its state unchanged."""

FAULTS = ["answer_altered", "half_of_the_batch_left_out",
          "fallback_counter_moved"]


def plant(monkeypatch, fault: str) -> None:
    from loro_tpu.obs import metrics as obs
    from loro_tpu.parallel.fleet import Fleet

    real = Fleet.merge_text_payloads
    calls = {"n": 0}

    def broken(self, payloads, cid):
        calls["n"] += 1
        if fault == "half_of_the_batch_left_out":
            return real(self, payloads[: len(payloads) // 2], cid)
        out = real(self, payloads, cid)
        if calls["n"] < 2:  # the warm-up call stays sound
            return out
        if fault == "answer_altered":
            out.texts[-1] = out.texts[-1][:-1] + "☃"
        elif fault == "fallback_counter_moved":
            obs.counter("fleet.host_fallback_total").inc(where="test")
        return out

    monkeypatch.setattr(Fleet, "merge_text_payloads", broken)
