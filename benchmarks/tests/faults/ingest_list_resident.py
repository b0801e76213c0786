"""Faults planted under the timed path of the ``ingest_list_resident``
driver (``ResidentServer("list").ingest`` with a durable directory,
``flush_durable``, ``server.batch.values()``, the WAL's own reader):
``correct`` must come out false under each.  One chip; the carried state
is the resident table, its value columns and the WAL, so the faults are a
value altered where it is read back, rounds whose payloads take the Python
walk in place of the native explode, and ``ingest_resident``'s five
(documents that never reach the state, documents in one another's slots,
a record the log loses, an acknowledgement given before the log is
synced, a moved fallback counter), planted as that driver's faults module
plants them: the driver's first ``ingest`` is set-up's native probe, its
second the warm-up round."""

from faults import ingest_resident as shared

FAULTS = ["value_altered", "half_a_round_left_out", "two_slots_swapped",
          "wal_record_dropped", "counted_before_flush",
          "fallback_counter_moved", "decoded_by_the_walk"]


def plant(monkeypatch, fault: str) -> None:
    if fault in shared.FAULTS:
        shared.plant(monkeypatch, fault)
        return
    from loro_tpu.parallel.fleet import DeviceDocBatch

    if fault == "value_altered":
        real_values = DeviceDocBatch.values
        reads = {"n": 0}

        def one_value_off(self, use_solver=False):
            out = real_values(self, use_solver)
            reads["n"] += 1
            if reads["n"] >= 2:  # the warm-up's read stays sound
                k = max(i for i, vs in enumerate(out) if vs)
                out[k] = out[k][:-1] + ["☃"]
            return out

        monkeypatch.setattr(DeviceDocBatch, "values", one_value_off)
        return
    # decoded_by_the_walk: after set-up's probe, every List payload takes
    # the Python decoder, as a program without value positions does
    walk_after_probe(monkeypatch, calls_before=1)


def walk_after_probe(monkeypatch, calls_before: int) -> None:
    """List payloads after the first ``calls_before`` appends decode in
    Python and walk (the parent's path: no native explode, no counter)."""
    from loro_tpu.codec.binary import decode_changes
    from loro_tpu.parallel.fleet import DeviceDocBatch

    real_append = DeviceDocBatch.append_payloads
    calls = {"n": 0}

    def walked(self, per_doc_payloads, cid):
        calls["n"] += 1
        if self.as_text or calls["n"] <= calls_before:
            return real_append(self, per_doc_payloads, cid)
        return self.append_changes(
            [decode_changes(p) if p else None for p in per_doc_payloads], cid)

    monkeypatch.setattr(DeviceDocBatch, "append_payloads", walked)
