"""Faults planted under the timed path of the ``ingest_resident`` driver
(``ResidentServer.ingest`` with a durable directory, ``flush_durable``,
``texts()``, the WAL's own reader): ``correct`` must come out false under
each.  One chip; the carried state is the resident table and the log, so
the faults are an answer altered where it is read back, documents that
never reach the state, documents in one another's slots, a record the log
loses, an acknowledgement given before the log is synced, and a moved
fallback counter."""

FAULTS = ["text_altered", "half_a_round_left_out", "two_slots_swapped",
          "wal_record_dropped", "counted_before_flush",
          "fallback_counter_moved"]


def plant(monkeypatch, fault: str) -> None:
    from loro_tpu.obs import metrics as obs
    from loro_tpu.parallel.server import ResidentServer
    from loro_tpu.persist.wal import R_ROUND, WriteAheadLog

    if fault == "text_altered":
        real_texts = ResidentServer.texts
        reads = {"n": 0}

        def one_character_off(self):
            out = real_texts(self)
            reads["n"] += 1
            if reads["n"] >= 2:  # the warm-up's read stays sound
                k = max(i for i, t in enumerate(out) if t)
                out[k] = out[k][:-1] + "☃"
            return out

        monkeypatch.setattr(ResidentServer, "texts", one_character_off)
        return
    if fault == "wal_record_dropped":
        real_records = WriteAheadLog.records

        def one_round_lost(self):
            rounds = 0
            for rec in real_records(self):
                rounds += rec.rtype == R_ROUND
                if rec.rtype == R_ROUND and rounds == 2:
                    continue
                yield rec

        monkeypatch.setattr(WriteAheadLog, "records", one_round_lost)
        return
    if fault == "counted_before_flush":
        # the group fsync never runs, so no round is ever durable when the
        # driver counts it (the default window of 8 rounds is not reached)
        monkeypatch.setattr(ResidentServer, "flush_durable", lambda self: 0)
        return
    real_ingest = ResidentServer.ingest
    calls = {"n": 0}

    def broken(self, per_doc_updates, cid=None):
        calls["n"] += 1
        ups = list(per_doc_updates)
        given = [k for k, u in enumerate(ups) if u is not None]
        if calls["n"] >= 2:  # the warm-up round stays sound
            if fault == "half_a_round_left_out":
                for k in given[len(given) // 2:]:
                    ups[k] = None
            elif fault == "two_slots_swapped":
                a, b = given[0], given[1]  # neighbours: two variants
                ups[a], ups[b] = ups[b], ups[a]
            elif fault == "fallback_counter_moved":
                obs.counter("fleet.host_fallback_total").inc(where="test")
        return real_ingest(self, ups, cid)

    monkeypatch.setattr(ResidentServer, "ingest", broken)
