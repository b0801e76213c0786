"""Faults planted under the timed path of the ``ingest_tree_resident``
driver (``ResidentServer("tree").ingest`` with a durable directory,
``flush_durable``, the settled standing state, the WAL's own reader):
``correct`` must come out false under each.  One chip; the carried state is
the resident log, its standing answer and the WAL, so the faults are a
standing parent altered on the device, a named document its commit leaves
unsettled, and ``ingest_resident``'s five (documents that never reach the
state, documents in one another's slots, a record the log loses, an
acknowledgement given before the log is synced, a moved fallback counter),
planted as that driver's faults module plants them."""

from faults import ingest_resident as shared

FAULTS = ["parent_altered", "half_a_round_left_out",
          "named_document_unsettled", "two_slots_swapped",
          "wal_record_dropped", "counted_before_flush",
          "fallback_counter_moved"]


def plant(monkeypatch, fault: str) -> None:
    if fault in shared.FAULTS:
        shared.plant(monkeypatch, fault)
        return
    import jax
    import numpy as np

    from loro_tpu.ops.tree_batch import ROOT
    from loro_tpu.parallel.fleet import DeviceTreeBatch

    real_settle = DeviceTreeBatch._settle
    calls = {"n": 0}

    def broken(self, named):
        calls["n"] += 1
        named = list(named)
        if calls["n"] < 2 or not named:  # the warm-up round stays sound
            return real_settle(self, named)
        if fault == "named_document_unsettled":
            return real_settle(self, named[:-1])
        real_settle(self, named)
        # parent_altered: node 0 of the last named document elsewhere
        parents = np.asarray(self.parents).copy()
        di = named[-1]
        parents[di, 0] = 1 if parents[di, 0] == ROOT else ROOT
        self.parents = jax.device_put(parents, self.parents.sharding)

    monkeypatch.setattr(DeviceTreeBatch, "_settle", broken)
