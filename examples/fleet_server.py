"""Server-side fleet reconciliation: the TPU-native path.

A sync server holds many documents; each round, clients send update
payloads; the whole fleet merges in batched XLA launches (docs axis
sharded over the device mesh).  Runs on whatever JAX gives it — the
chip where there is one.  On the CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/fleet_server.py
"""
import os, sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import random
import time

import loro_tpu as lt
from loro_tpu.config import configure_compile_cache
from loro_tpu.parallel.fleet import DeviceDocBatch, Fleet
from loro_tpu.parallel.mesh import make_mesh


def main() -> None:
    configure_compile_cache()
    rng = random.Random(0)
    n_docs = 24
    mesh = make_mesh()
    print(f"mesh: {mesh}")

    # client replicas (host engine) — the server only sees their payloads
    docs = [lt.LoroDoc(peer=i + 1) for i in range(n_docs)]
    cid = docs[0].get_text("doc").id
    batch = DeviceDocBatch(n_docs=n_docs, capacity=4096, mesh=mesh)
    marks = [d.oplog_vv() for d in docs]

    for round_no in range(4):
        # clients edit offline...
        for d in docs:
            t = d.get_text("doc")
            for _ in range(rng.randint(1, 20)):
                if len(t) and rng.random() < 0.3:
                    pos = rng.randint(0, len(t) - 1)
                    t.delete(pos, min(2, len(t) - pos))
                else:
                    t.insert(rng.randint(0, len(t)), rng.choice(["go ", "tpu ", "crdt "]))
            d.commit()
        # ...and sync: the server ingests every doc's delta in one batch
        updates = []
        for i, d in enumerate(docs):
            updates.append(d.oplog.changes_between(marks[i], d.oplog_vv()))
            marks[i] = d.oplog_vv()
        t0 = time.perf_counter()
        batch.append_changes(updates, cid)
        texts = batch.texts()
        dt = time.perf_counter() - t0
        ok = texts == [d.get_text("doc").to_string() for d in docs]
        print(f"round {round_no}: merged {n_docs} docs in {dt*1000:.0f} ms "
              f"({'consistent' if ok else 'DIVERGED'}) e.g. {texts[0][:30]!r}")
    # each round above placed only the DELTA rows (host ShadowOrder,
    # O(delta)) and materialized with one multi-key device sort — no
    # per-round re-rank of the standing table
    print(f"order renumbers across all rounds: "
          f"{sum(b.renumbers for b in batch.order)}")

    # very large imports can also shard the OP axis (sp) over a 2D mesh:
    # per-shard scatter-max partials combine with pmax collectives
    from loro_tpu.ops.columnar import extract_map_ops

    fleet2d = Fleet(make_mesh(op_parallel=2))
    for d in docs:
        m = d.get_map("meta")
        for k in "abc":
            m.set(k, f"{d.peer}:{k}")
        d.commit()
    extracts = [extract_map_ops(d.oplog.changes_in_causal_order()) for d in docs]
    wins = fleet2d.merge_map_docs_sharded(extracts)
    ok = all(wins[i] == d.get_map("meta").get_value() for i, d in enumerate(docs))
    print(f"sharded (docs x ops) LWW merge of {n_docs} docs: "
          f"{'consistent' if ok else 'DIVERGED'}")

    # long-lived server lifecycle: auto_grow repacks past the initial
    # capacity bucket, and once every client has acked an ingest epoch
    # the server reclaims causally-stable tombstones in place
    batch.auto_grow = True
    stable = batch.epoch  # every round above was fully synced
    reclaimed = batch.compact([stable] * batch.d)
    ok = batch.texts() == [d.get_text("doc").to_string() for d in docs]
    print(f"compaction: reclaimed {reclaimed} tombstone rows "
          f"({'consistent' if ok else 'DIVERGED'})")

    # server restart: the resident state checkpoints through the LTKV
    # store and the restored batch keeps serving appends + rich reads
    blob = batch.export_state()
    restored = DeviceDocBatch.import_state(blob, mesh=mesh)
    for d in docs:
        d.get_text("doc").insert(0, "post-restart ")
        d.commit()
    updates = []
    for i, d in enumerate(docs):
        updates.append(d.oplog.changes_between(marks[i], d.oplog_vv()))
        marks[i] = d.oplog_vv()
    restored.append_changes(updates, cid)
    ok = restored.texts() == [d.get_text("doc").to_string() for d in docs]
    print(f"checkpoint/restore: {len(blob)} bytes LTKV; restored server "
          f"{'consistent' if ok else 'DIVERGED'} after new appends")


if __name__ == "__main__":
    main()
