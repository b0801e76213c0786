"""The generator of the fed data of the movable-list cells: the seeded
edit script (``movable_script.py``) typed into the program's own replicas,
whose full-history update payload is what the cells feed to the chip.
Only the payload and its sizes are taken from here; what a document must
READ comes from the plain reference (``movable_reference.py``).

Replica 0 pushes the items, the others import them, the draws go to the
replica the script routes them to, and at every exchange of the script
replica 0 learns everything and hands it on (text's routing,
``gen.make_payload``).  Replica 0's export after the last exchange is the
fed payload.

Host-only Python; module-level functions for the worker processes.
"""
from __future__ import annotations

import time

import movable_script

CONTAINER = "board"


def make_payload(seed: int, c: dict, v: int) -> dict:
    """Variant ``v`` as one concurrent document: the full-history update
    ``payload`` (envelope stripped), the ops it holds (``n_ops`` = items +
    recorded moves + sets) and the slot rows, set rows and items the
    program extracts from it."""
    from loro_tpu import LoroDoc
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.doc import strip_envelope
    from loro_tpu.ops.movable_batch import extract_movable_from_payload

    t0 = time.perf_counter()
    docs = [LoroDoc(peer=p) for p in c["peer_ids"]]
    lists = [d.get_movable_list(CONTAINER) for d in docs]

    def gather():  # replica 0 learns everything
        for d in docs[1:]:
            docs[0].import_(d.export_updates(docs[0].oplog_vv()))

    def exchange():
        gather()
        for d in docs[1:]:
            d.import_(docs[0].export_updates(d.oplog_vv()))

    for n in range(c["items"]):
        lists[0].push(movable_script.created(n))
    exchange()
    for k, peer, i, j in movable_script.routed_draws(seed, c, v):
        if peer == movable_script.EXCHANGE:
            exchange()
        elif j == movable_script.SET:
            lists[peer].set(i, movable_script.edited(k))
        else:
            lists[peer].move(i, j)
    gather()
    payload = strip_envelope(docs[0].export_updates())
    cid = ContainerID.root(CONTAINER, ContainerType.MovableList)
    cols, items, _values = extract_movable_from_payload(payload, cid)
    slots, set_rows = int(cols.seq.parent.shape[0]), int(cols.set_elem.shape[0])
    return {"payload": payload, "slots": slots, "set_rows": set_rows,
            "items": len(items), "n_ops": slots + set_rows - len(items),
            "replay_s": time.perf_counter() - t0}
