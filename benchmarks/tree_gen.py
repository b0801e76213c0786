"""The generator of the fed data of the movable-tree cells: the seeded
move script (``tree_script.py``) typed into the program's own replicas,
whose full-history update payload is what the cells feed to the chip.
Only the payload and its sizes are taken from here; what a document must
READ comes from the plain reference (``tree_reference.py``).

Replica 0 creates the nodes, the others import them, each makes its own
moves with NO exchange until the end (the host engine's attached import
of concurrent tree moves is worse than quadratic: 4,000 moves 91 s,
PERF.md), and a ``detach()``ed collector imports all of them and exports
the whole history.  The script has already dropped every draw that is
cyclic where it is made, so ``TreeHandler.move`` is never asked for one.

Host-only Python; module-level functions for the worker processes.
"""
from __future__ import annotations

import time

import tree_script

CONTAINER = "tree"


def make_payload(seed: int, c: dict, v: int) -> dict:
    """Variant ``v`` as one concurrent document: the full-history update
    ``payload`` (envelope stripped), the ops it holds and the nodes and
    moves the program extracts from it."""
    from loro_tpu import LoroDoc
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.doc import strip_envelope
    from loro_tpu.ops.tree_batch import extract_tree_from_payload

    t0 = time.perf_counter()
    docs = [LoroDoc(peer=p) for p in c["peer_ids"]]
    trees = [d.get_tree(CONTAINER) for d in docs]
    ids = [trees[0].create() for _ in range(c["nodes"])]
    creates = docs[0].export_updates()
    for d in docs[1:]:
        d.import_(creates)
    for peer, i, j in tree_script.routed_moves(seed, c, v):
        trees[peer].move(ids[i], ids[j])
    collector = LoroDoc(peer=max(c["peer_ids"]) + 1)
    collector.detach()
    for d in docs:
        collector.import_(d.export_updates())
    payload = strip_envelope(collector.export_updates())
    cid = ContainerID.root(CONTAINER, ContainerType.Tree)
    cols, nodes, _pos = extract_tree_from_payload(payload, cid)
    return {"payload": payload, "n_ops": int(cols.target.shape[0]),
            "nodes": len(nodes), "replay_s": time.perf_counter() - t0}
