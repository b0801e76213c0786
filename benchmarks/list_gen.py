"""The generator of the fed data of the List cells: the seeded edit
script (``script.py``, text's) typed into the program's own replicas as
a ``LoroList`` of one-character string values, whose full-history update
payload is what the cells feed to the chip.  Only the payload and its
sizes are taken from here; what a document must READ comes from the
plain reference (``list_reference.py``), which reads the same script
and nothing else.

Host-only Python; module-level functions for the worker processes.
"""
from __future__ import annotations

import time

import script

CONTAINER = "list"


def make_payload(seed: int, c: dict, v: int) -> dict:
    """Variant ``v`` of the seeded script as one concurrent document of
    ``peers_per_document`` replicas, as ``gen.make_payload`` types text:
    the full-history update ``payload`` (envelope stripped), the ops
    applied, and the element / chain counts the program extracts."""
    from loro_tpu import LoroDoc
    from loro_tpu.doc import strip_envelope
    from loro_tpu.ops.columnar import contract_chains, extract_seq_container

    t0 = time.perf_counter()
    docs = [LoroDoc(peer=((v + 1) << 8) + i + 1)
            for i in range(c["peers_per_document"])]
    lists = [d.get_list(CONTAINER) for d in docs]

    def gather():  # replica 0 learns everything
        for d in docs[1:]:
            docs[0].import_(d.export_updates(docs[0].oplog_vv()))

    every = c["sync_every_patches"]
    patches = script.routed_patches(seed, c, v)
    for i, (peer, pos, ch) in enumerate(patches):
        lst = lists[peer]
        n = len(lst)
        if ch:
            lst.insert(min(pos, n), ch)
        else:
            lst.delete(min(pos, n - 1), 1)
        if (i + 1) % every == 0:
            gather()
            for d in docs[1:]:
                d.import_(docs[0].export_updates(d.oplog_vv()))
    gather()
    ex = extract_seq_container(docs[0].oplog.changes_in_causal_order(), lists[0].id)
    return {"payload": strip_envelope(docs[0].export_updates()),
            "n_ops": len(patches), "elements": int(ex.n),
            "chains": int(contract_chains(ex).n_chains),
            "replay_s": time.perf_counter() - t0}
