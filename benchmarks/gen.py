"""The generator of the fed data: the seeded edit script (``script.py``)
typed into the program's own replicas, whose full-history update payload
is what the import cells feed to the chip.  The payload format is the
program's, so only the program can write it; what the documents must
READ is not taken from here but from the plain reference
(``fugue_reference.py``), which reads the same script and nothing else.

Host-only Python; module-level functions, so that worker processes
(started before the parent touches JAX, pinned to the CPU) can run them.
Nothing is cached per seed: every run does the same work from its seed.
"""
from __future__ import annotations

import os
import time

import script

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(HERE, "cache")


def pin_worker_to_cpu() -> None:
    """Pool initializer: a worker never touches the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def make_payload(seed: int, c: dict, v: int) -> dict:
    """Variant ``v`` of the seeded script as one concurrent document of
    ``peers_per_document`` replicas: the full-history update ``payload``
    (envelope stripped), the ops applied, and the element / chain counts
    the program extracts from it (the callers of the packed entry must
    size its rows by them)."""
    from loro_tpu import LoroDoc
    from loro_tpu.doc import strip_envelope
    from loro_tpu.ops.columnar import contract_chains, extract_seq_container

    t0 = time.perf_counter()
    docs = [LoroDoc(peer=((v + 1) << 8) + i + 1)
            for i in range(c["peers_per_document"])]
    texts = [d.get_text("text") for d in docs]

    def gather():  # replica 0 learns everything
        for d in docs[1:]:
            docs[0].import_(d.export_updates(docs[0].oplog_vv()))

    every = c["sync_every_patches"]
    patches = script.routed_patches(seed, c, v)
    for i, (peer, pos, ch) in enumerate(patches):
        t = texts[peer]
        if ch:
            t.insert(min(pos, len(t)), ch)
        else:
            t.delete(min(pos, len(t) - 1), 1)
        if (i + 1) % every == 0:
            gather()
            for d in docs[1:]:
                d.import_(docs[0].export_updates(d.oplog_vv()))
    gather()
    ex = extract_seq_container(docs[0].oplog.changes_in_causal_order(), texts[0].id)
    return {"payload": strip_envelope(docs[0].export_updates()),
            "n_ops": len(patches), "elements": int(ex.n),
            "chains": int(contract_chains(ex).n_chains),
            "replay_s": time.perf_counter() - t0}
