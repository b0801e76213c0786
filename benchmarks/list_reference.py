"""The plain reference of the List cells.  It imports nothing of the
program and takes nothing the program made: it reads the seeded edit
script (``script.py``) and answers what every replica's ``LoroList`` must
read once all have exchanged everything.

Loro's List and Text place an element by the same Fugue rule, so the
merge is ``fugue_reference.Merge``'s, unchanged: each of its nodes carries
what was inserted there, which for a List is the VALUE — here the
one-character string the script types.  A list reads its visible nodes'
values in order.
"""
from __future__ import annotations

import time

import fugue_reference
import script


def replay(seed: int, c: dict, v: int) -> dict:
    """What variant ``v``'s list must read: ``values`` after the last
    exchange, ``stale_values`` as replica 0 reads them just before (the
    control's answer), and the counts of the document's own shape."""
    t0 = time.perf_counter()
    m = fugue_reference.Merge(c["peers_per_document"])
    every = c["sync_every_patches"]
    inserts = deletes = 0
    for i, (peer, pos, value) in enumerate(script.routed_patches(seed, c, v)):
        m.apply(peer, pos, value)
        inserts += bool(value)
        deletes += not value
        if (i + 1) % every == 0:
            m.exchange()
    value_of = m.ch  # node -> the value inserted there
    stale = [value_of[e] for chunk in m.views[0].chunks for e in chunk]
    m.exchange()
    return {"values": [value_of[e] for e in m.visible()], "stale_values": stale,
            "inserts": inserts, "deletes": deletes, "chains": m.chains(),
            "reference_s": time.perf_counter() - t0}
