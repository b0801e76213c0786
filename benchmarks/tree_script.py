"""The seeded move script of one movable-tree import document: upstream's
``benches/tree.rs`` case "10^3 tree move 10^5" routed over concurrent
replicas.  Plain Python over the standard library: both the generator of
the fed data (``tree_gen.make_payload``, which types the script into the
program's replicas) and the plain reference (``tree_reference.replay``)
read the script from here and share nothing else.

Shapes come from the configuration file (``configs/tree_import.json``):
``nodes`` creates under the root, then ``move_draws`` draws ``(i, j)``,
both uniform in ``0..nodes``: move node ``i`` under node ``j``.  A draw is
routed to one of ``peers_per_document`` replicas in windows of
``peer_window`` draws.  All replicas hold the creates and then never
exchange: each keeps its own parent array, and a draw that is cyclic
where it is made (``j`` inside ``i``'s subtree there, ``i == j`` too) is
dropped with no op, as upstream drops it (``unwrap_or_default()`` on
``CyclicMoveError``).
"""
from __future__ import annotations

import random

ROOT = -1


def routed_moves(seed: int, c: dict, v: int) -> list:
    """Variant ``v`` of the script: the kept moves ``(peer, i, j)`` in the
    order they were drawn (a replica's own moves keep their order)."""
    n, peers = c["nodes"], c["peers_per_document"]
    rng = random.Random(seed * 1_000_003 + 0x7EE + v)
    lo, hi = c["peer_window"]
    views = [[ROOT] * n for _ in range(peers)]
    out, cur, left = [], 0, 0
    for _ in range(c["move_draws"]):
        if left == 0:
            cur = rng.randrange(peers)
            left = rng.randint(lo, hi)
        left -= 1
        i, j = rng.randrange(n), rng.randrange(n)
        parent = views[cur]
        at = j
        while at != ROOT and at != i:  # is i on j's way to the root?
            at = parent[at]
        if at == i:
            continue  # cyclic where it is made: no op
        parent[i] = j
        out.append((cur, i, j))
    return out
