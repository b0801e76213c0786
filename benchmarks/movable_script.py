"""The seeded edit script of one movable-list import document: a
long-lived board — few items, each re-ordered and edited many times —
routed over concurrent replicas.  Plain Python over the standard library:
both the generator of the fed data (``movable_gen.make_payload``, which
types the script into the program's replicas) and the plain reference
(``movable_reference.replay``) read the script from here and share
nothing else.

Shapes come from the configuration file (``configs/movable_import.json``):
replica 0 pushes ``items`` items, every replica imports them, then
``draws`` draws follow, each with probability ``set_share`` a
``set(i, value)`` and else a ``move(i, j)``, ``i`` and ``j`` uniform over
the replica's own list (nothing is inserted or deleted after the pushes,
so every replica's list holds ``items`` items throughout).  A draw is
routed to one of ``peers_per_document`` replicas in windows of
``peer_window`` draws; all replicas exchange everything after every
``sync_every_draws`` draws (0: never), kept or not, but not after the
last: what follows that one is the import, and the control reads a replica
that has not seen it.  A ``move(i, i)`` leaves no op, as the handler
records none.
"""
from __future__ import annotations

import random

SET = -1  # the ``j`` of a draw that is a set
EXCHANGE = -1  # the ``peer`` of a row that is no draw: all replicas exchange


def created(n: int) -> str:
    """The value item ``n`` is pushed with."""
    return f"item {n}"


def edited(k: int) -> str:
    """The value draw ``k`` sets."""
    return f"edit {k}"


def routed_draws(seed: int, c: dict, v: int) -> list:
    """Variant ``v`` of the script: ``(k, peer, i, j)`` of every draw that
    leaves an op, ``k`` its index among all draws, ``j == SET`` for a set of
    the item at ``i`` to ``edited(k)``, else the move of the item at ``i``
    to position ``j``; and, before draw ``k``, a row ``(k, EXCHANGE, 0, 0)``
    where all replicas exchange everything."""
    n, peers, every = c["items"], c["peers_per_document"], c["sync_every_draws"]
    rng = random.Random(seed * 1_000_003 + 0x30FE + v)
    lo, hi = c["peer_window"]
    out, cur, left = [], 0, 0
    for k in range(c["draws"]):
        if every and k and k % every == 0:
            out.append((k, EXCHANGE, 0, 0))
        if left == 0:
            cur = rng.randrange(peers)
            left = rng.randint(lo, hi)
        left -= 1
        if rng.random() < c["set_share"]:
            out.append((k, cur, rng.randrange(n), SET))
            continue
        i, j = rng.randrange(n), rng.randrange(n)
        if i != j:
            out.append((k, cur, i, j))
    return out
