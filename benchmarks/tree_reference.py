"""The plain reference of the movable-tree cells: what every imported
document must read, from the move script alone (``tree_script.py``).
Standard library only; nothing of the program is imported, no payload is
read.

The semantics (Kleppmann et al., "A highly-available move operation for
replicated trees"; loro's ``diff_calc/tree.rs``): all ops of all replicas
apply in one total order, (lamport, peer); a move whose new parent lies
in the target's subtree AT THAT MOMENT is refused and leaves the tree as
it is.  A move that was sound where it was made can be refused here.

Lamports as the replicas count them: replica 0 makes the ``nodes``
creates (lamport 0..nodes-1), every replica imports them before its first
move, so a replica's k-th own move has lamport ``nodes + k``.  Ties are
broken by the configuration's ``peer_ids``.
"""
from __future__ import annotations

import tree_script

ROOT = tree_script.ROOT


def ordered_moves(seed: int, c: dict, v: int, without_peer=None) -> list:
    """``(i, j)`` of every kept move in (lamport, peer) order."""
    count = [0] * c["peers_per_document"]
    keyed = []
    for peer, i, j in tree_script.routed_moves(seed, c, v):
        if peer != without_peer:
            keyed.append((count[peer], c["peer_ids"][peer], i, j))
        count[peer] += 1
    keyed.sort()
    return [(i, j) for _lamport, _peer, i, j in keyed]


def apply_moves(n: int, moves: list, walks=None) -> tuple:
    """Replay ``moves`` over ``n`` nodes created under the root: the parent
    of every node by create index, the moves refused as cycles, and the
    parent reads the cycle checks took (``walks``: each move's own)."""
    parent = [ROOT] * n
    refused = reads = 0
    for i, j in moves:
        at, steps = j, 0
        while at != ROOT and at != i:
            at = parent[at]
            steps += 1
        reads += steps
        if walks is not None:
            walks.append(steps)
        if at == i:
            refused += 1
        else:
            parent[i] = j
    return parent, refused, reads


def replay(seed: int, c: dict, v: int, with_walks: bool = False) -> dict:
    """What document ``v`` must read: ``parents`` (by create index, -1 =
    under the root), ``refused``, the ops the fed payload must hold
    (``n_ops`` = creates + kept moves), the control's ``stale_parents``
    (the tree as a replica reads it that missed the last replica's
    moves), and the mean parent reads of a cycle check."""
    n = c["nodes"]
    moves = ordered_moves(seed, c, v)
    walks = [] if with_walks else None
    parents, refused, reads = apply_moves(n, moves, walks)
    last = c["peers_per_document"] - 1
    stale, _r, _s = apply_moves(n, ordered_moves(seed, c, v, without_peer=last))
    out = {"parents": parents, "refused": refused, "moves": len(moves),
           "n_ops": n + len(moves), "stale_parents": stale,
           "reads_per_move": reads / max(len(moves), 1)}
    if with_walks:
        out["walks"] = walks
    return out


def lockstep_factor(walks_per_doc: list) -> float:
    """Parent reads a move when documents replay side by side, one move
    each at a time: the slowest document paces every step."""
    steps = max(len(w) for w in walks_per_doc)
    total = sum(max(w[k] for w in walks_per_doc if k < len(w))
                for k in range(steps))
    return total / max(steps, 1)
