"""The plain reference of the movable-list cells: what every imported
document must read, from the edit script alone (``movable_script.py``).
Standard library only; nothing of the program is imported, no payload is
read.  The Fugue tree, its converged sequence and a replica's view of it
are ``fugue_reference.py``'s (text's reference): here its nodes are
position SLOTS.

The semantics (Kleppmann, "Moving Elements in List CRDTs", PaPoC 2020,
over Fugue; loro's ``MovableListDiffCalculator``): an item is created
with a slot and a value.  ``move(i, j)`` makes a NEW slot for the item at
``i``, placed by the Fugue rule where an insert would go so that the item
ends at ``j``; the slot it leaves stays in the sequence, empty, and
counts like a tombstone for every later placement.  ``set(i, value)``
writes the item's value.  Once all replicas have everything, an item
shows at its LAST slot and reads its LAST value: the one of the highest
``(lamport, peer id)``.  Nothing is deleted.

Lamports as the replicas count them: every op takes the next lamport of
its replica, and an exchange lifts every replica's next lamport to the
highest among them.
"""
from __future__ import annotations

import time

import fugue_reference
import movable_script


class Board(fugue_reference.Merge):
    """All replicas of one document: text's tree of nodes, as slots."""

    def __init__(self, peer_ids: list):
        super().__init__(len(peer_ids))
        self.ids = peer_ids  # sibling order and ties: by peer ID
        self.item, self.lamport = [], []  # of each slot
        self.clock = [0] * len(peer_ids)  # each replica's next lamport
        self.last_slot, self.last_value = {}, {}  # item -> (lamport, peer id, slot | value)
        self.sets = []  # (item, lamport, peer id, value) since the last exchange
        self.own = [{} for _ in peer_ids]  # each replica's own sets since then
        self.ops = 0

    def _stamp(self, peer: int) -> tuple:
        self.ops += 1
        ctr, lam = self.counter[peer], self.clock[peer]
        self.counter[peer], self.clock[peer] = ctr + 1, lam + 1
        return ctr, lam

    def _slot(self, peer: int, at: int, item: int) -> int:
        """A new slot of ``item`` at visible position ``at`` of ``peer``'s list."""
        ctr, lam = self._stamp(peer)
        node = len(self.item)
        parent, side = self.views[peer].insert(at, node)
        self.peer.append(self.ids[peer])
        self.ctr.append(ctr)
        self.parent.append(parent)
        self.side.append(side)
        self.nxt.append(-1)
        self.prv.append(-1)
        self.has_right.append(0)
        self.item.append(item)
        self.lamport.append(lam)
        return node

    def _item_at(self, peer: int, i: int) -> int:
        view = self.views[peer]
        ci, off = view._locate(i)
        return self.item[view.chunks[ci][off]]

    def push(self, peer: int) -> None:
        item = len(self.item)  # the pushes come first: a slot each
        self._slot(peer, self.views[peer].length, item)
        self.sets.append((item, self.lamport[item], self.ids[peer],
                          movable_script.created(item)))

    def move(self, peer: int, i: int, j: int) -> None:
        # the placement is the insert's at the boundary where the item
        # lands, counted in the list that still shows it at i
        item = self._item_at(peer, i)
        at = j if j < i else j + 1
        self._slot(peer, at, item)
        self.views[peer].delete(i + 1 if at <= i else i)  # the slot it leaves

    def set(self, peer: int, i: int, value: str) -> None:
        _ctr, lam = self._stamp(peer)
        item = self._item_at(peer, i)
        self.sets.append((item, lam, self.ids[peer], value))
        self.own[peer][item] = value  # a replica's newest op is its own

    def read(self, peer: int) -> list:
        """The list as replica ``peer`` reads it now."""
        view, own = self.views[peer], self.own[peer]
        return [own[it] if (it := self.item[node]) in own else self.last_value[it][2]
                for chunk in view.chunks for node in chunk]

    def exchange(self) -> None:
        for node in range(self.placed, len(self.item)):
            self._place(node)
            key = (self.lamport[node], self.peer[node], node)
            if key > self.last_slot.get(self.item[node], (-1,)):
                self.last_slot[self.item[node]] = key
        self.placed = len(self.item)
        for item, lam, peer, value in self.sets:
            if (lam, peer) > self.last_value.get(item, (-1,))[:2]:
                self.last_value[item] = (lam, peer, value)
        self.sets = []
        self.own = [{} for _ in self.ids]
        self.clock = [max(self.clock)] * len(self.ids)
        visible = self.visible()
        self.views = [fugue_reference._View(self, visible) for _ in self.ids]

    def visible(self) -> list:
        out, nxt, item, last = [], self.nxt, self.item, self.last_slot
        e = self.head
        while e >= 0:
            if last[item[e]][2] == e:
                out.append(e)
            e = nxt[e]
        return out


def replay(seed: int, c: dict, v: int) -> dict:
    """What document ``v`` must read: ``values`` after the import,
    ``stale_values`` as replica 0 reads the list just before (the
    control's answer: a replica that missed the last exchange), and the
    document's own counts (``n_ops`` = items + recorded moves + sets)."""
    t0 = time.perf_counter()
    b = Board(c["peer_ids"])
    for _ in range(c["items"]):
        b.push(0)
    b.exchange()
    moves = sets = 0
    for k, peer, i, j in movable_script.routed_draws(seed, c, v):
        if peer == movable_script.EXCHANGE:
            b.exchange()
        elif j == movable_script.SET:
            b.set(peer, i, movable_script.edited(k))
            sets += 1
        else:
            b.move(peer, i, j)
            moves += 1
    stale = b.read(0)
    b.exchange()
    return {"values": b.read(0), "stale_values": stale, "moves": moves,
            "sets": sets, "n_ops": b.ops, "slots": len(b.item),
            "reference_s": time.perf_counter() - t0}
