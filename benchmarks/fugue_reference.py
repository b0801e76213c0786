"""The plain reference of the import cells: a Fugue text merge in plain
Python.  It imports nothing of the program and takes nothing the program
made: it reads the seeded edit script (``script.py``) and answers what
every replica must read once all have exchanged everything.

Semantics (Weidner & Kleppmann, "The Art of the Fugue"; the placement
rule as Loro ships it): every character is a node of one tree.  A replica
that inserts after the visible character ``a`` makes the new node the
RIGHT child of ``a`` when ``a`` has no right child yet in its own view,
else the LEFT child of ``a``'s immediate successor (tombstones count);
an insert at the very start is the left child of the first node (or a
child of the root in an empty text).  Siblings on one side order by
``(peer, counter)``; the text is the in-order walk (left children, node,
right children) without the deleted nodes.  A delete tombstones the node.

Between two exchanges a replica sees the converged text of the last
exchange plus its own edits, so a view is kept as an overlay on the
converged sequence; an exchange places the new nodes of all replicas in
the converged sequence by the sibling rule.
"""
from __future__ import annotations

import time

import script

_CHUNK = 512
_RIGHT, _LEFT = 1, 0


class _View:
    """One replica between two exchanges: the converged sequence (the
    merge's ``nxt``) under an overlay of its own inserts, and its visible
    nodes in chunks (position -> node without a scan of the whole text)."""

    def __init__(self, merge: "Merge", visible: list):
        self.m = merge
        self.nxt = {}  # node -> successor in THIS view, where it differs
        self.has_right = set()  # nodes that got a right child in THIS view
        self.head = merge.head
        self.length = len(visible)
        self.chunks = [visible[i:i + _CHUNK] for i in range(0, len(visible), _CHUNK)]
        self._ci, self._base = 0, 0  # the chunk last used and its first position

    def _locate(self, k: int) -> tuple:
        """Chunk and offset of visible position ``k`` (``0 <= k < length``)."""
        chunks, ci, base = self.chunks, self._ci, self._base
        if not (ci < len(chunks) and base <= k < base + len(chunks[ci])):
            ci = base = 0
            while k >= base + len(chunks[ci]):
                base += len(chunks[ci])
                ci += 1
            self._ci, self._base = ci, base
        return ci, k - base

    def insert(self, k: int, node: int) -> tuple:
        """Type ``node`` at visible position ``k``: its ``(parent, side)``."""
        m = self.m
        if k == 0:
            parent, side = (self.head, _LEFT) if self.head >= 0 else (-1, _RIGHT)
            self.nxt[node] = self.head
            self.head = node
            if self.chunks:
                self.chunks[0].insert(0, node)
            else:
                self.chunks.append([node])
            self._ci = self._base = 0
        else:
            ci, off = self._locate(k - 1)
            chunk = self.chunks[ci]
            a = chunk[off]
            succ = self.nxt[a] if a in self.nxt else m.nxt[a]
            if a in self.has_right or m.has_right[a]:
                parent, side = succ, _LEFT
            else:
                parent, side = a, _RIGHT
                self.has_right.add(a)
            self.nxt[node] = succ
            self.nxt[a] = node
            chunk.insert(off + 1, node)
            if len(chunk) > 2 * _CHUNK:
                self.chunks[ci:ci + 1] = [chunk[:_CHUNK], chunk[_CHUNK:]]
        self.length += 1
        return parent, side

    def delete(self, k: int) -> int:
        """Delete the visible character at ``k``: the node it was."""
        ci, off = self._locate(k)
        node = self.chunks[ci].pop(off)
        if not self.chunks[ci]:
            del self.chunks[ci]
            self._ci = self._base = 0
        self.length -= 1
        return node

    def text(self) -> str:
        ch = self.m.ch
        return "".join(ch[e] for chunk in self.chunks for e in chunk)


class Merge:
    """The tree and its converged in-order sequence (a linked list)."""

    def __init__(self, n_peers: int):
        self.peer, self.ctr, self.ch = [], [], []
        self.parent, self.side = [], []
        self.nxt, self.prv = [], []
        self.deleted = bytearray()
        self.has_right = bytearray()
        self.kids = ({}, {})  # side -> parent -> children sorted by (peer, ctr)
        self.head = -1
        self.placed = 0  # nodes below this index are in the converged sequence
        self.tombstoned = []  # nodes deleted since the last exchange
        self.counter = [0] * n_peers
        self.views = [_View(self, []) for _ in range(n_peers)]

    def apply(self, peer: int, pos: int, ch: str) -> None:
        view = self.views[peer]
        ctr = self.counter[peer]
        self.counter[peer] = ctr + 1  # every op takes one counter of its peer
        if ch:
            node = len(self.ch)
            parent, side = view.insert(min(pos, view.length), node)
            self.peer.append(peer)
            self.ctr.append(ctr)
            self.ch.append(ch)
            self.parent.append(parent)
            self.side.append(side)
            self.nxt.append(-1)
            self.prv.append(-1)
            self.deleted.append(0)
            self.has_right.append(0)
        else:
            if view.length == 0:
                raise ValueError("a delete on an empty replica")
            self.tombstoned.append(view.delete(min(pos, view.length - 1)))

    def _after(self, pred: int, node: int) -> None:
        nxt, prv = self.nxt, self.prv
        if pred < 0:
            succ, self.head = self.head, node
        else:
            succ, nxt[pred] = nxt[pred], node
        nxt[node], prv[node] = succ, pred
        if succ >= 0:
            prv[succ] = node

    def _place(self, node: int) -> None:
        parent, side = self.parent[node], self.side[node]
        right, left = self.kids[_RIGHT], self.kids[_LEFT]
        sibs = self.kids[side].setdefault(parent, [])
        key = (self.peer[node], self.ctr[node])
        i = len(sibs)
        while i and (self.peer[sibs[i - 1]], self.ctr[sibs[i - 1]]) > key:
            i -= 1
        if i:  # after the whole subtree of the sibling before it
            pred = sibs[i - 1]
            while right.get(pred):
                pred = right[pred][-1]
        elif side == _RIGHT:
            pred = parent  # -1, the root: the very start
        else:  # the new first node of the parent's subtree
            first = parent
            while left.get(first):
                first = left[first][0]
            pred = self.prv[first]
        sibs.insert(i, node)
        self._after(pred, node)
        if side == _RIGHT and parent >= 0:
            self.has_right[parent] = 1

    def exchange(self) -> None:
        """All replicas exchange everything: the new nodes enter the
        converged sequence, and every replica reads it."""
        for node in range(self.placed, len(self.ch)):
            self._place(node)
        self.placed = len(self.ch)
        for node in self.tombstoned:
            self.deleted[node] = 1
        self.tombstoned = []
        visible = self.visible()
        self.views = [_View(self, visible) for _ in self.views]

    def visible(self) -> list:
        out, nxt, deleted = [], self.nxt, self.deleted
        e = self.head
        while e >= 0:
            if not deleted[e]:
                out.append(e)
            e = nxt[e]
        return out

    def chains(self) -> int:
        """Nodes left after contraction: a node folds into its parent when
        it is its only child, on the right, the next id of the same peer,
        and has no left child itself."""
        n = len(self.ch)
        children = [0] * n
        for p in self.parent:
            if p >= 0:
                children[p] += 1
        left = self.kids[_LEFT]
        links = sum(
            1 for e in range(n)
            if self.side[e] == _RIGHT and (p := self.parent[e]) >= 0
            and children[p] == 1 and self.peer[p] == self.peer[e]
            and self.ctr[p] + 1 == self.ctr[e] and not left.get(e))
        return n - links


def replay(seed: int, c: dict, v: int) -> dict:
    """What variant ``v``'s document must read: ``text`` after the last
    exchange, ``stale_text`` as replica 0 reads it just before (the
    control's answer), and the counts of the document's own shape."""
    t0 = time.perf_counter()
    m = Merge(c["peers_per_document"])
    every = c["sync_every_patches"]
    inserts = deletes = 0
    for i, (peer, pos, ch) in enumerate(script.routed_patches(seed, c, v)):
        m.apply(peer, pos, ch)
        inserts += bool(ch)
        deletes += not ch
        if (i + 1) % every == 0:
            m.exchange()
    stale = m.views[0].text()
    m.exchange()
    return {"text": "".join(m.ch[e] for e in m.visible()), "stale_text": stale,
            "inserts": inserts, "deletes": deletes, "chains": m.chains(),
            "reference_s": time.perf_counter() - t0}
