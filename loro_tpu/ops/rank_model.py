"""Host mirror of the device ring (numpy).

``build_ring`` is the numpy twin of ``fugue_batch._ring_and_anchors``'s
slot-numbered Euler-tour construction: the reference that
tests/test_rank_blocked.py::test_device_ring_matches_host_mirror diffs
the in-jit ring against, token for token.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

BIG = 2**30


def build_ring(
    parent_in: np.ndarray,
    side_in: np.ndarray,
    valid_in: np.ndarray,
    sib_keys: Optional[Tuple[np.ndarray, ...]] = None,
) -> np.ndarray:
    """succ i32[2*(n+1)] — the exact slot-numbered Euler-tour successor
    ring _order_core builds on device (ENTER(e) = sibling-sort slot,
    EXIT(e) = m-1-slot, invalid tokens chained by index)."""
    n = parent_in.shape[0]
    n1 = n + 1
    root = n
    parent = np.concatenate([np.where(valid_in, parent_in, BIG), [BIG]]).astype(np.int64)
    parent[:n] = np.where(valid_in & (parent_in < 0), root, parent[:n])
    side = np.concatenate([side_in, [1]]).astype(np.int64)
    valid = np.concatenate([valid_in, [False]])
    key = np.where(parent < BIG, parent * 2 + side, BIG)
    if sib_keys is None:
        order = np.argsort(key, kind="stable")
    else:
        minor = [np.concatenate([k.astype(np.uint32), [0]]) for k in sib_keys]
        order = np.lexsort(tuple(reversed(minor)) + (key,))
    slot = np.empty(n1, np.int64)
    slot[order] = np.arange(n1)
    p_s, s_s = parent[order], side[order]
    prev_same = (p_s == np.roll(p_s, 1)) & (s_s == np.roll(s_s, 1))
    prev_same[0] = False
    is_first = ~prev_same
    nxt_same = (p_s == np.roll(p_s, -1)) & (s_s == np.roll(s_s, -1))
    nxt_same[-1] = False
    is_last = ~nxt_same
    elem_s = order
    next_sib_s = np.where(nxt_same, np.roll(elem_s, -1), -1)
    next_sib = np.zeros(n1, np.int64)
    next_sib[elem_s] = next_sib_s
    is_child = p_s < BIG
    first_l = np.full(n1, -1, np.int64)
    first_r = np.full(n1, -1, np.int64)
    msk = is_first & is_child & (s_s == 0)
    first_l[p_s[msk]] = elem_s[msk]
    msk = is_first & is_child & (s_s == 1)
    first_r[p_s[msk]] = elem_s[msk]
    has_next_sib = next_sib >= 0
    has_l = first_l >= 0
    has_r = first_r >= 0

    m = 2 * n1
    ent = slot
    ext = (m - 1) - slot
    e_ids = np.arange(n1)
    post_l = np.where(has_r, ent[np.clip(first_r, 0, n)], ext[e_ids])
    succ_enter = np.where(has_l, ent[np.clip(first_l, 0, n)], post_l)
    par = np.where(parent < BIG, parent, root).astype(np.int64)
    succ_exit = np.where(
        has_next_sib,
        ent[np.clip(next_sib, 0, n)],
        np.where(side == 0, post_l[par], ext[par]),
    )
    succ_exit[root] = ext[root]
    succ = np.concatenate([succ_enter[order], succ_exit[order][::-1]])
    tok_valid = np.concatenate([valid[order], valid[order][::-1]])
    tok_ids = np.arange(m)
    chain_next = np.minimum(tok_ids + 1, m - 1)
    keep = tok_valid | (tok_ids == ext[root]) | (tok_ids == ent[root])
    succ = np.where(keep, succ, chain_next)
    succ[ent[root]] = succ_enter[root]
    succ[ext[root]] = ext[root]
    return succ.astype(np.int32)
