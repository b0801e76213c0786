"""Batched movable-tree merge kernel.

reference semantics: crates/loro-internal/src/diff_calc/tree.rs —
moves apply in global (lamport, peer, counter) order; a move whose new
parent lies in the target's subtree at that moment is skipped
(`effected = false`, tree.rs:499-508).  Deletion = move under TRASH.

The move log (host-sorted by key — cheap numpy radix) replays
sequentially per document and in parallel across documents; sibling
order (fractional index) is resolved host-side at materialization.
There are two replays of the same semantics, chosen by `replay_algo`
from what the code can observe (the platform and the table's size),
never by an argument or an environment variable:

- `xla:scan` (off the chip; the differential reference): `vmap` over a
  `lax.scan` of one step a move, whose cycle check is an early-exit
  `lax.while_loop` parent walk (`tree_merge_doc`).  On the chip this
  form is unusable at upstream's bench shape (PERF.md, PR 28: 256
  documents of 1,000 nodes and ~97,600 ops): under `vmap` the `while`
  runs to the slowest document, every iteration is a handful of tiny
  device ops, and `jnp.where(ok, state.at[t].set(p), state)` selects
  over the whole [D, n_nodes] state every step.
- `pallas:lockstep` (TPU): ONE fused kernel a launch (`_replay_kernel`).
  Documents sit on the lanes; each document's parent table lives in
  VMEM for the whole replay, two 16-bit parents to a word, as
  [groups, 8, docs]; the move log streams through in blocks of
  `_BLOCK_M` moves, one packed word a move; a parent read is a binary
  select over the group axis (one bit of the node index a level) and a
  sublane one-hot, the write a one-hot select; the walk is a `while`
  over all documents of the block in lock-step, sound for any depth.
  Several devices run it under `shard_map` over the doc axis
  (`doc_batch_jit`: Mosaic refuses automatic partitioning).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fugue_batch import doc_batch_jit, pad_bucket

ROOT = -1
TRASH = -2
ABSENT = -3


class TreeOpCols(NamedTuple):
    """[M] per-doc move log, sorted by (lamport, peer, counter).

    target: i32[M] node index (per-doc node dictionary)
    parent: i32[M] node index, ROOT, or TRASH
    valid:  bool[M] padding mask
    """

    target: jax.Array
    parent: jax.Array
    valid: jax.Array


def tree_merge_doc(
    cols: TreeOpCols, n_nodes: int, d_max: Optional[int] = None,
    with_steps: bool = False,
) -> Tuple[jax.Array, ...]:
    """Replay one doc's sorted move log.  Returns (parent i32[n_nodes]
    with ABSENT for never-created nodes, effected bool[M] per move) and,
    ``with_steps``, the walk steps i32[M] of each valid move's cycle check.

    `d_max` bounds the cycle-check walk.  Soundness requires
    d_max >= max tree depth; the default (n_nodes) is always sound —
    pass a smaller bound only when the workload guarantees a depth cap.
    """
    if d_max is None:
        d_max = n_nodes
    init = jnp.full(n_nodes, ABSENT, jnp.int32)

    def step(state, mv):
        t, p, v = mv

        # cycle check: does walking up from p reach t?  Early-exit
        # while_loop — cost follows the ACTUAL ancestor-chain depth,
        # not the d_max bound (the sound default d_max = n_nodes is
        # only the worst-case cap; typical trees walk O(depth) steps)
        def cond(carry):
            cur, hit, steps = carry
            return (cur >= 0) & ~hit & (steps < d_max)

        def walk(carry):
            cur, hit, steps = carry
            hit = hit | (cur == t)
            nxt = jnp.where(
                hit, jnp.int32(ROOT - 10), state[jnp.clip(cur, 0, n_nodes - 1)]
            )
            return nxt, hit, steps + 1

        cur, cycle, steps = jax.lax.while_loop(
            cond, walk, (p, jnp.bool_(False), jnp.int32(0))
        )
        cycle = cycle | (cur == t)
        ok = v & ~(cycle & (p >= 0))
        new_state = jnp.where(
            ok, state.at[jnp.clip(t, 0, n_nodes - 1)].set(p), state
        )
        return new_state, (ok, jnp.where(v, steps, 0))

    final, (effected, steps) = jax.lax.scan(
        step, init, (cols.target, cols.parent, cols.valid)
    )
    if with_steps:
        return final, effected, steps
    return final, effected


# ---------------------------------------------------------------------
# the fused replay (TPU)
# ---------------------------------------------------------------------

_LANES = 128
_BLOCK_M = 1024  # moves of the log resident in VMEM at a time
_WALK_UNROLL = 4  # walk steps between two tests of the lock-step `while`
_PAD_TARGET = 0xFFFF  # the target half of a padding move's word
_BIAS = 3  # parents are stored + 3: ABSENT -> 0 (a zeroed table is empty)
# tables past this many bytes of VMEM a doc block take the scan
_TABLE_VMEM_BYTES = 6 << 20


def tree_pads(m: int) -> int:
    """Moves a log of ``m`` moves is padded to: a power of two up to
    8,192 (`pad_bucket`), whole multiples of 8,192 past it — at 97,600
    moves a power of two would be a third padding."""
    q = 8192
    return pad_bucket(max(1, m), floor=16) if m <= q else -(-m // q) * q


def _pack_moves(xp, cols: TreeOpCols):
    """One u32 word a move, on the host (``xp`` numpy) or the device
    (jax.numpy): the target's node index in the low half, the parent
    (+ 3, so that ROOT, TRASH and ABSENT are 2, 1, 0) in the high half;
    an invalid move is a padding word."""
    word = (cols.target.astype(xp.uint32) & 0xFFFF) | (
        (cols.parent + _BIAS).astype(xp.uint32) << 16
    )
    return xp.where(cols.valid, word, xp.uint32(_PAD_TARGET))


def pack_tree_rows(cols_list, d_pad: int) -> np.ndarray:
    """Host: the packed upload of a batch, u32[d_pad, 1 + tree_pads(m)]:
    word 0 of a row is the document's move count, then its moves
    (`_pack_moves`), then padding words (target 0xFFFF)."""
    m = tree_pads(max([c.target.shape[0] for c in cols_list] + [1]))
    rows = np.full((d_pad, 1 + m), _PAD_TARGET, np.uint32)
    rows[:, 0] = 0
    for i, c in enumerate(cols_list):
        k = c.target.shape[0]
        rows[i, 0] = k
        rows[i, 1 : 1 + k] = _pack_moves(np, c)
    return rows


def _n_groups(n_nodes: int) -> int:
    """Sublane groups of a parent table: 16 nodes a group (8 sublanes, 2
    parents a word), a power of two of them (the lookup is a binary
    select over the group axis)."""
    g = 1
    while g * 16 < n_nodes:
        g *= 2
    return g


def replay_algo(n_nodes: int) -> str:
    """Which replay a batch of ``n_nodes``-node tables takes here:
    ``pallas:lockstep`` on a TPU while node indexes fit 16 bits and a
    doc block's table fits VMEM, else ``xla:scan``."""
    fits = (
        n_nodes < _PAD_TARGET - _BIAS
        and _n_groups(n_nodes) * 8 * _LANES * 4 <= _TABLE_VMEM_BYTES
    )
    return "pallas:lockstep" if fits and jax.default_backend() == "tpu" else "xla:scan"


def _replay_kernel(mmax_ref, log_ref, tbl_ref, stats_ref, *eff, d_max: int):
    """One grid step: the moves of log block j replayed, in order, over
    the documents of doc block i.  ``tbl_ref`` [G, 8, DB] (resident over
    j: the state) holds parent + 3 of node ``x`` in half ``x & 1`` of
    word [x >> 4, (x >> 1) & 7]; ``stats_ref`` [8, DB] counts per
    document the moves refused (row 0), the walk steps it took itself
    (row 1) and the lock-step steps of its block (row 2)."""
    i32 = jnp.int32
    j = pl.program_id(1)
    g_n, _, db = tbl_ref.shape
    block_m = log_ref.shape[0]

    @pl.when(j == 0)
    def _():
        tbl_ref[...] = jnp.zeros(tbl_ref.shape, i32)
        stats_ref[...] = jnp.zeros(stats_ref.shape, i32)

    if eff:
        eff[0][...] = jnp.zeros(eff[0].shape, i32)
    row_iota = jax.lax.broadcasted_iota(i32, (g_n, 8, db), 0) * 8 + (
        jax.lax.broadcasted_iota(i32, (g_n, 8, db), 1)
    )
    sub_iota = jax.lax.broadcasted_iota(i32, (8, db), 0)

    def lookup(cur):
        """state[cur] of every document: [1, DB]."""
        x = tbl_ref[...]
        grp = jnp.right_shift(cur, 4)
        k, bit = g_n, g_n.bit_length() - 2
        while k > 1:  # one bit of the group index a level, the top one first
            k //= 2
            up = jnp.broadcast_to(jnp.right_shift(grp, bit) & 1, (8, db))[None]
            x = jnp.where(up == 1, x[k:], x[:k])
            bit -= 1
        sub = jnp.broadcast_to(jnp.right_shift(cur, 1) & 7, (8, db))
        word = jnp.sum(jnp.where(sub_iota == sub, x[0], 0), axis=0, keepdims=True)
        odd = (cur & 1) == 1
        half = jnp.where(odd, jnp.right_shift(word, 16), word) & 0xFFFF
        return half - _BIAS

    def one_move(k, carry):
        w = log_ref[pl.ds(k, 1), :]
        t = w & 0xFFFF
        p = (jnp.right_shift(w, 16) & 0xFFFF) - _BIAS
        valid = t != _PAD_TARGET

        def walking(cur, hit):
            return jnp.max(((cur >= 0) & (hit == 0)).astype(i32))

        def walk(c):
            cur, hit, steps, _go, own = c
            for u in range(_WALK_UNROLL):
                on = (cur >= 0) & (hit == 0) & (steps + u < d_max)
                is_t = cur == t
                nxt = jnp.where(is_t, i32(ROOT - 10), lookup(cur))
                cur = jnp.where(on, nxt, cur)
                hit = jnp.where(on & is_t, 1, hit)
                own = own + on.astype(i32)
            steps = steps + _WALK_UNROLL
            go = jnp.where(steps < d_max, walking(cur, hit), 0)
            return cur, hit, steps, go, own

        cur0 = jnp.where(valid, p, i32(ROOT))
        hit0 = jnp.zeros_like(cur0)
        cur, hit, steps, _go, own = jax.lax.while_loop(
            lambda c: c[3] > 0, walk,
            (cur0, hit0, i32(0), walking(cur0, hit0), hit0),
        )
        cycle = (hit == 1) | (cur == t)
        ok = valid & ~(cycle & (p >= 0))
        old = tbl_ref[...]
        here = (row_iota == jnp.broadcast_to(jnp.right_shift(t, 1), (8, db))[None]) & (
            jnp.broadcast_to(ok.astype(i32), (8, db))[None] == 1
        )
        pw = jnp.broadcast_to(p + _BIAS, (8, db))[None]
        odd = jnp.broadcast_to(t & 1, (8, db))[None] == 1
        new = jnp.where(
            odd, (old & 0xFFFF) | jnp.left_shift(pw, 16), (old & i32(-65536)) | pw
        )
        tbl_ref[...] = jnp.where(here, new, old)
        stats_ref[0:1, :] += (valid & ~ok).astype(i32)
        stats_ref[1:2, :] += own
        stats_ref[2:3, :] += jnp.broadcast_to(steps, (1, db))
        if eff:
            eff[0][pl.ds(k, 1), :] = ok.astype(i32)
        return carry

    # no step for padding that the batch's longest log does not need
    todo = jnp.clip(mmax_ref[0] - j * block_m, 0, block_m)
    jax.lax.fori_loop(0, todo, one_move, 0)


def _replay_lockstep(words, lens, n_nodes: int, d_max: int, want_eff: bool,
                     interpret: bool = False):
    """The fused replay of one device's documents: ``words`` u32[D, M]
    (`_pack_moves`; padding 0xFFFF), ``lens`` i32[D] ->
    (parents i32[D, n_nodes], eff bool[D, M] | None, stats i32[D, 3])."""
    d, m = words.shape
    d_pad = -(-d // _LANES) * _LANES
    block_m = min(_BLOCK_M, -(-m // 8) * 8)
    m_pad = -(-m // block_m) * block_m
    log = jnp.pad(
        jax.lax.bitcast_convert_type(words, jnp.int32),
        ((0, d_pad - d), (0, m_pad - m)), constant_values=_PAD_TARGET,
    ).T  # [M, D]: a move of every document to a row
    m_max = jnp.max(lens).astype(jnp.int32).reshape(1)
    g = _n_groups(n_nodes)
    n_blocks = m_pad // block_m

    def log_block(i, j, mm):
        # a block past the longest log is not fetched again
        return jnp.minimum(j, jnp.maximum(mm[0] - 1, 0) // block_m), i

    out_shape = [
        jax.ShapeDtypeStruct((g, 8, d_pad), jnp.int32),
        jax.ShapeDtypeStruct((8, d_pad), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((g, 8, _LANES), lambda i, j, mm: (0, 0, i)),
        pl.BlockSpec((8, _LANES), lambda i, j, mm: (0, i)),
    ]
    if want_eff:
        out_shape.append(jax.ShapeDtypeStruct((m_pad, d_pad), jnp.int32))
        out_specs.append(pl.BlockSpec((block_m, _LANES), lambda i, j, mm: (j, i)))
    outs = pl.pallas_call(
        functools.partial(_replay_kernel, d_max=d_max),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d_pad // _LANES, n_blocks),
            in_specs=[pl.BlockSpec((block_m, _LANES), log_block)],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="tree_replay_lockstep",
    )(m_max, log)
    tbl = outs[0].reshape(g * 8, d_pad)
    halves = jnp.stack([tbl & 0xFFFF, jnp.right_shift(tbl, 16) & 0xFFFF], axis=1)
    parents = (halves.reshape(g * 16, d_pad)[:n_nodes, :d] - _BIAS).T
    eff = outs[2][:m, :d].T == 1 if want_eff else None
    return parents, eff, outs[1][:3, :d].T


def _replay_scan(cols: TreeOpCols, n_nodes: int, d_max: int):
    """The same answers from the scan: (parents, eff, stats)."""
    parents, eff, steps = jax.vmap(
        lambda c: tree_merge_doc(c, n_nodes, d_max, with_steps=True)
    )(cols)
    refused = jnp.sum(cols.valid & ~eff, axis=1, dtype=jnp.int32)
    # under vmap the walk of a move runs to the batch's slowest document
    lockstep = jnp.broadcast_to(jnp.sum(jnp.max(steps, axis=0)), refused.shape)
    return parents, eff, jnp.stack(
        [refused, jnp.sum(steps, axis=1, dtype=jnp.int32), lockstep], axis=1
    )


def _unpack_words(words) -> TreeOpCols:
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    t = w & 0xFFFF
    valid = t != _PAD_TARGET
    return TreeOpCols(
        target=jnp.where(valid, t, 0),
        parent=jnp.where(valid, (jnp.right_shift(w, 16) & 0xFFFF) - _BIAS, ROOT),
        valid=valid,
    )


def tree_replay(cols_or_rows, n_nodes: int, d_max: Optional[int] = None,
                want_eff: bool = True, algo: Optional[str] = None,
                interpret: bool = False):
    """The single dispatch point of the replay (traced under its
    caller's jit, inside `shard_docs`): `TreeOpCols` [D, M], or the
    packed rows of `pack_tree_rows` u32[D, 1 + M], ->
    (parents i32[D, n_nodes], eff bool[D, M] | None, stats i32[D, 3]:
    refused moves, own walk steps, lock-step walk steps).  ``algo`` and
    ``interpret`` are for the differential tests; callers leave them."""
    if d_max is None:
        d_max = n_nodes
    if algo is None:
        algo = replay_algo(n_nodes)
    packed = not isinstance(cols_or_rows, TreeOpCols)
    with jax.named_scope("tree_replay"):
        if algo == "xla:scan":
            cols = _unpack_words(cols_or_rows[:, 1:]) if packed else cols_or_rows
            parents, eff, stats = _replay_scan(cols, n_nodes, d_max)
            return parents, (eff if want_eff else None), stats
        if packed:
            words = cols_or_rows[:, 1:]
            lens = jax.lax.bitcast_convert_type(cols_or_rows[:, 0], jnp.int32)
        else:
            words = _pack_moves(jnp, cols_or_rows)
            # the last valid row + 1: a log may have holes
            m = words.shape[1]
            lens = jnp.max(
                jnp.where(cols_or_rows.valid, jnp.arange(1, m + 1, dtype=jnp.int32), 0),
                axis=1,
            )
        return _replay_lockstep(words, lens, n_nodes, d_max, want_eff, interpret)


def _doc_jit_as(name: str):
    """`doc_batch_jit` of a function, compiled under the public ``name``
    (the profile's and the compile events' name of the program)."""

    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return doc_batch_jit(fn)

    return wrap


@_doc_jit_as("tree_merge_batch")
def _tree_merge_batch_jit(cols: TreeOpCols, n_nodes: int, d_max: Optional[int]):
    parents, eff, _stats = tree_replay(cols, n_nodes, d_max)
    return parents, eff


def tree_merge_batch(
    cols: TreeOpCols, n_nodes: int, d_max: Optional[int] = None
) -> Tuple[jax.Array, jax.Array]:
    """[D, M] move logs -> ([D, n_nodes] parents, [D, M] effected), by
    the replay `replay_algo` selects; on several devices each replays
    its own documents (`doc_batch_jit`)."""
    return _tree_merge_batch_jit(cols, n_nodes, d_max)


@_doc_jit_as("tree_import_batch")
def tree_import_batch(rows, n_nodes: int, want_eff: bool):
    """The import's one launch: the packed rows of `pack_tree_rows`
    u32[D, 1 + M] -> i32[D, n_nodes + 2] (and, ``want_eff``, the moves
    effected bool[D, M], for the callers that order siblings)."""
    parents, eff, stats = tree_replay(rows, n_nodes, None, want_eff)
    with jax.named_scope("tree_deleted"):
        alive = jnp.where(is_deleted_batch(parents), TRASH, parents)
    # one answer a document, one fetch: the parent of every alive node
    # (TRASH for a deleted one, ABSENT for one never created), then the
    # moves refused and the walk steps taken
    out = jnp.concatenate([alive, stats[:, :2]], axis=1)
    return (out, eff) if want_eff else out


class TreeLogCols(NamedTuple):
    """[M] UNSORTED device-resident move log (append order; the
    resident path's buffer — DeviceTreeBatch).  Peers ship as u64
    halves; the global move key (lamport, peer, counter) is sorted on
    device at materialization."""

    lamport: jax.Array  # i32[M]
    peer_hi: jax.Array  # u32[M]
    peer_lo: jax.Array  # u32[M]
    counter: jax.Array  # i32[M]
    target: jax.Array  # i32[M] node ordinal
    parent: jax.Array  # i32[M] node ordinal, ROOT, or TRASH
    valid: jax.Array  # bool[M]


@_doc_jit_as("tree_replay_log_batch")
def _tree_replay_log_batch_jit(cols: TreeLogCols, n_nodes: int, d_max: Optional[int]):
    def sort_doc(c: TreeLogCols):
        m = c.lamport.shape[0]
        big = jnp.int32(2**31 - 1)
        lam = jnp.where(c.valid, c.lamport, big)  # pads sort last
        row_idx = jnp.arange(m, dtype=jnp.int32)
        _, _, _, _, t_s, p_s, v_s, row_s = jax.lax.sort(
            (
                lam,
                c.peer_hi,
                c.peer_lo,
                c.counter,
                c.target,
                c.parent,
                c.valid.astype(jnp.int32),
                row_idx,
            ),
            num_keys=4,
        )
        return TreeOpCols(target=t_s, parent=p_s, valid=v_s.astype(bool)), row_s

    sorted_cols, row_s = jax.vmap(sort_doc)(cols)
    parents, eff, _stats = tree_replay(sorted_cols, n_nodes, d_max)
    eff_rows = jax.vmap(lambda e, r: jnp.zeros_like(e).at[r].set(e))(eff, row_s)
    return parents, eff_rows


def tree_replay_log_batch(
    cols: TreeLogCols, n_nodes: int, d_max: Optional[int] = None
) -> Tuple[jax.Array, jax.Array]:
    """Sort each doc's standing move log by the global move key and
    replay it (`tree_replay`).  Returns ([D, n_nodes] parents, [D, M]
    effected in ROW (append) order — the host resolves sibling positions
    from the last effected non-delete move per node in key order)."""
    return _tree_replay_log_batch_jit(cols, n_nodes, d_max)


def is_deleted_batch(parents: jax.Array) -> jax.Array:
    """bool[D, N]: node is trash-reachable (pointer-doubling ancestor
    resolution — log-depth, fully parallel)."""

    def per_doc(par):
        n = par.shape[0]

        def body(_, p):
            # jump: p[i] <- p[p[i]] when parent is a real node
            nxt = jnp.where(p >= 0, p[jnp.clip(p, 0, n - 1)], p)
            return nxt

        # log2(n) doublings cover any depth <= n
        p = jax.lax.fori_loop(0, int(np.ceil(np.log2(max(n, 2)))) + 1, body, par)
        return p == TRASH

    return jax.vmap(per_doc)(parents)


def extract_tree_ops(changes, cid):
    """Host: explode TreeMove ops for `cid` into sorted columns + node
    dictionary.  Returns (TreeOpCols numpy, nodes list, row_positions
    list aligned with rows — resolve winners with positions_of after the
    kernel reports which moves were effected)."""
    from ..core.change import TreeMove

    rows = []  # (lamport, peer, counter, target, parent, position)
    node_ids = {}
    nodes = []

    def node_idx(tid):
        if tid not in node_ids:
            node_ids[tid] = len(nodes)
            nodes.append(tid)
        return node_ids[tid]

    for ch in changes:
        for op in ch.ops:
            if op.container != cid or not isinstance(op.content, TreeMove):
                continue
            c = op.content
            lam = ch.lamport + (op.counter - ch.ctr_start)
            t = node_idx(c.target)
            if c.is_delete:
                p = TRASH
            elif c.parent is None:
                p = ROOT
            else:
                p = node_idx(c.parent)
            rows.append((lam, ch.peer, op.counter, t, p, c.position))
    rows.sort(key=lambda r: (r[0], r[1], r[2]))
    m = len(rows)
    target = np.asarray([r[3] for r in rows], np.int32)
    parent = np.asarray([r[4] for r in rows], np.int32)
    row_positions = [r[5] for r in rows]
    cols = TreeOpCols(target=target, parent=parent, valid=np.ones(m, bool))
    return cols, nodes, row_positions


def positions_of(cols: TreeOpCols, row_positions, effected) -> dict:
    """Winning fractional index per node: the last *effected*, non-delete
    move in key order (deletes ship position=None and cycle-losing moves
    must not clobber the position the effective tree actually has)."""
    out: dict = {}
    effected = np.asarray(effected)
    for i in range(len(row_positions)):
        if not effected[i]:
            continue
        if int(cols.parent[i]) == TRASH:
            continue
        out[int(cols.target[i])] = row_positions[i]
    return out


def pad_tree_cols(cols: TreeOpCols, m: int) -> TreeOpCols:
    def pad(a, fill, dtype):
        out = np.full(m, fill, dtype)
        out[: a.shape[0]] = a
        return out

    return TreeOpCols(
        target=pad(cols.target, 0, np.int32),
        parent=pad(cols.parent, ROOT, np.int32),
        valid=pad(cols.valid, False, bool),
    )


class _LazyPositions:
    """Row-indexed fractional-index bytes, sliced from the payload on
    demand (positions_of touches only effected rows — no per-row copy
    for the losers)."""

    __slots__ = ("payload", "off", "ln", "has")

    def __init__(self, payload, off, ln, has):
        self.payload = payload
        self.off = off
        self.ln = ln
        self.has = has

    def __len__(self):
        return len(self.off)

    def __getitem__(self, i):
        if not self.has[i]:
            return None
        o = int(self.off[i])
        return bytes(self.payload[o : o + int(self.ln[i])])

    def __eq__(self, other):
        return list(self) == list(other)


def extract_tree_from_payload(payload: bytes, cid):
    """Native fast path: binary updates payload -> (TreeOpCols, nodes,
    row_positions) without Python Change objects (same contract as
    extract_tree_ops).  Returns None when the native library is
    unavailable; raises ValueError on malformed payloads."""
    from ..codec.binary import read_tables
    from ..native import available, explode_tree_payload

    if not available():
        return None
    from ..core.ids import TreeID

    peers_wire, _keys, cids, _r = read_tables(payload)
    try:
        target = cids.index(cid)
    except ValueError:
        return TreeOpCols(
            target=np.zeros(0, np.int32),
            parent=np.zeros(0, np.int32),
            valid=np.zeros(0, bool),
        ), [], []
    out = explode_tree_payload(payload, target)
    n = len(out["lamport"])
    peer_u64 = np.asarray(peers_wire, np.uint64)
    order = np.lexsort(
        (out["counter"], peer_u64[out["peer_idx"]] if n else out["peer_idx"], out["lamport"])
    )
    tp = out["target_peer_idx"][order].astype(np.int64)
    tc = out["target_ctr"][order].astype(np.int64)
    fl = out["flags"][order]
    pp = out["parent_peer_idx"][order].astype(np.int64)
    pc = out["parent_ctr"][order].astype(np.int64)
    po = out["pos_off"][order]
    pl = out["pos_len"][order]
    # vectorized node dictionary: pack (wire peer idx, ctr) into i64
    # (peer indexes are small; counters non-negative), unique+inverse
    from .columnar import pack_wire_ids

    has_parent = (fl & 4) != 0
    t_packed = pack_wire_ids(tp, tc)
    p_packed = pack_wire_ids(pp[has_parent], pc[has_parent])
    uniq, inv = np.unique(np.concatenate([t_packed, p_packed]), return_inverse=True)
    nodes = [TreeID(int(peers_wire[int(k) >> 32]), int(k) & 0xFFFFFFFF) for k in uniq]
    target_col = inv[:n].astype(np.int32)
    parent_col = np.full(n, ROOT, np.int32)
    parent_col[has_parent] = inv[n:].astype(np.int32)
    parent_col[(fl & 2) != 0] = TRASH
    cols = TreeOpCols(target=target_col, parent=parent_col, valid=np.ones(n, bool))
    return cols, nodes, _LazyPositions(payload, po, pl, (fl & 8) != 0)
