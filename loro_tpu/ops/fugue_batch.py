"""Batched Fugue sequence-order kernel.

The device-side merge engine for Text/List/MovableList — the TPU
reformulation of the reference's tracker replay
(crates/loro-internal/src/container/richtext/tracker/crdt_rope.rs
Fugue integration + tracker.rs diff extraction).

Because our wire format ships each insert's Fugue tree placement
`(parent, side)` (see core/change.py), integrating a batch of inserts
needs no sequential origin-scan.  The final sequence order is the
in-order traversal of the Fugue tree with siblings sorted by
(peer, counter).  We compute it fully in parallel:

1. lexsort elements by (parent, side, peer, counter) -> sibling groups
2. build the Euler-tour successor ring over 2 tokens per node
   (ENTER / EXIT — the directed-edge tour).  A node's in-order moment
   needs no third token: it is anchored just after EXIT(last L-child)
   when L-children exist, else just after its own ENTER; anchors are
   distinct tokens, so anchor rank orders elements exactly
3. Wyllie pointer-doubling list ranking (ceil(log2(2N)) rounds; dist
   and succ ride one [m, 2] row so each round is a single row gather —
   measured 2.3x over two separate [m] gathers on v5e)
4. element order = rank of its anchor token

Work O(N log N), depth O(log N), all gathers/sorts — ideal XLA/TPU
shapes.  `vmap` batches the whole thing across documents; the fleet
layer (parallel/fleet.py) shards the doc axis over the device mesh.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SeqColumns(NamedTuple):
    """Columnar element table for one document (padded to fixed N).

    parent: i32[N]  index of fugue parent element; -1 = virtual root
    side:   i32[N]  0 = Left child, 1 = Right child
    peer:   i32[N]  peer *rank* in the batch peer dictionary (order-
                    preserving w.r.t. u64 peer ids -> sibling order
                    matches the host engine)
    counter:i32[N]
    deleted:bool[N] tombstone flag
    content:i32[N]  codepoint / value-dictionary index
    valid:  bool[N] False for padding rows
    """

    parent: jax.Array
    side: jax.Array
    peer: jax.Array
    counter: jax.Array
    deleted: jax.Array
    content: jax.Array
    valid: jax.Array


def rank_bound(n: int) -> int:
    """Exclusive upper bound of fugue_order rank keys for an n-element
    table: ring distances live in [0, 2*(n+1))."""
    return 2 * (n + 1)


def _doc_mesh(batch):
    """The mesh of a doc-sharded batch that spans several devices, read
    off its first array (None for host arrays and for batches on one
    device)."""
    from jax.sharding import NamedSharding

    sh = getattr(jax.tree_util.tree_leaves(batch)[0], "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
        return sh.mesh
    return None


def shard_docs(batched, mesh):
    """``batched`` (a function of [D, ...] doc batches, every array
    argument and result led by the doc axis) as it runs on ``mesh``: on
    several devices under shard_map over the doc axis, each device on
    its own documents.  Documents never talk to each other, so nothing
    is lost; and the Pallas rank needs it — inside a plain jit whose
    inputs are doc-sharded, lowering refuses the kernel ("Mosaic kernels
    cannot be automatically partitioned").  On one device (or
    ``mesh=None``: host arrays) it is ``batched`` itself."""
    if mesh is None or mesh.size == 1:
        return batched
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DOC_AXIS

    spec = P(DOC_AXIS)
    # check_vma off: every output is sharded over the doc axis like its
    # inputs (nothing is claimed replicated), and pallas_call has no
    # varying-axes rule for its out_shape
    return jax.shard_map(
        batched, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )


def doc_batch_jit(fn):
    """``jax.jit`` for ``fn(batch, *statics)``, a function of one doc
    batch whose documents reach the rank dispatch: the batch's mesh is
    read off its arrays (``_doc_mesh``) and ``shard_docs(fn, mesh)`` is
    what gets jitted — one compile per (shapes, statics, mesh), under
    ``fn``'s own name.  ``.lower(batch, *statics)`` lowers the same
    program from shapes (``ShapeDtypeStruct``s carry their sharding)."""

    def on_mesh(batch, statics, mesh):
        return shard_docs(lambda b: fn(b, *statics), mesh)(batch)

    on_mesh.__name__ = on_mesh.__qualname__ = fn.__name__
    jitted = jax.jit(on_mesh, static_argnums=(1, 2))

    @functools.wraps(fn)
    def entry(batch, *statics):
        return jitted(batch, statics, _doc_mesh(batch))

    entry.lower = lambda batch, *statics: jitted.lower(
        batch, statics, _doc_mesh(batch))
    return entry


# Device stages carry a ``jax.named_scope`` at their single dispatch
# point — ring, rank, compact, place, unpack, checksum — so the profile's
# device ops keep a stage name whatever XLA numbers its fusions
# (metadata only: no operation, shape or fusion changes).


def _double(T: jax.Array, n_steps: int) -> jax.Array:
    """Weighted pointer doubling on (dist, target) [m, 2] rows — one row
    gather per round (the measured 2.3x-over-two-gathers layout)."""

    def body(_, T):
        g = jnp.take(T, T[:, 1], axis=0)  # one row gather: (d[t], t[t])
        return jnp.stack([T[:, 0] + g[:, 0], g[:, 1]], axis=1)

    return jax.lax.fori_loop(0, n_steps, body, T)


def _wyllie_dist(succ: jax.Array) -> jax.Array:
    """Distance-to-terminal by pointer doubling."""
    m = succ.shape[0]
    tok_ids = jnp.arange(m, dtype=jnp.int32)
    n_steps = max(1, int(np.ceil(np.log2(max(m, 2)))))
    dist0 = jnp.where(succ == tok_ids, 0, 1).astype(jnp.int32)
    T = _double(jnp.stack([dist0, succ], axis=1), n_steps)
    return T[:, 0]


def make_ring_rank_sharded(mesh, m: int, algo: str = "wyllie"):
    """Op-axis-sharded Wyllie ranking (SURVEY.md §2.4 item 2 for the
    sequence kernel): succ [D, m] sharded P(docs, ops) -> dist [D, m].
    algo="blocked" prepends a SHARD-LOCAL phase A (freeze-at-shard-exit
    doubling, zero collectives) and makes the all_gather doubling
    adaptive (early exit when every pointer rests on a terminal — rings
    with shard locality then pay far fewer all_gather rounds; the
    round cap keeps arbitrary rings exact).

    Each op-shard owns m/S contiguous ring rows; every doubling round
    all_gathers the (dist, succ) row table along the op axis and updates
    only its local rows — the random-row gathers (the measured ~all of
    the merge cost on v5e) divide by S while each round moves m*8B per
    doc over ICI.  Communication-optimal doubling would need an
    all-to-all of exactly the requested rows; the all_gather variant is
    the XLA-collective formulation of the same plan and is already
    latency-bound, not bandwidth-bound, at CRDT ring sizes (m*8B =
    ~260KB at the flagship m=32896).  Doc-axis sharding stays the
    default — see ARCHITECTURE.md §"Op-axis ranking verdict"."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DOC_AXIS, OP_AXIS

    if algo not in ("wyllie", "blocked"):
        from ..errors import ConfigError

        raise ConfigError("sharded rank algo", algo, "wyllie|blocked")
    n_steps = max(1, int(np.ceil(np.log2(max(m, 2)))))

    def local(succ_sh: jax.Array) -> jax.Array:  # [d_local, ms] global ids
        ms = succ_sh.shape[1]
        tok0 = jax.lax.axis_index(OP_AXIS).astype(jnp.int32) * ms
        tok = tok0 + jnp.arange(ms, dtype=jnp.int32)[None, :]
        dist0 = jnp.where(succ_sh == tok, 0, 1).astype(jnp.int32)
        T = jnp.stack([dist0, succ_sh], axis=-1)  # [d, ms, 2]

        if algo == "blocked":
            # phase A: collapse in-shard chains without touching ICI —
            # a pointer composes only while its target is a LOCAL row
            def body_a(_, T):
                t = T[:, :, 1]
                lt = t - tok0
                in_shard = (lt >= 0) & (lt < ms) & (t != tok)
                lt = jnp.clip(lt, 0, ms - 1)
                g = jnp.take_along_axis(T, lt[:, :, None], axis=1)
                return jnp.stack(
                    [
                        jnp.where(in_shard, T[:, :, 0] + g[:, :, 0], T[:, :, 0]),
                        jnp.where(in_shard, g[:, :, 1], T[:, :, 1]),
                    ],
                    axis=-1,
                )

            T = jax.lax.fori_loop(
                0, max(1, int(np.ceil(np.log2(max(ms, 2))))), body_a, T
            )

        def gather_step(T):
            T_full = jax.lax.all_gather(T, OP_AXIS, axis=1, tiled=True)  # [d, m, 2]
            g = jax.vmap(lambda full, t: jnp.take(full, t, axis=0))(
                T_full, T[:, :, 1]
            )  # [d, ms, 2]: (dist[t], succ[t])
            return jnp.stack([T[:, :, 0] + g[:, :, 0], g[:, :, 1]], axis=-1)

        if algo == "blocked":
            # adaptive all_gather doubling: T stabilizes exactly when
            # every pointer rests on a terminal (terminals are the only
            # fixpoint rows), so comparing post- vs pre-update targets
            # detects completion with ZERO extra gathers (one round
            # later than a lookahead check, but gathers are the cost
            # being minimized); agreement psum'd across the op shards
            def body(carry):
                i, T, _done = carry
                T_new = gather_step(T)
                local_done = jnp.all(T_new[:, :, 1] == T[:, :, 1])
                done = (
                    jax.lax.psum((~local_done).astype(jnp.int32), OP_AXIS) == 0
                )
                return i + 1, T_new, done

            def cond(carry):
                i, _T, done = carry
                return (i < n_steps) & ~done

            _, T, _ = jax.lax.while_loop(
                cond, body, (jnp.int32(0), T, jnp.bool_(False))
            )
        else:
            T = jax.lax.fori_loop(0, n_steps, lambda _, T: gather_step(T), T)
        return T[:, :, 0]

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(DOC_AXIS, OP_AXIS),),
            out_specs=P(DOC_AXIS, OP_AXIS),
            # the adaptive loop's `done` flag enters the carry unvarying
            # and leaves it varying over the doc axis; the outputs are
            # explicitly sharded, so the varying-axes check is skipped
            check_vma=algo != "blocked",
        )
    )


def fugue_order(cols: SeqColumns) -> jax.Array:
    """Return rank i32[N]: a key whose ascending order is the in-order
    position of each element in the Fugue traversal (keys may have gaps;
    pads get large keys).

    CONTRACT: rows must be pre-sorted by (peer, counter) — which the
    host extraction produces for free as per-peer concatenation, no
    comparison sort (SeqExtract.sort_by_peer_counter).  Sibling order is
    then one *stable* single-key sort by packed (parent, side), the only
    sort in the whole kernel."""
    return _order_core(cols.parent, cols.side, cols.valid)


def _resolve_rank_spec(rank_impl: None, m: int) -> Tuple[str, str]:
    """(backend, algo) for a ring of m tokens, from the platform and the
    ring's length alone: the Pallas kernels on a TPU while the ring fits
    VMEM (``pallas_rank_applicable``), else the XLA pointer doubling.
    ``rank_impl`` is always None: benchmarks/drivers/import_packed.py
    passes it, and a PR that may edit the benchmark drops it there."""
    from .pallas_rank import pallas_rank_applicable

    if rank_impl is not None:
        raise ValueError(f"rank_impl must be None, got {rank_impl!r}")
    return ("pallas", "ruling") if pallas_rank_applicable(m) else ("xla", "wyllie")


@jax.named_scope("rank")
def _rank_dist(succ: jax.Array) -> jax.Array:
    """Distance-to-terminal of a successor ring — the single ranking
    dispatch point."""
    backend, _ = _resolve_rank_spec(None, int(succ.shape[0]))
    if backend == "pallas":
        from .pallas_rank import wyllie_rank

        return wyllie_rank(succ)
    return _wyllie_dist(succ)


@jax.named_scope("ring")
def _ring_and_anchors(
    parent_in: jax.Array,
    side_in: jax.Array,
    valid_in: jax.Array,
    sib_keys: Optional[Tuple[jax.Array, ...]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(succ i32[2*(n+1)], anchor i32[n+1]) — the Euler-tour successor
    ring and each node's in-order anchor token (the virtual root at
    element index n).  Split from _order_core so tests can diff the
    in-jit ring against the host mirror (ops.rank_model.build_ring,
    which must stay in lockstep with this function)."""
    n = parent_in.shape[0]
    n1 = n + 1
    root = n  # virtual root element index
    big = jnp.int32(2**30)

    # -- extended element arrays incl. virtual root -------------------
    parent = jnp.concatenate([jnp.where(valid_in, parent_in, big), jnp.array([big], jnp.int32)])
    parent = parent.at[:n].set(jnp.where(valid_in & (parent_in < 0), root, parent[:n]))
    side = jnp.concatenate([side_in.astype(jnp.int32), jnp.array([1], jnp.int32)])
    valid = jnp.concatenate([valid_in, jnp.array([False])])  # root not a child

    key = jnp.where(parent < big, parent * 2 + side, big)
    if sib_keys is None:
        # ONE stable sort by (parent, side); (peer, counter) order within
        # groups comes from the input row-order contract
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
    else:
        minor = [
            jnp.concatenate([k.astype(jnp.uint32), jnp.zeros(1, jnp.uint32)]) for k in sib_keys
        ]
        order = jnp.lexsort(tuple(reversed(minor)) + (key,)).astype(jnp.int32)
    p_s = parent[order]
    s_s = side[order]
    prev_same = (p_s == jnp.roll(p_s, 1)) & (s_s == jnp.roll(s_s, 1))
    prev_same = prev_same.at[0].set(False)
    is_first = ~prev_same
    nxt_same = (p_s == jnp.roll(p_s, -1)) & (s_s == jnp.roll(s_s, -1))
    nxt_same = nxt_same.at[-1].set(False)
    is_last = ~nxt_same
    elem_s = order  # element index at each sorted slot
    next_sib_s = jnp.where(nxt_same, jnp.roll(elem_s, -1), -1)

    # scatter: per element, its next sibling; per (parent, side): the
    # first child (ring entry) and last L-child (in-order anchor)
    next_sib = jnp.zeros(n1, jnp.int32).at[elem_s].set(next_sib_s.astype(jnp.int32))
    is_child = p_s < big  # this sorted slot is a real child row
    tgt_l = jnp.where(is_first & is_child & (s_s == 0), p_s, n1)  # n1 = dump slot
    tgt_r = jnp.where(is_first & is_child & (s_s == 1), p_s, n1)
    tgt_ll = jnp.where(is_last & is_child & (s_s == 0), p_s, n1)
    first_l = jnp.full(n1 + 1, -1, jnp.int32).at[tgt_l].set(elem_s.astype(jnp.int32))[:n1]
    first_r = jnp.full(n1 + 1, -1, jnp.int32).at[tgt_r].set(elem_s.astype(jnp.int32))[:n1]
    last_l = jnp.full(n1 + 1, -1, jnp.int32).at[tgt_ll].set(elem_s.astype(jnp.int32))[:n1]

    has_next_sib = next_sib >= 0
    has_l = first_l >= 0
    has_r = first_r >= 0

    # -- Euler-tour successor ring over 2 tokens per node -------------
    # (directed-edge tour; no VISIT token — see module docstring)
    # ENTER(e) -> ENTER(first_l[e])   if has_l
    #          -> ENTER(first_r[e])   elif has_r
    #          -> EXIT(e)             else
    # EXIT(e)  -> ENTER(next_sib[e])  if has_next_sib
    #          -> post_L(parent[e])   if last sibling and side==L
    #             (post_L(p) = ENTER(first_r[p]) if has_r[p] else EXIT(p))
    #          -> EXIT(parent[e])     if last sibling and side==R
    # EXIT(root) -> itself (ring terminal)
    #
    # TOKEN NUMBERING: tokens are numbered by sibling-sort SLOT, not by
    # element row — ENTER(e) = slot[e], EXIT(e) = m-1-slot[e].  Real
    # traces then put consecutive ring steps at consecutive token
    # indices (a leaf run ENTER(c1)..EXIT(ck) walks slots s, s+1, ...
    # on the way in and mirrored indices on the way out; invalid
    # elements all sort into one contiguous slot range and chain below).
    # Any bijective numbering yields the same ORDER (ranks are compared,
    # never interpreted), so correctness is layout-free.
    m = 2 * n1
    slot = jnp.zeros(n1, jnp.int32).at[order].set(jnp.arange(n1, dtype=jnp.int32))
    ent = slot  # [n1] token id of ENTER(e)
    ext = (m - 1) - slot  # [n1] token id of EXIT(e)
    e_ids = jnp.arange(n1, dtype=jnp.int32)
    post_l = jnp.where(has_r, ent[jnp.clip(first_r, 0, n)], ext[e_ids])  # [n1]
    succ_enter = jnp.where(has_l, ent[jnp.clip(first_l, 0, n)], post_l)
    par = jnp.where(parent < big, parent, root).astype(jnp.int32)
    succ_exit = jnp.where(
        has_next_sib,
        ent[jnp.clip(next_sib, 0, n)],
        jnp.where(side == 0, post_l[par], ext[par]),
    )
    succ_exit = succ_exit.at[root].set(ext[root])  # terminal self-loop
    # token layout: first half = ENTER tokens in slot order, second
    # half = EXIT tokens in REVERSE slot order (ext = m-1-slot)
    succ = jnp.concatenate(
        [succ_enter[order], jnp.flip(succ_exit[order])]
    ).astype(jnp.int32)

    # invalid elements: chain their tokens by index (one run per
    # contiguous range instead of per-token self-loops; their
    # distances are never read — ranks of invalid rows are overwritten
    # below).  The ring-proper tokens keep their successors.
    tok_valid = jnp.concatenate([valid[order], jnp.flip(valid[order])])
    tok_ids = jnp.arange(m, dtype=jnp.int32)
    chain_next = jnp.minimum(tok_ids + 1, m - 1)
    keep = tok_valid | (tok_ids == ext[root]) | (tok_ids == ent[root])
    succ = jnp.where(keep, succ, chain_next)
    # root tokens: ENTER is a valid ring member, EXIT the terminal
    succ = succ.at[ent[root]].set(succ_enter[root])
    succ = succ.at[ext[root]].set(ext[root])

    # in-order anchor: EXIT(last L-child) when L-children exist, else
    # the node's own ENTER; anchors are distinct tokens, so their ring
    # distances order elements exactly (larger distance = earlier)
    anchor = jnp.where(has_l, ext[jnp.clip(last_l, 0, n)], ent[e_ids])  # [n1]
    return succ, anchor


def _order_core(
    parent_in: jax.Array,
    side_in: jax.Array,
    valid_in: jax.Array,
    sib_keys: Optional[Tuple[jax.Array, ...]] = None,
) -> jax.Array:
    """Euler-tour in-order ranking over generic node arrays (element- or
    chain-level).  Without `sib_keys`, rows must obey the (peer, counter)
    order contract (fugue_order); with `sib_keys` (e.g. peer_hi, peer_lo,
    counter arrays) sibling order comes from an explicit lexsort instead
    — row order becomes irrelevant, which the incremental/append path
    needs (appended rows land at the end of the buffer)."""
    n = parent_in.shape[0]
    root = n
    big = jnp.int32(2**30)
    succ, anchor = _ring_and_anchors(parent_in, side_in, valid_in, sib_keys)

    # -- list ranking: distance to terminal ---------------------------
    dist = _rank_dist(succ)

    anchor_dist = dist[anchor]
    rank = anchor_dist[root] - anchor_dist[:n]  # monotone along the traversal
    # pads / unreachable: push to the end
    rank = jnp.where(valid_in, rank, big)
    return rank.astype(jnp.int32)


def visible_order(cols: SeqColumns) -> Tuple[jax.Array, jax.Array]:
    """(perm, visible_count): perm[i] = element index of the i-th element
    in final order, with visible elements first in document order; count
    of visible elements."""
    rank = fugue_order(cols)
    visible = cols.valid & ~cols.deleted
    big = jnp.int32(2**30)
    key = jnp.where(visible, rank, big)  # visible first (stable argsort)
    perm = jnp.argsort(key, stable=True)
    return perm.astype(jnp.int32), visible.sum().astype(jnp.int32)


@jax.named_scope("compact")
def _compact(rank: jax.Array, visible: jax.Array, content: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sort-free compaction shared by both element-table layouts: ranks
    are unique values < rank_bound(N) = 2*(N+1), so a scatter into an
    m-bucket histogram + exclusive cumsum yields each visible element's
    final position directly; invisible rows scatter out of range
    (dropped)."""
    n = rank.shape[0]
    m = rank_bound(n)
    rk = jnp.clip(rank, 0, m - 1)
    hist = jnp.zeros(m, jnp.int32).at[jnp.where(visible, rk, m - 1)].add(
        visible.astype(jnp.int32)
    )
    pos_of_rank = jnp.cumsum(hist) - hist  # exclusive prefix sum
    pos = pos_of_rank[rk]
    count = visible.sum().astype(jnp.int32)
    codes = jnp.full(n, -1, jnp.int32).at[jnp.where(visible, pos, n)].set(
        content, mode="drop"
    )
    return codes, count


def materialize_content(cols: SeqColumns) -> Tuple[jax.Array, jax.Array]:
    """Gather content codes of visible elements in document order.
    Returns (codes i32[N] with tail padding = -1, count)."""
    rank = fugue_order(cols)
    return _compact(rank, cols.valid & ~cols.deleted, cols.content)


class SeqColumnsU(NamedTuple):
    """Row-order-free element table for the incremental/append path:
    peers carried as explicit u64 halves so sibling order needs no
    batch-wide rank dictionary and appended rows may sit anywhere."""

    parent: jax.Array  # i32[N]
    side: jax.Array  # i32[N]
    peer_hi: jax.Array  # u32[N]
    peer_lo: jax.Array  # u32[N]
    counter: jax.Array  # i32[N] (non-negative)
    deleted: jax.Array  # bool[N]
    content: jax.Array  # i32[N]
    valid: jax.Array  # bool[N]


def fugue_order_u(cols: SeqColumnsU) -> jax.Array:
    return _order_core(
        cols.parent,
        cols.side,
        cols.valid,
        sib_keys=(cols.peer_hi, cols.peer_lo, cols.counter.astype(jnp.uint32)),
    )


def materialize_content_u(cols: SeqColumnsU) -> Tuple[jax.Array, jax.Array]:
    """Order + compact for the row-order-free table (content=-1 rows —
    anchors — are invisible)."""
    rank = fugue_order_u(cols)
    visible = cols.valid & ~cols.deleted & (cols.content >= 0)
    return _compact(rank, visible, cols.content)


materialize_content_u_batch = jax.vmap(materialize_content_u)


@doc_batch_jit
def merge_docs_u(cols: SeqColumnsU) -> Tuple[jax.Array, jax.Array]:
    return materialize_content_u_batch(cols)


class ChainColumns(NamedTuple):
    """Chain-contracted document batch (see columnar.contract_chains):
    chain-level tree arrays [C] + element-level arrays [N]."""

    c_parent: jax.Array  # i32[C]
    c_side: jax.Array  # i32[C]
    c_valid: jax.Array  # bool[C]
    head_row: jax.Array  # i32[C]
    chain_id: jax.Array  # i32[N] element -> chain
    deleted: jax.Array  # bool[N]
    content: jax.Array  # i32[N]
    valid: jax.Array  # bool[N]


def chain_positions(
    crank: jax.Array,
    c_valid: jax.Array,
    chain_id: jax.Array,
    head_row: jax.Array,
    visible: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Histogram placement core: (pos i32[N], count) where pos[row] =
    number of visible rows strictly before the row in final document
    order — defined for EVERY row (zero-width/deleted rows included;
    the richtext anchors need exactly that).  Chain base positions from
    a rank histogram + exclusive cumsum, within-chain offsets from row
    cumsums (chain rows are contiguous)."""
    c = crank.shape[0]
    n = chain_id.shape[0]
    vis_i = visible.astype(jnp.int32)
    cid = jnp.clip(chain_id, 0, c)  # dump slot c for pads/overflow
    w = jnp.zeros(c + 1, jnp.int32).at[cid].add(vis_i)[:c]
    m = rank_bound(c)
    rk = jnp.clip(crank, 0, m - 1)
    hist = jnp.zeros(m, jnp.int32).at[jnp.where(c_valid, rk, m - 1)].add(
        jnp.where(c_valid, w, 0)
    )
    base_of_rank = jnp.cumsum(hist) - hist
    base = base_of_rank[rk]  # i32[C]
    row_excl = jnp.cumsum(vis_i) - vis_i
    head_excl = row_excl[jnp.clip(head_row, 0, n - 1)]  # i32[C]
    within = row_excl - head_excl[jnp.clip(chain_id, 0, c - 1)]
    pos = base[jnp.clip(chain_id, 0, c - 1)] + within
    count = vis_i.sum().astype(jnp.int32)
    return pos, count


def _place_by_chain_scatter(
    crank: jax.Array,
    c_valid: jax.Array,
    chain_id: jax.Array,
    head_row: jax.Array,
    visible: jax.Array,
    content: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Histogram placement (see chain_positions) + positional scatter of
    the content codes."""
    n = chain_id.shape[0]
    pos, count = chain_positions(crank, c_valid, chain_id, head_row, visible)
    codes = jnp.full(n, -1, jnp.int32).at[jnp.where(visible, pos, n)].set(
        content, mode="drop"
    )
    return codes, count


@jax.named_scope("place")
def _place_by_chain_sort(
    crank: jax.Array,
    c_valid: jax.Array,
    head_row: jax.Array,
    visible: jax.Array,
    content: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Sort placement: expand chain ranks to elements with a C-scatter
    of telescoping rank deltas at head rows + one N-cumsum (chain rows
    are contiguous and chain ids ascend with row, so the cumsum
    reconstructs crank[chain_id[row]] exactly, including int32
    wraparound), then ONE stable sort of (key, content) realizes the
    whole placement: ascending rank = document order, stability keeps
    within-chain row order.  Every invisible row (deleted, pad,
    overflow) gets the absolute max key so it sorts behind ALL visible
    rows and the first `count` sorted codes are exactly the document."""
    n = visible.shape[0]
    vis_i = visible.astype(jnp.int32)
    # invalid chains are trailing (both contraction paths), so the
    # telescoping prev of any valid chain is valid (or the 0 seed)
    prev = jnp.concatenate([jnp.zeros(1, crank.dtype), crank[:-1]])
    delta = jnp.where(c_valid, crank - prev, 0)
    seg = (
        jnp.zeros(n + 1, jnp.int32)
        .at[jnp.where(c_valid, head_row, n)]
        .add(delta, mode="drop")[:n]
    )
    crank_elem = jnp.cumsum(seg)
    key = jnp.where(
        visible, crank_elem.astype(jnp.uint32), jnp.uint32(0xFFFFFFFF)
    )
    _, content_sorted = jax.lax.sort((key, content), num_keys=1, is_stable=True)
    count = vis_i.sum().astype(jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    codes = jnp.where(idx < count, content_sorted, jnp.int32(-1))
    return codes, count


def chain_materialize(cols: ChainColumns) -> Tuple[jax.Array, jax.Array]:
    """Merge via chain contraction: rank C chains (C << N), then place
    all N elements by rank expansion (C-scatter + N-cumsum) and one
    stable N-row sort (_place_by_chain_sort) — the gather-heavy ranking
    runs on the contracted tree only.
    Returns (codes i32[N] padded with -1, visible count)."""
    crank = _order_core(cols.c_parent, cols.c_side, cols.c_valid)  # i32[C]
    visible = cols.valid & ~cols.deleted
    return _place_by_chain_sort(
        crank, cols.c_valid, cols.head_row, visible, cols.content
    )


chain_materialize_batch = jax.vmap(chain_materialize)


def _tick_rank_obs(n_docs: int, n_nodes: int) -> None:
    """rank.ring_tokens{algo} (docs/OBSERVABILITY.md) — ticked at
    host-level jit entry points only (inside a trace the count would be
    trace-time noise)."""
    from ..obs import metrics as obs_m

    m = rank_bound(n_nodes)
    label = ":".join(_resolve_rank_spec(None, m))
    obs_m.counter("rank.ring_tokens").inc(n_docs * m, algo=label)


@doc_batch_jit
def _chain_merge_docs_jit(cols: ChainColumns) -> Tuple[jax.Array, jax.Array]:
    return chain_materialize_batch(cols)


def chain_merge_docs(cols: ChainColumns) -> Tuple[jax.Array, jax.Array]:
    """One launch: chain-contracted merge for a doc batch ([D,C]/[D,N])."""
    _tick_rank_obs(cols.c_parent.shape[0], cols.c_parent.shape[1])
    return _chain_merge_docs_jit(cols)


@jax.named_scope("checksum")
def _weighted_checksum(codes: jax.Array) -> jax.Array:
    """Order-sensitive per-doc checksum of merged codes [D, N] -> [D]."""
    n = codes.shape[1]
    wgt = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)) % jnp.uint32(1 << 30)
    return ((jnp.where(codes >= 0, codes, 0).astype(jnp.uint32) * wgt[None, :]) % (1 << 30)).sum(
        axis=1, dtype=jnp.uint32
    )


@doc_batch_jit
def _chain_merge_docs_checksum_jit(cols: ChainColumns) -> Tuple[jax.Array, jax.Array]:
    codes, counts = chain_materialize_batch(cols)
    return _weighted_checksum(codes), counts


def chain_merge_docs_checksum(cols: ChainColumns) -> Tuple[jax.Array, jax.Array]:
    _tick_rank_obs(cols.c_parent.shape[0], cols.c_parent.shape[1])
    return _chain_merge_docs_checksum_jit(cols)


# ---- packed single-buffer transport (ingest pipeline) ----------------
# The e2e pipeline ships one chunk as ONE contiguous u8 buffer instead
# of 8 separate device_puts with loose dtypes: one transfer per chunk,
# and the byte-tight layout (u16 chain ids, u8 flags) is ~1.3x smaller
# than the i32 ChainColumns transport.  Layout per doc
# row (little-endian, matching both x86 hosts and TPU bitcast):
#   [0        : 2C)        c_parent  u16   (0xFFFF == -1 root)
#   [2C       : 2C+2N)     chain_id  u16   (pad rows carry 0; the dump
#                                           remap to pad_c happens
#                                           on-device via the valid mask)
#   [..       : +4C)       head_row  i32
#   [..       : +4N)       content   i32   (-1 == invisible)
#   [..       : +C)        c_side    u8
#   [..       : +C)        c_valid   u8
#   [..       : +N)        deleted   u8
#   [..       : +N)        valid     u8
# Total 8C + 8N bytes.  Requires pad_c < 0xFFFF.


def packed_row_bytes(pad_c: int, pad_n: int) -> int:
    assert pad_c < 0xFFFF, "u16 chain ids need pad_c < 65535"
    return 8 * pad_c + 8 * pad_n


def pack_chain_doc_into(cols: ChainColumns, out_row: np.ndarray) -> None:
    """Serialize one doc's numpy ChainColumns into a packed u8 row
    (shape [packed_row_bytes(C, N)]); the inverse of the in-jit
    unpack in chain_merge_docs_packed."""
    c = cols.c_parent.shape[0]
    n = cols.chain_id.shape[0]
    assert out_row.dtype == np.uint8 and out_row.shape[0] == packed_row_bytes(c, n)
    o = 0

    def sec(nbytes):
        nonlocal o
        s = out_row[o : o + nbytes]
        o += nbytes
        return s

    sec(2 * c).view("<u2")[:] = cols.c_parent.astype(np.int32).astype(np.uint16)
    sec(2 * n).view("<u2")[:] = cols.chain_id.astype(np.int32).astype(np.uint16)
    sec(4 * c).view("<i4")[:] = cols.head_row.astype(np.int32)
    sec(4 * n).view("<i4")[:] = cols.content.astype(np.int32)
    sec(c)[:] = cols.c_side.astype(np.uint8)
    sec(c)[:] = cols.c_valid.astype(np.uint8)
    sec(n)[:] = cols.deleted.astype(np.uint8)
    sec(n)[:] = cols.valid.astype(np.uint8)
    assert o == out_row.shape[0]


@jax.named_scope("unpack")
def _unpack_chain_batch(packed: jax.Array, pad_c: int, pad_n: int) -> ChainColumns:
    """In-jit inverse of pack_chain_doc_into ([D, W] u8 -> ChainColumns)."""
    d = packed.shape[0]
    c, n = pad_c, pad_n
    offs = [0]
    for nbytes in (2 * c, 2 * n, 4 * c, 4 * n, c, c, n, n):
        offs.append(offs[-1] + nbytes)

    def sec(i):
        return packed[:, offs[i] : offs[i + 1]]

    def u16(i, count):
        return jax.lax.bitcast_convert_type(
            sec(i).reshape(d, count, 2), jnp.uint16
        ).astype(jnp.int32)

    def i32(i, count):
        return jax.lax.bitcast_convert_type(sec(i).reshape(d, count, 4), jnp.int32)

    cp = u16(0, c)
    return ChainColumns(
        c_parent=jnp.where(cp == 0xFFFF, -1, cp),
        c_side=sec(4).astype(jnp.int32),
        c_valid=sec(5).astype(bool),
        head_row=i32(2, c),
        chain_id=u16(1, n),
        deleted=sec(6).astype(bool),
        content=i32(3, n),
        valid=sec(7).astype(bool),
    )


@doc_batch_jit
def chain_merge_docs_packed(packed: jax.Array, pad_c: int, pad_n: int):
    """One launch: unpack the u8 transport buffer + chain merge."""
    return chain_materialize_batch(_unpack_chain_batch(packed, pad_c, pad_n))


@doc_batch_jit
def chain_merge_docs_packed_checksum(packed: jax.Array, pad_c: int, pad_n: int):
    codes, counts = chain_materialize_batch(_unpack_chain_batch(packed, pad_c, pad_n))
    return _weighted_checksum(codes), counts


def merge_text_payloads_packed(
    payloads,
    cid,
    pad_c: int,
    pad_n: int,
    chunk: int,
    n_docs: int,
    budget_s: float = float("inf"),
):
    """The end-to-end bulk-import pipeline (the benchmark's packed64
    cell and chip_smoke.py's flagship step): per document, native payload decode
    -> chain contraction -> packed u8 row on a pool of decode threads
    (the native explode releases the GIL, so decodes overlap each other
    AND the asynchronous device merges); per ``chunk`` documents one
    device_put and one chain_merge_docs_packed_checksum launch, with up
    to three chunks decoded ahead.  ``payloads`` is a list of
    ``(bytes, n_ops)``; document i takes entry ``i % len(payloads)``.
    Stops after ``n_docs`` documents, or at the first chunk boundary
    past ``budget_s``.

    Returns ``(outs, docs_done, ops_done, seconds, n_workers)``: each
    launch's ``(checksums, counts)`` device arrays in launch order, and
    the wall time from the first decode submitted to the last launch
    finished.  Callers warm the jit first (one launch on a zero buffer)
    to keep the compile out of ``seconds``."""
    import time
    from concurrent.futures import ThreadPoolExecutor

    from ..obs import metrics as obs
    from ..utils import tracing
    from .columnar import contract_chains, extract_seq_from_payload, pack_chain_row

    if n_docs % chunk:
        raise ValueError(f"n_docs={n_docs} is not a multiple of chunk={chunk}")
    row_w = packed_row_bytes(pad_c, pad_n)
    # one trace id per launch round; a document's decode runs under the
    # id of the round that will take it
    call_id = tracing.new_trace_id("k")

    def decode_one(i: int):
        with tracing.span("packed.decode_one", trace_id=f"{call_id}.{i // chunk}", doc=i):
            pl, p_ops = payloads[i % len(payloads)]
            with tracing.span("packed.extract", bytes=len(pl)):
                exd = extract_seq_from_payload(pl, cid)
            row = np.empty(row_w, np.uint8)
            with tracing.span("packed.contract"):
                chains = contract_chains(exd)
            with tracing.span("packed.pack"):  # the u8 row, padded to the row's widths
                pack_chain_row(exd, chains, pad_c, pad_n, row)
            return row, p_ops

    n_workers = min(8, os.cpu_count() or 1)
    done = 0
    ops = 0
    outs = []
    ahead = obs.histogram(
        "packed.decoded_ahead",
        "documents of the next launch already decoded when the launcher "
        "asked (0 = host-bound, chunk = device-bound)",
        buckets=range(9),
    )
    n_docs_c, n_bytes_c, n_put_c, n_launch_c = (
        obs.counter(f"packed.{n}_total")
        for n in ("docs_decoded", "payload_bytes", "row_bytes_put", "launches")
    )
    pool = ThreadPoolExecutor(max_workers=n_workers)
    try:
        t0 = time.perf_counter()
        futs = [pool.submit(decode_one, i) for i in range(min(3 * chunk, n_docs))]
        next_submit = len(futs)
        while done < n_docs and (time.perf_counter() - t0) < budget_s:
            with tracing.span(
                "packed.round", trace_id=f"{call_id}.{done // chunk}", docs=chunk
            ):
                group = futs[done : done + chunk]
                ahead.observe(sum(f.done() for f in group))
                docs = []
                with tracing.span("packed.wait_decoded"):
                    for j, f in enumerate(group):
                        c, p_ops = f.result()
                        docs.append(c)
                        ops += p_ops
                        futs[done + j] = None  # release decoded columns
                with tracing.span("packed.submit"):
                    while next_submit < n_docs and next_submit < done + 3 * chunk:
                        futs.append(pool.submit(decode_one, next_submit))
                        next_submit += 1
                with tracing.span("packed.stack"):
                    stacked = np.stack(docs)
                with tracing.span("packed.put"):
                    dev = jax.device_put(stacked)  # one put per chunk
                del stacked  # jax holds it while it must: the next stack reuses the block
                with tracing.span("packed.dispatch"):
                    outs.append(chain_merge_docs_packed_checksum(dev, pad_c, pad_n))  # async
                n_docs_c.inc(chunk)
                n_bytes_c.inc(
                    sum(len(payloads[i % len(payloads)][0]) for i in range(done, done + chunk))
                )
                n_put_c.inc(chunk * row_w)
                n_launch_c.inc()
                done += chunk
        with tracing.span("packed.drain"):
            jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
    return outs, done, ops, dt, n_workers


def chain_contract_materialize_u(
    cols: SeqColumnsU, c_pad: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side chain contraction + order + compaction for the
    row-order-free layout (the resident-batch path).

    Chains (right-spine runs, columnar.contract_chains conditions) are
    detected on device: row i links to row i-1 iff parent==i-1, side=R,
    row i-1 has exactly one child and no L-children, and row i has no
    L-children.  Cross-epoch runs simply stay split (appended rows are
    only adjacent within their block) — correctness is unaffected, the
    contraction is just slightly less aggressive.

    `c_pad` is the static chain budget; returns (codes, count,
    n_chains).  When n_chains > c_pad the output is INVALID and the
    caller must retry with a bigger budget (DeviceDocBatch does)."""
    n = cols.parent.shape[0]
    valid = cols.valid
    pgt = jnp.clip(cols.parent, 0, n - 1)
    has_parent = valid & (cols.parent >= 0)
    cc = jnp.zeros(n, jnp.int32).at[jnp.where(has_parent, pgt, n - 1)].add(
        has_parent.astype(jnp.int32)
    )
    is_l = has_parent & (cols.side == 0)
    lc = jnp.zeros(n, jnp.int32).at[jnp.where(is_l, pgt, n - 1)].add(is_l.astype(jnp.int32))

    idx = jnp.arange(n, dtype=jnp.int32)
    prev_ok = jnp.concatenate([jnp.zeros(1, bool), valid[:-1]])
    link = (
        valid
        & prev_ok
        & (cols.parent == idx - 1)
        & (cols.side == 1)
        & (jnp.roll(cc, 1) == 1)
        & (jnp.roll(lc, 1) == 0)
        & (lc == 0)
    )
    link = link.at[0].set(False)
    is_head = valid & ~link
    chain_id = jnp.cumsum(is_head.astype(jnp.int32)) - 1  # per valid row
    chain_id = jnp.where(valid, chain_id, c_pad)  # pads -> dump
    n_chains = is_head.sum().astype(jnp.int32)

    cid_clip = jnp.clip(chain_id, 0, c_pad)
    # chain-level attributes scattered from head rows (chain_id is the
    # compact index — no sort needed)
    def head_scatter(src, fill):
        return jnp.full(c_pad + 1, fill, src.dtype).at[
            jnp.where(is_head, cid_clip, c_pad)
        ].set(src, mode="drop")[:c_pad]

    head_row = head_scatter(idx, 0)
    c_parent_row = head_scatter(jnp.where(cols.parent >= 0, cols.parent, -1), -1)
    c_parent = jnp.where(
        c_parent_row >= 0, chain_id[jnp.clip(c_parent_row, 0, n - 1)], -1
    ).astype(jnp.int32)
    c_side = head_scatter(cols.side.astype(jnp.int32), 0)
    c_hi = head_scatter(cols.peer_hi, 0)
    c_lo = head_scatter(cols.peer_lo, 0)
    c_ctr = head_scatter(cols.counter.astype(jnp.uint32), 0)
    c_valid = jnp.arange(c_pad) < n_chains

    crank = _order_core(
        c_parent, c_side, c_valid, sib_keys=(c_hi, c_lo, c_ctr)
    )  # [c_pad]

    visible = valid & ~cols.deleted & (cols.content >= 0)
    codes, count = _place_by_chain_sort(
        crank, c_valid, head_row, visible, cols.content
    )
    return codes, count, n_chains


@doc_batch_jit
def _chain_merge_docs_u_jit(cols: SeqColumnsU, c_pad: int):
    return jax.vmap(lambda c: chain_contract_materialize_u(c, c_pad))(cols)


def chain_merge_docs_u(cols: SeqColumnsU, c_pad: int):
    _tick_rank_obs(cols.parent.shape[0], c_pad)
    return _chain_merge_docs_u_jit(cols, c_pad)


@jax.jit
def materialize_by_key(cols: SeqColumnsU, key_hi, key_lo):
    """Visible content from standing order keys (incremental path):
    one multi-key sort by (key_hi, key_lo) replaces the rank solve —
    the host ShadowOrder (parallel/order_maintenance.py) guarantees
    ascending key == Fugue traversal order.  [D, N] -> (codes, counts)
    with the same contract as chain_merge_docs_u."""
    d, n = cols.content.shape
    inf = jnp.uint32(0xFFFFFFFF)
    hi = jnp.where(cols.valid, key_hi, inf)
    lo = jnp.where(cols.valid, key_lo, inf)
    visible = cols.valid & ~cols.deleted & (cols.content >= 0)
    _hi_s, _lo_s, content_s, vis_s = jax.lax.sort(
        (hi, lo, cols.content, visible.astype(jnp.int32)), dimension=1, num_keys=2
    )
    vis_s = vis_s.astype(bool)
    pos = jnp.cumsum(vis_s.astype(jnp.int32), axis=1) - 1
    counts = vis_s.sum(axis=1)
    target = jnp.where(vis_s, pos, n)  # invisible rows -> dump column
    out = jnp.full((d, n + 1), -1, cols.content.dtype)
    d_idx = jnp.broadcast_to(jnp.arange(d)[:, None], (d, n))
    out = out.at[d_idx, target].set(content_s, mode="drop")
    return out[:, :n], counts


# batched-over-documents variants --------------------------------------
fugue_order_batch = jax.vmap(fugue_order)
visible_order_batch = jax.vmap(visible_order)
materialize_content_batch = jax.vmap(materialize_content)

# jitted single-doc entry (one compilation per padded size — callers
# should bucket-pad N, e.g. to powers of two)
materialize_content_jit = jax.jit(materialize_content)


def pad_bucket(n: int, floor: int = 64) -> int:
    """Next power-of-two bucket >= n (bounds XLA recompilations)."""
    b = floor
    while b < n:
        b *= 2
    return b


def pad_seq_columns(cols: SeqColumns, n: int) -> SeqColumns:
    """Pad numpy SeqColumns to n rows (invalid tail)."""

    def pad(a, fill):
        if a.shape[0] == n:
            return a
        out = np.full(n, fill, a.dtype)
        out[: a.shape[0]] = a
        return out

    return SeqColumns(
        parent=pad(cols.parent, -1),
        side=pad(cols.side, 0),
        peer=pad(cols.peer, 0),
        counter=pad(cols.counter, 0),
        deleted=pad(cols.deleted, True),
        content=pad(cols.content, -1),
        valid=pad(cols.valid, False),
    )


@doc_batch_jit
def _merge_docs_jit(cols: SeqColumns) -> Tuple[jax.Array, jax.Array]:
    return materialize_content_batch(cols)


def merge_docs(cols: SeqColumns) -> Tuple[jax.Array, jax.Array]:
    """One XLA launch: resolve order + materialize visible content for a
    whole batch of documents.  cols arrays are [D, N]."""
    _tick_rank_obs(cols.parent.shape[0], cols.parent.shape[1])
    return _merge_docs_jit(cols)


@doc_batch_jit
def _merge_docs_checksum_jit(cols: SeqColumns) -> Tuple[jax.Array, jax.Array]:
    codes, counts = materialize_content_batch(cols)
    n = codes.shape[1]
    w = (jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)) % jnp.uint32(1 << 30)
    cs = ((jnp.where(codes >= 0, codes, 0).astype(jnp.uint32) * w[None, :]) % (1 << 30)).sum(
        axis=1, dtype=jnp.uint32
    )
    return cs, counts


def merge_docs_checksum(cols: SeqColumns) -> Tuple[jax.Array, jax.Array]:
    """Merge but return only a per-doc order-sensitive checksum [D] +
    counts [D].  Used by benchmarks: the merged state stays device-
    resident (the fleet model); only O(D) scalars cross the host link."""
    _tick_rank_obs(cols.parent.shape[0], cols.parent.shape[1])
    return _merge_docs_checksum_jit(cols)
