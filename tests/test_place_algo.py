"""The sort placement the merge launches (``_place_by_chain_sort``)
against the histogram + scatter formulation (``_place_by_chain_scatter``,
whose core ``chain_positions`` the richtext batch uses): identical
(codes, count) on real merged docs."""
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from loro_tpu import LoroDoc
from loro_tpu.ops import fugue_batch as fb
from loro_tpu.ops.columnar import contract_chains, extract_seq_container


def _chain_cols(doc, name="t"):
    cid = doc.get_text(name).id
    ex = extract_seq_container(doc.oplog.changes_in_causal_order(), cid)
    ch = contract_chains(ex)
    c = ch.parent.shape[0]
    return fb.ChainColumns(
        c_parent=jnp.asarray(ch.parent),
        c_side=jnp.asarray(ch.side),
        c_valid=jnp.asarray(ch.valid),
        head_row=jnp.asarray(ch.head_row),
        chain_id=jnp.asarray(ch.chain_id),
        deleted=jnp.asarray(ex.deleted),
        content=jnp.asarray(ex.content),
        valid=jnp.asarray(np.ones(ex.n, bool)),
    )


def _both_placements(cols):
    c = cols.c_parent.shape[0]
    crank = fb._order_core(cols.c_parent, cols.c_side, cols.c_valid)
    visible = cols.valid & ~cols.deleted
    chain_id = jnp.where(cols.valid, cols.chain_id, c)
    a = fb._place_by_chain_sort(
        crank, cols.c_valid, cols.head_row, visible, cols.content
    )
    b = fb._place_by_chain_scatter(
        crank, cols.c_valid, chain_id, cols.head_row, visible, cols.content
    )
    return a, b


@pytest.mark.parametrize("seed", range(6))
def test_sort_matches_scatter_on_merged_docs(seed):
    rng = random.Random(4000 + seed)
    docs = [LoroDoc(peer=i + 1) for i in range(3)]
    for _ in range(70):
        d = rng.choice(docs)
        t = d.get_text("t")
        if len(t) == 0 or rng.random() < 0.55:
            t.insert(
                rng.randint(0, len(t)),
                "".join(rng.choice("wxyz") for _ in range(rng.randint(1, 4))),
            )
        else:
            pos = rng.randint(0, len(t) - 1)
            t.delete(pos, min(rng.randint(1, 3), len(t) - pos))
        if rng.random() < 0.3:
            src, dst = rng.sample(docs, 2)
            dst.import_(src.export_updates(dst.oplog_vv()))
    for src in docs:
        for dst in docs:
            if src is not dst:
                dst.import_(src.export_updates(dst.oplog_vv()))
    cols = _chain_cols(docs[0])
    (codes_a, cnt_a), (codes_b, cnt_b) = _both_placements(cols)
    assert int(cnt_a) == int(cnt_b)
    np.testing.assert_array_equal(np.asarray(codes_a), np.asarray(codes_b))


def test_sort_matches_scatter_with_padding():
    """Bucket-padded columns: pad rows/chains must never leak into the
    placed region under either formulation."""
    doc = LoroDoc(peer=7)
    t = doc.get_text("t")
    t.insert(0, "hello world")
    t.delete(2, 3)
    t.insert(5, "XY")
    cols = _chain_cols(doc)
    n, c = cols.content.shape[0], cols.c_parent.shape[0]
    pad_n, pad_c = n + 13, c + 5

    def padn(a, fill):
        return jnp.concatenate([a, jnp.full(pad_n - n, fill, a.dtype)])

    def padc(a, fill):
        return jnp.concatenate([a, jnp.full(pad_c - c, fill, a.dtype)])

    padded = fb.ChainColumns(
        c_parent=padc(cols.c_parent, -1),
        c_side=padc(cols.c_side, 0),
        c_valid=padc(cols.c_valid, False),
        head_row=padc(cols.head_row, 0),
        chain_id=padn(cols.chain_id, pad_c),
        deleted=padn(cols.deleted, False),
        content=padn(cols.content, 0),
        valid=padn(cols.valid, False),
    )
    (codes_a, cnt_a), (codes_b, cnt_b) = _both_placements(padded)
    (codes_u, cnt_u), _ = _both_placements(cols)
    assert int(cnt_a) == int(cnt_b) == int(cnt_u)
    np.testing.assert_array_equal(np.asarray(codes_a), np.asarray(codes_b))
    np.testing.assert_array_equal(
        np.asarray(codes_a)[: int(cnt_u)], np.asarray(codes_u)[: int(cnt_u)]
    )
