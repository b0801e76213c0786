"""The device ring against its host mirror, and the extract cache's
schema gate.  (The rank algorithms this file once fuzzed are gone; the
rank that is left is held to the textbook in tests/test_rank.py.)
"""
import numpy as np

import jax
import jax.numpy as jnp

from loro_tpu.ops import rank_model as rm
from loro_tpu.ops.fugue_batch import _ring_and_anchors

from test_rank import _fuzz_docs


def test_device_ring_matches_host_mirror():
    """_ring_and_anchors (in-jit) against rank_model.build_ring (the
    host reference), token for token."""
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import contract_chains, extract_seq_container

    docs = _fuzz_docs(2, 120, 0.3, seed=5)
    cid = ContainerID.root("t", ContainerType.Text)
    for d in docs:
        ex = extract_seq_container(d.oplog.changes_in_causal_order(), cid)
        ch = contract_chains(ex)
        pad_c = ch.n_chains + 29
        parent = np.full(pad_c, -1, np.int32)
        parent[: ch.n_chains] = ch.parent
        side = np.zeros(pad_c, np.int32)
        side[: ch.n_chains] = ch.side
        valid = np.zeros(pad_c, bool)
        valid[: ch.n_chains] = True
        succ_dev, _ = jax.jit(_ring_and_anchors)(
            jnp.asarray(parent), jnp.asarray(side), jnp.asarray(valid)
        )
        succ_host = rm.build_ring(parent, side, valid)
        np.testing.assert_array_equal(np.asarray(succ_dev), succ_host)


# ---------------------------------------------------------------------------
# trace-cache schema tag (satellite: stale caches rebuild, never decode)
# ---------------------------------------------------------------------------


def test_extract_cache_schema_gate(tmp_path):
    from loro_tpu.bench_utils import CACHE_SCHEMA, _load_extract_cache

    base = dict(
        parent=np.array([-1, 0], np.int32),
        side=np.array([1, 1], np.int32),
        peer=np.zeros(2, np.int32),
        counter=np.arange(2, dtype=np.int32),
        deleted=np.zeros(2, bool),
        content=np.array([97, 98], np.int32),
        valid=np.ones(2, bool),
        peers=np.array([1], np.uint64),
        n_ops=2,
    )
    legacy = tmp_path / "legacy.npz"  # pre-schema cache: no tag
    np.savez_compressed(legacy, **base)
    assert _load_extract_cache(str(legacy)) is None
    stale = tmp_path / "stale.npz"
    np.savez_compressed(stale, **base, schema=np.int64(CACHE_SCHEMA - 1))
    assert _load_extract_cache(str(stale)) is None
    good = tmp_path / "good.npz"
    np.savez_compressed(good, **base, schema=np.int64(CACHE_SCHEMA))
    ex, n_ops = _load_extract_cache(str(good))
    assert n_ops == 2 and ex.n == 2
    assert _load_extract_cache(str(tmp_path / "absent.npz")) is None


def test_extract_cache_corrupt_file_returns_none(tmp_path):
    """A truncated/corrupt npz (a run killed mid-savez) must take
    the rebuild path, not crash every later run."""
    from loro_tpu.bench_utils import _load_extract_cache

    bad = tmp_path / "trunc.npz"
    bad.write_bytes(b"PK\x03\x04 not a real zip")
    assert _load_extract_cache(str(bad)) is None
