"""Blocked two-level rank + ring run-coalescing (ISSUE 6).

Every ranking algorithm must produce BIT-IDENTICAL distances on every
ring (the merge kernels compare ranks, so identical dists => identical
merges); the fuzz here drives the adversarial shapes the coalescing and
blocking transforms care about — single-token rings, one giant run,
run-length-1 (zero coalescing headroom), rings straddling block and
pad_bucket boundaries, tombstone-heavy documents — against the Wyllie
oracle and the host ``models/`` engine.  Perf is guarded by COUNTS
(gather rows from ops.rank_model), never wall clock.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from loro_tpu.errors import ConfigError
from loro_tpu.ops import rank_model as rm
from loro_tpu.ops.fugue_batch import (
    _blocked_dist,
    _coalesced_dist,
    _ring_and_anchors,
    _ruling_dist,
    _wyllie_dist,
    ring_run_heads,
)


def _random_ring(rng, m):
    """Random ring over a live subset; unused tokens self-loop."""
    live = rng.choice(m, size=rng.integers(2, m + 1), replace=False)
    p = rng.permutation(live).astype(np.int32)
    succ = np.arange(m, dtype=np.int32)
    succ[p[:-1]] = p[1:]
    return succ


def _runs_ring(m, run_len, seed):
    """Single chain walking index-consecutive runs of `run_len` tokens
    in shuffled run order (the coalescer's best case at mean run
    ~run_len)."""
    rng = np.random.default_rng(seed)
    starts = np.arange(0, m, run_len)
    order = rng.permutation(len(starts))
    succ = np.arange(1, m + 1, dtype=np.int32)
    succ[-1] = m - 1
    for a, b in zip(order[:-1], order[1:]):
        succ[min(starts[a] + run_len, m) - 1] = starts[b]
    last = starts[order[-1]]
    succ[min(last + run_len, m) - 1] = min(last + run_len, m) - 1
    return succ


def _assert_all_algos_match(succ, budget=None):
    s = jnp.asarray(succ)
    want = np.asarray(jax.jit(_wyllie_dist)(s))
    for name, fn in (
        ("ruling", _ruling_dist),
        ("blocked", lambda x: _blocked_dist(x)),
        ("blocked_b128", lambda x: _blocked_dist(x, 128)),
        ("coalesced", lambda x: _coalesced_dist(x)),
        ("coalesced_budget", lambda x: _coalesced_dist(x, budget)),
    ):
        if name == "coalesced_budget" and budget is None:
            continue
        got = np.asarray(jax.jit(fn)(s))
        np.testing.assert_array_equal(got, want, err_msg=name)
        d_sim, _ = rm.simulate(
            succ, name.split("_")[0], r_pad=budget if "budget" in name else None
        )
        np.testing.assert_array_equal(d_sim, want, err_msg=f"sim:{name}")


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("m", [5, 64, 257, 1000])
def test_algos_match_wyllie_random_rings(m, seed):
    rng = np.random.default_rng(seed)
    _assert_all_algos_match(_random_ring(rng, m))


def test_single_and_tiny_rings():
    """Single op: ring of 1-2 live tokens among self-loops."""
    for m in (1, 2, 3):
        succ = np.arange(m, dtype=np.int32)
        _assert_all_algos_match(succ)
    succ = np.arange(4, dtype=np.int32)
    succ[2] = 0  # one edge, rest terminals
    _assert_all_algos_match(succ)


def test_all_one_run():
    """succ[i] = i+1: the whole ring is ONE run — the contracted ring
    collapses to a single super-node and any budget suffices."""
    m = 1024
    succ = np.arange(1, m + 1, dtype=np.int32)
    succ[-1] = m - 1
    _assert_all_algos_match(succ, budget=128)
    # the chain + the terminal (a terminal is always its own run)
    assert int(rm.run_heads(succ).sum()) <= 2


def test_run_length_one_worst_case():
    """Reversed chain succ[i] = i-1: ZERO index-adjacent runs (the
    coalescer's worst case, n_runs == m) — the default budget must stay
    exact and the tight-budget variant must refuse in the simulator."""
    m = 512
    succ = np.concatenate([[0], np.arange(m - 1)]).astype(np.int32)
    assert int(rm.run_heads(succ).sum()) == m
    _assert_all_algos_match(succ)  # r_pad=None is always safe
    with pytest.raises(ValueError):
        rm.simulate(succ, "coalesced", r_pad=128)


@pytest.mark.parametrize("m", [127, 128, 129, 1023, 1024, 1025, 4097])
def test_blocked_straddles_block_boundaries(m):
    """Ring lengths around the 128-lane quantum and the default 1024
    block, incl. block > ring."""
    rng = np.random.default_rng(m)
    succ = _random_ring(rng, m)
    s = jnp.asarray(succ)
    want = np.asarray(jax.jit(_wyllie_dist)(s))
    for block in (128, 1024, 8192):
        got = np.asarray(jax.jit(lambda x, b=block: _blocked_dist(x, b))(s))
        np.testing.assert_array_equal(got, want, err_msg=f"block={block}")


def _fuzz_docs(n_docs, n_rounds, delete_p, seed):
    import loro_tpu as lt

    rng = np.random.default_rng(seed)
    docs = []
    for _ in range(n_docs):
        a, b = lt.LoroDoc(peer=1), lt.LoroDoc(peer=2)
        for _ in range(n_rounds):
            for d in (a, b):
                t = d.get_text("t")
                pos = int(rng.integers(0, len(t) + 1))
                if len(t) > 2 and rng.random() < delete_p:
                    t.delete(min(pos, len(t) - 1), 1)
                else:
                    t.insert(pos, chr(97 + int(rng.integers(0, 26))))
            if rng.random() < 0.2:
                b.import_(a.export_updates(b.oplog_vv()))
        b.import_(a.export_updates(b.oplog_vv()))
        a.import_(b.export_updates(a.oplog_vv()))
        docs.append(a)
    return docs


def _batched_cols(docs, pad_n, pad_c):
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import chain_columns, extract_seq_container
    from loro_tpu.ops.fugue_batch import ChainColumns

    cid = ContainerID.root("t", ContainerType.Text)
    exs = [extract_seq_container(d.oplog.changes_in_causal_order(), cid) for d in docs]
    cols = [chain_columns(e, pad_n=pad_n, pad_c=pad_c) for e in exs]
    return ChainColumns(
        *[np.stack([getattr(c, f) for c in cols]) for f in ChainColumns._fields]
    )


ALL_SPECS = (
    "xla:wyllie",
    "xla:ruling",
    "xla:blocked",
    "xla:coalesced",
    "pallas:ruling",
    "pallas:blocked",
    "pallas:coalesced",
)


def test_weighted_pallas_wide_domain():
    """A >65536-token ring that coalesces to a short super-node ring
    still carries pre-contraction distances past u16: the weighted
    pallas sub-rank must route to the wide (i32) kernel, not the packed
    one (silent overflow regression guard), and weighted callers must
    be forced to declare their distance domain."""
    from loro_tpu.ops.pallas_rank import wyllie_rank

    m = 70000  # > 65536, coalesces to ~m/L runs
    succ = _runs_ring(m, 512, seed=1)
    want = np.asarray(jax.jit(_wyllie_dist)(jnp.asarray(succ)))
    got = np.asarray(
        jax.jit(lambda x: _coalesced_dist(x, 512, use_pallas=True))(
            jnp.asarray(succ)
        )
    )
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="dist_bound"):
        wyllie_rank(
            jnp.arange(256, dtype=jnp.int32),
            interpret=True,
            weights=jnp.zeros(256, jnp.int32),
        )


def test_pallas_coalesced_at_vmem_cap_falls_back():
    """m == PALLAS_RANK_MAX_M with the default budget: the contracted
    ring is r+1 = cap+1 tokens, which cannot lane-pad into VMEM —
    _coalesced_dist must fall back to the XLA weighted ruling instead
    of raising at trace time for a ring pallas_rank_applicable
    approved (review regression)."""
    from loro_tpu.ops.pallas_rank import PALLAS_RANK_MAX_M

    m = PALLAS_RANK_MAX_M
    succ = _runs_ring(m, 4096, seed=3)
    want = np.asarray(jax.jit(_wyllie_dist)(jnp.asarray(succ)))
    got = np.asarray(
        jax.jit(lambda x: _coalesced_dist(x, None, use_pallas=True))(
            jnp.asarray(succ)
        )
    )
    np.testing.assert_array_equal(got, want)


def test_merge_specs_match_host_tombstone_heavy():
    """Tombstone-heavy concurrent docs (70% deletes): every rank spec
    must reproduce the host engine byte-for-byte, and the rank
    checksums must agree across specs (identical distances)."""
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import contract_chains, extract_seq_container
    from loro_tpu.ops.fugue_batch import chain_merge_docs_v, chain_rank_checksum_v

    docs = _fuzz_docs(3, 120, 0.7, seed=7)
    cid = ContainerID.root("t", ContainerType.Text)
    exs = [extract_seq_container(d.oplog.changes_in_causal_order(), cid) for d in docs]
    pad_n = max(e.n for e in exs) + 3
    pad_c = max(contract_chains(e).n_chains for e in exs) + 3
    batched = _batched_cols(docs, pad_n, pad_c)
    cs_ref = None
    for spec in ALL_SPECS:
        codes, counts = chain_merge_docs_v(batched, rank_impl=spec)
        for i, d in enumerate(docs):
            got = "".join(map(chr, np.asarray(codes[i])[: int(counts[i])]))
            assert got == d.get_text("t").to_string(), f"{spec} doc {i}"
        cs = np.asarray(chain_rank_checksum_v(batched, rank_impl=spec))
        if cs_ref is None:
            cs_ref = cs
        else:
            np.testing.assert_array_equal(cs, cs_ref, err_msg=spec)


def test_merge_specs_pad_bucket_straddle():
    """Chain pads straddling power-of-two buckets (the jit-cache
    quantum): 2^k-1 / 2^k / 2^k+1 chain budgets must all merge
    byte-identically under the new algos, incl. a tight coalesced
    budget derived from host ring stats."""
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import contract_chains, extract_seq_container
    from loro_tpu.ops.fugue_batch import chain_merge_docs_v

    docs = _fuzz_docs(2, 100, 0.25, seed=11)
    cid = ContainerID.root("t", ContainerType.Text)
    exs = [extract_seq_container(d.oplog.changes_in_causal_order(), cid) for d in docs]
    c_min = max(contract_chains(e).n_chains for e in exs)
    n_pad = max(e.n for e in exs) + 5
    for pad_c in (c_min, 256, 257):
        if pad_c < c_min:
            continue
        batched = _batched_cols(docs, n_pad, pad_c)
        n_runs = max(
            int(
                rm.run_heads(
                    rm.build_ring(b.c_parent, b.c_side, b.c_valid)
                ).sum()
            )
            for b in [
                type(batched)(*[a[i] for a in batched]) for i in range(len(docs))
            ]
        )
        budget = rm.coalesce_budget(n_runs)
        for spec, rb in (
            ("xla:blocked", None),
            ("xla:coalesced", None),
            ("xla:coalesced", budget),
        ):
            codes, counts = chain_merge_docs_v(batched, rank_impl=spec, ring_budget=rb)
            for i, d in enumerate(docs):
                got = "".join(map(chr, np.asarray(codes[i])[: int(counts[i])]))
                assert got == d.get_text("t").to_string(), (
                    f"{spec} rb={rb} pad_c={pad_c} doc {i}"
                )


def test_env_algos_cover_sibkeys_path(monkeypatch):
    """RANK_ALGO=blocked|coalesced through the row-order-free device
    contraction path (sib_keys lexsort ring) vs the host engine —
    fresh jit per env value (knobs bake at trace time)."""
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import extract_seq_container
    from loro_tpu.ops.fugue_batch import SeqColumnsU, chain_contract_materialize_u

    docs = _fuzz_docs(1, 150, 0.3, seed=3)
    d = docs[0]
    cid = ContainerID.root("t", ContainerType.Text)
    ex = extract_seq_container(d.oplog.changes_in_causal_order(), cid)
    n = ex.n + 7
    peers = np.asarray(ex.peers, np.uint64)

    def pad(a, fill, dtype=None):
        out = np.full(n, fill, dtype or a.dtype)
        out[: a.shape[0]] = a
        return out

    pe = peers[ex.peer]
    cols = SeqColumnsU(
        parent=pad(ex.parent, -1),
        side=pad(ex.side, 0),
        peer_hi=pad((pe >> np.uint64(32)).astype(np.uint32), 0),
        peer_lo=pad(pe.astype(np.uint32), 0),
        counter=pad(ex.counter, 0),
        deleted=pad(ex.deleted, True),
        content=pad(ex.content, -1),
        valid=pad(ex.valid, False),
    )
    want = d.get_text("t").to_string()
    c_pad = n  # generous chain budget
    for algo in ("blocked", "coalesced"):
        monkeypatch.setenv("RANK_ALGO", algo)
        codes, count, n_chains = jax.jit(
            lambda c: chain_contract_materialize_u(c, c_pad)
        )(cols)
        assert int(n_chains) <= c_pad
        got = "".join(map(chr, np.asarray(codes)[: int(count)]))
        assert got == want, f"RANK_ALGO={algo}"


def test_device_ring_matches_host_mirror():
    """_ring_and_anchors (in-jit) and rank_model.build_ring (host) must
    stay in lockstep — the bench sizes coalescing budgets from the host
    mirror, so a drift would silently corrupt tight-budget merges."""
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
        heads_dev, n_runs_dev = jax.jit(ring_run_heads)(jnp.asarray(succ_host))
        assert int(n_runs_dev) == int(rm.run_heads(succ_host).sum())


# ---------------------------------------------------------------------------
# count-based perf guards (gathers per ranked token — never wall clock)
# ---------------------------------------------------------------------------


def test_blocked_gather_bound():
    """The blocked path must stay within its documented schedule: global
    rows <= the analytic cap model, local rows == ceil(log2 b) * m."""
    for m, block in ((1024, 128), (4096, 1024), (5000, 1024)):
        rng = np.random.default_rng(m)
        succ = _random_ring(rng, m)
        _, counts = rm.simulate(succ, "blocked", block=block)
        cap = rm.gather_model(m, "blocked", block=block)
        assert counts["global_rows"] <= cap["global_rows"], (m, block)
        assert counts["local_rows"] == cap["local_rows"], (m, block)


def test_coalesced_supernode_guard():
    """On a synthetic runs trace the coalesced path must rank at most
    ring_tokens/mean_run super-nodes (+1 for the trailing partial run)
    and cut global gather rows >= 2x vs Wyllie — the ISSUE 6 acceptance
    bound, count-based."""
    m, L = 4096, 8
    succ = _runs_ring(m, L, seed=2)
    n_runs = int(rm.run_heads(succ).sum())
    assert n_runs <= m // L + 1
    budget = rm.coalesce_budget(n_runs, slack=0)
    _, cc = rm.simulate(succ, "coalesced", r_pad=budget)
    _, cw = rm.simulate(succ, "wyllie")
    assert cc["n_runs"] == n_runs
    assert cw["global_rows"] >= 2 * cc["global_rows"], (
        cw["global_rows"],
        cc["global_rows"],
    )


def test_coalesced_guard_on_trace_rings():
    """The flagship ring shape (a chain-contracted editing trace padded
    to the bench quantum — the kind of ring bench.py ranks; here the
    first 20,000 patches of the seeded synthetic source) must show the
    >=2x global gather-row reduction for coalesced-at-measured-budget
    vs wyllie.  This is the ISSUE 6 acceptance bound as a standing
    guard; the bench banks the same counts in its `rank` sidecar."""
    from loro_tpu.bench_utils import TraceSource, automerge_seq_extract
    from loro_tpu.ops.columnar import contract_chains

    ex, _n_ops = automerge_seq_extract(
        TraceSource.synthetic(patches=20_000), use_cache=False
    )
    ch = contract_chains(ex)
    pad_c = -(-ch.n_chains // 1024) * 1024  # the bench quantum
    parent = np.full(pad_c, -1, np.int32)
    parent[: ch.n_chains] = ch.parent
    side = np.zeros(pad_c, np.int32)
    side[: ch.n_chains] = ch.side
    valid = np.zeros(pad_c, bool)
    valid[: ch.n_chains] = True
    succ = rm.build_ring(parent, side, valid)
    budget = rm.coalesce_budget(int(rm.run_heads(succ).sum()))
    _, cc = rm.simulate(succ, "coalesced", r_pad=budget)
    _, cw = rm.simulate(succ, "wyllie")
    assert cw["global_rows"] >= 2 * cc["global_rows"], (
        cw["global_rows"],
        cc["global_rows"],
    )


# ---------------------------------------------------------------------------
# typed env-knob validation (satellite: ConfigError at first use)
# ---------------------------------------------------------------------------


def test_env_validation_typed_errors(monkeypatch):
    from loro_tpu.ops.fugue_batch import _place_algo, _rank_algo, _rank_block
    from loro_tpu.ops.pallas_rank import _pallas_rank_algo, wyllie_rank

    monkeypatch.setenv("RANK_ALGO", "bogus")
    with pytest.raises(ConfigError, match="RANK_ALGO.*wyllie"):
        _rank_algo()
    monkeypatch.setenv("PLACE_ALGO", "bogus")
    with pytest.raises(ConfigError, match="PLACE_ALGO.*sort"):
        _place_algo()
    for bad in ("0", "64", "100", "131072", "x"):
        monkeypatch.setenv("RANK_BLOCK", bad)
        with pytest.raises(ConfigError, match="RANK_BLOCK"):
            _rank_block()
    monkeypatch.setenv("PALLAS_RANK_ALGO", "bogus")
    with pytest.raises(ConfigError, match="PALLAS_RANK_ALGO.*ruling"):
        _pallas_rank_algo()
    monkeypatch.setenv("PALLAS_RANK_ALGO", "blocked")
    monkeypatch.setenv("PALLAS_RULING_K", "13")
    with pytest.raises(ConfigError, match="PALLAS_RULING_K"):
        wyllie_rank(jnp.arange(64, dtype=jnp.int32), interpret=True)
    # ConfigError subclasses ValueError: legacy guards keep working
    assert issubclass(ConfigError, ValueError)


def test_rank_impl_spec_validation():
    from loro_tpu.ops.fugue_batch import _resolve_rank_spec

    assert _resolve_rank_spec("xla:coalesced", 256) == ("xla", "coalesced")
    assert _resolve_rank_spec("pallas:blocked", 256) == ("pallas", "blocked")
    with pytest.raises(ValueError):
        _resolve_rank_spec("xla:bogus", 256)
    with pytest.raises(ValueError):
        _resolve_rank_spec("tpu:wyllie", 256)


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
    """A truncated/corrupt npz (bench child killed mid-savez) must take
    the rebuild path, not crash every later run."""
    from loro_tpu.bench_utils import _load_extract_cache

    bad = tmp_path / "trunc.npz"
    bad.write_bytes(b"PK\x03\x04 not a real zip")
    assert _load_extract_cache(str(bad)) is None


def test_ruling_model_caps_realized_adversarial():
    """Model >= realized must hold even when ruling phase 1 runs to its
    round cap (all non-rulers consecutive along the ring): the dense
    table is ceil(m/k)+1 rows incl. the sink, and the model must price
    exactly that (review regression: m//k+1 undercounted)."""
    for m in (1001, 2048, 4097):
        k = 8
        rulers = [i for i in range(m) if i % k == 0]
        others = [i for i in range(m) if i % k != 0]
        order = others + rulers
        succ = np.arange(m, dtype=np.int32)
        for a, b in zip(order[:-1], order[1:]):
            succ[a] = b
        _, realized = rm.simulate(succ, "ruling")
        cap = rm.gather_model(m, "ruling")
        assert realized["global_rows"] <= cap["global_rows"], (
            m,
            realized["global_rows"],
            cap["global_rows"],
        )
