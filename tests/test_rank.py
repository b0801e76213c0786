"""The rank the merge launches, and the rule that picks it.

One rule (``fugue_batch._resolve_rank_spec``) chooses from the platform
and the ring's length: the Pallas kernels on a TPU while the ring fits
VMEM, the XLA pointer doubling otherwise.  Here both are held to the
textbook two-gather Wyllie (``pallas_rank.wyllie_rank_xla``) on the ring
families that earlier algorithm variants were fuzzed with, both
resolutions are held to the host engine on whole merges, the rule's
table is spelled out, and the environment knobs that used to steer it
are shown to steer nothing.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from loro_tpu.ops import fugue_batch as fb
from loro_tpu.ops import pallas_rank
from loro_tpu.ops.pallas_rank import PALLAS_RANK_MAX_M, wyllie_rank, wyllie_rank_xla


# ---------------------------------------------------------------------------
# ring families
# ---------------------------------------------------------------------------


def _random_ring(m, seed):
    """Random ring over a live subset of tokens: unused tokens self-loop
    (like invalid pads); the chain ends in a terminal self-loop."""
    rng = np.random.default_rng(seed)
    live = rng.choice(m, size=rng.integers(2, m + 1), replace=False)
    p = rng.permutation(live).astype(np.int32)
    succ = np.arange(m, dtype=np.int32)
    succ[p[:-1]] = p[1:]
    return succ


def _runs_ring(m, run_len, seed):
    """One chain walking index-consecutive runs of ``run_len`` tokens in
    shuffled run order (the shape a chain-contracted trace gives)."""
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


def _one_edge_among_terminals():
    succ = np.arange(4, dtype=np.int32)
    succ[2] = 0
    return succ


def _one_run(m):
    """succ[i] = i + 1: the whole ring is one index-consecutive run."""
    succ = np.arange(1, m + 1, dtype=np.int32)
    succ[-1] = m - 1
    return succ


def _reversed_chain(m):
    """succ[i] = i - 1: no two consecutive tokens are consecutive steps."""
    return np.concatenate([[0], np.arange(m - 1)]).astype(np.int32)


def _ruler_gap(m, k=8):
    """Every non-ruler before any ruler (rulers: index % k == 0): the
    ruling kernel's phase 1 runs to its round cap."""
    order = [i for i in range(m) if i % k] + [i for i in range(m) if i % k == 0]
    succ = np.arange(m, dtype=np.int32)
    for a, b in zip(order[:-1], order[1:]):
        succ[a] = b
    return succ


RINGS = {
    **{f"random-m{m}-s{seed}": (_random_ring, m, seed)
       for m in (5, 64, 257, 1000) for seed in range(8)},
    # a contracted trace's shape at the ring lengths the cells launch
    # (36,866), past the 16-bit domain (the wide kernel) and at the cap
    "runs-m4096-len8": (_runs_ring, 4096, 8, 2),
    "runs-m36866-len32": (_runs_ring, 36_866, 32, 4),
    "runs-m70000-len512": (_runs_ring, 70_000, 512, 1),
    f"runs-m{PALLAS_RANK_MAX_M}-len4096": (_runs_ring, PALLAS_RANK_MAX_M, 4096, 3),
    **{f"terminals-m{m}": (lambda m: np.arange(m, dtype=np.int32), m) for m in (1, 2, 3)},
    "one-edge-among-terminals": (_one_edge_among_terminals,),
    "one-run-m1024": (_one_run, 1024),
    "reversed-chain-m512": (_reversed_chain, 512),
    "ruler-gap-m256": (_ruler_gap, 256),
    # round the lane quantum (128), the ruler quantum (1,024) and a pad
    # of nearly a whole quantum
    **{f"quantum-m{m}": (_random_ring, m, m)
       for m in (127, 128, 129, 1023, 1024, 1025, 4097)},
}

LAUNCHED = {
    "xla": lambda s: jax.jit(fb._wyllie_dist)(s),  # off the chip, and past VMEM
    "pallas": lambda s: wyllie_rank(s, interpret=True),  # on the chip
}


@pytest.mark.parametrize("ring", RINGS)
@pytest.mark.parametrize("backend", LAUNCHED)
def test_launched_rank_matches_textbook(backend, ring):
    make, *args = RINGS[ring]
    succ = jnp.asarray(make(*args))
    np.testing.assert_array_equal(
        np.asarray(LAUNCHED[backend](succ)), np.asarray(wyllie_rank_xla(succ)))


# ---------------------------------------------------------------------------
# whole merges against the host engine, under both resolutions
# ---------------------------------------------------------------------------


@pytest.fixture(params=["xla:wyllie", "pallas:ruling"])
def resolution(request, monkeypatch):
    """The rule as it answers off the chip, and as it answers on it (the
    kernel interpreted)."""
    on_chip = request.param.startswith("pallas")
    monkeypatch.setattr(pallas_rank, "use_pallas_rank", lambda: on_chip)
    assert ":".join(fb._resolve_rank_spec(None, 514)) == request.param
    return request.param


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


def _extracts(docs):
    from loro_tpu.core.ids import ContainerID, ContainerType
    from loro_tpu.ops.columnar import extract_seq_container

    cid = ContainerID.root("t", ContainerType.Text)
    return [extract_seq_container(d.oplog.changes_in_causal_order(), cid) for d in docs]


def _batched_cols(exs, pad_n, pad_c):
    from loro_tpu.ops.columnar import chain_columns

    cols = [chain_columns(e, pad_n=pad_n, pad_c=pad_c) for e in exs]
    return fb.ChainColumns(
        *[np.stack([getattr(c, f) for c in cols]) for f in fb.ChainColumns._fields])


def _assert_merge_is_host(docs, batched, what):
    # a fresh function, so a fresh trace: the rule is read while tracing
    codes, counts = jax.jit(lambda c: fb.chain_materialize_batch(c))(batched)
    for i, d in enumerate(docs):
        got = "".join(map(chr, np.asarray(codes[i])[: int(counts[i])]))
        assert got == d.get_text("t").to_string(), f"{what} doc {i}"


def test_merge_matches_host_tombstone_heavy(resolution):
    """Concurrent documents with 70 % deletes."""
    from loro_tpu.ops.columnar import contract_chains

    docs = _fuzz_docs(3, 120, 0.7, seed=7)
    exs = _extracts(docs)
    pad_n = max(e.n for e in exs) + 3
    pad_c = max(contract_chains(e).n_chains for e in exs) + 3
    _assert_merge_is_host(docs, _batched_cols(exs, pad_n, pad_c), resolution)


def test_merge_matches_host_across_pad_buckets(resolution):
    """Chain pads straddling a power-of-two bucket (the jit-cache
    quantum): the tight budget, 2^k and 2^k + 1 merge alike."""
    from loro_tpu.ops.columnar import contract_chains

    docs = _fuzz_docs(2, 100, 0.25, seed=11)
    exs = _extracts(docs)
    c_min = max(contract_chains(e).n_chains for e in exs)
    pad_n = max(e.n for e in exs) + 5
    assert c_min <= 256
    for pad_c in (c_min, 256, 257):
        _assert_merge_is_host(docs, _batched_cols(exs, pad_n, pad_c),
                              f"{resolution} pad_c={pad_c}")


def test_merge_matches_host_on_the_sib_keys_path(resolution):
    """The row-order-free device contraction (``sib_keys`` lexsort ring,
    the resident batch's solver)."""
    (d,) = _fuzz_docs(1, 150, 0.3, seed=3)
    (ex,) = _extracts([d])
    n = ex.n + 7
    pe = np.asarray(ex.peers, np.uint64)[ex.peer]

    def pad(a, fill):
        out = np.full(n, fill, a.dtype)
        out[: a.shape[0]] = a
        return out

    cols = fb.SeqColumnsU(
        parent=pad(ex.parent, -1), side=pad(ex.side, 0),
        peer_hi=pad((pe >> np.uint64(32)).astype(np.uint32), 0),
        peer_lo=pad(pe.astype(np.uint32), 0), counter=pad(ex.counter, 0),
        deleted=pad(ex.deleted, True), content=pad(ex.content, -1),
        valid=pad(ex.valid, False))
    codes, count, n_chains = jax.jit(
        lambda c: fb.chain_contract_materialize_u(c, n))(cols)  # a generous chain budget
    assert int(n_chains) <= n
    assert "".join(map(chr, np.asarray(codes)[: int(count)])) == d.get_text("t").to_string()


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

# 514: the resident solver's first chain budget; 36,866 and 65,536: the
# rings of packed64 and fleet16; 65,538 to 131,072: the wide kernel;
# 524,290: an uncontracted B4 document's element ring
RULE_RINGS = (514, 36_866, 65_536, 65_538, 131_072, 131_074, 524_290)


@pytest.mark.parametrize("m", RULE_RINGS)
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_rank_rule(monkeypatch, platform, m):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    want = (("pallas", "ruling") if platform == "tpu" and m <= PALLAS_RANK_MAX_M
            else ("xla", "wyllie"))
    assert fb._resolve_rank_spec(None, m) == want


def test_rank_rule_takes_no_choice():
    with pytest.raises(ValueError, match="must be None"):
        fb._resolve_rank_spec("xla:wyllie", 514)


def _lowered_merge_text():
    sds = lambda dt, n: jax.ShapeDtypeStruct((2, n), dt)  # noqa: E731
    cols = fb.ChainColumns(
        c_parent=sds(jnp.int32, 300), c_side=sds(jnp.int32, 300),
        c_valid=sds(jnp.bool_, 300), head_row=sds(jnp.int32, 300),
        chain_id=sds(jnp.int32, 2048), deleted=sds(jnp.bool_, 2048),
        content=sds(jnp.int32, 2048), valid=sds(jnp.bool_, 2048))
    return jax.jit(lambda c: fb.chain_materialize_batch(c)).lower(cols).as_text()


@pytest.mark.parametrize("var,value,on_chip", [
    ("RANK_ALGO", "blocked", True),
    ("PALLAS_RANK_ALGO", "wyllie", True),
    ("PALLAS_RULING_K", "2", True),
    ("PLACE_ALGO", "scatter", True),
    ("PALLAS_RANK", "1", None),  # under the real use_pallas_rank: no kernel on a CPU
])
def test_dead_knobs_change_nothing(monkeypatch, var, value, on_chip):
    """The variables that chose the rank and the placement until PR 30
    are read by nothing: the rule answers, and the merge lowers, as
    without them."""
    if on_chip is not None:
        monkeypatch.setattr(pallas_rank, "use_pallas_rank", lambda: on_chip)
    monkeypatch.delenv(var, raising=False)
    spec, text = fb._resolve_rank_spec(None, 602), _lowered_merge_text()
    assert spec == (("pallas", "ruling") if on_chip else ("xla", "wyllie"))
    monkeypatch.setenv(var, value)
    assert fb._resolve_rank_spec(None, 602) == spec
    assert _lowered_merge_text() == text
