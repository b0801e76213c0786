"""The Pallas rank kernels vs the textbook XLA loop (interpret mode on
the CPU; tests/test_chip_compile.py compiles them for a described v5e)."""
import numpy as np
import pytest

from loro_tpu.ops.pallas_rank import wyllie_rank, wyllie_rank_xla


def _random_ring(m: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    perm = rng.permutation(m).astype(np.int32)
    succ = np.empty(m, np.int32)
    succ[perm[:-1]] = perm[1:]
    succ[perm[-1]] = perm[-1]  # terminal self-loop
    return succ


@pytest.mark.parametrize("m", [8, 64, 257, 1024])
def test_matches_xla(m):
    import jax.numpy as jnp

    succ = jnp.asarray(_random_ring(m, m))
    got = np.asarray(wyllie_rank(succ, interpret=True))
    want = np.asarray(wyllie_rank_xla(succ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m", [65536, 65600])
def test_packed_boundary_and_wide_kernel(m):
    """m == 65536 is the last ring of the packed ruling kernel; m > 65536
    selects the dual-table wide kernel (_rank_kernel_wide / _vmem_gather2)."""
    import jax.numpy as jnp

    succ = jnp.asarray(_random_ring(m, m))
    got = np.asarray(wyllie_rank(succ, interpret=True))
    want = np.asarray(wyllie_rank_xla(succ))
    np.testing.assert_array_equal(got, want)


def test_too_long_ring_raises():
    import jax.numpy as jnp

    from loro_tpu.ops.pallas_rank import PALLAS_RANK_MAX_M

    succ = jnp.zeros(PALLAS_RANK_MAX_M + 1, jnp.int32)
    with pytest.raises(ValueError):
        wyllie_rank(succ, interpret=True)


def test_distances_are_list_positions():
    import jax.numpy as jnp

    succ = jnp.asarray(_random_ring(512, 7))
    dist = np.asarray(wyllie_rank(succ, interpret=True))
    # unique distances 0..m-1, strictly decreasing along the ring
    assert sorted(dist.tolist()) == list(range(512))


@pytest.mark.parametrize("m", [128, 1024, 32770])
def test_ruling_kernel_matches_xla(m):
    """The ruling-set kernel (phase-1 freeze at index%8 rulers + dense
    ring + sink row) at lane- and ruler-aligned and unaligned lengths."""
    import jax.numpy as jnp

    succ = jnp.asarray(_random_ring(m, m))
    got = np.asarray(wyllie_rank(succ, interpret=True))
    want = np.asarray(wyllie_rank_xla(succ))
    np.testing.assert_array_equal(got, want)


def test_ruling_kernel_adversarial_gap():
    """All non-rulers consecutive along the ring: the phase-1 round cap
    must still produce exact distances (cap-hit pointers rest on the
    terminal)."""
    import jax.numpy as jnp

    m, k = 2048, 8
    order = [i for i in range(m) if i % k != 0] + [i for i in range(m) if i % k == 0]
    succ = np.arange(m, dtype=np.int32)
    for a, b in zip(order[:-1], order[1:]):
        succ[a] = b
    s = jnp.asarray(succ)
    got = np.asarray(wyllie_rank(s, interpret=True))
    want = np.asarray(wyllie_rank_xla(s))
    np.testing.assert_array_equal(got, want)
