"""Pallas TPU kernel for Wyllie list-ranking — the gather-bound heart
of the Fugue order solve.

The XLA formulation (ops/fugue_batch._order_core) round-trips the succ/
dist arrays through HBM on every pointer-doubling step.  A
chain-contracted ring (typically <=48k tokens = <=200KB) fits in VMEM
(~16MB/core), so this kernel keeps both arrays on-chip for all
ceil(log2(m)) rounds and only touches HBM twice.

The arbitrary gather is decomposed as an R-step row-rotate loop over
within-row lane gathers (see _vmem_gather): take_along_axis along
axis 1, <=128 lanes, is the dynamic_gather form these kernels rely on.
All four kernels (packed wyllie, packed ruling, blocked, dual-table
wide) compile and agree with the XLA rank on a TPU v5 lite under
jax/jaxlib 0.9.0 + libtpu 0.0.34 (PERF.md, PR 21); tests/
test_chip_compile.py compiles each for a described v5e on every run.

Default: ON when the backend is TPU and the ring fits
PALLAS_RANK_MAX_M; force with PALLAS_RANK=1, disable with
PALLAS_RANK=0.  Off-TPU the XLA path remains the default (the
interpreter-mode kernel is for differential tests).

PALLAS_RANK_ALGO selects ruling (default) | wyllie | blocked for rings
<= 65536.  The ruling-set kernel: phase-1 adaptive freeze at index%8
rulers with terminal-absorption detection, dense m/8 ruler ring + sink
row, small-table recombine.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


PALLAS_RANK_ALGOS = ("wyllie", "ruling", "blocked")


def _pallas_rank_algo() -> str:
    """Kernel algorithm (PALLAS_RANK_ALGO): ruling (default) | wyllie |
    blocked.  Validated at first use with a typed ConfigError — never a
    silent fall-back."""
    from ..errors import ConfigError

    algo = os.environ.get("PALLAS_RANK_ALGO", "ruling")
    if algo not in PALLAS_RANK_ALGOS:
        raise ConfigError("PALLAS_RANK_ALGO", algo, "|".join(PALLAS_RANK_ALGOS))
    return algo


def use_pallas_rank() -> bool:
    """PALLAS_RANK=1 forces on, =0 forces off; unset = auto (on iff the
    backend is TPU)."""
    flag = os.environ.get("PALLAS_RANK", "")
    if flag == "0":
        return False
    if flag:
        return True
    return jax.default_backend() == "tpu"


# Above this ring length the R-step rotate loop (R = m/128 iterations
# per doubling round) loses to the HBM gather formulation; callers fall
# back to the XLA path.
PALLAS_RANK_MAX_M = 1 << 17


def pallas_rank_applicable(m: int) -> bool:
    return use_pallas_rank() and m <= PALLAS_RANK_MAX_M


_LANES = 128


def _vmem_gather(tbl, rows, cols):
    """Full dynamic gather out[i,j] = tbl[rows[i,j], cols[i,j]] from
    the within-row lane gather (take_along_axis axis=1, <=128 lanes, any
    sublane count).  Arbitrary (row, lane) addressing is decomposed as an
    R-step row-rotate loop: after t rolls, rot[i, :] = tbl[(i+t) % R, :],
    so a lane-gather with `cols` yields tbl[(i+t) % R, cols[i,j]], kept
    wherever rows[i,j] == (i+t) % R.  All operands stay in
    VMEM/registers; per-iteration work is ~5 VPU ops on a [R, 128]
    tile, so the whole loop is ~1 ms — vs an HBM round-trip per
    doubling round in the XLA formulation."""
    shape = tbl.shape
    n_rows = shape[0]
    iota0 = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

    def body(t, carry):
        acc, rot = carry
        g = jnp.take_along_axis(rot, cols, axis=1, mode="promise_in_bounds")
        src = iota0 + t
        src = jnp.where(src >= n_rows, src - n_rows, src)
        acc = jnp.where(rows == src, g, acc)
        return acc, pltpu.roll(rot, n_rows - 1, axis=0)

    acc = jnp.zeros(shape, tbl.dtype)
    acc, _ = jax.lax.fori_loop(0, n_rows, body, (acc, tbl))
    return acc


def _vmem_gather_near(tbl, rows, cols, radius: int):
    """Windowed variant of _vmem_gather: only resolves addresses whose
    target row lies within `radius` rows of the output row (others keep
    the zero fill — callers mask them off).  The rotate loop then runs
    min(2*radius+1, R) iterations instead of R: this is what makes the
    blocked kernel's phase-A gathers block-local (a b-token block is
    b/128 consecutive rows, so radius = b/128 - 1 covers every in-block
    target).  Out-of-window rows that happen to alias through the
    modular rotation are still gathered CORRECTLY (the hit test matches
    the true source row), just not guaranteed."""
    shape = tbl.shape
    n_rows = shape[0]
    span = min(2 * radius + 1, n_rows)
    iota0 = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    rot0 = pltpu.roll(tbl, radius % n_rows, axis=0) if radius % n_rows else tbl

    def body(t, carry):
        acc, rot = carry
        g = jnp.take_along_axis(rot, cols, axis=1, mode="promise_in_bounds")
        src = iota0 + (t - radius)
        src = jnp.where(src < 0, src + n_rows, src)
        src = jnp.where(src >= n_rows, src - n_rows, src)
        acc = jnp.where(rows == src, g, acc)
        return acc, pltpu.roll(rot, n_rows - 1, axis=0)

    acc = jnp.zeros(shape, tbl.dtype)
    acc, _ = jax.lax.fori_loop(0, span, body, (acc, rot0))
    return acc


def _vmem_gather2(tbl_a, tbl_b, rows, cols):
    """Gather TWO same-shape tables at the same (rows, cols) addresses in
    one rotate loop (shared hit masks; used when (dist, succ) cannot
    pack into one u32)."""
    shape = tbl_a.shape
    n_rows = shape[0]
    iota0 = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

    def body(t, carry):
        acc_a, acc_b, rot_a, rot_b = carry
        ga = jnp.take_along_axis(rot_a, cols, axis=1, mode="promise_in_bounds")
        gb = jnp.take_along_axis(rot_b, cols, axis=1, mode="promise_in_bounds")
        src = iota0 + t
        src = jnp.where(src >= n_rows, src - n_rows, src)
        hit = rows == src
        return (
            jnp.where(hit, ga, acc_a),
            jnp.where(hit, gb, acc_b),
            pltpu.roll(rot_a, n_rows - 1, axis=0),
            pltpu.roll(rot_b, n_rows - 1, axis=0),
        )

    acc_a = jnp.zeros(shape, tbl_a.dtype)
    acc_b = jnp.zeros(shape, tbl_b.dtype)
    acc_a, acc_b, _, _ = jax.lax.fori_loop(
        0, n_rows, body, (acc_a, acc_b, tbl_a, tbl_b)
    )
    return acc_a, acc_b


def _rank_kernel_wide(succ_ref, w_ref, dist_ref, n_steps: int):
    """Dual-table variant for rings longer than 65536 tokens (dist no
    longer fits 16 bits): carry (dist i32, succ i32) separately and
    gather both per round with shared address masks."""
    rows, cols = succ_ref.shape
    succ = succ_ref[:, :]
    dist = w_ref[:, :].astype(jnp.int32)

    def round_body(_, carry):
        d, s = carry
        gd, gs = _vmem_gather2(
            d, s, jnp.right_shift(s, 7), jnp.bitwise_and(s, 0x7F)
        )
        return d + gd, gs

    dist, _ = jax.lax.fori_loop(0, n_steps, round_body, (dist, succ))
    dist_ref[:, :] = dist


def _vmem_gather_from(tbl, rows, cols, out_shape_like):
    """Gather from a (possibly differently-sized) VMEM table:
    out[i,j] = tbl[rows[i,j], cols[i,j]].  Loops over the TABLE's rows
    (broadcast one row per iteration), so gathering m outputs from a
    small Rt-row table costs Rt iterations — the cheap recombine path
    of the ruling-set kernel."""
    n_rows = tbl.shape[0]

    def body(t, carry):
        acc, rot = carry
        brow = rot[0:1, :]  # static slice; roll brings row t here at step t
        g = jnp.take_along_axis(
            jnp.broadcast_to(brow, out_shape_like.shape), cols, axis=1,
            mode="promise_in_bounds",
        )
        acc = jnp.where(rows == t, g, acc)
        return acc, pltpu.roll(rot, n_rows - 1, axis=0)

    acc = jnp.zeros(out_shape_like.shape, tbl.dtype)
    acc, _ = jax.lax.fori_loop(0, n_rows, body, (acc, tbl))
    return acc


def _rank_kernel_ruling(succ_ref, w_ref, dist_ref, n_steps: int, k: int = 8):
    """Ruling-set variant of the packed kernel (see _rank_kernel for the
    u32 (dist, succ) packing).  Init from the caller's weights, then the
    shared ruling phases."""
    succ = succ_ref[:, :]
    packed = jnp.bitwise_or(
        jnp.left_shift(w_ref[:, :].astype(jnp.uint32), 16), succ.astype(jnp.uint32)
    )
    dist_ref[:, :] = _ruling_from_packed(packed, n_steps, k)


def _rank_kernel_blocked(
    succ_ref, w_ref, dist_ref, n_steps: int, k: int = 8, block: int = 1024
):
    """Blocked two-level variant (PALLAS_RANK_ALGO=blocked): phase A
    collapses in-block pointer chains with WINDOWED rotate gathers
    (radius = block/128 - 1 rows, so each of the ceil(log2(block))
    rounds costs ~2·block/128 rotate iterations instead of m/128 —
    the dense-VMEM-inside-blocks half of the two-level plan), then the
    shared ruling phases rank the weighted block-exit graph (short
    inter-block work; the adaptive phase-1 freeze converges in few
    rounds when blocks actually collapse chains, and its cap keeps the
    worst case exact)."""
    rows, cols = succ_ref.shape
    m = rows * cols
    succ = succ_ref[:, :]
    packed = jnp.bitwise_or(
        jnp.left_shift(w_ref[:, :].astype(jnp.uint32), 16), succ.astype(jnp.uint32)
    )
    flat_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    )
    shift = int(np.log2(block))
    radius = min(block // _LANES - 1, rows - 1)
    n_a = max(1, int(np.ceil(np.log2(min(block, m)))))

    def phase_a(_, p):
        s = jnp.bitwise_and(p, jnp.uint32(0xFFFF)).astype(jnp.int32)
        in_blk = jnp.right_shift(s, shift) == jnp.right_shift(flat_idx, shift)
        g = _vmem_gather_near(
            p, jnp.right_shift(s, 7), jnp.bitwise_and(s, 0x7F), radius
        )
        p2 = jnp.bitwise_and(p, jnp.uint32(0xFFFF0000)) + g
        return jnp.where(in_blk, p2, p)

    packed = jax.lax.fori_loop(0, n_a, phase_a, packed)
    dist_ref[:, :] = _ruling_from_packed(packed, n_steps, k)


def _ruling_from_packed(packed, n_steps: int, k: int = 8):
    """The ruling-set phases over a generic packed (dist:16 | succ:16)
    pointer state — dist(i) = d_i + dist(t_i), terminals are (0, self)
    self-loops.  Shared by the ruling kernel (unit/caller weights) and
    the blocked kernel (phase-A block-collapsed state).  Rulers are
    tokens with index % k == 0 — a pure bit test on the packed low
    half, so the phase-1 freeze check needs NO extra gather.

    Phase 1: double every pointer whose target is not yet a ruler;
    terminals absorb automatically (gathering a self-loop adds dist 0).
    Adaptive while_loop — typically ~log2(k*ln m) rounds of the
    expensive full-ring rotate gather instead of log2(m); the round cap
    keeps the worst case exact (a pointer that never froze has doubled
    log2(m) times and so rests on a terminal, and at fixpoint every
    non-ruler stop is a terminal).

    Phase 2: dense ruler ring (slot r <-> token r*k) + one extra
    128-lane row holding the absorbing sink at slot mr: ruler-terminal
    slots are naturally absorbing ((0, self)); rulers resting on a
    non-ruler terminal edge to the sink.  Rotate gathers here are
    k-times cheaper.

    Phase 3: dist = d1 + dense_dist[t1 / k] via one small-table gather
    (pointers resting on non-ruler terminals take d1 alone)."""
    rows, cols = packed.shape
    m = rows * cols

    def tgt(p):
        return jnp.bitwise_and(p, jnp.uint32(0xFFFF)).astype(jnp.int32)

    def phase1_cond(carry):
        # done carried as i32 0/1 (i1 vectors in while carries fail
        # Mosaic legalization)
        i, p, done = carry
        return (i < n_steps) & jnp.any(done == 0)

    def phase1_body(carry):
        i, p, done = carry
        s = tgt(p)
        at_ruler = (s & (k - 1)) == 0
        g = _vmem_gather(p, jnp.right_shift(s, 7), jnp.bitwise_and(s, 0x7F))
        # target's own target: t2 == s means the target is a terminal —
        # the pointer has absorbed (applying the update is a no-op), so
        # it is done even when the terminal is not a ruler
        t2 = jnp.bitwise_and(g, jnp.uint32(0xFFFF)).astype(jnp.int32)
        done_now = at_ruler | (t2 == s)
        p2 = jnp.bitwise_and(p, jnp.uint32(0xFFFF0000)) + g
        p_next = jnp.where(at_ruler, p, p2)
        return i + 1, p_next, jnp.maximum(done, done_now.astype(jnp.int32))

    done0 = ((tgt(packed) & (k - 1)) == 0).astype(jnp.int32)
    _, p1, _ = jax.lax.while_loop(
        phase1_cond, phase1_body, (jnp.int32(0), packed, done0)
    )

    # ---- dense ruler ring + sink row ---------------------------------
    mr = m // k  # caller pads m to a multiple of 128*k, so mr % 128 == 0
    rows_d = mr // _LANES
    d_idx = (
        jax.lax.broadcasted_iota(jnp.int32, (rows_d, cols), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (rows_d, cols), 1)
    )
    r_tok = d_idx * k
    pr = _vmem_gather_from(
        p1, jnp.right_shift(r_tok, 7), jnp.bitwise_and(r_tok, 0x7F), d_idx
    )
    d1r = jnp.right_shift(pr, 16)
    t1r = jnp.bitwise_and(pr, jnp.uint32(0xFFFF)).astype(jnp.int32)
    # at fixpoint a non-ruler stop is a terminal -> edge to the sink
    # (slot mr, dist 0 self-loop); ruler stops edge to t1r / k.  Ruler
    # terminals come out naturally absorbing: (d1=0, t1=self).
    dense_t = jnp.where(
        (t1r & (k - 1)) != 0, jnp.int32(mr), t1r // k
    ).astype(jnp.uint32)
    ring_top = jnp.bitwise_or(jnp.left_shift(d1r, 16), dense_t)
    # sink row: every slot in [mr, mr+128) is a (0, self) absorber
    sink_row = (jnp.uint32(mr) + jax.lax.broadcasted_iota(
        jnp.uint32, (1, cols), 1
    ))
    ring_d = jnp.concatenate([ring_top, sink_row], axis=0)  # [rows_d+1, 128]

    n_steps_d = max(1, int(np.ceil(np.log2(max(mr, 2)))))

    def round_d(_, p):
        s = tgt(p)
        g = _vmem_gather(p, jnp.right_shift(s, 7), jnp.bitwise_and(s, 0x7F))
        return jnp.bitwise_and(p, jnp.uint32(0xFFFF0000)) + g

    ring_d = jax.lax.fori_loop(0, n_steps_d, round_d, ring_d)
    dist_d = jnp.right_shift(ring_d, 16).astype(jnp.int32)  # [rows_d+1, 128]

    # ---- recombine ---------------------------------------------------
    t1 = tgt(p1)
    d1 = jnp.right_shift(p1, 16).astype(jnp.int32)
    dense_all = t1 // k
    extra = _vmem_gather_from(
        dist_d, jnp.right_shift(dense_all, 7), jnp.bitwise_and(dense_all, 0x7F),
        t1,
    )
    at_nonruler_term = (t1 & (k - 1)) != 0
    return d1 + jnp.where(at_nonruler_term, 0, extra)


def _rank_kernel(succ_ref, w_ref, dist_ref, n_steps: int):
    """(dist, succ) packed as one u32 per element — dist in the high 16
    bits, succ in the low 16 (legal while m <= 65536; dist-to-terminal
    is < m so the high half never carries).  One packed gather per
    Wyllie round: g = p[s];  p' = (p & 0xffff0000) + g  gives
    dist' = dist + dist[s], succ' = succ[s] in two VPU ops."""
    succ = succ_ref[:, :]
    packed = jnp.bitwise_or(
        jnp.left_shift(w_ref[:, :].astype(jnp.uint32), 16), succ.astype(jnp.uint32)
    )

    def round_body(_, p):
        s = jnp.bitwise_and(p, jnp.uint32(0xFFFF)).astype(jnp.int32)
        g = _vmem_gather(p, jnp.right_shift(s, 7), jnp.bitwise_and(s, 0x7F))
        return jnp.bitwise_and(p, jnp.uint32(0xFFFF0000)) + g

    packed = jax.lax.fori_loop(0, n_steps, round_body, packed)
    dist_ref[:, :] = jnp.right_shift(packed, 16).astype(jnp.int32)


def wyllie_rank(
    succ: jax.Array,
    interpret: Optional[bool] = None,
    algo: Optional[str] = None,
    weights: Optional[jax.Array] = None,
    dist_bound: Optional[int] = None,
) -> jax.Array:
    """dist-to-terminal for a successor ring (terminal = self-loop).
    succ: i32[m]; returns i32[m].  `interpret=None` auto-selects the
    interpreter off-TPU (CI / CPU mesh runs).  Pads internally to a
    multiple of 128 lanes (pad tokens are self-loop terminals, dist 0);
    rings <= 65536 tokens use the packed-u32 kernel (PALLAS_RANK_ALGO
    selects wyllie | ruling | blocked — read at TRACE time like
    RANK_ALGO: set it before the first merge of the process,
    already-jitted kernels do not retrace on env changes; an explicit
    `algo` argument beats the env), longer rings the dual-table one.

    `weights` generalizes to a weighted pointer state: dist(i) =
    weights[i] + dist(succ[i]), with terminals carrying weight 0 — the
    run-coalesced path ranks its contracted super-node ring this way.
    Weighted callers MUST pass `dist_bound` (an exclusive upper bound
    on any resulting distance, e.g. the pre-contraction ring length):
    the packed kernels carry dist in 16 bits, so a bound past 65535
    forces the dual-table wide kernel even when the ring itself is
    short — silent u16 overflow otherwise."""
    from ..errors import ConfigError

    m = succ.shape[0]
    if algo is None:
        algo = _pallas_rank_algo()
    elif algo not in PALLAS_RANK_ALGOS:
        raise ConfigError("pallas rank algo", algo, "|".join(PALLAS_RANK_ALGOS))
    # ruler spacing: phase-1 rounds grow ~log2(k*ln m) while the dense
    # phase-2 ring shrinks k-fold — PALLAS_RULING_K exposes the
    # tradeoff for on-chip sweeps (power of two; read at trace time;
    # capped at 512 so the 128*k pad quantum stays within the packed
    # kernel's 65536-token domain)
    if algo in ("ruling", "blocked"):
        raw_k = os.environ.get("PALLAS_RULING_K", "8")
        try:
            k = int(raw_k)
        except ValueError:
            k = -1
        if not 2 <= k <= 512 or (k & (k - 1)) != 0:
            raise ConfigError(
                "PALLAS_RULING_K", raw_k, "a power of two in [2, 512]"
            )
        quantum = _LANES * k  # dense ruler ring must be 128-aligned
        if -(-m // quantum) * quantum > 65536 >= m:
            # the k-aligned pad would leave the packed-kernel domain
            # (and the wide kernel ignores k anyway, with up to 2x pad
            # waste) — fall back to the plain packed wyllie kernel,
            # which only needs lane alignment
            algo = "wyllie"
            quantum = _LANES
    else:
        k = 8  # unused off the ruling path
        quantum = _LANES
    block = 0
    if algo == "blocked":
        from .fugue_batch import _rank_block

        block = _rank_block()
    # the packed kernels hold dist in 16 bits: both the ring length AND
    # the weighted-distance domain must fit (a short contracted ring
    # can still carry pre-contraction distances past u16).  Wide rings
    # ignore the ruler quantum — pad to lanes only.
    needs_wide = (-(-m // _LANES) * _LANES) > 65536 or (
        weights is not None and dist_bound is not None and dist_bound > 65536
    )
    if needs_wide:
        quantum = _LANES
    # at least two rows in every table the kernel gathers from (the ring
    # itself, and the dense ruler ring of mp/quantum rows): Mosaic refuses
    # the lane gather on a one-row table ("Shape mismatch in input,
    # indices and output").  Pad tokens are self-loop terminals.
    mp = max(2, -(-m // quantum)) * quantum
    if mp > PALLAS_RANK_MAX_M:
        raise ValueError(f"ring too long for VMEM ranking: {m}")
    tok = jnp.arange(m, dtype=jnp.int32)
    w = (
        jnp.where(succ == tok, 0, 1).astype(jnp.int32)
        if weights is None
        else weights.astype(jnp.int32)
    )
    if mp != m:
        pad_ids = jnp.arange(m, mp, dtype=jnp.int32)
        succ = jnp.concatenate([succ.astype(jnp.int32), pad_ids])
        w = jnp.concatenate([w, jnp.zeros(mp - m, jnp.int32)])
    n_steps = max(1, int(np.ceil(np.log2(max(m, 2)))))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = mp // _LANES
    if weights is not None and dist_bound is None:
        raise ValueError("weighted wyllie_rank needs dist_bound (see docstring)")
    if not needs_wide:
        if algo == "ruling":
            kernel = functools.partial(_rank_kernel_ruling, k=k)
        elif algo == "blocked":
            kernel = functools.partial(_rank_kernel_blocked, k=k, block=block)
        else:
            kernel = _rank_kernel
    else:
        kernel = _rank_kernel_wide
    fn = pl.pallas_call(
        functools.partial(kernel, n_steps=n_steps),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )
    return fn(succ.reshape(rows, _LANES), w.reshape(rows, _LANES)).reshape(mp)[:m]


def wyllie_rank_xla(succ: jax.Array) -> jax.Array:
    """Reference XLA implementation of plain two-gather Wyllie ranking.
    NOTE: production (_order_core) now fuses (dist, succ) into one
    [m, 2] row so each round is a single gather (measured 2.3x on v5e);
    this reference keeps the textbook formulation — both compute the
    same distances, which is what the differential tests assert."""
    m = succ.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    dist = jnp.where(succ == idx, 0, 1).astype(jnp.int32)
    n_steps = max(1, int(np.ceil(np.log2(max(m, 2)))))

    def body(_, carry):
        d, s = carry
        return d + d[s], s[s]

    dist, _ = jax.lax.fori_loop(0, n_steps, body, (dist, succ))
    return dist
