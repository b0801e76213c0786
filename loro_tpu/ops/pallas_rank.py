"""Pallas TPU kernels for list ranking — the gather-bound heart of the
Fugue order solve.

The XLA formulation (ops/fugue_batch._wyllie_dist) round-trips the succ/
dist arrays through HBM on every pointer-doubling step.  A
chain-contracted ring (typically <=48k tokens = <=200KB) fits in VMEM
(~16MB/core), so these kernels keep both arrays on-chip for all rounds
and only touch HBM twice.

The arbitrary gather is decomposed as an R-step row-rotate loop over
within-row lane gathers (see _vmem_gather): take_along_axis along
axis 1, <=128 lanes, is the dynamic_gather form these kernels rely on.
Two kernels, chosen by the ring's length alone: the packed ruling-set
kernel for lane-padded rings <= 65,536 tokens (phase-1 adaptive freeze
at index%8 rulers with terminal-absorption detection, dense m/8 ruler
ring + sink row, small-table recombine), the dual-table wide kernel up
to PALLAS_RANK_MAX_M.  Both compile and agree with the XLA rank on a
TPU v5 lite under jax/jaxlib 0.9.0 + libtpu 0.0.34 (PERF.md, PR 21);
tests/test_chip_compile.py compiles each for a described v5e on every
run.

On when the backend is TPU and the ring fits PALLAS_RANK_MAX_M; off the
TPU the XLA path runs (the interpreter-mode kernel is for differential
tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def use_pallas_rank() -> bool:
    """The Pallas rank runs on the TPU and nowhere else.  A test that
    wants the interpreted kernel on a CPU patches this function."""
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


# Ruler spacing of the packed kernel: phase-1 rounds grow ~log2(k*ln m)
# while the dense phase-2 ring shrinks k-fold.  A power of two; 65,536
# is a multiple of the 128*k pad quantum, so every ring of the packed
# domain pads inside it.
_RULING_K = 8


def _rank_kernel_ruling(succ_ref, w_ref, dist_ref, n_steps: int):
    """The packed ruling-set kernel.  (dist, succ) ride one u32 per
    element — dist in the high 16 bits, succ in the low 16 (legal while
    m <= 65536; dist-to-terminal is < m so the high half never carries)
    — so one packed gather per round does both:  g = p[s];
    p' = (p & 0xffff0000) + g  gives dist' = dist + dist[s],
    succ' = succ[s] in two VPU ops.  Init from the caller's weights,
    then the ruling phases."""
    succ = succ_ref[:, :]
    packed = jnp.bitwise_or(
        jnp.left_shift(w_ref[:, :].astype(jnp.uint32), 16), succ.astype(jnp.uint32)
    )
    dist_ref[:, :] = _ruling_from_packed(packed, n_steps, _RULING_K)


def _ruling_from_packed(packed, n_steps: int, k: int):
    """The ruling-set phases over a packed (dist:16 | succ:16) pointer
    state — dist(i) = d_i + dist(t_i), terminals are (0, self)
    self-loops.  Rulers are tokens with index % k == 0 — a pure bit
    test on the packed low half, so the phase-1 freeze check needs NO
    extra gather.

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


def wyllie_rank(succ: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """dist-to-terminal for a successor ring (terminal = self-loop).
    succ: i32[m]; returns i32[m].  `interpret=None` auto-selects the
    interpreter off-TPU (CI / CPU mesh runs).  Rings of
    <= 65536 tokens take the packed ruling-set kernel, longer
    ones the dual-table wide kernel; pad tokens are self-loop terminals
    (dist 0)."""
    m = succ.shape[0]
    # the packed kernel holds dist in 16 bits and pads to its ruler
    # quantum (the dense ruler ring must be 128-aligned); the wide one
    # pads to lanes only
    wide = m > 65536
    quantum = _LANES if wide else _LANES * _RULING_K
    # at least two rows in every table the kernel gathers from (the ring
    # itself, and the dense ruler ring of mp/quantum rows): Mosaic refuses
    # the lane gather on a one-row table ("Shape mismatch in input,
    # indices and output").
    mp = max(2, -(-m // quantum)) * quantum
    if mp > PALLAS_RANK_MAX_M:
        raise ValueError(f"ring too long for VMEM ranking: {m}")
    tok = jnp.arange(m, dtype=jnp.int32)
    w = jnp.where(succ == tok, 0, 1).astype(jnp.int32)
    if mp != m:
        pad_ids = jnp.arange(m, mp, dtype=jnp.int32)
        succ = jnp.concatenate([succ.astype(jnp.int32), pad_ids])
        w = jnp.concatenate([w, jnp.zeros(mp - m, jnp.int32)])
    n_steps = max(1, int(np.ceil(np.log2(max(m, 2)))))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = mp // _LANES
    kernel = _rank_kernel_wide if wide else _rank_kernel_ruling
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
    NOTE: production (fugue_batch._wyllie_dist) fuses (dist, succ) into
    one [m, 2] row so each round is a single gather (measured 2.3x on
    v5e); this reference keeps the textbook formulation — both compute the
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
