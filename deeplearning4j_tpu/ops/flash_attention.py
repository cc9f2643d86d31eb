"""Pallas flash-attention kernel for TPU.

Role parity: the reference accelerates its hot layers with hand-written
cuDNN kernels loaded as optional fast paths
(reference: deeplearning4j-cuda/.../CudnnConvolutionHelper.java, loaded
reflectively at ConvolutionLayer.java:69-76 with a pure-Java fallback).
Attention is this framework's hottest net-new op (the reference has
none, SURVEY.md §5.7), so it gets the same treatment: a Pallas kernel
(VMEM-tiled, online-softmax over query blocks, f32 accumulation) used
when available, with the jnp reference path as fallback — selection at
call time, zero API change (`dot_product_attention` dispatches).

Operand layout (PR 27): the kernels read and write the block's own
``[B, T, H*Dh]`` layout, the q/k/v projections' output reshaped and
never transposed. A program takes ``W = max(128, Dh)`` lanes of it,
``hpb = W // Dh`` heads (a head pair at head_dim 64, one head at 128),
all of a sequence's rows, on a grid over batch x lane groups. The
heads of a block are told apart by lane MASK, not by lane slice:
``dot(where(lane in head s, q, 0), k)`` contracts all W lanes, the
other head's lanes adding exact zeros, and on a 128-deep MXU a
contraction of 128 costs what one of 64 costs. The softmax statistics
are lane-major, ``[B, groups, rows, T]`` with T on the lanes. A head of
a multiple of 128 lanes is a program's own block at every length and in
any query group (a K/V head's block is its query head's ``// group``):
the host's splits of long sequences, the forward's q chunks and ranges
of heads and the backward's q x k tiles, move static offsets in the
index maps over the whole operands and never slice them. Narrower heads
share a block within one superblock and without a group. Shapes that
cannot take that form (``H*Dh`` not a multiple of W, Dh 80 or 96, head
pairs past one superblock or in a group) run the SAME kernel bodies over
``[B*H, T, Dh]`` with one head a program (`_lane_dense_width` decides,
from shapes alone; a trace-time mark ``flash_attention.layout`` and the
counter ``flash_attention_calls{layout}`` say which was taken, and the
mark how many kernel calls a forward and a backward issue).

What was measured before it (ledger, PR 26, GPT-2 medium at
``[32, 1024, 16, 64]``): with ``[B*H, T, 64]`` operands half of every
128-lane tile was padding in HBM, the ``[T, 1]`` statistics columns
held 1 live lane of 128 (256 MiB each for 2 MiB of data), and the
``[B,T,H,Dh] <-> [B,H,T,Dh]`` transposes around every call cost 63 ms
of a 903 ms step: a forward call moved 1.07 GB where 0.27 GB is
required, at 9.35% of its roofline. The round-4 head-packing
experiment (two ``[T, 64]`` heads a program with the SAME per-head
operands and layout) changed neither a byte moved nor a copy and
measured 1.00x; it is the layout that this form changes.

Kernel shape strategy (round-3): ONE program holds its heads' full
Q/K/V rows in VMEM and loops over [bq, bk] score tiles inside the
program. The round-2 layout
(grid (B*H, q-blocks), full K/V per program) re-read K/V from HBM once
per q-block and was measured HBM-bound on exactly that traffic; one
program per head reads each operand once. Position offsets are Python
ints on this path (attention.py falls back to jnp for traced offsets),
so the causal tile structure is resolved at trace time: tiles past the
causal diagonal are skipped outright when offsets prove no row can be
fully masked (kv_offset <= q_offset), and diagonal-straddling tiles
run a masked body while fully-valid tiles skip the iota/compare/select
arithmetic entirely. Loops are lax.fori_loop (Mosaic reuses the tile
stack across iterations; a fully unrolled Python loop was measured to
blow the 16MB scoped-VMEM budget). See the measured support matrix at
the end of this docstring for the per-direction sequence-length
limits on this backend.

Backward pass: ONE fused Pallas kernel producing dQ, dK and dV from
shared probability panels (the separate-dQ variant paid the VPU-bound
panel recompute twice). The forward additionally emits the per-row
log-sum-exp (the running max and the log-normalizer apart where a row
can be fully masked); the backward recomputes probabilities
tile-by-tile from (q, k, stats) in VMEM, k-major (``s^T = k q^T``, so
a q row's statistic is a ``[1, bq]`` row that broadcasts over
sublanes) — never materializing [T,S] in HBM in either direction.
Shapes the kernels can't tile (kv length not block-divisible) fall
back to a jnp-recompute VJP.

Single-chip support matrix. On an earlier toolchain (one v5e,
2026-07-30/31, BASELINE.md r3-r5): forward compiled and ran to T=16384
(bh-chunked 2-D grids — larger grids failed to compile, see
_MAX_2D_GRID_*); the fused backward to T=4096 (q-chunked past
_BWD_Q_CHUNK, k-superblocks capped at 2); FULL train-step programs
(scan + remat + several kernel instantiations) to T=2048. On the
installed jax 0.9.0 / libtpu 0.0.34 (2026-09-26, chip_smoke.py): bf16
forward and backward at B*H=64, T=2048, head_dim 128 compile and match
the float32 reference, alone and inside the 12-layer remat'd train
step; the same forward on float32 inputs asks for 16.96MB of scoped
VMEM against the 16MB limit and does not compile. Longer-context
training is sequence parallelism's job (parallel/ring.py,
parallel/ulysses.py shard T so local blocks stay in the supported
range), which is the documented first-class long-context mechanism
(SURVEY §5.7).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

Array = jax.Array

NEG_INF = -1e30
BLOCK_Q = 128          # floor / eligibility granularity


def _inner_block(n: int, cap: int = 512) -> int:
    """Score-tile edge: the largest power-of-two (<= cap) dividing n,
    or n itself when it fits in one tile. 512-edge tiles measured
    fastest on v5e at T=2048 (bigger tiles amortize per-tile loop
    overhead; 1024+ blows the panel VMEM budget at long T). Small or
    odd extents (short sequences, cross-attention kv lengths) become a
    single tile rather than degrading to sub-sublane slivers."""
    if n <= cap:
        return n
    b = cap
    while n % b and b > 8:
        b //= 2
    return b if n % b == 0 else n


def _reference_attention(q, k, v, scale: float, causal: bool,
                         q_offset, kv_offset):
    """jnp reference path ([B*H, T, D] layout), f32 softmax."""
    s = jnp.einsum("btd,bsd->bts", q, k).astype(jnp.float32) * scale
    if causal:
        tq = q.shape[1]
        sk = k.shape[1]
        qi = jnp.arange(tq)[:, None] + q_offset
        ki = jnp.arange(sk)[None, :] + kv_offset
        s = jnp.where(qi >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p.astype(q.dtype), v)


def _masked_scores(q, k, scale, masked, qi_base, ki_base,
                   k_major: bool = False):
    """Scaled score tile; causal mask applied only when ``masked`` —
    the one definition shared by the forward and the backward kernel
    so their masking can never drift apart. ``k_major`` gives the
    tile transposed, ``[bk, bq]`` (the backward's form). Returns
    (scores, valid) where valid is the boolean keep-mask (None when
    unmasked): the backward must zero dS at masked positions, because
    in the reference formulation the mask's where() makes masked scores
    constants that carry no gradient — p=0 handles that for ordinary
    rows, but a fully-masked row has uniform nonzero p and still must
    not push gradient into q/k."""
    rows, cols = (k, q) if k_major else (q, k)
    s = jax.lax.dot_general(
        rows, cols, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if not masked:
        return s, None
    q_dim = 1 if k_major else 0
    qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_dim) + qi_base
    ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_dim) + ki_base
    valid = qi >= ki
    return jnp.where(valid, s, NEG_INF), valid


def _qtile_bounds(causal: bool, skip_safe: bool, q0, bq: int, qo: int,
                  ko: int, nkb: int, bk: int):
    """Per-q-tile k-bounds (nb_full, nb), traced in the tile index:
    k-tiles [0, nb_full) are fully below the causal diagonal (unmasked
    body), [nb_full, nb) straddle or cross it (masked body), tiles >=
    nb are skipped. Skipping past the diagonal is exact only when
    ``skip_safe`` (kv_offset <= q_offset: every query sees at least its
    own position, so no row can be fully masked); otherwise every tile
    is processed so fully-masked rows reproduce the reference's
    uniform-softmax semantics exactly."""
    if not causal:
        return nkb, nkb
    qstart_g = q0 + qo
    if skip_safe:
        nb = jnp.minimum(nkb, jnp.maximum(
            0, (qstart_g + bq - 1 - ko) // bk + 1))
    else:
        nb = nkb
    nb_full = jnp.minimum(nb, jnp.maximum(
        0, (qstart_g - ko - bk + 1) // bk + 1))
    return nb_full, nb


def _head_lanes(shape, s: int, dh: int):
    """Boolean [rows, W] mask of the lanes that hold head ``s`` of a
    block (lanes [s*dh, (s+1)*dh))."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= s * dh) & (lane < (s + 1) * dh)


def _keep_head(x, s: int, dh: int):
    """``x`` [rows, W] with every lane outside head ``s`` an exact
    zero, so a contraction over all W lanes is that head's alone and a
    product with it leaves the other heads' lanes zero. One head a
    block: ``x`` itself."""
    if x.shape[-1] == dh:
        return x
    return jnp.where(_head_lanes(x.shape, s, dh), x, jnp.zeros_like(x))


def _tile_rows(i, edge: int, tiles: int):
    """Rows (or, of a lane-major statistic, lanes) of tile ``i``. Known
    to the compiler as a multiple of the edge, which a lane offset has
    to be (of 128); a short sequence is one tile whose edge need not
    be, and is addressed statically."""
    import jax.experimental.pallas as pl

    if tiles == 1:
        return pl.ds(0, edge)
    return pl.ds(pl.multiple_of(i * edge, edge), edge)


def _lane_major(cols):
    """Per-row statistics, a list of [bq, 1] f32 columns (one lane
    live in 128), as the rows of one [len(cols), bq] array: T on the
    lanes. Tile-aligned edges go through one [bq, 128] transpose;
    others (a short sequence that is a single tile) select the
    diagonal of a [bq, bq] panel, which asks nothing of the tiling."""
    bq = cols[0].shape[0]
    if bq % 128 == 0:
        lane = jax.lax.broadcasted_iota(jnp.int32, (bq, 128), 1)
        packed = jnp.broadcast_to(cols[0], (bq, 128))
        for r, col in enumerate(cols[1:], 1):
            packed = jnp.where(lane == r, col, packed)
        return packed.T[:len(cols)]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)
         for col in cols], axis=0)


def _one_lse(causal: bool, qo: int, ko: int) -> bool:
    """Whether one log-sum-exp a row is enough for the backward: no
    row can be fully masked (no causal mask, or kv_offset <= q_offset:
    every query sees at least its own position). Where rows can be, m
    and log(l) must not be pre-summed: there m is -1e30 and
    log(l)=log(S) would be absorbed by f32 rounding, making the
    backward reconstruct p=1 instead of the forward's uniform 1/S;
    exp((s - m) - log l) is exact."""
    return not causal or ko <= qo


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, stat_ref, *,
                      scale: float, causal: bool, qo: int, ko: int,
                      bq: int, bk: int, dh: int):
    """One (batch row, lane group, q-superblock) program: online
    softmax over [bq, bk] score tiles, head after head of the block's
    ``W // dh``. K/V stay VMEM-resident across a head's q-superblocks
    (their block index is constant in the superblock grid dim, so
    Mosaic does not re-DMA them); the superblock bounds per-program
    VMEM so long sequences (T > 2048) still fit."""
    import jax.experimental.pallas as pl

    qsb, w = q_ref.shape[1], q_ref.shape[2]
    sk = k_ref.shape[1]
    nkb = sk // bk
    skip_safe = causal and ko <= qo
    q_base = pl.program_id(2) * qsb

    def q_tile(i, _):
        rows = _tile_rows(i, bq, qsb // bq)
        q = q_ref[0, rows, :]
        nb_full, nb = _qtile_bounds(causal, skip_safe,
                                    q_base + i * bq, bq, qo, ko, nkb,
                                    bk)
        out = None
        stats = []
        for s in range(w // dh):
            qs = _keep_head(q, s, dh)

            def make_body(masked: bool, qs=qs):
                def body(j, carry):
                    m, l, acc = carry     # [BQ,1], [BQ,1], [BQ,W] f32
                    kj = k_ref[0, pl.ds(j * bk, bk), :]
                    vj = v_ref[0, pl.ds(j * bk, bk), :]
                    sc, _ = _masked_scores(qs, kj, scale, masked,
                                           q_base + i * bq + qo,
                                           j * bk + ko)
                    m_new = jnp.maximum(m, jnp.max(sc, axis=-1,
                                                   keepdims=True))
                    p = jnp.exp(sc - m_new)
                    corr = jnp.exp(m - m_new)
                    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
                    # all W lanes of p @ v; the head keeps its own below
                    acc = acc * corr + jax.lax.dot_general(
                        p.astype(vj.dtype), vj, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return m_new, l, acc
                return body

            init = (jnp.full((bq, 1), -jnp.inf, jnp.float32),
                    jnp.zeros((bq, 1), jnp.float32),
                    jnp.zeros((bq, w), jnp.float32))
            carry = jax.lax.fori_loop(0, nb_full, make_body(False), init)
            m, l, acc = jax.lax.fori_loop(nb_full, nb, make_body(causal),
                                          carry)
            o_s = acc / l
            out = o_s if out is None else jnp.where(
                _head_lanes(o_s.shape, s, dh), o_s, out)
            stats += [m + jnp.log(l)] if _one_lse(causal, qo, ko) \
                else [m, jnp.log(l)]
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        # saved for the Pallas backward, a row a statistic a head
        stat_ref[0, 0, :, rows] = _lane_major(stats)
        return ()

    jax.lax.fori_loop(0, qsb // bq, q_tile, ())


def _flash_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, stat_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, delta, *,
                      scale: float, causal: bool, qo: int, ko: int,
                      bq: int, bk: int, dh: int):
    """One (batch row, lane group) per program, ALL THREE gradients in
    one pass: looping k-blocks outer / q-tiles inner, each tile's
    probability and dS panels are computed ONCE, k-major ([bk, bq]: a
    q row's statistics are [1, bq] rows over the sublanes), and feed
    dV += Pᵀ dO, dK += dSᵀ Q and dQ[i] += dS K (accumulated across the
    outer loop in a VMEM scratch, written out at the end). Before
    them, once a program, Δ_i = Σ_d dO_id · O_id of every row and head
    goes into a second scratch, lane-major like the statistics: left
    to XLA it is a reduction over the minor 64 of [B, T, H, 64] whose
    result wants T minor, which XLA bought with a transposed f32 copy
    of the whole product. A block's heads take turns; each masks its
    q, dO and k to its own lanes, so its three products leave the
    other heads' lanes exact zeros and every accumulator is shared.
    The panel recompute (exp) is the
    VPU-bound cost of the backward — the separate-dQ variant paid it
    twice. Under causal+skip-safe offsets, q-tiles strictly above the
    diagonal contribute exactly 0 and the loop starts at the diagonal;
    without it every tile runs — fully-masked rows carry p = 1/S into
    dV (the reference's uniform-softmax gradient)."""
    import jax.experimental.pallas as pl

    tq, w = q_ref.shape[1], q_ref.shape[2]
    ksb = k_ref.shape[1]           # this program's k-superblock extent
    hpb = w // dh
    nstat = stat_ref.shape[2] // hpb
    nqb = tq // bq
    skip_safe = causal and ko <= qo
    k_base = pl.program_id(2) * ksb

    # both scratches persist across the k-superblock grid dim: zero the
    # dq accumulator and fill delta on the first superblock only
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

        def delta_tile(i, _):
            rows = _tile_rows(i, bq, nqb)
            prod = (do_ref[0, rows, :].astype(jnp.float32)
                    * o_ref[0, rows, :].astype(jnp.float32))
            delta[:, rows] = _lane_major(
                [jnp.sum(_keep_head(prod, s, dh), axis=-1, keepdims=True)
                 for s in range(hpb)])
            return ()

        jax.lax.fori_loop(0, nqb, delta_tile, ())

    def k_tile(jk, _):
        k = k_ref[0, pl.ds(jk * bk, bk), :]
        v = v_ref[0, pl.ds(jk * bk, bk), :]
        ki0 = k_base + jk * bk + ko
        if skip_safe:
            # first q-tile whose LAST row reaches this k-block's first
            # col: i*bq + bq - 1 + qo >= ki0
            start = jnp.maximum(0, -(-(ki0 - qo - (bq - 1)) // bq))
        else:
            start = 0
        if causal:
            # first q-tile FULLY below the diagonal (first row >= this
            # k-block's last col) — masked/unmasked phase split
            full_start = jnp.clip(-(-(ki0 + bk - 1 - qo) // bq),
                                  start, nqb)
        else:
            full_start = start

        carry = (jnp.zeros((bk, w), jnp.float32),
                 jnp.zeros((bk, w), jnp.float32))
        for s in range(hpb):
            ks = _keep_head(k, s, dh)

            def make_body(masked: bool, s=s, ks=ks):
                def body(i, carry):
                    dk, dv = carry
                    rows = _tile_rows(i, bq, nqb)
                    qi = _keep_head(q_ref[0, rows, :], s, dh)
                    doi = _keep_head(do_ref[0, rows, :], s, dh)
                    st = [stat_ref[0, 0, pl.ds(s * nstat + r, 1), rows]
                          for r in range(nstat)]         # [1, bq] each
                    deltai = delta[pl.ds(s, 1), rows]
                    sc, valid = _masked_scores(qi, k, scale, masked,
                                               i * bq + qo, ki0,
                                               k_major=True)
                    p = jnp.exp(sc - st[0]) if nstat == 1 \
                        else jnp.exp((sc - st[0]) - st[1])
                    dv = dv + jax.lax.dot_general(
                        p.astype(doi.dtype), doi,
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dp = jax.lax.dot_general(
                        v, doi, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    ds = p * (dp - deltai)
                    if valid is not None:
                        ds = jnp.where(valid, ds, 0.0)
                    dsq = ds.astype(qi.dtype)
                    dk = dk + jax.lax.dot_general(
                        dsq, qi, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dq_acc[rows, :] += jax.lax.dot_general(
                        dsq, ks, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    return dk, dv
                return body

            carry = jax.lax.fori_loop(start, full_start,
                                      make_body(causal), carry)
            carry = jax.lax.fori_loop(full_start, nqb, make_body(False),
                                      carry)
        dk, dv = carry
        dk_ref[0, pl.ds(jk * bk, bk), :] = \
            (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, pl.ds(jk * bk, bk), :] = dv.astype(dv_ref.dtype)
        return ()

    jax.lax.fori_loop(0, ksb // bk, k_tile, ())
    # written every superblock; only the final state leaves VMEM (the
    # dq block index is constant in the superblock grid dim)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


# max programs per pallas_call when the grid has REAL superblocks
# (nsb > 1). Larger such grids do not compile: on libtpu 0.0.34
# (2026-09-26) a (32, 4) forward grid fails with a scoped-vmem stack
# OOM ("Ran out of memory in memory space vmem while allocating on
# stack ... It should not be possible to run out of scoped vmem") even
# though its per-program footprint equals that of the capped chunks
# that compile — the accounting grows with the number of grid programs.
# Earlier toolchains showed the same boundary (fwd: (32,4) and (64,2)
# fail; the scratch-carrying fused backward fails earlier, at (32,2)),
# first as a compiler death with no diagnostic, then with this message.
# Grids with nsb == 1 are exempt from the cap — they are the T<=2048
# hot path and compile at least to (128, 1).
# The grid is (batch-heads, lane groups, superblocks) since PR 27, one
# lane group in the per-head form that long sequences take; the caps
# count batch-heads x superblocks as before. With the statistics as
# rows and not [T, 1] columns, that (32, 4) forward at T=8192 compiles
# for a described v5e (PR 27; no chip run, so the caps stay).
# `benchmarks/grid_crash_repro.py` is the checked-in minimal repro: run
# it after any jax/libtpu bump — if it stops failing, the caps can be
# raised; if smaller grids start failing, lower them. It lifts the caps
# by assigning these names in its own process.
_MAX_2D_GRID_FWD = 96
_MAX_2D_GRID_BWD = 32
# The forward over the block's own layout, counted as its grid's
# programs (entries x lane groups x superblocks): on one v5e, jax 0.9.0 /
# libtpu 0.0.34, grids of (2, 20, 4), (3, 16, 4), (4, 16, 4) and
# (2, 32, 4) at T 8192 and head_dim 256 compiled and ran (2026-10-15),
# the last two 256 programs; nothing larger was tried. The one-head-an-
# entry form keeps its own cap above, as it was measured.
_MAX_LANE_GRID_FWD = 256


def _bh_chunks(bh: int, nsb: int, cap: int, group: int = 1):
    """Slice extents over the batch-head axis keeping the grid's
    chunk x nsb within ``cap`` programs; whole groups of ``group``
    query heads (those that share a K/V head) stay in one chunk."""
    if nsb <= 1:
        return [(0, bh)]
    step = max(group, cap // nsb // group * group)
    return [(lo, min(step, bh - lo)) for lo in range(0, bh, step)]


def _grid_parts(n: int, groups: int, nsb: int, cap: int):
    """The block's own layout split for the caps above, as (first entry,
    entries, first lane group, lane groups) of each call: every program
    the grid has counts, entries x lane groups x superblocks. Even
    ranges of lane groups while one entry range fits, else one lane
    group a call over as many entries as fit. Each part addresses its
    blocks by offsets in the index maps and writes into the one output
    buffer the parts share: nothing is sliced or concatenated."""
    if nsb <= 1 or n * groups * nsb <= cap:
        return [(0, n, 0, groups)]
    if n * nsb <= cap:
        calls = -(-groups // (cap // (n * nsb)))
        step = -(-groups // calls)
        return [(0, n, g0, min(step, groups - g0))
                for g0 in range(0, groups, step)]
    step = max(1, cap // nsb)
    return [(b0, min(step, n - b0), g0, 1)
            for g0 in range(groups) for b0 in range(0, n, step)]


def _plus(x, c: int):
    """A block index moved by a static offset; the index itself at 0,
    so that a call that is not split lowers as it always has."""
    return x + c if c else x


def _skip_refs(kernel, first: int, count: int):
    """``kernel`` handed every ref but the ``count`` from ``first`` on:
    the buffers a part writes into by aliasing, which it never reads."""
    def body(*refs):
        return kernel(*refs[:first], *refs[first + count:])
    return body


# q extent per forward kernel call: at T=16384 the full-T call's
# scoped-vmem accounting lands 156KB over the 16MB cap (measured r5),
# so longer sequences split over q at host level — forward q chunks
# are fully independent (per-row online-softmax stats), no merge pass.
_FWD_Q_CHUNK = 8192
_FWD_QSB = 2048


def _q_superblock(tq: int) -> int:
    """Forward q-superblock: bounds per-program VMEM (full-T q/o
    blocks blow the 16MB budget past T=2048); K/V block indices are
    constant in that grid dim, so they stay VMEM-resident across a
    head's superblocks. Very long K/V (>8k rows
    resident) needs a smaller superblock to stay under the scoped-vmem
    cap (r5)."""
    return _inner_block(tq, _FWD_QSB)


def _k_superblock(sk: int, bk: int) -> int:
    """Backward k-superblock (long-T VMEM bound, mirroring the
    forward's q-superblocks); q/do/stats blocks stay VMEM-resident
    across it and the dq scratch accumulates through it. At most TWO
    superblocks — backward grids with a superblock dim >= 4 failed to
    compile on the toolchain this was tuned on (no diagnostic) — and
    ksb must be a multiple of bk (the kernel loops ksb // bk tiles; a
    non-multiple would silently skip the tail k-rows)."""
    return sk // 2 if (sk % (2 * bk) == 0 and sk // 2 >= 2048) else sk


def _flash_forward(q3, k3, v3, dh: int, scale: float, causal: bool,
                   q_offset: int, kv_offset: int, interpret: bool,
                   group: int = 1):
    """(out [N, T, L], stats [N, L // W, rows, T]) of operands
    [N, T, L] holding L // dh heads of dh lanes side by side: the
    block's own layout (N = B, L = H*Dh) or one head an entry
    (N = B*H, L = Dh). rows: a log-sum-exp a head of a block, or m and
    log(l) apart (`_one_lse`). With ``group`` > 1 (grouped-query
    attention) k3 and v3 hold a group's shared K/V head once: in the
    block's own layout lane group g of q3 reads lane group g // group of
    them, one head an entry entry n reads entry n // group, through the
    block index, never repeated."""
    if q3.shape[2] != dh:
        return _forward_lane_dense(q3, k3, v3, dh, scale, causal, q_offset,
                                   kv_offset, interpret, group)
    tq = q3.shape[1]
    chunk = _fwd_q_chunk(tq, causal, q_offset, kv_offset)
    if chunk < tq:
        outs = [_flash_forward_impl(
            q3[:, lo:lo + chunk], k3, v3, dh, scale, causal,
            q_offset + lo, kv_offset, interpret, group)
            for lo in range(0, tq, chunk)]
        return (jnp.concatenate([o for o, _ in outs], axis=1),
                jnp.concatenate([st for _, st in outs], axis=-1))
    return _flash_forward_impl(q3, k3, v3, dh, scale, causal, q_offset,
                               kv_offset, interpret, group)


def _fwd_q_chunk(tq: int, causal: bool, q_offset: int,
                 kv_offset: int) -> int:
    """q rows a forward call takes: past `_FWD_Q_CHUNK` the largest
    chunk that divides T, else T. Rows that can be fully masked keep
    one call over T, so that every call writes the same statistic rows
    (`_one_lse`)."""
    if tq > _FWD_Q_CHUNK and _one_lse(causal, q_offset, kv_offset):
        return _chunk_of(tq, _FWD_Q_CHUNK) or tq
    return tq


def _fwd_pieces(shape, dh: int, causal: bool, q_offset: int,
                kv_offset: int):
    """The calls of a forward over the block's own layout, as (q rows,
    part): rows (first, count) as `_fwd_q_chunk` gives them, parts as
    `_grid_parts` does."""
    n, tq, lanes = shape
    chunk = _fwd_q_chunk(tq, causal, q_offset, kv_offset)
    parts = _grid_parts(n, lanes // _block_width(lanes, dh),
                        chunk // _q_superblock(chunk), _MAX_LANE_GRID_FWD)
    return [((lo, chunk), part) for lo in range(0, tq, chunk)
            for part in parts]


def _forward_lane_dense(q3, k3, v3, dh, scale, causal, q_offset, kv_offset,
                        interpret, group):
    """The forward over the block's own layout at every length: each
    call addresses its q rows and its heads through the index maps, and
    all of them write one output and one statistics buffer."""
    out = None
    for rows, part in _fwd_pieces(q3.shape, dh, causal, q_offset,
                                  kv_offset):
        out = _fwd_call(q3, k3, v3, dh, scale, causal, q_offset, kv_offset,
                        interpret, group, rows=rows, part=part, into=out)
    return tuple(out)


def _block_width(lanes: int, dh: int) -> int:
    """Lanes a program takes of operands [N, T, lanes]: all of one
    head's where an entry is a head, else 128 or a whole wider head."""
    return dh if lanes == dh else max(128, dh)


def _vmem_params(nbytes: int):
    """Compiler parameters for a call whose resident blocks (double
    buffered) pass the 16 MB default of scoped VMEM: a K/V pair of
    8192 rows at head_dim 256 is 8 MB before its second buffer. Shapes
    under the default get none, and compile as they always have."""
    if 2 * nbytes <= 12 * 2 ** 20:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(100 * 2 ** 20, 2 * nbytes + 24 * 2 ** 20))


def _kv_index(lanes: int, dh: int, group: int, b0: int, g0: int, rows):
    """Block index map of a K/V operand, its row block ``rows(i)`` of
    the grid's third index: a group's shared head through the entry
    (one head an entry) or through the lane group (the block's own
    layout), moved by the part's offsets."""
    if lanes == dh or group == 1:
        return lambda b, g, i: (_plus(b, b0) // group, rows(i),
                                _plus(g, g0))
    return lambda b, g, i: (_plus(b, b0), rows(i), _plus(g, g0) // group)


def _fwd_call(q3, k3, v3, dh: int, scale: float, causal: bool,
              q_offset: int, kv_offset: int, interpret: bool,
              group: int = 1, rows=None, part=None, into=None):
    """One `flash_fwd` call, by default over all of q3, returning new
    (o, stats). ``rows`` (first q row, rows) and ``part`` (first entry,
    entries, first lane group, lane groups) select a piece by static
    offsets in the index maps; its results still have the whole's
    shape, the piece's blocks written and the rest left to the pieces
    after it, which write into ``into``, aliased: the buffers an earlier
    piece returned."""
    import jax.experimental.pallas as pl

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    n, tq, lanes = q3.shape
    sk = k3.shape[1]
    w = _block_width(lanes, dh)
    groups = lanes // w
    r0, nr = rows or (0, tq)
    b0, nb, g0, ng = part or (0, n, 0, groups)
    nrows = (w // dh) * (1 if _one_lse(causal, q_offset, kv_offset) else 2)
    bq = _inner_block(nr)
    bk = _inner_block(sk)
    qsb = _q_superblock(nr)
    i0 = r0 // qsb
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        qo=int(q_offset + r0), ko=int(kv_offset), bq=bq, bk=bk, dh=dh)
    qspec = pl.BlockSpec((1, qsb, w), lambda b, g, i: (
        _plus(b, b0), _plus(i, i0), _plus(g, g0)))
    kvspec = pl.BlockSpec((1, sk, w), _kv_index(lanes, dh, group, b0, g0,
                                                lambda i: 0))
    stat_spec = pl.BlockSpec((1, 1, nrows, qsb), lambda b, g, i: (
        _plus(b, b0), _plus(g, g0), 0, _plus(i, i0)))
    resident = (2 * sk + 2 * qsb) * w * q3.dtype.itemsize
    args, in_specs, aliases = (q3, k3, v3), [qspec, kvspec, kvspec], {}
    if into is not None:
        args += tuple(into)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        aliases = {3: 0, 4: 1}
        kernel = _skip_refs(kernel, 3, 2)
    return pl.pallas_call(
        kernel,
        out_shape=[out_struct((n, tq, lanes), q3.dtype, q3, k3, v3),
                   out_struct((n, groups, nrows, tq), jnp.float32,
                              q3, k3, v3)],
        grid=(nb, ng, nr // qsb),
        in_specs=in_specs,
        out_specs=[qspec, stat_spec],
        input_output_aliases=aliases,
        interpret=interpret_arg(interpret, q3, k3, v3),
        compiler_params=_vmem_params(resident),
        name="flash_fwd",
    )(*args)


def _flash_forward_impl(q3, k3, v3, dh: int, scale: float, causal: bool,
                        q_offset: int, kv_offset: int, interpret: bool,
                        group: int = 1):
    """The forward of one head an entry: the batch-head axis sliced
    into chunks that keep the grid within its cap."""
    n, tq, _ = q3.shape

    def call(qc, kc, vc):
        return _fwd_call(qc, kc, vc, dh, scale, causal, q_offset,
                         kv_offset, interpret, group)

    chunks = _bh_chunks(n, tq // _q_superblock(tq), _MAX_2D_GRID_FWD, group)
    if len(chunks) == 1:
        return call(q3, k3, v3)
    outs = [call(q3[lo:lo + c], k3[lo // group:(lo + c) // group],
                 v3[lo // group:(lo + c) // group])
            for lo, c in chunks]
    return tuple(jnp.concatenate([o[i] for o in outs], axis=0)
                 for i in range(2))


def _bwd_call(q3, k3, v3, o3, do3, stats, dh, scale, causal, q_offset,
              kv_offset, interpret, group=1, rows=None, krows=None,
              part=None, into=None):
    """One `flash_bwd` call, by default over all of its operands,
    returning new (dq, dk, dv), dk and dv a query head's own. ``rows``
    (first q row, rows) and ``krows`` (first k row, rows) select a tile
    of the whole operands by static offsets in the index maps; its
    results are the tile's, dq [N, rows, L] and dk, dv [N, krows, L].
    ``part`` and ``into`` split the tile's grid as `_fwd_call`'s do."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    n, tq, lanes = q3.shape
    sk = k3.shape[1]
    w = _block_width(lanes, dh)
    groups = lanes // w
    r0, nr = rows or (0, tq)
    k0, nk = krows or (0, sk)
    b0, nb, g0, ng = part or (0, n, 0, groups)
    bq = _inner_block(nr)
    # 256-col k-tiles: the fused three-gradient kernel's panel stack
    # (s/p/dp/ds + dq scratch) must fit the 16MB scoped-VMEM budget
    bk = _inner_block(nk, 256)
    ksb = _k_superblock(nk, bk)
    i0, j0 = r0 // nr, k0 // ksb
    kernel = functools.partial(
        _flash_bwd_kernel, scale=scale, causal=causal,
        qo=int(q_offset + r0), ko=int(kv_offset + k0), bq=bq, bk=bk, dh=dh)
    full = pl.BlockSpec((1, nr, w), lambda b, g, j: (
        _plus(b, b0), i0, _plus(g, g0)))
    kin = pl.BlockSpec((1, ksb, w), _kv_index(lanes, dh, group, b0, g0,
                                              lambda j: _plus(j, j0)))
    stat_spec = pl.BlockSpec((1, 1, stats.shape[2], nr), lambda b, g, j: (
        _plus(b, b0), _plus(g, g0), 0, i0))
    dq_spec = pl.BlockSpec((1, nr, w), lambda b, g, j: (
        _plus(b, b0), 0, _plus(g, g0)))
    dkv_spec = pl.BlockSpec((1, ksb, w), lambda b, g, j: (
        _plus(b, b0), j, _plus(g, g0)))
    resident = ((3 * nr + 4 * ksb) * q3.dtype.itemsize + 2 * nr) * w
    args = (q3, k3, v3, o3, do3, stats)
    in_specs, aliases = [full, kin, kin, full, full, stat_spec], {}
    if into is not None:
        args += tuple(into)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 3
        aliases = {6: 0, 7: 1, 8: 2}
        kernel = _skip_refs(kernel, 6, 3)
    return pl.pallas_call(
        kernel,
        out_shape=[out_struct((n, nr, lanes), q3.dtype, *args[:6]),
                   out_struct((n, nk, lanes), k3.dtype, *args[:6]),
                   out_struct((n, nk, lanes), v3.dtype, *args[:6])],
        grid=(nb, ng, nk // ksb),
        in_specs=in_specs,
        out_specs=[dq_spec, dkv_spec, dkv_spec],
        input_output_aliases=aliases,
        scratch_shapes=[pltpu.VMEM((nr, w), jnp.float32),
                        pltpu.VMEM((w // dh, nr), jnp.float32)],
        interpret=interpret_arg(interpret, *args[:6]),
        compiler_params=_vmem_params(resident),
        name="flash_bwd",
    )(*args)


def _flash_backward(q3, k3, v3, o3, stats, g, dh, scale, causal,
                    q_offset, kv_offset, interpret, group: int = 1):
    """Pallas backward of one head an entry: ONE program per (batch
    row, lane group) producing dQ, dK and dV together (shared
    probability panels). With ``group`` > 1 a program reads its group's
    shared K/V entry and writes its own query head's dK and dV, which
    are then summed over the group in float32."""
    n = q3.shape[0]
    sk = k3.shape[1]
    ksb = _k_superblock(sk, _inner_block(sk, 256))

    def call(args):
        return _bwd_call(*args, dh, scale, causal, q_offset, kv_offset,
                         interpret, group)

    def over_group(x):          # a K/V head's query heads add up
        if group == 1:
            return x
        return jnp.sum(x.astype(jnp.float32).reshape(
            (n // group, group) + x.shape[1:]), axis=1).astype(x.dtype)

    operands = (q3, k3, v3, o3, g, stats)
    chunks = _bh_chunks(n, sk // ksb, _MAX_2D_GRID_BWD, group)
    if len(chunks) == 1:
        dq, dk, dv = call(operands)
        return dq, over_group(dk), over_group(dv)
    outs = [call(tuple(
        a[lo // group:(lo + c) // group] if a is k3 or a is v3
        else a[lo:lo + c] for a in operands)) for lo, c in chunks]
    dq, dk, dv = (jnp.concatenate([o[i] for o in outs], axis=0)
                  for i in range(3))
    return dq, over_group(dk), over_group(dv)


def _bwd_tiles(tq: int, sk: int, causal: bool, q_offset: int,
               kv_offset: int):
    """The backward's tiles (first q row, rows, first k row, rows): past
    `_BWD_K_CHUNK` keys a q x k grid of (<=2048, <=2048) tiles without
    those wholly above the causal diagonal (skipped only where no row
    can be fully masked: such a row's uniform softmax reaches every key,
    and its dV with it); else q chunks past `_BWD_Q_CHUNK` over all
    keys; else one tile."""
    if sk > _BWD_K_CHUNK:
        kc, qc = _chunk_of(sk, _BWD_LONG_TILE), _chunk_of(tq, _BWD_LONG_TILE)
        if kc and qc:
            skip = causal and _one_lse(causal, q_offset, kv_offset)
            return [(qlo, qc, klo, kc) for qlo in range(0, tq, qc)
                    for klo in range(0, sk, kc)
                    if not (skip and kv_offset + klo
                            > q_offset + qlo + qc - 1)]
    chunk = tq if tq <= _BWD_Q_CHUNK else _chunk_of(tq, _BWD_Q_CHUNK)
    return [(lo, chunk, 0, sk) for lo in range(0, tq, chunk)]


def _bwd_pieces(shape, sk: int, dh: int, causal: bool, q_offset: int,
                kv_offset: int):
    """The backward's tiles over the block's own layout, each with the
    parts (`_grid_parts`) its grid is called in."""
    n, tq, lanes = shape
    groups = lanes // _block_width(lanes, dh)
    return [(tile, _grid_parts(
        n, groups, tile[3] // _k_superblock(tile[3],
                                            _inner_block(tile[3], 256)),
        _MAX_2D_GRID_BWD))
        for tile in _bwd_tiles(tq, sk, causal, q_offset, kv_offset)]


def _backward_lane_dense(q3, k3, v3, o3, stats, g, dh, scale, causal,
                         q_offset, kv_offset, interpret, group):
    """The backward over the block's own layout at every length: each
    tile's q, o, dO and statistics rows and its k and v rows are
    selected by the index maps over the whole operands, and dq, dk and
    dv leave in the same layout. A tile's partials are added in float32
    once its last has come (with a query group a call's dk and dv first
    over a K/V head's query heads, by a reshape of the lane axis), and
    the tiles are laid side by side along T: one pass over the partials
    and one over the result. The tiles run so that the widest partials
    are added soonest (a k column after another for dk and dv, with a
    group a q row after another for dq): fewer are held at once."""
    tq, sk = q3.shape[1], k3.shape[1]
    pieces = sorted(_bwd_pieces(q3.shape, sk, dh, causal, q_offset,
                                kv_offset),
                    key=lambda p: p[0][2::-2] if group == 1 else p[0])
    sizes = pieces[0][0][1::2] + pieces[0][0][3:]
    left = ({}, {}, {})              # partials still to come, by tile
    for (qlo, _, klo, _), _ in pieces:
        for count, lo in zip(left, (qlo, klo, klo)):
            count[lo] = count.get(lo, 0) + 1
    parts, tiles = ({}, {}, {}), ({}, {}, {})
    for (qlo, qc, klo, kc), split in pieces:
        out = None
        for part in split:
            out = _bwd_call(q3, k3, v3, o3, g, stats, dh, scale, causal,
                            q_offset, kv_offset, interpret, group,
                            rows=(qlo, qc), krows=(klo, kc), part=part,
                            into=out)
        for i, lo in enumerate((qlo, klo, klo)):
            x = out[i] if i == 0 else _over_group(out[i], dh, group)
            parts[i].setdefault(lo, []).append(x)
            left[i][lo] -= 1
            if not left[i][lo]:
                ps = parts[i].pop(lo)
                tiles[i][lo] = ps[0] if len(ps) == 1 else functools.reduce(
                    jnp.add, [p.astype(jnp.float32) for p in ps]
                ).astype(ps[0].dtype)
    return tuple(_side_by_side(t, length, rows) for t, length, rows in
                 zip(tiles, (tq, sk, sk), sizes))


def _side_by_side(tiles, length: int, rows: int):
    """One gradient from its tiles ({first row: [N, rows, L]}) along T;
    a tile that no call reached is zero."""
    some = next(iter(tiles.values()))
    ts = [tiles.get(lo, jnp.zeros_like(some)) for lo in range(0, length,
                                                              rows)]
    return ts[0] if len(ts) == 1 else jnp.concatenate(ts, axis=1)


def _over_group(x, dh: int, group: int):
    """dk or dv of a call's query heads [N, rows, H*Dh] as its K/V
    heads', a group's ``group`` query heads added in float32 by a
    reshape of the lane axis; without a group, ``x`` itself."""
    if group == 1:
        return x
    n, rows, lanes = x.shape
    return jnp.sum(x.astype(jnp.float32).reshape(
        n, rows, lanes // (group * dh), group, dh), axis=3).reshape(
        n, rows, lanes // group).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_attention3(q3, k3, v3, dh, scale, causal, q_offset, kv_offset,
                      interpret, group=1):
    return _flash_forward(q3, k3, v3, dh, scale, causal, q_offset,
                          kv_offset, interpret, group)[0]


# The forward kernel's two results, as `jax.checkpoint` policies may ask
# for them by name (models/remat.py: a rematerialised layer that keeps them
# hands `flash_bwd` what the first forward wrote, and its recomputation
# holds no forward kernel). Identities outside a checkpoint.
RESIDUAL_NAMES = ("flash_fwd.o", "flash_fwd.stats")


def _fwd(q3, k3, v3, dh, scale, causal, q_offset, kv_offset, interpret,
         group=1):
    out, stats = _flash_forward(q3, k3, v3, dh, scale, causal, q_offset,
                                kv_offset, interpret, group)
    # named before they leave as output and as residuals: the residual
    # has to be the named value for a policy to save it
    out, stats = map(checkpoint_name, (out, stats), RESIDUAL_NAMES)
    return out, (q3, k3, v3, out, stats)


# q-extent per fused-backward call: the kernel holds full-T q/do and
# the dq scratch in VMEM (the limit was found when the statistics were
# three [T, 1] columns, lane-padded 128x; not found again since they
# are rows) — past this the 16MB budget blew, so longer sequences
# split over q at the host level (dK/dV are linear in the q chunks and
# sum; dQ concatenates). Do NOT lower it
# chasing speed — the round-4 end-to-end A/B measured chunk 512 COSTS
# 16% on the flagship step (4x K/V re-reads); the default is the
# measured optimum
_BWD_Q_CHUNK = 4096


# K/V extent past which the backward is 2-D host-tiled (see _bwd).
# 4096 = the longest sk the single fused call compiles at on this
# toolchain; the TILE edge is 2048 — the per-call extent PROVEN to
# compose (the 12-layer T=2048 training program holds 12 such calls).
_BWD_K_CHUNK = 4096
_BWD_LONG_TILE = 2048


def _chunk_of(n: int, cap: int) -> int:
    """Largest BLOCK_Q-multiple divisor of n that is <= cap (0 if none)."""
    start = max(BLOCK_Q, (cap // BLOCK_Q) * BLOCK_Q)
    for c in range(start, 0, -BLOCK_Q):
        if n % c == 0:
            return c
    return 0


def _bwd(dh, scale, causal, q_offset, kv_offset, interpret, group, res, g):
    """Long-sequence backward = 2-D host tiling over the fused kernel
    (r5). Sequences past ~4k failed to compile even with q chunked —
    and two (3072, 3072) kernel calls that each compile ALONE failed
    when jitted into one program (the spurious scoped-vmem accounting,
    grid_crash_repro.py family; observed on an earlier toolchain, not
    retried on libtpu 0.0.34), while twelve
    (2048, 2048) calls provably coexist (the flagship training
    program). So for sk > _BWD_K_CHUNK the backward runs a q x k grid
    of (<=2048, <=2048) kernel calls: each tile's partial
    probabilities use the GLOBAL softmax stats — the same
    decomposition the in-kernel k-superblock loop applies — so dQ
    sums over k tiles, dK/dV sum over q tiles, and causally
    fully-masked tiles (k tile entirely after the q tile's last row)
    are skipped at trace time. This takes single-chip training from
    T<=4096 to T=8192+ on this toolchain. Over the block's own layout
    the same tiles (`_bwd_tiles`) address the whole operands through the
    index maps (`_backward_lane_dense`); one head an entry slices them."""
    q3, k3, v3, o3, stats = res
    if q3.shape[2] != dh:
        return _backward_lane_dense(q3, k3, v3, o3, stats, g, dh, scale,
                                    causal, q_offset, kv_offset, interpret,
                                    group)
    sk = k3.shape[1]
    tq = q3.shape[1]
    if sk > _BWD_K_CHUNK:
        kc = _chunk_of(sk, _BWD_LONG_TILE)
        qc = _chunk_of(tq, _BWD_LONG_TILE)
        if kc and qc:
            tiles = _bwd_tiles(tq, sk, causal, q_offset, kv_offset)
            dqs = []
            dks = [None] * (sk // kc)
            dvs = [None] * (sk // kc)
            for qlo in range(0, tq, qc):
                qsl = slice(qlo, qlo + qc)
                dq = None
                for ki, klo in enumerate(range(0, sk, kc)):
                    if (qlo, qc, klo, kc) not in tiles:
                        continue    # tile entirely above the diagonal
                    ksl = slice(klo, klo + kc)
                    dq_c, dk_c, dv_c = _flash_backward(
                        q3[:, qsl], k3[:, ksl], v3[:, ksl], o3[:, qsl],
                        stats[..., qsl], g[:, qsl], dh, scale,
                        causal, q_offset + qlo, kv_offset + klo,
                        interpret, group)
                    dq = (dq_c.astype(jnp.float32) if dq is None
                          else dq + dq_c.astype(jnp.float32))
                    dk32 = dk_c.astype(jnp.float32)
                    dv32 = dv_c.astype(jnp.float32)
                    dks[ki] = dk32 if dks[ki] is None else dks[ki] + dk32
                    dvs[ki] = dv32 if dvs[ki] is None else dvs[ki] + dv32
                dqs.append(jnp.zeros_like(q3[:, qsl]) if dq is None
                           else dq.astype(q3.dtype))
            zk = jnp.zeros((k3.shape[0], kc, k3.shape[2]), jnp.float32)
            return (jnp.concatenate(dqs, axis=1),
                    jnp.concatenate(
                        [zk if d is None else d for d in dks],
                        axis=1).astype(k3.dtype),
                    jnp.concatenate(
                        [zk if d is None else d for d in dvs],
                        axis=1).astype(v3.dtype))
    return _bwd_qchunks(dh, scale, causal, q_offset, kv_offset, interpret,
                        group, res, g)


def _bwd_qchunks(dh, scale, causal, q_offset, kv_offset, interpret, group,
                 res, g):
    q3, k3, v3, o3, stats = res
    sk = k3.shape[1]
    tq = q3.shape[1]
    # kv must tile AND long-tq must be chunkable: a tq like 6144 that
    # exceeds _BWD_Q_CHUNK without dividing by it must NOT run the
    # full-T fused kernel the module docstring says blows VMEM
    # (advisor r3). The chunk is the largest BLOCK_Q-multiple divisor
    # of tq <= _BWD_Q_CHUNK (6144 -> 3072), so such shapes stay on the
    # fused path; only a truly undividable tq falls back to the
    # jnp-recompute VJP (which materializes [B*H, tq, sk] f32 — fine
    # at the short lengths that can actually reach it).
    chunk = tq
    if tq > _BWD_Q_CHUNK:
        chunk = _chunk_of(tq, _BWD_Q_CHUNK)
    if sk % min(BLOCK_Q, sk) == 0 and chunk:
        if tq > chunk:
            dqs = []
            dk = dv = None
            for lo in range(0, tq, chunk):
                sl = slice(lo, lo + chunk)
                dq_c, dk_c, dv_c = _flash_backward(
                    q3[:, sl], k3, v3, o3[:, sl], stats[..., sl],
                    g[:, sl], dh, scale, causal, q_offset + lo,
                    kv_offset, interpret, group)
                dqs.append(dq_c)
                dk = dk_c.astype(jnp.float32) if dk is None \
                    else dk + dk_c.astype(jnp.float32)
                dv = dv_c.astype(jnp.float32) if dv is None \
                    else dv + dv_c.astype(jnp.float32)
            return (jnp.concatenate(dqs, axis=1),
                    dk.astype(k3.dtype), dv.astype(v3.dtype))
        return _flash_backward(q3, k3, v3, o3, stats, g, dh, scale,
                               causal, q_offset, kv_offset, interpret,
                               group)
    # kv length doesn't tile: jnp-recompute fallback (a head an entry:
    # `_lane_dense_width` keeps such lengths in the per-head form)
    _, vjp = jax.vjp(
        lambda q, k, v: _reference_attention(
            q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0),
            scale, causal, q_offset, kv_offset),
        q3, k3, v3)
    return vjp(g)


_flash_attention3.defvjp(_fwd, _bwd)


def flash_attention_available(q: Array, k: Array,
                              mask: Optional[Array]) -> bool:
    """Kernel eligibility: TPU backend (or forced interpret), no arbitrary
    mask (padding masks take the jnp path), q length divisible by the
    block."""
    env = os.environ.get("DL4JTPU_FLASH", "auto")
    if env == "0":
        return False
    if mask is not None:
        return False
    if q.ndim != 4:
        return False
    # f64 nets (gradient checks) must keep full-precision accumulation;
    # the kernel computes in f32
    if q.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
        return False
    tq = q.shape[1]
    if tq % min(BLOCK_Q, tq) != 0 or tq < 8:
        return False
    # kv extents with no power-of-two tile (e.g. cross-attention
    # S=2500) would become ONE untiled panel, silently bypassing the
    # VMEM bounds the tile caps enforce (advisor r3) — jnp path instead
    sk = k.shape[1]
    if sk > 512 and _inner_block(sk) == sk:
        return False
    if env == "interpret":
        return True
    return jax.default_backend() == "tpu"


def _lane_dense_width(h: int, d: int, tq: int, sk: int,
                      group: int = 1) -> int:
    """Lanes a program takes of the block's own [B, T, H*Dh] layout
    (128, or a whole wider head), or 0 for shapes that keep one head an
    entry of [B*H, T, Dh]. From shapes alone: heads must fill whole
    128-lane groups (Dh divides 128 or is a multiple of it, the local
    H*Dh a multiple of the width) and the keys tile (else the
    backward's jnp fallback). A head of a multiple of 128 lanes is a
    program's own block at every length and for any query group: the
    host's tilings move index maps over it, and a K/V head's lane block
    is its query head's // group. Narrower heads share a block by lane
    mask, which holds one superblock in each direction with no host
    tiling and no group; longer or grouped, they keep one head an
    entry."""
    w = max(128, d)
    if (128 % d and d % 128) or (h * d) % w:
        return 0
    if sk % min(BLOCK_Q, sk):
        return 0
    if d % 128 == 0:
        return w
    if group > 1 or _q_superblock(tq) != tq or tq > _BWD_Q_CHUNK \
            or sk > _BWD_K_CHUNK \
            or _k_superblock(sk, _inner_block(sk, 256)) != sk:
        return 0
    return w


def _kernel_calls(n: int, tq: int, sk: int, lanes: int, dh: int,
                  causal: bool, q_offset: int, kv_offset: int,
                  group: int):
    """(forward, backward): the kernel calls that one forward and one
    backward of operands [n, tq, lanes] over sk keys issue, from the
    plans the two paths follow."""
    if lanes != dh:
        return (len(_fwd_pieces((n, tq, lanes), dh, causal, q_offset,
                                kv_offset)),
                sum(len(parts) for _, parts in _bwd_pieces(
                    (n, tq, lanes), sk, dh, causal, q_offset, kv_offset)))
    chunk = _fwd_q_chunk(tq, causal, q_offset, kv_offset)
    fwd = tq // chunk * len(_bh_chunks(
        n, chunk // _q_superblock(chunk), _MAX_2D_GRID_FWD, group))
    if sk % min(BLOCK_Q, sk):           # the jnp fallback
        return fwd, 0
    return fwd, sum(len(_bh_chunks(
        n, kc // _k_superblock(kc, _inner_block(kc, 256)),
        _MAX_2D_GRID_BWD, group))
        for *_, kc in _bwd_tiles(tq, sk, causal, q_offset, kv_offset))


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = False,
                    q_offset=0, kv_offset=0,
                    scale: Optional[float] = None) -> Array:
    """[B, T, H, D] attention via the Pallas kernel. Same contract as
    attention.dot_product_attention (which dispatches here). k and v
    may hold fewer heads than q (grouped-query attention): KV head j
    serves query heads [j * H / Hkv, (j + 1) * H / Hkv), the K/V block
    shared through its block index in either layout."""
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import mark

    b, tq, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    interpret = os.environ.get("DL4JTPU_FLASH") == "interpret"
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"query heads {h} not a multiple of KV heads "
                         f"{hkv}")
    group = h // hkv
    w = _lane_dense_width(h, d, tq, k.shape[1], group)
    layout = "lane_dense" if w else "per_head"
    fwd_calls, bwd_calls = _kernel_calls(
        b if w else b * h, tq, k.shape[1], h * d if w else d, d,
        bool(causal), int(q_offset), int(kv_offset), group)
    # trace time: once a compiled program, not once a step
    mark("flash_attention.layout", layout=layout,
         heads_per_block=max(1, w // d), fwd_calls=fwd_calls,
         bwd_calls=bwd_calls)
    default_registry().counter(
        "flash_attention_calls",
        "flash_attention traces by operand layout (lane_dense: the "
        "block's own [B, T, H*Dh]; per_head: [B*H, T, Dh])",
        labelnames=("layout",)).labels(layout).inc()

    if w:       # [B, T, H, D] -> [B, T, H*D]: a bitcast
        def to3(x):
            return x.reshape(b, x.shape[1], x.shape[2] * d)
    else:       # [B, T, H, D] -> [B*H, T, D]: a transpose
        def to3(x):
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(
                b * x.shape[2], x.shape[1], d)

    def call(q3, k3, v3):
        return _flash_attention3(q3, k3, v3, d, float(scale), bool(causal),
                                 int(q_offset), int(kv_offset), interpret,
                                 group)

    # Traced under a jit that spans several devices (GSPMD: the FSDP
    # step says so by naming its mesh), a Mosaic call cannot be
    # partitioned automatically and refuses to lower. Batch rows (and
    # heads) are independent, so there the call runs under a shard_map
    # over the mesh's 'data' axis, where the batch is sharded: dimension
    # 0 of either layout. Inside another
    # shard_map (the composite step, the serving programs) the mesh's
    # axes are already manual and the call is left as it is.
    mesh = jax.sharding.get_abstract_mesh()
    if (not mesh.empty and not mesh.manual_axes
            and mesh.shape.get("data", 1) > 1
            and (b if w else b * hkv) % mesh.shape["data"] == 0):
        from jax.sharding import PartitionSpec as P
        call = jax.shard_map(call, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False)
    out3 = call(to3(q), to3(k), to3(v))
    if w:
        return out3.reshape(b, tq, h, d)
    return jnp.transpose(out3.reshape(b, h, tq, d), (0, 2, 1, 3))
