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
are lane-major, ``[B, groups, rows, T]`` with T on the lanes. Shapes
that cannot take that form (``H*Dh`` not a multiple of W, Dh 80 or 96,
sequences past one superblock) run the SAME kernel bodies over
``[B*H, T, Dh]`` with one head a program (`_lane_dense_width` decides,
from shapes alone; a trace-time mark ``flash_attention.layout`` and the
counter ``flash_attention_calls{layout}`` say which was taken).

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
# by assigning these two names in its own process.
_MAX_2D_GRID_FWD = 96
_MAX_2D_GRID_BWD = 32


def _bh_chunks(bh: int, nsb: int, cap: int, group: int = 1):
    """Slice extents over the batch-head axis keeping the grid's
    chunk x nsb within ``cap`` programs; whole groups of ``group``
    query heads (those that share a K/V head) stay in one chunk."""
    if nsb <= 1:
        return [(0, bh)]
    step = max(group, cap // nsb // group * group)
    return [(lo, min(step, bh - lo)) for lo in range(0, bh, step)]


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
    attention, one head an entry) k3 and v3 hold N // group entries and
    entry n of q3 reads entry n // group of them: the K/V block is
    shared by its group through the block index, never repeated."""
    tq = q3.shape[1]
    if tq > _FWD_Q_CHUNK:
        chunk = _chunk_of(tq, _FWD_Q_CHUNK)
        if chunk and chunk < tq:
            outs = [_flash_forward_impl(
                q3[:, lo:lo + chunk], k3, v3, dh, scale, causal,
                q_offset + lo, kv_offset, interpret, group)
                for lo in range(0, tq, chunk)]
            return (jnp.concatenate([o for o, _ in outs], axis=1),
                    jnp.concatenate([st for _, st in outs], axis=-1))
    return _flash_forward_impl(q3, k3, v3, dh, scale, causal, q_offset,
                               kv_offset, interpret, group)


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


def _flash_forward_impl(q3, k3, v3, dh: int, scale: float, causal: bool,
                        q_offset: int, kv_offset: int, interpret: bool,
                        group: int = 1):
    import jax.experimental.pallas as pl

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    n, tq, lanes = q3.shape
    sk = k3.shape[1]
    w = _block_width(lanes, dh)
    groups = lanes // w
    nrows = (w // dh) * (1 if _one_lse(causal, q_offset, kv_offset) else 2)
    bq = _inner_block(tq)
    bk = _inner_block(sk)
    qsb = _q_superblock(tq)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        qo=int(q_offset), ko=int(kv_offset), bq=bq, bk=bk, dh=dh)
    qspec = pl.BlockSpec((1, qsb, w), lambda b, g, i: (b, i, g))
    kvspec = pl.BlockSpec((1, sk, w), lambda b, g, i: (b // group, 0, g))
    stat_spec = pl.BlockSpec((1, 1, nrows, qsb),
                             lambda b, g, i: (b, g, 0, i))
    resident = (2 * sk + 2 * qsb) * w * q3.dtype.itemsize

    def call(qc, kc, vc):
        c = qc.shape[0]
        return pl.pallas_call(
            kernel,
            out_shape=[out_struct((c, tq, lanes), q3.dtype, qc, kc, vc),
                       out_struct((c, groups, nrows, tq), jnp.float32,
                                  qc, kc, vc)],
            grid=(c, groups, tq // qsb),
            in_specs=[qspec, kvspec, kvspec],
            out_specs=[qspec, stat_spec],
            interpret=interpret_arg(interpret, qc, kc, vc),
            compiler_params=_vmem_params(resident),
            name="flash_fwd",
        )(qc, kc, vc)

    chunks = _bh_chunks(n, tq // qsb, _MAX_2D_GRID_FWD, group)
    if len(chunks) == 1:
        return call(q3, k3, v3)
    outs = [call(q3[lo:lo + c], k3[lo // group:(lo + c) // group],
                 v3[lo // group:(lo + c) // group])
            for lo, c in chunks]
    return tuple(jnp.concatenate([o[i] for o in outs], axis=0)
                 for i in range(2))


def _flash_backward(q3, k3, v3, o3, stats, g, dh, scale, causal,
                    q_offset, kv_offset, interpret, group: int = 1):
    """Pallas backward: ONE program per (batch row, lane group)
    producing dQ, dK and dV together (shared probability panels).
    With ``group`` > 1 a program reads its group's shared K/V entry and
    writes its own query head's dK and dV, which are then summed over
    the group in float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    n, tq, lanes = q3.shape
    sk = k3.shape[1]
    w = _block_width(lanes, dh)
    groups = lanes // w
    bq = _inner_block(tq)
    # 256-col k-tiles: the fused three-gradient kernel's panel stack
    # (s/p/dp/ds + dq scratch) must fit the 16MB scoped-VMEM budget
    bk = _inner_block(sk, 256)
    ksb = _k_superblock(sk, bk)
    statics = dict(scale=scale, causal=causal, qo=int(q_offset),
                   ko=int(kv_offset), bq=bq, bk=bk, dh=dh)
    full = pl.BlockSpec((1, tq, w), lambda b, g, j: (b, 0, g))
    kspec = pl.BlockSpec((1, ksb, w), lambda b, g, j: (b, j, g))
    kin = pl.BlockSpec((1, ksb, w), lambda b, g, j: (b // group, j, g))
    stat_spec = pl.BlockSpec((1, 1, stats.shape[2], tq),
                             lambda b, g, j: (b, g, 0, 0))
    resident = ((3 * tq + 4 * ksb) * q3.dtype.itemsize + 2 * tq) * w

    def call(args):
        c = args[0].shape[0]
        return pl.pallas_call(
            functools.partial(_flash_bwd_kernel, **statics),
            out_shape=[out_struct((c, tq, lanes), q3.dtype, *args),
                       out_struct((c, sk, lanes), k3.dtype, *args),
                       out_struct((c, sk, lanes), v3.dtype, *args)],
            grid=(c, groups, sk // ksb),
            in_specs=[full, kin, kin, full, full, stat_spec],
            out_specs=[full, kspec, kspec],
            scratch_shapes=[pltpu.VMEM((tq, w), jnp.float32),
                            pltpu.VMEM((w // dh, tq), jnp.float32)],
            interpret=interpret_arg(interpret, *args),
            compiler_params=_vmem_params(resident),
            name="flash_bwd",
        )(*args)

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
    T<=4096 to T=8192+ on this toolchain."""
    q3, k3, v3, o3, stats = res
    sk = k3.shape[1]
    tq = q3.shape[1]
    if sk > _BWD_K_CHUNK:
        kc = _chunk_of(sk, _BWD_LONG_TILE)
        qc = _chunk_of(tq, _BWD_LONG_TILE)
        if kc and qc:
            dqs = []
            dks = [None] * (sk // kc)
            dvs = [None] * (sk // kc)
            for qlo in range(0, tq, qc):
                qsl = slice(qlo, qlo + qc)
                dq = None
                for ki, klo in enumerate(range(0, sk, kc)):
                    if causal and (kv_offset + klo
                                   > q_offset + qlo + qc - 1):
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


def _lane_dense_width(h: int, d: int, tq: int, sk: int) -> int:
    """Lanes a program takes of the block's own [B, T, H*Dh] layout
    (128, or a whole wider head), or 0 for shapes that keep one head an
    entry of [B*H, T, Dh]. From shapes alone: heads must fill whole
    128-lane groups (Dh divides 128 or is a multiple of it, the local
    H*Dh a multiple of the width), and the sequence one superblock in
    each direction with no host tiling — every training shape the
    kernels are measured at; the long-T tilings were found for, and
    stay with, the per-head form."""
    w = max(128, d)
    if (128 % d and d % 128) or (h * d) % w:
        return 0
    if _q_superblock(tq) != tq or tq > _BWD_Q_CHUNK or sk > _BWD_K_CHUNK \
            or _k_superblock(sk, _inner_block(sk, 256)) != sk:
        return 0
    if sk % min(BLOCK_Q, sk):       # the backward's jnp fallback
        return 0
    return w


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = False,
                    q_offset=0, kv_offset=0,
                    scale: Optional[float] = None) -> Array:
    """[B, T, H, D] attention via the Pallas kernel. Same contract as
    attention.dot_product_attention (which dispatches here). k and v
    may hold fewer heads than q (grouped-query attention): KV head j
    serves query heads [j * H / Hkv, (j + 1) * H / Hkv), one head an
    entry, the K/V block shared through its block index."""
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
    w = _lane_dense_width(h, d, tq, k.shape[1]) if group == 1 else 0
    layout = "lane_dense" if w else "per_head"
    # trace time: once a compiled program, not once a step
    mark("flash_attention.layout", layout=layout,
         heads_per_block=max(1, w // d))
    default_registry().counter(
        "flash_attention_calls",
        "flash_attention traces by operand layout (lane_dense: the "
        "block's own [B, T, H*Dh]; per_head: [B*H, T, Dh])",
        labelnames=("layout",)).labels(layout).inc()

    if w:       # [B, T, H, D] -> [B, T, H*D]: a bitcast
        def to3(x):
            return x.reshape(b, x.shape[1], h * d)
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
