"""Pallas kernels for Mamba-2's state-space-dual (SSD) scan, chunked.

A head's state ``S`` is ``[P, N]`` (head size by state size) and a position
does

    S = a_t S + d_t x_t B_t^T;        y_t = S C_t

(``a_t = exp(la_t)`` in (0, 1] the decay, ``d_t > 0`` the step, ``x_t`` the
head's ``P`` channels, ``B_t`` and ``C_t`` the ``N``-wide input and output
maps that every head of a group shares). Position by position that is T
dependent rank-1 updates; the kernels take ``Q`` positions (a chunk) at a
time. With ``A_i = sum_{k <= i} la_k`` inside a chunk and ``S0`` the state
before it,

    M[i, j] = (C_i . B_j) exp(A_i - A_j) d_j              j <= i
    Y  = M X + exp(A) (C S0^T)
    S1 = exp(A_Q) S0 + (w X)^T B,     w_j = exp(A_Q - A_j) d_j

all matrix products. ``C B^T`` is one ``[Q, Q]`` panel a chunk for every
head of the group; a head's own work is the decay panel (VPU) and three
products. The chunk is tiling, not mathematics.

Every product's result is float32; the decays and the state are never
rounded. As in ops/gated_delta.py (`_dot`), what a product costs follows
from its operands: x, B, C and the output's cotangent go to the MXU in the
dtype they arrive in, a computed float32 panel beside one of them takes the
three passes of its exact split, two computed panels the six of `HIGHEST`.

Operands keep the block's own layout: ``x`` is ``[B, T, H * P]`` with a
head's ``P`` lanes side by side, B and C ``[B, T, G * N]``. A program is one
(batch row, block of `HEADS` heads, block of chunks), the blocks of a
sequence in order with the state carried in a VMEM scratch as ``S^T``,
``[N, heads * P]``; the chunks of a block are a `fori_loop` (unrolled, the
two kernels were 64 head bodies each and took a minute to compile and
fifty seconds to load from the cache, for 0.06% of a step: PERF.md, PR 34),
a chunk's lane groups and heads a Python loop. Heads narrower than 128 lanes are worked a lane group at
a time (two heads of 64): a head is told apart by lane mask, never by lane
slice, so every panel and product is 128 lanes wide (ops/flash_attention.py
has the measurement). The decay arrives summed inside each chunk, and the
step beside it, as ``[B, H, T / Q, Q]`` float32 with positions on the lanes.

`ssd_fwd` writes ``y`` and each chunk's ``S0^T`` (what the backward starts
from). `ssd_bwd` walks blocks and chunks in reverse with ``dS^T`` carried
the same way; a chunk's gradients are written out (`_group_bwd`): the decay
panels come from the one function the forward uses (`_decays`), and the
gates' gradients are row and column sums of panels the other gradients
need. B and C get one partial gradient a block of heads, summed outside.
Off the chip the same two chunk functions run under `lax.scan`
(`_scan_fwd`, `_scan_bwd`): no interpreter, the same sums.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.gated_delta import _NN, _NT, _TN, _dot

Array = jax.Array
F32 = jnp.float32
NEG = -1e30
CHUNK = 128         # positions a chunk: one MXU contraction deep
BLOCK = 1024        # positions a program: eight chunks, a sublane tile of gates
HEADS = 8           # heads a program (512 lanes at head size 64)


def _lane_group(heads: int, p: int) -> int:
    """Heads worked together in one panel: as many as fill 128 lanes."""
    g = max(1, 128 // p)
    while heads % g:
        g -= 1
    return g


class _Decays(NamedTuple):
    """One head's panels of one chunk that both passes need."""
    gcol: Array     # [Q, 1]: A_i
    lmat: Array     # [Q, Q]: exp(A_i - A_j) on and under the diagonal
    glast: Array    # [1, 1]: A_Q
    wrow: Array     # [1, Q]: exp(A_Q - A_j) d_j
    wcol: Array     # [Q, 1]


def _masks(q: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return row == col, row >= col, col[:1] == q - 1


def _column(eye, r):        # [1, Q] -> [Q, 1] by the diagonal of a panel
    return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)


def _decays(gam, d, eye, live, last) -> _Decays:
    """gam (the log decay summed from the chunk's start) and d [1, Q]."""
    gcol = _column(eye, gam)
    lmat = jnp.exp(jnp.where(live, gcol - gam, NEG))
    glast = jnp.sum(jnp.where(last, gam, 0.0), axis=1, keepdims=True)
    wrow = jnp.exp(glast - gam) * d
    return _Decays(gcol, lmat, glast, wrow, _column(eye, wrow))


def _head_of_lane(wg: int, p: int):
    return jax.lax.broadcasted_iota(jnp.int32, (1, wg), 1) // p


def _group_fwd(x, bm, cm, g, gam, d, st, p: int):
    """One chunk of one lane group. x [Q, wg] (wg / p heads side by side),
    bm, cm [Q, N], g = cm bm^T [Q, Q] float32, gam[s] and d[s] the [1, Q]
    rows of the group's head s, st = S0^T [N, wg] float32. Returns
    (y [Q, wg] float32, S1^T)."""
    q, wg = x.shape
    eye, live, last = _masks(q)
    lane = _head_of_lane(wg, p)
    y = e_l = w_l = jnp.zeros((q, wg), F32)
    eq_l = jnp.zeros((1, wg), F32)
    for s in range(wg // p):
        k = _decays(gam[s], d[s], eye, live, last)
        mine = lane == s
        y = jnp.where(mine, _dot(g * k.lmat * d[s], x, _NN), y)
        e_l = jnp.where(mine, jnp.exp(k.gcol), e_l)
        w_l = jnp.where(mine, k.wcol, w_l)
        eq_l = jnp.where(mine, jnp.exp(k.glast), eq_l)
    y = y + e_l * _dot(cm, st, _NN)
    return y, eq_l * st + _dot(bm, w_l * x.astype(F32), _TN)


def _group_bwd(x, bm, cm, g, gam, d, st, dy, dst1, p: int):
    """Gradients of `_group_fwd`, written out: (dx [Q, wg], dG [Q, Q]
    summed over the group's heads, what B and C get beside dG's part
    [Q, N] each, dgam and dd as lists of [1, Q] rows, dS0^T)."""
    q, wg = x.shape
    eye, live, last = _masks(q)
    lane = _head_of_lane(wg, p)
    xf, dyf = x.astype(F32), dy.astype(F32)
    bds = _dot(bm, dst1, _NN)               # [Q, wg]: B dS1^T
    cs = _dot(cm, st, _NN)                  # [Q, wg]: C S0^T
    dycs, xbds, dss = dyf * cs, xf * bds, dst1 * st

    def rowsum(a):
        return jnp.sum(a, axis=1, keepdims=True)

    def colsum(a):
        return jnp.sum(a, axis=0, keepdims=True)

    def to_row(c):          # [Q, 1] -> [1, Q], `_column` backwards
        return colsum(jnp.where(eye, c, 0.0))

    dx = e_l = w_l = jnp.zeros((q, wg), F32)
    eq_l = jnp.zeros((1, wg), F32)
    dg = jnp.zeros((q, q), F32)
    dgam, dd = [], []
    for s in range(wg // p):
        k = _decays(gam[s], d[s], eye, live, last)
        mine = lane == s
        gl = g * k.lmat
        dm = _dot(jnp.where(mine, dy, jnp.zeros_like(dy)), x, _NT)
        dx = jnp.where(mine, _dot(gl * d[s], dy, _TN), dx)
        kp = dm * gl                        # d M, without the step
        kk = kp * d[s]
        dg = dg + dm * k.lmat * d[s]
        ecol = jnp.exp(k.gcol)
        zrow = to_row(rowsum(jnp.where(mine, xbds, 0.0)))
        dglast = (rowsum(k.wrow * zrow) + jnp.exp(k.glast)
                  * colsum(rowsum(jnp.where(mine, dss, 0.0))))
        dgam.append(
            to_row(rowsum(kk) + ecol * rowsum(jnp.where(mine, dycs, 0.0)))
            - colsum(kk) - k.wrow * zrow + jnp.where(last, dglast, 0.0))
        dd.append(colsum(kp) + jnp.exp(k.glast - gam[s]) * zrow)
        e_l = jnp.where(mine, ecol, e_l)
        w_l = jnp.where(mine, k.wcol, w_l)
        eq_l = jnp.where(mine, jnp.exp(k.glast), eq_l)
    edy = e_l * dyf
    return (dx + w_l * bds, dg, _dot(w_l * xf, dst1, _NT),
            _dot(edy, st, _NT), dgam, dd,
            eq_l * dst1 + _dot(cm, edy, _TN))


def _bc_grads(dg, bm, cm, db, dc):
    """B's and C's gradients of one chunk: dG's part and the rest."""
    return db + _dot(dg, cm, _TN), dc + _dot(dg, bm, _NN)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, c_ref, g_ref, d_ref, y_ref, st_ref, s_scr, *,
                chunk: int, p: int, wg: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    hpg = wg // p

    def body(c, _):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        bm, cm = b_ref[0, rows, :], c_ref[0, rows, :]
        g = _dot(cm, bm, _NT)
        for gi in range(x_ref.shape[2] // wg):
            lanes = pl.ds(gi * wg, wg)
            heads = range(gi * hpg, (gi + 1) * hpg)
            st = s_scr[:, lanes]
            st_ref[0, c, :, lanes] = st
            y, st1 = _group_fwd(
                x_ref[0, rows, lanes], bm, cm, g,
                [g_ref[0, h, pl.ds(c, 1), :] for h in heads],
                [d_ref[0, h, pl.ds(c, 1), :] for h in heads], st, p)
            y_ref[0, rows, lanes] = y.astype(y_ref.dtype)
            s_scr[:, lanes] = st1
        return ()

    jax.lax.fori_loop(0, g_ref.shape[2], body, ())


def _bwd_kernel(x_ref, b_ref, c_ref, g_ref, d_ref, st_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dg_ref, dd_ref, ds_scr, *,
                chunk: int, p: int, wg: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    hpg = wg // p
    nc = g_ref.shape[2]

    def body(i, _):
        c = nc - 1 - i
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        bm, cm = b_ref[0, rows, :], c_ref[0, rows, :]
        g = _dot(cm, bm, _NT)
        dg = jnp.zeros_like(g)
        db = dc = jnp.zeros(bm.shape, F32)
        for gi in range(x_ref.shape[2] // wg):
            lanes = pl.ds(gi * wg, wg)
            heads = range(gi * hpg, (gi + 1) * hpg)
            dx, dg_i, db_i, dc_i, dgam, dd, dst = _group_bwd(
                x_ref[0, rows, lanes], bm, cm, g,
                [g_ref[0, h, pl.ds(c, 1), :] for h in heads],
                [d_ref[0, h, pl.ds(c, 1), :] for h in heads],
                st_ref[0, c, :, lanes], dy_ref[0, rows, lanes],
                ds_scr[:, lanes], p)
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
            for h, a, b in zip(heads, dgam, dd):
                dg_ref[0, h, pl.ds(c, 1), :] = a
                dd_ref[0, h, pl.ds(c, 1), :] = b
            ds_scr[:, lanes] = dst
            dg, db, dc = dg + dg_i, db + db_i, dc + dc_i
        db, dc = _bc_grads(dg, bm, cm, db, dc)
        db_ref[0, 0, rows, :] = db
        dc_ref[0, 0, rows, :] = dc
        return ()

    jax.lax.fori_loop(0, nc, body, ())


class _Tiling(NamedTuple):
    heads: int      # H
    p: int          # head size
    n: int          # state size
    groups: int     # G
    chunk: int
    tb: int         # positions a program
    hb: int         # heads a program
    interpret: bool

    @property
    def hpg(self) -> int:           # heads a lane group
        return _lane_group(self.hb, self.p)

    @property
    def wg(self) -> int:            # lanes a lane group
        return self.hpg * self.p

    @property
    def lg(self) -> int:            # lane groups of all the heads
        return self.heads // self.hpg


def _specs(tl: _Tiling, tp: int, reverse: bool):
    import jax.experimental.pallas as pl
    nt = tp // tl.tb
    per_group = tl.heads // tl.groups // tl.hb      # head blocks a group

    def tt(i):
        return nt - 1 - i if reverse else i

    nc = tl.tb // tl.chunk
    xs = pl.BlockSpec((1, tl.tb, tl.hb * tl.p), lambda n, h, i: (n, tt(i), h))
    bc = pl.BlockSpec((1, tl.tb, tl.n),
                      lambda n, h, i: (n, tt(i), h // per_group))
    gate = pl.BlockSpec((1, tl.hb, nc, tl.chunk),
                        lambda n, h, i: (n, h, tt(i), 0))
    state = pl.BlockSpec((1, nc, tl.n, tl.hb * tl.p),
                         lambda n, h, i: (n, tt(i), 0, h))
    part = pl.BlockSpec((1, 1, tl.tb, tl.n), lambda n, h, i: (n, h, tt(i), 0))
    return xs, bc, gate, state, part


def _pallas(kernel, name, tl: _Tiling, ops, in_specs, out_shape, out_specs):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import interpret_arg
    b, tp, _ = ops[0].shape
    return pl.pallas_call(
        functools.partial(kernel, chunk=tl.chunk, p=tl.p, wg=tl.wg),
        out_shape=out_shape,
        grid=(b, tl.heads // tl.hb, tp // tl.tb),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tl.n, tl.hb * tl.p), F32)],
        # the backward's blocks (x, dy, dx, eight chunks' states), double
        # buffered, with its panels pass the 16 MB default of scoped VMEM
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret_arg(tl.interpret, *ops),
        name=name,
    )(*ops)


def _state_shape(x, tl: _Tiling):
    b, tp, lanes = x.shape
    return (b, tp // tl.chunk, tl.n, lanes)


def _kernel_fwd(x, bm, cm, gam, d, tl: _Tiling):
    from deeplearning4j_tpu.ops.pallas_util import out_struct
    ops = (x, bm, cm, gam, d)
    xs, bc, gate, state, _ = _specs(tl, x.shape[1], False)
    return _pallas(
        _fwd_kernel, "ssd_fwd", tl, ops, [xs, bc, bc, gate, gate],
        [out_struct(x.shape, x.dtype, *ops),
         out_struct(_state_shape(x, tl), F32, *ops)], [xs, state])


def _kernel_bwd(x, bm, cm, gam, d, states, dy, tl: _Tiling):
    from deeplearning4j_tpu.ops.pallas_util import out_struct
    ops = (x, bm, cm, gam, d, states, dy)
    xs, bc, gate, state, part = _specs(tl, x.shape[1], True)
    b, tp, _ = x.shape
    parts = out_struct((b, tl.heads // tl.hb, tp, tl.n), F32, *ops)
    gates = out_struct(gam.shape, F32, *ops)
    return _pallas(
        _bwd_kernel, "ssd_bwd", tl, ops,
        [xs, bc, bc, gate, gate, state, xs],
        [out_struct(x.shape, x.dtype, *ops), parts, parts, gates, gates],
        [xs, part, part, gate, gate])


# ---------------------------------------------------------------------------
# the same chunks under lax.scan, off the chip
# ---------------------------------------------------------------------------

def _by_chunk(x, bm, cm, gam, d, tl: _Tiling):
    """Operands a chunk first, a lane group an entry: x [nc, B, LG, Q, wg],
    bm and cm [nc, B, LG, Q, N] (each lane group its own group's), the
    gates [nc, B, LG, hpg, 1, Q]."""
    b, tp, _ = x.shape
    nc, q, hpg, lg = tp // tl.chunk, tl.chunk, tl.hpg, tl.lg
    xc = jnp.moveaxis(x.reshape(b, nc, q, lg, tl.wg), (1, 3), (0, 2))

    def maps(m):
        m = m.reshape(b, nc, q, tl.groups, tl.n)
        m = jnp.repeat(m, lg // tl.groups, axis=3)
        return jnp.moveaxis(m, (1, 3), (0, 2))

    def gates(a):
        return jnp.moveaxis(a, 2, 0).reshape(nc, b, lg, hpg, 1, q)

    return xc, maps(bm), maps(cm), gates(gam), gates(d)


def _g(cm, bm):
    return _dot(cm, bm, _NT)


def _scan_fwd(x, bm, cm, gam, d, tl: _Tiling):
    b, tp, lanes = x.shape
    lg, wg = tl.lg, tl.wg
    group = jax.vmap(jax.vmap(
        lambda x_, b_, c_, g_, d_, s_: _group_fwd(
            x_, b_, c_, _g(c_, b_), g_, d_, s_, tl.p)))

    def step(st, ops):
        y, st1 = group(*ops, st)
        return st1, (y, st)

    _, (y, states) = jax.lax.scan(
        step, jnp.zeros((b, lg, tl.n, wg), F32),
        _by_chunk(x, bm, cm, gam, d, tl))
    y = jnp.moveaxis(y, (0, 2), (1, 3)).reshape(b, tp, lanes)
    states = jnp.moveaxis(states, (0, 2), (1, 3))   # [B, nc, N, LG, wg]
    return y.astype(x.dtype), states.reshape(_state_shape(x, tl))


def _scan_bwd(x, bm, cm, gam, d, states, dy, tl: _Tiling):
    b, tp, lanes = x.shape
    nc, q, lg, wg = tp // tl.chunk, tl.chunk, tl.lg, tl.wg

    def one(x_, b_, c_, g_, d_, s_, dy_, ds_):
        dx, dg, db, dc, dgam, dd, dst = _group_bwd(
            x_, b_, c_, _g(c_, b_), g_, d_, s_, dy_, ds_, tl.p)
        db, dc = _bc_grads(dg, b_, c_, db, dc)
        return dx, db, dc, jnp.stack(dgam), jnp.stack(dd), dst

    group = jax.vmap(jax.vmap(one))
    st = jnp.moveaxis(states.reshape(b, nc, tl.n, lg, wg), (1, 3), (0, 2))
    dyc = jnp.moveaxis(dy.reshape(b, nc, q, lg, wg), (1, 3), (0, 2))

    def step(ds, ops):
        *out, ds0 = group(*ops, ds)
        return ds0, tuple(out)

    _, (dx, db, dc, dgam, dd) = jax.lax.scan(
        step, jnp.zeros((b, lg, tl.n, wg), F32),
        _by_chunk(x, bm, cm, gam, d, tl) + (st, dyc), reverse=True)
    dx = jnp.moveaxis(dx, (0, 2), (1, 3)).reshape(b, tp, lanes)

    def maps(m):        # [nc, B, LG, Q, N] -> [B, LG, T, N]
        return jnp.moveaxis(m, 0, 2).reshape(b, lg, tp, tl.n)

    def gates(a):       # [nc, B, LG, hpg, 1, Q] -> [B, H, nc, Q]
        return jnp.moveaxis(a.reshape(nc, b, tl.heads, q), 0, 2)

    return dx.astype(x.dtype), maps(db), maps(dc), gates(dgam), gates(dd)


# ---------------------------------------------------------------------------
# the differentiable call
# ---------------------------------------------------------------------------

def _count(which: str) -> None:
    from deeplearning4j_tpu.observability.metrics import default_registry
    default_registry().counter(
        "ssd_calls", "state-space-dual scan traces by pass",
        labelnames=("pass",)).labels(which).inc()


def _run_fwd(x, bm, cm, gam, d, tl: _Tiling):
    run = _scan_fwd if tl.interpret is None else _kernel_fwd
    return run(x, bm, cm, gam, d, tl)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _core(x, bm, cm, gam, d, tl: _Tiling):
    return _run_fwd(x, bm, cm, gam, d, tl)[0]


def _core_fwd(x, bm, cm, gam, d, tl: _Tiling):
    _count("forward")
    y, states = _run_fwd(x, bm, cm, gam, d, tl)
    return y, (x, bm, cm, gam, d, states)


def _core_bwd(tl: _Tiling, res, dy):
    x, bm, cm, gam, d, states = res
    _count("backward")
    run = _scan_bwd if tl.interpret is None else _kernel_bwd
    dx, db, dc, dgam, dd = run(x, bm, cm, gam, d, states, dy, tl)
    b, tp, _ = x.shape

    def over_heads(part):   # a group's blocks of heads add up
        part = part.reshape(b, tl.groups, -1, tp, tl.n)
        return jnp.moveaxis(jnp.sum(part, axis=2), 1, 2).reshape(
            b, tp, tl.groups * tl.n).astype(bm.dtype)

    return dx, over_heads(db), over_heads(dc), dgam, dd


_core.defvjp(_core_fwd, _core_bwd)


def ssd_scan(x: Array, bm: Array, cm: Array, d: Array, la: Array, *,
             chunk: int = CHUNK, kernel=None) -> Array:
    """y [B, T, H, P] of x [B, T, H, P], bm and cm [B, T, G, N], the step d
    and the log decay la (<= 0) [B, T, H] float32; the state starts at
    nought. T need not be whole chunks: the tail is padded with positions
    that leave the state as it is. `kernel`: None takes the Pallas kernels
    on a TPU and the `lax.scan` form of the same chunks elsewhere; True
    forces the kernels (through the interpreter off the chip)."""
    from deeplearning4j_tpu.observability.tracing import mark
    from deeplearning4j_tpu.ops.pallas_util import off_chip

    b, t, h, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    if h % groups:
        raise ValueError(f"heads {h} not a multiple of groups {groups}")
    hb = min(HEADS, h // groups)
    while (h // groups) % hb:
        hb -= 1
    tb = min(BLOCK // CHUNK * chunk, -(-t // chunk) * chunk)
    tp = -(-t // tb) * tb
    interpret = off_chip() if (kernel or not off_chip()) else None
    tl = _Tiling(h, p, n, groups, chunk, tb, hb, interpret)
    if interpret is False and (tl.wg % 128 or n % 128):
        raise ValueError(
            f"ssd_scan: on the chip a lane group of heads and the state must "
            f"be multiples of 128 lanes, got {tl.hpg} x {p} and {n}")
    operands = jnp.result_type(x, bm, cm).name
    mark("ssd.layout", chunk=chunk, heads=h, head_dim=p, state=n,
         groups=groups, block=tb, operands=operands)

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, tp - t)) + ((0, 0),) * (a.ndim - 2))

    def lanes(a):           # [B, T, H] -> [B, H, T / Q, Q]
        return jnp.moveaxis(pad(a.astype(F32)), 1, 2).reshape(
            b, h, tp // chunk, chunk)

    y = _core(pad(x).reshape(b, tp, h * p), pad(bm).reshape(b, tp, groups * n),
              pad(cm).reshape(b, tp, groups * n),
              jnp.cumsum(lanes(la), axis=-1), lanes(d), tl)
    return y[:, :t].reshape(b, t, h, p)
