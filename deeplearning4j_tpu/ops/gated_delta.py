"""Pallas kernels for the gated delta rule (Gated DeltaNet), chunked.

A head's state ``S`` is ``[dk, dv]`` and a position does

    S = exp(g_t) S;  u = (v_t - S^T k_t) beta_t;  S = S + k_t u^T;
    o_t = S^T q_t

(``g_t <= 0`` the log decay, ``beta_t`` in (0, 1)). Position by position
that is T dependent rank-1 updates; the kernels take ``C`` positions (a
chunk, 64) at a time through the WY form of the update. With
``G_t = exp(sum_{s <= t} g_s)`` inside a chunk and ``S0`` the state before
it,

    A[t, s] = beta_t (G_t / G_s) (k_t . k_s)            s < t
    U = (I + A)^-1 (beta (v  -  G (k S0)))               [C, dv]
    O = G (q S0) + (tril(G_t / G_s) * q k^T) U
    S1 = G_C S0 + k^T ((G_C / G) U)

all matrix products. ``A`` is strictly lower triangular, so
``(I + A)^-1 = prod_i (I + (-A)^(2^i))`` over ``log2 C`` factors: squarings
and products of ``[C, C]`` panels, no substitution loop. Polynomials in
``A`` commute, so a round's two products are one on 128 lanes
(`_panels`).

Every product's result is float32 and no operand that the kernels compute
is ever rounded: the inverse amplifies rounding. What a product costs on
the MXU follows from where its operands came from (`_dot`): q, k, v and
the output's cotangent are taken in the dtype they arrive in, and a
bfloat16 value fills one of the three bfloat16 pieces a float32 holds, so
a product of two of them is one pass, of one with a computed float32
panel three (over the panel's exact split), and only two computed panels
need the six of `Precision.HIGHEST`. Handed float32 operands, every
product takes the passes of two computed panels. Diagonal scalings
therefore sit on the float32 side of a product (``G (q S0)``, not
``(G q) S0``). A pass contracts 128 rows, and the inverse's rounds, which
contract a chunk's 64 positions, stack two pieces of a split along the
contraction: three passes for the six (`_dot64`).

What the time follows is how many products wait for one another, and the
scheduler overlaps only what is written close together. So a chain of
dependent products is a generator that yields between its products, and
the kernels trace several chains a step each in turn (`_lockstep`). Only
``k S0 -> U -> S1`` must wait for the chunk before (backward:
``k dS1 -> dU -> dr -> dS0``), three products; a chunk's panels up to the
inverse (`_panels`, six rounds) need no state. A program is one (batch
row, pair of value heads of one key head, block of chunks): ``[k; q]
k^T`` is formed once for the pair, and under the state chains of chunk
``c``'s two heads run the last rounds of chunk ``c + 1``'s two inverses
and the first of chunk ``c + 2``'s (`_sweep`). Where a key head's value
heads do not pair up (``Hv / Hk`` odd), a program is one head.

`gdn_fwd` takes the blocks of a program's heads in order with ``S``
carried in a VMEM scratch from block to block; it writes ``o`` and each
chunk's ``S0`` (what the backward starts from). `gdn_bwd` walks the
blocks and the chunks of a block in reverse with ``dS`` carried the same
way, and sums ``dq`` and ``dk`` over a program's heads. A chunk's
gradients are written out (`_bwd_rest`): the panels come from the one
function the forward uses (`_panels`), so the two cannot drift apart; the
inverse's gradient is one product, ``dA = -(M^T dU) U^T`` with
``M = (I + A)^-1``, not the squarings' transposes; the gates' gradients
are row and column sums of panels the other gradients need. `_chunk` is
the plain form of a chunk, which the tests differentiate to check both.

Operands keep the block's own layout, ``[B, T, H * d]`` with a head's
``d`` lanes side by side (on the chip ``d`` must be a multiple of 128);
a program's value heads read their key head by block index, never a
repeated copy. The decay arrives summed inside each chunk and with beta
as ``[B, Hv, T / C, C]`` float32, positions on the lanes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
BF16 = jnp.bfloat16
NEG = -1e30
CHUNK = 64          # positions a chunk (the published kernel's)
BLOCK = 512         # positions a program: whole chunks
# the dependent products of a chunk's state chain, and half the rounds of
# its inverse (log2 CHUNK = 6): steps of `_lockstep` a chunk (`_sweep`)
_STEPS = 3
assert 4 ** _STEPS == CHUNK
_HI = jax.lax.Precision.HIGHEST


def _split3(x):
    """A float32 panel as the three bfloat16 pieces that sum to it."""
    hi = x.astype(BF16)
    rest = x - hi.astype(F32)
    mid = rest.astype(BF16)
    return hi, mid, (rest - mid.astype(F32)).astype(BF16)


def _dot(a, b, dims):
    """The float32 product of two panels, in as many MXU passes as the
    operands need. A bfloat16 operand is one the kernel was handed (q, k,
    v, do); nothing computed here is ever rounded to it. Two such: one
    pass. One: the three passes over the other's pieces. None: the six of
    `Precision.HIGHEST` -- which are these three and three more that a
    bfloat16 value leaves empty, so every form gives the same sums."""
    def dot(x, y, precision=None):
        return jax.lax.dot_general(x, y, (dims, ((), ())),
                                   precision=precision,
                                   preferred_element_type=F32)

    if a.dtype == BF16 and b.dtype == BF16:
        return dot(a, b)
    if a.dtype == BF16:
        hi, mid, lo = _split3(b)
        return dot(a, hi) + dot(a, mid) + dot(a, lo)
    if b.dtype == BF16:
        hi, mid, lo = _split3(a)
        return dot(hi, b) + dot(mid, b) + dot(lo, b)
    return dot(a, b, _HI)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot64(a, b):
    """``a b`` of two computed panels that contract a chunk's 64 rows, in
    three passes for `_dot`'s six: the MXU contracts 128 rows a pass, so
    two pieces of the exact split ride side by side along the contraction
    and one pass sums two of the six products of pieces. The same
    products of the same pieces as `Precision.HIGHEST`'s, every sum
    float32. For the inverse's rounds (`_panels`), the one place where
    the passes saved show in a step's time (PERF.md section 6, PR 35)."""
    def dot(xs, ys):
        return jax.lax.dot_general(
            jnp.concatenate(xs, axis=1), jnp.concatenate(ys, axis=0),
            (_NN, ((), ())), preferred_element_type=F32)

    (a1, a2, a3), (b1, b2, b3) = _split3(a), _split3(b)
    return (dot([a1, a3], [b3, b1]) + dot([a1, a2], [b2, b1])
            + dot([a1, a2], [b1, b2]))


def _chunk(q, k, v, gam, beta, s0):
    """One chunk, plainly: what the tests differentiate (`jax.vjp`) to
    check `_fwd_rest` and `_bwd_rest`. q, k [C, dk], v [C, dv], s0
    [dk, dv] float32; gam (the decay summed from the chunk's start) and
    beta [1, C], positions on the lanes. Returns (o [C, dv], s1)."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = row == col

    def column(r):          # [1, C] -> [C, 1] by the diagonal of a panel
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    gcol, bcol = column(gam), column(beta)
    decay = jnp.exp(jnp.where(row >= col, gcol - gam, NEG))   # G_t / G_s
    a = jnp.where(row > col, bcol * decay * _dot(k, k, _NT), 0.0)
    # (I + A)^-1 = prod (I + (-A)^(2^i))
    p = -a
    inv = jnp.where(eye, 1.0, 0.0) + p
    n = 2
    while n < c:
        p = _dot(p, p, _NN)
        inv = inv + _dot(inv, p, _NN)
        n *= 2
    gexp = jnp.exp(gcol)
    w = _dot(inv, k * (bcol * gexp), _NN)                     # [C, dk]
    u = _dot(inv, v * bcol, _NN) - _dot(w, s0, _NN)
    o = _dot(q * gexp, s0, _NN) + _dot(decay * _dot(q, k, _NT), u, _NN)
    glast = jnp.sum(jnp.where(col[:1] == c - 1, gam, 0.0), axis=1,
                    keepdims=True)      # [1, 1]: the chunk's whole decay
    s1 = jnp.exp(glast) * s0 + _dot(k * jnp.exp(glast - gcol), u, _TN)
    return o, s1


class _Panels(NamedTuple):
    """A head's panels of one chunk that need no state (`_panels`)."""
    eye: Array      # [C, C] bool
    lower: Array    # [C, C] bool: strictly under the diagonal
    last: Array     # [1, C] bool: the chunk's last position
    decay: Array    # [C, C]: G_t / G_s on and under the diagonal
    ak: Array       # [C, C]: A without beta, strictly lower
    inv: Array      # [C, C]: (I + A)^-1
    bcol: Array     # [C, 1]: beta
    gexp: Array     # [C, 1]: G_t
    p: Array        # [C, C]: decay * q k^T
    glast: Array    # [1, 1]: the chunk's whole decay, G_C
    dlast: Array    # [C, 1]: G_C / G_t


def _lockstep(gens, steps=None):
    """Runs generators a step each in turn, `steps` steps at most, and
    returns what they return (None for one that has not yet). What one
    traces between two `yield`s lands in the program beside its
    neighbours' step, and that is where the scheduler looks for work that
    need not wait: products written one chain after the other run one
    chain after the other."""
    out = [None] * len(gens)
    live = list(enumerate(gens))
    while live and steps != 0:
        still = []
        for i, g in live:
            try:
                next(g)
                still.append((i, g))
            except StopIteration as done:
                out[i] = done.value
        live = still
        if steps is not None:
            steps -= 1
    return out


def _panels(kkqk, gam, beta):
    """Generator: a head's state-free panels of one chunk, from the
    chunk's [k; q] k^T (the heads of a key head share it) and the head's
    gam and beta [1, C] float32; a round of the inverse a step. With
    p = -A a panel rides as X = [p^n | sum_{j < n} p^j], and since
    polynomials in p commute a round is the one product
    X[:, :C] X = [p^(2n) | p^n sum] on 128 lanes."""
    c = kkqk.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye, lower, last = row == col, row > col, col[:1] == c - 1

    def column(r):          # [1, C] -> [C, 1] by the diagonal of a panel
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    gcol, bcol = column(gam), column(beta)
    decay = jnp.exp(jnp.where(row >= col, gcol - gam, NEG))   # G_t / G_s
    ak = jnp.where(lower, decay * kkqk[:c], 0.0)
    # (I + A)^-1 = prod (I + (-A)^(2^i))
    x = jnp.concatenate([-(bcol * ak), jnp.where(eye, 1.0, 0.0)], axis=1)
    n = 1
    while n < c:
        if n > 1:
            yield
        # p^n has nothing in its first n rows: whole sublane tiles of
        # them stay out of the product
        m = n // 8 * 8
        right = jax.lax.broadcasted_iota(jnp.int32, (c - m, 2 * c), 1) >= c
        y = _dot64(x[m:, :c], x) + jnp.where(right, x[m:], 0.0)
        x = jnp.concatenate([x[:m], y], axis=0) if m else y
        n *= 2
    glast = jnp.sum(jnp.where(last, gam, 0.0), axis=1, keepdims=True)
    return _Panels(eye, lower, last, decay, ak, x[:, c:], bcol,
                   jnp.exp(gcol), decay * kkqk[c:], jnp.exp(glast),
                   jnp.exp(glast - gcol))


def _fwd_rest(w: _Panels, kq, v, s0):
    """Generator: (o [C, dv], s1) of a chunk from its panels and the state
    before it, a product of the state's chain a step; each diagonal
    scaling on the float32 side of its product. `u` is formed without
    `w`: (I + A)^-1 (beta (v - G (k S0)))."""
    c = v.shape[0]
    kqs = _dot(kq, s0, _NN)             # k S0 over q S0: one product
    yield
    u = _dot(w.inv, w.bcol * (v.astype(F32) - w.gexp * kqs[:c]), _NN)
    yield
    s1 = w.glast * s0 + _dot(kq[:c], w.dlast * u, _TN)
    return w.gexp * kqs[c:] + _dot(w.p, u, _NN), s1


class _Grads(NamedTuple):
    """A head's gradients of one chunk (`_bwd_rest`). The heads of a key
    head share k and q, so what multiplies them adds up first
    (`_dq_dk`)."""
    dqk: Array      # [C, C]: times k, into dq
    dq: Array       # [C, dk]: the rest of dq
    dkq: Array      # [C, 2C]: times [k; q], into dk
    dk: Array       # [C, dk]: the rest of dk
    dv: Array       # [C, dv]
    dgam: Array     # [1, C]
    dbeta: Array    # [1, C]
    ds0: Array      # [dk, dv]


def _bwd_rest(w: _Panels, kq, v, s0, do, ds1):
    """Generator: a chunk's gradients from its panels, the state before
    it and the cotangents of o and of the state after it, a product of
    the chain from ds1 to ds0 a step. With M = (I + A)^-1, u = M r and
    dr = M^T du, the inverse's gradient is dA = -dr u^T under the
    strict-lower mask: one product, not the squarings' transposes. `o` is
    not formed again. The gates' gradients are row and column sums of
    panels the products' gradients need. Two products of 64 contracted
    rows that add up are one of 128."""
    c = v.shape[0]
    k, q = kq[:c], kq[c:]

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def colsum(x):
        return jnp.sum(x, axis=0, keepdims=True)

    def to_row(x):          # [C, 1] -> [1, C], `column` backwards
        return colsum(jnp.where(w.eye, x, 0.0))

    # o = G (q S0) + P u;  s1 = G_C S0 + k^T ((G_C / G) u)
    kds = _dot(k, ds1, _NN)                                 # [C, dv]
    ks = _dot(k, s0, _NN)
    du = _dot(w.p, do, _TN) + w.dlast * kds
    # u = M (beta vk);  vk = v - G (k S0)
    vk = v.astype(F32) - w.gexp * ks
    u = _dot(w.inv, w.bcol * vk, _NN)
    yield
    dr = _dot(w.inv, du, _TN)
    dos = _dot(do, s0, _NT)                                 # [C, dk]
    dp = _dot(do, u, _NT)                                   # [C, C]
    yield
    e = -(w.bcol * w.gexp) * dr         # d(k S0)
    ds0 = w.glast * ds1 + _dot(
        kq, jnp.concatenate([e, w.gexp * do.astype(F32)], axis=0), _TN)
    da = -_dot(dr, u, _NT)
    dk = _dot(w.dlast * u, ds1, _NT) + _dot(e, s0, _NT)
    # A = beta ak, ak and P = decay * (k k^T, q k^T)
    dkk = jnp.where(w.lower, w.bcol * w.decay * da, 0.0)
    dqk = dp * w.decay
    # the decay: d(G_t / G_s) G_t / G_s, summed along t and along s
    x = da * w.ak
    dd = w.bcol * x + dp * w.p
    ddl = rowsum(kds * u) * w.dlast
    dgexp = rowsum(dos * q.astype(F32)) - w.bcol * rowsum(dr * ks)
    dglast = colsum(ddl) + w.glast * colsum(rowsum(ds1 * s0))
    dgam = (to_row(rowsum(dd) + dgexp * w.gexp - ddl) - colsum(dd)
            + jnp.where(w.last, dglast, 0.0))
    dbeta = to_row(rowsum(x) + rowsum(dr * vk))
    return _Grads(dqk, w.gexp * dos,
                  jnp.concatenate([dkk + dkk.T, dqk.T], axis=1), dk,
                  w.bcol * dr, dgam, dbeta, ds0)


def _dq_dk(kq, grads):
    """dq and dk [C, dk], summed over the heads that share `kq`."""
    c = kq.shape[0] // 2
    dq = sum(g.dq for g in grads) + _dot(sum(g.dqk for g in grads), kq[:c],
                                         _NN)
    dk = sum(g.dk for g in grads) + _dot(sum(g.dkq for g in grads), kq, _NN)
    return dq, dk


def _sweep(order, refs, chunk, rest, write):
    """A block's chunks in `order`. Chunk c's state work (`rest(c, rows,
    kq, panels)`: a generator a head, done in `_STEPS` steps) is traced in
    lockstep with the last rounds of the next chunk's panels and the
    first of the one after it, so that every step holds products that do
    not wait for one another; `write(c, rows, kq, results)` stores what
    the generators return. A chunk is loaded as it came: bfloat16 q and k
    stay bfloat16, which is what `_dot` goes by."""
    q_ref, k_ref, g_ref, b_ref = refs
    heads = g_ref.shape[1]

    def load(c):    # its rows, [k; q] and the generators of its panels
        rows = slice(c * chunk, (c + 1) * chunk)
        k = k_ref[0, rows, :]
        kq = jnp.concatenate([k, q_ref[0, rows, :]], axis=0)
        kkqk = _dot(kq, k, _NT)         # k k^T over q k^T: one product
        return rows, kq, [_panels(kkqk, g_ref[0, j, c:c + 1, :],
                                  b_ref[0, j, c:c + 1, :])
                          for j in range(heads)]

    ahead = {}      # the chunks whose panels are under way, in order

    def start(i):
        if i < len(order):
            ahead[order[i]] = load(order[i])

    def panels():
        return [g for _, _, gens in ahead.values() for g in gens]

    start(0)
    _lockstep(panels(), _STEPS)
    start(1)
    ws = _lockstep(panels(), _STEPS)[:heads]
    for i, c in enumerate(order):
        rows, kq, _ = ahead.pop(c)
        gens = rest(c, rows, kq, ws)
        start(i + 2)
        out = _lockstep(gens + panels(), _STEPS)
        write(c, rows, kq, out[:heads])
        ws = out[heads:2 * heads]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, s_scr, *,
                chunk: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    heads, nc = g_ref.shape[1], g_ref.shape[2]
    dv = v_ref.shape[2] // heads
    s = [s_scr[j] for j in range(heads)]

    def rest(c, rows, kq, ws):
        for j in range(heads):
            st_ref[0, j, c] = s[j]
        return [_fwd_rest(ws[j], kq, v_ref[0, rows, j * dv:(j + 1) * dv],
                          s[j]) for j in range(heads)]

    def write(c, rows, kq, out):
        for j, (o, s1) in enumerate(out):
            o_ref[0, rows, j * dv:(j + 1) * dv] = o.astype(o_ref.dtype)
            s[j] = s1

    _sweep(range(nc), (q_ref, k_ref, g_ref, b_ref), chunk, rest, write)
    for j in range(heads):
        s_scr[j] = s[j]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *,
                chunk: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    heads, nc = g_ref.shape[1], g_ref.shape[2]
    dv = v_ref.shape[2] // heads
    ds = [ds_scr[j] for j in range(heads)]

    def rest(c, rows, kq, ws):
        return [_bwd_rest(ws[j], kq, v_ref[0, rows, j * dv:(j + 1) * dv],
                          st_ref[0, j, c],
                          do_ref[0, rows, j * dv:(j + 1) * dv], ds[j])
                for j in range(heads)]

    def write(c, rows, kq, grads):
        dq, dk = _dq_dk(kq, grads)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        for j, g in enumerate(grads):
            dv_ref[0, rows, j * dv:(j + 1) * dv] = g.dv.astype(dv_ref.dtype)
            dg_ref[0, j, c:c + 1, :] = g.dgam
            db_ref[0, j, c:c + 1, :] = g.dbeta
            ds[j] = g.ds0

    _sweep(range(nc)[::-1], (q_ref, k_ref, g_ref, b_ref), chunk, rest, write)
    for j in range(heads):
        ds_scr[j] = ds[j]


def _heads_per_program(hk: int, hv: int) -> int:
    """Two value heads of one key head a program where a key head's value
    heads pair up: `[k; q] k^T` is formed once for both and their chains
    run under one another; else one."""
    return 2 if (hv // hk) % 2 == 0 else 1


def _specs(tp, hk, hv, dk, dv, tb, chunk, reverse: bool):
    import jax.experimental.pallas as pl
    nt = tp // tb
    hp = _heads_per_program(hk, hv)
    r = hv // hk // hp          # programs a key head

    def tt(i):
        return nt - 1 - i if reverse else i

    qk = pl.BlockSpec((1, tb, dk), lambda n, h, i: (n, tt(i), h // r))
    qk_own = pl.BlockSpec((1, tb, dk), lambda n, h, i: (n, tt(i), h))
    vv = pl.BlockSpec((1, tb, hp * dv), lambda n, h, i: (n, tt(i), h))
    gate = pl.BlockSpec((1, hp, tb // chunk, chunk),
                        lambda n, h, i: (n, h, tt(i), 0))
    state = pl.BlockSpec((1, hp, tb // chunk, dk, dv),
                         lambda n, h, i: (n, h, tt(i), 0, 0))
    return hp, qk, qk_own, vv, gate, state


def _check_lanes(dk: int, dv: int, interpret) -> None:
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"gated_delta_rule: on the chip a head's key and value sizes "
            f"must be multiples of 128 lanes, got {dk} and {dv}")


# (under `jax.jit` a layer's kernels are traced once a step, not once a
# layer and again for its remat: the bodies are long to trace)
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    b, tp, _ = q.shape
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    _check_lanes(dk, dv, interpret)
    hp, qk, _, vv, gate, state = _specs(tp, hk, hv, dk, dv, tb, chunk, False)
    ops = (q, k, v, gam, beta)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        out_shape=[out_struct(v.shape, v.dtype, *ops),
                   out_struct((b, hv, tp // chunk, dk, dv), F32, *ops)],
        grid=(b, hv // hp, tp // tb),
        in_specs=[qk, qk, vv, gate, gate],
        out_specs=[vv, state],
        scratch_shapes=[pltpu.VMEM((hp, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_arg(interpret, *ops),
        name="gdn_fwd",
    )(*ops)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11))
def _backward(q, k, v, gam, beta, states, do, hk, hv, chunk, tb, interpret):
    """dq and dk come a program's heads already summed: `[B, T, (Hv / hp)
    * dk]`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    b, tp, _ = q.shape
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    hp, qk, qk_own, vv, gate, state = _specs(tp, hk, hv, dk, dv, tb, chunk,
                                             True)
    ops = (q, k, v, gam, beta, states, do)
    per_program = out_struct((b, tp, hv // hp * dk), q.dtype, *ops)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        out_shape=[per_program, per_program,
                   out_struct(v.shape, v.dtype, *ops),
                   out_struct(gam.shape, F32, *ops),
                   out_struct(gam.shape, F32, *ops)],
        grid=(b, hv // hp, tp // tb),
        in_specs=[qk, qk, vv, gate, gate, state, vv],
        out_specs=[qk_own, qk_own, vv, gate, gate],
        scratch_shapes=[pltpu.VMEM((hp, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_arg(interpret, *ops),
        name="gdn_bwd",
    )(*ops)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _core(q, k, v, gam, beta, hk, hv, chunk, tb, interpret):
    return _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret)[0]


def _core_fwd(q, k, v, gam, beta, hk, hv, chunk, tb, interpret):
    _count("forward", q, k, v, _heads_per_program(hk, hv))
    o, states = _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret)
    return o, (q, k, v, gam, beta, states)


def _core_bwd(hk, hv, chunk, tb, interpret, res, do):
    q, k, v, gam, beta, states = res
    _count("backward", q, k, v, _heads_per_program(hk, hv))
    dq, dk, dv, dgam, dbeta = _backward(q, k, v, gam, beta, states, do, hk,
                                        hv, chunk, tb, interpret)
    b, tp, _ = q.shape

    def to_key_heads(x):        # a key head's programs add up
        x = x.astype(F32).reshape(b, tp, hk, -1, q.shape[2] // hk)
        return jnp.sum(x, axis=3).reshape(b, tp, -1).astype(q.dtype)

    return to_key_heads(dq), to_key_heads(dk), dv, dgam, dbeta


_core.defvjp(_core_fwd, _core_bwd)


def _operands(q, k, v) -> str:
    """Which products a call's kernels run: `bfloat16` where q, k and v
    all arrive so (their products with each other one MXU pass, with a
    float32 panel three), else `float32` (six throughout)."""
    return jnp.result_type(q, k, v).name


def _count(which: str, q, k, v, heads_per_program: int) -> None:
    from deeplearning4j_tpu.observability.metrics import default_registry
    default_registry().counter(
        "gdn_calls", "gated-delta-rule kernel traces by pass, operands and "
        "value heads a program",
        labelnames=("pass", "operands", "heads_per_program")).labels(
            which, _operands(q, k, v), str(heads_per_program)).inc()


def gated_delta_rule(q: Array, k: Array, v: Array, g: Array,
                     beta: Array) -> Array:
    """o [B, T, Hv, dv] of q, k [B, T, Hk, dk] (already normalised and
    scaled), v [B, T, Hv, dv], g and beta [B, T, Hv] float32; the state
    starts at nought. T need not be a multiple of the chunk: the tail is
    padded with positions that leave the state as it is."""
    from deeplearning4j_tpu.observability.tracing import mark
    from deeplearning4j_tpu.ops.pallas_util import off_chip

    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    if hv % hk:
        raise ValueError(f"value heads {hv} not a multiple of key heads "
                         f"{hk}")
    chunk = CHUNK
    tb = min(BLOCK, -(-t // chunk) * chunk)
    tp = -(-t // tb) * tb
    mark("gdn.layout", chunk=chunk, heads=hv, block=tb,
         operands=_operands(q, k, v),
         heads_per_program=_heads_per_program(hk, hv), inverse="phased")

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, tp - t)) + ((0, 0),) * (x.ndim - 2))

    def lanes(x):           # [B, T, Hv] -> [B, Hv, T / C, C]
        return jnp.moveaxis(pad(x.astype(F32)), 1, 2).reshape(
            b, hv, tp // chunk, chunk)

    gam = jnp.cumsum(lanes(g), axis=-1)
    o = _core(pad(q).reshape(b, tp, hk * dk), pad(k).reshape(b, tp, hk * dk),
              pad(v).reshape(b, tp, hv * dv), gam, lanes(beta), hk, hv,
              chunk, tb, off_chip())
    return o[:, :t].reshape(b, t, hv, dv)
