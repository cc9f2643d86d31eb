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
and products of ``[C, C]`` panels, no substitution loop.

Every product's result is float32 and no operand that the kernels compute
is ever rounded: the inverse amplifies rounding. What a product costs on
the MXU follows from where its operands came from (`_dot`): q, k, v and
the output's cotangent are taken in the dtype they arrive in, and a
bfloat16 value fills one of the three bfloat16 pieces a float32 holds, so
a product of two of them is one pass, of one with a computed float32
panel three (over the panel's exact split), and only two computed panels
need the six of `Precision.HIGHEST`. Handed float32 operands, every
product is `HIGHEST`. Diagonal scalings therefore sit on the float32 side
of a product (``G (q S0)``, not ``(G q) S0``).

`gdn_fwd`: a program is one (batch row, value head, block of chunks), the
blocks of a head in order with ``S`` carried in a VMEM scratch; it writes
``o`` and each chunk's ``S0`` (what the backward starts from). `gdn_bwd`
walks the blocks and the chunks of a block in reverse with ``dS`` carried
the same way. A chunk's gradients are written out (`_chunk_bwd`): the
panels come from the one function the forward uses (`_wy`), so the two
cannot drift apart; the inverse's gradient is one product,
``dA = -(M^T dU) U^T`` with ``M = (I + A)^-1``, not the squarings'
transposes; the gates' gradients are row and column sums of panels the
other gradients need. `_chunk` is the plain form of a chunk, which the
tests differentiate to check both.

Operands keep the block's own layout, ``[B, T, H * d]`` with a head's
``d`` lanes side by side (on the chip ``d`` must be a multiple of 128);
value head ``h`` reads key head ``h // (Hv / Hk)`` by block index, never
a repeated copy. The decay arrives summed inside each chunk and with
beta as ``[B, Hv, T / C, C]`` float32, positions on the lanes.
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


def _chunk(q, k, v, gam, beta, s0):
    """One chunk, plainly: what the tests differentiate (`jax.vjp`) to
    check `_chunk_fwd` and `_chunk_bwd`. q, k [C, dk], v [C, dv], s0
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


class _WY(NamedTuple):
    """A chunk's panels that both passes need (`_wy`)."""
    eye: Array      # [C, C] bool
    lower: Array    # [C, C] bool: strictly under the diagonal
    last: Array     # [1, C] bool: the chunk's last position
    kq: Array       # [2C, dk]: k over q, as they came
    decay: Array    # [C, C]: G_t / G_s on and under the diagonal
    ak: Array       # [C, C]: A without beta, strictly lower
    inv: Array      # [C, C]: (I + A)^-1
    bcol: Array     # [C, 1]: beta
    gexp: Array     # [C, 1]: G_t
    ks: Array       # [C, dv]: k S0
    vk: Array       # [C, dv]: v - G (k S0)
    u: Array        # [C, dv]
    p: Array        # [C, C]: decay * q k^T
    glast: Array    # [1, 1]: the chunk's whole decay, G_C
    dlast: Array    # [C, 1]: G_C / G_t


def _wy(q, k, v, gam, beta, s0) -> _WY:
    """The panels of one chunk, from operands in the dtype they came in
    (`_dot` reads it); s0 [dk, dv], gam and beta [1, C] float32. `u` is
    formed without `w`: (I + A)^-1 (beta (v - G (k S0)))."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye, lower, last = row == col, row > col, col[:1] == c - 1

    def column(r):          # [1, C] -> [C, 1] by the diagonal of a panel
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    gcol, bcol = column(gam), column(beta)
    decay = jnp.exp(jnp.where(row >= col, gcol - gam, NEG))   # G_t / G_s
    kq = jnp.concatenate([k, q], axis=0)
    kkqk = _dot(kq, k, _NT)             # k k^T over q k^T: one product
    ak = jnp.where(lower, decay * kkqk[:c], 0.0)
    # (I + A)^-1 = prod (I + (-A)^(2^i))
    p = -(bcol * ak)
    inv = jnp.where(eye, 1.0, 0.0) + p
    n = 2
    while n < c:
        p = _dot(p, p, _NN)
        inv = inv + _dot(inv, p, _NN)
        n *= 2
    gexp = jnp.exp(gcol)
    ks = _dot(k, s0, _NN)
    vk = v.astype(F32) - gexp * ks
    glast = jnp.sum(jnp.where(last, gam, 0.0), axis=1, keepdims=True)
    return _WY(eye, lower, last, kq, decay, ak, inv, bcol, gexp, ks, vk,
               _dot(inv, bcol * vk, _NN), decay * kkqk[c:], jnp.exp(glast),
               jnp.exp(glast - gcol))


def _chunk_fwd(q, k, v, gam, beta, s0):
    """(o [C, dv], s1) of one chunk: `_chunk`'s, with each diagonal
    scaling on the float32 side of its product."""
    w = _wy(q, k, v, gam, beta, s0)
    o = w.gexp * _dot(q, s0, _NN) + _dot(w.p, w.u, _NN)
    return o, w.glast * s0 + _dot(k, w.dlast * w.u, _TN)


def _chunk_bwd(q, k, v, gam, beta, s0, do, ds1):
    """(dq, dk, dv, dgam, dbeta, ds0) of one chunk, written out. With
    M = (I + A)^-1, u = M r and dr = M^T du, the inverse's gradient is
    dA = -dr u^T under the strict-lower mask: one product, not the
    squarings' transposes. `o` is not formed again. The gates' gradients
    are row and column sums of panels the products' gradients need. Two
    products of 64 contracted rows that add up are one of 128."""
    w = _wy(q, k, v, gam, beta, s0)

    def rowsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    def colsum(x):
        return jnp.sum(x, axis=0, keepdims=True)

    def to_row(x):          # [C, 1] -> [1, C], `column` backwards
        return colsum(jnp.where(w.eye, x, 0.0))

    # o = G (q S0) + P u;  s1 = G_C S0 + k^T ((G_C / G) u)
    dp = _dot(do, w.u, _NT)                                 # [C, C]
    dos = _dot(do, s0, _NT)                                 # [C, dk]
    kds = _dot(k, ds1, _NN)                                 # [C, dv]
    du = _dot(w.p.T, do, _NN) + w.dlast * kds
    # u = M (beta vk);  vk = v - G (k S0)
    dr = _dot(w.inv, du, _TN)
    da = -_dot(dr, w.u, _NT)
    e = -(w.bcol * w.gexp) * dr         # d(k S0)
    # A = beta ak, ak and P = decay * (k k^T, q k^T)
    dkk = jnp.where(w.lower, w.bcol * w.decay * da, 0.0)
    dqk = dp * w.decay
    dq = w.gexp * dos + _dot(dqk, k, _NN)
    # (float32 k over q: this one product ran faster in six passes than
    # in three with the panel's split, PERF.md section 6, PR 31)
    dk = (_dot(jnp.concatenate([dkk + dkk.T, dqk.T], axis=1),
               w.kq.astype(F32), _NN)
          + _dot(w.dlast * w.u, ds1, _NT) + _dot(e, s0, _NT))
    ds0 = w.glast * ds1 + _dot(
        w.kq, jnp.concatenate([e, w.gexp * do.astype(F32)], axis=0), _TN)
    # the decay: d(G_t / G_s) G_t / G_s, summed along t and along s
    x = da * w.ak
    dd = w.bcol * x + dp * w.p
    ddl = rowsum(kds * w.u) * w.dlast
    dgexp = rowsum(dos * q.astype(F32)) - w.bcol * rowsum(dr * w.ks)
    dglast = colsum(ddl) + w.glast * colsum(rowsum(ds1 * s0))
    dgam = (to_row(rowsum(dd) + dgexp * w.gexp - ddl) - colsum(dd)
            + jnp.where(w.last, dglast, 0.0))
    dbeta = to_row(rowsum(x) + rowsum(dr * w.vk))
    return dq, dk, w.bcol * dr, dgam, dbeta, ds0


def _load(refs, c, chunk):
    """A chunk's rows and its operands as they came: bfloat16 q, k, v
    stay bfloat16, which is what `_dot` goes by."""
    import jax.experimental.pallas as pl
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    return (rows, q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :],
            g_ref[0, 0, pl.ds(c, 1), :], b_ref[0, 0, pl.ds(c, 1), :])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, s_scr, *,
                chunk: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    def body(c, _):
        rows, q, k, v, gam, beta = _load(
            (q_ref, k_ref, v_ref, g_ref, b_ref), c, chunk)
        s0 = s_scr[...]
        st_ref[0, 0, c] = s0
        o, s1 = _chunk_fwd(q, k, v, gam, beta, s0)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        s_scr[...] = s1
        return ()

    jax.lax.fori_loop(0, g_ref.shape[2], body, (), unroll=True)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *,
                chunk: int):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    nc = g_ref.shape[2]

    def body(i, _):
        c = nc - 1 - i
        rows, q, k, v, gam, beta = _load(
            (q_ref, k_ref, v_ref, g_ref, b_ref), c, chunk)
        dq, dk, dv, dgam, dbeta, ds0 = _chunk_bwd(
            q, k, v, gam, beta, st_ref[0, 0, c], do_ref[0, rows, :],
            ds_scr[...])
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, 0, pl.ds(c, 1), :] = dgam
        db_ref[0, 0, pl.ds(c, 1), :] = dbeta
        ds_scr[...] = ds0
        return ()

    jax.lax.fori_loop(0, nc, body, (), unroll=True)


def _specs(tp, hk, hv, dk, dv, tb, chunk, reverse: bool):
    import jax.experimental.pallas as pl
    nt = tp // tb
    r = hv // hk

    def tt(i):
        return nt - 1 - i if reverse else i

    qk = pl.BlockSpec((1, tb, dk), lambda n, h, i: (n, tt(i), h // r))
    qk_own = pl.BlockSpec((1, tb, dk), lambda n, h, i: (n, tt(i), h))
    vv = pl.BlockSpec((1, tb, dv), lambda n, h, i: (n, tt(i), h))
    gate = pl.BlockSpec((1, 1, tb // chunk, chunk),
                        lambda n, h, i: (n, h, tt(i), 0))
    state = pl.BlockSpec((1, 1, tb // chunk, dk, dv),
                         lambda n, h, i: (n, h, tt(i), 0, 0))
    return qk, qk_own, vv, gate, state


def _check_lanes(dk: int, dv: int, interpret) -> None:
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"gated_delta_rule: on the chip a head's key and value sizes "
            f"must be multiples of 128 lanes, got {dk} and {dv}")


def _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    b, tp, _ = q.shape
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    _check_lanes(dk, dv, interpret)
    qk, _, vv, gate, state = _specs(tp, hk, hv, dk, dv, tb, chunk, False)
    ops = (q, k, v, gam, beta)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        out_shape=[out_struct(v.shape, v.dtype, *ops),
                   out_struct((b, hv, tp // chunk, dk, dv), F32, *ops)],
        grid=(b, hv, tp // tb),
        in_specs=[qk, qk, vv, gate, gate],
        out_specs=[vv, state],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_arg(interpret, *ops),
        name="gdn_fwd",
    )(*ops)


def _backward(q, k, v, gam, beta, states, do, hk, hv, chunk, tb, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    b, tp, _ = q.shape
    dk, dv = q.shape[2] // hk, v.shape[2] // hv
    qk, qk_own, vv, gate, state = _specs(tp, hk, hv, dk, dv, tb, chunk, True)
    ops = (q, k, v, gam, beta, states, do)
    per_head = out_struct((b, tp, hv * dk), q.dtype, *ops)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        out_shape=[per_head, per_head, out_struct(v.shape, v.dtype, *ops),
                   out_struct(gam.shape, F32, *ops),
                   out_struct(gam.shape, F32, *ops)],
        grid=(b, hv, tp // tb),
        in_specs=[qk, qk, vv, gate, gate, state, vv],
        out_specs=[qk_own, qk_own, vv, gate, gate],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_arg(interpret, *ops),
        name="gdn_bwd",
    )(*ops)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _core(q, k, v, gam, beta, hk, hv, chunk, tb, interpret):
    return _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret)[0]


def _core_fwd(q, k, v, gam, beta, hk, hv, chunk, tb, interpret):
    _count("forward", q, k, v)
    o, states = _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret)
    return o, (q, k, v, gam, beta, states)


def _core_bwd(hk, hv, chunk, tb, interpret, res, do):
    q, k, v, gam, beta, states = res
    _count("backward", q, k, v)
    dq, dk, dv, dgam, dbeta = _backward(q, k, v, gam, beta, states, do, hk,
                                        hv, chunk, tb, interpret)
    b, tp, _ = q.shape
    r = hv // hk

    def to_key_heads(x):        # a key head's value heads add up
        x = x.astype(F32).reshape(b, tp, hk, r, -1)
        return jnp.sum(x, axis=3).reshape(b, tp, -1).astype(q.dtype)

    return to_key_heads(dq), to_key_heads(dk), dv, dgam, dbeta


_core.defvjp(_core_fwd, _core_bwd)


def _operands(q, k, v) -> str:
    """Which products a call's kernels run: `bfloat16` where q, k and v
    all arrive so (their products with each other one MXU pass, with a
    float32 panel three), else `float32` (six throughout)."""
    return jnp.result_type(q, k, v).name


def _count(which: str, q, k, v) -> None:
    from deeplearning4j_tpu.observability.metrics import default_registry
    default_registry().counter(
        "gdn_calls", "gated-delta-rule kernel traces by pass and operands",
        labelnames=("pass", "operands")).labels(
            which, _operands(q, k, v)).inc()


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
         operands=_operands(q, k, v))

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
