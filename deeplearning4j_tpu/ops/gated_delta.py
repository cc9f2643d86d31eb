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
    U = (I + A)^-1 (beta v  -  (beta G k) S0)            [C, dv]
    O = (G q) S0 + (tril(G_t / G_s) * q k^T) U
    S1 = G_C S0 + ((G_C / G) k)^T U

all matrix products. ``A`` is strictly lower triangular, so
``(I + A)^-1 = prod_i (I + (-A)^(2^i))`` over ``log2 C`` factors: squarings
and products of ``[C, C]`` panels, no substitution loop. Every product is
float32 (`Precision.HIGHEST` on the MXU): the inverse amplifies rounding.

`gdn_fwd`: a program is one (batch row, value head, block of chunks), the
blocks of a head in order with ``S`` carried in a VMEM scratch; it writes
``o`` and each chunk's ``S0`` (what the backward starts from). `gdn_bwd`
walks the blocks and the chunks of a block in reverse with ``dS`` carried
the same way; a chunk's gradients are `jax.vjp` of the chunk function that
the forward runs, taken inside the kernel, so the two cannot drift apart.

Operands keep the block's own layout, ``[B, T, H * d]`` with a head's
``d`` lanes side by side (on the chip ``d`` must be a multiple of 128);
value head ``h`` reads key head ``h // (Hv / Hk)`` by block index, never
a repeated copy. The decay arrives summed inside each chunk and with
beta as ``[B, Hv, T / C, C]`` float32, positions on the lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
NEG = -1e30
CHUNK = 64          # positions a chunk (the published kernel's)
BLOCK = 512         # positions a program: whole chunks
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=F32)


def _chunk(q, k, v, gam, beta, s0):
    """One chunk. q, k [C, dk], v [C, dv], s0 [dk, dv] float32; gam (the
    decay summed from the chunk's start) and beta [1, C], positions on the
    lanes. Returns (o [C, dv], s1)."""
    c = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = row == col

    def column(r):          # [1, C] -> [C, 1] by the diagonal of a panel
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    gcol, bcol = column(gam), column(beta)
    decay = jnp.exp(jnp.where(row >= col, gcol - gam, NEG))   # G_t / G_s
    a = jnp.where(row > col, bcol * decay * _dot(k, k, ((1,), (1,))), 0.0)
    # (I + A)^-1 = prod (I + (-A)^(2^i))
    p = -a
    inv = jnp.where(eye, 1.0, 0.0) + p
    n = 2
    while n < c:
        p = _dot(p, p, ((1,), (0,)))
        inv = inv + _dot(inv, p, ((1,), (0,)))
        n *= 2
    gexp = jnp.exp(gcol)
    w = _dot(inv, k * (bcol * gexp), ((1,), (0,)))            # [C, dk]
    u = _dot(inv, v * bcol, ((1,), (0,))) - _dot(w, s0, ((1,), (0,)))
    o = _dot(q * gexp, s0, ((1,), (0,))) + _dot(
        decay * _dot(q, k, ((1,), (1,))), u, ((1,), (0,)))
    glast = jnp.sum(jnp.where(col[:1] == c - 1, gam, 0.0), axis=1,
                    keepdims=True)      # [1, 1]: the chunk's whole decay
    s1 = jnp.exp(glast) * s0 + _dot(k * jnp.exp(glast - gcol), u,
                                    ((0,), (0,)))
    return o, s1


def _load(refs, c, chunk):
    import jax.experimental.pallas as pl
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    return (rows, q_ref[0, rows, :].astype(F32),
            k_ref[0, rows, :].astype(F32), v_ref[0, rows, :].astype(F32),
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
        o, s1 = _chunk(q, k, v, gam, beta, s0)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        s_scr[...] = s1
        return ()

    jax.lax.fori_loop(0, g_ref.shape[2], body, ())


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
        _, pull = jax.vjp(_chunk, q, k, v, gam, beta, st_ref[0, 0, c])
        dq, dk, dv, dgam, dbeta, ds0 = pull(
            (do_ref[0, rows, :].astype(F32), ds_scr[...]))
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, 0, pl.ds(c, 1), :] = dgam
        db_ref[0, 0, pl.ds(c, 1), :] = dbeta
        ds_scr[...] = ds0
        return ()

    jax.lax.fori_loop(0, nc, body, ())


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
    _count("forward")
    o, states = _forward(q, k, v, gam, beta, hk, hv, chunk, tb, interpret)
    return o, (q, k, v, gam, beta, states)


def _core_bwd(hk, hv, chunk, tb, interpret, res, do):
    _count("backward")
    q, k, v, gam, beta, states = res
    dq, dk, dv, dgam, dbeta = _backward(q, k, v, gam, beta, states, do, hk,
                                        hv, chunk, tb, interpret)
    b, tp, _ = q.shape
    r = hv // hk

    def to_key_heads(x):        # a key head's value heads add up
        x = x.astype(F32).reshape(b, tp, hk, r, -1)
        return jnp.sum(x, axis=3).reshape(b, tp, -1).astype(q.dtype)

    return to_key_heads(dq), to_key_heads(dk), dv, dgam, dbeta


_core.defvjp(_core_fwd, _core_bwd)


def _count(which: str) -> None:
    from deeplearning4j_tpu.observability.metrics import default_registry
    default_registry().counter(
        "gdn_calls", "gated-delta-rule kernel traces by pass",
        labelnames=("pass",)).labels(which).inc()


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
    mark("gdn.layout", chunk=chunk, heads=hv, block=tb)

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
