"""Fused Pallas LSTM scan for TPU — the accelerated LSTM path.

Role parity: the reference names an accelerated LSTM path in its north
star but ships none at this version (SURVEY.md §2.3 note: no
CudnnLSTMHelper — LSTM always runs the Java LSTMHelpers loop,
reference: deeplearning4j-nn/.../recurrent/LSTMHelpers.java:161).
Here the fast path exists: one Pallas kernel runs the WHOLE recurrence
with the recurrent weights, h and c pinned in VMEM across all T steps —
the classic fused-RNN design (cuDNN's persistent RNN idea, TPU-style).
The `lax.scan` formulation in nn/layers/recurrent.py remains the
fallback, and the kernel is validated against it numerically (the
CuDNNGradientChecks pattern, reference: deeplearning4j-cuda/.../
CuDNNGradientChecks.java).

Shapes/dataflow:
- input projection x·W for all T is one big MXU matmul OUTSIDE the
  kernel (same hoisting as the scan path);
- the kernel grids over T (sequential on TPU), with VMEM scratch
  carrying (h, c) between grid steps and one [B,4H] recurrent matmul
  per step on the MXU;
- per-step gate activations and cell states stream out to HBM as the
  backward's reserve space (what cuDNN calls the RNN reserve);
- backward is a reverse `lax.scan` over the saved reserve (elementwise
  + matmuls — XLA-fused), mirroring LSTMHelpers.java:333's reverse
  loop but derived, not hand-scheduled.

Supports the Graves/peephole formulation (pI/pF/pO vectors; zeros give
a standard LSTM) with sigmoid gates and tanh activations — the
eligibility check falls back to the scan path for anything else.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def _lstm_step_kernel(xw_ref, h0_ref, c0_ref, rw_ref, b_ref, pi_ref,
                      pf_ref, po_ref, hs_ref, cs_ref, gates_ref,
                      h_scr, c_scr):
    """Grid step t: one recurrent matmul + gate math, carry in VMEM
    scratch (TPU grid steps run sequentially, so scratch persists)."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    z = (xw_ref[0].astype(jnp.float32)
         + jax.lax.dot_general(h_prev, rw_ref[:].astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
         + b_ref[:].astype(jnp.float32))
    hdim = h_prev.shape[-1]
    zi = z[:, :hdim]
    zf = z[:, hdim:2 * hdim]
    zg = z[:, 2 * hdim:3 * hdim]
    zo = z[:, 3 * hdim:]
    pi = pi_ref[:].astype(jnp.float32)
    pf = pf_ref[:].astype(jnp.float32)
    po = po_ref[:].astype(jnp.float32)
    i = jax.nn.sigmoid(zi + c_prev * pi)
    f = jax.nn.sigmoid(zf + c_prev * pf)
    g = jnp.tanh(zg)
    c = f * c_prev + i * g
    o = jax.nn.sigmoid(zo + c * po)
    h = o * jnp.tanh(c)
    h_scr[:] = h
    c_scr[:] = c
    dt = hs_ref.dtype
    hs_ref[0] = h.astype(dt)
    cs_ref[0] = c.astype(dt)
    gates_ref[0] = jnp.concatenate([i, f, g, o], axis=-1).astype(dt)


def _forward(xw_t, h0, c0, rw, b, pi, pf, po, interpret):
    """Run the fused kernel. xw_t [T,B,4H] → (hs_t [T,B,H], cs_t, gates_t)
    with the reserve tensors in f32 (the backward math runs in f32)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import (interpret_arg,
                                                    out_struct)

    t, bsz, h4 = xw_t.shape
    hdim = h4 // 4
    operands = (xw_t, h0, c0, rw, b.reshape(1, h4), pi.reshape(1, hdim),
                pf.reshape(1, hdim), po.reshape(1, hdim))
    return pl.pallas_call(
        _lstm_step_kernel,
        out_shape=[out_struct((t, bsz, hdim), jnp.float32, *operands),
                   out_struct((t, bsz, hdim), jnp.float32, *operands),
                   out_struct((t, bsz, h4), jnp.float32, *operands)],
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, bsz, h4), lambda i: (i, 0, 0)),
            pl.BlockSpec((bsz, hdim), lambda i: (0, 0)),
            pl.BlockSpec((bsz, hdim), lambda i: (0, 0)),
            pl.BlockSpec((hdim, h4), lambda i: (0, 0)),
            pl.BlockSpec((1, h4), lambda i: (0, 0)),
            pl.BlockSpec((1, hdim), lambda i: (0, 0)),
            pl.BlockSpec((1, hdim), lambda i: (0, 0)),
            pl.BlockSpec((1, hdim), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bsz, hdim), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, bsz, hdim), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, bsz, h4), lambda i: (i, 0, 0))],
        scratch_shapes=[pltpu.VMEM((bsz, hdim), jnp.float32),
                        pltpu.VMEM((bsz, hdim), jnp.float32)],
        interpret=interpret_arg(interpret, *operands),
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _lstm_core(xw_t, h0, c0, rw, b, pi, pf, po, interpret):
    hs, cs, _ = _forward(xw_t, h0, c0, rw, b, pi, pf, po, interpret)
    dt = xw_t.dtype
    return hs.astype(dt), hs[-1].astype(dt), cs[-1].astype(dt)


def _core_fwd(xw_t, h0, c0, rw, b, pi, pf, po, interpret):
    hs, cs, gates = _forward(xw_t, h0, c0, rw, b, pi, pf, po, interpret)
    dt = xw_t.dtype
    out = (hs.astype(dt), hs[-1].astype(dt), cs[-1].astype(dt))
    return out, (hs, cs, gates, h0, c0, rw, pi, pf, po)


def _core_bwd(interpret, res, grads):
    """Reverse-scan BPTT over the saved reserve (the LSTMHelpers.java:333
    analog, autodiff-grade math in f32)."""
    hs, cs, gates, h0, c0, rw, pi, pf, po = res
    dys, dh_last, dc_last = grads
    t, bsz, hdim = hs.shape
    f32 = jnp.float32
    rw32 = rw.astype(f32)
    pi32, pf32, po32 = (p.astype(f32) for p in (pi, pf, po))
    # h_prev/c_prev streams: [h0, hs[:-1]], [c0, cs[:-1]]
    h_prevs = jnp.concatenate([h0.astype(f32)[None], hs[:-1]], axis=0)
    c_prevs = jnp.concatenate([c0.astype(f32)[None], cs[:-1]], axis=0)

    def step(carry, inp):
        dh_next, dc_next, dRW, db, dpI, dpF, dpO = carry
        dy, i, f, g, o, c, c_prev, h_prev = inp
        dh = dy.astype(f32) + dh_next
        tanh_c = jnp.tanh(c)
        do = dh * tanh_c
        dzo = do * o * (1 - o)
        dc = (dh * o * (1 - tanh_c ** 2) + dc_next + dzo * po32)
        di = dc * g
        dzi = di * i * (1 - i)
        df = dc * c_prev
        dzf = df * f * (1 - f)
        dg = dc * i
        dzg = dg * (1 - g ** 2)
        dc_prev = dc * f + dzi * pi32 + dzf * pf32
        dz = jnp.concatenate([dzi, dzf, dzg, dzo], axis=-1)
        dh_prev = jnp.matmul(dz, rw32.T)
        dRW = dRW + jnp.matmul(h_prev.T, dz)
        db = db + jnp.sum(dz, axis=0)
        dpI = dpI + jnp.sum(dzi * c_prev, axis=0)
        dpF = dpF + jnp.sum(dzf * c_prev, axis=0)
        dpO = dpO + jnp.sum(dzo * c, axis=0)
        return (dh_prev, dc_prev, dRW, db, dpI, dpF, dpO), dz

    i_s = gates[..., :hdim]
    f_s = gates[..., hdim:2 * hdim]
    g_s = gates[..., 2 * hdim:3 * hdim]
    o_s = gates[..., 3 * hdim:]
    init = (dh_last.astype(f32), dc_last.astype(f32),
            jnp.zeros_like(rw32), jnp.zeros((4 * hdim,), f32),
            jnp.zeros((hdim,), f32), jnp.zeros((hdim,), f32),
            jnp.zeros((hdim,), f32))
    (dh0, dc0, dRW, db, dpI, dpF, dpO), dzs = jax.lax.scan(
        step, init, (dys, i_s, f_s, g_s, o_s, cs, c_prevs, h_prevs),
        reverse=True)
    dt = dys.dtype
    return (dzs.astype(dt), dh0.astype(dt), dc0.astype(dt),
            dRW.astype(rw.dtype), db.astype(rw.dtype),
            dpI.astype(rw.dtype), dpF.astype(rw.dtype),
            dpO.astype(rw.dtype))


_lstm_core.defvjp(_core_fwd, _core_bwd)


def fused_lstm_available(x: Array, hdim: int, mask, gate_activation: str,
                         activation: str) -> bool:
    """Eligibility: TPU (or forced interpret), standard sigmoid/tanh
    gates, no mask, MXU-friendly shapes (H a lane multiple, batch a
    sublane multiple)."""
    env = os.environ.get("DL4JTPU_FUSED_LSTM", "auto")
    if env == "0":
        return False
    if mask is not None:
        return False
    if gate_activation != "sigmoid" or activation not in ("tanh", None):
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    bsz = x.shape[0]
    if hdim % 128 != 0 or bsz % 8 != 0:
        return False
    # VMEM budget: the kernel pins the RW block [H, 4H], the step's x
    # block [B, 4H], f32 gates [B, 4H], and h/c [B, H] in VMEM, and the
    # autodiff pass roughly 2.5x's the footprint. Estimate and reject
    # what would overflow the 16MB scoped-vmem limit at compile time
    # (calibrated on v5e: B=512,H=256 bf16 fits, B=768,H=256 does not) —
    # oversize configs take the lax.scan path instead of crashing
    # compilation.
    itemsize = jnp.dtype(x.dtype).itemsize
    vmem_est = (4 * hdim * hdim * itemsize          # RW block
                + bsz * 4 * hdim * (4 + itemsize)   # f32 gates + x block
                + 2 * bsz * hdim * 4)               # h/c carries
    if vmem_est > 6_400_000:
        return False
    if env == "interpret":
        return True
    return jax.default_backend() == "tpu"


def fused_lstm_scan(params, x, carry: Tuple[Array, Array],
                    reverse: bool = False
                    ) -> Tuple[Array, Tuple[Array, Array]]:
    """Drop-in for LSTM.scan_sequence's hot path: x [B,T,F] + (h0, c0)
    → (ys [B,T,H], (h_T, c_T)). Reverse runs the flipped sequence
    through the same kernel."""
    interpret = os.environ.get("DL4JTPU_FUSED_LSTM") == "interpret"
    h0, c0 = carry
    xw = jnp.matmul(x, params["W"])          # [B, T, 4H] — one MXU pass
    xw_t = jnp.swapaxes(xw, 0, 1)            # time-major
    if reverse:
        xw_t = xw_t[::-1]
    hdim = h0.shape[-1]
    zeros = jnp.zeros((hdim,), xw_t.dtype)
    ys_t, h_f, c_f = _lstm_core(
        xw_t, h0, c0, params["RW"], params["b"],
        params.get("pI", zeros), params.get("pF", zeros),
        params.get("pO", zeros), interpret)
    if reverse:
        ys_t = ys_t[::-1]
    return jnp.swapaxes(ys_t, 0, 1), (h_f, c_f)
