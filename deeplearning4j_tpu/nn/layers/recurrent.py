"""Recurrent layers: LSTM, GravesLSTM (peepholes), GravesBidirectionalLSTM.

Reference: deeplearning4j-nn/.../nn/layers/recurrent/LSTMHelpers.java
(forward time loop :161, BPTT reverse loop :333, Graves/peephole formulation
per the weight layout at :59), GravesLSTM.java:94,142,
GravesBidirectionalLSTM.java:96-224, BaseRecurrentLayer.java (stateMap for
rnnTimeStep streaming inference).

TPU-native design: the per-timestep Java loop becomes `lax.scan` with all four
gates computed in ONE [*, 4H] matmul per step (MXU-friendly), the input
projection x·W for all timesteps hoisted out of the scan as a single batched
matmul, and autodiff-through-scan replacing the hand-written BPTT loop. Gate
order in the packed 4H axis: [i, f, g(cell), o].
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.nn.conf import inputs as it
from deeplearning4j_tpu.nn.conf.serde import register
from deeplearning4j_tpu.nn.layers.base import BaseLayer
from deeplearning4j_tpu.nn.weights import init_weights

Array = jax.Array


@register
@dataclass
class LSTM(BaseLayer):
    """Standard LSTM (no peepholes) over [B, T, F] -> [B, T, H]."""
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    peephole: bool = False

    @property
    def family(self) -> str:
        return "rnn"

    def update_input_type(self, input_type):
        if isinstance(input_type, it.InputTypeRecurrent):
            if self.n_in is None:
                self.n_in = input_type.size
            return it.InputType.recurrent(self.n_out,
                                          input_type.time_series_length)
        raise ValueError(f"{type(self).__name__} needs recurrent input, "
                         f"got {input_type}")

    def init_params(self, key, dtype=jnp.float32) -> Dict[str, Array]:
        k1, k2, k3 = jax.random.split(key, 3)
        h = self.n_out
        scheme = self.weight_init or "xavier"
        w = init_weights(k1, (self.n_in, 4 * h), self.n_in, h, scheme,
                         self.dist, dtype)
        rw = init_weights(k2, (h, 4 * h), h, h, scheme, self.dist, dtype)
        b = jnp.zeros((4 * h,), dtype)
        # forget-gate bias init (reference: conf field forgetGateBiasInit)
        b = b.at[h:2 * h].set(self.forget_gate_bias_init)
        params = {"W": w, "RW": rw, "b": b}
        if self.peephole:
            params["pI"] = jnp.zeros((h,), dtype)
            params["pF"] = jnp.zeros((h,), dtype)
            params["pO"] = jnp.zeros((h,), dtype)
        return params

    def weight_param_keys(self):
        return ("W", "RW")

    def _gates(self, params, xw_t, h_prev, c_prev):
        """One step's gate math. xw_t: [B, 4H] precomputed input projection."""
        z = xw_t + jnp.matmul(h_prev, params["RW"]) + params["b"]
        return self._gates_from_z(params, z, c_prev)

    def _gates_from_z(self, params, z, c_prev):
        """Gate math from a fully-formed pre-activation z [B, 4H]
        (input projection + recurrence + bias already summed) — the
        entry point the cross-layer wavefront uses so its fused GEMMs
        share this exact cell (peepholes, activations and all)."""
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        gate = get_activation(self.gate_activation)
        act = get_activation(self.activation or "tanh")
        if self.peephole:
            i = gate(zi + c_prev * params["pI"])
            f = gate(zf + c_prev * params["pF"])
        else:
            i = gate(zi)
            f = gate(zf)
        g = act(zg)
        c = f * c_prev + i * g
        if self.peephole:
            o = gate(zo + c * params["pO"])
        else:
            o = gate(zo)
        h = o * act(c)
        return h, c

    # Unidirectional LSTMs carry (h, c) across rnn_time_step calls and TBPTT
    # chunks; bidirectional overrides this to False — its backward pass needs
    # the full sequence (the reference likewise throws from rnnTimeStep on
    # GravesBidirectionalLSTM).
    supports_streaming = True

    def initial_carry(self, batch: int, dtype=jnp.float32):
        h = jnp.zeros((batch, self.n_out), dtype)
        c = jnp.zeros((batch, self.n_out), dtype)
        return (h, c)

    def scan_sequence(self, params, x, carry=None, mask=None, reverse=False):
        """Run the full sequence: x [B, T, F] -> (outputs [B, T, H], carry).

        The input projection for ALL timesteps is one big matmul outside the
        scan (the reference computes x_t·W inside its Java time loop,
        LSTMHelpers.java:161 — hoisting it is the TPU win)."""
        b = x.shape[0]
        if carry is None:
            carry = self.initial_carry(b, x.dtype)
        # Fused Pallas path (the accelerated-LSTM analog of the
        # reference's cuDNN helper plug point; ops/lstm.py) — whole
        # recurrence in one kernel, weights/h/c pinned in VMEM.
        from deeplearning4j_tpu.ops.lstm import (fused_lstm_available,
                                                 fused_lstm_scan)
        if fused_lstm_available(x, self.n_out, mask,
                                self.gate_activation,
                                self.activation or "tanh"):
            return fused_lstm_scan(params, x, carry, reverse=reverse)
        xw = jnp.matmul(x, params["W"])  # [B, T, 4H]
        xw_t = jnp.swapaxes(xw, 0, 1)    # [T, B, 4H] time-major for scan
        if mask is not None:
            mask_t = jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None]
        else:
            mask_t = None

        def step(c, inp):
            if mask_t is None:
                xw_step = inp
                m = None
            else:
                xw_step, m = inp
            h_prev, c_prev = c
            h, cc = self._gates(params, xw_step, h_prev, c_prev)
            if m is not None:
                # masked steps pass state through unchanged, output 0
                h_keep = m * h + (1 - m) * h_prev
                c_keep = m * cc + (1 - m) * c_prev
                return (h_keep, c_keep), m * h
            return (h, cc), h

        xs = xw_t if mask_t is None else (xw_t, mask_t)
        carry, ys = lax.scan(step, carry, xs, reverse=reverse)
        return jnp.swapaxes(ys, 0, 1), carry  # back to [B, T, H]

    def apply(self, params, state, x, *, train=False, key=None, mask=None
              ) -> Tuple[Array, Dict]:
        ys, _ = self.scan_sequence(params, x, mask=mask)
        return ys, state

    def step(self, params, carry, x_t):
        """Single-timestep inference (reference: rnnTimeStep,
        MultiLayerNetwork.java:2234 / BaseRecurrentLayer stateMap)."""
        xw_t = jnp.matmul(x_t, params["W"])
        h_prev, c_prev = carry
        h, c = self._gates(params, xw_t, h_prev, c_prev)
        return (h, c), h


def wavefront_scan_stack(layers, plist, x, carries=None):
    """Run a STACK of unidirectional LSTM layers as one wavefront scan
    (measured r4: 1.14x at B=1024, 1.28x at B=8192 on the 2x200
    char-RNN vs per-layer sequential scans; BASELINE.md r4, an
    earlier toolchain).

    Schedule: T + n - 1 steps; at step s, layer j advances to time
    s - j, consuming h_{j-1}[s-j] — exactly the carry layer j-1 holds
    BEFORE its own update this step. Layer j's recurrence and layer
    j+1's input projection therefore share one operand and fuse into a
    single [B,H]x[H,8H] GEMM per layer (n wide GEMMs per step instead
    of 2n narrow ones over 2·sum(T) sequential steps). An exact
    reordering of the per-layer scans: each layer's cell math runs
    through its own _gates_from_z (peepholes/activations preserved),
    off-wavefront lanes are liveness-masked so states and final
    carries equal the sequential schedule's.

    x: [B, T, F] -> (outputs of the LAST layer [B, T, H_last],
    [per-layer (h, c) final carries]).
    """
    n = len(layers)
    b, t = x.shape[0], x.shape[1]
    xw0 = jnp.matmul(x, plist[0]["W"])            # hoisted, [B, T, 4H0]
    xw0t = jnp.swapaxes(xw0, 0, 1)
    pad = jnp.zeros((n - 1,) + xw0t.shape[1:], xw0t.dtype)
    xs = jnp.concatenate([xw0t, pad], axis=0)     # [T+n-1, B, 4H0]
    if carries is None:
        carries = [l.initial_carry(b, x.dtype) for l in layers]
    fused_w = []
    for j in range(n):
        if j + 1 < n:
            fused_w.append(jnp.concatenate(
                [plist[j]["RW"], plist[j + 1]["W"]], axis=1))
        else:
            fused_w.append(plist[j]["RW"])

    def step(carry, inp):
        xw, s = inp
        hs = [c[0] for c in carry]
        cs = [c[1] for c in carry]
        gem = [jnp.matmul(hs[j], fused_w[j]) for j in range(n)]
        inputs = [xw] + [gem[j - 1][:, 4 * layers[j - 1].n_out:]
                         for j in range(1, n)]
        new = []
        for j, lay in enumerate(layers):
            z = (inputs[j] + gem[j][:, :4 * lay.n_out]
                 + plist[j]["b"])
            h_new, c_new = lay._gates_from_z(plist[j], z, cs[j])
            live = jnp.logical_and(s >= j, s < t + j)
            new.append((jnp.where(live, h_new, hs[j]),
                        jnp.where(live, c_new, cs[j])))
        return tuple(new), new[-1][0]

    carry, ys = lax.scan(step, tuple(carries),
                         (xs, jnp.arange(t + n - 1)))
    return jnp.swapaxes(ys[n - 1:], 0, 1), list(carry)


def wavefront_eligible_run(layers, names, start, *, train, mask,
                           carries, preprocessors, enabled=True):
    """Longest run of fusable LSTM layers beginning at ``start`` (>=2
    indices, else []). Fusable: plain unidirectional LSTM/GravesLSTM
    (supports_streaming), no mask, no inter-layer preprocessor or
    (train-time) dropout inside the run, and the streaming-carries
    dict either covers the whole run or none of it. ``enabled=False``
    (the instance-level switch, e.g. MultiLayerNetwork.lstm_wavefront)
    or DL4JTPU_WAVEFRONT=0 disables."""
    import os
    if (not enabled
            or os.environ.get("DL4JTPU_WAVEFRONT", "1") == "0"
            or mask is not None):
        return []
    def fusable(lay):
        return isinstance(lay, LSTM) and lay.supports_streaming
    if not fusable(layers[start]):
        return []
    run = [start]
    for j in range(start + 1, len(layers)):
        lay = layers[j]
        if not fusable(lay):
            break
        if preprocessors.get(str(j)) is not None:
            break
        if train and (lay.dropout or 0.0) > 0:
            break
        run.append(j)
    if len(run) < 2:
        return []
    if carries is not None:
        inside = [names[j] in carries for j in run]
        if any(inside) and not all(inside):
            return []
    return run


@register
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections — the reference's Graves formulation
    (GravesLSTM.java, LSTMHelpers weight layout :59 appends 3 peephole
    columns to the recurrent weights; here they are separate [H] vectors,
    which shards cleaner under tensor parallelism)."""

    def __post_init__(self):
        self.peephole = True

    def weight_param_keys(self):
        return ("W", "RW")


@register
@dataclass
class GravesBidirectionalLSTM(LSTM):
    """Bidirectional Graves LSTM (reference:
    GravesBidirectionalLSTM.java:96-224). ``mode``='add' sums forward and
    backward activations (the reference's behavior); 'concat' concatenates
    (doubling output size)."""
    mode: str = "add"

    supports_streaming = False  # backward direction needs the full sequence

    def __post_init__(self):
        self.peephole = True

    def update_input_type(self, input_type):
        out = super().update_input_type(input_type)
        if self.mode == "concat":
            return it.InputType.recurrent(2 * self.n_out,
                                          out.time_series_length)
        return out

    def init_params(self, key, dtype=jnp.float32) -> Dict[str, Array]:
        kf, kb = jax.random.split(key)
        fwd = super().init_params(kf, dtype)
        bwd = super().init_params(kb, dtype)
        params = {f"F{k}": v for k, v in fwd.items()}
        params.update({f"B{k}": v for k, v in bwd.items()})
        return params

    def weight_param_keys(self):
        return ("FW", "FRW", "BW", "BRW")

    def _split_dir(self, params, prefix):
        return {k[1:]: v for k, v in params.items() if k.startswith(prefix)}

    def apply(self, params, state, x, *, train=False, key=None, mask=None
              ) -> Tuple[Array, Dict]:
        fwd_p = self._split_dir(params, "F")
        bwd_p = self._split_dir(params, "B")
        ys_f, _ = self.scan_sequence(fwd_p, x, mask=mask, reverse=False)
        ys_b, _ = self.scan_sequence(bwd_p, x, mask=mask, reverse=True)
        if self.mode == "concat":
            return jnp.concatenate([ys_f, ys_b], axis=-1), state
        return ys_f + ys_b, state
