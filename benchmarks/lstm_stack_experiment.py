"""Stacked-gate-GEMM experiment for the 2-layer char-RNN LSTM
(VERDICT r3 #10 — the remaining untried idea for BASELINE config 3,
which sits at 6.7% MFU, scan-bound).

Idea under test: the production path runs layer 1's T-step scan to
completion, hoists layer 2's input projection into one big matmul,
then runs layer 2's T-step scan — 2T sequential scan steps with one
small [B,H]x[H,4H] recurrent GEMM each. A WAVEFRONT schedule runs both
layers in ONE scan of T+1 steps: at step s, layer 1 advances to time s
while layer 2 advances to time s-1, consuming h1[s-1] — which is
exactly the carry layer 1 holds BEFORE its update, so layer 2's input
projection and layer 1's recurrence share one operand and fuse into a
single [B,H]x[H,8H] GEMM (h1 @ [R1 | W2]), plus layer 2's own
[B,H]x[H,4H] recurrence. Same FLOPs (the hoisted projection moves
in-scan), HALF the scan steps, fewer+wider MXU calls per step. If the
LSTM config is bound by per-scan-step overhead (the batch-scaling
evidence: 4.1% MFU at B=1024 -> 6.7% at B=8192), halving steps should
show up directly.

The wavefront is an exact reordering — both variants are checked for
loss/grad equality before timing.

Run (on the chip): PYTHONPATH=. python
benchmarks/lstm_stack_experiment.py [--batch 1024]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def init(key, v, h):
    ks = jax.random.split(key, 7)

    def w(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * 0.05
    return {"W1": w(ks[0], (v, 4 * h)), "R1": w(ks[1], (h, 4 * h)),
            "b1": jnp.zeros((4 * h,)),
            "W2": w(ks[2], (h, 4 * h)), "R2": w(ks[3], (h, 4 * h)),
            "b2": jnp.zeros((4 * h,)),
            "Wout": w(ks[4], (h, v))}


def _cell(z, c_prev, h_dim):
    i = jax.nn.sigmoid(z[:, :h_dim])
    f = jax.nn.sigmoid(z[:, h_dim:2 * h_dim])
    g = jnp.tanh(z[:, 2 * h_dim:3 * h_dim])
    o = jax.nn.sigmoid(z[:, 3 * h_dim:])
    c = f * c_prev + i * g
    return o * jnp.tanh(c), c


def loss_sequential(params, x_oh, targets, h_dim, dtype):
    """Production-shaped: two sequential T-step scans, layer-2 input
    projection hoisted into one big matmul between them."""
    b = x_oh.shape[0]
    p = {k: v.astype(dtype) for k, v in params.items()}
    xw1 = jnp.einsum("btv,vg->btg", x_oh.astype(dtype), p["W1"]) + p["b1"]

    def step1(carry, xw):
        h, c = carry
        z = (xw + jnp.matmul(h, p["R1"])).astype(jnp.float32)
        h, c = _cell(z, c, h_dim)
        return (h.astype(dtype), c), h.astype(dtype)

    hc0 = (jnp.zeros((b, h_dim), dtype), jnp.zeros((b, h_dim),
                                                   jnp.float32))
    _, h1 = lax.scan(step1, hc0, jnp.swapaxes(xw1, 0, 1))   # [T, B, H]
    xw2 = jnp.einsum("tbh,hg->tbg", h1, p["W2"]) + p["b2"]

    def step2(carry, xw):
        h, c = carry
        z = (xw + jnp.matmul(h, p["R2"])).astype(jnp.float32)
        h, c = _cell(z, c, h_dim)
        return (h.astype(dtype), c), h.astype(dtype)

    _, h2 = lax.scan(step2, hc0, xw2)                       # [T, B, H]
    logits = jnp.einsum("tbh,hv->tbv", h2, p["Wout"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.swapaxes(targets, 0, 1)
    return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()


def loss_wavefront(params, x_oh, targets, h_dim, dtype):
    """ONE scan of T+1 steps; per step: h1 @ [R1|W2] (one 8H-wide GEMM)
    + h2 @ R2. Layer 2 lags one timestep; step T runs only layer 2's
    final time index (layer 1's lane is masked by feeding zeros and
    discarding the output)."""
    b, t = x_oh.shape[0], x_oh.shape[1]
    p = {k: v.astype(dtype) for k, v in params.items()}
    xw1 = jnp.einsum("btv,vg->btg", x_oh.astype(dtype), p["W1"]) + p["b1"]
    xw1 = jnp.concatenate(
        [jnp.swapaxes(xw1, 0, 1),
         jnp.zeros((1, b, 4 * h_dim), dtype)], axis=0)      # [T+1,B,4H]
    r1w2 = jnp.concatenate([p["R1"], p["W2"]], axis=1)      # [H, 8H]

    def step(carry, inp):
        xw, s = inp
        h1, c1, h2, c2 = carry
        both = jnp.matmul(h1, r1w2)                         # [B, 8H]
        z1 = (xw + both[:, :4 * h_dim]).astype(jnp.float32)
        h1n, c1n = _cell(z1, c1, h_dim)
        z2 = (both[:, 4 * h_dim:] + p["b2"]
              + jnp.matmul(h2, p["R2"])).astype(jnp.float32)
        h2n, c2n = _cell(z2, c2, h_dim)
        # s=0: layer 2 has no input yet — its state must stay zero
        # (the lag step would otherwise seed time 0 with cell(b2))
        live = (s > 0)
        h2n = jnp.where(live, h2n, h2.astype(jnp.float32))
        c2n = jnp.where(live, c2n, c2)
        return ((h1n.astype(dtype), c1n, h2n.astype(dtype), c2n),
                h2n.astype(dtype))

    z0 = jnp.zeros((b, h_dim), dtype)
    z0f = jnp.zeros((b, h_dim), jnp.float32)
    _, h2 = lax.scan(step, (z0, z0f, z0, z0f),
                     (xw1, jnp.arange(t + 1)))        # [T+1, B, H]
    h2 = h2[1:]                                       # drop lag step
    logits = jnp.einsum("tbh,hv->tbv", h2, p["Wout"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.swapaxes(targets, 0, 1)
    return -jnp.take_along_axis(logp, tgt[..., None], -1).mean()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--hidden", type=int, default=200)
    ap.add_argument("--seqlen", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=80)
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args()
    b, h, t, v = args.batch, args.hidden, args.seqlen, args.vocab

    params = init(jax.random.PRNGKey(0), v, h)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, v, (b, t)), jnp.int32)
    x_oh = jax.nn.one_hot(ids, v)
    tgts = jnp.roll(ids, -1, axis=1)

    # exactness: the wavefront is a reordering, not an approximation
    # (checked in f32 where the schedules are bit-comparable)
    l1, g1 = jax.value_and_grad(
        lambda p: loss_sequential(p, x_oh, tgts, h, jnp.float32))(params)
    l2, g2 = jax.value_and_grad(
        lambda p: loss_wavefront(p, x_oh, tgts, h, jnp.float32))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, c in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-4, atol=1e-6)

    def bench(loss_fn, reps=3):
        def train(params, x_oh, tgts):
            def body(p, _):
                g = jax.grad(lambda pp: loss_fn(pp, x_oh, tgts, h,
                                                jnp.bfloat16))(p)
                p = jax.tree_util.tree_map(
                    lambda a, gg: a - 0.1 * gg.astype(jnp.float32),
                    p, g)
                return p, ()
            p, _ = lax.scan(body, params, None, length=args.steps)
            return p
        f = jax.jit(train, donate_argnums=(0,))
        p = f(jax.tree_util.tree_map(jnp.copy, params), x_oh, tgts)
        float(jnp.sum(p["Wout"]))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            p = f(p, x_oh, tgts)
            float(jnp.sum(p["Wout"]))
            best = min(best, time.perf_counter() - t0)
        return best / args.steps * 1e3

    seq_ms = bench(loss_sequential)
    wav_ms = bench(loss_wavefront)
    print(json.dumps({
        "experiment": "lstm_2layer_wavefront_stacked_gemm",
        "config": f"B{b}_T{t}_H{h}_V{v}_bf16",
        "sequential_ms_per_step": round(seq_ms, 2),
        "wavefront_ms_per_step": round(wav_ms, 2),
        "speedup": round(seq_ms / wav_ms, 3),
        "chars_per_sec_seq": round(b * t / (seq_ms / 1e3)),
        "chars_per_sec_wavefront": round(b * t / (wav_ms / 1e3)),
    }), flush=True)


if __name__ == "__main__":
    main()
