"""LeNet component profile + conv1 lever experiments (VERDICT r4 #6).

`python benchmarks/lenet_profile.py` (real chip; ~2 min)

Method: per-dispatch host latency swamps single-op timing, so
every probe is a 100-iteration `lax.scan` whose body applies a PREFIX of
the net and folds the output back into the carry through a scalar — the
projection cost is identical across probes, so stage costs are the
successive differences (the same in-program methodology as bench.py).

r5 findings (chip, B=4096, bf16 — the bench config):

- cumulative fwd: conv1 alone ~1.3-1.5 ms; adding pool1/conv2/pool2/
  dense/out moves the total by <=0.25 ms each (XLA fuses them into the
  stream) — THE FORWARD IS conv1.
- conv1 [B,28,28,1]x(5,5,1,20) is 2.36 GFLOP at ~1.3 ms = ~1.8 TF/s:
  the C_in=1 / K=25 contraction uses ~3% of an MXU tile by shape, and
  the op is memory-bound on its [B,24,24,20] output + implicit
  patches. conv2's marginal cost (~0.23 ms for 13.1 GFLOP = ~57 TF/s,
  ~29% MFU) shows the MXU-shaped ops in the same net run fine.
- levers measured IN-SCAN (all negative or marginal):
    explicit slice-im2col + matmul   2.7 ms   (2.1x WORSE — patch
                                              materialization)
    C_out padded 20->128             1.7 ms   (1.3x worse)
    space-to-depth probe 14x14x4 3x3 1.4 ms   (no gain)
    f32 instead of bf16              1.14 ms  (~10% better; not
                                              adopted — doubles
                                              activation memory and
                                              the config pins bf16)
- conclusion (BASELINE.md round-5 notes): 12-13% MFU is the honest
  ceiling for THIS topology at B=4096 — the model's FLOPs sit in
  conv2/dense (which run near 30% MFU) but the wall clock sits in
  conv1+pools whose arithmetic intensity is intrinsically tiny.
  Config-bound, not framework-bound — the d512-transformer-style
  close (r3) applied to BASELINE config 1.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

B, N = 4096, 100


def scan_time(f, x, n=N):
    def run(c):
        def body(c, _):
            s = jnp.sum(f(c).astype(jnp.float32)) * jnp.bfloat16(1e-12)
            return c + s.astype(c.dtype), ()
        c, _ = lax.scan(body, c, None, length=n)
        return c
    g = jax.jit(run)
    o = g(x)
    jax.block_until_ready(o)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        o = g(x)
        float(jnp.sum(o.astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e3


def main() -> None:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((B, 28, 28, 1), np.float32), jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    w1 = jax.random.normal(key, (5, 5, 1, 20), jnp.bfloat16) * 0.1
    w2 = jax.random.normal(key, (5, 5, 20, 50), jnp.bfloat16) * 0.1
    wd = jax.random.normal(key, (800, 500), jnp.bfloat16) * 0.1
    wo = jax.random.normal(key, (500, 10), jnp.bfloat16) * 0.1
    dn = lax.conv_dimension_numbers((B, 28, 28, 1), (5, 5, 1, 20),
                                    ("NHWC", "HWIO", "NHWC"))
    dn2 = lax.conv_dimension_numbers((B, 12, 12, 20), (5, 5, 20, 50),
                                     ("NHWC", "HWIO", "NHWC"))

    def stage(upto, c):
        h = lax.conv_general_dilated(c, w1, (1, 1), "VALID",
                                     dimension_numbers=dn)
        if upto >= 2:
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        if upto >= 3:
            h = lax.conv_general_dilated(h, w2, (1, 1), "VALID",
                                         dimension_numbers=dn2)
        if upto >= 4:
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        if upto >= 5:
            h = jnp.maximum(jnp.matmul(h.reshape(B, -1), wd), 0)
        if upto >= 6:
            h = jnp.matmul(h, wo)
        return h

    names = {1: "conv1", 2: "+pool1", 3: "+conv2", 4: "+pool2",
             5: "+dense", 6: "+out"}
    prev = 0.0
    for k in range(1, 7):
        t = scan_time(lambda c, k=k: stage(k, c), x)
        print(f"fwd {names[k]:<7} cum {t:.4f} ms  delta {t - prev:.4f}")
        prev = t

    # levers
    wflat = w1.reshape(25, 20)

    def conv_slices(c):
        img = c[..., 0]
        cols = [img[:, di:di + 24, dj:dj + 24]
                for di in range(5) for dj in range(5)]
        pat = jnp.stack(cols, axis=-1)
        return jnp.matmul(pat.reshape(-1, 25), wflat).reshape(
            B, 24, 24, 20)

    w1f = w1.astype(jnp.float32)

    def conv_f32(c):
        return lax.conv_general_dilated(c.astype(jnp.float32), w1f,
                                        (1, 1), "VALID",
                                        dimension_numbers=dn)

    w1p = jnp.pad(w1, ((0, 0), (0, 0), (0, 0), (0, 108)))

    def conv_pad(c):
        return lax.conv_general_dilated(c, w1p, (1, 1), "VALID",
                                        dimension_numbers=dn)

    print(f"lever slice-im2col: {scan_time(conv_slices, x):.4f} ms")
    print(f"lever f32:          {scan_time(conv_f32, x):.4f} ms")
    print(f"lever C_out=128:    {scan_time(conv_pad, x):.4f} ms")


if __name__ == "__main__":
    main()
