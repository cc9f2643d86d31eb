"""Decode-kernel shape sweep: isolate where the per-call ~1ms goes
(per-program overhead vs lane-padded VPU work vs DMA) by timing the
kernel across (bb, bs) grid shapes and positions. Methodology as
flagship.py (scanned multi-call programs, forced host read)."""
import json
import os
import time

import jax
import jax.numpy as jnp


def timed_scan(fn, q, k, v, n=128, reps=3):
    """fn(q, k, v, i) -> out; operands are jit ARGUMENTS (closing over
    them would embed 128MB of constants in the compiled program)."""
    def run(q, k, v):
        def body(c, i):
            return c + fn(q, k, v, i).astype(jnp.float32).sum(), ()
        c, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32),
                            jnp.arange(n, dtype=jnp.int32))
        return c
    f = jax.jit(run)
    float(jnp.sum(f(q, k, v)))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(jnp.sum(f(q, k, v)))
        best = min(best, time.perf_counter() - t0)
    return best / n * 1e3


def bandwidth_probe():
    """Sustained HBM bandwidth on this chip — the denominator of the
    decode roofline claim. Copy (read+write, donated) and fused-read
    probes; the copy number is the honest streaming capability
    (measured 2026-07-31 on an earlier toolchain: 554 GB/s r+w against
    the nominal v5e 819 GB/s; not re-measured)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (512 * 1024 * 1024,),
                          jnp.bfloat16)                        # 1 GiB
    one = jnp.asarray(1.0001, jnp.bfloat16)

    def run(x):
        def body(y, _):
            return y * one, ()
        y, _ = jax.lax.scan(body, x, jnp.arange(32))
        return y

    f = jax.jit(run, donate_argnums=(0,))
    y = f(x)
    float(jnp.sum(y[:8].astype(jnp.float32)))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        y = f(y)
        float(jnp.sum(y[:8].astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    print(json.dumps({"probe": "hbm_copy_bandwidth",
                      "gb_per_s": round(32 * 2 * y.nbytes / best / 1e9,
                                        1)}), flush=True)


def main():
    from deeplearning4j_tpu.ops import flash_decode as fd

    B, H, Dh, S = 64, 8, 64, 2048
    D = H * Dh
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, Dh), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, D), jnp.bfloat16)

    def call(q, k, v, pos, bs, bb):
        # the production dispatch, with its two sweep overrides (read
        # at trace time; every (bs, bb) traces its own program)
        os.environ["DL4JTPU_DECODE_BS"] = str(bs)
        os.environ["DL4JTPU_DECODE_BLOCK_BYTES"] = str(
            bb * bs * D * k.dtype.itemsize)
        return fd.decode_attention(q, k, v, pos, H)

    # r5 geometry experiment (VERDICT r4 #7): the second row of combos
    # doubles the per-block VMEM footprint to 4MB (more bytes in
    # flight per DMA) and (2048, 2) reads the whole cache prefix in
    # one block per batch-slab — probing whether the ~1.6x gap to the
    # measured copy roofline is DMA-pipelining overhead
    for bs, bb in [(256, 4), (256, 8), (512, 4), (128, 8), (1024, 2),
                   (256, 16), (512, 8), (1024, 4), (2048, 2),
                   (128, 16), (2048, 4)]:
        for pos in (100, 2000):
            try:
                ms = timed_scan(lambda q, k, v, i, bs=bs, bb=bb, pos=pos:
                                call(q, k, v, pos + 0 * i, bs, bb),
                                q, k, v)
                print(json.dumps({"bs": bs, "bb": bb, "pos": pos,
                                  "grid": [B // bb, S // bs],
                                  "ms_per_call": round(ms, 3)}),
                      flush=True)
            except Exception as e:
                print(json.dumps({"bs": bs, "bb": bb, "pos": pos,
                                  "error": f"{type(e).__name__}: "
                                  f"{e}"[:120]}), flush=True)


if __name__ == "__main__":
    import sys
    if "--bandwidth" in sys.argv:
        bandwidth_probe()
    else:
        main()
