"""Minimal repro for the flash-attention grid-size compile failure
(VERDICT r3 #7).

The flash-attention kernels cap 2-D superblock grids at
_MAX_2D_GRID_FWD=96 / _MAX_2D_GRID_BWD=32 programs because larger
grids do not compile. Signatures seen at the same boundary:

  * 2026-07-30, an earlier toolchain: the compiler died with exit
    code 1 and no Mosaic/XLA message.
  * 2026-07-31 and, re-run for PR 21 on 2026-09-26, jax 0.9.0 /
    libtpu 0.0.34 on a TPU v5 lite: the (32, 4) grid fails with a
    spurious scoped-vmem STACK OOM:
    `Ran out of memory in memory space vmem while allocating on stack
    ... It should not be possible to run out of scoped vmem` —
    spurious because the per-program VMEM footprint is IDENTICAL under
    the cap (bh-chunking changes only the grid's first extent), and
    the capped (24, 4) chunks of the very same shape compile and run.
    The accounting scales with grid programs.

This script deliberately compiles a (32, 4)-superblock forward —
the smallest observed-failing configuration — with the cap lifted,
and reports whether the boundary still holds. Run it on the chip after
any jax/libtpu bump:

  * "CRASH REPRODUCED" -> the caps are still needed; nothing to do
    (matches_known_signature tells you which signature appeared).
  * "NO CRASH" -> the toolchain moved the boundary; the caps can be
    raised (edit the caps in ops/flash_attention.py; the forward over
    the block's own layout has its own, `_MAX_LANE_GRID_FWD`, measured
    to 256 programs on libtpu 0.0.34).

Chip-only; the failure is a compile error, nothing hangs. Not
collected by pytest (benchmarks/ is outside tests/).

Usage: python benchmarks/grid_crash_repro.py
"""
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main() -> int:
    # the module, not the function that ops/__init__ re-exports as it
    fa = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
    fa._MAX_2D_GRID_FWD = fa._MAX_2D_GRID_BWD = 100000   # repro mode
    fa._MAX_LANE_GRID_FWD = 100000
    if jax.default_backend() != "tpu":
        print(json.dumps({"repro": "grid_crash", "skipped":
                          "needs the real TPU backend"}))
        return 0
    # bh=32, T=8192 -> qsb=2048 -> grid (32, 4) = 128 programs with a
    # real superblock dim: the smallest observed-crashing fwd grid
    bh, t, d = 32, 8192, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (bh, t, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (bh, t, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (bh, t, d), jnp.bfloat16)
    try:
        out, _ = jax.jit(lambda a, b, c: fa._flash_forward(
            a, b, c, d, 0.125, True, 0, 0, False))(q, k, v)
        float(jnp.sum(out.astype(jnp.float32)))
    except Exception as e:
        msg = f"{type(e).__name__}: {e}"
        if "It should not be possible to run out of scoped vmem" in msg:
            sig = "spurious_vmem_stack_oom"
        else:
            sig = "UNKNOWN - inspect; may be a genuine kernel error"
        print(json.dumps({
            "repro": "grid_crash", "result": "CRASH REPRODUCED",
            "matches_known_signature": sig,
            "error": msg[:300]}))
        return 0
    print(json.dumps({
        "repro": "grid_crash", "result": "NO CRASH",
        "note": "toolchain boundary moved - re-sweep and raise the "
                "caps in ops/flash_attention.py"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
