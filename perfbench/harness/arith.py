"""The yardstick's arithmetic that belongs to no family of model: chip peaks,
what a roofline is, and the live pairs of causal attention.

What a family's block needs of operations and bytes is counted in its own
module, `perfbench/archs/<arch>.py`, which a configuration's file names
(`harness/cells.py`); those counts are of required work only, so a share of
a peak stays a bound after a later PR swaps a kernel.

Copied in spirit from `deeplearning4j_tpu/util/flops.py` (peaks) and
`observability/profiling.roofline`; the originals are listed in PERF.md for
a later PR to delete.
"""
from __future__ import annotations

# Published per-chip peaks. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB). Keyed by jax's `device_kind`,
# matched exactly: a device that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"perfbench: no published peak for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}. Add it to arith.PEAKS with its source."
        ) from None


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    """Least seconds the chip needs for that much required work: the larger
    of FLOPs over peak and bytes over peak."""
    pk = peaks(device_kind)
    return max(flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"])


def causal_pairs(t: int) -> int:
    """Live (query, key) pairs of one causal sequence of length t."""
    return t * (t + 1) // 2


def span_pairs(start: int, n: int) -> int:
    """Live pairs of n consecutive positions that follow `start` cached
    ones: position start + i attends start + i + 1 keys."""
    return n * start + n * (n + 1) // 2
