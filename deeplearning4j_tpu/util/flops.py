"""FLOPs accounting and MFU (model-FLOPs utilization) reporting.

The reference measures throughput only in examples/sec
(reference: optimize/listeners/PerformanceListener.java — examples/sec,
batches/sec); it has no FLOPs accounting because eager per-op dispatch
has no single program to account for. Here every training run IS one XLA
program, so the compiler's own cost model gives an un-gameable FLOP
count for exactly the computation executed: MFU = (program FLOPs /
wall-clock) / chip peak. This is the honest cross-round perf metric —
unlike examples/sec it cannot be inflated by shrinking the model, and
unlike vs-an-estimate ratios it needs no reference measurement.

Note XLA counts every executed FLOP, including rematerialized
(jax.checkpoint) recompute — so for remat'd programs this reports
hardware-FLOPs utilization (HFU), an upper bound on the work actually
"in the model". Callers that want textbook MFU for a remat'd model
should pass analytic model FLOPs instead.

CAVEAT (verified on jax 0.9 / TPU v5e): XLA's cost model counts a
`lax.scan` body ONCE, independent of trip count. For scanned multi-step
programs, cost a single-step program and multiply by the step count.
"""
from __future__ import annotations

import jax

# Peak dense matmul throughput per chip, FLOP/s, by jax device_kind
# prefix. bf16 MXU numbers from public TPU specs (v5e: 197 TFLOP/s bf16;
# v4: 275; v5p: 459; v6e "Trillium": 918).
_PEAK_BF16 = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5": 459e12,       # v5p reports "TPU v5"; v5e reports "v5 lite"
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# Peak HBM bandwidth per chip, bytes/s, from the same public specs
# (v5e: 819 GB/s; v4: 1228; v5p: 2765; v6e: 1640) — the denominator of
# the roofline ridge point (observability/profiling.py: a program
# whose arithmetic intensity sits left of peak_flops/peak_bw is
# memory-bound on that chip).
_PEAK_HBM_BPS = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v4": 1228e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}


def _peak_lookup(table: dict, device) -> float | None:
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    # longest-prefix match so "TPU v5 lite" beats "TPU v5"
    best = None
    for k, v in table.items():
        if kind.startswith(k) and (best is None or len(k) > best[0]):
            best = (len(k), v)
    return best[1] if best else None


def chip_peak_flops(device: "jax.Device | None" = None) -> float | None:
    """Peak bf16 FLOP/s for one chip, or None when unknown (CPU etc.)."""
    return _peak_lookup(_PEAK_BF16, device)


def chip_peak_bytes_per_s(device: "jax.Device | None" = None
                          ) -> float | None:
    """Peak HBM bytes/s for one chip, or None when unknown (CPU
    etc.) — the roofline ridge point's denominator."""
    return _peak_lookup(_PEAK_HBM_BPS, device)


def cost_analysis(jitted_fn, *args, **kwargs) -> dict:
    """XLA cost analysis ({'flops': ..., 'bytes accessed': ...}) for the
    program ``jitted_fn(*args)`` would run. Lower+compile only — nothing
    executes, so donated buffers are untouched."""
    compiled = jitted_fn.lower(*args, **kwargs).compile()
    try:
        ca = compiled.cost_analysis()
    except Exception:  # some PJRT plugins raise UNIMPLEMENTED here
        return {}
    return dict(ca) if ca else {}


def program_flops(jitted_fn, *args, **kwargs) -> float | None:
    """Total FLOPs XLA accounts to one execution of the program, or None
    when the backend offers no estimate."""
    flops = cost_analysis(jitted_fn, *args, **kwargs).get("flops")
    return float(flops) if flops and flops > 0 else None


def mfu(flops: float | None, seconds: float,
        device: "jax.Device | None" = None) -> float | None:
    """Fraction of one chip's peak bf16 throughput achieved: (flops /
    seconds) / peak. None when either side is unknown."""
    peak = chip_peak_flops(device)
    if flops is None or peak is None or seconds <= 0:
        return None
    return flops / seconds / peak
