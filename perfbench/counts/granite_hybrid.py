"""Counts of required work that metrics added after `archs/granite_hybrid.py`
ask for, kept beside it (`readers/kernel_roofline_counts.py` finds them by
the configuration's `arch`). The sizes are the arch module's `Sizes`."""
from __future__ import annotations

from perfbench.harness.arith import roofline_s


def causal_conv_train_roofline_s(s, rows: int, t: int, device_kind: str,
                                 act_bytes: int = 2) -> float:
    """Least seconds for the Mamba layers' causal convolutions of one step,
    with the bias and SiLU on their outputs. Bytes, each once: the forward
    reads x B C and writes the output, the backward reads x B C and the
    output's gradient and writes x B C's; the taps and the bias (float32)
    read by both passes, their gradients written. FLOPs: 2 W a channel a
    position forward, 4 W backward. A recomputed forward counts nothing."""
    n = rows * t
    c = s.mamba_n_heads * s.mamba_d_head + 2 * s.mamba_n_groups \
        * s.mamba_d_state
    w = s.mamba_d_conv
    nbytes = 5 * n * c * act_bytes + 3 * 4 * (w + 1) * c
    return s.layers_of("mamba") * roofline_s(6.0 * w * c * n, nbytes,
                                             device_kind)
