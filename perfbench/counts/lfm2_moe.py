"""Counts of required work that metrics added after `archs/lfm2_moe.py` ask
for, kept beside it (`readers/kernel_roofline_counts.py` finds them by the
configuration's `arch`). The sizes are the arch module's `Sizes`."""
from __future__ import annotations

from perfbench.harness.arith import roofline_s


def causal_conv_train_roofline_s(s, rows: int, t: int, device_kind: str,
                                 act_bytes: int = 2) -> float:
    """Least seconds for the gated short convolutions of one step, their two
    gates with them. Bytes, each once: the forward reads B, C and x~ and
    writes the output, the backward reads B, C, x~ and the output's gradient
    and writes the gradients of B, C and x~; the taps (float32) read by both
    passes, their gradient written. FLOPs: 2 W a channel a position forward,
    4 W backward (the gates' products are fewer than one tap's). A
    recomputed forward counts nothing."""
    n, c, w = rows * t, s.hidden_size, s.conv_L_cache
    nbytes = 11 * n * c * act_bytes + 3 * 4 * w * c
    return s.layer_types.count("conv") * roofline_s(6.0 * w * c * n, nbytes,
                                                    device_kind)
