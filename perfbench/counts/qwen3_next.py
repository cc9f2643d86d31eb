"""Counts of required work that metrics added after `archs/qwen3_next.py` ask
for, kept beside it (`readers/kernel_roofline_counts.py` finds them by the
configuration's `arch`). The sizes are the arch module's `Sizes`."""
from __future__ import annotations

from perfbench.harness.arith import roofline_s


def causal_conv_train_roofline_s(s, rows: int, t: int, device_kind: str,
                                 act_bytes: int = 2) -> float:
    """Least seconds for the Gated DeltaNet layers' causal convolutions of
    one step, SiLU on their outputs. The channels are q, k and v of every
    head. Bytes, each once: the forward reads them and writes the output,
    the backward reads them and the output's gradient and writes theirs; the
    taps (float32) read by both passes, their gradient written. FLOPs: 2 W a
    channel a position forward, 4 W backward. A recomputed forward counts
    nothing."""
    n, w = rows * t, s.linear_conv_kernel_dim
    c = 2 * s.linear_num_key_heads * s.linear_key_head_dim \
        + s.linear_num_value_heads * s.linear_value_head_dim
    nbytes = 5 * n * c * act_bytes + 3 * 4 * w * c
    layers = s.n_periods * (s.full_attention_interval - 1)
    return layers * roofline_s(6.0 * w * c * n, nbytes, device_kind)
