"""The Qwen3-Next block family, as the harness sees it: a period of Gated
DeltaNet layers closed by one gated grouped-query attention layer (q/k
norms, rotary positions on part of the head, a sigmoid output gate), every
layer followed by a top-k mixture of experts with a gated shared expert;
RMSNorm `(1 + w)`, no learned positions, an untied head.

The keys are the published `config.json`'s. `num_experts` is the number of
routed experts HELD here (the chip's share of an expert-parallel
deployment); `router_width` is the published count the router scores;
`router_trained` false freezes the router's matrices (the file's
`departures` say why a share trained alone wants that).

Counts are of required work only: causal attention counts the live half of
the score matrix, the routed experts the rows they expect
(`tokens x top_k x held / router_width`), the delta rule the recurrence as
written (7 operations a state element a position: decay, S^T k, the rank-1
update, S^T q); recomputation, chunking, gathers, casts and copies count
nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

from perfbench.harness.arith import causal_pairs, roofline_s


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden_size: int
    num_hidden_layers: int
    full_attention_interval: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    num_experts: int               # the router's width
    experts_held: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    vocab_size: int
    rms_norm_eps: float
    weights_key: int
    router_trained: bool = True

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """One period: deltanet layers closed by a full-attention one."""
        return ("deltanet",) * (self.full_attention_interval - 1) + ("full",)

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // self.full_attention_interval


def sizes(cfg: dict) -> Sizes:
    if int(cfg["num_hidden_layers"]) % int(cfg["full_attention_interval"]):
        raise SystemExit("perfbench: num_hidden_layers must be whole "
                         "periods of full_attention_interval")
    return Sizes(
        num_experts=int(cfg["router_width"]),
        experts_held=int(cfg["num_experts"]),
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        router_trained=bool(cfg.get("router_trained", True)),
        **{k: int(cfg[k]) for k in (
            "hidden_size", "num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "num_experts_per_tok",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "vocab_size", "weights_key")})


def rehearsal(cfg: dict) -> dict:
    """The --rehearse dry run's model: CPU-sized, never a measurement."""
    return dict(hidden_size=64, num_hidden_layers=2,
                full_attention_interval=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=16,
                linear_value_head_dim=16, router_width=16, num_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=32,
                shared_expert_intermediate_size=32, vocab_size=512)


def program_config(cfg: dict, s: Sizes, **training):
    """The program's TransformerConfig for a configuration's file."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=s.vocab_size, d_model=s.hidden_size,
        n_heads=s.num_attention_heads, n_kv_heads=s.num_key_value_heads,
        head_dim=s.head_dim, n_layers=s.num_hidden_layers,
        layer_types=s.layer_types, eps=s.rms_norm_eps,
        rotary_fraction=s.partial_rotary_factor, rope_theta=s.rope_theta,
        gdn_key_heads=s.linear_num_key_heads,
        gdn_value_heads=s.linear_num_value_heads,
        gdn_key_dim=s.linear_key_head_dim,
        gdn_value_dim=s.linear_value_head_dim,
        gdn_conv_width=s.linear_conv_kernel_dim,
        n_experts=s.num_experts, experts_held=s.experts_held,
        moe_top_k=s.num_experts_per_tok, moe_d_ff=s.moe_intermediate_size,
        shared_d_ff=s.shared_expert_intermediate_size,
        train_router=s.router_trained,
        dtype=cfg["activation_dtype"], **training)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _moe_outside(s: Sizes) -> int:
    d = s.hidden_size
    return (d * s.num_experts + 3 * d * s.shared_expert_intermediate_size
            + d)


def _expert(s: Sizes) -> int:
    return 3 * s.hidden_size * s.moe_intermediate_size


def _gdn_dims(s: Sizes):
    kd = s.linear_num_key_heads * s.linear_key_head_dim
    vd = s.linear_num_value_heads * s.linear_value_head_dim
    return kd, vd


def _gdn_matrices(s: Sizes) -> int:
    """in_proj_qkvz, in_proj_ba, the convolution's taps, out_proj."""
    kd, vd = _gdn_dims(s)
    d = s.hidden_size
    return (d * (2 * kd + 2 * vd) + d * 2 * s.linear_num_value_heads
            + s.linear_conv_kernel_dim * (2 * kd + vd) + vd * d)


def _attn_matrices(s: Sizes) -> int:
    d, h, hk, dh = (s.hidden_size, s.num_attention_heads,
                    s.num_key_value_heads, s.head_dim)
    return d * 2 * h * dh + 2 * d * hk * dh + h * dh * d


def held_params(s: Sizes) -> int:
    """Parameters in the program's tree."""
    d = s.hidden_size
    gdn = (_gdn_matrices(s) + 2 * s.linear_num_value_heads
           + s.linear_value_head_dim)
    full = _attn_matrices(s) + 2 * s.head_dim
    moe = _moe_outside(s) + s.experts_held * _expert(s)
    n_full = s.n_periods
    n_gdn = s.num_hidden_layers - n_full
    return (n_gdn * gdn + n_full * full
            + s.num_hidden_layers * (moe + 2 * d)
            + 2 * s.vocab_size * d + d)


def matmul_params(s: Sizes) -> float:
    """Weights a token is multiplied by, forward: the mixers' matrices, the
    router, the shared expert and its gate, the routed experts at their
    expectation `top_k x held / router_width` of an expert a token, the
    head. Embedding rows are looked up; norms are not counted."""
    n_full = s.n_periods
    n_gdn = s.num_hidden_layers - n_full
    routed = (s.num_experts_per_tok * s.experts_held / s.num_experts
              * _expert(s))
    return (n_gdn * _gdn_matrices(s) + n_full * _attn_matrices(s)
            + s.num_hidden_layers * (_moe_outside(s) + routed)
            + s.hidden_size * s.vocab_size)


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------

DELTA_RULE_OPS = 7      # a state element a position: see the docstring


def _delta_rule_flops(s: Sizes, n_tokens: int) -> float:
    n_gdn = s.num_hidden_layers - s.n_periods
    return (float(DELTA_RULE_OPS) * s.linear_key_head_dim
            * s.linear_value_head_dim * s.linear_num_value_heads
            * n_tokens * n_gdn)


def _attn_flops(s: Sizes, pairs: int) -> float:
    """QK^T and PV: 2 x 2 x (heads x head_dim) a live pair a full layer."""
    return 4.0 * s.num_attention_heads * s.head_dim * pairs * s.n_periods


def train_flops_per_step(s: Sizes, rows: int, t: int) -> float:
    """Forward plus backward of one step, no recomputation: three times
    the forward."""
    n = rows * t
    return 3.0 * (2.0 * matmul_params(s) * n
                  + _attn_flops(s, rows * causal_pairs(t))
                  + _delta_rule_flops(s, n))


def flash_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                           act_bytes: int = 2) -> float:
    """Least seconds for the full layers' attention of one step, forward
    and backward. Forward 2 products, backward 4 over the live half; q and o
    are `heads x head_dim` wide, k and v `kv heads x head_dim`: forward
    reads q, k, v and writes o, backward reads q, k, v, o, do and writes dq,
    dk, dv, each once."""
    flops = 3.0 * _attn_flops(s, rows * causal_pairs(t))
    wide = s.num_attention_heads * s.head_dim
    narrow = s.num_key_value_heads * s.head_dim
    nbytes = ((2 + 4) * wide + (2 + 4) * narrow) * rows * t * act_bytes \
        * s.n_periods
    return roofline_s(flops, nbytes, device_kind)


def gdn_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                         act_bytes: int = 2) -> float:
    """Least seconds for the delta rule of one step, forward and backward,
    every DeltaNet layer. Bytes: forward reads q, k (key heads wide), v, the
    decay and beta (float32 a value head) and writes o; backward reads q, k,
    v, do, decay, beta and writes dq, dk, dv, d decay, d beta."""
    n = rows * t
    kd, vd = _gdn_dims(s)
    n_gdn = s.num_hidden_layers - s.n_periods
    act = (2 * kd + 2 * vd) + (2 * kd + 2 * vd) + (2 * kd + vd)
    gates = (2 + 4) * s.linear_num_value_heads * 4
    nbytes = float(act * act_bytes + gates) * n * n_gdn
    return roofline_s(3.0 * _delta_rule_flops(s, n), nbytes, device_kind)


def moe_live_rows(s: Sizes, n_tokens: int) -> float:
    """Rows the held experts expect of `n_tokens` tokens, a layer."""
    return n_tokens * s.num_experts_per_tok * s.experts_held / s.num_experts


def moe_gmm_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                             act_bytes: int = 2) -> float:
    """Least seconds for the routed experts' three matrices of one step,
    forward and backward, every layer, at the expected live rows. Bytes:
    each held weight read once forward and once backward in the activation
    dtype and its gradient written once in float32; a live row's input read
    and output written forward, and its input, its output's gradient read
    and its input's gradient written backward."""
    live = moe_live_rows(s, rows * t)
    flops = 3.0 * 2.0 * _expert(s) * live
    weights = s.experts_held * _expert(s) * (2 * act_bytes + 4)
    nbytes = weights + 5.0 * live * s.hidden_size * act_bytes
    return s.num_hidden_layers * roofline_s(flops, nbytes, device_kind)
