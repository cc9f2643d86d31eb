"""The LFM2 MoE block family (`lfm2_moe`), as the harness sees it: a gated
short convolution (`conv`: `in_proj` to B, C and x, B * x through a
depthwise causal convolution of `conv_L_cache` taps, gated by C, `out_proj`)
in most layers and grouped-query attention with q/k norms and rotary
positions (`full_attention`) in the others, as `layer_types` lists them;
`num_dense_layers` leading layers with a dense SwiGLU, then layers of
sigmoid-routed top-k experts with an expert bias for the choice and no
shared expert; RMSNorm, a head tied to the embedding.

The keys are the published `config.json`'s. `num_experts` is the number of
routed experts HELD here (the chip's share of an expert-parallel
deployment); `router_width` is the published count the router scores;
`router_trained` false freezes the router's matrix (the file's
`departures` say why). `layer_types` lists the layers held, the leading
ones first; the layers after them are whole periods of the shortest
pattern that repeats.

Counts are of required work only: causal attention counts the live half of
the score matrix, the routed experts the rows they expect (`tokens x top_k
x held / router_width`); the convolution's taps and gates, recomputation,
gathers, casts and copies count nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

from perfbench.harness.arith import causal_pairs, roofline_s

TYPES = ("conv", "full_attention")
PROGRAM_KIND = {"conv": "shortconv", "full_attention": "attention"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden_size: int
    num_hidden_layers: int
    num_dense_layers: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    conv_L_cache: int
    layer_types: Tuple[str, ...]
    rope_theta: float
    router_width: int
    experts_held: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    routed_scaling_factor: float
    norm_eps: float
    vocab_size: int
    weights_key: int
    router_trained: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def lead_types(self) -> Tuple[str, ...]:
        return self.layer_types[:self.num_dense_layers]

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest pattern whose repeats are the layers after the
        leading ones."""
        after = self.layer_types[self.num_dense_layers:]
        n = len(after)
        return next(after[:p] for p in range(1, n + 1)
                    if n % p == 0 and after == after[:p] * (n // p))

    @property
    def n_periods(self) -> int:
        return self.n_expert_layers // len(self.period)

    def layers_of(self, kind: str, lead: bool = False) -> int:
        """Expert layers of that type, or with `lead` the leading ones."""
        if lead:
            return self.lead_types.count(kind)
        return self.n_periods * self.period.count(kind)


def sizes(cfg: dict) -> Sizes:
    types = tuple(cfg["layer_types"])
    if len(types) != int(cfg["num_hidden_layers"]) or set(types) - set(TYPES):
        raise SystemExit(f"perfbench: layer_types must list the "
                         f"num_hidden_layers held, each one of {TYPES}")
    if not 0 <= int(cfg["num_dense_layers"]) < len(types):
        raise SystemExit("perfbench: num_hidden_layers must hold the "
                         "leading dense layers and an expert layer")
    if not (bool(cfg["norm_topk_prob"]) and bool(cfg["use_expert_bias"])):
        raise SystemExit("perfbench: this family's router normalises the "
                         "chosen weights and chooses with the expert bias")
    if bool(cfg["conv_bias"]):
        raise SystemExit("perfbench: this family's short convolution has no "
                         "bias")
    return Sizes(
        layer_types=types,
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        router_width=int(cfg["router_width"]),
        experts_held=int(cfg["num_experts"]),
        router_trained=bool(cfg.get("router_trained", True)),
        **{k: float(cfg[k]) for k in ("routed_scaling_factor", "norm_eps")},
        **{k: int(cfg[k]) for k in (
            "hidden_size", "num_hidden_layers", "num_dense_layers",
            "intermediate_size", "num_attention_heads", "num_key_value_heads",
            "conv_L_cache", "num_experts_per_tok", "moe_intermediate_size",
            "vocab_size", "weights_key")})


def rehearsal(cfg: dict) -> dict:
    """The --rehearse dry run's model: CPU-sized, never a measurement."""
    return dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=2, router_width=16, num_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=32,
                vocab_size=512)


def program_config(cfg: dict, s: Sizes, **training):
    """The program's TransformerConfig for a configuration's file."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    fields = dict(
        vocab_size=s.vocab_size, d_model=s.hidden_size,
        n_heads=s.num_attention_heads, n_kv_heads=s.num_key_value_heads,
        head_dim=s.head_dim, n_layers=s.num_hidden_layers,
        layer_types=tuple(PROGRAM_KIND[k] for k in s.period),
        lead_dense_layers=s.num_dense_layers,
        lead_layer_types=tuple(PROGRAM_KIND[k] for k in s.lead_types),
        stack_runs=True, eps=s.norm_eps, rope_theta=s.rope_theta,
        rotary_fraction=1.0, attn_gate=False, qk_norm=True,
        shortconv_width=s.conv_L_cache,
        dense_d_ff=s.intermediate_size, mlp_kind="moe",
        n_experts=s.router_width, experts_held=s.experts_held,
        moe_top_k=s.num_experts_per_tok, moe_d_ff=s.moe_intermediate_size,
        shared_d_ff=0, router_scoring="sigmoid",
        routed_scale=s.routed_scaling_factor, shared_gate=False,
        train_router=s.router_trained, tie_head=True,
        dtype=cfg["activation_dtype"], **training)
    try:
        return TransformerConfig(**fields)
    except TypeError as e:      # a program from before this family
        raise SystemExit(f"perfbench: this tree's TransformerConfig cannot "
                         f"describe {cfg['name']}: {e}") from None


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _conv_matrices(s: Sizes) -> int:
    """in_proj [D, 3 D] and out_proj [D, D]."""
    return 4 * s.hidden_size * s.hidden_size


def _attn_matrices(s: Sizes) -> int:
    """q_proj and out_proj [D, H dh], k_proj and v_proj [D, Hkv dh]."""
    return 2 * s.hidden_size * s.head_dim * (s.num_attention_heads
                                             + s.num_key_value_heads)


def _mixer(s: Sizes, kind: str) -> int:
    """The matrices and the rest: the taps, or the two head norms."""
    if kind == "conv":
        return _conv_matrices(s) + s.conv_L_cache * s.hidden_size
    return _attn_matrices(s) + 2 * s.head_dim


def _matrices(s: Sizes, kind: str) -> int:
    return _conv_matrices(s) if kind == "conv" else _attn_matrices(s)


def _dense_mlp(s: Sizes) -> int:
    return 3 * s.hidden_size * s.intermediate_size


def _expert(s: Sizes) -> int:
    return 3 * s.hidden_size * s.moe_intermediate_size


def held_params(s: Sizes) -> int:
    """Parameters in the program's tree."""
    d = s.hidden_size
    out = s.vocab_size * d + d
    for kind in TYPES:
        out += s.layers_of(kind, lead=True) * (_mixer(s, kind) + 2 * d
                                               + _dense_mlp(s))
        out += s.layers_of(kind) * (_mixer(s, kind) + 2 * d
                                    + d * s.router_width + s.router_width
                                    + s.experts_held * _expert(s))
    return out


def matmul_params(s: Sizes) -> float:
    """Weights a token is multiplied by, forward: the mixers' matrices, the
    leading layers' dense MLP, the router, the routed experts at their
    expectation `top_k x held / router_width` of an expert, the head.
    Embedding rows are looked up; norms, taps and gates are not counted."""
    routed = (s.num_experts_per_tok * s.experts_held / s.router_width
              * _expert(s))
    out = float(s.hidden_size * s.vocab_size)
    for kind in TYPES:
        out += s.layers_of(kind, lead=True) * (_matrices(s, kind)
                                               + _dense_mlp(s))
        out += s.layers_of(kind) * (_matrices(s, kind)
                                    + s.hidden_size * s.router_width + routed)
    return out


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------

def _attn_layers(s: Sizes) -> int:
    return (s.layers_of("full_attention")
            + s.layers_of("full_attention", lead=True))


def _attn_flops(s: Sizes, pairs: int) -> float:
    """QK^T and PV: 2 x 2 x (heads x head_dim) a live pair a layer."""
    return (4.0 * s.num_attention_heads * s.head_dim * pairs
            * _attn_layers(s))


def train_flops_per_step(s: Sizes, rows: int, t: int) -> float:
    """Forward plus backward of one step, no recomputation: three times
    the forward."""
    return 3.0 * (2.0 * matmul_params(s) * rows * t
                  + _attn_flops(s, rows * causal_pairs(t)))


def flash_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                           act_bytes: int = 2) -> float:
    """Least seconds for the attention layers' attention of one step,
    forward and backward. Forward 2 products, backward 4 over the live
    half; forward reads q, k, v and writes o, backward reads q, k, v, o, do
    and writes dq, dk, dv, each once (k and v `Hkv x dh` wide)."""
    flops = 3.0 * _attn_flops(s, rows * causal_pairs(t))
    wide = s.num_attention_heads * s.head_dim
    narrow = s.num_key_value_heads * s.head_dim
    nbytes = ((2 + 4) * wide + (2 + 4) * narrow) * rows * t * act_bytes \
        * _attn_layers(s)
    return roofline_s(flops, nbytes, device_kind)


def moe_live_rows(s: Sizes, n_tokens: int) -> float:
    """Rows the held experts expect of `n_tokens` tokens, a layer."""
    return (n_tokens * s.num_experts_per_tok * s.experts_held
            / s.router_width)


def moe_gmm_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                             act_bytes: int = 2) -> float:
    """Least seconds for the routed experts' three matrices of one step,
    forward and backward, every expert layer, at the expected live rows.
    Bytes: each held weight read once forward and once backward in the
    activation dtype and its gradient written once in float32; a live row's
    input read and output written forward, and its input, its output's
    gradient read and its input's gradient written backward. The count of a
    balanced router, so a roofline only where the held experts' load is the
    balanced one."""
    live = moe_live_rows(s, rows * t)
    weights = s.experts_held * _expert(s) * (2 * act_bytes + 4)
    return s.n_expert_layers * roofline_s(
        3.0 * 2.0 * _expert(s) * live,
        weights + 5.0 * live * s.hidden_size * act_bytes, device_kind)
