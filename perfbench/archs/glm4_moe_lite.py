"""The GLM-4.7-Flash block family (`glm4_moe_lite`), as the harness sees
it: multi-head latent attention in every layer (low-rank latents for the
queries and for keys and values, a rotary key shared by all heads, a value
width of its own), `first_k_dense_replace` leading layers with a dense
SwiGLU, then layers of sigmoid-routed top-k experts (`noaux_tc`, one group)
with an ungated shared expert, and `num_nextn_predict_layers` multi-token-
prediction modules of one expert layer each, whose loss joins the main one;
RMSNorm, rotary positions, an untied head.

The keys are the published `config.json`'s. `n_routed_experts` is the number
of routed experts HELD here (the chip's share of an expert-parallel
deployment); `router_width` is the published count the router scores;
`router_trained` false freezes the router's matrices (the file's
`departures` say why a share trained alone wants that). `num_hidden_layers`
counts the leading layers and the expert layers, not the module.

Counts are of required work only: causal attention counts the live half of
the score matrix (a key `qk_nope + qk_rope` wide, a value `v_head_dim`), the
routed experts the rows they expect (`tokens x top_k x held /
router_width`), the module its T - 1 positions a row; recomputation,
gathers, casts and copies count nothing.
"""
from __future__ import annotations

import dataclasses

from perfbench.harness.arith import causal_pairs, roofline_s


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    n_routed_experts: int          # the router's width
    experts_held: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    routed_scaling_factor: float
    num_nextn_predict_layers: int
    mtp_loss_weight: float
    vocab_size: int
    rms_norm_eps: float
    weights_key: int
    router_trained: bool = True

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def sizes(cfg: dict) -> Sizes:
    if (cfg["topk_method"], int(cfg["n_group"]), int(cfg["topk_group"]),
            bool(cfg["norm_topk_prob"])) != ("noaux_tc", 1, 1, True):
        raise SystemExit("perfbench: this family's router is noaux_tc with "
                         "one group and normalised weights")
    if not 0 <= int(cfg["first_k_dense_replace"]) < \
            int(cfg["num_hidden_layers"]):
        raise SystemExit("perfbench: num_hidden_layers must hold the "
                         "leading dense layers and an expert layer")
    return Sizes(
        n_routed_experts=int(cfg["router_width"]),
        experts_held=int(cfg["n_routed_experts"]),
        router_trained=bool(cfg.get("router_trained", True)),
        **{k: float(cfg[k]) for k in (
            "rope_theta", "routed_scaling_factor", "mtp_loss_weight",
            "rms_norm_eps")},
        **{k: int(cfg[k]) for k in (
            "hidden_size", "num_hidden_layers", "first_k_dense_replace",
            "intermediate_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "moe_intermediate_size",
            "n_shared_experts", "num_nextn_predict_layers", "vocab_size",
            "weights_key")})


def rehearsal(cfg: dict) -> dict:
    """The --rehearse dry run's model: CPU-sized, never a measurement."""
    return dict(hidden_size=64, num_hidden_layers=3, intermediate_size=128,
                num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
                qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
                router_width=16, n_routed_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=32, vocab_size=512)


def program_config(cfg: dict, s: Sizes, **training):
    """The program's TransformerConfig for a configuration's file."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    fields = dict(
        vocab_size=s.vocab_size, d_model=s.hidden_size,
        n_heads=s.num_attention_heads, n_layers=s.num_hidden_layers,
        layer_types=("mla",), eps=s.rms_norm_eps, rope_theta=s.rope_theta,
        q_lora_rank=s.q_lora_rank, kv_lora_rank=s.kv_lora_rank,
        qk_nope_dim=s.qk_nope_head_dim, qk_rope_dim=s.qk_rope_head_dim,
        v_head_dim=s.v_head_dim,
        lead_dense_layers=s.first_k_dense_replace,
        dense_d_ff=s.intermediate_size, mlp_kind="moe",
        n_experts=s.n_routed_experts, experts_held=s.experts_held,
        moe_top_k=s.num_experts_per_tok, moe_d_ff=s.moe_intermediate_size,
        shared_d_ff=s.n_shared_experts * s.moe_intermediate_size,
        router_scoring="sigmoid", routed_scale=s.routed_scaling_factor,
        shared_gate=False, train_router=s.router_trained,
        mtp_layers=s.num_nextn_predict_layers,
        mtp_loss_weight=s.mtp_loss_weight,
        dtype=cfg["activation_dtype"], **training)
    try:
        return TransformerConfig(**fields)
    except TypeError as e:      # a program from before this family
        raise SystemExit(f"perfbench: this tree's TransformerConfig cannot "
                         f"describe {cfg['name']}: {e}") from None


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _mixer_matrices(s: Sizes) -> int:
    """q_a_proj, q_b_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj."""
    d, h = s.hidden_size, s.num_attention_heads
    return (d * s.q_lora_rank + s.q_lora_rank * h * s.qk_head_dim
            + d * (s.kv_lora_rank + s.qk_rope_head_dim)
            + s.kv_lora_rank * h * (s.qk_nope_head_dim + s.v_head_dim)
            + h * s.v_head_dim * d)


def _mixer(s: Sizes) -> int:
    """The matrices and the two latents' norms."""
    return _mixer_matrices(s) + s.q_lora_rank + s.kv_lora_rank


def _dense_mlp(s: Sizes) -> int:
    return 3 * s.hidden_size * s.intermediate_size


def _expert(s: Sizes) -> int:
    return 3 * s.hidden_size * s.moe_intermediate_size


def _moe_outside(s: Sizes) -> int:
    """The router's matrix and the shared expert (the correction bias is
    added, not multiplied)."""
    return (s.hidden_size * s.n_routed_experts
            + s.n_shared_experts * _expert(s))


def _expert_layer(s: Sizes) -> int:
    return (_mixer(s) + 2 * s.hidden_size + _moe_outside(s)
            + s.n_routed_experts + s.experts_held * _expert(s))


def held_params(s: Sizes) -> int:
    """Parameters in the program's tree."""
    d = s.hidden_size
    lead = _mixer(s) + 2 * d + _dense_mlp(s)
    mtp = 3 * d + 2 * d * d + _expert_layer(s)
    return (s.first_k_dense_replace * lead
            + s.n_expert_layers * _expert_layer(s)
            + 2 * s.vocab_size * d + d + s.num_nextn_predict_layers * mtp)


def _expert_layer_met(s: Sizes) -> float:
    """Weights of an expert layer a token is multiplied by: the mixer, the
    router, the shared expert, the routed experts at their expectation
    `top_k x held / router_width` of an expert."""
    routed = (s.num_experts_per_tok * s.experts_held / s.n_routed_experts
              * _expert(s))
    return _mixer_matrices(s) + _moe_outside(s) + routed


def matmul_params(s: Sizes) -> float:
    """Weights a token of the main model is multiplied by, forward: the
    leading layers, the expert layers, the head. Embedding rows are looked
    up; norms are not counted."""
    return (s.first_k_dense_replace * (_mixer_matrices(s) + _dense_mlp(s))
            + s.n_expert_layers * _expert_layer_met(s)
            + s.hidden_size * s.vocab_size)


def mtp_matmul_params(s: Sizes) -> float:
    """The same of a position of the module: `eh_proj`, its expert layer,
    the head once more."""
    d = s.hidden_size
    return s.num_nextn_predict_layers * (2 * d * d + _expert_layer_met(s)
                             + d * s.vocab_size)


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------

def _attn_pairs(s: Sizes, rows: int, t: int) -> int:
    """Live (query, key) pairs of every attention layer of a step: the
    main model's over T positions, the module's over T - 1."""
    main = s.num_hidden_layers * causal_pairs(t)
    return rows * (main + s.num_nextn_predict_layers * causal_pairs(t - 1))


def _attn_flops(s: Sizes, pairs: int) -> float:
    """QK^T over the key's width and PV over the value's: 2 x heads x
    (qk + v) a live pair."""
    return 2.0 * s.num_attention_heads * (s.qk_head_dim + s.v_head_dim) \
        * pairs


def train_flops_per_step(s: Sizes, rows: int, t: int) -> float:
    """Forward plus backward of one step, no recomputation: three times
    the forward."""
    return 3.0 * (2.0 * matmul_params(s) * rows * t
                  + 2.0 * mtp_matmul_params(s) * rows * (t - 1)
                  + _attn_flops(s, _attn_pairs(s, rows, t)))


def flash_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                           act_bytes: int = 2) -> float:
    """Least seconds for every layer's attention of one step, forward and
    backward, over K and V as the kernels are given them (every head's,
    formed from the latent). Forward 2 products, backward 4 over the live
    half. Bytes, each once: forward reads q and k (`heads x qk` wide) and v
    and writes o (`heads x v` wide); backward reads q, k, v, o, do and
    writes dq, dk, dv."""
    flops = 3.0 * _attn_flops(s, _attn_pairs(s, rows, t))
    wide = s.num_attention_heads * (s.qk_head_dim + s.v_head_dim)
    positions = rows * (s.num_hidden_layers * t
                        + s.num_nextn_predict_layers * (t - 1))
    nbytes = (2 + 4) * wide * positions * act_bytes
    return roofline_s(flops, nbytes, device_kind)


def moe_live_rows(s: Sizes, n_tokens: int) -> float:
    """Rows the held experts expect of `n_tokens` tokens, a layer."""
    return (n_tokens * s.num_experts_per_tok * s.experts_held
            / s.n_routed_experts)


def moe_gmm_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                             act_bytes: int = 2) -> float:
    """Least seconds for the routed experts' three matrices of one step,
    forward and backward, every expert layer and the module's, at the
    expected live rows. Bytes: each held weight read once forward and once
    backward in the activation dtype and its gradient written once in
    float32; a live row's input read and output written forward, and its
    input, its output's gradient read and its input's gradient written
    backward.

    The count of a balanced router, so a roofline only where the held
    experts' load is the balanced one. In `glm47flash-train-ep8share-1chip`
    it is not: trained at Adam 3e-4 the routing collapses, the held experts
    get 0.39 of a balanced share by step 70, the kernels skip the tiles
    that are not live, and `moe_gmm_roofline.train` read 111% (PERF.md,
    Findings, PR 36). The cell is therefore not on that metric's list; a
    reader that knows the rows the step really routed would call `layer`
    with them (PERF.md, Open questions, From PR 36 d)."""
    weights = s.experts_held * _expert(s) * (2 * act_bytes + 4)

    def layer(n_tokens: int) -> float:
        live = moe_live_rows(s, n_tokens)
        return roofline_s(3.0 * 2.0 * _expert(s) * live,
                          weights + 5.0 * live * s.hidden_size * act_bytes,
                          device_kind)
    return (s.n_expert_layers * layer(rows * t)
            + s.num_nextn_predict_layers * layer(rows * (t - 1)))
