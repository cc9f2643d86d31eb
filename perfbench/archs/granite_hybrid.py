"""The Granite 4.0-H block family, as the harness sees it: a period of
Mamba-2 layers (state-space-dual scan, one group of B and C, a causal
convolution with a bias, the gate before the norm) with one grouped-query
attention layer that has no positions, no norms and no gate; every layer
followed by a dense gated MLP; RMSNorm, four scalar multipliers, a head tied
to the embedding.

The keys are the published `config.json`'s. `layer_types` holds one period;
`num_hidden_layers` is whole periods of it.

Counts are of required work only: causal attention counts the live half of
the score matrix, the state-space layers the recurrence as written (5
operations a state element a position: the decay, the outer product
`d x B^T`'s entry, its sum into the state, `S C`'s product and its sum);
recomputation, chunking, the convolution's taps, norms, casts and copies
count nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

from perfbench.harness.arith import causal_pairs, roofline_s

KINDS = {"mamba": "mamba2", "attention": "attention"}     # the program's


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]        # one period
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    shared_intermediate_size: int
    vocab_size: int
    rms_norm_eps: float
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // len(self.layer_types)

    def layers_of(self, kind: str) -> int:
        return self.n_periods * self.layer_types.count(kind)


def sizes(cfg: dict) -> Sizes:
    period = tuple(cfg["layer_types"])
    if set(period) - set(KINDS) or \
            int(cfg["num_hidden_layers"]) % len(period):
        raise SystemExit("perfbench: layer_types must be one period of "
                         f"{sorted(KINDS)} and num_hidden_layers whole "
                         "periods of it")
    if cfg["mamba_expand"] * cfg["hidden_size"] != \
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise SystemExit("perfbench: mamba_expand x hidden_size must be "
                         "mamba_n_heads x mamba_d_head")
    return Sizes(
        layer_types=period,
        **{k: float(cfg[k]) for k in (
            "rms_norm_eps", "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling")},
        **{k: int(cfg[k]) for k in (
            "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
            "shared_intermediate_size", "vocab_size")})


def rehearsal(cfg: dict) -> dict:
    """The --rehearse dry run's model: CPU-sized, never a measurement."""
    return dict(hidden_size=64, num_hidden_layers=4,
                layer_types=["mamba", "mamba", "attention", "mamba"],
                num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                shared_intermediate_size=128, vocab_size=512)


def program_config(cfg: dict, s: Sizes, **training):
    """The program's TransformerConfig for a configuration's file."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    fields = dict(
        vocab_size=s.vocab_size, d_model=s.hidden_size,
        n_heads=s.num_attention_heads, n_kv_heads=s.num_key_value_heads,
        head_dim=s.head_dim, n_layers=s.num_hidden_layers,
        layer_types=tuple(KINDS[k] for k in s.layer_types), stack_runs=True,
        eps=s.rms_norm_eps, attn_gate=False, qk_norm=False,
        rotary_fraction=0.0, attn_scale=s.attention_multiplier,
        ssm_heads=s.mamba_n_heads, ssm_head_dim=s.mamba_d_head,
        ssm_state=s.mamba_d_state, ssm_groups=s.mamba_n_groups,
        ssm_conv_width=s.mamba_d_conv, mlp_kind="swiglu",
        dense_d_ff=s.shared_intermediate_size,
        embed_scale=s.embedding_multiplier,
        residual_scale=s.residual_multiplier, logits_scale=s.logits_scaling,
        tie_head=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["activation_dtype"], **training)
    try:
        return TransformerConfig(**fields)
    except TypeError as e:      # a program from before this family
        raise SystemExit(f"perfbench: this tree's TransformerConfig cannot "
                         f"describe {cfg['name']}: {e}") from None


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _inner(s: Sizes) -> int:
    return s.mamba_n_heads * s.mamba_d_head


def _xbc(s: Sizes) -> int:
    return _inner(s) + 2 * s.mamba_n_groups * s.mamba_d_state


def _mamba_matrices(s: Sizes) -> int:
    """in_proj (z, x B C, dt) and out_proj."""
    return (s.hidden_size * (_inner(s) + _xbc(s) + s.mamba_n_heads)
            + _inner(s) * s.hidden_size)


def _mamba_mixer(s: Sizes) -> int:
    """The matrices, the convolution's taps and bias, A_log, D and dt_bias,
    the gated norm's gain."""
    return (_mamba_matrices(s) + (s.mamba_d_conv + 1) * _xbc(s)
            + 3 * s.mamba_n_heads + _inner(s))


def _attn_matrices(s: Sizes) -> int:
    wide = s.num_attention_heads * s.head_dim
    narrow = s.num_key_value_heads * s.head_dim
    return s.hidden_size * (2 * wide + 2 * narrow)


def _mlp(s: Sizes) -> int:
    return 3 * s.hidden_size * s.shared_intermediate_size


def held_params(s: Sizes) -> int:
    """Parameters in the program's tree; the head is the embedding."""
    d = s.hidden_size
    return (s.layers_of("mamba") * _mamba_mixer(s)
            + s.layers_of("attention") * _attn_matrices(s)
            + s.num_hidden_layers * (_mlp(s) + 2 * d)
            + s.vocab_size * d + d)


def matmul_params(s: Sizes) -> int:
    """Weights a token is multiplied by, forward: the mixers' matrices, the
    MLPs, the head. Embedding rows are looked up."""
    return (s.layers_of("mamba") * _mamba_matrices(s)
            + s.layers_of("attention") * _attn_matrices(s)
            + s.num_hidden_layers * _mlp(s) + s.hidden_size * s.vocab_size)


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------

RECURRENCE_OPS = 5      # a state element a position: see the docstring


def _recurrence_flops(s: Sizes, n_tokens: int) -> float:
    return (float(RECURRENCE_OPS) * _inner(s) * s.mamba_d_state * n_tokens
            * s.layers_of("mamba"))


def _attn_flops(s: Sizes, pairs: int) -> float:
    """QK^T and PV: 2 x 2 x (heads x head_dim) a live pair a layer."""
    return (4.0 * s.num_attention_heads * s.head_dim * pairs
            * s.layers_of("attention"))


def train_flops_per_step(s: Sizes, rows: int, t: int) -> float:
    """Forward plus backward of one step, no recomputation: three times
    the forward."""
    n = rows * t
    return 3.0 * (2.0 * matmul_params(s) * n
                  + _attn_flops(s, rows * causal_pairs(t))
                  + _recurrence_flops(s, n))


def flash_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                           act_bytes: int = 2) -> float:
    """Least seconds for the attention layers' attention of one step,
    forward and backward. Forward 2 products, backward 4 over the live
    half; forward reads q, k, v and writes o, backward reads q, k, v, o, do
    and writes dq, dk, dv, each once."""
    flops = 3.0 * _attn_flops(s, rows * causal_pairs(t))
    wide = s.num_attention_heads * s.head_dim
    narrow = s.num_key_value_heads * s.head_dim
    nbytes = ((2 + 4) * wide + (2 + 4) * narrow) * rows * t * act_bytes \
        * s.layers_of("attention")
    return roofline_s(flops, nbytes, device_kind)


def ssd_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                         act_bytes: int = 2) -> float:
    """Least seconds for the state-space recurrence of one step, forward and
    backward, every Mamba layer. Bytes, each operand once: forward reads x,
    B, C and the step (float32 a head; the decay is a function of it) and
    writes y; backward reads those and dy and writes dx, dB, dC and the
    step's gradient."""
    n = rows * t
    di, bc = _inner(s), 2 * s.mamba_n_groups * s.mamba_d_state
    act = (2 * di + bc) + (2 * di + bc) + (di + bc)
    nbytes = float(act * act_bytes + 3 * 4 * s.mamba_n_heads) * n \
        * s.layers_of("mamba")
    return roofline_s(3.0 * _recurrence_flops(s, n), nbytes, device_kind)
