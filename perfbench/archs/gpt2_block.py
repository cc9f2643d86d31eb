"""The GPT-2 block family (GPT-2, Cerebras-GPT), as the harness sees it:
LayerNorm, learned positions, full multi-head attention with
`d_head = n_embd / n_head`, a GELU MLP, a separate output head.

Everything the benchmark knows of this family's shape is here: which keys of
a configuration's file are sizes, the program's config for them, the CPU
sizes of `--rehearse`, and the counts of required work. The harness, the
readers and the tools reach it through `Cell.arch()` and name no size.

Counts are of required work only. Causal attention counts the live half of
the score matrix; decode attention reads each live K and V row once;
recomputed (rematerialised) work, gathers, casts and copies count nothing.
So a share of a peak stays a bound after a later PR swaps a kernel or takes
a gather out.
"""
from __future__ import annotations

import dataclasses

from perfbench.harness.arith import causal_pairs, roofline_s


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the arithmetic and the reference need of a configuration."""
    n_layer: int
    n_embd: int
    n_head: int
    n_inner: int
    vocab_size: int
    n_positions: int
    eps: float = 1e-5

    @property
    def d_head(self) -> int:
        return self.n_embd // self.n_head


def sizes(cfg: dict) -> Sizes:
    return Sizes(n_layer=int(cfg["n_layer"]), n_embd=int(cfg["n_embd"]),
                 n_head=int(cfg["n_head"]), n_inner=int(cfg["n_inner"]),
                 vocab_size=int(cfg["vocab_size"]),
                 n_positions=int(cfg["n_positions"]),
                 eps=float(cfg["layer_norm_epsilon"]))


def rehearsal(cfg: dict) -> dict:
    """The --rehearse dry run's model: CPU-sized, never a measurement."""
    return dict(n_layer=2, n_embd=128, n_head=4, n_inner=512,
                vocab_size=512, n_positions=256)


def program_config(cfg: dict, s: Sizes, **training):
    """The program's TransformerConfig for a configuration's file."""
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=s.vocab_size, d_model=s.n_embd, n_heads=s.n_head,
        n_layers=s.n_layer, max_len=s.n_positions,
        mlp_ratio=s.n_inner // s.n_embd, eps=s.eps,
        dtype=cfg["activation_dtype"], **training)


def matmul_params(s: Sizes) -> int:
    """Parameters that a token is multiplied by: q, k, v, o, the two MLP
    matrices of every layer, and the output head. Embedding and position
    rows are looked up, not multiplied; biases and norms are not counted."""
    per_layer = 4 * s.n_embd * s.n_embd + 2 * s.n_embd * s.n_inner
    return s.n_layer * per_layer + s.n_embd * s.vocab_size


def held_params(s: Sizes) -> int:
    """Parameters in the program's tree (separate head, MLP biases only)."""
    d, f = s.n_embd, s.n_inner
    per_layer = 4 * d * d + 2 * d * f + f + d + 4 * d
    return (s.n_layer * per_layer + s.vocab_size * d + s.n_positions * d
            + 2 * d + d * s.vocab_size)


def forward_flops(s: Sizes, n_tokens: int, attended: int) -> float:
    """Forward pass over `n_tokens` tokens that between them attend
    `attended` (query, key) pairs per layer per head-dim: 2 FLOPs a weight a
    token, plus QK^T and PV at 2 * 2 * d_model a live pair a layer."""
    return (2.0 * matmul_params(s) * n_tokens
            + 4.0 * s.n_embd * s.n_layer * attended)


def train_flops_per_step(s: Sizes, rows: int, t: int) -> float:
    """Forward plus backward of one step, no recomputation: three times the
    forward's matrix products (the backward makes two for each), the
    attention's included."""
    return 3.0 * forward_flops(s, rows * t, rows * causal_pairs(t))


def flash_train_roofline_s(s: Sizes, rows: int, t: int, device_kind: str,
                           act_bytes: int = 2) -> float:
    """Least seconds the chip needs for the attention of one step, forward
    and backward, all layers. Forward 2 products, backward 4 (dV, dP, dQ,
    dK) over the live half; bytes are q, k, v, o read or written once
    forward, and q, k, v, o, do read and dq, dk, dv written once backward."""
    pairs = rows * causal_pairs(t) * s.n_layer
    flops = 6 * 2.0 * s.n_embd * pairs
    nbytes = (4 + 8) * rows * t * s.n_embd * act_bytes * s.n_layer
    return roofline_s(flops, nbytes, device_kind)


def decode_attn_roofline_s(s: Sizes, live_rows: int, device_kind: str,
                           cache_bytes: int = 2) -> float:
    """Least seconds for decode attention that reads `live_rows` cached
    positions in all (summed over slots and steps), every layer: each live K
    and V row once (bytes), and 2 products over it (FLOPs)."""
    nbytes = 2.0 * live_rows * s.n_embd * cache_bytes * s.n_layer
    flops = 4.0 * live_rows * s.n_embd * s.n_layer
    return roofline_s(flops, nbytes, device_kind)
