"""Transformer checkpoint quantization: per-output-channel weight trees.

`quantize_params` turns a float `models/transformer.init_params` tree
into a drop-in quantized one: every large matmul operand — Wq/Wk/Wv/Wo,
the MLP W1/W2 (or MoE We1/We2), the embedding table, and the output
projection — becomes a `QuantizedTensor` with per-output-channel
float32 scales, while everything numerically fragile or tiny stays
float32 (layer norms, biases, the positional table, the MoE router:
routing decisions are argmax-over-logits and a mis-rounded router
flips token→expert assignment, the one discrete decision in the
block).

Axis conventions (see quant/core.py for the scales layout contract):

- 2-D mats ``[in, out]`` and stacked ``[L, in, out]`` /
  ``[L, E, in, out]`` quantize over the INPUT axis (``-2``): one scale
  per output channel, so the dequantized column reproduces that
  channel's dynamic range.
- the embedding ``[V, D]`` quantizes over ``-1``: one scale per token
  ROW (a row is the output of the lookup, so the row is the channel).

`quantize_specs` mirrors the same walk over a PartitionSpec tree so a
quantized tree can be placed on a serving mesh: the value keeps the
float weight's spec; the scale drops any sharding on its size-1
(reduced) axis — sharding a size-1 dim is ill-formed — and keeps the
channel axis's placement, which is exactly what keeps each model-rank's
local dequantization self-contained (its channel shard pairs with its
scale shard; no collective touches scales, ever).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.quant.core import (QuantizedTensor, dequantize,
                                           quantize, resolve_mode)

# weight name -> (rank, quantization axis). Rank rides along so spec
# derivation can normalize short PartitionSpecs without a params tree.
_TOP_RULES: Dict[str, tuple] = {"embed": (2, -1), "Wout": (2, -2)}
_BLOCK_RULES: Dict[str, tuple] = {
    "Wq": (3, -2), "Wk": (3, -2), "Wv": (3, -2), "Wo": (3, -2),
    "W1": (3, -2), "W2": (3, -2),
    "We1": (4, -2), "We2": (4, -2),
}


def quantize_params(params: Dict[str, Any],
                    mode: str = "int8") -> Dict[str, Any]:
    """Quantize a float transformer param tree (weights + embedding;
    norms/biases/pos/router untouched). ``mode`` goes through
    `resolve_mode`, so "fp8" silently lands on int8 where fp8 isn't
    supported. Idempotent-hostile by design: feeding an already
    quantized tree raises (re-quantizing quantized values would
    silently compound error)."""
    m = resolve_mode(mode)
    if m is None:
        raise ValueError("quantize_params needs a mode ('int8'/'fp8')")
    out = dict(params)
    for name, (_, ax) in _TOP_RULES.items():
        if name in out:
            if isinstance(out[name], QuantizedTensor):
                raise ValueError(f"param {name!r} is already quantized")
            out[name] = quantize(out[name], axis=ax, mode=m)
    blocks = dict(params["blocks"])
    for name, (_, ax) in _BLOCK_RULES.items():
        if name in blocks:
            if isinstance(blocks[name], QuantizedTensor):
                raise ValueError(f"param blocks.{name!r} is already "
                                 "quantized")
            blocks[name] = quantize(blocks[name], axis=ax, mode=m)
    out["blocks"] = blocks
    return out


def dequantize_params(params: Dict[str, Any],
                      dtype=jnp.float32) -> Dict[str, Any]:
    """Dense float tree from a (possibly partially) quantized one —
    the accuracy-study inverse of `quantize_params`."""
    return jax.tree_util.tree_map(
        lambda leaf: (dequantize(leaf, dtype)
                      if isinstance(leaf, QuantizedTensor) else leaf),
        params,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))


def _scale_spec(spec: P, rank: int, axis: int) -> P:
    """The scale's PartitionSpec: the value's spec normalized to full
    rank, with the reduced (size-1) axis forced unsharded."""
    entries = list(spec) + [None] * (rank - len(spec))
    entries[axis % rank] = None
    return P(*entries)


def quantize_specs(specs: Dict[str, Any],
                   mode: str = "int8") -> Dict[str, Any]:
    """Mirror `quantize_params` over a PartitionSpec tree: quantized
    weight names become `QuantizedTensor(value_spec, scale_spec)`
    nodes (same treedef as the quantized params, including the mode
    aux), everything else passes through. Feed it
    `parallel.serving.serving_param_specs(cfg)` to get the in_specs /
    placement tree for a quantized serving tree."""
    m = resolve_mode(mode)
    if m is None:
        raise ValueError("quantize_specs needs a mode ('int8'/'fp8')")
    out = dict(specs)
    for name, (rank, ax) in _TOP_RULES.items():
        if name in out:
            out[name] = QuantizedTensor(
                out[name], _scale_spec(out[name], rank, ax), m)
    blocks = dict(specs["blocks"])
    for name, (rank, ax) in _BLOCK_RULES.items():
        if name in blocks:
            blocks[name] = QuantizedTensor(
                blocks[name], _scale_spec(blocks[name], rank, ax), m)
    out["blocks"] = blocks
    return out


def shard_quantized_serving_params(params_q: Dict[str, Any], cfg,
                                   mesh: Mesh,
                                   mode: str = "int8"):
    """Place a quantized tree on a serving mesh: the serving layout's
    specs, run through `quantize_specs`, applied leaf-by-leaf (values
    and scales each get their own NamedSharding)."""
    from deeplearning4j_tpu.parallel.serving import serving_param_specs
    specs_q = quantize_specs(serving_param_specs(cfg), mode=mode)
    return jax.tree_util.tree_map(
        lambda p, sp: jax.device_put(p, NamedSharding(mesh, sp)),
        params_q, specs_q)


def draft_tree(params: Dict[str, Any], draft: str, cfg, mesh: Mesh,
               base_mode: Optional[str] = None):
    """Build the DRAFT param tree for self-speculative decoding
    (serving/engine.py `EngineConfig(draft=)`), from the engine's
    live serving tree. Returns (draft_params, draft_quantized,
    draft_layers):

    - ``"int8"`` (the default drafter) — the int8-quantized weight
      tree: quantize the live float tree on the mesh (scales shard
      with their channels via `shard_quantized_serving_params`). When
      the engine is ALREADY weight-quantized the live tree IS the
      cheap drafter — it is shared, not re-quantized (requantizing
      quantized values would compound error), so draft == target and
      greedy acceptance is 100% by construction.
    - ``"self"`` — the target tree itself (zero extra HBM; acceptance
      is 100% at any temperature — the exactness-test drafter, and
      the honest baseline for measuring pure verify-batching wins).
    - ``"layers:N"`` — early-exit self-drafting: the SAME tree run
      through only its first N blocks + the final norm/output head.
      Shallow layers' K/V are bit-identical to the target's own, so
      draft cache writes cost nothing to correctness; draft step cost
      scales ~N/L.
    """
    draft = str(draft)
    if draft == "self":
        return params, base_mode, 0
    if draft.startswith("layers:"):
        try:
            n = int(draft.split(":", 1)[1])
        except ValueError:
            raise ValueError(f"malformed draft spec {draft!r}: "
                             "expected 'layers:<int>'")
        if not 0 < n <= cfg.n_layers:
            raise ValueError(f"draft layers {n} out of "
                             f"(0, {cfg.n_layers}]")
        return params, base_mode, n
    try:
        mode = resolve_mode(draft)
    except ValueError:
        mode = None
    if mode is None:
        raise ValueError(f"unknown draft spec {draft!r}: expected "
                         "'int8'/'fp8', 'self', or 'layers:N'")
    if base_mode is not None:
        # the engine's weights are already quantized — they ARE the
        # cheap drafter; share the tree
        return params, base_mode, 0
    qp = quantize_params(params, mode=mode)
    return (shard_quantized_serving_params(qp, cfg, mesh, mode=mode),
            mode, 0)


def param_bytes(tree) -> int:
    """At-rest bytes of a param tree (quantized or float): the sum of
    every leaf's nbytes — QuantizedTensor nodes contribute values AND
    scales (they flatten to both). The `serving_param_bytes` gauge's
    backing computation."""
    return int(sum(int(leaf.nbytes)
                   for leaf in jax.tree_util.tree_leaves(tree)
                   if hasattr(leaf, "nbytes")))


def max_logit_divergence(cfg, params_f: Dict[str, Any],
                         params_q: Dict[str, Any], tokens,
                         dtype=None) -> float:
    """max |logits_float - logits_quantized| over a token batch — the
    scalar the accuracy tests report.
    Runs both trees through the SAME `forward` so the only delta is
    the weights' precision."""
    from deeplearning4j_tpu.models.transformer import forward
    toks = jnp.asarray(tokens, jnp.int32)
    lf = forward(cfg, params_f, toks).astype(jnp.float32)
    lq = forward(cfg, params_q, toks).astype(jnp.float32)
    return float(jnp.max(jnp.abs(lf - lq)))
