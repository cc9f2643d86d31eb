"""Quantized inference: int8/fp8 weight-only matmuls + quantized KV.

The continuous-batching engine (PR 4) made serving slot-bound: capacity
is limited by HBM spent on float32 weights at rest and activation-dtype
slot-pool KV caches. On a memory-bound decode path, halving bytes
multiplies tokens/sec — the classic reduced-precision lever (cuDNN,
arxiv 1410.0759). This package is that lever for the flagship LM:

- `quant.core` — `QuantizedTensor` (a pytree of int8/fp8 values +
  per-channel float32 scales), symmetric absmax `quantize` /
  `dequantize`, `fake_quant` for accuracy studies, and
  `quantized_matmul` (dequantize-on-the-fly into the activation
  dtype). The fp8 `e4m3` variant sits behind `fp8_supported()` and
  falls back to int8 on CPU — `resolve_mode` owns that decision.
- `quant.model` — `quantize_params` for transformer checkpoints
  (per-output-channel scales on every W matrix and the embedding;
  norms/biases/positional/router stay float32), spec derivation so a
  quantized tree shards onto a serving mesh, and `param_bytes` for
  HBM accounting.
- `quant.kv` — per-row quantization for the slot-pool KV cache:
  `init_quant_slot_state` allocates int8 caches + per-(layer, slot,
  position, model-rank) float32 scales so the same slot count costs
  ~4x fewer cache bytes.

Integration points: `TransformerConfig.cache_dtype` (bf16 caches with
f32 activations — the non-quantized half-step),
`parallel.serving.make_continuous_{prefill,decode}(kv_mode=...)`,
`serving.InferenceEngine(quantize=..., kv_quantize=...)`, checkpoint
round-trip of QuantizedTensor trees through the manifest
(tests/test_quant.py). Accuracy envelope and layout:
docs/quantization.md.
"""
from deeplearning4j_tpu.quant.core import (  # noqa: F401
    QuantizedTensor, dequantize, fake_quant, fp8_supported, quantize,
    quantized_matmul, resolve_mode)
from deeplearning4j_tpu.quant.model import (  # noqa: F401
    dequantize_params, param_bytes, quantize_params, quantize_specs)
from deeplearning4j_tpu.quant.kv import (  # noqa: F401
    init_quant_slot_state, quantize_rows, slot_pool_bytes)
