"""Quantized continuous-batching serving — int8 weights + int8 slot KV.

Round-10 subsystem (docs/quantization.md): the engine quantizes the
weight tree ON LOAD (per-output-channel symmetric absmax int8 via
`quant.model.quantize_params`) and runs the slot-pool KV cache as int8
rows + per-row float32 scales (`quant/kv.py`) — ~4x fewer at-rest
bytes on both axes, which on the slot-bound continuous-batching path
means ~4x the concurrent slots per HBM byte. `quantize="fp8"` requests
the e4m3 variant and falls back to int8 off-TPU (`resolve_mode`).

The example serves one burst of mixed-length prompts through a float
engine and an int8/int8 engine over the SAME params and mesh, then
prints both engines' HBM accounting (the `serving_param_bytes` /
`serving_kv_*` pull gauges surfaced via health()) and the served
tokens side by side.

On a TPU slice this uses all chips; elsewhere:
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/quantized_serving.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


import jax
import numpy as np

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving.engine import (EngineConfig,
                                               InferenceEngine)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", type=int, default=2)
    ap.add_argument("--model", type=int, default=2)
    ap.add_argument("--quantize", default="int8",
                    choices=["int8", "fp8"])
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    from _devices import require_devices
    require_devices(args.data * args.model)
    mesh = make_mesh(MeshSpec(data=args.data, model=args.model))
    cfg = TransformerConfig(vocab_size=256, d_model=128, n_heads=8,
                            n_layers=4, max_len=256)
    params = init_params(cfg, jax.random.PRNGKey(0))
    econf = EngineConfig(max_batch_size=4, max_new_tokens=args.new_tokens,
                         decode_chunk=4)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(8, 33))).astype(np.int32)
               for _ in range(args.requests)]

    engines = {
        "float32": InferenceEngine(cfg, mesh, params, econf),
        args.quantize: InferenceEngine(cfg, mesh, params, econf,
                                       quantize=args.quantize,
                                       kv_quantize=args.quantize),
    }
    results = {}
    for name, eng in engines.items():
        hs = [eng.submit(p) for p in prompts]
        eng.run_pending()
        results[name] = [h.result(5.0) for h in hs]
        h = eng.health()
        print(f"[{name:>7}] quantize={h['quantize']} "
              f"kv={h['kv_quantize']}  "
              f"param_bytes={h['param_bytes']:>10,}  "
              f"kv_pool_bytes={h['kv_pool_bytes']:>10,}  "
              f"kv_bytes/slot={h['kv_bytes_per_slot']:>9,}")

    fbytes = engines["float32"].health()
    qbytes = engines[args.quantize].health()
    resident_f = fbytes["param_bytes"] + fbytes["kv_pool_bytes"]
    resident_q = qbytes["param_bytes"] + qbytes["kv_pool_bytes"]
    print(f"resident weight+KV bytes: {resident_f:,} -> {resident_q:,} "
          f"({100 * (1 - resident_q / resident_f):.1f}% smaller)")

    names = list(results)
    match = np.mean([
        float(np.mean(a[p.shape[0]:] == b[p.shape[0]:]))
        for p, a, b in zip(prompts, results[names[0]],
                           results[names[1]])])
    print(f"greedy token agreement ({names[0]} vs {names[1]}): "
          f"{100 * match:.1f}%")
    first = results[names[1]][0]
    print(f"sample continuation (quantized, request 0): "
          f"{first[prompts[0].shape[0]:].tolist()}")


if __name__ == "__main__":
    main()
