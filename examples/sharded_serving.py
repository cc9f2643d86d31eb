"""Tensor+data-parallel generation — sharded serving of the flagship LM.

Net-new vs the reference, whose serving story is single-process
`MultiLayerNetwork.output`/`rnnTimeStep`: here autoregressive KV-cache
decode runs SPMD over a (data x model) mesh — megatron-sharded
heads/MLP, per-device cache shards, one psum per step
(parallel/serving.py). Greedy parallel decode reproduces the
single-chip `models/transformer.generate` token-for-token; sampled
decode carries the full single-chip surface (temperature / top-k /
nucleus) and matches token-for-token on TP-only meshes (r5).

On a TPU slice this uses all chips; elsewhere:
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  python examples/sharded_serving.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.serving import (make_parallel_generate,
                                                 shard_serving_params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", type=int, default=2)
    ap.add_argument("--model", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    args = ap.parse_args()

    from _devices import require_devices
    require_devices(args.data * args.model)
    mesh = make_mesh(MeshSpec(data=args.data, model=args.model))
    cfg = TransformerConfig(vocab_size=256, d_model=128, n_heads=8,
                            n_layers=4, max_len=256)
    params = shard_serving_params(
        init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh)
    pgen = make_parallel_generate(cfg, mesh,
                                  max_new_tokens=args.new_tokens,
                                  top_k=args.top_k, top_p=args.top_p,
                                  temperature=args.temperature)
    prompt = jnp.tile(jnp.arange(16, dtype=jnp.int32)[None],
                      (2 * args.data, 1))
    out = pgen(params, prompt, jax.random.PRNGKey(7))
    print(f"mesh data={args.data} model={args.model}; generated "
          f"{out.shape[0]}x{out.shape[1]} tokens")
    print("first row:", list(map(int, out[0])))


if __name__ == "__main__":
    main()
