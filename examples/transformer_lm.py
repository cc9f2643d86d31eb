"""Composite-parallel transformer LM training — the net-new capability
layer the reference lacks (SURVEY.md §5.7: its only long-sequence tool
is truncated BPTT; there is no attention, no tensor/pipeline/sequence/
expert parallelism).

Trains a small decoder-only LM on this script's own bytes over a device
mesh combining data, megatron tensor, pipeline (GPipe or 1F1B) and ring-attention
sequence parallelism — one shard_mapped XLA program, collectives over
ICI. It needs dp*tp*pp*sp devices and says so when JAX sees fewer.

Run: python examples/transformer_lm.py [--dp 2 --tp 2 --pp 1 --sp 2]
(on a host with no accelerator: JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8 python ...)
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--schedule", default="gpipe",
                    choices=["gpipe", "1f1b"],
                    help="pipeline microbatch schedule (1f1b: O(S) "
                         "activation store instead of O(M))")
    ap.add_argument("--sp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    args = ap.parse_args()

    from _devices import require_devices
    require_devices(args.dp * args.tp * args.pp * args.sp)
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params)
    from deeplearning4j_tpu.parallel.megatron import (
        init_adam_state, make_parallel_train_step, shard_params)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

    text = open(__file__, "rb").read()
    ids = np.frombuffer(text, np.uint8).astype(np.int32)
    T = args.seq_len
    n_seq = (len(ids) - 1) // T
    x = ids[:n_seq * T].reshape(n_seq, T)
    y = ids[1:n_seq * T + 1].reshape(n_seq, T)

    mesh = make_mesh(MeshSpec(data=args.dp, model=args.tp, pipe=args.pp,
                              seq=args.sp))
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_heads=4,
                            n_layers=4, max_len=T)
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                          mesh)
    opt = init_adam_state(params)
    step = make_parallel_train_step(cfg, mesh, learning_rate=3e-3,
                                    pipeline_schedule=args.schedule)
    if args.pp > 1:
        from deeplearning4j_tpu.parallel.megatron import \
            pipeline_bubble_fraction
        print(f"pipeline schedule {args.schedule}: bubble "
              f"{pipeline_bubble_fraction(args.schedule, args.pp, args.pp):.3f}")

    rng = np.random.default_rng(0)
    for i in range(args.steps):
        idx = rng.integers(0, n_seq, args.batch)
        params, opt, loss = step(params, opt, x[idx], y[idx])
        print(f"step {i:3d}  loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
