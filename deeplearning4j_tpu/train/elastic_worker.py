"""Elastic training worker: one ZeRO-1 shard owner behind a JSON pipe.

`train/elastic.py`'s `ElasticCoordinator` spawns this module
(``python -m deeplearning4j_tpu.train.elastic_worker``) to put a REAL
process boundary under the elastic membership scenarios — the training
analog of `serving/fleet_worker.py`. Protocol:

- stdin, line 1: the worker spec — ``{"cfg": {TransformerConfig
  kwargs}, "worker_id", "seq_len", "microbatch_size", "data_seed",
  "learning_rate", "b1", "b2", "eps"}``. Batches are re-derived from
  the deterministic data cursor (`elastic.data_batch`), so only the
  param vector ever crosses the pipe.
- stdout, line 1: ``{"ev": "hello", "pid": ..., "worker": ...,
  "platform": "cpu"}``.
- stdin thereafter, one JSON command per line (``epoch`` echoed back
  verbatim on every response so the coordinator can drop stale-epoch
  answers after a resize):

  - ``grads {step, mbs, params}``: compute this step's assigned
    microbatch gradients from the broadcast flat params (base64
    float32) -> ``{"ev": "grads", step, mbs, g: [b64...],
    loss: [float...]}`` in microbatch order.
  - ``adopt_shard {lo, hi, p, m, v}``: become the owner of shard
    ``[lo, hi)`` -> ``{"ev": "adopted", lo, hi, state_bytes}`` —
    state_bytes is the 3×float32 shard footprint the 1/N updater-
    memory assertion measures.
  - ``export_shard``: ship the shard back for a resize gather /
    checkpoint -> ``{"ev": "shard", lo, hi, p, m, v}``.
  - ``update {step, t, grad}``: one Adam step on the owned shard
    (`elastic.apply_adam_slice` — elementwise, so slice-wise is
    bit-identical to full-vector) -> ``{"ev": "updated", step, lo,
    hi, p}``. Updates apply STRICTLY in arrival order: a loose-sync
    straggler's queued backlog replays the exact sequential chain.
  - ``slow {seconds}``: injected per-command stall before every
    grads/update (the `ElasticFaultInjector.slow_at` knob; 0 clears)
    -> ``{"ev": "slowed", seconds}``.
  - ``ping`` -> ``{"ev": "pong", state_bytes}`` / ``stop`` -> bye.

A SIGKILL at any point leaves the coordinator holding the last
published checkpoint, which is exactly what the resize barrier
reshards from.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _force_cpu() -> None:
    """An elastic worker runs on the CPU: a chip belongs to one
    process, and the coordinator's process holds it. The hello line
    says so (``platform``); a number measured through
    `ElasticCoordinator` is a CPU number."""
    import jax
    jax.config.update("jax_platforms", "cpu")


def main() -> int:
    _force_cpu()
    spec = json.loads(sys.stdin.readline())

    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.train.elastic import (apply_adam_slice,
                                                  data_batch, dec_array,
                                                  enc_array, make_grad_fn,
                                                  param_template,
                                                  unflatten_tree,
                                                  flatten_tree)

    cfg = TransformerConfig(**spec["cfg"])
    wid = int(spec.get("worker_id", 0))
    seq_len = int(spec["seq_len"])
    mb_size = int(spec["microbatch_size"])
    data_seed = int(spec.get("data_seed", 0))
    hyper = {"learning_rate": float(spec.get("learning_rate", 1e-3)),
             "b1": float(spec.get("b1", 0.9)),
             "b2": float(spec.get("b2", 0.999)),
             "eps": float(spec.get("eps", 1e-8))}
    vg = make_grad_fn(cfg)
    template = param_template(cfg)
    # warm up BEFORE hello: the first vg call compiles (seconds); the
    # coordinator's startup timeout absorbs it, its step barrier must
    # not (a compiling worker would look like a straggler at step 0)
    import jax
    import numpy as np
    _zeros = np.zeros(sum(int(np.prod(l.shape)) for l in
                          jax.tree_util.tree_leaves(template)),
                      dtype=np.float32)
    _tok, _tgt = data_batch(cfg.vocab_size, seq_len, mb_size, 0, 0,
                            data_seed)
    vg(unflatten_tree(_zeros, template), _tok, _tgt)[0].block_until_ready()

    def emit(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    emit({"ev": "hello", "pid": os.getpid(), "worker": wid,
          "platform": jax.default_backend()})

    shard = None     # {"lo", "hi", "p", "m", "v"} — this worker's
    #                  authoritative slice of params + Adam moments
    slow_s = 0.0

    def state_bytes() -> int:
        if shard is None:
            return 0
        return int(shard["p"].nbytes + shard["m"].nbytes
                   + shard["v"].nbytes)

    for line in sys.stdin:
        try:
            cmd = json.loads(line)
        except ValueError:
            continue
        op = cmd.get("op")
        epoch = cmd.get("epoch")
        if op in ("grads", "update") and slow_s > 0:
            time.sleep(slow_s)
        if op == "grads":
            step = int(cmd["step"])
            params = unflatten_tree(dec_array(cmd["params"]), template)
            gs, losses = [], []
            for mb in cmd["mbs"]:
                tok, tgt = data_batch(cfg.vocab_size, seq_len, mb_size,
                                      step, int(mb), data_seed)
                loss, gtree = vg(params, tok, tgt)
                gs.append(enc_array(flatten_tree(gtree)))
                losses.append(float(loss))
            emit({"ev": "grads", "epoch": epoch, "step": step,
                  "mbs": list(cmd["mbs"]), "g": gs, "loss": losses})
        elif op == "update":
            if shard is None:
                emit({"ev": "error", "epoch": epoch,
                      "msg": "update before adopt_shard"})
                continue
            step = int(cmd["step"])
            g = dec_array(cmd["grad"])
            shard["p"], shard["m"], shard["v"] = apply_adam_slice(
                shard["p"], g, shard["m"], shard["v"],
                int(cmd["t"]), **hyper)
            emit({"ev": "updated", "epoch": epoch, "step": step,
                  "lo": shard["lo"], "hi": shard["hi"],
                  "p": enc_array(shard["p"])})
        elif op == "adopt_shard":
            shard = {"lo": int(cmd["lo"]), "hi": int(cmd["hi"]),
                     "p": dec_array(cmd["p"]),
                     "m": dec_array(cmd["m"]),
                     "v": dec_array(cmd["v"])}
            # warm the Adam kernels for THIS shard shape inside the
            # resize barrier — the first update must not pay an eager
            # compile against the step deadline (throwaway inputs; the
            # adopted state is untouched)
            z = np.zeros_like(shard["p"])
            apply_adam_slice(z, z, z, z, 1, **hyper)
            emit({"ev": "adopted", "epoch": epoch, "lo": shard["lo"],
                  "hi": shard["hi"], "state_bytes": state_bytes()})
        elif op == "export_shard":
            if shard is None:
                emit({"ev": "error", "epoch": epoch,
                      "msg": "export before adopt_shard"})
                continue
            emit({"ev": "shard", "epoch": epoch, "lo": shard["lo"],
                  "hi": shard["hi"], "p": enc_array(shard["p"]),
                  "m": enc_array(shard["m"]),
                  "v": enc_array(shard["v"])})
        elif op == "slow":
            slow_s = float(cmd.get("seconds", 0.0))
            emit({"ev": "slowed", "epoch": epoch, "seconds": slow_s})
        elif op == "ping":
            emit({"ev": "pong", "epoch": epoch,
                  "state_bytes": state_bytes()})
        elif op == "stop":
            break
    emit({"ev": "bye"})
    return 0


if __name__ == "__main__":
    sys.exit(main())
