"""What a rematerialised layer keeps.

Under `cfg.remat` a layer is a `jax.checkpoint` that keeps its input and
the two results of its attention kernel, the output `o` and the softmax
statistics (`ops/flash_attention.RESIDUAL_NAMES`). The backward pass
computes again everything of the layer that XLA emits (norms, projections,
q, k and v, the MLP) and nothing that the Pallas forward made: every output
of that call is saved, so it is dead code in the recomputation and
`flash_fwd` runs once a layer a step. The cost is the kernel's output, `B x
T x H*Dh` activations a layer (`H*Dh / D` of the input's bytes), and the
statistics, a float32 or two a row and head. A layer without flash
attention (a scan mixer, the jnp fallback) has nothing by those names and
keeps its input alone.

Every site that rematerialises a layer builds its checkpoint here:
`parallel/megatron._stage_fn`, `models/layer_kinds._one_layer` and
`period_forward`, `models/transformer.forward_hidden`.
"""
from __future__ import annotations

from typing import Callable

import jax

from deeplearning4j_tpu.ops.flash_attention import RESIDUAL_NAMES

# `cfg.remat_policy` -> what the `keeps` label of the mark and the counter
# says of it
KEEPS = {"full": "input+attention", "dots": "input+attention+dots"}


def remat_layer(fn: Callable, cfg, *, site: str,
                prevent_cse: bool = True) -> Callable:
    """`fn` (a layer of `(h, p)`) as `cfg.remat` and `cfg.remat_policy`
    say: `fn` itself without remat; with 'full' a checkpoint that keeps
    the input and the attention kernel's results; with 'dots' the matrix
    products' outputs beside them. `prevent_cse` is the site's own (off
    under a `lax.scan` whose body is the layer alone). A policy this
    builds no checkpoint for ('mlp' lives inside `block_forward`, which
    only `forward_hidden` calls with it) is refused with `site`'s name."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy not in KEEPS:
        raise ValueError(
            f"remat_policy {cfg.remat_policy!r} is not implemented by "
            f"{site}: expected one of {sorted(KEEPS)}")
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import mark
    keeps = KEEPS[cfg.remat_policy]
    # trace time: once a site of a traced program, not once a step
    mark("remat.layer", keeps=keeps, site=site)
    default_registry().counter(
        "remat_layers_total", "rematerialised layer bodies traced, by what "
        "the checkpoint keeps", labelnames=("keeps",)).labels(keeps).inc()
    policy = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable, policy)
    return jax.checkpoint(fn, prevent_cse=prevent_cse, policy=policy)
