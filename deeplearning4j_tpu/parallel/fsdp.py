"""Fully-sharded data parallelism (ZeRO-3/FSDP-style).

NET-NEW vs the reference: its three data-parallel modes all keep a FULL
model replica per worker (ParallelWrapper thread replicas,
`ParallelWrapper.java:603`; Spark executors get the whole params
broadcast, `ParameterAveragingTrainingMaster.java`), so model size is
capped by one device's memory. Here parameters, gradients, AND optimizer
state are sharded over the mesh's 'data' axis — per-device memory for
the model + Adam state drops by the axis size — and XLA's SPMD
partitioner (GSPMD) materializes each layer's weights just-in-time with
`all_gather` in forward/backward and reduces gradients straight into the
shards with `reduce_scatter`. This is the scaling-book recipe verbatim:
pick a mesh, annotate shardings, let the compiler place the collectives
on ICI.

No wrapper classes, no gather/scatter hooks: FSDP is a *sharding policy*
over the same traced train step the other strategies use — the whole
module is the leaf-spec chooser plus a jitted Adam step with sharded
in/out shardings.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params, loss_fn)
from deeplearning4j_tpu.parallel.optim import (AdamState, adam_update_tree,
                                               init_adam_state)


def fsdp_leaf_spec(shape: Tuple[int, ...], axis_size: int,
                   axis_name: str = "data") -> P:
    """Shard the largest axis divisible by the mesh axis; scalars and
    leaves with no divisible axis stay replicated (their memory is
    negligible — norms/biases)."""
    if not shape or axis_size <= 1:
        return P()
    for i in sorted(range(len(shape)), key=lambda j: -shape[j]):
        if shape[i] >= axis_size and shape[i] % axis_size == 0:
            spec: list = [None] * len(shape)
            spec[i] = axis_name
            return P(*spec)
    return P()


def fsdp_shardings(params, mesh: Mesh, axis_name: str = "data"):
    """NamedSharding pytree for a param (or same-shaped opt-state) tree."""
    size = mesh.shape[axis_name]
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, fsdp_leaf_spec(jnp.shape(p), size,
                                                     axis_name)), params)


def shard_params_fsdp(params, mesh: Mesh, axis_name: str = "data"):
    """Place a replicated param tree into its FSDP shards."""
    return jax.device_put(params, fsdp_shardings(params, mesh, axis_name))


def init_fsdp_adam_state(params) -> AdamState:
    """Zeros with the params' sharding — `zeros_like` on placed shards
    keeps the sharding, so the optimizer state is born sharded (the
    ZeRO-1 half of the memory win). Same AdamState as the composite
    step (parallel/optim.py)."""
    return init_adam_state(params)


def zero1_partition(n_params: int, n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous ZeRO-1 shard boundaries over a flattened parameter
    vector: ``n_shards`` half-open ``(lo, hi)`` ranges covering
    ``[0, n_params)``, remainder spread over the FIRST shards (the
    np.array_split convention). Deterministic in its inputs — the
    elastic coordinator's resharding contract (ISSUE-18) is that the
    same ``(n_params, n_shards)`` always yields the same cut points,
    so which workers hold which ranges is a pure function of live
    membership SIZE, never of join order or failure history."""
    if n_params < 0:
        raise ValueError(f"n_params must be >= 0, got {n_params}")
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    base, extra = divmod(int(n_params), int(n_shards))
    bounds: List[Tuple[int, int]] = []
    lo = 0
    for i in range(int(n_shards)):
        hi = lo + base + (1 if i < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def flatten_tree(tree) -> np.ndarray:
    """Flatten a float pytree into ONE contiguous float32 vector in
    canonical (tree_flatten) leaf order — the byte layout the ZeRO-1
    shards slice. Deterministic: dict leaves flatten in sorted-key
    order, so coordinator and every worker agree on offsets."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return np.zeros((0,), dtype=np.float32)
    return np.concatenate(
        [np.asarray(leaf, dtype=np.float32).ravel() for leaf in leaves])


def unflatten_tree(vec: np.ndarray, template):
    """Inverse of `flatten_tree` given a same-structure ``template``
    tree (shapes read from its leaves): split the flat vector back
    into a pytree of float32 numpy arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    vec = np.asarray(vec, dtype=np.float32)
    total = sum(int(np.prod(jnp.shape(leaf))) for leaf in leaves)
    if vec.size != total:
        raise ValueError(f"flat vector has {vec.size} elements; "
                         f"template needs {total}")
    out, off = [], 0
    for leaf in leaves:
        shape = jnp.shape(leaf)
        n = int(np.prod(shape)) if shape else 1
        out.append(vec[off:off + n].reshape(shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def make_fsdp_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                         learning_rate: float = 1e-3,
                         betas: Tuple[float, float] = (0.9, 0.999),
                         eps: float = 1e-8):
    """Jitted Adam train step with params/grads/opt-state sharded over
    'data' and the batch sharded over 'data'. GSPMD inserts the
    all_gathers (weights, just-in-time per layer) and reduce_scatters
    (gradients) — the step body is the plain single-device math."""
    example = jax.eval_shape(lambda k: init_params(cfg, k),
                             jax.random.PRNGKey(0))
    p_shard = fsdp_shardings(example, mesh)
    opt_shard = AdamState(m=p_shard, v=p_shard,
                          count=NamedSharding(mesh, P()))
    batch_shard = NamedSharding(mesh, P("data"))
    b1, b2 = betas

    def step(params, opt: AdamState, tokens, targets):
        # the mesh is named while the loss is traced so that code which
        # cannot be partitioned automatically (the Pallas attention
        # kernel) can see it and shard itself over 'data'
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, tokens, targets))(params)
        count = opt.count + 1
        with jax.named_scope("optimizer"):
            params, m, v = adam_update_tree(
                params, grads, opt.m, opt.v, count.astype(jnp.float32),
                learning_rate=learning_rate, b1=b1, b2=b2, eps=eps)
        return params, AdamState(m, v, count), loss

    return jax.jit(step,
                   in_shardings=(p_shard, opt_shard, batch_shard,
                                 batch_shard),
                   out_shardings=(p_shard, opt_shard,
                                  NamedSharding(mesh, P())),
                   donate_argnums=(0, 1))
