"""Pallas grouped matmul for a dropless mixture of experts.

Rows arrive sorted by expert in a buffer whose groups are padded to whole
row tiles (`plan_groups`), so a tile of ``tm`` rows belongs to one expert
and the kernel is a tiled matmul whose weight block is picked by a
scalar-prefetched ``tile_expert[tile]``. The buffer is sized for the worst
case (every routed pair lands here); only the first ``n_live`` tiles hold
rows. Tiles past them cost a grid step and nothing else: their block
indices repeat the last live tile's, so nothing is fetched for them, their
body is skipped, and their rows of the output are never written (whoever
reads the buffer masks by `valid`). A program takes all the columns of
its expert's matrix where they fit a 4 MB block (the cell's 2048 x 1024 and
512 x 2048 do), so the matrix is fetched once an expert: fetched once a
tile it was 4 MB for 1 GFLOP, and the kernel ran at the memory's speed.

`moe_gmm_fwd`: out[tile] = a[tile] @ w[tile_expert[tile]] (also the
gradient of ``a``, with the weights transposed). `moe_gmm_dw`:
dw[e] = sum over e's tiles of a[tile]^T @ dc[tile], accumulated in the
output block, which stays in VMEM while consecutive tiles name the same
expert; every expert has at least one tile, so every block is written.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jax.Array
F32 = jnp.float32
TILE_M = 256


BLOCK_BYTES = 4 * 2 ** 20       # an expert's weight (or gradient) block
VMEM_BYTES = 40 * 2 ** 20       # asked of the compiler for these calls


def _tile_n(n: int, k: int, itemsize: int) -> int:
    """Columns of an expert's [k, n] matrix a program takes: all of them
    where that is at most BLOCK_BYTES, so that consecutive row tiles of
    one expert name the same block and the matrix is fetched once an
    expert, not once a tile; else the largest power-of-two part."""
    t = n
    while t * k * itemsize > BLOCK_BYTES and t % 2 == 0 and t > 128:
        t //= 2
    return t


def _fwd_kernel(te_ref, nl_ref, a_ref, w_ref, o_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) < nl_ref[0])
    def _():
        o_ref[...] = jnp.dot(a_ref[...], w_ref[0],
                             preferred_element_type=F32).astype(o_ref.dtype)


def _dw_kernel(te_ref, nl_ref, a_ref, dc_ref, o_ref):
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    live = i < nl_ref[0]
    first = jnp.logical_or(i == 0,
                           te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

    @pl.when(jnp.logical_and(live, first))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        o_ref[0] += jax.lax.dot_general(
            a_ref[...], dc_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=F32)


def _gmm_call(a, w, tile_expert, n_live, tm: int):
    """a [M, K] @ w[tile_expert] [E, K, N] -> [M, N]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import interpret_arg, off_chip

    m, k = a.shape
    n = w.shape[2]
    tn = _tile_n(n, k, w.dtype.itemsize)
    nn = n // tn

    def last_live(i, nl):
        return jnp.minimum(i, nl[0] - 1)

    def col(i, j, nl):        # a dead tile repeats the step before it
        return jnp.where(i < nl[0], j, nn - 1)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(m // tm, nn),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j, te, nl: (last_live(i, nl), 0)),
            pl.BlockSpec((1, k, tn),
                         lambda i, j, te, nl: (te[i], 0, col(i, j, nl)))],
        out_specs=pl.BlockSpec(
            (tm, tn),
            lambda i, j, te, nl: (jnp.minimum(i, nl[0]), col(i, j, nl))))
    # the dead tiles' one shared output block is tile n_live: the buffer
    # has a spare tile so that it exists (plan_groups)
    return pl.pallas_call(
        _fwd_kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret_arg(off_chip(), a, w),
        name="moe_gmm_fwd",
    )(tile_expert, n_live, a, w)


def _gmm_dw_call(a, dc, tile_expert, n_live, n_experts: int, tm: int):
    """dw [E, K, N] float32 of a [M, K], dc [M, N]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import interpret_arg, off_chip

    m, k = a.shape
    n = dc.shape[1]
    tn = _tile_n(n, k, 4)

    def last_live(i, nl):
        return jnp.minimum(i, nl[0] - 1)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tn, m // tm),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, nl: (last_live(i, nl), 0)),
            pl.BlockSpec((tm, tn),
                         lambda j, i, te, nl: (last_live(i, nl), j))],
        out_specs=pl.BlockSpec((1, k, tn),
                               lambda j, i, te, nl: (te[i], 0, j)))
    return pl.pallas_call(
        _dw_kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((n_experts, k, n), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret_arg(off_chip(), a, dc),
        name="moe_gmm_dw",
    )(tile_expert, n_live, a, dc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(a: Array, w: Array, tile_expert: Array, n_live: Array,
                   tm: int = TILE_M) -> Array:
    """out [M, N]: row tile t of ``a`` [M, K] times ``w[tile_expert[t]]``
    (``w`` [E, K, N], float32 at rest, multiplied in ``a``'s dtype); tiles
    from ``n_live[0]`` on are left unwritten."""
    return _gmm_call(a, w.astype(a.dtype), tile_expert, n_live, tm)


def _gmm_fwd(a, w, tile_expert, n_live, tm):
    return grouped_matmul(a, w, tile_expert, n_live, tm), \
        (a, w, tile_expert, n_live)


def _gmm_bwd(tm, res, g):
    a, w, tile_expert, n_live = res
    da = _gmm_call(g, jnp.swapaxes(w, 1, 2).astype(g.dtype), tile_expert,
                   n_live, tm)
    dw = _gmm_dw_call(a, g, tile_expert, n_live, w.shape[0], tm)
    return da, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


class GroupPlan(NamedTuple):
    """Where the routed (token, slot) pairs of the experts held lie in the
    dropless buffer. M rows, N*k pairs."""
    tile_expert: Array      # [M / tm] int32, the expert of a row tile
    n_live: Array           # [1] int32, tiles that hold rows
    token_of: Array         # [M] int32, a row's token (0 where not valid)
    pair_of: Array          # [M] int32, a row's pair (0 where not valid)
    valid: Array            # [M] bool
    row_of: Array           # [N, k] int32, a pair's row (0 where not held)
    held: Array             # [N, k] bool, the pair's expert is held here


def buffer_rows(n_pairs: int, held: int, tm: int = TILE_M) -> int:
    """Rows of the dropless buffer: every pair, each group padded to whole
    tiles (an empty group takes one), and one spare tile."""
    return (-(-n_pairs // tm) + held + 1) * tm


def plan_groups(expert: Array, first: int, held: int,
                tm: int = TILE_M) -> GroupPlan:
    """`expert` [N, k] int32, the routed expert of every (token, slot)
    pair over all experts; this share holds ``[first, first + held)``."""
    n, k = expert.shape
    nk = n * k
    m = buffer_rows(nk, held, tm)
    local = expert.reshape(-1) - first
    is_held = (local >= 0) & (local < held)
    key = jnp.where(is_held, local, held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    start = jnp.searchsorted(
        key[order], jnp.arange(held + 1, dtype=jnp.int32)).astype(jnp.int32)
    count = start[1:] - start[:-1]                            # [held]
    tiles = jnp.maximum(1, -(-count // tm))
    tile_end = jnp.cumsum(tiles).astype(jnp.int32)
    tile_start = tile_end - tiles
    n_live = tile_end[-1:]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m // tm, dtype=jnp.int32),
                         side="right"), held - 1).astype(jnp.int32)
    j = jnp.arange(m, dtype=jnp.int32)
    e = tile_expert[j // tm]
    rank = j - tile_start[e] * tm
    valid = (j // tm < n_live[0]) & (rank < count[e])
    pair = order[jnp.clip(start[e] + rank, 0, nk - 1)]
    pair_of = jnp.where(valid, pair, 0)
    place = jnp.argsort(order).astype(jnp.int32)      # a pair's sorted rank
    pe = jnp.minimum(key, held - 1)
    row_of = jnp.where(is_held,
                       tile_start[pe] * tm + place - start[pe], 0)
    return GroupPlan(tile_expert, n_live, pair_of // k, pair_of, valid,
                     row_of.astype(jnp.int32).reshape(n, k),
                     is_held.reshape(n, k))


@jax.custom_vjp
def dispatch(x: Array, plan: GroupPlan) -> Array:
    """The buffer [M, D] of x [N, D]: row j is its token's row, nought
    where the row is padding. Its transpose is `combine`: both directions
    are gathers, never a scatter."""
    return jnp.where(plan.valid[:, None], x[plan.token_of], 0)


@jax.custom_vjp
def combine(buf: Array, plan: GroupPlan) -> Array:
    """y [N, D] = the sum over a token's held pairs of their buffer rows."""
    rows = jnp.where(plan.held[..., None], buf[plan.row_of], 0)  # [N, k, D]
    return jnp.sum(rows.astype(F32), axis=1).astype(buf.dtype)


def _dispatch_fwd(x, plan):
    return dispatch(x, plan), plan


def _dispatch_bwd(plan, g):
    return combine(g, plan), None


def _combine_fwd(buf, plan):
    return combine(buf, plan), plan


def _combine_bwd(plan, g):
    return dispatch(g, plan), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)
combine.defvjp(_combine_fwd, _combine_bwd)
