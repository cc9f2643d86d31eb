"""A dropless mixture of experts: the row plan, its Pallas kernels and the
routed sum built from them.

**The sorted buffer.** The routed (token, slot) pairs of the experts held
here are sorted by expert into a buffer whose groups are padded to whole
row tiles (`plan_groups`), so a tile of ``tm`` rows belongs to one expert.
Only the first ``n_live`` tiles hold rows, and how many that is depends on
the router: at most every pair (`buffer_rows`, the worst case), a fraction
``held / n_experts`` of them from a balanced one.

**Chunks of a capacity.** Nothing here is sized by the worst case. The
buffer is cut into chunks of `capacity_rows` rows, `CAPACITY_FACTOR` times
what a balanced router sends plus the padding, chosen from shapes alone,
and one function (`_chunk`) does a chunk: gather its token rows
(`dispatch`), the two grouped matmuls around the activation, the mask, the
routing weight, and the chunk's part of the sum over a token's pairs
(`combine`). The live rows are a prefix of the buffer, so where ``n_live``
fits the first chunk that chunk is the whole layer; `dropless_experts`
asks at run time (`lax.cond`) and otherwise runs every chunk that holds a
live tile in turn, each adding its part to a float32 sum. That overflow
path is slower and still exact: no row is dropped whatever the router
sends, and its temporaries are one chunk's. Reverse mode is the block's
own (`custom_vjp`): it keeps the arguments, computes a chunk again and
pulls the cotangent through it, behind the same branch, so nothing but
the result crosses a `lax.cond`. Where the capacity is the worst case
(every expert held) there is one chunk and no branch.

**Kernels.** `moe_gmm_fwd`: out[tile] = a[tile] @ w[tile_expert[tile]],
the weight block picked by the scalar-prefetched ``tile_expert`` (also the
gradient of ``a``, with the weights transposed). A program takes all the
columns of its expert's matrix where they fit a 4 MB block (the cell's
2048 x 1024 and 512 x 2048 do), so the matrix is fetched once an expert.
Tiles past the live ones cost a grid step and nothing else: their block
indices repeat the last live tile's, their body is skipped, and their rows
of the output are never written (whoever reads the buffer masks by
``valid``). `moe_gmm_dw`: dw[e] = sum over e's tiles of a[tile]^T @
dc[tile], accumulated in the output block, which stays in VMEM while
consecutive tiles name the same expert; an expert with no tile in the
chunk is never visited, so its block is zeroed afterwards unless the
caller says the chunk is the whole buffer (every expert has at least one
tile). `moe_combine`: y[token tile] = sum of its rows. The chunk's rows
are laid out once more, by tile of ``tm`` tokens exactly as `plan_groups`
lays experts out (`_pack` does both), gathered into that order by XLA,
and a row block is added into its token tile as ``onehot(local token)
[tm, tm] @ rows [tm, D]`` on the MXU, accumulated in float32 while
consecutive blocks name the same tile: the same products in the same
precisions as a float32 sum over a token's pairs, with no `[N, k, D]`
array and no scatter. The same kernel is `dispatch`'s transpose.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array
F32 = jnp.float32
I32 = jnp.int32
TILE_M = 256
# rows of a chunk over the rows a balanced router sends the experts held
CAPACITY_FACTOR = 2


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


def _combine_kernel(bt_ref, nb_ref, local_ref, rows_ref, o_ref, acc_ref):
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    tile = bt_ref[b]
    live = b < nb_ref[0]
    first = jnp.logical_or(b == 0, tile != bt_ref[jnp.maximum(b - 1, 0)])
    last = jnp.logical_or(
        b == nb_ref[0] - 1,
        tile != bt_ref[jnp.minimum(b + 1, pl.num_programs(0) - 1)])

    @pl.when(jnp.logical_and(live, first))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        tm = rows_ref.shape[0]
        # [token of the tile, row of the block]: 1 where the row is the
        # token's (a padding row's local token is -1)
        onehot = (lax.broadcasted_iota(I32, (tm, tm), 0)
                  == local_ref[0]).astype(rows_ref.dtype)
        acc_ref[...] += jnp.dot(
            onehot, rows_ref[...], preferred_element_type=F32,
            precision=(lax.Precision.HIGHEST if rows_ref.dtype == F32
                       else None))

    @pl.when(jnp.logical_and(live, last))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _last_live(i, n_live):
    """The block a grid step reads: its own, or past the live ones the
    last live one's again, which is fetched once and not again."""
    return jnp.minimum(i, n_live[0] - 1)


def _gmm_call(a, w, tile_expert, n_live, tm: int):
    """a [M, K] @ w[tile_expert] [E, K, N] -> [M, N]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import interpret_arg, off_chip

    m, k = a.shape
    n = w.shape[2]
    tn = _tile_n(n, k, w.dtype.itemsize)
    nn = n // tn

    def col(i, j, nl):        # a dead tile repeats the step before it
        return jnp.where(i < nl[0], j, nn - 1)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(m // tm, nn),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j, te, nl: (_last_live(i, nl), 0)),
            pl.BlockSpec((1, k, tn),
                         lambda i, j, te, nl: (te[i], 0, col(i, j, nl)))],
        out_specs=pl.BlockSpec(
            (tm, tn),
            lambda i, j, te, nl: (jnp.minimum(i, nl[0]), col(i, j, nl))))
    # the dead tiles' one shared output block is tile n_live, which
    # exists wherever a tile is dead
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

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n // tn, m // tm),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, te, nl: (_last_live(i, nl), 0)),
            pl.BlockSpec((tm, tn),
                         lambda j, i, te, nl: (_last_live(i, nl), j))],
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


def _combine_call(rows, block_tile, n_blocks, local, tokens: int):
    """y [tokens, D]: row block b of ``rows`` [B * tm, D] added into token
    tile ``block_tile[b]`` by ``local`` [B, 1, tm], a row's token within
    the tile; every token tile has a block, so every tile is written."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeplearning4j_tpu.ops.pallas_util import interpret_arg, off_chip

    tm, d = local.shape[2], rows.shape[1]
    tiles = -(-tokens // tm)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(block_tile.shape[0],),
        in_specs=[
            pl.BlockSpec((1, 1, tm),
                         lambda b, bt, nb: (_last_live(b, nb), 0, 0)),
            pl.BlockSpec((tm, d), lambda b, bt, nb: (_last_live(b, nb), 0))],
        out_specs=pl.BlockSpec((tm, d), lambda b, bt, nb: (bt[b], 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), F32)])
    return pl.pallas_call(
        _combine_kernel, grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((tiles * tm, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret_arg(off_chip(), rows),
        name="moe_combine",
    )(block_tile, n_blocks, local, rows)[:tokens]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(a: Array, w: Array, tile_expert: Array, n_live: Array,
                   tm: int = TILE_M, whole: bool = True) -> Array:
    """out [M, N]: row tile t of ``a`` [M, K] times ``w[tile_expert[t]]``
    (``w`` [E, K, N], float32 at rest, multiplied in ``a``'s dtype); tiles
    from ``n_live[0]`` on are left unwritten. ``whole``: every expert has
    a live tile among these (the buffer's first chunk where ``n_live``
    fits it); otherwise the gradient of an expert with none is zeroed."""
    return _gmm_call(a, w.astype(a.dtype), tile_expert, n_live, tm)


def _gmm_fwd(a, w, tile_expert, n_live, tm, whole):
    return grouped_matmul(a, w, tile_expert, n_live, tm, whole), \
        (a, w, tile_expert, n_live)


def _gmm_bwd(tm, whole, res, g):
    a, w, tile_expert, n_live = res
    da = _gmm_call(g, jnp.swapaxes(w, 1, 2).astype(g.dtype), tile_expert,
                   n_live, tm)
    dw = _gmm_dw_call(a, g, tile_expert, n_live, w.shape[0], tm)
    if not whole:
        live = jnp.arange(tile_expert.shape[0]) < n_live[0]
        seen = jnp.any((tile_expert == jnp.arange(w.shape[0])[:, None])
                       & live, axis=1)
        dw = jnp.where(seen[:, None, None], dw, 0)
    return da, dw.astype(w.dtype), None, None


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def buffer_rows(n_pairs: int, held: int, tm: int = TILE_M) -> int:
    """Rows of the sorted buffer in the worst case: every pair, each group
    padded to whole tiles (an empty group takes one), and one spare
    tile."""
    return (-(-n_pairs // tm) + held + 1) * tm


def capacity_rows(n_pairs: int, held: int, n_experts: int,
                  tm: int = TILE_M) -> int:
    """Rows of a chunk, the rows the always-taken path moves:
    CAPACITY_FACTOR times what a balanced router sends the ``held`` of
    ``n_experts`` experts, in whole tiles, a tile an expert for padding
    and the spare tile; never more than the worst case."""
    balanced = -(-n_pairs * held // n_experts)
    tiles = -(-CAPACITY_FACTOR * balanced // tm) + held + 1
    return min(tiles * tm, buffer_rows(n_pairs, held, tm))


def _pack(key: Array, groups: int, tiles: int, tm: int, *carried: Array):
    """Lay the entries of ``key`` (a group in ``[0, groups)``, or
    ``groups``: not here) out by group in the order they come, each group
    padded to whole tiles of ``tm`` rows (an empty group takes one), in
    ``tiles`` tiles: (tile_group [tiles], n_live [1] the tiles that hold
    groups, valid [tiles * tm], a row's entry [tiles * tm] or 0, and for
    each of ``carried``, arrays beside ``key``, a row's entry's value or
    0). A tile's rows are ``tm`` neighbours of the sorted entries, so
    everything is worked out a tile and rows are fetched a tile at a
    time: a gather of single int32s costs the chip nanoseconds each."""
    n = key.shape[0]
    by_group = lax.sort((key, jnp.arange(n, dtype=I32), *carried),
                        num_keys=1, is_stable=True)
    start = jnp.searchsorted(
        by_group[0], jnp.arange(groups + 1, dtype=I32)).astype(I32)
    count = start[1:] - start[:-1]                            # [groups]
    span = jnp.maximum(1, -(-count // tm))
    tile_end = jnp.cumsum(span).astype(I32)
    tile_start = tile_end - span
    n_live = tile_end[-1:]
    tile = jnp.arange(tiles, dtype=I32)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right"),
        groups - 1).astype(I32)
    ahead = (tile - tile_start[tile_group]) * tm    # of the group's rows
    left = jnp.where(tile < n_live[0], count[tile_group] - ahead, 0)
    valid = (jnp.arange(tm, dtype=I32) < left[:, None]).reshape(-1)
    first = jnp.clip(start[tile_group] + ahead, 0, n)

    def rows(sorted_entries):
        padded = jnp.pad(sorted_entries, (0, tm))
        got = jax.vmap(lambda at: lax.dynamic_slice(padded, (at,), (tm,)))(
            first)
        return jnp.where(valid, got.reshape(-1), 0)
    return (tile_group, n_live, valid, *(rows(a) for a in by_group[1:]))


class GroupPlan(NamedTuple):
    """Where the routed (token, slot) pairs of the experts held lie in the
    sorted buffer, cut into chunks of C rows (`capacity_rows`); the chunks
    cover the worst case."""
    tile_expert: Array      # [chunks, C / tm] int32, the expert of a tile
    n_live: Array           # [1] int32, tiles that hold rows, a prefix
    token_of: Array         # [chunks, C] int32, a row's token (0: none)
    pair_of: Array          # [chunks, C] int32, a row's pair (0: none)
    valid: Array            # [chunks, C] bool


def plan_groups(expert: Array, first: int, held: int, n_experts: int,
                tm: int = TILE_M) -> GroupPlan:
    """`expert` [N, k] int32, the routed expert of every (token, slot)
    pair over all ``n_experts``; this share holds ``[first, first +
    held)``."""
    n, k = expert.shape
    rows = capacity_rows(n * k, held, n_experts, tm)
    chunks = -(-buffer_rows(n * k, held, tm) // rows)
    local = expert.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held).astype(I32)
    tile_expert, n_live, valid, pair_of = _pack(
        key, held, chunks * rows // tm, tm)
    return GroupPlan(tile_expert.reshape(chunks, -1), n_live,
                     (pair_of // k).reshape(chunks, rows),
                     pair_of.reshape(chunks, rows),
                     valid.reshape(chunks, rows))


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["tile_expert", "n_live", "token_of",
                                "pair_of", "valid", "block_tile",
                                "n_blocks", "src", "local"],
                   meta_fields=["tokens"])
@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """One chunk of a `GroupPlan`, C rows, and the same rows by token
    tile: B = C / tm + N / tm row blocks, a token tile's blocks side by
    side (an empty tile takes one)."""
    tile_expert: Array      # [C / tm] int32
    n_live: Array           # [1] int32, the chunk's own live tiles
    token_of: Array         # [C] int32
    pair_of: Array          # [C] int32
    valid: Array            # [C] bool
    block_tile: Array       # [B] int32, the token tile of a row block
    n_blocks: Array         # [1] int32, blocks that hold token tiles
    src: Array              # [B * tm] int32, a block row's chunk row
    local: Array            # [B, 1, tm] int32, its token in the tile, or -1
    tokens: int             # N


def chunk_plan(plan: GroupPlan, c, tokens: int) -> ChunkPlan:
    """Chunk ``c`` (an int or a traced scalar) of ``plan``."""
    tile_expert, token_of, pair_of, valid = (
        a[c] for a in (plan.tile_expert, plan.token_of, plan.pair_of,
                       plan.valid))
    ct = tile_expert.shape[0]
    tm = token_of.shape[0] // ct
    n_live = jnp.clip(plan.n_live - c * ct, 0, ct).astype(I32)
    tiles = -(-tokens // tm)
    block_tile, n_blocks, has, src, local = _pack(
        jnp.where(valid, token_of // tm, tiles).astype(I32), tiles,
        ct + tiles, tm, token_of % tm)
    local = jnp.where(has, local, -1).reshape(-1, 1, tm)
    return ChunkPlan(tile_expert, n_live, token_of, pair_of, valid,
                     block_tile, n_blocks, src, local, tokens)


@jax.custom_vjp
def dispatch(x: Array, cp: ChunkPlan) -> Array:
    """The chunk's rows [C, D] of x [N, D]: row j is its token's row,
    nought where the row is padding. Its transpose is `combine`: neither
    direction is a scatter."""
    return jnp.where(cp.valid[:, None], x[cp.token_of], 0)


@jax.custom_vjp
def combine(buf: Array, cp: ChunkPlan) -> Array:
    """y [N, D]: a token's sum over its rows of the chunk ``buf`` [C, D],
    accumulated in float32, in ``buf``'s dtype. A padding row of a block
    fetches chunk row 0, which a live tile holds (a chunk that runs has
    one), and meets a nought of the one-hot matrix: no mask of its own."""
    return _combine_call(buf[cp.src], cp.block_tile, cp.n_blocks, cp.local,
                         cp.tokens)


def _dispatch_fwd(x, cp):
    return dispatch(x, cp), cp


def _dispatch_bwd(cp, g):
    return combine(g, cp), None


def _combine_fwd(buf, cp):
    return combine(buf, cp), cp


def _combine_bwd(cp, g):
    return dispatch(g, cp), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)
combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.jit, static_argnames=("dtype", "whole"))
def _chunk(x, weight, w_gu, w_down, plan: GroupPlan, c, dtype,
           whole: bool) -> Array:
    """Chunk ``c``'s part of the routed sum, [N, D] in x's dtype; the
    rows are computed in ``dtype``. Under `jax.jit` so that the layers
    and passes of a step, which all call it on the same shapes, share one
    trace and one lowered function."""
    with jax.named_scope("moe.route"):
        cp = chunk_plan(plan, c, x.shape[0])
    tm = cp.local.shape[2]
    with jax.named_scope("moe.dispatch"):
        buf = dispatch(x, cp).astype(dtype)
        w_row = jnp.where(cp.valid, weight.reshape(-1)[cp.pair_of], 0.0)
    with jax.named_scope("moe.experts"):
        gu = grouped_matmul(buf, w_gu, cp.tile_expert, cp.n_live, tm, whole)
        f = gu.shape[-1] // 2
        mid = jax.nn.silu(gu[:, :f]) * gu[:, f:]
        out = grouped_matmul(mid, w_down, cp.tile_expert, cp.n_live, tm,
                             whole)
    with jax.named_scope("moe.combine"):
        # rows of tiles that hold nothing were never written: mask before
        # anything is multiplied into them
        out = jnp.where(cp.valid[:, None], out, 0)
        out = out * w_row[:, None].astype(out.dtype)
        return combine(out.astype(x.dtype), cp)


def counts_overflow() -> bool:
    """Whether `dropless_experts` traces the host callback that counts
    ``moe_overflow_total``. A program with a host callback is never
    written to JAX's persistent compilation cache (jax 0.9.0,
    ``compiler._cache_write``), so where such a cache is configured the
    callback is left out and the counter stays where it is."""
    from deeplearning4j_tpu.util import compile_cache
    return not compile_cache.in_use()


def _count_overflow() -> None:
    from deeplearning4j_tpu.observability.metrics import default_registry
    default_registry().counter("moe_overflow").inc()


def _branches(plan: GroupPlan, fast, slow, *operands):
    """``fast`` where the live tiles fit the first chunk, else ``slow``;
    where there is one chunk, ``fast`` and no branch."""
    chunks, ct = plan.tile_expert.shape
    if chunks == 1:
        return fast(*operands)
    return lax.cond(plan.n_live[0] <= ct, fast, slow, *operands)


def _live_chunks(plan: GroupPlan, step, acc):
    """``acc`` through ``step(acc, c)`` for every chunk c that holds a
    live tile, in turn."""
    chunks, ct = plan.tile_expert.shape

    def body(acc, c):
        return lax.cond(c * ct < plan.n_live[0], lambda a: step(a, c),
                        lambda a: a, acc), None
    return lax.scan(body, acc, jnp.arange(chunks, dtype=I32))[0]


@jax.custom_vjp
def dropless_experts(x: Array, weight: Array, w_gu: Array, w_down: Array,
                     plan: GroupPlan) -> Array:
    """y [N, D]: the held experts' part of every token's routed sum.
    x [N, D]; weight [N, k] float32, the routing weights; w_gu [E, D, 2F]
    and w_down [E, F, D], a SwiGLU an expert. Reverse mode keeps the
    arguments and computes a chunk again: what a `lax.cond` hands from a
    forward branch to a backward one is the union of both branches'
    residuals, resident together."""
    def fast(x, weight, w_gu, w_down):
        return _chunk(x, weight, w_gu, w_down, plan, 0, x.dtype, True)

    def slow(x, weight, w_gu, w_down):
        if counts_overflow():
            jax.debug.callback(_count_overflow)
        # x in float32, so that a token's rows of different chunks are
        # added in float32 as a chunk adds its own
        xs = x.astype(F32)
        return _live_chunks(
            plan, lambda y, c: y + _chunk(xs, weight, w_gu, w_down, plan, c,
                                          x.dtype, False),
            jnp.zeros(xs.shape, F32)).astype(x.dtype)

    return _branches(plan, fast, slow, x, weight, w_gu, w_down)


def _experts_fwd(x, weight, w_gu, w_down, plan):
    return (dropless_experts(x, weight, w_gu, w_down, plan),
            (x, weight, w_gu, w_down, plan))


def _experts_bwd(res, g):
    x, weight, w_gu, w_down, plan = res

    def grads(c, whole, x, *leaves):
        """Chunk c's part of every gradient, x's in x's dtype."""
        _, pull = jax.vjp(
            lambda *a: _chunk(*a, plan, c, g.dtype, whole), x, *leaves)
        return pull(g.astype(x.dtype))

    def slow(x, *leaves):
        xs = x.astype(F32)        # as forward: float32 across chunks
        dx, *rest = _live_chunks(
            plan, lambda acc, c: jax.tree_util.tree_map(
                jnp.add, acc, grads(c, False, xs, *leaves)),
            jax.tree_util.tree_map(jnp.zeros_like, (xs, *leaves)))
        return (dx.astype(x.dtype), *rest)

    return (*_branches(plan, functools.partial(grads, 0, True), slow,
                       x, weight, w_gu, w_down), None)


dropless_experts.defvjp(_experts_fwd, _experts_bwd)
