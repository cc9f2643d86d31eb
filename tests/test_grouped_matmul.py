"""The dropless experts' chunks (ops/grouped_matmul.py) under routings made
by hand: the capacity-sized first chunk where the live rows fit it, every
live chunk in turn where they do not, against the plain reference's `moe`
and against the same layer with one worst-case chunk, which is the buffer
the layer had before it was cut. float32 on the CPU, the kernels through
the Pallas interpreter.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import layer_kinds as lk
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.ops import grouped_matmul as gm

from test_qwen3_next import MM, agree, close, layer_params, model, ref

# 512 tokens x 8 slots, 8 of 64 experts held: a balanced router sends
# 512 pairs, the capacity is 2 x 512 rows = 4 tiles + 8 + 1 = 13 tiles of
# the worst case's 16 + 8 + 1 = 25: two chunks
TOKENS, TOP_K, HELD, WIDTH = 512, 8, 8, 64
CAPACITY_TILES = 13


def _routing(rows_of_expert):
    """idx [TOKENS, TOP_K]: held expert e gets the first
    ``rows_of_expert[e]`` tokens' slot e; every other pair goes to an
    expert that is not held."""
    idx = np.full((TOKENS, TOP_K), HELD, np.int32) + np.arange(TOP_K)
    for e, rows in enumerate(rows_of_expert):
        idx[:rows, e] = e
    return jnp.asarray(idx)


def _tiles(rows_of_expert):
    return sum(max(1, -(-r // gm.TILE_M)) for r in rows_of_expert)


ROUTINGS = {
    # name: (rows sent to each held expert, the branch taken)
    "balanced": ([64] * 8, "fast"),
    "every_pair_held": ([512] * 8, "slow"),         # 16 tiles, both chunks
    "at_the_capacity": ([300] * 5 + [40] * 3, "fast"),          # 13 tiles
    "one_tile_above": ([300] * 6 + [40] * 2, "slow"),           # 14 tiles
    "an_expert_with_no_row": ([64, 64, 64, 0, 64, 64, 64, 64], "fast"),
    "an_empty_expert_and_overflow": ([512] * 6 + [0, 512], "slow"),
}


@pytest.fixture
def counted():
    """The overflow counter's host callback is traced only where no
    persistent compilation cache would be defeated by it."""
    from jax.experimental.compilation_cache import compilation_cache
    assert not gm.counts_overflow()         # conftest turned the cache on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield default_registry().counter("moe_overflow")
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_chunks_match_the_reference_whatever_the_routing(
        name, monkeypatch, counted):
    rows_of_expert, branch = ROUTINGS[name]
    idx = _routing(rows_of_expert)
    assert (_tiles(rows_of_expert) <= CAPACITY_TILES) is (branch == "fast")
    s, cfg = model(router_width=WIDTH, num_experts=HELD,
                   num_experts_per_tok=TOP_K)
    assert gm.capacity_rows(TOKENS * TOP_K, HELD, WIDTH) \
        == CAPACITY_TILES * gm.TILE_M
    assert gm.buffer_rows(TOKENS * TOP_K, HELD) == 25 * gm.TILE_M
    p = layer_params(s, "full", seed=11)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, TOKENS // 2, 64),
                          jnp.float32)

    def route(xf, router, k, *_):   # the pairs by hand, the weights live
        return idx, jax.nn.softmax(
            jnp.matmul(xf.astype(jnp.float32), router[:, :k]), axis=-1)

    monkeypatch.setattr(lk, "route", route)
    monkeypatch.setattr(ref, "route", route)

    def uncut(x_, p_):
        """One chunk of the worst case: the buffer before it was cut."""
        plan = gm.plan_groups(idx, 0, HELD, HELD)
        assert plan.tile_expert.shape == (1, 25)
        xf = x_.reshape(-1, 64)
        y = gm.dropless_experts(xf, route(xf, p_["router"], TOP_K)[1],
                                p_["We_gu"], p_["We_down"], plan)
        return y.reshape(x_.shape) + ref.shared_expert(
            xf, p_, MM).reshape(x_.shape)

    before = counted.value
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(lambda x_, p_: lk.moe_topk(x_, p_, cfg), x, p)
        want, pull_r = jax.vjp(lambda x_, p_: ref.moe(x_, p_, s, MM), x, p)
        same, pull_s = jax.vjp(uncut, x, p)
        (gx, gp), (rx, rp), (sx, sp) = pull(want), pull_r(want), pull_s(want)
    jax.effects_barrier()
    if branch == "fast":
        assert counted.value == before
    else:
        assert counted.value >= before + 1
    close(got, want, 2e-5)
    close(gx, rx, 5e-5)
    agree(got, same, 1e-6)
    agree(gx, sx, 1e-6)
    for leaf in ("We_gu", "We_down", "router"):
        close(gp[leaf], rp[leaf], 1e-4)
        agree(gp[leaf], sp[leaf], 1e-6)


def _branches(jaxpr):
    """`cond` equations of a jaxpr and of every jaxpr inside it, the
    kernels' bodies apart (the interpreter turns `pl.when` into one)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _branches(sub)
    return found


def test_a_whole_layer_has_one_chunk_and_no_branch():
    """Every expert held: the capacity is the worst case, and the traced
    layer has no conditional; a share of the experts has its one (and one
    a chunk of the overflow path)."""
    def traced(**over):
        s, cfg = model(**over)
        p = layer_params(s, "full", seed=2)
        x = jnp.zeros((2, 128, 64), jnp.float32)
        return jax.make_jaxpr(
            lambda x_, p_: lk.moe_topk(x_, p_, cfg))(x, p).jaxpr

    assert gm.capacity_rows(256 * 2, 4, 4) == gm.buffer_rows(256 * 2, 4)
    assert _branches(traced(router_width=4)) == 0
    assert _branches(traced(router_width=64, num_experts=4,
                            num_experts_per_tok=6)) == 2


def test_capacity_rows_at_the_cell_by_hand():
    """24,576 tokens x 10 slots, 32 of 512 experts held: a balanced
    router sends 15,360 rows = 60 tiles; twice that, a tile an expert and
    the spare tile are 153 tiles of the worst case's 960 + 32 + 1."""
    assert gm.buffer_rows(245760, 32) == 993 * 256 == 254208
    assert gm.capacity_rows(245760, 32, 512) == 153 * 256 == 39168
    assert -(-254208 // 39168) == 7             # chunks of the worst case
    assert gm.capacity_rows(245760, 512, 512) == gm.buffer_rows(245760, 512)


def test_the_combine_kernel_is_the_float32_sum_over_a_tokens_rows():
    """`combine` against the plain sum it replaced, in bfloat16 rows:
    the same products, accumulated in float32, rounded once."""
    k = 4
    idx = jax.random.randint(jax.random.PRNGKey(0), (300, k), 0, 12)
    plan = gm.plan_groups(idx.astype(jnp.int32), 2, 5, 12)
    cp = gm.chunk_plan(plan, 0, 300)
    buf = jax.random.normal(jax.random.PRNGKey(1), (cp.valid.shape[0], 128),
                            jnp.float32).astype(jnp.bfloat16)
    want = jax.ops.segment_sum(
        jnp.where(cp.valid[:, None], buf, 0).astype(jnp.float32),
        cp.token_of, num_segments=300).astype(jnp.bfloat16)
    got = gm.combine(buf, cp)
    assert got.dtype == jnp.bfloat16 and got.shape == (300, 128)
    # a float32 sum of at most four terms in another order, rounded once
    agree(got.astype(jnp.float32), want.astype(jnp.float32), 2e-3)
    np.testing.assert_array_equal(
        np.asarray(gm.dispatch(got, cp)),
        np.asarray(jnp.where(cp.valid[:, None], got[cp.token_of], 0)))
