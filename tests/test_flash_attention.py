"""Pallas flash-attention kernel vs jnp reference.

Models the reference's CuDNNGradientChecks strategy (SURVEY.md §2.3:
numeric check of the accelerated path against the baseline path) — here
the Pallas kernel (interpret mode on CPU) against the jnp attention.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
from deeplearning4j_tpu.ops.flash_attention import (_lane_dense_width,
                                                    flash_attention,
                                                    flash_attention_available)

# (heads, head_dim) that take the block's own [B, T, H*Dh] layout, two
# heads a program at 64 and one at 128; the (2, 16) and (2, 32) beside
# them in the lists below keep one head an entry of [B*H, T, Dh]
# (H*Dh < 128), and (4, 32) fills one 128-lane group with four heads
LANE_DENSE = [(2, 64), (4, 64), (2, 128)]


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    yield


def _rand(shape, seed):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape).astype(np.float32))


@pytest.mark.parametrize("h,d", [(4, 32), (2, 32)] + LANE_DENSE)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal, h, d):
    b, t = 2, 128
    assert bool(_lane_dense_width(h, d, t, t)) == (h * d % 128 == 0)
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    got = flash_attention(q, k, v, causal=causal)
    os.environ["DL4JTPU_FLASH"] = "0"
    want = dot_product_attention(q, k, v, causal=causal)
    os.environ["DL4JTPU_FLASH"] = "interpret"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("h,d", [(2, 16)] + LANE_DENSE)
def test_flash_offsets_match_reference(h, d):
    """Blockwise callers pass global position offsets; causal masking must
    line up with the monolithic computation (a suffix block of a longer
    sequence: sk != tq)."""
    b, t = 1, 128
    q, k, v = (_rand((b, 2 * t, h, d), s) for s in (3, 4, 5))
    os.environ["DL4JTPU_FLASH"] = "0"
    full = dot_product_attention(q, k, v, causal=True)
    os.environ["DL4JTPU_FLASH"] = "interpret"
    # second query block attending over the full 2t keys
    blk = flash_attention(q[:, t:], k, v, causal=True, q_offset=t,
                          kv_offset=0)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(full[:, t:]),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("h,d,tq,sk,causal", [
    (2, 16, 64, 64, True),
    # two q tiles by two k tiles, so the diagonal splits the loops
    (2, 64, 1024, 1024, True), (4, 64, 128, 128, True),
    (2, 128, 128, 128, True),
    # cross-attention lengths: sk != tq, every tile unmasked
    (2, 64, 128, 384, False), (2, 128, 128, 256, False)])
def test_flash_gradients_match_reference(h, d, tq, sk, causal):
    b = 1
    q = _rand((b, tq, h, d), 6)
    k, v = (_rand((b, sk, h, d), s) for s in (7, 8))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        os.environ["DL4JTPU_FLASH"] = "0"
        out = dot_product_attention(q, k, v, causal=causal)
        os.environ["DL4JTPU_FLASH"] = "interpret"
        return jnp.sum(out ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


def test_dispatcher_uses_fallback_for_masks():
    """Padding masks must take the jnp path (kernel ineligible) and still
    be correct."""
    b, t, h, d = 2, 16, 2, 8
    q, k, v = (_rand((b, t, h, d), s) for s in (9, 10, 11))
    mask = jnp.asarray(np.array([[1] * 10 + [0] * 6, [1] * 16],
                                np.float32))
    assert not flash_attention_available(q, k, mask)
    out = dot_product_attention(q, k, v, mask=mask)
    # masked keys contribute nothing: perturbing them changes nothing
    v2 = v.at[0, 12].set(99.0)
    out2 = dot_product_attention(q, k, v2, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2))


def test_eligibility_rules():
    q = _rand((1, 128, 2, 16), 0)
    assert flash_attention_available(q, q, None)  # interpret env set
    os.environ["DL4JTPU_FLASH"] = "0"
    assert not flash_attention_available(q, q, None)
    os.environ["DL4JTPU_FLASH"] = "interpret"
    q_small = _rand((1, 5, 2, 16), 0)
    assert not flash_attention_available(q_small, q_small, None)
    # kv extents with no power-of-two tile (cross-attention S=2500)
    # must take the jnp path — an untiled single panel would bypass
    # the VMEM bounds the tile caps enforce (advisor r3)
    q_ok = _rand((1, 128, 2, 16), 0)
    k_odd = _rand((1, 2500, 2, 16), 1)
    assert not flash_attention_available(q_ok, k_odd, None)


@pytest.mark.parametrize("h,d", [(2, 16)] + LANE_DENSE)
def test_gradients_with_fully_masked_rows(h, d):
    """kv_offset > q_offset creates causal rows with zero valid keys;
    the forward degenerates to a uniform average and the Pallas
    backward must reproduce the reference VJP exactly (regression:
    a single pre-summed logsumexp lost log(l) to f32 rounding on
    those rows, inflating p from 1/S to 1). Either layout keeps m and
    log(l) apart there, as two lane-major rows a head."""
    b, t = 1, 128
    q, k, v = (_rand((b, t, h, d), s) for s in (7, 8, 9))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                q_offset=0, kv_offset=64) ** 2).sum()

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)

    os.environ["DL4JTPU_FLASH"] = "0"

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=True,
                                      q_offset=0, kv_offset=64) ** 2).sum()

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    os.environ["DL4JTPU_FLASH"] = "interpret"
    for g1, g2 in zip(got, want):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("h,d", LANE_DENSE)
def test_suffix_block_gradients_match_reference(h, d):
    """What ring attention asks of the entry: the gradients of a suffix
    block of queries over a longer key sequence, by integer offsets."""
    b, t = 1, 128
    q = _rand((b, t, h, d), 12)
    k, v = (_rand((b, 2 * t, h, d), s) for s in (13, 14))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, q_offset=t, kv_offset=0) ** 2)

    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    os.environ["DL4JTPU_FLASH"] = "0"
    want = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    os.environ["DL4JTPU_FLASH"] = "interpret"
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


def test_layout_gate_mark_and_counter():
    """Shapes alone choose the layout, and a trace-time mark and a
    counter say which was taken."""
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import default_spans

    assert _lane_dense_width(16, 64, 1024, 1024) == 128
    assert _lane_dense_width(16, 128, 2048, 2048) == 128
    assert _lane_dense_width(4, 256, 1024, 1024) == 256
    assert _lane_dense_width(2, 16, 128, 128) == 0      # H*Dh = 32
    assert _lane_dense_width(8, 96, 1024, 1024) == 0    # Dh = 96
    assert _lane_dense_width(3, 64, 1024, 1024) == 0    # half a group
    # a head of whole 128-lane tiles is a program's block at every
    # length, the host's tilings moving index maps, for any group:
    # GLM-4.7-Flash's attention (20 heads of 256 at T 8192) and
    # Qwen3-Next's (16 query heads on 2 KV heads of 256)
    assert _lane_dense_width(16, 128, 4096, 4096) == 128
    assert _lane_dense_width(16, 128, 8192, 8192) == 128
    assert _lane_dense_width(16, 256, 4096, 4096) == 256
    assert _lane_dense_width(20, 256, 8192, 8192) == 256
    assert _lane_dense_width(16, 128, 1024, 8192) == 128
    assert _lane_dense_width(16, 256, 8192, 8192, 8) == 256
    # a head pair past one superblock, or grouped, keeps the per-head
    # form: Granite-4.0-H's 32 query heads on 8 KV heads of 64
    assert _lane_dense_width(32, 64, 8192, 8192, 4) == 0
    assert _lane_dense_width(16, 64, 8192, 8192) == 0
    assert _lane_dense_width(16, 64, 4096, 4096) == 0
    assert _lane_dense_width(16, 64, 1024, 1024, 2) == 0
    # a kv length the backward cannot tile (its jnp fallback)
    assert _lane_dense_width(16, 64, 128, 200) == 0
    assert _lane_dense_width(16, 128, 128, 200) == 0

    def seen(layout):
        fam = default_registry().get("flash_attention_calls")
        return fam.labels(layout).value if fam else 0.0

    for (h, d), layout, hpb in (((2, 16), "per_head", 1),
                                ((2, 64), "lane_dense", 2),
                                ((2, 128), "lane_dense", 1)):
        before = seen(layout)
        x = _rand((1, 128, h, d), 0)
        flash_attention(x, x, x, causal=True)
        assert seen(layout) == before + 1
        last = [sp for sp in default_spans().snapshot().spans
                if sp.name == "flash_attention.layout"][-1]
        assert last.args == {"layout": layout, "heads_per_block": hpb,
                             "fwd_calls": 1, "bwd_calls": 1}


@pytest.mark.parametrize("shape,group,calls", [
    # (entries, T, lanes, head_dim): the parent's per-head GLM split the
    # forward's 40 entries 24 + 16 and tiled the backward's 10 live tiles
    ((40, 8192, 256, 256), 1, (2, 10)),
    ((2, 8192, 20 * 256, 256), 1, (1, 10)),       # GLM-4.7-Flash
    ((3, 8192, 16 * 256, 256), 8, (1, 10)),       # Qwen3-Next
    ((32, 8192, 64, 64), 4, (2, 10)),             # Granite-4.0-H
    ((32, 1024, 16 * 64, 64), 1, (1, 1)),         # GPT-2 medium
], ids=["glm_per_head", "glm", "qwen", "granite", "gpt2m"])
def test_kernel_calls_of_the_cells_attention(shape, group, calls):
    """The calls one forward and one backward issue at the cells' sizes,
    from the plans the two paths follow (no trace)."""
    from deeplearning4j_tpu.ops import flash_attention as _  # noqa: F401
    import sys
    fa = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    n, t, lanes, d = shape
    assert fa._kernel_calls(n, t, t, lanes, d, True, 0, 0, group) == calls


def test_glm_shaped_trace_marks_its_kernel_calls(monkeypatch):
    """GLM's attention at a tiny size: 20 heads of 128 lanes (one a
    program) over four backward tiles a side, the tiles forced small.
    The mark says lane_dense, one forward call (two parts of one buffer
    where the grid cap splits the heads) and the ten live tiles, and
    tracing the gradient makes exactly those kernel calls."""
    import sys

    import jax.experimental.pallas as pl

    from deeplearning4j_tpu.observability.tracing import default_spans
    fa = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_BWD_K_CHUNK", 256)
    monkeypatch.setattr(fa, "_BWD_LONG_TILE", 128)
    inner = fa._inner_block
    # q-superblocks of 256 rows, score tiles of 128
    monkeypatch.setattr(fa, "_inner_block", lambda n, cap=512: inner(
        n, 256 if cap == fa._FWD_QSB else 128))
    names = []
    real = pl.pallas_call

    def counted(*a, **kw):
        names.append(kw["name"])
        return real(*a, **kw)

    monkeypatch.setattr(pl, "pallas_call", counted)
    b, t, h, d = 1, 512, 20, 128
    q = _rand((b, t, h, d), 0)

    def loss(x):
        return jnp.sum(flash_attention(x, x, x, causal=True) ** 2)

    # 1 x 20 heads x 2 superblocks = 40 programs: one call, or two
    # parts of 10 heads under a cap of 20
    for cap, fwd in ((256, 1), (20, 2)):
        monkeypatch.setattr(fa, "_MAX_LANE_GRID_FWD", cap)
        names.clear()
        jax.eval_shape(jax.grad(loss), q)
        last = [sp for sp in default_spans().snapshot().spans
                if sp.name == "flash_attention.layout"][-1]
        assert last.args == {"layout": "lane_dense", "heads_per_block": 1,
                             "fwd_calls": fwd, "bwd_calls": 10}
        assert names.count("flash_fwd") == fwd
        assert names.count("flash_bwd") == 10


def test_lane_dense_lowering_has_no_transpose(monkeypatch):
    """Lowered for the TPU (no chip needed to lower), the gradient of
    an eligible shape is the two Mosaic calls and reshapes: no
    transpose of q, k, v, o or their cotangents. The per-head shape
    beside it shows what the search would find."""
    import re

    monkeypatch.setenv("DL4JTPU_FLASH", "auto")   # the Mosaic call itself

    def lowered(shape):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32) ** 2), (0, 1, 2))
        # Mosaic has no 64-bit index arithmetic; the tests' x64 mode is
        # not how the chip runs
        with jax.enable_x64(False):
            return jax.jit(grad).trace(x, x, x).lower(
                lowering_platforms=("tpu",)).as_text()

    dense = lowered((2, 256, 16, 64))
    assert dense.count("tpu_custom_call") == 2
    assert not re.findall(r"stablehlo\.transpose", dense)
    per_head = lowered((2, 256, 2, 16))
    assert len(re.findall(r"stablehlo\.transpose", per_head)) >= 6


def test_multi_superblock_and_chunked_backward_paths():
    """Exercise the long-context structures at SMALL T by shrinking the
    internal tile caps: multiple q/k-superblocks per head, batch-head
    chunked calls, and the q-chunked host-split backward — the paths
    real CPU tests never reach (they all fit one superblock) and that
    only long-T chip runs would otherwise cover (round-3)."""
    import importlib

    fa = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
    orig_inner = fa._inner_block
    orig_chunk = fa._BWD_Q_CHUNK

    def small_inner(n, cap=512):
        # superblock cap 128, tile cap 64 -> nsb up to 4 at T=512
        return orig_inner(n, 128 if cap == 2048 else 64)

    fa._inner_block = small_inner
    fa._BWD_Q_CHUNK = 256
    try:
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 2, 32),
                              jnp.float32)
        got = fa.flash_attention(q, q, q, causal=True)
        q3 = jnp.moveaxis(q, 2, 1).reshape(2, 512, 32)
        want = fa._reference_attention(q3, q3, q3, 32 ** -0.5, True, 0, 0)
        want = jnp.moveaxis(want.reshape(1, 2, 512, 32), 1, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

        g1 = jax.grad(lambda x: jnp.sum(
            fa.flash_attention(x, x, x, causal=True)))(q)

        def ref_loss(x):
            x3 = jnp.moveaxis(x, 2, 1).reshape(2, 512, 32)
            return jnp.sum(fa._reference_attention(
                x3, x3, x3, 32 ** -0.5, True, 0, 0))

        g2 = jax.grad(ref_loss)(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-4, atol=2e-5)
        # tq > _BWD_Q_CHUNK with tq NOT a multiple of it (384 % 256):
        # the backward must pick the largest dividing chunk (128) and
        # stay on the fused path, not run full-T or fall to jnp
        # (advisor r3 / r4 review)
        q_nd = jax.random.normal(jax.random.PRNGKey(1), (1, 384, 2, 32),
                                 jnp.float32)
        g3 = jax.grad(lambda x: jnp.sum(
            fa.flash_attention(x, x, x, causal=True)))(q_nd)

        def ref_loss_nd(x):
            x3 = jnp.moveaxis(x, 2, 1).reshape(2, 384, 32)
            return jnp.sum(fa._reference_attention(
                x3, x3, x3, 32 ** -0.5, True, 0, 0))

        g4 = jax.grad(ref_loss_nd)(q_nd)
        np.testing.assert_allclose(np.asarray(g3), np.asarray(g4),
                                   rtol=2e-4, atol=2e-5)
    finally:
        fa._inner_block = orig_inner
        fa._BWD_Q_CHUNK = orig_chunk


@pytest.mark.parametrize("h,hk,dh,kv_offset", [
    (2, 2, 32, 0), (2, 2, 32, 64), (2, 2, 128, 0), (4, 2, 128, 0),
    (2, 2, 128, 64)], ids=["per_head", "per_head_masked_rows",
                           "lane_dense", "lane_dense_group2",
                           "lane_dense_masked_rows"])
def test_bwd_2d_host_tiling_matches_reference(monkeypatch, h, hk, dh,
                                              kv_offset):
    """The r5 long-sequence backward (2-D q x k host tiling over the
    fused kernel, global softmax stats per tile, causal tile skipping)
    must equal the jnp reference grads, one head an entry (slices) and
    in the block's own layout (index maps over the whole operands,
    grouped too). Forced tiny tiles so the path runs at test-sized T,
    and grid caps so small that the calls split into parts (of the
    batch-head axis, or of one buffer by aliasing). Where kv_offset
    passes q_offset rows can be fully masked: no tile may be skipped."""
    import sys

    import deeplearning4j_tpu.ops.flash_attention  # noqa: F401
    # sys.modules lookup: the ops package re-exports the
    # flash_attention FUNCTION under the same name, so an attribute
    # import would shadow the module
    fa = sys.modules["deeplearning4j_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_BWD_K_CHUNK", 128)
    monkeypatch.setattr(fa, "_BWD_LONG_TILE", 128)
    # also force the r5 host-level FORWARD q split (independent chunks,
    # per-row stats) so fwd+bwd chunked paths are covered together
    monkeypatch.setattr(fa, "_FWD_Q_CHUNK", 256)
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    # superblocks of 128 rows (two k-superblocks from 256 keys on), tiles
    # of 64, and caps that split every grid with superblocks
    inner, ksb = fa._inner_block, fa._k_superblock
    monkeypatch.setattr(fa, "_inner_block", lambda n, cap=512: inner(
        n, 128 if cap == fa._FWD_QSB else 64))
    monkeypatch.setattr(fa, "_k_superblock", lambda sk, bk: (
        sk // 2 if sk >= 256 and sk % (2 * bk) == 0 else ksb(sk, bk)))
    for cap in ("_MAX_2D_GRID_FWD", "_MAX_2D_GRID_BWD", "_MAX_LANE_GRID_FWD"):
        monkeypatch.setattr(fa, cap, 4)
    rng = np.random.RandomState(0)
    B, T = 2, 512
    q = jnp.asarray(rng.randn(B, T, h, dh), jnp.float32)
    k, v = (jnp.asarray(rng.randn(B, T, hk, dh), jnp.float32)
            for _ in range(2))
    kw = dict(kv_offset=kv_offset)
    for causal in (True, False):
        def loss_kernel(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=causal, **kw).astype(jnp.float32) ** 2)

        # tiled grads (chunk attrs forced small by the monkeypatches)
        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)

        # 1) vs the UNCHUNKED fused kernel: tiling is a pure
        #    re-scheduling, so this must match tightly
        with monkeypatch.context() as mp:
            mp.setattr(fa, "_BWD_K_CHUNK", 1 << 20)
            mp.setattr(fa, "_FWD_Q_CHUNK", 1 << 20)
            gu = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gu, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5,
                err_msg=f"tiled vs unchunked d{name} causal={causal}")

        # 2) vs the TRUE jnp reference (kernel dispatch forced OFF —
        #    without this the 'reference' is the kernel itself):
        #    tolerance covers the kernel's f32-accumulation-order
        #    noise at grad scale ~5 (~1.3e-2 max-abs, present in the
        #    unchunked kernel too)
        with monkeypatch.context() as mp:
            mp.setenv("DL4JTPU_FLASH", "0")

            def loss_ref(q, k, v):
                from deeplearning4j_tpu.nn.layers.attention import \
                    dot_product_attention
                return jnp.sum(dot_product_attention(
                    q, k, v, causal=causal, **kw).astype(jnp.float32) ** 2)

            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-2,
                err_msg=f"tiled vs jnp d{name} causal={causal}")


@pytest.mark.parametrize("name", ["flash_attention", "flash_decode"])
def test_the_kernel_file_reads_one_environment_key(name):
    """DL4JTPU_FLASH (the platform's path: auto, 0, interpret) is the
    only environment key an attention kernel file reads: the tile
    sizes are module constants, which a test that needs another size
    sets on the module."""
    import ast
    import pathlib

    import deeplearning4j_tpu.ops as ops
    tree = ast.parse((pathlib.Path(ops.__file__).parent
                      / f"{name}.py").read_text())
    keys, reads = set(), 0
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and ast.unparse(node.func) in (
                "os.environ.get", "os.getenv"):
            keys.add(ast.literal_eval(node.args[0]))
            reads += 1
        elif isinstance(node, ast.Subscript) and ast.unparse(
                node.value) == "os.environ":
            keys.add(ast.literal_eval(node.slice))
            reads += 1
    assert keys == {"DL4JTPU_FLASH"}
    # no other way in: every mention of the environment is such a read
    assert reads == sum(
        isinstance(n, ast.Attribute)
        and ast.unparse(n) in ("os.environ", "os.getenv")
        for n in ast.walk(tree))
