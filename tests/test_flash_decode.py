"""Decode-attention kernel vs jnp reference (VERDICT r3 #2): the
split-K Pallas kernel must reproduce the reference decode numerics at
every prefix length, including block boundaries and traced positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.flash_decode import (
    decode_attention, decode_attention_available,
    reference_decode_attention)


def _mk(b, h, dh, s, dtype, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = h * dh
    q = jax.random.normal(kq, (b, h, dh), dtype)
    k = jax.random.normal(kk, (b, s, d), dtype)
    v = jax.random.normal(kv, (b, s, d), dtype)
    return q, k, v


@pytest.fixture()
def interpret_mode(monkeypatch):
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")


@pytest.mark.parametrize("pos", [0, 5, 255, 256, 300, 511])
def test_kernel_matches_reference_at_every_prefix(interpret_mode, pos):
    q, k, v = _mk(4, 4, 16, 512, jnp.float32)
    assert decode_attention_available(q, k)
    out = decode_attention(q, k, v, pos, n_heads=4)
    ref = reference_decode_attention(q, k, v, pos, n_heads=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_bfloat16(interpret_mode):
    q, k, v = _mk(2, 4, 16, 256, jnp.bfloat16, seed=1)
    out = decode_attention(q, k, v, 200, n_heads=4)
    ref = reference_decode_attention(q, k, v, 200, n_heads=4)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_kernel_traced_pos_in_scan(interpret_mode):
    """pos is traced inside generate's sampling scan — the prefetched
    scalar must work with a dynamic value."""
    q, k, v = _mk(2, 4, 16, 512, jnp.float32, seed=2)

    def step(pos, _):
        return pos + 7, decode_attention(q, k, v, pos, n_heads=4)

    _, outs = jax.lax.scan(step, jnp.asarray(3, jnp.int32), None,
                           length=4)
    for i, pos in enumerate([3, 10, 17, 24]):
        ref = reference_decode_attention(q, k, v, pos, n_heads=4)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_kernel_stacked_cache_layer_select(interpret_mode, layer):
    """The [L, B, S, D] stacked-cache path (layer plane selected in the
    BlockSpec — the no-copy fast path _block_decode uses) must equal
    the per-layer reference."""
    L, b, h, dh, s = 3, 2, 4, 16, 512
    d = h * dh
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (b, h, dh), jnp.float32)
    ks = jax.random.normal(kk, (L, b, s, d), jnp.float32)
    vs = jax.random.normal(kv, (L, b, s, d), jnp.float32)
    out = decode_attention(q, ks, vs, 300, n_heads=4, layer=layer)
    ref = reference_decode_attention(q, ks[layer], vs[layer], 300,
                                     n_heads=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fallback_when_unavailable(monkeypatch):
    """Short caches / odd head dims take the jnp reference path."""
    monkeypatch.delenv("DL4JTPU_FLASH", raising=False)
    q, k, v = _mk(2, 2, 12, 64, jnp.float32, seed=3)
    assert not decode_attention_available(q, k)
    out = decode_attention(q, k, v, 30, n_heads=2)
    ref = reference_decode_attention(q, k, v, 30, n_heads=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("stacked", [False, True])
def test_kernel_vector_pos_matches_per_row_reference(interpret_mode,
                                                     stacked):
    """PER-ROW positions (the slotted/paged call sites: every slot is
    at its OWN prefix) must equal the per-row scalar reference — on
    the kernel path, where the rows' positions arrive as a VMEM block
    and their per-batch-block maximum, the prefetched scalar, bounds
    the block's DMA at its furthest row."""
    b, h, dh, s = 4, 4, 16, 512
    pos = np.array([3, 255, 256, 500], np.int32)
    if stacked:
        L = 2
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(kq, (b, h, dh), jnp.float32)
        k = jax.random.normal(kk, (L, b, s, h * dh), jnp.float32)
        v = jax.random.normal(kv, (L, b, s, h * dh), jnp.float32)
        out = decode_attention(q, k, v, jnp.asarray(pos), n_heads=h,
                               layer=1)
        k, v = k[1], v[1]
    else:
        q, k, v = _mk(b, h, dh, s, jnp.float32, seed=10)
        out = decode_attention(q, k, v, jnp.asarray(pos), n_heads=h)
    for i in range(b):
        ref = reference_decode_attention(q[i:i + 1], k[i:i + 1],
                                         v[i:i + 1], int(pos[i]),
                                         n_heads=h)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(ref), rtol=2e-5,
                                   atol=2e-5)


def test_vector_pos_reference_matches_slotted_formula():
    """The fused slotted decode call site (parallel/serving.py
    `_local_block_decode_slotted`) replaced a hand-rolled masked
    softmax with decode_attention(pos_vector) — the PORTED parity
    assertion: both formulations bit-agree on the jnp path."""
    b, h, dh, s = 3, 4, 16, 96
    q, k, v = _mk(b, h, dh, s, jnp.float32, seed=5)
    pos = jnp.asarray([0, 40, 95], jnp.int32)
    out = decode_attention(q, k, v, pos, n_heads=h)
    from deeplearning4j_tpu.ops.flash_decode import NEG_INF
    kh = k.reshape(b, s, h, dh)
    vh = v.reshape(b, s, h, dh)
    sc = jnp.einsum("bhd,bshd->bhs", q, kh).astype(jnp.float32) \
        * (1.0 / dh ** 0.5)
    sc = jnp.where(jnp.arange(s)[None, None, :]
                   <= pos[:, None, None], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    want = jnp.einsum("bhs,bshd->bhd", pr.astype(q.dtype), vh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_scale_folded_reference_matches_quant_formula():
    """The quantized call sites (`_local_block_decode_slotted_q` /
    `_local_block_decode_paged_q`) fold per-row K/V scales through
    decode_attention(k_scale=, v_scale=) — ported parity vs the
    hand-rolled quantized attention they replaced, INCLUDING the
    multiplication order (row scale before 1/sqrt(d)), which is what
    keeps the fusion bit-identical."""
    from deeplearning4j_tpu.ops.flash_decode import NEG_INF
    from deeplearning4j_tpu.quant.kv import quantize_rows
    b, h, dh, s = 2, 4, 16, 64
    _, kf, vf = _mk(b, h, dh, s, jnp.float32, seed=6)
    q = jax.random.normal(jax.random.PRNGKey(9), (b, h, dh),
                          jnp.float32)
    kq, ks = quantize_rows(kf, "int8")
    vq, vs = quantize_rows(vf, "int8")
    pos = jnp.asarray([17, 63], jnp.int32)
    out = decode_attention(q, kq, vq, pos, n_heads=h, k_scale=ks,
                           v_scale=vs)
    kh = kq.astype(jnp.float32).reshape(b, s, h, dh)
    vh = vq.astype(jnp.float32).reshape(b, s, h, dh)
    sc = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kh) \
        * ks[:, None, :] * (1.0 / dh ** 0.5)
    sc = jnp.where(jnp.arange(s)[None, None, :]
                   <= pos[:, None, None], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    want = jnp.einsum("bhs,bshd->bhd", pr * vs[:, None, :],
                      vh).astype(q.dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    # and the dequantized result is close to the float attention
    ref = reference_decode_attention(q, kf, vf, 63, n_heads=h)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]),
                               rtol=0.05, atol=0.05)


def test_reference_matches_block_decode_semantics():
    """reference_decode_attention == the shared attention core's jnp
    path at q-length 1 (what _block_decode used before the kernel):
    same masking, same softmax dtype contract."""
    from deeplearning4j_tpu.nn.layers.attention import \
        dot_product_attention
    b, h, dh, s = 2, 4, 16, 128
    q, k, v = _mk(b, h, dh, s, jnp.float32, seed=4)
    pos = 77
    ref = reference_decode_attention(q, k, v, pos, n_heads=h)
    old = dot_product_attention(q[:, None].reshape(b, 1, h, dh),
                                k.reshape(b, s, h, dh),
                                v.reshape(b, s, h, dh),
                                causal=True, q_offset=pos, kv_offset=0)
    np.testing.assert_allclose(np.asarray(ref),
                               np.asarray(old[:, 0]), rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# K+1-window verify attention (ISSUE-19): the spec verify pass routes
# its [B, T, H, Dh] window through the vector-pos kernel with the
# window folded into pseudo-heads
# ---------------------------------------------------------------------------

def _mk_window(b, t, h, dh, s, dtype, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = h * dh
    q = jax.random.normal(kq, (b, t, h, dh), dtype)
    k = jax.random.normal(kk, (b, s, d), dtype)
    v = jax.random.normal(kv, (b, s, d), dtype)
    return q, k, v


@pytest.mark.parametrize("pos", [[0, 5, 255, 500], [250, 251, 252, 253],
                                 [508, 509, 510, 511]])
def test_window_kernel_matches_reference(interpret_mode, pos):
    """The window-as-pseudo-heads kernel must equal the jnp window
    reference at every per-row prefix — including rows whose K+1
    window straddles a block boundary and rows clipped at the cache
    end (pos + t - 1 > s - 1)."""
    from deeplearning4j_tpu.ops.flash_decode import (
        decode_window_attention, reference_window_attention,
        window_attention_available)
    b, t, h, dh, s = 4, 5, 4, 16, 512
    q, k, v = _mk_window(b, t, h, dh, s, jnp.float32)
    assert window_attention_available(q, k)
    pv = jnp.asarray(pos, jnp.int32)
    out = decode_window_attention(q, k, v, pv, n_heads=h)
    ref = reference_window_attention(q, k, v, pv, n_heads=h)
    assert out.shape == (b, t, h, dh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_window_kernel_stacked_cache_layer_select(interpret_mode):
    """The verify pass hands the kernel the STACKED [L, B, S, D] pool
    and a layer index (no-copy plane select in the BlockSpec)."""
    from deeplearning4j_tpu.ops.flash_decode import (
        decode_window_attention, reference_window_attention)
    L, b, t, h, dh, s = 2, 2, 3, 4, 16, 256
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(kq, (b, t, h, dh), jnp.float32)
    ks = jax.random.normal(kk, (L, b, s, h * dh), jnp.float32)
    vs = jax.random.normal(kv, (L, b, s, h * dh), jnp.float32)
    pos = jnp.asarray([30, 200], jnp.int32)
    out = decode_window_attention(q, ks, vs, pos, n_heads=h, layer=1)
    ref = reference_window_attention(q, ks[1], vs[1], pos, n_heads=h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_window_kernel_scale_folded_quant(interpret_mode):
    """Per-row int8 K/V scales fold through the window kernel exactly
    as they do in the scalar decode kernel: row scale applied before
    1/sqrt(d), value scale on the probabilities."""
    from deeplearning4j_tpu.ops.flash_decode import (
        decode_window_attention, reference_window_attention)
    from deeplearning4j_tpu.quant.kv import quantize_rows
    b, t, h, dh, s = 2, 3, 4, 16, 256
    q, kf, vf = _mk_window(b, t, h, dh, s, jnp.float32, seed=21)
    kq8, ksc = quantize_rows(kf, "int8")
    vq8, vsc = quantize_rows(vf, "int8")
    pos = jnp.asarray([17, 250], jnp.int32)
    kqf = kq8.astype(jnp.float32)
    vqf = vq8.astype(jnp.float32)
    out = decode_window_attention(q, kqf, vqf, pos, n_heads=h,
                                  k_scale=ksc, v_scale=vsc)
    ref = reference_window_attention(q, kqf, vqf, pos, n_heads=h,
                                     k_scale=ksc, v_scale=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # and close to the float window attention after dequantization
    fref = reference_window_attention(q, kf, vf, pos, n_heads=h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fref),
                               rtol=0.05, atol=0.05)


def test_window_reference_matches_verify_phase_formula():
    """PORTED parity: reference_window_attention reproduces the
    hand-rolled masked softmax the spec verify pass used before
    ISSUE-19, bit for bit — this is what keeps the fused verify
    token-identical to the sync engine."""
    from deeplearning4j_tpu.ops.flash_decode import (
        NEG_INF, reference_window_attention)
    b, t, h, dh, s = 3, 4, 4, 16, 96
    q, k, v = _mk_window(b, t, h, dh, s, jnp.float32, seed=8)
    pos = jnp.asarray([0, 40, 93], jnp.int32)
    out = reference_window_attention(q, k, v, pos, n_heads=h)
    kh = k.reshape(b, s, h, dh)
    vh = v.reshape(b, s, h, dh)
    posw = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    wp = jnp.clip(posw, 0, s - 1)
    sc = jnp.einsum("bthd,bshd->bhts", q, kh).astype(jnp.float32) \
        * (1.0 / dh ** 0.5)
    sc = jnp.where(jnp.arange(s)[None, None, None, :]
                   <= wp[:, None, :, None], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    want = jnp.einsum("bhts,bshd->bthd", pr.astype(q.dtype), vh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_window_fallback_when_unavailable(monkeypatch):
    """Short caches drop to the jnp window reference, same availability
    contract as scalar decode."""
    monkeypatch.delenv("DL4JTPU_FLASH", raising=False)
    from deeplearning4j_tpu.ops.flash_decode import (
        decode_window_attention, reference_window_attention,
        window_attention_available)
    q, k, v = _mk_window(2, 3, 2, 12, 64, jnp.float32, seed=3)
    assert not window_attention_available(q, k)
    out = decode_window_attention(q, k, v, jnp.asarray([5, 30]),
                                  n_heads=2)
    ref = reference_window_attention(q, k, v, jnp.asarray([5, 30]),
                                     n_heads=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# the kernels where the serving programs put them: inside
# jax.shard_map(check_vma=True) — a pallas_call's out_shape must carry
# the operands' varying-manual-axes set there, and the interpreter must
# be one that can run on vma-typed blocks (ops/pallas_util.py)
# ---------------------------------------------------------------------------

def test_kernels_inside_shard_map_check_vma(interpret_mode, devices8):
    """Vector-position decode and the K+1 window kernel, traced inside
    shard_map(check_vma=True) over a (data=2, model=2) mesh with the
    serving programs' layout (slots over 'data', heads over 'model',
    positions varying over 'data' only), against the unsharded
    reference."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.ops.flash_decode import (
        decode_window_attention, reference_window_attention,
        window_attention_available)
    mesh = Mesh(np.array(devices8[:4]).reshape(2, 2), ("data", "model"))
    L, b, h, dh, s, t = 2, 4, 4, 16, 256, 3
    ks = jax.random.split(jax.random.PRNGKey(31), 4)
    q = jax.random.normal(ks[0], (b, h, dh), jnp.float32)
    qw = jax.random.normal(ks[1], (b, t, h, dh), jnp.float32)
    ck = jax.random.normal(ks[2], (L, b, s, h * dh), jnp.float32)
    cv = jax.random.normal(ks[3], (L, b, s, h * dh), jnp.float32)
    pos = jnp.asarray([0, 77, 128, 250], jnp.int32)
    h_loc = h // 2

    def body(q, qw, ck, cv, pos):
        assert jax.typeof(q).vma == {"data", "model"}
        assert decode_attention_available(q, ck)
        assert window_attention_available(qw, ck)
        a = decode_attention(q, ck, cv, pos, h_loc, layer=1)
        w = decode_window_attention(qw, ck[1], cv[1], pos, h_loc)
        return a, w

    cache = P(None, "data", None, "model")
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", "model"), P("data", None, "model"), cache,
                  cache, P("data")),
        out_specs=(P("data", "model"), P("data", None, "model")),
        check_vma=True))
    a, w = fn(q, qw, ck, cv, pos)
    np.testing.assert_allclose(
        np.asarray(a),
        np.asarray(reference_decode_attention(q, ck[1], cv[1], pos, h)),
        rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(w),
        np.asarray(reference_window_attention(qw, ck[1], cv[1], pos, h)),
        rtol=2e-5, atol=2e-5)


def test_window_batch_block_shrinks_with_the_window(monkeypatch):
    """The window kernel's batch block is the decode kernel's divided
    by T: Mosaic keeps one f32 product per unrolled pseudo-head, and a
    T-row window has T times the decode kernel's pseudo-heads (the 2MB
    block at K+1=5 asked for 28MB of scoped VMEM on the chip)."""
    import jax.experimental.pallas as pl

    from deeplearning4j_tpu.ops import flash_decode as fd
    grids = []
    real = pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        grids.append(grid_spec.grid)
        return real(kernel, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    monkeypatch.setattr(fd, "_BLOCK_BYTES",
                        8 * 128 * 64 * 4)           # 8 rows of [128, 64] f32
    b, h, dh, s = 8, 4, 16, 256
    q, k, v = _mk(b, h, dh, s, jnp.float32)
    pos = jnp.zeros((b,), jnp.int32)
    fd.decode_attention(q, k, v, pos, h)
    qw = jnp.zeros((b, 4, h, dh), jnp.float32)
    fd.decode_window_attention(qw, k, v, pos, h)
    assert grids == [(1, 2), (4, 2)]      # bb = 8, then 8 // 4 = 2
