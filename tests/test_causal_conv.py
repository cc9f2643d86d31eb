"""The causal convolution's Pallas kernels (ops/causal_conv.py) through the
interpreter against the jnp form the mixers computed before them, value and
every gradient, in the three forms the mixers call: bias and SiLU (Mamba-2),
SiLU (Gated DeltaNet), two gates (LFM2's short convolution). The programs'
blocks are made small here so that a sequence spans three blocks of
positions and a block two steps of rows: the carried rows cross both edges,
forward and backward. The operands are read where they lie, lanes inside a
wider array, and the batch has two rows, so the taps' gradients sum over
rows. The kernels on the chip: tests/test_tpu_compile.py compiles them at
the cells' sizes.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import layer_kinds as lk
from deeplearning4j_tpu.ops import causal_conv as cc

F32 = jnp.float32


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """Programs of 32 rows x 128 lanes, worked 16 rows at a time."""
    monkeypatch.setattr(cc, "BLOCK", 32 * 128)
    monkeypatch.setattr(cc, "SUB", 16 * 128)


def normal(seed, shape, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             F32).astype(dtype)


# the three forms, each on a [2, T, 896] array: (x, pre, post) as lane
# ranges, bias, act. DeltaNet's x gathers three ranges out of order.
FORMS = {
    "bias+silu": (((128, 384),), None, None, True, "silu"),
    "silu": (((512, 640), (128, 256), (768, 896)), None, None, False,
             "silu"),
    "gates": (((512, 768),), ((0, 256),), ((256, 512),), False, None),
}


def call(src, w, bias, form, kernel):
    xr, pr, qr, _, act = FORMS[form]
    view = (lambda r: None if r is None else cc.Lanes(src, r))
    return cc.causal_conv(view(xr), w, bias, act=act, pre=view(pr),
                          post=view(qr), kernel=kernel)


def operands(form, taps, t, dtype=F32):
    xr = FORMS[form][0]
    c = sum(b - a for a, b in xr)
    src = normal(1, (2, t, 896), dtype)
    w = 0.5 * normal(2, (taps, c))
    bias = normal(3, (c,)) if FORMS[form][3] else None
    return src, w, bias


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_kernels_match_the_jnp_form(form, taps):
    """T 96: three blocks of 32 rows, each two steps of 16."""
    src, w, bias = operands(form, taps, 96)
    got, want = (call(src, w, bias, form, k) for k in (True, False))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    cot = normal(4, got.shape)
    args = (0, 1, 2) if bias is not None else (0, 1)

    def grads(kernel):
        return jax.grad(lambda *a: jnp.sum(
            call(*a, form, kernel) * cot), args)(src, w, bias)

    for g, h in zip(grads(True), grads(False)):
        assert g.shape == h.shape and g.dtype == h.dtype
        np.testing.assert_allclose(g, h, rtol=1e-5, atol=1e-4)


def test_the_lanes_the_operands_do_not_read_get_nought():
    src, w, bias = operands("bias+silu", 4, 48)
    g = jax.grad(lambda s: jnp.sum(call(s, w, bias, "bias+silu", True)))(src)
    assert not np.any(np.asarray(g[..., :128]))
    assert not np.any(np.asarray(g[..., 384:]))
    assert np.all(np.abs(np.asarray(g[..., 128:384])).sum(axis=1) > 0)


def test_in_bfloat16_the_kernels_round_once():
    """The gates and the taps in float32, one rounding at the output: nearer
    the float32 answer over the same bfloat16 operands than the jnp form,
    which rounds the pre-gated input and the convolution's output too."""
    src, w, _ = operands("gates", 3, 64, jnp.bfloat16)
    exact = call(src.astype(F32), w, None, "gates", False)
    got = call(src, w, None, "gates", True)
    old = call(src, w, None, "gates", False)
    assert got.dtype == old.dtype == jnp.bfloat16

    def gap(a):
        return float(jnp.mean(jnp.abs(a.astype(F32) - exact)))
    assert gap(got) < gap(old)
    assert float(jnp.max(jnp.abs(got.astype(F32) - exact))) <= \
        float(jnp.max(jnp.abs(exact))) * 2 ** -8


def test_wide_lane_blocks_and_a_single_block_of_positions(monkeypatch):
    """Channels in whole 256-lane blocks take programs of 256 lanes; a
    sequence shorter than a block is one block (the carried rows start
    and end at nought)."""
    monkeypatch.setattr(cc, "BLOCK", 1 << 20)
    src, w, bias = operands("bias+silu", 4, 32)
    x = cc.lanes(src, (512, 768))
    assert cc._tiling([x], w) == (256, 32)
    got, want = (cc.causal_conv(x, w, bias, act="silu", kernel=k)
                 for k in (True, False))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def counter():
    from deeplearning4j_tpu.observability.metrics import default_registry
    return default_registry().counter(
        "causal_conv_calls", "", labelnames=("path", "prologue", "epilogue"))


def layout():
    from deeplearning4j_tpu.observability.tracing import default_spans
    return [sp.args for sp in default_spans().snapshot().spans
            if sp.name == "causal_conv.layout"]


@pytest.mark.parametrize("form,labels,lanes_", [
    ("bias+silu", ("none", "bias+silu"), 128), ("silu", ("none", "silu"), 128),
    ("gates", ("gate", "gate"), 256)])
def test_a_trace_counts_its_path_and_leaves_its_mark(form, labels, lanes_):
    """The gates' three operands lie in whole 256-lane blocks: programs of
    256 lanes and, for the same elements, 16 rows."""
    calls = counter()
    src, w, bias = operands(form, 4, 64)
    before = [calls.labels(p, *labels).value for p in ("kernel", "jnp")]
    marks = len(layout())
    jax.eval_shape(lambda s: call(s, w, bias, form, True), src)
    jax.eval_shape(lambda s: call(s, w, bias, form, False), src)
    assert [calls.labels(p, *labels).value - v for p, v in
            zip(("kernel", "jnp"), before)] == [1, 1]
    got = layout()
    assert len(got) == marks + 1          # the kernel's trace alone
    assert got[-1] == {"channels": w.shape[1], "taps": 4,
                       "lane_block": lanes_, "t_block": 4096 // lanes_}


def test_channels_off_the_lane_blocks_take_the_jnp_form(monkeypatch):
    """On the chip (its gate steered here) channels that are not whole
    128-lane blocks, or lanes read from off a block's edge, are the jnp
    form's; forcing the kernels on them is an error."""
    from deeplearning4j_tpu.ops import pallas_util
    monkeypatch.setattr(pallas_util, "off_chip", lambda: False)
    calls = counter()
    w = normal(2, (4, 96))
    for x in (normal(1, (2, 48, 96)),
              cc.lanes(normal(1, (2, 48, 256)), (64, 160))):
        before = calls.labels("jnp", "none", "silu").value
        y = cc.causal_conv(x, w, act="silu")
        assert calls.labels("jnp", "none", "silu").value == before + 1
        assert y.shape == (2, 48, 96)
        with pytest.raises(ValueError, match="128-lane"):
            cc.causal_conv(x, w, act="silu", kernel=True)


def test_lanes_read_twice_take_the_jnp_form():
    """The gate and the input on the same lanes: their gradients would have
    to be summed, which the kernels' side-by-side cotangent does not do."""
    src = normal(1, (2, 32, 256))
    w = normal(2, (3, 128))
    with pytest.raises(ValueError, match="read once"):
        cc.causal_conv(cc.lanes(src, (0, 128)), w,
                       pre=cc.lanes(src, (0, 128)), kernel=True)
    y = cc.causal_conv(cc.lanes(src, (0, 128)), w,
                       pre=cc.lanes(src, (0, 128)))
    np.testing.assert_allclose(y, cc._jnp_form(
        src[..., :128], w, None, False, src[..., :128], None))


def test_operands_of_unequal_channels_are_refused():
    with pytest.raises(ValueError, match="channels"):
        cc.causal_conv(normal(1, (1, 16, 128)), normal(2, (3, 128)),
                       pre=normal(3, (1, 16, 256)))


# ---------------------------------------------------------------------------
# the mixers that call it
# ---------------------------------------------------------------------------

def mixer_case(kind):
    d = 128
    if kind == "shortconv":
        cfg = types.SimpleNamespace(shortconv_width=3)
        p = {"Win": normal(5, (d, 3 * d)) / d ** 0.5,
             "conv": normal(6, (3, d)), "Wo": normal(7, (d, d)) / d ** 0.5}
        return lk.short_conv, p, cfg
    if kind == "deltanet":          # two key heads, q | k | v | z of 128
        cfg = types.SimpleNamespace(gdn_key_heads=2, gdn_value_heads=2,
                                    gdn_key_dim=d, gdn_value_dim=d, eps=1e-6)
        p = {"Wqkvz": normal(5, (d, 8 * d)) / d ** 0.5,
             "Wba": normal(6, (d, 4)) / d ** 0.5,
             "conv": normal(7, (4, 6 * d)), "A_log": jnp.zeros((2,)),
             "dt_bias": jnp.ones((2,)), "gnorm": jnp.ones((d,)),
             "Wo": normal(8, (2 * d, d)) / 16.0}
        return lk.gated_deltanet, p, cfg
    h, hd, n = 4, 32, 64             # x B C: 128 + 2 x 64 channels
    cfg = types.SimpleNamespace(ssm_heads=h, ssm_head_dim=hd, ssm_state=n,
                                ssm_groups=1, eps=1e-5)
    xbc = h * hd + 2 * n
    p = {"Win": normal(5, (d, h * hd + xbc)) / d ** 0.5,
         "Wdt": normal(6, (d, h)) / d ** 0.5, "conv": normal(7, (4, xbc)),
         "conv_b": normal(8, (xbc,)), "dt_bias": jnp.full((h,), -2.0),
         "A_log": jnp.zeros((h,)), "D": jnp.ones((h,)),
         "gnorm": jnp.ones((h * hd,)), "Wo": normal(9, (h * hd, d)) / 11.3}
    return lk.mamba2, p, cfg


LABELS = {"shortconv": ("gate", "gate"), "mamba2": ("none", "bias+silu"),
          "deltanet": ("none", "silu")}


@pytest.mark.parametrize("kind", sorted(LABELS))
def test_the_mixers_read_their_lanes_in_place(kind, monkeypatch):
    """LFM2's short convolution (B, C and x~ the thirds of one projection),
    Mamba-2's (x B C after z) and Gated DeltaNet's (q, k and v of each key
    head, z left out), kernels against the jnp form, value and every
    gradient."""
    fn, p, cfg = mixer_case(kind)
    x = normal(10, (2, 64, 128))

    def loss(x, p):
        return jnp.sum(fn(x, p, cfg) ** 2)

    want = jax.value_and_grad(loss, (0, 1))(x, p)
    calls = counter()
    before = calls.labels("kernel", *LABELS[kind]).value
    monkeypatch.setattr(lk, "causal_conv", lambda *a, **k: cc.causal_conv(
        *a, **dict(k, kernel=True)))
    got = jax.value_and_grad(loss, (0, 1))(x, p)
    assert calls.labels("kernel", *LABELS[kind]).value == before + 1
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    # float32 sums in another order: to rounding of the gradients' scale
    for name, g, h in [("x", got[1][0], want[1][0])] + [
            (k, got[1][1][k], want[1][1][k]) for k in p]:
        np.testing.assert_allclose(g, h, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(h))),
                                   err_msg=name)
