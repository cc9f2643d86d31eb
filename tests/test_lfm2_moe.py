"""The LFM2 MoE layers (the gated short convolution, grouped-query attention
with q/k norms, a leading dense layer of its own kind, sigmoid-routed
experts with an expert bias and no shared expert, the tied head) against
the plain reference `perfbench/references/lfm2_moe.py`, at small sizes on
the CPU with seeded random weights; the Pallas kernels run through the
interpreter.

float32 comparisons are to rounding; the bfloat16 ones inside the limits
stated beside them, which the same program with its matrix products dropped
to int8 fails.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import layer_kinds as lk
from deeplearning4j_tpu.models import transformer as tf

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench.harness.cells import load_json, load_module  # noqa: E402

ref = load_module(ROOT / "perfbench" / "references" / "lfm2_moe.py",
                  "tests_lfm2_moe_reference")
arch = load_module(ROOT / "perfbench" / "archs" / "lfm2_moe.py",
                   "tests_lfm2_moe_arch")

# the cut's layers: a leading conv layer, then one period that starts with
# attention; 4 query heads on 2 KV heads of 16
SMALL = dict(name="small", hidden_size=64, num_hidden_layers=5,
             num_dense_layers=1, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
             conv_bias=False,
             layer_types=["conv", "full_attention", "conv", "conv", "conv"],
             rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
             router_width=16, num_experts=4, num_experts_per_tok=4,
             moe_intermediate_size=32, routed_scaling_factor=1.0,
             norm_topk_prob=True, use_expert_bias=True, vocab_size=512,
             norm_eps=1e-5, weights_key=4711, router_trained=False,
             activation_dtype="float32")


def normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)     # (the tests run x64)


def model(dtype="float32", **over):
    cfgd = dict(SMALL, activation_dtype=dtype, **over)
    s = arch.sizes(cfgd)
    cfg = arch.program_config(cfgd, s, remat=True, remat_policy="full",
                              xent_chunk=0)
    return s, cfg


def layer_params(s, kind, mlp, seed=0):
    """One layer's leaves, without leading axes, float32, none at a value
    (nought) that hides a term; the bias large enough to change choices."""
    shapes = ref.layer_shapes(s, kind, mlp)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = normal(k, shape)
        if name.startswith("W") or name in ("router", "conv"):
            out[name] = z / np.float32(np.sqrt(shape[-2]))
        else:
            out[name] = 0.1 * z
    return out


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.max(np.abs(b)), 1e-12)
    assert np.max(np.abs(a - b)) <= tol * scale, \
        (np.max(np.abs(a - b)), scale)


MM = ref._mm_fn("f32")
PIECES = {
    "shortconv": ("conv", "dense",
                  lambda x, p, s, cfg: lk.short_conv(x, p, cfg),
                  lambda x, p, s, cfg: ref.short_conv(x, p, s, MM)),
    "attention": ("full_attention", "dense",
                  lambda x, p, s, cfg: lk.grouped_query_attention(x, p, cfg),
                  lambda x, p, s, cfg: ref.attention(x, p, s, MM)),
    "moe": ("conv", "moe", lambda x, p, s, cfg: lk.moe_topk(x, p, cfg),
            lambda x, p, s, cfg: ref.moe(x, p, s, MM)),
    "dense_layer": ("conv", "dense",
                    lambda x, p, s, cfg: lk.layer_forward(
                        x, p, cfg, "shortconv", "swiglu"),
                    lambda x, p, s, cfg: ref.layer(x, p, s, "conv", "dense",
                                                   MM)),
    "conv_expert_layer": ("conv", "moe",
                          lambda x, p, s, cfg: lk.layer_forward(
                              x, p, cfg, "shortconv"),
                          lambda x, p, s, cfg: ref.layer(x, p, s, "conv",
                                                         "moe", MM)),
    "attention_expert_layer": ("full_attention", "moe",
                               lambda x, p, s, cfg: lk.layer_forward(
                                   x, p, cfg, "attention"),
                               lambda x, p, s, cfg: ref.layer(
                                   x, p, s, "full_attention", "moe", MM)),
}
# leaves that get a gradient: the frozen router and the bias get none, and
# a piece alone does not read the rest of a layer's leaves
READ = {"shortconv": 3, "attention": 6, "moe": 2, "dense_layer": 7,
        "conv_expert_layer": 7, "attention_expert_layer": 10}


def compare_piece(piece, s, cfg, t=80, p=None):
    kind, mlp, prog, plain = PIECES[piece]
    p = p or layer_params(s, kind, mlp, seed=3)
    x = normal(jax.random.PRNGKey(1), (2, t, s.hidden_size))
    co = normal(jax.random.PRNGKey(2), x.shape)

    def loss(fn):
        return lambda x_, p_: jnp.sum(fn(x_, p_, s, cfg) * co)

    with jax.default_matmul_precision("highest"):
        close(prog(x, p, s, cfg), plain(x, p, s, cfg), 2e-5)
        gp = jax.grad(loss(prog), argnums=(0, 1))(x, p)
        gr = jax.grad(loss(plain), argnums=(0, 1))(x, p)
    close(gp[0], gr[0], 5e-5)
    read = 0
    for name in gr[1]:
        if float(jnp.max(jnp.abs(gr[1][name]))) == 0:
            assert float(jnp.max(jnp.abs(gp[1][name]))) == 0, name
            continue
        close(gp[1][name], gr[1][name], 1e-4)
        read += 1
    return read


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_piece_matches_the_reference_forward_and_gradients(piece):
    """float32 to rounding: the short-convolution mixer alone (its three
    leaves), attention alone (six), the routed experts, and the three kinds
    of whole layer."""
    s, cfg = model()
    assert compare_piece(piece, s, cfg) == READ[piece]


@pytest.mark.parametrize("t", [1, 2, 3])
def test_a_row_no_longer_than_the_taps(t):
    """T at or under the convolution's width: the missing positions before
    the row's start are nought, in program and reference alike."""
    s, cfg = model()
    assert compare_piece("shortconv", s, cfg, t=t) == 3


def test_four_taps_are_the_references():
    """`conv_L_cache` 4: the program's convolution takes its width from the
    configuration, in the leaf and in the sum."""
    s, cfg = model(conv_L_cache=4)
    assert cfg.shortconv_width == 4
    assert lk.layer_shapes(cfg, "shortconv")["conv"] == (4, s.hidden_size)
    assert compare_piece("shortconv", s, cfg) == 3


def test_a_convolution_bias_is_refused():
    """The family's convolution has no bias; the arch refuses one rather
    than run without it."""
    with pytest.raises(SystemExit, match="no bias"):
        model(conv_bias=True)


def test_the_first_tap_multiplies_the_oldest_position():
    """By hand on one channel: an impulse at position 0 comes out as the
    taps read backwards, `y_t = w[L - 1 - t]`, as the published Conv1d with
    padding L - 1 cut to its first T outputs gives it; and a program whose
    taps were reversed parts from the reference."""
    w = jnp.asarray([[1.0], [10.0], [100.0]], jnp.float32)
    u = jnp.zeros((1, 5, 1), jnp.float32).at[0, 0, 0].set(1.0)
    np.testing.assert_array_equal(np.asarray(lk.causal_conv(u, w))[0, :, 0],
                                  [100.0, 10.0, 1.0, 0.0, 0.0])
    np.testing.assert_array_equal(np.asarray(ref.causal_conv(u, w))[0, :, 0],
                                  [100.0, 10.0, 1.0, 0.0, 0.0])
    s, cfg = model()
    p = layer_params(s, "conv", "dense", seed=3)
    x = normal(jax.random.PRNGKey(1), (2, 16, s.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref.short_conv(x, p, s, MM)
        backwards = lk.short_conv(x, dict(p, conv=p["conv"][::-1]), cfg)
    gap = float(jnp.max(jnp.abs(backwards - want)))
    assert gap > 0.1 * float(jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_the_bias_enters_the_choice_and_not_the_weight():
    """The top_k by `s + b`, the weights `s` over their sum times the
    published 1. By hand on one token, a bias that changes the chosen
    set."""
    x = jnp.eye(4, dtype=jnp.float32)[:1]               # logits = row 0
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3,
                         jnp.float32)
    sc = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    none = jnp.zeros((4,), jnp.float32)
    lifts_last = jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)
    idx, _ = lk.route(x, router, 2, "sigmoid", none, 1.0)
    assert sorted(np.asarray(idx[0])) == [0, 1]
    idx_b, w_b = lk.route(x, router, 2, "sigmoid", lifts_last, 1.0)
    assert sorted(np.asarray(idx_b[0])) == [0, 3]         # 0.269 + 0.6 > s_1
    order = np.argsort(np.asarray(idx_b[0]))
    want = sc[[0, 3]] / (sc[0] + sc[3])                   # s, not s + b
    np.testing.assert_allclose(np.asarray(w_b[0])[order], want, rtol=1e-6)
    for got, plain in zip((idx_b, w_b),
                          ref.route(x, router, lifts_last, 2, 1.0)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   rtol=1e-6)


def test_the_bias_changes_which_experts_a_batch_meets():
    """At the layer's own sizes a bias of N(0, 0.1^2) changes some tokens'
    chosen set; program and reference agree with it, and differ from the
    layer without it."""
    s, cfg = model()
    p = layer_params(s, "conv", "moe", seed=3)
    x = normal(jax.random.PRNGKey(5), (96, s.hidden_size))
    idx, _ = lk.route(x, p["router"], 4, "sigmoid", p["router_bias"], 1.0)
    idx0, _ = lk.route(x, p["router"], 4, "sigmoid",
                       jnp.zeros_like(p["router_bias"]), 1.0)
    changed = int(jnp.sum(jnp.any(jnp.sort(idx, -1) != jnp.sort(idx0, -1),
                                  -1)))
    assert 0 < changed < 96
    want, _ = ref.route(x, p["router"], p["router_bias"], 4, 1.0)
    assert bool(jnp.array_equal(jnp.sort(idx, -1), jnp.sort(want, -1)))


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 experts held 2 at a time: the eight shares' outputs add up to the
    uncut reference layer's. No shared expert: nothing is counted once."""
    s, _ = model(router_width=16, num_experts=16)
    whole = layer_params(s, "conv", "moe", seed=5)
    x = normal(jax.random.PRNGKey(7), (2, 48, s.hidden_size))
    s2, cfg2 = model(router_width=16, num_experts=2)
    assert "Ws_gu" not in lk.layer_shapes(cfg2, "shortconv")
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, whole, s, MM)
        total = jnp.zeros_like(want)
        for first in range(0, 16, 2):
            part = dict(whole, We_gu=whole["We_gu"][first:first + 2],
                        We_down=whole["We_down"][first:first + 2])
            got = lk.moe_topk(x, part, cfg2, first=first)
            total = total + got
            # the reference's own share agrees with the program's
            close(got, ref.moe(x, part, s2, MM, first=first), 2e-5)
    close(total, want, 2e-5)


# ---------------------------------------------------------------------------
# the whole model: the leading layer, the period, the tied head
# ---------------------------------------------------------------------------

def batch(s, rows=2, seq=48, seed=11):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, s.vocab_size, (rows, seq + 1)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


def test_the_tree_is_the_references_and_the_layers_are_where_they_belong():
    s, cfg = model()
    shapes = jax.tree_util.tree_map(
        lambda x: x.shape, lk.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes == ref.leaf_shapes(s)
    assert (cfg.layer_types, cfg.lead_layer_types) == (
        ("attention", "shortconv", "shortconv", "shortconv"), ("shortconv",))
    assert lk.n_periods(cfg) == 1 and "Wout" not in shapes
    lead = shapes["lead"]["l0"]
    assert (lead["Win"], lead["conv"], lead["W_gu"]) == ((64, 192), (3, 64),
                                                         (64, 256))
    assert "Wq" not in lead and "router" not in lead
    assert shapes["blocks"]["r0"]["Wq"] == (1, 1, 64, 64)
    assert shapes["blocks"]["r0"]["qnorm"] == (1, 1, 16)
    assert shapes["blocks"]["r1"]["Win"] == (1, 3, 64, 192)
    assert shapes["blocks"]["r1"]["We_gu"] == (1, 3, 4, 64, 64)
    assert not any(k.startswith("Ws_") for b in shapes["blocks"].values()
                   for k in b)


@pytest.mark.parametrize("over", [
    {},
    dict(num_dense_layers=2, num_hidden_layers=6,
         layer_types=["conv", "conv", "full_attention", "conv", "conv",
                      "conv"]),
    dict(num_hidden_layers=9,
         layer_types=["conv"] + ["full_attention", "conv", "conv",
                                 "conv"] * 2),
    dict(num_dense_layers=0, num_hidden_layers=4,
         layer_types=["full_attention", "conv", "conv", "conv"]),
    dict(conv_L_cache=4),
    dict(routed_scaling_factor=1.5),
    dict(num_experts_per_tok=2),
], ids=["published", "two_lead", "two_periods", "no_lead", "taps4",
        "scale", "top2"])
def test_the_loss_is_the_references(over):
    """The leading short-convolution layer before a period that starts with
    attention, and the tied head's loss."""
    s, cfg = model(**over)
    params = ref.make_init(s)(ref.seed_key(3))
    tok, tgt = batch(s)
    with jax.default_matmul_precision("highest"):
        got = tf.loss_fn(cfg, params, tok, tgt)
        want = ref.loss(s, params, tok, tgt)
        base = ref.loss(model()[0], ref.make_init(model()[0])(
            ref.seed_key(3)), tok, tgt)
    assert abs(float(got) - float(want)) <= 2e-6 * abs(float(want))
    if over:        # and it is not a field that nothing reads
        assert abs(float(want) - float(base)) > 2e-5 * abs(float(base))


def test_a_leading_layer_of_another_kind_than_the_periods_first():
    """`lead_layer_types` names the leading layers' mixers: a conv layer
    before a period that starts with attention, which the period's first
    kind would have made an attention layer."""
    s, cfg = model()
    params = ref.make_init(s)(ref.seed_key(3))
    tok, tgt = batch(s)
    with jax.default_matmul_precision("highest"):
        h = tf.embed_tokens(cfg, params, jnp.asarray(tok))
        got = lk.lead_forward(h, params["lead"], cfg)
        want = ref.layer(h, params["lead"]["l0"], s, "conv", "dense", MM)
    close(got, want, 2e-5)
    with pytest.raises(ValueError, match="lead_layer_types"):
        lk.lead_shapes(dataclasses.replace(
            cfg, lead_layer_types=("shortconv", "shortconv")))


def test_the_gradients_reach_both_uses_of_the_embedding():
    """Every leaf's gradient against the reference's; `embed`'s is the sum
    of the gather's and the tied head's, each a large part of it."""
    s, cfg = model()
    params = ref.make_init(s)(ref.seed_key(3))
    tok, tgt = batch(s)
    n = tgt.size

    def split(e_in, e_out):
        hf = tf.forward_hidden(cfg, dict(params, embed=e_in), tok)
        return tf.head_loss_sum(cfg, dict(params, embed=e_out), hf, tgt) / n

    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: tf.loss_fn(cfg, p, tok, tgt))(params)
        want = jax.grad(lambda p: ref.loss(s, p, tok, tgt))(params)
        g_in, g_out = jax.grad(split, argnums=(0, 1))(params["embed"],
                                                      params["embed"])
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        name = ".".join(k.key for k in path)
        frozen = path[-1].key in ("router", "router_bias")
        assert (float(jnp.max(jnp.abs(flat_w[path]))) == 0) == frozen, name
        if frozen:
            assert float(jnp.max(jnp.abs(g))) == 0, name
        else:
            close(g, flat_w[path], 2e-4)
    close(g_in + g_out, got["embed"], 1e-5)
    whole = np.linalg.norm(np.asarray(got["embed"]))
    for part in (g_in, g_out):
        assert np.linalg.norm(np.asarray(part)) > 0.05 * whole


def test_the_counts_by_hand():
    """The configuration's file at its own sizes: 469,285,248 parameters
    held, as the configuration's deployment reckons them."""
    cfgd = load_json(ROOT / "perfbench" / "configs" / "lfm2-24b-a2b.json")
    s = arch.sizes(cfgd)
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 64 + 64
    assert (conv, attn) == (16783360, 10485888)
    dense = conv + 2 * 2048 + 3 * 2048 * 11776
    outside = 2048 * 64 + 64
    experts = 8 * 3 * 2048 * 1536
    conv_layer = conv + 2 * 2048 + outside + experts
    attn_layer = attn + 2 * 2048 + outside + experts
    ends = 8192 * 2048 + 2048
    assert (dense, outside, experts, conv_layer, attn_layer, ends) == (
        89139200, 131136, 75497472, 92416064, 86118592, 16779264)
    total = dense + 3 * conv_layer + attn_layer + ends
    assert arch.held_params(s) == total == 469285248
    assert 8 * 8192 == 65536 and s.n_expert_layers == 4
    # and the program's tree at these sizes holds as many
    cfg = arch.program_config(cfgd, s, remat=True, remat_policy="full",
                              xent_chunk=0)
    tree = {"embed": (8192, 2048), "lnfg": (2048,),
            "lead": lk.lead_shapes(cfg),
            "blocks": {key: {k: (1,) + lead + v for k, v in
                             lk.layer_shapes(cfg, kind).items()}
                       for key, kind, lead in lk.block_keys(cfg)}}
    assert tree == ref.leaf_shapes(s)
    assert sum(int(np.prod(sh)) for sh in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple))) == 469285248


def test_the_mark_the_counter_and_the_scopes_of_a_traced_step():
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import default_spans
    s, cfg = model()
    calls = default_registry().counter("shortconv_calls", "")
    before = calls.value
    tok, tgt = batch(s)
    lowered = jax.jit(lambda p: jax.grad(
        lambda q: tf.loss_fn(cfg, q, tok, tgt))(p)).lower(
            lk.init_params(cfg, jax.random.PRNGKey(0)))
    # the lead layer and the scanned run of three, each again for its
    # rematerialised backward
    assert calls.value - before >= 2
    spans = default_spans().snapshot().spans
    last = lambda name: [sp for sp in spans if sp.name == name][-1].args  # noqa: E731,E501
    assert last("shortconv.layout") == {"channels": 64, "taps": 3,
                                        "gates": 2}
    assert last("moe.share")["scoring"] == "sigmoid"
    text = lowered.as_text(debug_info=True)
    for scope in ("shortconv", "shortconv.conv", "lead", "attn",
                  "moe.experts", "head_loss"):
        assert scope in text, scope
    assert "moe.shared" not in text


# ---------------------------------------------------------------------------
# three steps through the Megatron entry on a one-device mesh
# ---------------------------------------------------------------------------

ROWS, SEQ, LR = 4, 96, 1e-5
# bfloat16 activations against the float32 reference at this size, at the
# cell's Adam 1e-5; the program with int8 matrix products fails four of them
# (readings at this size, loss 1 / loss 3 / norm / update / direction:
# bfloat16 4.4e-5 / 4.9e-5 / 0.0147 (a norm) / 0.0202 (a norm) / 0.0026;
# int8 2.0e-4 / 2.4e-5 / 0.0351 / 0.0293 (the taps) / 0.0128)
BF16_LIMITS = {"loss_step1": 1e-4, "loss_step3": 1.5e-4,
               "grad_norm_gap": 0.025, "update_norm_gap": 0.025,
               "grad_direction_gap": 6e-3}
# float32: rounding, through three Adam steps (whose first divides a
# gradient by its own size, so a leaf with a gradient near nought moves by
# its rounding)
F32_LIMITS = {"loss_step1": 2e-6, "loss_step3": 5e-5, "grad_norm_gap": 2e-4,
              "update_norm_gap": 1e-2, "grad_direction_gap": 1e-6}


def three_steps(dtype):
    """The harness's own readings of the first three steps, program and
    reference, and its comparison."""
    from jax.sharding import NamedSharding

    from deeplearning4j_tpu.parallel.megatron import (
        make_parallel_train_step, param_specs)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.optim import init_adam_state
    from perfbench.harness import train

    s, cfg = model(dtype)
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), param_specs(cfg),
        is_leaf=lambda x: not isinstance(x, dict))
    init = ref.make_init(s, shardings)
    params = init(ref.seed_key(0))
    step = make_parallel_train_step(cfg, mesh, learning_rate=LR)
    rng = np.random.default_rng(11)
    tok = rng.integers(0, s.vocab_size, (3, ROWS, SEQ + 1)).astype(np.int32)
    batches = [(tok[i, :, :-1], tok[i, :, 1:]) for i in range(3)]
    with jax.default_matmul_precision("highest"):
        got = train.first_steps(ref, step, params, init_adam_state(params),
                                batches, init, 0)
    got.pop("state")
    tr = {"learning_rate": LR, "check": {"ref_rows_per_block": 2}}
    want = train.reference_readings(ref, s, batches, tr, 0, mesh)
    return s, got, want


@pytest.mark.parametrize("dtype,limits", [("float32", F32_LIMITS),
                                          ("bfloat16", BF16_LIMITS)])
def test_three_steps_through_the_megatron_entry(dtype, limits):
    from perfbench.harness import train
    s, got, want = three_steps(dtype)
    compared = train.compare_readings(got, want, limits, ref.leaf_names(s))
    assert train.is_correct(compared), compared


def test_int8_matrix_products_fail_the_bfloat16_limits(monkeypatch):
    from perfbench.harness import train
    low = ref._low("int8")
    monkeypatch.setattr(
        lk, "_mm", lambda x, w: low(x.astype(jnp.float32), w).astype(x.dtype))
    s, got, want = three_steps("bfloat16")
    compared = train.compare_readings(got, want, BF16_LIMITS,
                                      ref.leaf_names(s))
    assert not train.is_correct(compared), compared


def test_the_weights_are_the_keys_and_the_bias_is_not_nought():
    s, _ = model()
    a = ref.make_init(s)(ref.seed_key(1))
    b = ref.make_init(s)(ref.seed_key(2 ** 33 + 5))
    other = ref.make_init(dataclasses.replace(s, weights_key=4712))(
        ref.seed_key(1))
    assert bool(jnp.array_equal(a["embed"], b["embed"]))
    assert not bool(jnp.array_equal(a["embed"], other["embed"]))
    bias = np.asarray(a["blocks"]["r1"]["router_bias"])
    assert 0.003 < float(np.std(bias)) < 0.03


# ---------------------------------------------------------------------------
# where it does not run
# ---------------------------------------------------------------------------

def test_serving_refuses_the_short_convolution_by_name():
    from deeplearning4j_tpu.parallel import serving
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="short convolution "
                       r"\(shortconv_width=3\).*last 2 positions.*"
                       "lead_dense_layers=1"):
        serving.make_paged_decode(cfg, mesh, 4, 2, 16, 16, 32)
    with pytest.raises(ValueError, match="shortconv_width=3"):
        serving._check_spec(cfg, 2, 0)


@pytest.mark.parametrize("axis", ["model", "seq", "pipe"])
def test_the_axes_that_do_not_divide_it_refuse_it_by_name(axis):
    from deeplearning4j_tpu.parallel.megatron import make_parallel_train_step
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    # two periods, so that a pipe of two would divide them
    _, cfg = model(num_hidden_layers=9, layer_types=["conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2)
    mesh = make_mesh(MeshSpec(**{axis: 2}), devices=jax.devices()[:2])
    what = "lead_dense_layers=1" if axis == "pipe" else \
        "gated short convolution"
    with pytest.raises(ValueError, match=what):
        make_parallel_train_step(cfg, mesh)
