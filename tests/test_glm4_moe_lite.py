"""The GLM-4.7-Flash layers (latent attention, a leading dense layer,
sigmoid-routed experts with a correction bias and an ungated shared expert,
the multi-token-prediction module and its loss) against the plain reference
`perfbench/references/glm4_moe_lite.py`, at small sizes on the CPU with
seeded random weights; the Pallas kernels run through the interpreter.

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

ref = load_module(ROOT / "perfbench" / "references" / "glm4_moe_lite.py",
                  "tests_glm4_moe_lite_reference")
arch = load_module(ROOT / "perfbench" / "archs" / "glm4_moe_lite.py",
                   "tests_glm4_moe_lite_arch")

# the head's widths unequal: qk_nope 24 is not v 32 (the key is 24 + 8)
SMALL = dict(name="small", hidden_size=64, num_hidden_layers=3,
             first_k_dense_replace=1, intermediate_size=128,
             num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
             qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
             rope_theta=1e6, router_width=16, n_routed_experts=4,
             num_experts_per_tok=2, moe_intermediate_size=32,
             n_shared_experts=1, routed_scaling_factor=1.8,
             topk_method="noaux_tc", n_group=1, topk_group=1,
             norm_topk_prob=True, num_nextn_predict_layers=1,
             mtp_loss_weight=0.3, vocab_size=512, rms_norm_eps=1e-5,
             weights_key=4711, router_trained=False,
             activation_dtype="float32")


def normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)     # (the tests run x64)


def model(dtype="float32", **over):
    cfgd = dict(SMALL, activation_dtype=dtype, **over)
    s = arch.sizes(cfgd)
    cfg = arch.program_config(cfgd, s, remat=True, remat_policy="full",
                              xent_chunk=0)
    return s, cfg


def layer_params(s, kind, seed=0):
    """One layer's leaves, without leading axes, float32, none at a value
    (nought) that hides a term; the bias large enough to change choices."""
    shapes = ref.layer_shapes(s, kind)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = normal(k, shape)
        if name.startswith("W") or name == "router":
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
    "mla": ("dense", lambda x, p, s, cfg: lk.latent_attention(x, p, cfg),
            lambda x, p, s, cfg: ref.mla(x, p, s, MM)),
    "moe": ("moe", lambda x, p, s, cfg: lk.moe_topk(x, p, cfg),
            lambda x, p, s, cfg: ref.moe(x, p, s, MM)),
    "dense_layer": ("dense",
                    lambda x, p, s, cfg: lk.layer_forward(x, p, cfg, "mla",
                                                          "swiglu"),
                    lambda x, p, s, cfg: ref.layer(x, p, s, "dense", MM)),
    "expert_layer": ("moe",
                     lambda x, p, s, cfg: lk.layer_forward(x, p, cfg, "mla"),
                     lambda x, p, s, cfg: ref.layer(x, p, s, "moe", MM)),
}
# the value's width against the key's 32: equal (the published case, 256
# and 256), narrower and wider (noughts behind the narrower of the two)
WIDTHS = {"equal": 32, "narrower": 16, "wider": 48}


def compare_piece(piece, s, cfg, t=80):
    kind, prog, plain = PIECES[piece]
    p = layer_params(s, kind, seed=3)
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
            # a piece alone does not read the rest of a layer's leaves;
            # the frozen router and the bias get no gradient
            assert float(jnp.max(jnp.abs(gp[1][name]))) == 0, name
            continue
        close(gp[1][name], gr[1][name], 1e-4)
        read += 1
    return read


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_piece_matches_the_reference_forward_and_gradients(piece):
    """float32 to rounding: the mixer alone (every one of its seven leaves
    gets a gradient), the routed and shared experts, and both kinds of
    whole layer."""
    s, cfg = model()
    read = compare_piece(piece, s, cfg)
    assert read == {"mla": 7, "moe": 4, "dense_layer": 11,
                    "expert_layer": 13}[piece]


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_latent_attention_with_a_value_width_of_its_own(width):
    s, cfg = model(v_head_dim=WIDTHS[width])
    assert compare_piece("mla", s, cfg) == 7


def test_latent_attention_runs_the_flash_kernels(monkeypatch):
    """4 query heads on 4 key heads of 24 + 8 and values of 32 through
    `flash_fwd` and `flash_bwd` in the interpreter: the one rotary key's
    gradient is the sum over the heads."""
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    from deeplearning4j_tpu.observability.metrics import default_registry
    s, cfg = model()
    calls = default_registry().counter(
        "flash_attention_calls", "", labelnames=("layout",))
    before = sum(calls.labels(w).value for w in ("per_head", "lane_dense"))
    assert compare_piece("mla", s, cfg, t=128) == 7
    assert sum(calls.labels(w).value
               for w in ("per_head", "lane_dense")) > before


def test_the_latent_norms_multiply_by_one_plus_w():
    """A published gain `w` is stored as `w - 1`: nought leaves the
    normalised latent as it is, in program and reference alike."""
    s, cfg = model()
    p = dict(layer_params(s, "dense", seed=9))
    x = normal(jax.random.PRNGKey(1), (1, 16, s.hidden_size))
    with jax.default_matmul_precision("highest"):
        base = lk.latent_attention(x, p, cfg)
        p["kv_a_norm"] = p["kv_a_norm"] + 1.0
        moved = lk.latent_attention(x, p, cfg)
        close(moved, ref.mla(x, p, s, MM), 2e-5)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-3


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_the_bias_enters_the_choice_and_not_the_weight():
    """`noaux_tc`: the top_k by `s + b`, the weights `s` over their sum
    times 1.8. By hand on one token, a bias that changes the chosen set."""
    x = jnp.eye(4, dtype=jnp.float32)[:1]               # logits = row 0
    router = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] + [[0.0] * 4] * 3,
                         jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    none = jnp.zeros((4,), jnp.float32)
    lifts_last = jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)
    idx, w = lk.route(x, router, 2, "sigmoid", none, 1.8)
    assert sorted(np.asarray(idx[0])) == [0, 1]
    idx_b, w_b = lk.route(x, router, 2, "sigmoid", lifts_last, 1.8)
    assert sorted(np.asarray(idx_b[0])) == [0, 3]         # 0.269 + 0.6 > s_1
    order = np.argsort(np.asarray(idx_b[0]))
    want = 1.8 * s[[0, 3]] / (s[0] + s[3])                # s, not s + b
    np.testing.assert_allclose(np.asarray(w_b[0])[order], want, rtol=1e-6)
    assert abs(float(jnp.sum(w_b)) - 1.8) < 1e-5
    for got, plain in zip((idx_b, w_b),
                          ref.route(x, router, lifts_last, 2, 1.8)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   rtol=1e-6)


def test_the_bias_changes_which_experts_a_batch_meets():
    """At the layer's own sizes a bias of N(0, 0.1^2) changes some tokens'
    chosen set; program and reference agree with it, and differ from the
    layer without it."""
    s, cfg = model()
    p = layer_params(s, "moe", seed=3)
    x = normal(jax.random.PRNGKey(5), (96, s.hidden_size))
    idx, _ = lk.route(x, p["router"], 2, "sigmoid", p["router_bias"], 1.8)
    idx0, _ = lk.route(x, p["router"], 2, "sigmoid",
                       jnp.zeros_like(p["router_bias"]), 1.8)
    changed = int(jnp.sum(jnp.any(jnp.sort(idx, -1) != jnp.sort(idx0, -1),
                                  -1)))
    assert 0 < changed < 96
    want, _ = ref.route(x, p["router"], p["router_bias"], 2, 1.8)
    assert bool(jnp.array_equal(jnp.sort(idx, -1), jnp.sort(want, -1)))


def test_softmax_scoring_is_what_it_was():
    """The other router (Qwen's) through the same function: softmax, the
    top_k, their weights over their sum; no bias, no scale."""
    x = normal(jax.random.PRNGKey(0), (32, 16))
    router = normal(jax.random.PRNGKey(1), (16, 8))
    idx, w = lk.route(x, router, 3)
    prob = jax.nn.softmax(jnp.matmul(x, router, precision="highest"), -1)
    top, want = jax.lax.top_k(prob, 3)
    assert bool(jnp.array_equal(idx, want))
    close(w, top / jnp.sum(top, -1, keepdims=True), 1e-6)
    s, cfg = model()
    with pytest.raises(ValueError, match="router_scoring"):
        lk.moe_topk(normal(jax.random.PRNGKey(2), (1, 8, s.hidden_size)),
                    layer_params(s, "moe"),
                    dataclasses.replace(cfg, router_scoring="tanh"))


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_a_tree_and_a_config_that_disagree_on_the_router_raise(scoring):
    """One switch, `cfg.router_scoring`: a softmax config over a tree with
    the correction bias, or a sigmoid one over a tree without it, is
    refused and not routed by whichever the tree happens to hold."""
    s, cfg = model()
    p = dict(layer_params(s, "moe"))
    if scoring == "sigmoid":
        del p["router_bias"]
    with pytest.raises(ValueError, match="disagree"):
        lk.moe_topk(normal(jax.random.PRNGKey(2), (1, 8, s.hidden_size)), p,
                    dataclasses.replace(cfg, router_scoring=scoring))


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 experts held 2 at a time: the eight shares' outputs, with the
    ungated shared expert counted once, are the uncut reference layer's."""
    s, _ = model(n_routed_experts=16, num_experts_per_tok=4)
    whole = layer_params(s, "moe", seed=5)
    x = normal(jax.random.PRNGKey(7), (2, 48, s.hidden_size))
    s2, cfg2 = model(n_routed_experts=2, num_experts_per_tok=4)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, whole, s, MM)
        shared = ref.shared_expert(x.reshape(-1, s.hidden_size), whole,
                                   MM).reshape(x.shape)
        total = shared
        for first in range(0, 16, 2):
            part = dict(whole, We_gu=whole["We_gu"][first:first + 2],
                        We_down=whole["We_down"][first:first + 2])
            got = lk.moe_topk(x, part, cfg2, first=first)
            total = total + (got - shared)
            # the reference's own share agrees with the program's
            close(got, ref.moe(x, part, s2, MM, first=first), 2e-5)
    close(total, want, 2e-5)


# ---------------------------------------------------------------------------
# the whole model: the leading layer, the periods, the two-term loss
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
    assert lk.n_periods(cfg) == 2 and set(shapes["lead"]) == {"l0"}
    assert shapes["lead"]["l0"]["W_gu"] == (64, 256)       # dense, no [P]
    assert "router" not in shapes["lead"]["l0"]
    assert shapes["blocks"]["l0"]["We_gu"] == (2, 4, 64, 64)
    assert shapes["blocks"]["l0"]["router_bias"] == (2, 16)
    assert "Ws_gate" not in shapes["blocks"]["l0"]         # ungated
    assert shapes["mtp"]["eh_proj"] == (128, 64)
    assert shapes["mtp"]["layer"]["We_gu"] == (4, 64, 64)


@pytest.mark.parametrize("over", [
    {},
    dict(num_nextn_predict_layers=0),
    dict(first_k_dense_replace=0, num_hidden_layers=2),
    dict(first_k_dense_replace=2, num_hidden_layers=3),
    dict(mtp_loss_weight=1.0),
    dict(routed_scaling_factor=1.0),
], ids=["published", "no_mtp", "no_lead", "two_lead", "weight", "scale"])
def test_the_loss_is_the_references(over):
    """The leading dense layers before the scanned expert layers, and the
    loss's two terms: the main head's mean plus the weight times the MTP
    head's mean over a row's first T - 1 positions."""
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


def test_the_mtp_term_by_hand():
    """`L = L_main + w L_mtp` with `L_mtp` the module's mean over B x
    (T - 1): from the reference's two sums, and from the program with the
    module's weight at nought and at one."""
    s, cfg = model()
    params = ref.make_init(s)(ref.seed_key(3))
    tok, tgt = batch(s)
    b, t = tok.shape
    with jax.default_matmul_precision("highest"):
        main, mtp = ref.nll_sums(s, params, tok, tgt)
        at = lambda w: float(tf.loss_fn(  # noqa: E731
            dataclasses.replace(cfg, mtp_loss_weight=w), params, tok, tgt))
        l0, l1, l03 = at(0.0), at(1.0), at(0.3)
    assert abs(l0 - float(main) / (b * t)) <= 2e-6 * l0
    assert abs((l1 - l0) - float(mtp) / (b * (t - 1))) <= 1e-5 * l0
    assert abs(l03 - (l0 + 0.3 * (l1 - l0))) <= 2e-6 * l0
    assert l1 - l0 > 1.0        # a loss over 512 ids at random weights


def test_the_gradients_of_both_losses_reach_the_shared_leaves():
    """Every leaf's gradient against the reference's; `embed` and `Wout`
    get both losses' (with the module's weight at nought they differ), and
    `eh_proj` and the module's layer get the second's alone."""
    s, cfg = model()
    params = ref.make_init(s)(ref.seed_key(3))
    tok, tgt = batch(s)
    main_only = dataclasses.replace(cfg, mtp_loss_weight=0.0)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: tf.loss_fn(cfg, p, tok, tgt))(params)
        want = jax.grad(lambda p: ref.loss(s, p, tok, tgt))(params)
        main = jax.grad(lambda p: tf.loss_fn(main_only, p, tok, tgt))(params)
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    flat_m = dict(jax.tree_util.tree_flatten_with_path(main)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        name = ".".join(k.key for k in path)
        frozen = path[-1].key in ("router", "router_bias")
        assert (float(jnp.max(jnp.abs(flat_w[path]))) == 0) == frozen, name
        if frozen:
            assert float(jnp.max(jnp.abs(g))) == 0, name
        else:
            close(g, flat_w[path], 2e-4)
        if path[0].key == "mtp" and not frozen:
            assert float(jnp.max(jnp.abs(flat_m[path]))) == 0, name
    for name in ("embed", "Wout"):
        both, one = np.asarray(got[name]), np.asarray(main[name])
        assert np.linalg.norm(both - one) > 0.05 * np.linalg.norm(one), name


def test_the_counts_by_hand():
    """The configuration's file at its own sizes: 706,518,848 parameters
    held, as ISSUE 36's table reckons them."""
    cfgd = load_json(ROOT / "perfbench" / "configs" / "glm-4.7-flash.json")
    s = arch.sizes(cfgd)
    mixer = (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * (512 + 64) + 512
             + 512 * 20 * (192 + 256) + 20 * 256 * 2048)
    assert mixer == 21759232
    dense = mixer + 2 * 2048 + 3 * 2048 * 10240
    expert = 3 * 2048 * 1536
    layer = mixer + 2 * 2048 + 2048 * 64 + 64 + expert + 8 * expert
    ends = 2 * 19360 * 2048 + 2048
    mtp = 3 * 2048 + 4096 * 2048 + layer
    assert (dense, layer, 4 * layer, ends, mtp) == (
        84677888, 106829120, 427316480, 79300608, 115223872)
    assert arch.held_params(s) == dense + 4 * layer + ends + mtp == 706518848
    assert 8 * 19360 == 154880 and s.n_expert_layers == 4
    # and the program's tree at these sizes holds as many
    cfg = arch.program_config(cfgd, s, remat=True, remat_policy="full",
                              xent_chunk=0)
    tree = {"embed": (19360, 2048), "lnfg": (2048,), "Wout": (2048, 19360),
            "lead": lk.lead_shapes(cfg), "mtp": lk.mtp_shapes(cfg),
            "blocks": {"l0": {k: (4,) + v for k, v in
                              lk.layer_shapes(cfg, "mla").items()}}}
    assert tree == ref.leaf_shapes(s)
    assert sum(int(np.prod(sh)) for sh in jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple))) == 706518848


def test_the_marks_and_the_counter_of_a_traced_step():
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import default_spans
    s, cfg = model()
    calls = default_registry().counter("mla_calls", "",
                                       labelnames=("form",))
    before = calls.labels("expanded").value
    tok, tgt = batch(s)
    lowered = jax.jit(lambda p: jax.grad(
        lambda q: tf.loss_fn(cfg, q, tok, tgt))(p)).lower(
            lk.init_params(cfg, jax.random.PRNGKey(0)))
    # one lead layer, one scanned body, the module's layer; each again
    # for its rematerialised backward
    assert calls.labels("expanded").value - before >= 3
    spans = default_spans().snapshot().spans
    last = lambda name: [sp for sp in spans if sp.name == name][-1].args  # noqa: E731,E501
    assert last("mla.layout") == {
        "form": "expanded", "heads": 4, "qk_dim": 32, "v_dim": 32,
        "kv_rank": 16, "rope_dim": 8, "kv_expanded_elems": 4 * 64,
        "kv_latent_elems": 24}
    assert last("mtp.share") == {"depth": 1, "weight": 0.3}
    assert last("moe.share")["scoring"] == "sigmoid"
    assert (last("moe.share")["held"], last("moe.share")["of"]) == (4, 16)
    text = lowered.as_text(debug_info=True)
    for scope in ("mla.q", "mla.kv", "mla.rope", "mtp", "mtp.head_loss",
                  "moe.shared", "head_loss"):
        assert scope in text, scope
    # the published widths read 17.78: 20 x (256 + 256) over 512 + 64
    assert 20 * 512 / 576 == pytest.approx(17.78, abs=0.005)


# ---------------------------------------------------------------------------
# three steps through the Megatron entry on a one-device mesh
# ---------------------------------------------------------------------------

ROWS, SEQ, LR = 4, 96, 3e-4
# bfloat16 activations against the float32 reference at this size; the
# program with int8 matrix products fails three of them
# (readings at this size, loss 1 / loss 3 / norm / update / direction:
# bfloat16 4.3e-4 / 4.6e-4 / 0.0191 (a We_gu) / 0.0085 (lead ln2) / 0.0044;
# int8 7.7e-4 / 1.6e-3 / 0.0561 (a latent's norm) / 0.0128 / 0.0092. The
# direction reads high for a toy: the sigmoid scores of 16 experts at
# random weights lie close, and bfloat16 swaps some tokens' second expert)
BF16_LIMITS = {"loss_step1": 6e-4, "loss_step3": 1e-3,
               "grad_norm_gap": 0.03, "update_norm_gap": 0.05,
               "grad_direction_gap": 6.5e-3}
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
    bias = np.asarray(a["blocks"]["l0"]["router_bias"])
    assert 0.003 < float(np.std(bias)) < 0.03


# ---------------------------------------------------------------------------
# where it does not run
# ---------------------------------------------------------------------------

def test_serving_refuses_the_new_fields_by_name():
    from deeplearning4j_tpu.parallel import serving
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="mla.*q_lora_rank=32.*"
                       "lead_dense_layers=1.*mtp_layers=1"):
        serving.make_paged_decode(cfg, mesh, 4, 2, 16, 16, 32)
    with pytest.raises(ValueError, match="kv_lora_rank=16"):
        serving._check_spec(cfg, 2, 0)


@pytest.mark.parametrize("axis", ["model", "seq", "pipe"])
def test_the_axes_that_do_not_divide_it_refuse_it_by_name(axis):
    from deeplearning4j_tpu.parallel.megatron import make_parallel_train_step
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(**{axis: 2}), devices=jax.devices()[:2])
    what = "lead_dense_layers=1, mtp_layers=1" if axis == "pipe" else "mla"
    with pytest.raises(ValueError, match=what):
        make_parallel_train_step(cfg, mesh)


def test_a_mixer_without_its_ranks_or_a_second_module_is_an_error():
    _, cfg = model()
    with pytest.raises(ValueError, match="q_lora_rank"):
        lk.layer_shapes(dataclasses.replace(cfg, q_lora_rank=0), "mla")
    with pytest.raises(ValueError, match="mtp_layers=2"):
        lk.mtp_shapes(dataclasses.replace(cfg, mtp_layers=2))
    with pytest.raises(ValueError, match="lead_dense_layers"):
        lk.n_periods(dataclasses.replace(cfg, layer_types=("mla", "mla"),
                                         n_layers=4))
