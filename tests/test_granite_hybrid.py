"""The Granite 4.0-H layers (Mamba-2, plain grouped-query attention, a dense
gated MLP, four multipliers, a tied head) against the plain reference
`perfbench/references/granite_hybrid.py`, at small sizes on the CPU with
seeded random weights.

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
from deeplearning4j_tpu.ops import mamba2_ssd as ssd

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench.harness.cells import load_module  # noqa: E402

ref = load_module(ROOT / "perfbench" / "references" / "granite_hybrid.py",
                  "tests_granite_hybrid_reference")
arch = load_module(ROOT / "perfbench" / "archs" / "granite_hybrid.py",
                   "tests_granite_hybrid_arch")

SMALL = dict(hidden_size=64, num_hidden_layers=4,
             layer_types=["mamba", "mamba", "attention", "mamba"],
             num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
             mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
             mamba_d_conv=4, mamba_expand=2, shared_intermediate_size=128,
             vocab_size=512, rms_norm_eps=1e-5, attention_multiplier=0.0625,
             embedding_multiplier=12, residual_multiplier=0.22,
             logits_scaling=8, tie_word_embeddings=True,
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
    """One layer's leaves, without the leading axes, float32, none at a
    value (nought, one) that hides a term."""
    shapes = ref.layer_shapes(s, kind, ())
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = normal(k, shape)
        if name.startswith("W") or name == "conv":
            out[name] = z / np.float32(np.sqrt(shape[-2]))
        elif name == "A_log":
            out[name] = jnp.log(1.0 + 4.0 * jax.random.uniform(
                k, shape, jnp.float32))
        elif name == "dt_bias":
            out[name] = -3.0 + 0.5 * z
        elif name in ("gnorm", "D"):
            out[name] = 1.0 + 0.1 * z
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
    "mamba": ("mamba", lambda x, p, s, cfg: lk.mamba2(x, p, cfg),
              lambda x, p, s, cfg: ref.mamba(x, p, s, MM)),
    "attention": ("attention",
                  lambda x, p, s, cfg: lk.grouped_query_attention(x, p, cfg),
                  lambda x, p, s, cfg: ref.attention(x, p, s, MM)),
    "mamba_layer": ("mamba",
                    lambda x, p, s, cfg: lk.layer_forward(x, p, cfg,
                                                          "mamba2"),
                    lambda x, p, s, cfg: ref.layer(x, p, s, "mamba", MM)),
    "attention_layer": ("attention",
                        lambda x, p, s, cfg: lk.layer_forward(
                            x, p, cfg, "attention"),
                        lambda x, p, s, cfg: ref.layer(x, p, s, "attention",
                                                       MM)),
}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_piece_matches_the_reference_forward_and_gradients(piece):
    """A mixer alone, and a whole layer: mixer, the dense gated MLP and
    the residual multiplier on both."""
    s, cfg = model()
    kind, prog, plain = PIECES[piece]
    p = layer_params(s, kind, seed=3)
    x = normal(jax.random.PRNGKey(1), (2, 80, s.hidden_size))
    co = normal(jax.random.PRNGKey(2), x.shape)

    def loss(fn):
        return lambda x_, p_: jnp.sum(fn(x_, p_, s, cfg) * co)

    with jax.default_matmul_precision("highest"):
        close(prog(x, p, s, cfg), plain(x, p, s, cfg), 2e-5)
        gp = jax.grad(loss(prog), argnums=(0, 1))(x, p)
        gr = jax.grad(loss(plain), argnums=(0, 1))(x, p)
    close(gp[0], gr[0], 5e-5)
    for name in gr[1]:
        if float(jnp.max(jnp.abs(gr[1][name]))) == 0:
            # a mixer alone reads neither the MLP's leaves nor ln1, ln2
            assert not piece.endswith("_layer"), name
            continue
        close(gp[1][name], gr[1][name], 1e-4)


def test_attention_at_head_dim_64_runs_the_flash_kernels(monkeypatch):
    """Granite's shape of head, 4 query heads on 2 KV heads of 64 with the
    scale from the config (1/64, not 1/8), through `flash_fwd` and
    `flash_bwd` in the interpreter."""
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    from deeplearning4j_tpu.observability.metrics import default_registry
    s, cfg = model(hidden_size=256, mamba_n_heads=8, mamba_d_head=64,
                   attention_multiplier=1.0 / 64)
    assert (cfg.d_head, cfg.attn_scale) == (64, 1.0 / 64)
    p = layer_params(s, "attention", seed=5)
    x = normal(jax.random.PRNGKey(1), (2, 128, 256))
    co = normal(jax.random.PRNGKey(2), x.shape)
    calls = default_registry().counter(
        "flash_attention_calls", "", labelnames=("layout",))
    before = calls.labels("per_head").value
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(lambda x_, p_: ref.attention(x_, p_, s, MM),
                             x, p)
        got, pull_k = jax.vjp(
            lambda x_, p_: lk.grouped_query_attention(x_, p_, cfg), x, p)
        close(got, want, 2e-5)
        (dx, dp), (rx, rp) = pull_k(co), pull(co)
    assert calls.labels("per_head").value > before
    close(dx, rx, 5e-5)
    for name in rp:
        close(dp[name], rp[name], 1e-4)


# ---------------------------------------------------------------------------
# the state-space-dual scan
# ---------------------------------------------------------------------------

def ssd_operands(t, dtype=jnp.float32, b=2, h=4, p=16, g=1, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = normal(ks[0], (b, t, h, p)).astype(dtype)
    bm = normal(ks[1], (b, t, g, n)).astype(dtype)
    cm = normal(ks[2], (b, t, g, n)).astype(dtype)
    d = jax.nn.softplus(normal(ks[3], (b, t, h)) - 2.0)
    la = -jnp.exp(normal(ks[4], (h,))) * d
    co = normal(ks[5], (b, t, h, p))
    return (x, bm, cm, d, la), co


def recurrence(x, bm, cm, d, la):
    f = jnp.float32
    return ref.ssm_recurrence(x.astype(f), bm.astype(f), cm.astype(f), d, la)


@pytest.mark.parametrize("kernel", [None, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t", [32, 64, 83], ids=["one", "two", "odd"])
def test_chunked_ssd_matches_the_recurrence(t, dtype, kernel):
    """One chunk, two, and a length that is not whole chunks, through the
    `lax.scan` form and through `ssd_fwd` / `ssd_bwd` in the interpreter:
    the value and every gradient, to rounding. bfloat16 x, B and C change
    no sum: the recurrence on the same rounded values is the yardstick."""
    args, co = ssd_operands(t, dtype)
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(recurrence, *args)
        got, pull_k = jax.vjp(
            lambda *a: ssd.ssd_scan(*a, chunk=32, kernel=kernel), *args)
    tol = 3e-6 if dtype == jnp.float32 else 1e-2   # y itself is rounded
    close(got.astype(jnp.float32), want, tol)
    for a, c in zip(pull_k(co.astype(got.dtype)), pull(co)):
        close(a.astype(jnp.float32), c.astype(jnp.float32), 4 * tol)


def test_groups_and_wide_heads_match_the_recurrence():
    """Two groups of B and C, and heads as wide as a lane group."""
    for kw in (dict(g=2), dict(h=2, p=128, n=32)):
        args, co = ssd_operands(48, **kw)
        with jax.default_matmul_precision("highest"):
            want, pull = jax.vjp(recurrence, *args)
            got, pull_k = jax.vjp(
                lambda *a: ssd.ssd_scan(*a, chunk=16), *args)
        close(got, want, 3e-6)
        for a, c in zip(pull_k(co), pull(co)):
            close(a, c, 1e-5)


def test_the_written_out_chunk_backward_is_the_plain_chunks_vjp():
    """`_group_bwd` against `jax.vjp` of `_group_fwd` (with dG folded into
    B and C as the kernel does), two heads a lane group."""
    q, p, n = 32, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(7), 9)
    x = normal(ks[0], (q, 2 * p))
    bm, cm = (normal(k, (q, n)) for k in ks[1:3])
    d = jax.nn.softplus(normal(ks[3], (2, 1, q)) - 1.0)
    gam = jnp.cumsum(-d * jnp.array([0.5, 2.0], jnp.float32)[:, None, None], axis=-1)
    st = normal(ks[4], (n, 2 * p))
    dy = normal(ks[5], (q, 2 * p))
    dst1 = normal(ks[6], (n, 2 * p))

    def fwd(x, bm, cm, gam, d, st):
        return ssd._group_fwd(x, bm, cm, ssd._g(cm, bm), gam, d, st, p)

    with jax.default_matmul_precision("highest"):
        _, pull = jax.vjp(fwd, x, bm, cm, gam, d, st)
        want = pull((dy, dst1))
        dx, dg, db, dc, dgam, dd, dst = ssd._group_bwd(
            x, bm, cm, ssd._g(cm, bm), gam, d, st, dy, dst1, p)
        db, dc = ssd._bc_grads(dg, bm, cm, db, dc)
    got = (dx, db, dc, jnp.stack(dgam), jnp.stack(dd), dst)
    for a, c in zip(got, want):
        close(a, c, 1e-5)


def test_a_traced_call_leaves_its_mark_and_counts_its_passes():
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import default_spans
    calls = default_registry().counter("ssd_calls", "", labelnames=("pass",))
    before = [calls.labels(w).value for w in ("forward", "backward")]
    args, co = ssd_operands(40, jnp.bfloat16)
    jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=16) * co))(*args)
    assert [calls.labels(w).value - v for w, v in
            zip(("forward", "backward"), before)] == [1, 1]
    last = [sp for sp in default_spans().snapshot().spans
            if sp.name == "ssd.layout"][-1]
    assert last.args == {"chunk": 16, "heads": 4, "head_dim": 16,
                         "state": 16, "groups": 1, "block": 48,
                         "operands": "bfloat16"}


# ---------------------------------------------------------------------------
# the whole model: multipliers, the tied head
# ---------------------------------------------------------------------------

def batch(s, rows=2, seq=48, seed=11):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, s.vocab_size, (rows, seq + 1)).astype(np.int32)
    return tok[:, :-1], tok[:, 1:]


@pytest.mark.parametrize("over", [
    {},
    dict(embedding_multiplier=3.0),
    dict(residual_multiplier=0.7),
    dict(logits_scaling=2.0),
    dict(attention_multiplier=2.0),
], ids=["published", "embedding", "residual", "logits", "attention"])
def test_every_multiplier_is_applied_as_the_reference_applies_it(over):
    s, cfg = model(**over)
    params = ref.make_init(s)(ref.seed_key(3))
    tok, tgt = batch(s)
    with jax.default_matmul_precision("highest"):
        got = tf.loss_fn(cfg, params, tok, tgt)
        want = ref.nll_sum(s, params, tok, tgt) / tok.size
        base = ref.nll_sum(model()[0], params, tok, tgt) / tok.size
    assert abs(float(got) - float(want)) <= 2e-6 * abs(float(want))
    if over:        # and it is not a multiplier that nothing reads
        assert abs(float(want) - float(base)) > 2e-5 * abs(float(base))


def test_the_tied_heads_gradient_is_the_gathers_plus_the_heads():
    s, cfg = model()
    params = ref.make_init(s)(ref.seed_key(4))
    assert "Wout" not in params and "Wout" not in lk.init_params(
        cfg, jax.random.PRNGKey(0))
    tok, tgt = batch(s)
    untied = dataclasses.replace(cfg, tie_head=False)
    with jax.default_matmul_precision("highest"):
        tied = jax.grad(lambda p: tf.loss_fn(cfg, p, tok, tgt))(params)
        apart = jax.grad(lambda p: tf.loss_fn(untied, p, tok, tgt))(
            dict(params, Wout=params["embed"].T))
    close(tied["embed"], apart["embed"] + apart["Wout"].T, 1e-5)
    assert float(jnp.max(jnp.abs(apart["Wout"]))) > 0
    close(tied["lnfg"], apart["lnfg"], 1e-5)


def test_runs_of_like_layers_are_stacked_and_scanned():
    s, cfg = model()
    assert lk.block_keys(cfg) == [("r0", "mamba2", (2,)),
                                  ("r1", "attention", (1,)),
                                  ("r2", "mamba2", (1,))]
    shapes = jax.tree_util.tree_map(lambda x: x.shape,
                                    lk.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes == ref.leaf_shapes(s)
    assert shapes["blocks"]["r0"]["Win"] == (1, 2, 64, 128 + 160)
    # one body a run: three mixers traced for four layers
    from deeplearning4j_tpu.observability.metrics import default_registry
    calls = default_registry().counter("ssd_calls", "", labelnames=("pass",))
    before = calls.labels("forward").value
    tok, tgt = batch(s)
    jax.jit(lambda p: tf.loss_fn(cfg, p, tok, tgt)).lower(
        lk.init_params(cfg, jax.random.PRNGKey(0)))
    assert calls.labels("forward").value - before <= 2


# ---------------------------------------------------------------------------
# three steps through the Megatron entry on a one-device mesh
# ---------------------------------------------------------------------------

ROWS, SEQ, LR = 4, 96, 3e-4
# bfloat16 activations against the float32 reference at this size; the
# program with int8 matrix products fails them
# (readings at this size, losses / norm / update / direction: bfloat16
# 1.5e-6 / 0.0062 (a D) / 0.0084 (an A_log) / 0.00020; int8 5.6e-6 / 0.025 /
# 0.0106 / 0.0016: the norm of the first gradient and its direction tell
# them apart, the losses and the update do not)
BF16_LIMITS = {"loss_step1": 3e-4, "loss_step3": 2e-3,
               "grad_norm_gap": 0.0125, "update_norm_gap": 0.05,
               "grad_direction_gap": 6e-4}
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


def test_the_weights_are_made_from_the_seed():
    s, _ = model()
    a = ref.make_init(s)(ref.seed_key(1))
    b = ref.make_init(s)(ref.seed_key(1))
    c = ref.make_init(s)(ref.seed_key(2 ** 33 + 5))
    assert bool(jnp.array_equal(a["embed"], b["embed"]))
    assert not bool(jnp.array_equal(a["embed"], c["embed"]))


def test_serving_refuses_the_new_kinds_by_name():
    from deeplearning4j_tpu.parallel import serving
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="mamba2.*attention"):
        serving.make_paged_decode(cfg, mesh, 4, 2, 16, 16, 32)
    with pytest.raises(ValueError, match="Mamba-2"):
        serving._check_spec(cfg, 2, 0)


@pytest.mark.parametrize("axis", ["model", "seq"])
def test_tensor_and_sequence_axes_refuse_the_new_kinds_by_name(axis):
    from deeplearning4j_tpu.parallel.megatron import make_parallel_train_step
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(**{axis: 2}), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="mamba2.*attention"):
        make_parallel_train_step(cfg, mesh)


def test_an_unknown_kind_of_layer_or_mlp_is_an_error():
    _, cfg = model()
    with pytest.raises(ValueError, match="unknown layer kind"):
        lk.layer_shapes(cfg, "hyena")
    with pytest.raises(ValueError, match="mlp_kind"):
        lk.layer_shapes(dataclasses.replace(cfg, mlp_kind="gelu"), "mamba2")
