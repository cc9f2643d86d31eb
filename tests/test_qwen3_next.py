"""The typed layers (Gated DeltaNet, gated grouped-query attention, top-k
MoE with a share of the experts held) against the plain reference
`perfbench/references/qwen3_next.py`, at small sizes on the CPU with seeded
random weights; the Pallas kernels run through the interpreter.

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


from perfbench.harness.cells import load_module  # noqa: E402

ref = load_module(ROOT / "perfbench" / "references" / "qwen3_next.py",
                  "tests_qwen3_next_reference")
arch = load_module(ROOT / "perfbench" / "archs" / "qwen3_next.py",
                   "tests_qwen3_next_arch")

SMALL = dict(hidden_size=64, num_hidden_layers=4, full_attention_interval=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16,
             router_width=16, num_experts=4, num_experts_per_tok=2,
             moe_intermediate_size=32, shared_expert_intermediate_size=32,
             vocab_size=512, partial_rotary_factor=0.25, rope_theta=1e7,
             rms_norm_eps=1e-6, linear_conv_kernel_dim=4, weights_key=1234,
             activation_dtype="float32")


def model(dtype="float32", **over):
    cfgd = dict(SMALL, activation_dtype=dtype, **over)
    s = arch.sizes(cfgd)
    cfg = arch.program_config(cfgd, s, remat=True, remat_policy="full",
                              xent_chunk=0)
    return s, cfg


def layer_params(s, kind, seed=0):
    """One layer's leaves, without the period axis, float32."""
    shapes = ref.layer_shapes(s, kind, 1)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k, shape[1:], jnp.float32)
        if name.startswith("W") or name in ("router", "conv"):
            out[name] = z / np.float32(np.sqrt(shape[-2]))
        elif name == "A_log":
            out[name] = jnp.log(1.0 + 4.0 * jax.random.uniform(k, shape[1:], jnp.float32))
        elif name == "dt_bias":
            out[name] = -3.0 + 0.5 * z
        elif name == "gnorm":
            out[name] = 1.0 + 0.1 * z
        else:
            out[name] = 0.1 * z
    return out


def close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.max(np.abs(b)), 1e-12)
    assert np.max(np.abs(a - b)) <= tol * scale, \
        (np.max(np.abs(a - b)), scale)


def agree(a, b, tol):
    """Relative in the norm: what two orders of one float32 sum differ
    by, where `close`'s largest element is one unlucky cancellation."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.linalg.norm(a - b) <= tol * np.linalg.norm(b), \
        (np.linalg.norm(a - b), np.linalg.norm(b))


MM = ref._mm_fn("f32")
PIECES = {
    "deltanet": (lambda x, p, s, cfg: lk.gated_deltanet(x, p, cfg),
                 lambda x, p, s, cfg: ref.gated_deltanet(x, p, s, MM)),
    "full": (lambda x, p, s, cfg: lk.grouped_query_attention(x, p, cfg),
             lambda x, p, s, cfg: ref.gated_attention(x, p, s, MM)),
    "moe": (lambda x, p, s, cfg: lk.moe_topk(x, p, cfg),
            lambda x, p, s, cfg: ref.moe(x, p, s, MM)),
}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_piece_matches_the_reference_forward_and_gradients(piece):
    s, cfg = model()
    p = layer_params(s, "full" if piece == "full" else "deltanet", seed=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 80, s.hidden_size), jnp.float32)
    co = jax.random.normal(jax.random.PRNGKey(2), x.shape, jnp.float32)
    prog, plain = PIECES[piece]

    def loss(fn):
        return lambda x_, p_: jnp.sum(fn(x_, p_, s, cfg) * co)

    with jax.default_matmul_precision("highest"):
        close(prog(x, p, s, cfg), plain(x, p, s, cfg), 2e-5)
        gp = jax.grad(loss(prog), argnums=(0, 1))(x, p)
        gr = jax.grad(loss(plain), argnums=(0, 1))(x, p)
    close(gp[0], gr[0], 5e-5)
    for name in gr[1]:
        if float(jnp.max(jnp.abs(gr[1][name]))) > 0:
            close(gp[1][name], gr[1][name], 1e-4)


def test_the_shares_of_a_moe_layer_add_up_to_the_uncut_layer():
    """64 experts held 4 at a time: the 16 shares' routed parts, with the
    shared expert counted once, are the uncut reference layer's output."""
    s, _ = model(router_width=64, num_experts=64, num_experts_per_tok=6)
    whole = layer_params(s, "full", seed=5)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 48, s.hidden_size), jnp.float32)
    s4, cfg4 = model(router_width=64, num_experts=4, num_experts_per_tok=6)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, whole, s, MM)
        shared = ref.shared_expert(x.reshape(-1, s.hidden_size), whole,
                                   MM).reshape(x.shape)
        total = shared
        for first in range(0, 64, 4):
            part = dict(whole, We_gu=whole["We_gu"][first:first + 4],
                        We_down=whole["We_down"][first:first + 4])
            total = total + (lk.moe_topk(x, part, cfg4, first=first)
                             - shared)
            # the reference's own share agrees with the program's
            close(lk.moe_topk(x, part, cfg4, first=first),
                  ref.moe(x, part, s4, MM, first=first), 2e-5)
    close(total, want, 2e-5)


@pytest.mark.parametrize("skewed", [False, True])
def test_no_row_is_dropped_whatever_the_router_sends(skewed):
    """4 of 64 experts held: a balanced router sends them a sixteenth of
    the pairs, a skewed one four of every token's six; every row is
    computed either way."""
    s, cfg = model(router_width=64, num_experts=4, num_experts_per_tok=6)
    p = layer_params(s, "full", seed=9)
    if skewed:
        p["router"] = p["router"].at[:, :4].add(1.0)      # x is positive
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 512, 64),
                                  jnp.float32))
    idx, _ = lk.route(x.reshape(-1, 64), p["router"], 6)
    assert (int(jnp.sum(idx < 4)) == 4 * 1024) is skewed
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(lambda x_, p_: lk.moe_topk(x_, p_, cfg), x, p)
        want, pull_r = jax.vjp(lambda x_, p_: ref.moe(x_, p_, s, MM), x, p)
        close(got, want, 2e-5)
        (gx, gp), (rx, rp) = pull(want), pull_r(want)
    close(gx, rx, 5e-5)
    close(gp["We_gu"], rp["We_gu"], 1e-4)
    close(gp["We_down"], rp["We_down"], 1e-4)


def _delta_inputs(seed, b, t, hk, hv, dk, dv, dtype):
    """q, k (normalised), v, g, beta and a cotangent; q, k, v and the
    cotangent are bfloat16 values, held in `dtype`."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def held(x):
        return x.astype(jnp.bfloat16).astype(dtype)

    q = ref._l2(jax.random.normal(ks[0], (b, t, hk, dk), jnp.float32)) * dk ** -0.5
    k = ref._l2(jax.random.normal(ks[1], (b, t, hk, dk), jnp.float32))
    v = jax.random.normal(ks[2], (b, t, hv, dv), jnp.float32)
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (b, t, hv), jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv), jnp.float32))
    co = jax.random.normal(ks[5], (b, t, hv, dv), jnp.float32)
    return held(q), held(k), held(v), g, beta, held(co)


def _delta_loss_and_grads(q, k, v, g, beta, co):
    from deeplearning4j_tpu.ops.gated_delta import gated_delta_rule

    def loss(q, k, v, g, beta):
        o = gated_delta_rule(q, k, v, g, beta)
        return jnp.sum(co.astype(jnp.float32) * o.astype(jnp.float32)), o

    with jax.default_matmul_precision("highest"):
        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, g, beta)
    return (o,) + grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t,ratio", [(50, 2), (64, 2), (200, 2), (1100, 2),
                                     (200, 1), (600, 3), (200, 4)])
def test_chunked_delta_rule_matches_the_recurrence(t, ratio, dtype):
    """T not a multiple of the chunk (64), one that is, and two that span
    more than one block (512) so the state crosses programs; two value
    heads a key head (a program is the pair), four (two programs a key
    head, whose `dq` and `dk` add up outside the kernel), one and three (a
    program is one head). float32 operands to rounding; bfloat16 q, k, v
    against the recurrence run in float32 on the same values, as far as
    the bfloat16 outputs (o, dq, dk, dv: a sum over a program's heads, a
    rounding, a sum, a rounding) allow. The gates' gradients are float32
    either way."""
    b, hk, dk, dv = 2, 2, 16, 32
    hv = hk * ratio
    q, k, v, g, beta, co = _delta_inputs(t, b, t, hk, hv, dk, dv, dtype)
    f32 = [x.astype(jnp.float32) for x in (q, k, v, g, beta, co)]

    def plain(q, k, v, g, beta):
        r = hv // hk
        o = ref.delta_rule(jnp.repeat(q, r, 2), jnp.repeat(k, r, 2), v, g,
                           beta)
        return jnp.sum(f32[5] * o), o

    with jax.default_matmul_precision("highest"):
        (_, o), grads = jax.value_and_grad(
            plain, argnums=(0, 1, 2, 3, 4), has_aux=True)(*f32[:5])
    got = _delta_loss_and_grads(q, k, v, g, beta, co)
    held = 2e-5 if dtype == "float32" else 2.0 ** -7
    for a, c, tol in zip(got, (o,) + grads, [held] * 4 + [2e-5] * 2):
        close(a, c, tol)


def test_bfloat16_operands_change_no_sum():
    """What the kernels' cheaper products rest on: q, k, v and the
    cotangent taken as bfloat16 give the sums that the same values give
    as float32 operands (every product `HIGHEST`), over four chunks. The
    float32 outputs (the gates' gradients, which the backward forms from
    every chunk's saved state) to 1e-6; the outputs held in bfloat16 to
    their rounding."""
    shape = dict(b=1, t=200, hk=2, hv=4, dk=16, dv=32)
    narrow = _delta_inputs(7, dtype="bfloat16", **shape)
    wide = _delta_inputs(7, dtype="float32", **shape)
    for x, y in zip(narrow, wide):
        assert np.array_equal(np.asarray(x, np.float32), np.asarray(y))
    got, want = _delta_loss_and_grads(*narrow), _delta_loss_and_grads(*wide)
    for a, c in zip(got[:4], want[:4]):
        close(a, c, 2.0 ** -7)
    for a, c in zip(got[4:], want[4:]):
        agree(a, c, 1e-6)


def _one_chunk(seed, dtype):
    c, dk, dv = 64, 16, 32
    q, k, v, g, beta, do = (x[0, :, 0] for x in _delta_inputs(
        seed, 1, c, 1, 1, dk, dv, dtype))
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    gam, beta = jnp.cumsum(g)[None, :], beta[None, :]
    s0 = jax.random.normal(ks[0], (dk, dv), jnp.float32)
    ds1 = jax.random.normal(ks[1], (dk, dv), jnp.float32)
    return (q, k, v, gam, beta, s0), (do, ds1)


def _chunk_panels(q, k, gam, beta):
    """[k; q] and the state-free panels of one chunk of one head, as a
    kernel program forms them."""
    from deeplearning4j_tpu.ops import gated_delta as gd
    kq = jnp.concatenate([k, q], axis=0)
    w, = gd._lockstep([gd._panels(gd._dot(kq, k, gd._NT), gam, beta)])
    return kq, w


def _chunk_fwd(q, k, v, gam, beta, s0):
    """(o [C, dv], s1) of one chunk through the kernels' generators."""
    from deeplearning4j_tpu.ops import gated_delta as gd
    kq, w = _chunk_panels(q, k, gam, beta)
    return gd._lockstep([gd._fwd_rest(w, kq, v, s0)])[0]


def _chunk_bwd(q, k, v, gam, beta, s0, do, ds1):
    """(dq, dk, dv, dgam, dbeta, ds0) of one chunk through them."""
    from deeplearning4j_tpu.ops import gated_delta as gd
    kq, w = _chunk_panels(q, k, gam, beta)
    g, = gd._lockstep([gd._bwd_rest(w, kq, v, s0, do, ds1)])
    return gd._dq_dk(kq, [g]) + (g.dv, g.dgam, g.dbeta, g.ds0)


def test_the_written_out_chunk_backward_is_the_plain_chunks_vjp():
    """One chunk, a state before it and a cotangent of the state after
    it: the kernels' generators (`_panels`, `_fwd_rest`, `_bwd_rest`)
    against `_chunk` and `jax.vjp` of it, the gates' gradients among
    them."""
    from deeplearning4j_tpu.ops import gated_delta as gd
    args, cot = _one_chunk(3, "float32")
    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(gd._chunk, *args)
        for a, c in zip(_chunk_fwd(*args), want):
            close(a, c, 2e-6)
        for a, c in zip(_chunk_bwd(*args, *cot), pull(cot)):
            close(a, c, 1e-5)


def test_a_chunk_in_bfloat16_operands_is_the_chunk_in_float32():
    """The same on one chunk, where every output is float32: o, the
    state and all six gradients to 1e-6."""
    (narrow, ncot), (wide, wcot) = (_one_chunk(5, d)
                                    for d in ("bfloat16", "float32"))
    assert narrow[0].dtype == jnp.bfloat16 and wide[0].dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        for a, c in zip(_chunk_fwd(*narrow), _chunk_fwd(*wide)):
            agree(a, c, 1e-6)
        for a, c in zip(_chunk_bwd(*narrow, *ncot),
                        _chunk_bwd(*wide, *wcot)):
            agree(a, c, 1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_merged_inverse_is_the_plain_chunks(dtype):
    """`_panels`' inverse, six rounds of `p [p | sum]` with the pieces of
    a split side by side along the contraction, against `_chunk`'s
    `p = p p; inv += inv p`: float32 operands to 2e-6, and bfloat16
    operands against float32 ones of the same values to 1e-6."""
    from deeplearning4j_tpu.ops import gated_delta as gd
    (q, k, _, gam, beta, _), _ = _one_chunk(9, dtype)
    c = q.shape[0]
    with jax.default_matmul_precision("highest"):
        _, w = _chunk_panels(q, k, gam, beta)
        _, wide = _chunk_panels(q.astype(jnp.float32),
                                k.astype(jnp.float32), gam, beta)
        a = jnp.tril(beta.T * jnp.exp(jnp.tril(gam.T - gam))
                     * gd._dot(k, k, gd._NT), -1)
        p = -a
        inv = jnp.eye(c) + p
        for _ in range(5):
            p = gd._dot(p, p, gd._NN)
            inv = inv + gd._dot(inv, p, gd._NN)
    close(w.inv, inv, 2e-6)
    agree(w.inv, wide.inv, 1e-6)
    # and it is the inverse
    close(np.asarray(w.inv, np.float64) @ (np.eye(c) + np.asarray(a)),
          np.eye(c), 2e-6)


@pytest.mark.parametrize("ratio,heads_per_program", [(2, 2), (4, 2), (1, 1),
                                                     (3, 1)])
def test_a_traced_call_says_how_many_heads_a_program_took(
        ratio, heads_per_program):
    """The mark `gdn.layout` and the counter `gdn_calls` carry the form a
    call took: a key head's value heads in pairs where they pair up, else
    one a program."""
    from deeplearning4j_tpu.observability.metrics import default_registry
    from deeplearning4j_tpu.observability.tracing import default_spans
    calls = default_registry().counter(
        "gdn_calls", "", labelnames=("pass", "operands",
                                     "heads_per_program"))
    keys = [(w, "bfloat16", str(heads_per_program))
            for w in ("forward", "backward")]
    before = [calls.labels(*key).value for key in keys]
    _delta_loss_and_grads(*_delta_inputs(1, 1, 40, 1, ratio, 16, 16,
                                         "bfloat16"))
    assert [calls.labels(*key).value - v
            for key, v in zip(keys, before)] == [1, 1]
    last = [sp for sp in default_spans().snapshot().spans
            if sp.name == "gdn.layout"][-1]
    assert last.args == {"chunk": 64, "heads": ratio, "block": 64,
                         "operands": "bfloat16",
                         "heads_per_program": heads_per_program,
                         "inverse": "phased"}


def test_grouped_query_flash_kernel_matches_plain_attention(monkeypatch):
    """16 query heads on 2 KV heads of 256, the kernel's shared K/V block
    against plain attention with K and V repeated."""
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    from deeplearning4j_tpu.ops.flash_attention import flash_attention
    b, t, h, hk, dh = 1, 256, 16, 2, 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, t, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hk, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hk, dh), jnp.float32)
    co = jax.random.normal(ks[3], (b, t, h, dh), jnp.float32)

    def plain(q, k, v):
        kk, vv = jnp.repeat(k, h // hk, 2), jnp.repeat(v, h // hk, 2)
        sc = jnp.einsum("bthd,bshd->bhts", q, kk) * dh ** -0.5
        live = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        w = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), -1)
        return jnp.einsum("bhts,bshd->bthd", w, vv)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(plain, q, k, v)
        got, pull_k = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        close(got, want, 2e-5)
        for a, c in zip(pull_k(co), pull(co)):
            close(a, c, 5e-5)


# ---------------------------------------------------------------------------
# a whole model through the Megatron entry on a one-device mesh
# ---------------------------------------------------------------------------

ROWS, SEQ, LR = 4, 96, 3e-4
# bfloat16 activations against the float32 reference at this size; the
# program with int8 matrix products fails them
# (readings at this size: bfloat16 4e-5 / 7e-4 / 0.22 (a router, whose
# near-tied top-2 of 16 flip) / 0.025 / 0.031; int8 6e-4 / 1.2e-3 / 0.42 /
# 0.035 / 0.147: the first loss, the norms and the direction tell them
# apart)
BF16_LIMITS = {"loss_step1": 3e-4, "loss_step3": 2e-3, "grad_norm_gap": 0.3,
               "update_norm_gap": 0.05, "grad_direction_gap": 0.07}
# float32: rounding, through three Adam steps (whose first divides a
# gradient by its own size, so a leaf with a gradient near nought moves by
# its rounding: ln2 of the first layer, 3e-3)
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


def test_the_weights_do_not_change_with_the_seed():
    s, _ = model()
    a = ref.make_init(s)(ref.seed_key(1))
    b = ref.make_init(s)(ref.seed_key(2 ** 33 + 5))
    assert all(bool(jnp.array_equal(x, y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    other = ref.make_init(dataclasses.replace(s, weights_key=99))(
        ref.seed_key(1))
    assert not bool(jnp.array_equal(a["Wout"], other["Wout"]))


def test_serving_refuses_layer_kinds_and_kv_heads():
    from deeplearning4j_tpu.parallel import serving
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="layer_types"):
        serving.make_paged_decode(cfg, mesh, 4, 2, 16, 16, 32)
    plain = tf.TransformerConfig(n_heads=4, n_kv_heads=2)
    with pytest.raises(ValueError, match="n_kv_heads"):
        serving.make_paged_decode(plain, mesh, 4, 2, 16, 16, 32)


def test_tensor_and_sequence_axes_are_refused_for_typed_layers():
    from deeplearning4j_tpu.parallel.megatron import make_parallel_train_step
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    _, cfg = model()
    mesh = make_mesh(MeshSpec(model=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="layer_types"):
        make_parallel_train_step(cfg, mesh)


def test_a_frozen_router_keeps_every_other_gradient():
    """`train_router=False`: the router's matrix gets no gradient, in
    program and reference alike, and every other gradient (the hidden
    state's through the routing weights among them) is what it is with
    the router trained."""
    s, cfg = model()
    p = layer_params(s, "full", seed=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, s.hidden_size),
                          jnp.float32)
    co = jax.random.normal(jax.random.PRNGKey(6), x.shape, jnp.float32)

    def grads(cfg_, s_):
        with jax.default_matmul_precision("highest"):
            gp = jax.grad(
                lambda x_, p_: jnp.sum(lk.moe_topk(x_, p_, cfg_) * co),
                argnums=(0, 1))(x, p)
            gr = jax.grad(
                lambda x_, p_: jnp.sum(ref.moe(x_, p_, s_, MM) * co),
                argnums=(0, 1))(x, p)
        return gp, gr

    gp, gr = grads(dataclasses.replace(cfg, train_router=False),
                   dataclasses.replace(s, router_trained=False))
    whole, _ = grads(cfg, s)
    assert float(jnp.max(jnp.abs(gp[1]["router"]))) == 0.0
    assert float(jnp.max(jnp.abs(gr[1]["router"]))) == 0.0
    assert float(jnp.max(jnp.abs(whole[1]["router"]))) > 0.0
    close(gp[0], gr[0], 5e-5)
    close(gp[0], whole[0], 1e-6)
    close(gp[1]["We_gu"], gr[1]["We_gu"], 1e-4)
    close(gp[1]["Ws_gate"], gr[1]["Ws_gate"], 1e-4)
