"""Composite parallelism (TP/PP/SP/EP) equivalence tests on the virtual CPU
mesh — every strategy must reproduce single-device training numerically
(the framework's version of the reference's spark-vs-single-machine proof,
SURVEY.md §4)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params, loss_fn)
from deeplearning4j_tpu.parallel.megatron import (init_adam_state,
                                                  make_parallel_train_step,
                                                  shard_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.ring import ring_attention
from deeplearning4j_tpu.parallel.ulysses import ulysses_attention


CFG = TransformerConfig(vocab_size=50, d_model=32, n_heads=4, n_layers=4,
                        max_len=32)

# Parallel-vs-single param equality after 2 Adam steps. Reassociation
# noise in the gradients gets amplified by Adam's m/sqrt(v) at early
# steps, and the amplification is XLA-codegen dependent: 5e-4 covers
# every leaf on jax 0.8's CPU backend, while jax 0.4.x CPU fusion
# leaves ~1 element in 16k at 2-3.5e-3 (worst on the deep-pipeline
# meshes). The bound stays ~100x below the param scale, so the
# equivalence proof keeps its teeth; the loss checks stay at 1e-4.
ATOL_TRAIN = 5e-3


def _data(seed=0, b=8, t=32):
    rng = np.random.RandomState(seed)
    toks = jnp.asarray(rng.randint(0, 50, (b, t)).astype(np.int32))
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, 1).astype(np.int32))
    return toks, tgts


def _train(cfg, spec, toks, tgts, steps=2, lr=1e-2):
    mesh = make_mesh(spec)
    p = init_params(cfg, jax.random.PRNGKey(0))
    step = make_parallel_train_step(cfg, mesh, learning_rate=lr)
    ps = shard_params(p, cfg, mesh)
    st = init_adam_state(ps)
    for _ in range(steps):
        ps, st, loss = step(ps, st, toks, tgts)
    return jax.tree_util.tree_map(np.asarray, ps), float(loss)


@pytest.mark.parametrize("attn_fn", [ring_attention, ulysses_attention],
                         ids=["ring", "ulysses"])
def test_sequence_parallel_attention_matches_full(devices8, attn_fn):
    """Both SP strategies (ring K/V rotation, Ulysses all-to-all head
    resharding) == full single-device causal attention, fwd and grad."""
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("seq",))
    rng = np.random.RandomState(0)
    q, k, v = (rng.randn(2, 32, 4, 8).astype(np.float32) for _ in range(3))
    ref = dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), causal=True)
    fn = jax.jit(jax.shard_map(
        partial(attn_fn, axis_name="seq", causal=True), mesh=mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # gradients flow through the collective identically
    gr = jax.grad(lambda a: jnp.sum(fn(a, k, v) ** 2))(jnp.asarray(q))
    gf = jax.grad(lambda a: jnp.sum(
        dot_product_attention(a, jnp.asarray(k), jnp.asarray(v),
                              causal=True) ** 2))(jnp.asarray(q))
    np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), atol=1e-5)


def test_ulysses_training_matches_single_device(devices8):
    """Composite step with seq_impl='ulysses' reproduces single-device
    training, including combined sp x tp (local heads 4/2=2, sp=2)."""
    toks, tgts = _data()
    base, base_loss = _train(CFG, MeshSpec(), toks, tgts)
    cfg_u = dataclasses.replace(CFG, seq_impl="ulysses")
    for spec in (MeshSpec(seq=2), MeshSpec(seq=2, model=2)):
        got, gl = _train(cfg_u, spec, toks, tgts)
        assert abs(gl - base_loss) < 1e-4
        for a, b in zip(jax.tree_util.tree_leaves(base),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_allclose(a, b, atol=ATOL_TRAIN)


@pytest.mark.parametrize("spec", [
    MeshSpec(model=2),
    MeshSpec(seq=2),
    MeshSpec(pipe=2),
    MeshSpec(pipe=2, data=2, model=2),
    MeshSpec(pipe=2, seq=2, model=2),
], ids=["tp", "sp", "pp", "pp-dp-tp", "pp-sp-tp"])
def test_parallel_training_matches_single_device(devices8, spec):
    toks, tgts = _data()
    base, base_loss = _train(CFG, MeshSpec(), toks, tgts)
    got, gl = _train(CFG, spec, toks, tgts)
    assert abs(gl - base_loss) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a, b, atol=ATOL_TRAIN)


def test_expert_parallel_matches_single_device(devices8):
    cfg = TransformerConfig(vocab_size=50, d_model=32, n_heads=4, n_layers=2,
                            max_len=32, n_experts=4, capacity_factor=8.0)
    toks, tgts = _data()
    base, base_loss = _train(cfg, MeshSpec(), toks, tgts)
    got, gl = _train(cfg, MeshSpec(data=4), toks, tgts)
    assert abs(gl - base_loss) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(a, b, atol=8e-3)


def _train_sched(cfg, spec, toks, tgts, schedule, steps=2, lr=1e-2,
                 n_microbatches=None):
    mesh = make_mesh(spec)
    p = init_params(cfg, jax.random.PRNGKey(0))
    step = make_parallel_train_step(cfg, mesh, learning_rate=lr,
                                    pipeline_schedule=schedule,
                                    n_microbatches=n_microbatches)
    ps = shard_params(p, cfg, mesh)
    st = init_adam_state(ps)
    for _ in range(steps):
        ps, st, loss = step(ps, st, toks, tgts)
    return jax.tree_util.tree_map(np.asarray, ps), float(loss)


@pytest.mark.parametrize("spec,m", [
    (MeshSpec(pipe=2), None),
    (MeshSpec(pipe=4), None),
    (MeshSpec(pipe=2), 4),
    (MeshSpec(pipe=2, data=2, model=2), None),
], ids=["pp2", "pp4", "pp2-m4", "pp-dp-tp"])
def test_1f1b_matches_gpipe_and_single_device(devices8, spec, m):
    """The 1F1B schedule must be a pure re-scheduling: loss and every
    updated param leaf equal the GPipe path AND single-device training
    (same math, O(S) instead of O(M) activation store)."""
    toks, tgts = _data()
    base, base_loss = _train(CFG, MeshSpec(), toks, tgts)
    gp, gp_loss = _train_sched(CFG, spec, toks, tgts, "gpipe",
                               n_microbatches=m)
    fb, fb_loss = _train_sched(CFG, spec, toks, tgts, "1f1b",
                               n_microbatches=m)
    assert abs(fb_loss - base_loss) < 1e-4
    assert abs(fb_loss - gp_loss) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(fb)):
        np.testing.assert_allclose(a, b, atol=ATOL_TRAIN)
    # 1f1b sums grads per microbatch; gpipe's autodiff sums in a
    # different order — reassociation noise that Adam's m/sqrt(v)
    # amplifies at early steps, so same tolerance as vs single-device
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(fb)):
        np.testing.assert_allclose(a, b, atol=ATOL_TRAIN)


def test_1f1b_chunked_xent_and_remat(devices8):
    """1F1B composes with the streaming chunked cross-entropy head and
    with blockwise remat inside the stage function."""
    import dataclasses as dc
    cfg = dc.replace(CFG, xent_chunk=25, remat=True)
    toks, tgts = _data()
    base, base_loss = _train(cfg, MeshSpec(), toks, tgts)
    fb, fb_loss = _train_sched(cfg, MeshSpec(pipe=2, model=2), toks,
                               tgts, "1f1b", n_microbatches=4)
    assert abs(fb_loss - base_loss) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(base),
                    jax.tree_util.tree_leaves(fb)):
        np.testing.assert_allclose(a, b, atol=ATOL_TRAIN)


def test_pipeline_bubble_fraction():
    from deeplearning4j_tpu.parallel.megatron import \
        pipeline_bubble_fraction
    assert pipeline_bubble_fraction("gpipe", 1, 8) == 0.0
    assert pipeline_bubble_fraction("gpipe", 4, 8) == pytest.approx(3 / 11)
    assert pipeline_bubble_fraction("1f1b", 4, 8) == pytest.approx(6 / 14)
    # the memory win converts to a bubble win at equal activation
    # budget: 1f1b at M=32 beats gpipe at M=8 (docstring rationale)
    assert (pipeline_bubble_fraction("1f1b", 4, 32)
            < pipeline_bubble_fraction("gpipe", 4, 8))
    with pytest.raises(ValueError, match="unknown"):
        pipeline_bubble_fraction("zb-h1", 4, 8)


def test_unknown_schedule_rejected(devices8):
    with pytest.raises(ValueError, match="pipeline_schedule"):
        make_parallel_train_step(CFG, make_mesh(MeshSpec(pipe=2)),
                                 pipeline_schedule="interleaved")


def test_parallel_loss_decreases(devices8):
    toks, tgts = _data()
    _, l0 = _train(CFG, MeshSpec(pipe=2, data=2, model=2), toks, tgts,
                   steps=1)
    _, l8 = _train(CFG, MeshSpec(pipe=2, data=2, model=2), toks, tgts,
                   steps=8)
    assert l8 < l0


def test_transformer_remat_same_loss_and_grads():
    """jax.checkpoint remat path is numerically identical to the
    standard path (memory-for-FLOPs only; net-new TPU capability,
    task-required long-context lever)."""
    from deeplearning4j_tpu.models.transformer import loss_fn

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=3,
                max_len=32)
    cfg = TransformerConfig(**base)
    cfg_r = TransformerConfig(**base, remat=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    tgt = jnp.roll(tok, -1, axis=1)

    l1, g1 = jax.value_and_grad(lambda p: loss_fn(cfg, p, tok, tgt))(params)
    l2, g2 = jax.value_and_grad(lambda p: loss_fn(cfg_r, p, tok, tgt))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_generate_top_k_and_top_p():
    """Sampling filters: top_k=1 == greedy; top-k/top-p draws stay
    inside the allowed candidate sets at every step; _filter_logits
    keeps exactly the documented tokens."""
    from deeplearning4j_tpu.models.transformer import (_filter_logits,
                                                       generate)
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                            n_layers=2, max_len=48)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((2, 4), jnp.int32)
    key = jax.random.PRNGKey(3)
    greedy = np.asarray(generate(cfg, params, prompt, 12, key,
                                 temperature=0.0))
    k1 = np.asarray(generate(cfg, params, prompt, 12, key,
                             temperature=1.0, top_k=1))
    np.testing.assert_array_equal(k1, greedy)
    # k=5 actually samples (differs from k=1 for this seed — a top_k
    # no-op regression would fail this), deterministically per key
    k5a = np.asarray(generate(cfg, params, prompt, 12, key,
                              temperature=1.0, top_k=5))
    k5b = np.asarray(generate(cfg, params, prompt, 12, key,
                              temperature=1.0, top_k=5))
    np.testing.assert_array_equal(k5a, k5b)
    assert not np.array_equal(k5a, k1)
    # unfiltered sampling with the same key picks tokens OUTSIDE the
    # top-5 at some step; the filtered run must not equal it either
    free = np.asarray(generate(cfg, params, prompt, 12, key,
                               temperature=1.0))
    assert not np.array_equal(k5a, free)
    with pytest.raises(ValueError, match="top_p"):
        generate(cfg, params, prompt, 4, key, top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        generate(cfg, params, prompt, 4, key, top_k=-1)

    # unit checks on the filter itself
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
    f2 = np.asarray(_filter_logits(logits, 2, 1.0))[0]
    assert np.isinf(f2[:2]).all() and (f2[2:] == [2.0, 3.0]).all()
    # top_p tiny -> only the argmax survives
    fp = np.asarray(_filter_logits(logits, 0, 1e-6))[0]
    assert np.isfinite(fp[3]) and np.isinf(fp[:3]).all()
    # top_p that spans two tokens: softmax([0,1,2,3]) top probs are
    # ~0.644, ~0.237 -> cumulative 0.88; top_p=0.7 keeps both (the
    # mass reaches 0.7 only WITH the second token)
    fp2 = np.asarray(_filter_logits(logits, 0, 0.7))[0]
    assert np.isfinite(fp2[3]) and np.isfinite(fp2[2])
    assert np.isinf(fp2[:2]).all()


def test_parallel_training_chunked_xent_matches_single_device(devices8):
    """xent_chunk flows through the megatron sharded step: parallel
    training with the streaming vocab-panel loss == the dense-loss
    parallel path AND the single-device chunked loss_fn (the
    real-vocab flagship on a mesh)."""
    from deeplearning4j_tpu.models.transformer import loss_fn

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                max_len=32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
    tgts = jnp.roll(toks, -1, axis=1)
    spec = MeshSpec(data=2, model=2, seq=2)
    cfg_c = TransformerConfig(**base, xent_chunk=16)
    cfg_d = TransformerConfig(**base)
    got_c, loss_c = _train(cfg_c, spec, toks, tgts)
    got_d, loss_d = _train(cfg_d, spec, toks, tgts)
    np.testing.assert_allclose(loss_c, loss_d, rtol=1e-5)
    # params after TWO Adam steps: panel-order summation differs from
    # the dense reduction at f32 ulp level, and Adam's m/sqrt(v) near
    # init amplifies that to ~0.4% on individual weights — the loss
    # parity above and the lr=0 scalar check below are the tight
    # checks; this pins the updates to the same trajectory
    for a, b in zip(jax.tree_util.tree_leaves(got_c),
                    jax.tree_util.tree_leaves(got_d)):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=1e-4)
    # scalar parity with the single-device chunked loss
    params = init_params(cfg_c, jax.random.PRNGKey(0))
    want = float(loss_fn(cfg_c, params, toks, tgts))
    _, l0 = _train(cfg_c, spec, toks, tgts, steps=1, lr=0.0)
    np.testing.assert_allclose(l0, want, rtol=1e-5)


def test_chunked_cross_entropy_matches_dense():
    """xent_chunk streaming loss == dense log_softmax loss in value AND
    grads (the real-vocab flagship path: never materializes [B,T,V])."""
    from deeplearning4j_tpu.models.transformer import (chunked_cross_entropy,
                                                       loss_fn)

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                max_len=32)
    cfg_d = TransformerConfig(**base)
    cfg_c = TransformerConfig(**base, xent_chunk=16)
    params = init_params(cfg_d, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    tgt = jnp.roll(tok, -1, axis=1)
    l1, g1 = jax.value_and_grad(
        lambda p: loss_fn(cfg_d, p, tok, tgt))(params)
    l2, g2 = jax.value_and_grad(
        lambda p: loss_fn(cfg_c, p, tok, tgt))(params)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)

    # direct function check with adversarial logit magnitudes (the
    # online-logsumexp rescale must not overflow where a naive
    # sum-of-exp would)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 16)) * 30.0
    w = jax.random.normal(jax.random.PRNGKey(3), (16, 48))
    y = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, 48)
    dense = -jnp.take_along_axis(
        jax.nn.log_softmax(jnp.matmul(h, w), axis=-1),
        y[..., None], axis=-1).mean()
    for c in (8, 16, 48):
        np.testing.assert_allclose(
            float(chunked_cross_entropy(h, w, y, c)), float(dense),
            rtol=1e-5)
    with pytest.raises(ValueError):
        chunked_cross_entropy(h, w, y, 13)


def test_kv_cache_decode_matches_full_forward():
    """Cached decode logits at each position == full-sequence forward
    logits (the correctness contract of the KV cache)."""
    from deeplearning4j_tpu.models.transformer import (decode_step,
                                                       forward,
                                                       init_cache)
    cfg = TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                            n_layers=2, max_len=16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (3, 10), 0, 50)
    full = np.asarray(forward(cfg, params, tok))  # [3, 10, 50]

    caches = init_cache(cfg, 3)
    outs = []
    for t in range(10):
        logits, caches = decode_step(cfg, params, tok[:, t], caches,
                                     jnp.asarray(t, jnp.int32))
        outs.append(np.asarray(logits))
    np.testing.assert_allclose(np.stack(outs, 1), full, rtol=2e-4,
                               atol=2e-4)


def test_decode_step_branching_without_donation():
    """donate=False keeps the input caches valid — several continuations
    can branch from one prefill cache (the advisor's branching-decode
    scenario; the default donating path invalidates its input)."""
    from deeplearning4j_tpu.models.transformer import (decode_step,
                                                       init_cache, prefill)
    cfg = TransformerConfig(vocab_size=50, d_model=32, n_heads=4,
                            n_layers=2, max_len=16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 50)
    _, caches = prefill(cfg, params, prompt)
    pos = jnp.asarray(6, jnp.int32)
    tok_a = jnp.asarray([1, 2], jnp.int32)
    tok_b = jnp.asarray([3, 4], jnp.int32)
    la, _ = decode_step(cfg, params, tok_a, caches, pos, donate=False)
    # caches must still be alive and reusable for a second branch
    lb, _ = decode_step(cfg, params, tok_b, caches, pos, donate=False)
    assert np.isfinite(np.asarray(la)).all()
    assert np.isfinite(np.asarray(lb)).all()
    assert not np.allclose(np.asarray(la), np.asarray(lb))


def test_generate_greedy_and_sampled():
    from deeplearning4j_tpu.models.transformer import TransformerLM
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                            n_layers=2, max_len=24)
    lm = TransformerLM(cfg, seed=3)
    prompt = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    out = np.asarray(lm.generate(prompt, 8, temperature=0.0))
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(out[:, :3], prompt)
    assert out.max() < 32 and out.min() >= 0
    # greedy is deterministic
    out2 = np.asarray(lm.generate(prompt, 8, temperature=0.0, seed=9))
    np.testing.assert_array_equal(out, out2)
    # sampling differs across seeds (overwhelmingly likely)
    s1 = np.asarray(lm.generate(prompt, 8, temperature=1.0, seed=0))
    s2 = np.asarray(lm.generate(prompt, 8, temperature=1.0, seed=1))
    assert not np.array_equal(s1, s2)
    # greedy continuation agrees with argmax over the full forward
    from deeplearning4j_tpu.models.transformer import forward
    ctx = out[:, :3]
    nxt = np.asarray(forward(cfg, lm.params, jnp.asarray(ctx)))[:, -1]
    np.testing.assert_array_equal(out[:, 3], nxt.argmax(-1))


def test_remat_policies_same_loss_and_grads():
    """remat off / 'full' / 'dots' / 'mlp' are pure memory-schedule
    choices — loss AND gradients must agree (round-3: the 'mlp' mode
    checkpoints only the MLP branch inside the scanned block)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params,
                                                       loss_fn)

    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 64)),
                       jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    results = []
    for remat, pol in [(False, "full"), (True, "full"), (True, "dots"),
                       (True, "mlp")]:
        cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=3, max_len=64, remat=remat,
                                remat_policy=pol)
        params = init_params(cfg, jax.random.PRNGKey(0))
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, toks, tgts))(params)
        results.append((float(loss), grads))
    base_loss, base_grads = results[0]
    for loss, grads in results[1:]:
        assert abs(loss - base_loss) < 1e-5, (loss, base_loss)
        for a, b in zip(jax.tree_util.tree_leaves(base_grads),
                        jax.tree_util.tree_leaves(grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-5)
