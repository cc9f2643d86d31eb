"""Tensor+data-parallel generation == single-chip generation.

The serving analog of the spark-vs-single equivalence proof (SURVEY
§4): greedy decode through parallel/serving.py on a (data x model)
mesh must reproduce models/transformer.generate token-for-token."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   generate, init_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.parallel.serving import (make_parallel_generate,
                                                 shard_serving_params)


@pytest.fixture
def mesh(devices8):
    return make_mesh(MeshSpec(data=2, model=2))


def test_tp_generate_matches_single_chip(mesh):
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=3, max_len=96)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    key = jax.random.PRNGKey(2)
    want = np.asarray(generate(cfg, params, prompt, max_new_tokens=24,
                               key=key, temperature=0.0))
    pgen = make_parallel_generate(cfg, mesh, max_new_tokens=24,
                                  temperature=0.0)
    got = np.asarray(pgen(shard_serving_params(params, cfg, mesh),
                          prompt, key))
    np.testing.assert_array_equal(got, want)


def test_moe_tp_generate_matches_single_chip(mesh):
    """MoE serving (experts replicated, FFN hidden sharded over
    'model', GLOBAL capacity-drop decisions) == single-chip MoE
    generate token-for-token. capacity_factor chosen so the cap BINDS
    (B=4 tokens/step, E=2, cap=int(0.6*4/2)=1): the global-position
    drop logic is exercised, not just the no-drop happy path."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, max_len=64, n_experts=2,
                            capacity_factor=0.6)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    key = jax.random.PRNGKey(2)
    want = np.asarray(generate(cfg, params, prompt, max_new_tokens=16,
                               key=key, temperature=0.0))
    pgen = make_parallel_generate(cfg, mesh, max_new_tokens=16,
                                  temperature=0.0)
    got = np.asarray(pgen(shard_serving_params(params, cfg, mesh),
                          prompt, key))
    np.testing.assert_array_equal(got, want)


def test_tp_generate_sampled_is_valid(mesh):
    """Sampled decode: valid tokens, deterministic for a fixed key."""
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                            n_layers=2, max_len=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.zeros((4, 8), jnp.int32)
    pgen = make_parallel_generate(cfg, mesh, max_new_tokens=12,
                                  temperature=1.0)
    sp = shard_serving_params(params, cfg, mesh)
    a = np.asarray(pgen(sp, prompt, jax.random.PRNGKey(3)))
    b = np.asarray(pgen(sp, prompt, jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (4, 20)
    assert (a >= 0).all() and (a < 32).all()
    # identical prompts on DIFFERENT data shards must not sample
    # identical continuations (per-shard key fold; rows 0-1 live on
    # data rank 0, rows 2-3 on rank 1)
    assert not np.array_equal(a[:2, 8:], a[2:, 8:])


def test_tp_sampled_filters_match_single_chip(devices8):
    """SAMPLED decode with top-k + nucleus filtering on a TP-only mesh
    (dp=1 — key schedule identical to single-chip by construction) ==
    `generate` token-for-token: same key, same temperature, same
    filters. Logits are replicated on every model rank, so the filter
    + categorical draw must agree exactly (VERDICT r4 weak #5 — the
    greedy tests cannot see a filter gap because greedy ignores it)."""
    mesh = make_mesh(MeshSpec(data=1, model=4))
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=3, max_len=96)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    key = jax.random.PRNGKey(7)
    for top_k, top_p in [(8, 1.0), (0, 0.7), (8, 0.9)]:
        want = np.asarray(generate(cfg, params, prompt,
                                   max_new_tokens=24, key=key,
                                   temperature=0.8, top_k=top_k,
                                   top_p=top_p))
        pgen = make_parallel_generate(cfg, mesh, max_new_tokens=24,
                                      temperature=0.8, top_k=top_k,
                                      top_p=top_p)
        got = np.asarray(pgen(shard_serving_params(params, cfg, mesh),
                              prompt, key))
        np.testing.assert_array_equal(got, want)


def test_tp_generate_rejects_bad_filters(devices8):
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                            n_layers=2, max_len=64)
    mesh = make_mesh(MeshSpec(data=2, model=2))
    with pytest.raises(ValueError, match="top_p"):
        make_parallel_generate(cfg, mesh, max_new_tokens=4,
                               temperature=1.0, top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        make_parallel_generate(cfg, mesh, max_new_tokens=4,
                               temperature=1.0, top_k=-1)


@pytest.mark.slow
def test_flagship_geometry_serving_smoke(mesh):
    """Serving at the FLAGSHIP geometry (12L/512d/8H, max_len=2048) on
    the CPU mesh — tiny-shape tests can miss shape-dependent sharding
    bugs (VERDICT r3 #8); this pins the real layer count, width and
    cache length end-to-end with check_vma ON, and cross-checks the
    first greedy tokens against single-chip generate."""
    cfg = TransformerConfig(vocab_size=256, d_model=512, n_heads=8,
                            n_layers=12, max_len=2048)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    key = jax.random.PRNGKey(2)
    want = np.asarray(generate(cfg, params, prompt, max_new_tokens=4,
                               key=key, temperature=0.0))
    pgen = make_parallel_generate(cfg, mesh, max_new_tokens=4,
                                  temperature=0.0)
    got = np.asarray(pgen(shard_serving_params(params, cfg, mesh),
                          prompt, key))
    np.testing.assert_array_equal(got, want)


def test_tp_generate_rejects_bad_meshes_and_lengths(devices8):
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                            n_layers=2, max_len=16)
    with pytest.raises(ValueError, match="pipe"):
        make_parallel_generate(cfg, make_mesh(MeshSpec(pipe=2, model=2,
                                                       data=2)),
                               max_new_tokens=4)
    mesh = make_mesh(MeshSpec(data=2, model=2))
    pgen = make_parallel_generate(cfg, mesh, max_new_tokens=12)
    params = shard_serving_params(init_params(cfg, jax.random.PRNGKey(0)),
                                  cfg, mesh)
    with pytest.raises(ValueError, match="exceeds"):
        pgen(params, jnp.zeros((4, 8), jnp.int32), jax.random.PRNGKey(1))
