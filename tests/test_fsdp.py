"""FSDP/ZeRO-style fully-sharded data parallelism (parallel/fsdp.py).

The reference's data-parallel modes replicate the full model per worker
(ParallelWrapper.java:603; Spark broadcast) — sharded-state DP is
net-new. Proof obligations: (1) numerics identical to single-device
training, (2) per-device param/opt-state memory actually drops by the
axis size for shardable leaves.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.fsdp import (fsdp_leaf_spec,
                                              init_fsdp_adam_state,
                                              make_fsdp_train_step,
                                              shard_params_fsdp)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh

CFG = TransformerConfig(vocab_size=50, d_model=32, n_heads=4, n_layers=4,
                        max_len=32)


def test_fsdp_leaf_spec_rules():
    # largest divisible axis is sharded
    assert fsdp_leaf_spec((4, 32, 64), 8) == P(None, None, "data")
    # largest axis not divisible -> next largest divisible one
    assert fsdp_leaf_spec((50, 32), 8) == P(None, "data")
    # nothing divisible -> replicated
    assert fsdp_leaf_spec((7, 3), 8) == P()
    assert fsdp_leaf_spec((), 8) == P()
    # axis of exactly the mesh size is eligible
    assert fsdp_leaf_spec((8,), 8) == P("data")
    # size-1 axis (no mesh) -> replicated
    assert fsdp_leaf_spec((64, 64), 1) == P()


def _data(seed=0, b=8, t=32):
    rng = np.random.RandomState(seed)
    toks = jnp.asarray(rng.randint(0, 50, (b, t)).astype(np.int32))
    tgts = jnp.asarray(np.roll(np.asarray(toks), -1, 1).astype(np.int32))
    return toks, tgts


def _train(mesh_spec, steps=3):
    mesh = make_mesh(mesh_spec)
    params = shard_params_fsdp(init_params(CFG, jax.random.PRNGKey(0)),
                               mesh)
    opt = init_fsdp_adam_state(params)
    step = make_fsdp_train_step(CFG, mesh, learning_rate=1e-2)
    toks, tgts = _data()
    for _ in range(steps):
        params, opt, loss = step(params, opt, toks, tgts)
    return params, opt, float(loss)


def test_fsdp_matches_single_device(devices8):
    base_p, _, base_loss = _train(MeshSpec())
    got_p, _, got_loss = _train(MeshSpec(data=8))
    assert abs(got_loss - base_loss) < 1e-4
    for a, b in zip(jax.tree_util.tree_leaves(base_p),
                    jax.tree_util.tree_leaves(got_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_fsdp_shards_the_attention_kernel_over_data(devices8,
                                                    monkeypatch):
    """On several chips the FSDP step is one GSPMD jit, and a Mosaic
    call inside it refuses to lower ("cannot be automatically
    partitioned"). The step names its mesh while tracing and the flash
    kernel, seeing it, runs under a shard_map over 'data'. Interpret
    mode on the CPU: the shard-mapped kernel step equals the
    single-device kernel step."""
    import jax.experimental.pallas as pl
    calls = []
    real = pl.pallas_call

    def spy(kernel, *a, **kw):
        calls.append(jax.sharding.get_abstract_mesh().manual_axes)
        return real(kernel, *a, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    _, _, base_loss = _train(MeshSpec(), steps=2)
    assert calls and not any(calls)         # one device: no shard_map
    del calls[:]
    _, _, got_loss = _train(MeshSpec(data=4), steps=2)
    assert calls and all("data" in c for c in calls)
    assert abs(got_loss - base_loss) < 1e-4


def test_fsdp_state_is_actually_sharded(devices8):
    mesh = make_mesh(MeshSpec(data=8))
    params = shard_params_fsdp(init_params(CFG, jax.random.PRNGKey(0)),
                               mesh)
    opt = init_fsdp_adam_state(params)
    wq = params["blocks"]["Wq"]          # [L=4, 32, 32]: d axis sharded
    assert wq.sharding.spec != P()
    local = wq.addressable_shards[0].data
    assert local.size == wq.size // 8
    # optimizer state inherits the shards (ZeRO-1 half of the win)
    mu_wq = opt.m["blocks"]["Wq"]
    assert mu_wq.addressable_shards[0].data.size == mu_wq.size // 8
    # embed's vocab axis (50) is indivisible; its d axis shards instead
    emb = params["embed"]                # [50, 32] -> d axis sharded
    assert emb.addressable_shards[0].data.size == emb.size // 8


def test_fsdp_replicated_leaves_stay_whole(devices8):
    """Leaves with no axis divisible by the mesh (odd-shaped norms/
    biases) are replicated intact — every device holds the full leaf."""
    mesh = make_mesh(MeshSpec(data=8))
    tree = {"w": jnp.ones((16, 64)), "odd": jnp.ones((7, 3)),
            "scalar": jnp.ones(())}
    placed = shard_params_fsdp(tree, mesh)
    assert placed["w"].addressable_shards[0].data.size == 16 * 64 // 8
    for name in ("odd", "scalar"):
        leaf = placed[name]
        assert leaf.sharding.spec == P()
        assert leaf.addressable_shards[0].data.size == leaf.size
        np.testing.assert_array_equal(
            np.asarray(leaf.addressable_shards[0].data),
            np.asarray(tree[name]))


@pytest.mark.parametrize("use_orbax", [True, False], ids=["orbax", "npz"])
def test_fsdp_checkpoint_resume(devices8, tmp_path, use_orbax):
    """Distributed checkpoint/resume of a sharded training state
    (SURVEY §5.3/5.4 TPU-native answer): save mid-run, restore into
    freshly-placed shards via a sharded template, and continue — must
    equal the uninterrupted run, with shards preserved."""
    from deeplearning4j_tpu.util.checkpointing import (CheckpointManager,
                                                       HAVE_ORBAX)
    if use_orbax and not HAVE_ORBAX:
        pytest.skip("orbax unavailable")
    mesh = make_mesh(MeshSpec(data=8))
    toks, tgts = _data()

    def fresh():
        p = shard_params_fsdp(init_params(CFG, jax.random.PRNGKey(0)), mesh)
        return p, init_fsdp_adam_state(p)

    step = make_fsdp_train_step(CFG, mesh, learning_rate=1e-2)
    # uninterrupted 4 steps
    p_ref, o_ref = fresh()
    for _ in range(4):
        p_ref, o_ref, _ = step(p_ref, o_ref, toks, tgts)

    # 2 steps -> save -> restore into a fresh sharded template -> 2 more
    p, o = fresh()
    for _ in range(2):
        p, o, _ = step(p, o, toks, tgts)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), use_orbax=use_orbax)
    mgr.save_tree({"params": p, "opt": o}, step=2)
    tmpl_p, tmpl_o = fresh()
    restored = mgr.restore_tree({"params": tmpl_p, "opt": tmpl_o})
    p2, o2 = restored["params"], restored["opt"]
    # shardings survive the round-trip
    wq = p2["blocks"]["Wq"]
    assert wq.addressable_shards[0].data.size == wq.size // 8
    for _ in range(2):
        p2, o2, _ = step(p2, o2, toks, tgts)
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_restore_tree_abstract_template_npz(devices8, tmp_path):
    """npz-fallback restore with a jax.eval_shape abstract template
    (ShapeDtypeStructs carrying .sharding) re-places leaves onto their
    shards — same contract the orbax path honors (advisor r1 finding:
    abstract templates silently yielded unsharded host arrays)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.util.checkpointing import CheckpointManager

    mesh = make_mesh(MeshSpec(data=8))
    sharding = NamedSharding(mesh, P("data"))
    x = jax.device_put(jnp.arange(16, dtype=jnp.float32), sharding)
    mgr = CheckpointManager(str(tmp_path / "ckpt"), use_orbax=False)
    mgr.save_tree({"x": x}, step=1)

    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding), {"x": x})
    restored = mgr.restore_tree(abstract)["x"]
    assert restored.sharding == sharding
    assert restored.addressable_shards[0].data.size == restored.size // 8
    np.testing.assert_array_equal(np.asarray(restored), np.asarray(x))


def test_fsdp_loss_decreases(devices8):
    _, _, l3 = _train(MeshSpec(data=8), steps=1)
    _, _, l8 = _train(MeshSpec(data=8), steps=10)
    assert l8 < l3
