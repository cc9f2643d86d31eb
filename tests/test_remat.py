"""What a rematerialised layer keeps (models/remat.py): its input and the
results of its attention kernel, so the gradient program holds the Pallas
forward once a layer where a checkpoint that keeps the input alone holds it
twice. On the CPU at small sizes, the kernels traced through the
interpreter; the compiled count for a described v5e is in
tests/test_tpu_compile.py.
"""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import remat
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params, loss_fn)
from deeplearning4j_tpu.observability.metrics import default_registry
from deeplearning4j_tpu.observability.tracing import default_spans
from deeplearning4j_tpu.parallel.fsdp import (init_fsdp_adam_state,
                                              make_fsdp_train_step,
                                              shard_params_fsdp)
from deeplearning4j_tpu.parallel.megatron import (init_adam_state,
                                                  make_parallel_train_step,
                                                  shard_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from test_tracing_spans import _pallas_names

GPT2 = TransformerConfig(vocab_size=64, d_model=128, n_heads=2, n_layers=3,
                         max_len=128, remat=True)
# a period of the three kinds that run the attention kernel: plain
# grouped-query attention, the gated and rotated one, latent attention
TYPED = TransformerConfig(
    vocab_size=64, d_model=64, n_heads=2, n_kv_heads=1, head_dim=32,
    n_layers=3, max_len=128, layer_types=("attention", "full", "mla"),
    rotary_fraction=0.25, mlp_kind="swiglu", dense_d_ff=96, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_dim=24, qk_rope_dim=8, v_head_dim=32,
    remat=True)
# the same with a leading dense layer and the multi-token-prediction
# module: two layers outside the period's loop
TYPED_OUTSIDE = dataclasses.replace(TYPED, n_layers=4, lead_dense_layers=1,
                                    mtp_layers=1, mtp_loss_weight=0.3)


@pytest.fixture(autouse=True)
def _interpreted_kernels(monkeypatch):
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")


def batch(seed, rows=2, t=128, vocab=64):
    toks = np.random.default_rng(seed).integers(0, vocab, (rows, t))
    toks = jnp.asarray(toks, jnp.int32)
    return toks, jnp.roll(toks, -1, axis=1)


def kernel_calls(jaxpr):
    """Pallas calls by kernel name in a jaxpr and every jaxpr inside it;
    a scanned body counts once, as it is traced."""
    return collections.Counter(name for name, _ in _pallas_names(jaxpr))


def single_device_grad(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    fn = jax.jit(jax.value_and_grad(
        lambda p, toks, tgts: loss_fn(cfg, p, toks, tgts)))
    return fn, (params,)


def parallel_step(cfg):
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = shard_params(init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh)
    step = make_parallel_train_step(cfg, mesh, learning_rate=1e-2)
    return step, (params, init_adam_state(params))


def fsdp_step(cfg):
    """The FSDP step on two devices: the kernel call sits in a
    `shard_map` over 'data' inside the checkpoint."""
    mesh = make_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    params = shard_params_fsdp(init_params(cfg, jax.random.PRNGKey(0)), mesh)
    step = make_fsdp_train_step(cfg, mesh, learning_rate=1e-2)
    return step, (params, init_fsdp_adam_state(params))


# site -> (the program that goes through it, its config, attention layers
# traced: a scanned block is one)
SITES = {
    "megatron.stage_fn": (parallel_step, GPT2, 1),
    "layer_kinds.period_forward": (single_device_grad, TYPED, 3),
    "layer_kinds.one_layer": (single_device_grad, TYPED_OUTSIDE, 5),
    "transformer.forward_hidden.full": (single_device_grad, GPT2, 1),
    "transformer.forward_hidden.dots": (
        single_device_grad, dataclasses.replace(GPT2, remat_policy="dots"),
        1),
    "transformer.forward_hidden.fsdp": (fsdp_step, GPT2, 1),
    "megatron.stage_fn.typed": (parallel_step, TYPED_OUTSIDE, 5),
}


def traced_kernels(site):
    build, cfg, layers = SITES[site]
    fn, state = build(cfg)
    return kernel_calls(jax.make_jaxpr(fn)(*state, *batch(0)).jaxpr), layers


@pytest.mark.parametrize("site", sorted(SITES))
def test_gradient_program_holds_one_forward_kernel_a_layer(site,
                                                           monkeypatch):
    calls, layers = traced_kernels(site)
    assert calls == {"flash_fwd": layers, "flash_bwd": layers}
    # the rule before: a checkpoint that keeps its input alone runs the
    # forward kernel again for the backward one's sake
    monkeypatch.setattr(remat, "RESIDUAL_NAMES", ())
    calls, _ = traced_kernels(site)
    assert calls == {"flash_fwd": 2 * layers, "flash_bwd": layers}


def three_steps(site, cfg):
    """Loss and every gradient of three batches (a jitted
    value_and_grad), or three Adam steps' losses and the state they leave
    (the parallel step hands no gradient out)."""
    build = SITES[site][0]
    fn, state = build(cfg)
    out = []
    for seed in range(3):
        got = fn(*state, *batch(seed))
        if build is not single_device_grad:
            state, got = got[:2], (got[2], got[:2])
        out.append(jax.tree_util.tree_map(np.asarray, got))
    return out


@pytest.mark.parametrize("site", ["megatron.stage_fn",
                                  "layer_kinds.one_layer",
                                  "transformer.forward_hidden.full"])
def test_remat_changes_no_bit(site, monkeypatch):
    """The kept `o` and statistics are the values a second run of the
    kernel would write: the step with remat, the step without it and the
    step that keeps the input alone agree in every bit of the loss and of
    every gradient (through Adam, of every parameter and moment)."""
    cfg = SITES[site][1]
    kept = three_steps(site, cfg)
    plain = three_steps(site, dataclasses.replace(cfg, remat=False))
    monkeypatch.setattr(remat, "RESIDUAL_NAMES", ())
    input_alone = three_steps(site, cfg)
    for other in (plain, input_alone):
        for a, b in zip(jax.tree_util.tree_leaves(kept),
                        jax.tree_util.tree_leaves(other), strict=True):
            np.testing.assert_array_equal(a, b)


def test_parallel_step_refuses_the_mlp_policy_by_name():
    """`remat_policy='mlp'` is `block_forward`'s, which the parallel step
    does not run: it says so where it used to take it for 'full'."""
    step, state = parallel_step(dataclasses.replace(GPT2,
                                                    remat_policy="mlp"))
    with pytest.raises(ValueError, match="remat_policy 'mlp' is not "
                       "implemented by parallel.megatron"):
        jax.make_jaxpr(step)(*state, *batch(0))
    fn, state = single_device_grad(dataclasses.replace(TYPED,
                                                       remat_policy="mlp"))
    with pytest.raises(ValueError, match="layer_kinds.period"):
        jax.make_jaxpr(fn)(*state, *batch(0))


@pytest.mark.parametrize("site,marks,keeps", [
    ("megatron.stage_fn", 1, "input+attention"),
    ("transformer.forward_hidden.full", 1, "input+attention"),
    ("transformer.forward_hidden.dots", 1, "input+attention+dots"),
    # a layer a site: the leading one, the period's three, the module's
    ("layer_kinds.one_layer", 5, "input+attention"),
])
def test_mark_and_counter_once_a_traced_site(site, marks, keeps):
    def counted():
        fam = default_registry().get("remat_layers_total")
        return fam.labels(keeps).value if fam else 0

    def marked(after=-1):
        return [sp for sp in default_spans().snapshot().spans
                if sp.name == "remat.layer" and sp.id > after]

    before = counted()
    last = max((sp.id for sp in marked()), default=-1)
    traced_kernels(site)
    assert counted() - before == marks
    new = marked(after=last)
    assert len(new) == marks and {sp.args["keeps"] for sp in new} == {keeps}
    # and none where nothing is rematerialised
    build, cfg, _ = SITES[site]
    fn, state = build(dataclasses.replace(cfg, remat=False))
    jax.make_jaxpr(fn)(*state, *batch(0))
    assert counted() - before == marks
