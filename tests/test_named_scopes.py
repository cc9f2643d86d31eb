"""Every operation of a training step resolves to one of the program's
named scopes (docs/observability.md, "Names on the device"), which
`perfbench/harness/scopes.py` reads off the device's trace by pass and by
scope. On the CPU at small sizes: each family's step through
`make_parallel_train_step`, compiled, every fusion, matrix product,
convolution, custom call and copy that carries a name path holding a name
of the vocabulary, and the rematerialised forward under
`rematted_computation`.
"""
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench.harness import scopes  # noqa: E402

KINDS = ("fusion", "dot", "convolution", "custom-call", "copy")
# jnp.cumsum as XLA:CPU lowers it (a windowed sum) carries its own name
# and no path: not the program's, and not a TPU program's either
LOWERING_OWN = {"reduce_window_sum"}


def _remat_step(name):
    import test_remat as tr
    step, state = tr.parallel_step(getattr(tr, name))
    return step, (*state, *tr.batch(0))


def _family_step(module):
    import importlib

    from jax.sharding import NamedSharding

    from deeplearning4j_tpu.parallel.megatron import (
        make_parallel_train_step, param_specs)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
    from deeplearning4j_tpu.parallel.optim import init_adam_state
    m = importlib.import_module(module)
    s, cfg = m.model("float32")
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    shardings = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), param_specs(cfg),
        is_leaf=lambda x: not isinstance(x, dict))
    params = m.ref.make_init(s, shardings)(m.ref.seed_key(0))
    step = make_parallel_train_step(cfg, mesh, learning_rate=1e-3)
    tok = np.zeros((2, 64), np.int32)
    return step, (params, init_adam_state(params), tok, tok)


STEPS = {
    "gpt2": lambda: _remat_step("GPT2"),
    "typed": lambda: _remat_step("TYPED"),
    "typed_outside": lambda: _remat_step("TYPED_OUTSIDE"),
    "qwen3_next": lambda: _family_step("test_qwen3_next"),
    "granite_hybrid": lambda: _family_step("test_granite_hybrid"),
    "glm4_moe_lite": lambda: _family_step("test_glm4_moe_lite"),
    # the gated short convolution's own names are outside the vocabulary:
    # its operations resolve to `lead` or `layers`
    "lfm2_moe": lambda: _family_step("test_lfm2_moe"),
}


@pytest.fixture(autouse=True)
def _interpreted_kernels(monkeypatch):
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")


@pytest.mark.parametrize("family", sorted(STEPS))
def test_every_operation_of_the_step_has_a_scope(family, names_in_cache_key):
    step, args = STEPS[family]()
    ops = scopes.index_from_hlo(step.lower(*args).compile().as_text())
    named = {n: op for n, op in ops.items()
             if op.opcode in KINDS and op.path
             and op.path not in LOWERING_OWN}
    assert len(named) > 50
    unscoped = sorted({re.sub(r"\d+", "N", op.path)
                       for op in named.values()
                       if scopes.scope_of(op.path) == scopes.UNSCOPED})
    assert unscoped == []
    # `other`: what depends on no parameter, such as the rotary tables,
    # computed once outside the differentiated function
    passes = {scopes.pass_of(op.path) for op in named.values()}
    assert passes - {"other"} == {"forward", "recompute", "backward",
                                  "optimizer"}
    layers = {scopes.scope_of(op.path) for op in named.values()
              if scopes.pass_of(op.path) == "recompute"}
    assert "mlp" in layers
    assert all("rematted_computation" in op.path for op in named.values()
               if scopes.pass_of(op.path) == "recompute")


def _documented_scopes():
    """The first column of the scope table in docs/observability.md."""
    text = (ROOT / "docs" / "observability.md").read_text()
    section = text.split("### Names on the device", 1)[1].split("\n## ")[0]
    return re.findall(r"^\| `([\w.]+)` \|", section, re.M)


def test_the_vocabulary_is_the_documented_list():
    assert list(scopes.VOCAB) == _documented_scopes()


@pytest.mark.parametrize("path,pass_,scope", [
    ("jit(step)/jvp(layers)/while/body/closed_call/attn/dot_general",
     "forward", "attn"),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/add", "recompute", "mlp"),
    ("jit(step)/transpose(jvp(layers))/while/body/dynamic_update_slice",
     "backward", "layers"),
    ("jit(step)/transpose(jvp(head_loss))/jit(log_softmax)/add_any",
     "backward", "head_loss"),
    ("jit(step)/optimizer/mul", "optimizer", "optimizer"),
    ("jit(step)/jvp(layers)/while/body/closed_call/mlp/moe.overflow/while/"
     "body/cond/branch_1_fun/jit(_chunk)/moe.experts/pallas_call:",
     "forward", "moe.experts"),
    ("jit(step)/jvp()/convert_element_type:", "forward", scopes.UNSCOPED),
    (None, "other", scopes.INSERTED),
])
def test_a_path_classified_by_pass_and_scope(path, pass_, scope):
    assert scopes.pass_of(path) == pass_
    assert scopes.scope_of(path) == scope
