"""The serving engine on its KERNEL path (ISSUE-21).

On a TPU every serving program dispatches the Pallas kernels
(ops/flash_decode.py, ops/flash_attention.py) from inside
`jax.shard_map(check_vma=True)`. The rest of the suite runs shapes the
kernels are not eligible for, or leaves `DL4JTPU_FLASH` unset, so on
the CPU it only ever sees the jnp reference path. Here the kernels are
forced on in interpret mode at eligible shapes (head_dim % 8 == 0,
cache >= 128 rows):

- requests complete through the background worker, token for token
  equal to the jnp-path engine (float32: no near-ties to flip), with
  nothing quarantined;
- the speculative engine's verify window takes its kernel too;
- a program that cannot compile RAISES out of `_resolve_program`
  instead of being handed back un-compiled to the retry loop.
"""
import jax
import jax.experimental.pallas as pl
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import EngineConfig, InferenceEngine
from deeplearning4j_tpu.serving.engine import (_ProgramLRU,
                                               _program_cache)

# distinct geometry: no other module's cached programs can be reused
CFG = TransformerConfig(vocab_size=48, d_model=64, n_heads=4, n_layers=2,
                        max_len=160)
NEW = 6


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(MeshSpec(data=1, model=1))


def _clear_programs():
    """The process-wide program caches key on geometry, not on
    DL4JTPU_FLASH: drop them so a program traced under one setting is
    never served under the other."""
    for c in _ProgramLRU._instances:
        c.cache_clear()


@pytest.fixture()
def kernel_calls(monkeypatch):
    """Force the kernels on (interpret mode) and count pallas_call
    traces; the program caches are dropped on the way in and out."""
    calls = []
    real = pl.pallas_call

    def spy(kernel, *a, **kw):
        calls.append(getattr(kernel, "func", kernel).__name__)
        return real(kernel, *a, **kw)

    _clear_programs()
    monkeypatch.setattr(pl, "pallas_call", spy)
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    yield calls
    _clear_programs()


def _prompts():
    rng = np.random.default_rng(17)
    shared = rng.integers(0, CFG.vocab_size, 32).astype(np.int32)
    out = [np.concatenate([shared, rng.integers(
        0, CFG.vocab_size, 9 + 20 * i).astype(np.int32)])
        for i in range(2)]
    out.append(rng.integers(0, CFG.vocab_size, 70).astype(np.int32))
    return out


def _config(**kw):
    return EngineConfig(mode="continuous", paged=True, page_size=16,
                        prefix_cache=True, prefill_chunk=64,
                        pipeline=True, num_slots=4, max_batch_size=4,
                        decode_chunk=2, max_new_tokens=NEW,
                        backoff_base_s=0.0, **kw)


def _serve(mesh, params, background: bool, **kw):
    eng = InferenceEngine(CFG, mesh, params, _config(**kw))
    eng.warmup()
    if background:
        eng.start()
    try:
        hs = [eng.submit(p) for p in _prompts()]
        if not background:
            eng.run_pending()
        outs = [np.asarray(h.result(120))[-NEW:] for h in hs]
    finally:
        eng.stop(drain=False)
    assert eng.stats["quarantined"] == 0
    assert eng.stats["step_failures"] == 0
    assert eng.health()["breaker"] == "closed"
    return outs


def test_engine_completes_on_the_kernel_path(params, mesh1, kernel_calls):
    """Paged + chunked-prefill + pipelined engine, background worker,
    kernels inside shard_map(check_vma=True): every request completes,
    and the tokens equal the jnp-path engine's."""
    got = _serve(mesh1, params, background=True)
    assert "_decode_kernel" in kernel_calls
    _clear_programs()
    n = len(kernel_calls)
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("DL4JTPU_FLASH")
        want = _serve(mesh1, params, background=False)
    assert len(kernel_calls) == n, "the reference engine traced a kernel"
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_spec_engine_verifies_through_the_window_kernel(params, mesh1,
                                                        kernel_calls):
    """spec_decode on the paged pool: warmup resolves the PAGED
    speculative program (it used to warm the contiguous one against
    the paged state), the verify pass traces `_window_kernel`, drafts
    are accepted, and the tokens equal the plain kernel-path engine."""
    spec = _serve(mesh1, params, background=True, spec_decode=True,
                  draft="self", spec_k=2, spec_adaptive=False)
    assert "_window_kernel" in kernel_calls
    plain = _serve(mesh1, params, background=False)
    for a, b in zip(spec, plain):
        np.testing.assert_array_equal(a, b)


def test_program_that_cannot_compile_raises(params, mesh1):
    """`_resolve_program` used to log "falling back to lazy jit" and
    return the un-compiled callable, which then failed inside every
    retry until the request was quarantined. It raises now, and
    memoises nothing."""
    @_program_cache
    def _compiled_broken(cfg_fields, mesh, chunk, num_slots):
        def run(x):
            raise ValueError("this program does not trace")
        return jax.jit(run)

    eng = InferenceEngine(CFG, mesh1, params, _config())
    fargs = (("broken",), mesh1, 2, 4)
    try:
        with pytest.raises(ValueError, match="does not trace"):
            eng._resolve_program("decode", _compiled_broken, fargs, {},
                                 (np.zeros((2,), np.float32),))
        assert _compiled_broken.entry(*fargs).get("exec") is None
    finally:
        _ProgramLRU._instances.remove(_compiled_broken)
