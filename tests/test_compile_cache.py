"""Persistent AOT compile cache + unified program cache (ISSUE-12).

The cold-start guarantees, each proven deterministically on CPU:

- round-trip: a compiled executable serialized into the cache loads in
  a "fresh process" (in-memory program caches + jax caches cleared)
  and serves token-identically — zero jit compiles, every resolution
  ``source="aot_cache"``;
- durability: entries publish atomically (staging suffix + os.replace,
  orphaned staging files swept), and a corrupt/truncated/foreign entry
  fails CLOSED — load returns None, the entry is deleted, the engine
  recompiles and republishes, tokens unchanged;
- keying: the environment salt (jax/jaxlib versions, backend) and the
  user salt are key inputs — a different salt misses instead of
  loading a stale binary;
- warmup: `engine.warmup()` resolves the whole closed program set, so
  traffic after warmup triggers ZERO new program-cache entries and
  zero new compiles;
- the unified program cache: one `EngineConfig.program_cache_size`
  bound for every factory (the old mix of lru 8/64), with evictions
  published to ``serving_program_cache_evictions_total`` — a silent
  eviction is a silent steady-state recompile.
"""
import pathlib

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import (CompileCache, EngineConfig,
                                        InferenceEngine)
from deeplearning4j_tpu.serving.compile_cache import (
    _STAGING_SUFFIX, sweep_stray_caches)
from deeplearning4j_tpu.serving.engine import (
    DEFAULT_PROGRAM_CACHE_SIZE, _ProgramLRU, set_program_cache_size)

CFG = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                        n_layers=2, max_len=64)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(MeshSpec(data=1, model=1))


@pytest.fixture(autouse=True)
def _restore_program_cache_size():
    yield
    set_program_cache_size(DEFAULT_PROGRAM_CACHE_SIZE)


def _prompt(t0=8, seed=0):
    return (np.arange(t0, dtype=np.int32) * (seed + 3)) % CFG.vocab_size


def _run(mesh, params, prompts, **cfg_kw):
    base = dict(decode_chunk=2, max_new_tokens=6, num_slots=4,
                backoff_base_s=0.0)
    base.update(cfg_kw)
    eng = InferenceEngine(CFG, mesh, params, EngineConfig(**base))
    hs = [eng.submit(p) for p in prompts]
    eng.run_pending()
    return eng, [h.result(0) for h in hs]


def _fresh_process():
    """Simulate a replica restart inside this process: drop the
    in-memory program caches (factory entries AND their AOT-resolved
    executables) and jax's own dispatch caches — what a new process
    starts without. The on-disk AOT cache is all that survives."""
    for c in _ProgramLRU._instances:
        c.cache_clear()
    jax.clear_caches()


def _compiles(eng, source):
    total = 0.0
    for labels, child in eng._m_compiles.collect():
        if labels[1] == source:
            total += child.value
    return int(total)


# ---------------------------------------------------------------------------
# CompileCache unit behavior
# ---------------------------------------------------------------------------

def test_store_load_roundtrip_and_atomic_publish(tmp_path):
    """A toy jitted program round-trips through the cache; the
    directory never contains a staging file after publish, and a
    pre-existing orphaned staging file is swept at construction."""
    stray = tmp_path / ("x.bin" + _STAGING_SUFFIX + "-123-9")
    stray.write_bytes(b"torn half-write")
    cache = CompileCache(tmp_path)
    assert not stray.exists(), "orphaned staging file must be swept"

    fn = jax.jit(lambda x: x * 2 + 1)
    comp = fn.lower(np.ones((4,), np.float32)).compile()
    key = cache.entry_key("toy", None, (("shape", 4),))
    assert cache.load(key, jax.devices()[:1]) is None   # miss: no store
    assert cache.store(key, comp)
    assert not any(_STAGING_SUFFIX in p.name
                   for p in tmp_path.iterdir())
    loaded = cache.load(key, jax.devices()[:1])
    assert loaded is not None
    np.testing.assert_array_equal(
        np.asarray(loaded(np.ones((4,), np.float32))),
        np.asarray(comp(np.ones((4,), np.float32))))
    st = cache.stats()
    assert st["stores"] == 1 and st["hits"] == 1 and st["corrupt"] == 0


def test_corrupt_entry_fails_closed_and_is_deleted(tmp_path):
    """Truncated payloads, flipped bytes, and foreign files all load
    as None (counted corrupt) and the bad entry is removed so the
    next store publishes clean."""
    cache = CompileCache(tmp_path)
    fn = jax.jit(lambda x: x + 1)
    comp = fn.lower(np.zeros((2,), np.float32)).compile()
    key = cache.entry_key("toy", None, ())
    cache.store(key, comp)
    p = cache.path(key)

    blob = p.read_bytes()
    p.write_bytes(blob[: len(blob) // 2])            # truncated
    assert cache.load(key, jax.devices()[:1]) is None
    assert not p.exists()

    cache.store(key, comp)
    raw = bytearray(p.read_bytes())
    raw[len(raw) // 2] ^= 0xFF                       # bit flip
    p.write_bytes(bytes(raw))
    assert cache.load(key, jax.devices()[:1]) is None

    p.write_bytes(b"not an AOT entry at all")        # foreign file
    assert cache.load(key, jax.devices()[:1]) is None
    assert cache.stats()["corrupt"] == 3


def test_keys_are_salted_by_environment_and_user_salt(tmp_path, mesh1):
    """Same geometry, different salt (the stand-in for a different
    jax/jaxlib/backend) -> different key: an upgraded runtime misses
    instead of replaying a stale executable."""
    a = CompileCache(tmp_path, salt="jax-A")
    b = CompileCache(tmp_path, salt="jax-B")
    fields = (("bucket", 16), ("slots", 4))
    ka = a.entry_key("prefill", mesh1, fields)
    kb = b.entry_key("prefill", mesh1, fields)
    assert ka != kb
    assert ka == a.entry_key("prefill", mesh1, fields)  # stable
    assert a.entry_key("decode", mesh1, fields) != ka   # program name


def test_sweep_stray_caches(tmp_path):
    (tmp_path / "dl4j-aot-test-abc").mkdir()
    (tmp_path / "dl4j-aot-test-def").mkdir()
    (tmp_path / "unrelated").mkdir()
    n = sweep_stray_caches(root=tmp_path, prefix="dl4j-aot-test-")
    assert n == 2
    assert (tmp_path / "unrelated").exists()
    assert not (tmp_path / "dl4j-aot-test-abc").exists()


# ---------------------------------------------------------------------------
# engine integration: cold start -> warm start
# ---------------------------------------------------------------------------

def test_cold_then_warm_restart_loads_instead_of_compiling(
        tmp_path, params, mesh1):
    """The tentpole round-trip: a cold engine populates the cache
    (every resolution source="jit"); after a simulated restart the
    same config resolves its ENTIRE warmup set from disk
    (source="aot_cache", zero jit compiles) and serves byte-identical
    tokens."""
    prompts = [_prompt(6 + i, i) for i in range(5)]
    _, ref = _run(mesh1, params, prompts)

    _fresh_process()
    cold, got = _run(mesh1, params, prompts,
                     compile_cache_dir=str(tmp_path),
                     warmup_on_init=True)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert cold.last_warmup["jit"] == cold.last_warmup["programs"] > 0
    assert cold.last_warmup["aot_cache"] == 0
    assert cold._aot.stats()["stores"] == cold.last_warmup["programs"]

    _fresh_process()
    warm, got2 = _run(mesh1, params, prompts,
                      compile_cache_dir=str(tmp_path),
                      warmup_on_init=True)
    for a, b in zip(ref, got2):
        np.testing.assert_array_equal(a, b)
    assert warm.last_warmup["jit"] == 0, \
        "a warm restart must not XLA-compile anything"
    assert warm.last_warmup["aot_cache"] == warm.last_warmup["programs"]


def test_corrupt_cache_entry_recompiles_token_exact(
        tmp_path, params, mesh1):
    """Corrupting one on-disk entry degrades exactly one resolution to
    a recompile (which republishes a clean entry); tokens unchanged."""
    prompts = [_prompt(7, 1)]
    _fresh_process()
    _, ref = _run(mesh1, params, prompts,
                  compile_cache_dir=str(tmp_path), warmup_on_init=True)
    victim = sorted(pathlib.Path(tmp_path).glob("*.bin"))[0]
    victim.write_bytes(victim.read_bytes()[:64])

    _fresh_process()
    eng, got = _run(mesh1, params, prompts,
                    compile_cache_dir=str(tmp_path),
                    warmup_on_init=True)
    np.testing.assert_array_equal(ref[0], got[0])
    assert eng._aot.stats()["corrupt"] == 1
    assert eng.last_warmup["jit"] == 1          # only the victim
    assert eng.last_warmup["aot_cache"] == eng.last_warmup["programs"] - 1
    assert victim.exists(), "recompile must republish the entry"


def test_warmup_makes_traffic_compile_free(tmp_path, params, mesh1):
    """After warmup() the whole mixed-length trace adds ZERO compiles
    and ZERO program-cache entries — the closed-program-set claim the
    warm-up API rests on."""
    _fresh_process()
    eng = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(decode_chunk=2, max_new_tokens=6, num_slots=4,
                     compile_cache_dir=str(tmp_path)))
    report = eng.warmup()
    assert report["programs"] > 0
    jit0, aot0 = _compiles(eng, "jit"), _compiles(eng, "aot_cache")
    sizes0 = [c.cache_info().currsize for c in _ProgramLRU._instances]
    hs = [eng.submit(_prompt(4 + 5 * i, i)) for i in range(6)]
    eng.run_pending()
    assert all(h.done() for h in hs)
    assert _compiles(eng, "jit") == jit0
    assert _compiles(eng, "aot_cache") == aot0
    assert [c.cache_info().currsize
            for c in _ProgramLRU._instances] == sizes0


def test_quantized_and_paged_geometries_roundtrip(tmp_path, params,
                                                  mesh1):
    """int8-KV and paged engines cache and reload their own program
    set (distinct keys from the float/contiguous ones), token-exact
    across the restart."""
    prompts = [_prompt(6, 2), _prompt(11, 3)]
    for kw in ({"kv_quantize": "int8"},
               {"paged": True, "page_size": 8}):
        d = tmp_path / ("-".join(sorted(kw)))
        _fresh_process()
        _, ref = _run(mesh1, params, prompts, **kw)
        _fresh_process()
        _, cold = _run(mesh1, params, prompts,
                       compile_cache_dir=str(d), warmup_on_init=True,
                       **kw)
        _fresh_process()
        warm_eng, warm = _run(mesh1, params, prompts,
                              compile_cache_dir=str(d),
                              warmup_on_init=True, **kw)
        for a, b, c in zip(ref, cold, warm):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
        assert warm_eng.last_warmup["jit"] == 0


# ---------------------------------------------------------------------------
# the unified program cache (satellite)
# ---------------------------------------------------------------------------

def test_program_cache_size_unified_and_evictions_published(
        params, mesh1):
    """Shrinking EngineConfig.program_cache_size to 2 while driving >2
    prefill-bucket geometries forces evictions: the counter publishes
    them, the caches never exceed the bound, and the engine still
    completes every request correctly."""
    # the reference engine FIRST: engine construction applies its
    # config's (process-wide) program_cache_size, so the constrained
    # engine must be built last
    ref_eng = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(decode_chunk=2, max_new_tokens=4, num_slots=2,
                     max_batch_size=2))
    eng = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(decode_chunk=2, max_new_tokens=4, num_slots=2,
                     max_batch_size=2, program_cache_size=2))
    # 3 bucket geometries (16, 32, 64) + the decode program > size 2
    outs = []
    for t0 in (8, 24, 40):
        h = eng.submit(_prompt(t0, 1))
        eng.run_pending()
        outs.append(h.result(0))
    evicted = eng.registry.get(
        "serving_program_cache_evictions").value
    assert evicted > 0, "a 2-entry cache over 4+ geometries must evict"
    for c in _ProgramLRU._instances:
        assert c.cache_info().currsize <= 2
        assert c.cache_info().maxsize == 2
    # behavior unaffected: an unconstrained engine agrees byte-for-byte
    set_program_cache_size(DEFAULT_PROGRAM_CACHE_SIZE)
    for t0, want in zip((8, 24, 40), outs):
        h = ref_eng.submit(_prompt(t0, 1))
        ref_eng.run_pending()
        np.testing.assert_array_equal(h.result(0), want)


def test_program_cache_size_validates():
    with pytest.raises(ValueError, match="program_cache_size"):
        set_program_cache_size(0)


def test_compile_metrics_have_samples(params, mesh1):
    """serving_compiles_total{program,source} and
    serving_compile_seconds{program} carry samples on a plain engine —
    recompiles are observable without any cache configured."""
    _fresh_process()
    eng, _ = _run(mesh1, params, [_prompt(6, 4)])
    assert _compiles(eng, "jit") >= 2           # prefill + decode
    fams = {labels[0] for labels, _ in eng._m_compile_seconds.collect()}
    assert {"prefill", "decode"} <= fams


# ---------------------------------------------------------------------------
# util/compile_cache.enable(): where JAX's own persistent cache lives
# (ISSUE-21) — placed from outside, one fixed default, nothing else
# ---------------------------------------------------------------------------

@pytest.fixture()
def _restore_jax_cache_config():
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


def test_helper_leaves_an_outside_cache_dir_alone(
        monkeypatch, tmp_path, _restore_jax_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX has read it at import;
    the helper reports it and sets no directory in code."""
    from deeplearning4j_tpu.util import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "/set/by/jax")
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "/set/by/jax"
    assert not any(tmp_path.iterdir())


def test_helper_default_is_fixed_inside_the_checkout(
        monkeypatch, _restore_jax_cache_config):
    """Unset, the cache goes to <checkout>/.cache/jax — a fixed path
    (it is part of every entry's key), never a temp name, pid or time —
    and the keep-threshold moves only when the caller asks."""
    from deeplearning4j_tpu.util import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    assert compile_cache.enable() == str(root / ".cache" / "jax")
    assert jax.config.jax_compilation_cache_dir == str(
        root / ".cache" / "jax")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    compile_cache.enable(min_compile_time_secs=0.0)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_no_other_cache_directory_is_set_in_code():
    """Outside the tests, the helper is the only place in the repo
    that touches jax_compilation_cache_dir."""
    root = pathlib.Path(__file__).resolve().parents[1]
    helper = pathlib.Path("deeplearning4j_tpu/util/compile_cache.py")
    offenders = []
    for path in root.rglob("*.py"):
        rel = path.relative_to(root)
        if rel.parts[0].startswith(".") or rel.parts[0] == "tests" \
                or rel == helper:
            continue
        if "jax_compilation_cache_dir" in path.read_text():
            offenders.append(str(rel))
    assert offenders == []
