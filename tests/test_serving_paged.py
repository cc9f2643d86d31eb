"""Paged slot KV cache + radix prefix sharing (ISSUE-7) suite.

The tentpole guarantees, each proven deterministically on the CPU
backend against the CONTIGUOUS path as the regression baseline:

- token fidelity: the paged engine (prefix sharing on) is
  byte-identical to the contiguous engine for float AND int8 KV
  pools, fresh prompts and prefix hits alike;
- the named O(1)-prefill and no-recompile-within-bucket regression
  tests hold on the paged path (block tables are runtime data);
- prefix hits SKIP prefill compute (the admission prefills only the
  un-cached suffix; `admitted` trace events carry prefix_hit_tokens)
  and share KV bytes (refcounted pages);
- copy-on-write: a full-prefix hit re-computes its last token inside
  a COPY of the shared boundary page — divergent writers never
  corrupt readers (also proven adversarially via the
  `corrupt_page_at` injector knob);
- free-list exhaustion BLOCKS admission (requests wait, resident
  pages are never corrupted) and LRU-evicts unreferenced prefix
  entries to make room;
- quarantine and hot-reload preemption release only the departing
  slot's page references — shared pages survive for their readers,
  and a reload flushes the prefix cache (cached KV encodes the old
  weights).
"""
import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.failure import ServingFaultInjector
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import (EngineConfig, InferenceEngine,
                                        RequestQuarantined,
                                        RequestStatus)
from deeplearning4j_tpu.serving.engine import (_compiled_paged_decode,
                                               _compiled_paged_prefill)
from deeplearning4j_tpu.serving.paging import (PageAllocator,
                                               RadixPrefixCache)

CFG = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                        n_layers=2, max_len=64)
PS = 8                                     # page_size for the suite


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(MeshSpec(data=1, model=1))


def _prompt(t0=8, seed=0):
    return (np.arange(t0, dtype=np.int32) * (seed + 3)) % CFG.vocab_size


def _config(**kw):
    base = dict(decode_chunk=2, max_new_tokens=6, backoff_base_s=0.0,
                paged=True, page_size=PS)
    base.update(kw)
    return EngineConfig(**base)


def _contiguous(**kw):
    kw.pop("paged", None), kw.pop("page_size", None)
    kw.pop("kv_pages", None), kw.pop("prefix_cache", None)
    base = dict(decode_chunk=2, max_new_tokens=6, backoff_base_s=0.0)
    base.update(kw)
    return EngineConfig(**base)


def _prefill_count(eng):
    return eng.registry.get(
        "serving_prefill_seconds")._unlabeled().snapshot()[2]


def _step_count(eng):
    return eng.registry.get(
        "serving_decode_step_seconds")._unlabeled().snapshot()[2]


def _shared_mix(n_shared=3, n_unique=2):
    """Co-tenant traffic: n_shared requests share an 18-token system
    prompt (2 full 8-token pages) with distinct tails, plus unique
    prompts."""
    rng = np.random.default_rng(11)
    sys_prompt = rng.integers(0, CFG.vocab_size, 18).astype(np.int32)
    out = [np.concatenate([sys_prompt,
                           rng.integers(0, CFG.vocab_size,
                                        2 + i).astype(np.int32)])
           for i in range(n_shared)]
    out += [rng.integers(0, CFG.vocab_size,
                         7 + 3 * i).astype(np.int32)
            for i in range(n_unique)]
    return out


# ---------------------------------------------------------------------------
# token fidelity vs the contiguous path
# ---------------------------------------------------------------------------

def test_paged_matches_contiguous_float(params, mesh1):
    """Paged + prefix sharing is byte-identical to the contiguous
    engine on a shared-prefix mix — fresh admissions AND a second wave
    of prefix hits, across chunk sizes."""
    for chunk in (2, 5):
        cont = InferenceEngine(CFG, mesh1, params,
                               _contiguous(decode_chunk=chunk))
        want = [cont.submit(p) for p in _shared_mix()]
        cont.run_pending()
        eng = InferenceEngine(CFG, mesh1, params,
                              _config(decode_chunk=chunk))
        got = [eng.submit(p) for p in _shared_mix()]
        eng.run_pending()
        # second wave: every prompt now hits the prefix cache
        got2 = [eng.submit(p) for p in _shared_mix()]
        eng.run_pending()
        for w, g, g2 in zip(want, got, got2):
            np.testing.assert_array_equal(g.result(0), w.result(0))
            np.testing.assert_array_equal(g2.result(0), w.result(0))
        assert eng.registry.get(
            "serving_prefix_cache_hits")._unlabeled().value >= 1


def test_paged_matches_contiguous_int8_kv(params, mesh1):
    """int8-KV paged (prefix cache off: every prompt prefills fresh,
    the exactness regime) is byte-identical to the int8-KV contiguous
    engine — quantize-on-write per page row == per slot row."""
    cont = InferenceEngine(CFG, mesh1, params, _contiguous(),
                           kv_quantize="int8")
    want = [cont.submit(p) for p in _shared_mix()]
    cont.run_pending()
    eng = InferenceEngine(CFG, mesh1, params,
                          _config(prefix_cache=False),
                          kv_quantize="int8")
    got = [eng.submit(p) for p in _shared_mix()]
    eng.run_pending()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g.result(0), w.result(0))


def test_paged_int8_prefix_hits_stay_within_quant_envelope(params,
                                                           mesh1):
    """int8 KV + prefix hits re-read the shared prefix through its
    quantization (contiguous prefill attends the float activations),
    so hit admissions are NOT bit-guaranteed — assert they still
    complete and match the contiguous int8 run at high fraction (the
    documented approximation; docs/serving.md)."""
    cont = InferenceEngine(CFG, mesh1, params, _contiguous(),
                           kv_quantize="int8")
    want = [cont.submit(p) for p in _shared_mix()]
    cont.run_pending()
    eng = InferenceEngine(CFG, mesh1, params, _config(),
                          kv_quantize="int8")
    [eng.submit(p) for p in _shared_mix()]
    eng.run_pending()
    got = [eng.submit(p) for p in _shared_mix()]   # hit wave
    eng.run_pending()
    match = np.mean([np.mean(w.result(0) == g.result(0))
                     for w, g in zip(want, got)])
    assert match >= 0.8, f"hit-wave match fraction {match}"


def test_paged_sampled_decode_matches_contiguous(params, mesh1):
    """The position-keyed sampling schedule is slot- and
    page-placement-independent: sampled decode (temperature/top_k) is
    byte-identical between paged and contiguous engines."""
    kw = dict(temperature=0.8, top_k=5, seed=3)
    cont = InferenceEngine(CFG, mesh1, params, _contiguous(**kw))
    want = [cont.submit(p) for p in _shared_mix()]
    cont.run_pending()
    eng = InferenceEngine(CFG, mesh1, params, _config(**kw))
    got = [eng.submit(p) for p in _shared_mix()]
    eng.run_pending()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g.result(0), w.result(0))


# ---------------------------------------------------------------------------
# the named regression tests, ported to the paged path
# ---------------------------------------------------------------------------

def test_paged_prefill_invocations_constant_in_chunk_count(params,
                                                           mesh1):
    """REGRESSION (ISSUE-4 port): a paged request's prompt is
    prefilled exactly ONCE however its budget divides into chunks."""
    counts = {}
    for chunk in (1, 2, 6):
        eng = InferenceEngine(
            CFG, mesh1, params,
            _config(decode_chunk=chunk, max_new_tokens=12))
        h = eng.submit(_prompt())
        eng.run_pending()
        assert h.status == RequestStatus.COMPLETED
        counts[chunk] = _prefill_count(eng)
        assert _step_count(eng) == -(-11 // chunk)
    assert counts == {1: 1, 2: 1, 6: 1}


def test_paged_no_recompile_within_bucket(params, mesh1):
    """Mixed prompt lengths inside one bucket add NO paged-prefill or
    paged-decode cache entries — block tables, hit boundaries, and
    admission patterns are runtime data. A repeat prompt (prefix hit,
    smaller suffix bucket) adds at most one prefill entry on its
    FIRST hit, then the compiled-program space is closed."""
    from helpers import assert_no_recompiles
    cfg = _config(max_new_tokens=4)
    eng = InferenceEngine(CFG, mesh1, params, cfg)
    eng.submit(_prompt(8))
    eng.run_pending()
    with assert_no_recompiles(_compiled_paged_prefill,
                              _compiled_paged_decode):
        for t0, seed in [(9, 1), (11, 2), (16, 3), (8, 4), (13, 5)]:
            eng.submit(_prompt(t0, seed))
        eng.run_pending()
    # steady-state hit traffic: the first hit may compile its (smaller)
    # suffix bucket once; repeats stay closed
    with assert_no_recompiles(_compiled_paged_prefill, allow_new=1):
        eng.submit(_prompt(16, 3))
        eng.run_pending()
    with assert_no_recompiles(_compiled_paged_prefill,
                              _compiled_paged_decode):
        eng.submit(_prompt(16, 3))
        eng.submit(_prompt(8, 4))
        eng.run_pending()


def test_paged_spec_off_bit_identical_with_unchanged_cache_keys(
        params, mesh1):
    """REGRESSION (ISSUE-8 satellite, paged twin of the continuous
    guard): a spec-off paged engine stays bit-identical to the PR-7
    paged engine and its compiled-program cache keys are unchanged —
    the legacy-signature call must HIT the entries it just created."""
    from dataclasses import astuple
    cfg = _config(max_new_tokens=4, decode_chunk=2)
    eng = InferenceEngine(CFG, mesh1, params, cfg)
    h = eng.submit(_prompt())
    eng.run_pending()
    ref = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(max_new_tokens=4, decode_chunk=2))
    hr = ref.submit(_prompt())
    ref.run_pending()
    np.testing.assert_array_equal(h.result(0), hr.result(0))
    pf = _compiled_paged_prefill.cache_info()
    dc = _compiled_paged_decode.cache_info()
    _compiled_paged_prefill(astuple(CFG), mesh1, 16, eng._num_slots,
                            PS, eng._max_pages, eng._num_pages, 0.0,
                            0, 1.0)
    _compiled_paged_decode(astuple(CFG), mesh1, 2, eng._num_slots,
                           PS, eng._max_pages, eng._num_pages, 0.0,
                           0, 1.0)
    assert _compiled_paged_prefill.cache_info().currsize == pf.currsize
    assert _compiled_paged_decode.cache_info().currsize == dc.currsize
    assert _compiled_paged_prefill.cache_info().hits > pf.hits
    assert _compiled_paged_decode.cache_info().hits > dc.hits


# ---------------------------------------------------------------------------
# prefix sharing: hits skip prefill, share bytes
# ---------------------------------------------------------------------------

def test_prefix_hit_skips_prefill_compute(params, mesh1):
    """A second tenant with the same 26-token prompt admits with a
    24-token (3-page) hit: ONE prefill invocation covering only the
    2-token suffix (the admitted event's bucket shrinks to the
    minimum), shared pages refcounted, and the output byte-equal to
    the first tenant's."""
    p26 = _prompt(26, 7)
    eng = InferenceEngine(CFG, mesh1, params,
                          _config(prefill_bucket_min=4))
    a = eng.submit(p26)
    eng.run_pending()
    assert _prefill_count(eng) == 1
    b = eng.submit(p26)
    eng.run_pending()
    assert _prefill_count(eng) == 2          # one per admission round
    adm = [e for e in b.trace.events if e.kind == "admitted"][0]
    assert adm.data["prefix_hit_tokens"] == 24
    assert adm.data["bucket"] == 4           # suffix bucket, not 32
    a_adm = [e for e in a.trace.events if e.kind == "admitted"][0]
    assert a_adm.data["prefix_hit_tokens"] == 0
    assert a_adm.data["bucket"] == 32
    np.testing.assert_array_equal(a.result(0), b.result(0))
    assert eng.registry.get(
        "serving_prefix_shared_tokens")._unlabeled().value == 24


def test_cow_divergence_on_full_prefix_hit(params, mesh1):
    """A FULL-prefix hit (prompt length a page multiple) must
    re-compute its last token inside a page the cache owns: the engine
    copies the boundary page (copy-on-write) before writing. The
    writer's run and later re-readers of the original prefix all stay
    byte-exact — the shared page was never written."""
    p24 = _prompt(24, 5)                      # 24 = 3 full pages
    cont = InferenceEngine(CFG, mesh1, params, _contiguous())
    w = cont.submit(p24)
    cont.run_pending()

    eng = InferenceEngine(CFG, mesh1, params, _config())
    a = eng.submit(p24)
    eng.run_pending()
    b = eng.submit(p24)                       # full-prefix hit -> COW
    eng.run_pending()
    adm = [e for e in b.trace.events if e.kind == "admitted"][0]
    assert adm.data["prefix_hit_tokens"] == 23   # capped at plen-1
    # a diverging tenant: same 24 tokens + a different tail
    c = eng.submit(np.concatenate([p24, _prompt(3, 9)]))
    eng.run_pending()
    d = eng.submit(p24)                       # re-read the original
    eng.run_pending()
    solo = InferenceEngine(CFG, mesh1, params, _contiguous())
    sc = solo.submit(np.concatenate([p24, _prompt(3, 9)]))
    solo.run_pending()
    for h in (a, b, d):
        np.testing.assert_array_equal(h.result(0), w.result(0))
    np.testing.assert_array_equal(c.result(0), sc.result(0))


# ---------------------------------------------------------------------------
# free-list exhaustion: admission blocks, never corrupts
# ---------------------------------------------------------------------------

def test_page_exhaustion_blocks_admission_then_proceeds(params, mesh1):
    """A pool with room for ONE resident: the second request stays
    QUEUED (blocked, not shed, nothing corrupted) until the first
    frees its pages, then completes with its exact solo tokens."""
    # prompt 9 + budget 6 -> 15 tokens -> 2 pages; a pool of 2 usable
    # pages fits exactly one resident, and the finisher's
    # cache-retained prefix page must be LRU-evicted to seat the next
    eng = InferenceEngine(CFG, mesh1, params,
                          _config(kv_pages=3, max_batch_size=2))
    a = eng.submit(_prompt(9, 1))
    b = eng.submit(_prompt(9, 2))
    assert eng.tick()                          # a admitted; b blocked
    assert a.status == RequestStatus.RUNNING
    assert b.status == RequestStatus.QUEUED
    assert eng.health()["queue_depth"] == 1
    eng.run_pending()
    assert a.status == RequestStatus.COMPLETED
    assert b.status == RequestStatus.COMPLETED
    ev = eng.registry.get(
        "serving_prefix_cache_evictions")._unlabeled().value
    assert ev >= 1                             # a's cached page evicted
    for h in (a, b):
        solo = InferenceEngine(CFG, mesh1, params, _contiguous())
        s = solo.submit(h.prompt)
        solo.run_pending()
        np.testing.assert_array_equal(h.result(0), s.result(0))


def test_request_that_can_never_fit_is_rejected(params, mesh1):
    """Static validation: a request whose worst case exceeds the whole
    pool is rejected at submit (blocking would deadlock)."""
    eng = InferenceEngine(CFG, mesh1, params, _config(kv_pages=3))
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit(_prompt(30), max_new_tokens=6)


# ---------------------------------------------------------------------------
# fault isolation on shared pages
# ---------------------------------------------------------------------------

def test_quarantine_never_frees_shared_pages(params, mesh1):
    """Reader A and poisoned writer B share a cached prefix. B's pool
    failure preempts both; B quarantines, A completes solo with its
    exact clean-run tokens, and a LATER tenant C still hits the shared
    prefix and decodes exactly — B's quarantine released only B's own
    references."""
    p = _prompt(26, 7)
    cont = InferenceEngine(CFG, mesh1, params,
                           _contiguous(max_new_tokens=8))
    w = cont.submit(p)
    cont.run_pending()

    inj = ServingFaultInjector()
    eng = InferenceEngine(CFG, mesh1, params,
                          _config(max_new_tokens=8, max_retries=1),
                          fault_injector=inj)
    seed_req = eng.submit(p)                   # populates the cache
    eng.run_pending()
    a = eng.submit(p)                          # reader (prefix hit)
    bad = eng.submit(p)                        # writer twin
    inj.poison_requests.add(bad.rid)
    eng.run_pending()
    assert bad.status == RequestStatus.QUARANTINED
    with pytest.raises(RequestQuarantined):
        bad.result(0)
    np.testing.assert_array_equal(a.result(0), w.result(0))
    np.testing.assert_array_equal(seed_req.result(0), w.result(0))
    c = eng.submit(p)
    eng.run_pending()
    adm = [e for e in c.trace.events if e.kind == "admitted"][0]
    assert adm.data["prefix_hit_tokens"] > 0   # cache survived
    np.testing.assert_array_equal(c.result(0), w.result(0))


def test_corrupt_page_knob_isolates_writer_from_reader(params, mesh1):
    """`corrupt_page_at`: poison the WRITER's next-write page mid-
    stream. COW isolation means the writer's tokens go wrong while the
    co-resident reader sharing the prefix — and every later reader of
    the cached pages — stays byte-exact."""
    p = _prompt(26, 7)
    clean = InferenceEngine(CFG, mesh1, params,
                            _contiguous(max_new_tokens=8))
    w = clean.submit(p)
    clean.run_pending()

    inj = ServingFaultInjector(corrupt_page_at={})
    eng = InferenceEngine(CFG, mesh1, params,
                          _config(max_new_tokens=8),
                          fault_injector=inj)
    seed_req = eng.submit(p)
    eng.run_pending()
    reader = eng.submit(p)
    writer = eng.submit(p)
    eng.tick()                                 # both admitted, 1 chunk
    # poison the writer's decode page before the NEXT chunk
    inj.corrupt_page_at[eng._step_counter] = writer.rid
    eng.run_pending()
    assert inj.pages_corrupted == 1
    assert writer.status == RequestStatus.COMPLETED
    assert not np.array_equal(writer.result(0), w.result(0)), \
        "corruption must actually land on the writer"
    np.testing.assert_array_equal(reader.result(0), w.result(0))
    later = eng.submit(p)
    eng.run_pending()
    np.testing.assert_array_equal(later.result(0), w.result(0))
    np.testing.assert_array_equal(seed_req.result(0), w.result(0))


# ---------------------------------------------------------------------------
# hot reload: preemption + prefix-cache flush
# ---------------------------------------------------------------------------

def test_reload_preempts_and_flushes_prefix_cache(tmp_path, params,
                                                  mesh1):
    """Mid-stream reload on a paged engine: the in-flight slot is
    preempted and resumes under the new weights with its committed
    prefix intact, AND the prefix cache is flushed — a post-reload
    admission of a previously-cached prompt must MISS (stale KV
    encodes the old weights) and decode under the new tree."""
    from deeplearning4j_tpu.util.checkpointing import CheckpointManager
    mgr = CheckpointManager(str(tmp_path / "w"), use_orbax=False)
    mgr.save_tree(params, 1)
    mgr.save_tree(jax.tree_util.tree_map(lambda a: a * 0, params), 2)

    p = _prompt(26, 7)
    eng = InferenceEngine(CFG, mesh1, params,
                          _config(max_new_tokens=10))
    warm = eng.submit(p)                       # populate the cache
    eng.run_pending()
    h = eng.submit(p)
    for _ in range(4):        # prefix hit, ~1 chunk committed (the
        eng.tick()            # pipelined default commits a tick late)
        committed = h.generated.copy()
        if committed.shape[0] > 0:
            break
    assert 0 < committed.shape[0] < 10
    assert eng.reload_weights(mgr, step=2) == 2
    assert h.status == RequestStatus.QUEUED
    assert len(eng._prefix_cache) == 0         # flushed
    assert eng._allocator.pages_used == 0      # everything returned
    eng.run_pending()
    assert h.status == RequestStatus.COMPLETED
    np.testing.assert_array_equal(
        h.generated[:committed.shape[0]], committed)
    # post-flush traffic decodes under the NEW weights even when it
    # hits a (re-populated, new-weights) prefix: byte-equal to a
    # contiguous engine built on the zeroed tree, and different from
    # the old-weights run
    nxt = eng.submit(p)
    eng.run_pending()
    zeroed = jax.tree_util.tree_map(lambda a: a * 0, params)
    ref = InferenceEngine(CFG, mesh1, zeroed,
                          _contiguous(max_new_tokens=10))
    hz = ref.submit(p)
    ref.run_pending()
    np.testing.assert_array_equal(nxt.result(0), hz.result(0))
    old = InferenceEngine(CFG, mesh1, params,
                          _contiguous(max_new_tokens=10))
    ho = old.submit(p)
    old.run_pending()
    assert not np.array_equal(nxt.generated, ho.generated)
    assert warm.status == RequestStatus.COMPLETED


# ---------------------------------------------------------------------------
# observability: gauges, counters, naming conventions, debugz
# ---------------------------------------------------------------------------

def test_paged_metrics_published_and_lint_clean(params, mesh1):
    """The new series publish into the engine registry with the exact
    names ISSUE-7 specifies and obey the test_metrics_naming.py
    conventions (counters expose _total, gauges never do)."""
    import re

    from deeplearning4j_tpu.observability.export import prometheus_text

    eng = InferenceEngine(CFG, mesh1, params, _config())
    p = _prompt(26, 7)
    eng.submit(p)
    eng.run_pending()
    eng.submit(p)
    eng.run_pending()
    free = eng.registry.get("serving_kv_pages_free")
    used = eng.registry.get("serving_kv_pages_used")
    assert free.value + used.value == eng._allocator.usable_pages
    assert used.value > 0                      # cache retains pages
    text = prometheus_text(eng.registry)
    assert "serving_prefix_cache_hits_total 1" in text
    assert "serving_prefix_cache_misses_total 1" in text
    assert "serving_prefix_cache_evictions_total 0" in text
    assert "serving_prefix_shared_tokens_total 24" in text
    assert "serving_kv_pages_free" in text
    assert "serving_kv_pages_used" in text
    snake = re.compile(r"^[a-z][a-z0-9_]*$")
    types = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
    for name, kind in types.items():
        assert snake.match(name), name
        if kind == "counter":
            assert name.endswith("_total"), name
        else:
            assert not name.endswith("_total"), name

    d = eng.debugz()["paged"]
    assert d["page_size"] == PS
    assert d["pages_free"] == free.value
    assert d["prefix_cache"]["hits"] == 1
    assert d["prefix_cache"]["shared_tokens"] == 24
    # kv accounting: analytic (fresh engine) vs measured agree
    fresh = InferenceEngine(CFG, mesh1, params, _config())
    analytic = fresh.kv_pool_bytes()
    fresh.submit(_prompt())
    fresh.run_pending()
    assert fresh.kv_pool_bytes() == analytic


def test_paged_pool_is_smaller_at_equal_capacity(params, mesh1):
    """The capacity lever itself: serving the shared-prefix mix at the
    same slot count, a working-set-sized paged pool holds >= 40% fewer
    KV bytes than the contiguous pool (ISSUE-7 acceptance)."""
    cont = InferenceEngine(CFG, mesh1, params, _contiguous())
    want = [cont.submit(p) for p in _shared_mix()]
    cont.run_pending()
    # working set: 5 requests x <= 4 pages, shared prefix 2 pages
    eng = InferenceEngine(CFG, mesh1, params, _config(kv_pages=24))
    got = [eng.submit(p) for p in _shared_mix()]
    eng.run_pending()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g.result(0), w.result(0))
    saved = 1 - eng.kv_pool_bytes() / cont.kv_pool_bytes()
    assert saved >= 0.40, f"paged pool only saved {saved:.1%}"


# ---------------------------------------------------------------------------
# host-layer units: allocator + radix cache
# ---------------------------------------------------------------------------

def test_page_allocator_refcounts():
    al = PageAllocator(num_pages=4, page_size=8)
    assert al.usable_pages == 3
    a, b = al.alloc(), al.alloc()
    assert {a, b}.isdisjoint({0})
    al.incref(a)
    al.decref(a)
    assert al.refcount(a) == 1 and al.pages_free == 1
    al.decref(a)
    assert al.pages_free == 2
    with pytest.raises(ValueError):
        al.decref(a)
    c, d = al.alloc(), al.alloc()
    assert al.alloc() is None                  # exhausted
    assert {b, c, d} == {1, 2, 3} and al.pages_used == 3


def test_radix_cache_match_insert_evict():
    al = PageAllocator(num_pages=8, page_size=2)
    cache = RadixPrefixCache(2, al)
    pages = [al.alloc() for _ in range(3)]
    cache.insert([1, 2, 3, 4, 5, 6], pages)
    assert len(cache) == 3
    assert [al.refcount(p) for p in pages] == [2, 2, 2]
    assert cache.match([1, 2, 3, 4, 9, 9]) == pages[:2]
    assert cache.match([7, 7]) == []
    # owner departs; chain becomes evictable leaf-first
    for p in pages:
        al.decref(p)
    assert cache.evict(1) == 1 and len(cache) == 2
    assert cache.match([1, 2, 3, 4, 5, 6]) == pages[:2]
    assert cache.evict(10) == 2 and len(cache) == 0
    assert al.pages_free == al.usable_pages
    # flush decrefs everything
    pages2 = [al.alloc() for _ in range(2)]
    cache.insert([1, 2, 3, 4], pages2)
    for p in pages2:
        al.decref(p)
    assert cache.flush() == 2
    assert al.pages_free == al.usable_pages


def test_paged_requires_continuous_and_data1(params):
    with pytest.raises(ValueError, match="continuous"):
        InferenceEngine(CFG, make_mesh(MeshSpec(data=1, model=1)),
                        params, _config(mode="batch"))


def test_paged_on_tp_mesh(params, devices8):
    """Paged serving on a tensor-parallel (model=2) mesh matches the
    1x1 contiguous run — heads shard over 'model', pages replicate."""
    mesh = make_mesh(MeshSpec(data=1, model=2))
    mesh1 = make_mesh(MeshSpec(data=1, model=1))
    cont = InferenceEngine(CFG, mesh1, params, _contiguous())
    want = [cont.submit(p) for p in _shared_mix()]
    cont.run_pending()
    eng = InferenceEngine(CFG, mesh, params, _config())
    got = [eng.submit(p) for p in _shared_mix()]
    eng.run_pending()
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g.result(0), w.result(0))
    mesh_d = make_mesh(MeshSpec(data=2, model=1))
    with pytest.raises(ValueError, match="data=1"):
        InferenceEngine(CFG, mesh_d, params, _config())
