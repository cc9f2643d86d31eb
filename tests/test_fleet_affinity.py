"""Fleet-wide prefix-cache affinity dispatch + KV migration (ISSUE-14).

The properties, each proven deterministically on CPU:

- digest mechanics: chain hashes are deterministic and page-aligned,
  the top-K ranking advertises the hottest/deepest chains, the bloom
  false-positive rate respects its analytic bound, the generation
  counter bumps on insert/evict/flush (the idle-replica staleness
  fix), and the digest is stable (cached) across probe cycles;
- affinity dispatch: two requests sharing a system prompt land on the
  SAME replica (counted serving_fleet_affinity_hits_total), the
  anti-herd cap spills a hot tenant off an occupied replica, and a
  stale advertisement ages out by TTL;
- KV migration: capacity-forced spillover ships the cached chain to
  the cold replica (engine.export_cached_chain -> cache-source
  KVHandoff -> radix-cache seed), which then serves the request as an
  ordinary prefix hit — token-exact, no re-prefill of the shared
  chain, and zero steady-state recompiles on the adopt path;
- mispredicts (evicted chain / bloom false positive) cost one normal
  prefill and are counted, never wrong.
"""
import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import (EngineConfig, FleetConfig,
                                        InferenceEngine, Router)
from deeplearning4j_tpu.serving.engine import (_compiled_chain_adopt,
                                               _compiled_page_gather)
from deeplearning4j_tpu.serving.paging import (PageAllocator,
                                               RadixPrefixCache,
                                               chain_hashes,
                                               digest_lookup)
from helpers import assert_no_recompiles

CFG = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                        n_layers=2, max_len=64)
PS = 4                                     # page size under test
SHARED = np.arange(16, dtype=np.int32)     # 4 full pages


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(MeshSpec(data=1, model=1))


def _prompt(i):
    """SHARED system prompt + a 2-token per-request tail."""
    return np.concatenate([SHARED,
                           np.asarray([5 + i, (7 + i) % 32], np.int32)])


def _config(**kw):
    base = dict(decode_chunk=2, max_new_tokens=4, max_batch_size=1,
                num_slots=1, paged=True, page_size=PS,
                backoff_base_s=0.0)
    base.update(kw)
    return EngineConfig(**base)


def _router(params, mesh1, n=2, fleet_kw=None, **cfg_kw):
    return Router(cfg=CFG, mesh=mesh1, params=params, num_replicas=n,
                  engine_config=_config(**cfg_kw),
                  config=FleetConfig(migrate_min_tokens=8,
                                     **(fleet_kw or {})))


def _dispatch_replicas(fr):
    return [e.data["replica"] for e in fr.trace.events
            if e.kind == "dispatched"]


# ---------------------------------------------------------------------------
# digest mechanics
# ---------------------------------------------------------------------------

def test_chain_hashes_deterministic_and_page_aligned():
    toks = np.arange(19, dtype=np.int32)   # 4 full pages + 3 tail
    hs = chain_hashes(toks, PS)
    assert len(hs) == 4                    # the tail never hashes
    assert hs == chain_hashes(toks.tolist(), PS)
    # prefix property: shorter prompts share the leading hashes
    assert chain_hashes(toks[:8], PS) == hs[:2]
    # content-sensitive
    other = toks.copy()
    other[0] += 1
    assert chain_hashes(other, PS)[0] != hs[0]


def _warm_cache(chains):
    """A radix cache whose sole owner is the cache itself (the
    post-_free_slot steady state)."""
    al = PageAllocator(256, PS)
    c = RadixPrefixCache(PS, al)
    for toks in chains:
        pages = [al.alloc() for _ in range(len(toks) // PS)]
        c.insert(toks, pages)
        al.release_chain(pages)
    return c, al


def test_digest_top_k_ranks_hot_chains_and_matches_exactly():
    chains = [np.arange(100 * i, 100 * i + 16, dtype=np.int32) % 97
              for i in range(6)]
    c, _ = _warm_cache(chains)
    # touch chain 3 last: its nodes are the most recent
    c.match(chains[3])
    d = c.chain_digest(top_k=4)
    assert d["entries"] == 24 and d["page_size"] == PS
    assert len(d["top"]) == 4
    top_hashes = {h for h, _ in d["top"]}
    want = chain_hashes(chains[3], PS)
    assert want[-1] in top_hashes          # the hot deep chain leads
    # exact lookup on the hot chain, bloom fallback on a cold one
    toks, h = digest_lookup(d, want)
    assert toks == 16 and h == want[-1]
    toks0, _ = digest_lookup(d, chain_hashes(chains[0], PS))
    assert toks0 == 16                     # via bloom


def test_digest_bloom_false_positive_bound():
    """Measured per-hash FP rate over absent probes stays within 2x
    the analytic (1 - e^{-kn/m})^k bound (+ small-sample slack)."""
    import math
    from deeplearning4j_tpu.serving.paging import bloom_has
    chains = [np.arange(31 * i, 31 * i + 16, dtype=np.int32) % 1009
              for i in range(16)]
    c, _ = _warm_cache(chains)
    d = c.chain_digest(top_k=0)            # bloom-only digest
    n = d["entries"]
    m, k = d["bloom_m"], d["bloom_k"]
    bits = int(d["bloom"], 16)
    bound = (1 - math.exp(-k * n / m)) ** k
    trials, fp = 5000, 0
    rng = np.random.default_rng(7)
    for h in rng.integers(1, 2 ** 63, trials):
        fp += bloom_has(bits, int(h), m, k)
    rate = fp / trials
    assert rate <= 2 * bound + 0.01, \
        f"bloom FP {rate:.4f} vs bound {bound:.4f} (n={n})"


def test_generation_bumps_on_insert_evict_flush():
    c, al = _warm_cache([np.arange(16, dtype=np.int32)])
    g0 = c.generation
    assert g0 >= 1
    assert c.evict(1) == 1
    assert c.generation == g0 + 1
    pages = [al.alloc() for _ in range(2)]
    c.insert(np.arange(50, 58, dtype=np.int32), pages)
    al.release_chain(pages)
    assert c.generation == g0 + 2
    c.flush()
    assert c.generation == g0 + 3
    # and the digest is REBUILT per generation, cached within one
    d = c.chain_digest()
    assert d["generation"] == c.generation
    assert c.chain_digest() is d


def test_digest_stable_across_probe_cycles(params, mesh1):
    """An idle engine's health probes return the SAME digest object
    cycle after cycle (generation-keyed cache) — and traffic moves
    the generation."""
    eng = InferenceEngine(CFG, mesh1, params, _config())
    h = eng.submit(_prompt(0))
    eng.run_pending()
    d1 = eng.health()["prefix_digest"]
    d2 = eng.health()["prefix_digest"]
    assert d1 is d2                        # cached: idle probes are free
    g = d1["generation"]
    h2 = eng.submit(np.arange(40, 58, dtype=np.int32) % 32)
    eng.run_pending()
    assert eng.health()["prefix_digest"]["generation"] > g
    assert h.done() and h2.done()


# ---------------------------------------------------------------------------
# affinity dispatch
# ---------------------------------------------------------------------------

def test_shared_prompt_lands_on_the_same_replica(params, mesh1):
    """The e2e affinity property: with equal occupancy everywhere, a
    request sharing an already-served system prompt follows the cache
    — counted as an affinity hit, served as a prefix-cache hit."""
    router = _router(params, mesh1)
    try:
        h0 = router.submit(_prompt(0))
        router.run_pending()
        first = _dispatch_replicas(h0)[0]
        h1 = router.submit(_prompt(1))
        router.run_pending()
        ev = [e for e in h1.trace.events if e.kind == "dispatched"][0]
        assert ev.data["replica"] == first
        assert ev.data["affinity_tokens"] >= SHARED.shape[0]
        assert router.stats["affinity_hits"] == 1
        assert router.stats["affinity_mispredicts"] == 0
        eng = router._ctl(first).replica.engine
        assert eng.registry.get(
            "serving_prefix_cache_hits").value == 1
    finally:
        router.close()


@pytest.mark.parametrize("trace", ["one_prompt", "tenants"])
def test_occupancy_only_control_arm_ignores_affinity(params, mesh1,
                                                     trace):
    """affinity_weight=0 is the control: dispatch falls back to pure
    occupancy and no affinity series moves. `tenants`: on three
    tenants' requests over two 2-slot replicas (each tenant its own
    16-token system prompt; one request a tenant first, then the other
    nine at once) the control prefills at least 1.5x the tokens that
    affinity dispatch with migration does, for the same tokens out."""
    def tenant_prompt(t, i):
        system = (np.arange(16, dtype=np.int32) * (t + 2) + t) % 29
        return np.concatenate(
            [system, np.asarray([5 + i, (7 + i) % 32], np.int32)])
    if trace == "one_prompt":
        slots, waves = 1, [[_prompt(i)] for i in range(3)]
    else:
        rest = [(t, i) for i in range(1, 4) for t in range(3)]
        slots, waves = 2, [
            [tenant_prompt(t, 0) for t in range(3)],
            [tenant_prompt(*rest[5 * k % 9]) for k in range(9)]]

    def replay(fleet_kw):
        router = _router(params, mesh1, fleet_kw=fleet_kw,
                         num_slots=slots, max_batch_size=slots)
        try:
            hs = []
            for wave in waves:
                hs += [router.submit(p) for p in wave]
                router.run_pending()
            prefilled = sum(
                c.replica.engine.registry.get(
                    "serving_prefill_tokens").value
                for c in router._ctls)
            return (prefilled, dict(router.stats),
                    [h.result(0) for h in hs])
        finally:
            router.close()

    occ, occ_stats, occ_tokens = replay(
        dict(affinity_weight=0.0, migrate_kv=False))
    assert occ_stats["affinity_hits"] == 0
    assert occ_stats["kv_migrations_ok"] == 0
    if trace == "tenants":
        aff, aff_stats, aff_tokens = replay(None)
        assert aff_stats["affinity_hits"] > 0
        assert occ >= 1.5 * aff, (occ, aff)
        for a, b in zip(occ_tokens, aff_tokens):
            np.testing.assert_array_equal(a, b)


def test_anti_herd_cap_spills_to_an_emptier_replica(params, mesh1):
    """A warm replica at/above the occupancy cap gets NO affinity
    bonus: the shared-prefix request spills to the empty replica
    instead of piling onto the hot one (which, with seats still free,
    plain affinity WOULD have picked)."""
    router = _router(params, mesh1,
                     fleet_kw=dict(migrate_kv=False,
                                   affinity_max_occupancy=0.5),
                     max_new_tokens=24, decode_chunk=2,
                     num_slots=2, max_batch_size=2)
    try:
        h0 = router.submit(_prompt(0))
        router.run_pending()
        first = _dispatch_replicas(h0)[0]
        # park a long decode on the warm replica (affinity sends it
        # there; occupancy then sits AT the 0.5 cap), then submit a
        # shared-prefix request while it is still resident
        long = router.submit(_prompt(1), max_new_tokens=24)
        for _ in range(200):
            router.tick()
            if _dispatch_replicas(long):
                break
        assert _dispatch_replicas(long) == [first]
        h2 = router.submit(_prompt(2))
        router.run_pending()
        # a free seat remained on the warm replica — only the
        # anti-herd cap explains the spill
        assert _dispatch_replicas(h2)[0] == 1 - first
        assert long.done() and h2.done()
    finally:
        router.close()


def test_stale_digest_ages_out_by_ttl(params, mesh1):
    """An advertisement older than affinity_digest_ttl_s is ignored —
    probes that stopped refreshing a digest stop attracting traffic."""
    router = _router(params, mesh1)
    try:
        h0 = router.submit(_prompt(0))
        router.run_pending()
        ctl = router._ctl(_dispatch_replicas(h0)[0])
        assert ctl.digest is not None
        now = router._clock()
        assert router._affinity_tokens(ctl, _FR(_prompt(1)), now)[0] \
            >= SHARED.shape[0]
        ctl.digest_at = now - (router.config.affinity_digest_ttl_s + 1)
        assert router._affinity_tokens(ctl, _FR(_prompt(1)),
                                       now) == (0, None)
    finally:
        router.close()


class _FR:
    """Minimal FleetHandle stand-in for the affinity-lookup unit."""

    def __init__(self, prompt):
        self.prompt = np.asarray(prompt, np.int32)
        self._chain_hashes = {}


# ---------------------------------------------------------------------------
# KV migration
# ---------------------------------------------------------------------------

def test_migration_seeds_the_cold_replica(params, mesh1):
    """THE scale-out property: capacity forces a shared-prefix request
    onto the cold replica; the router ships the chain with the
    dispatch; the cold replica serves it as an ordinary prefix hit —
    no re-prefill of the shared chain, token-exact vs a solo run."""
    router = _router(params, mesh1)
    try:
        h0 = router.submit(_prompt(0))
        router.run_pending()
        first = _dispatch_replicas(h0)[0]
        # two CONCURRENT shared-prefix requests against capacity-1
        # replicas: one must spill to the cold replica
        ha = router.submit(_prompt(1))
        hb = router.submit(_prompt(2))
        router.run_pending()
        s = router.stats
        assert s["kv_migrations_ok"] == 1, s
        assert s["kv_migrated_tokens"] >= SHARED.shape[0]
        spilled = [fr for fr in (ha, hb)
                   if _dispatch_replicas(fr)[0] != first]
        assert len(spilled) == 1
        mig = [e for fr in (ha, hb) for e in fr.trace.events
               if e.kind == "kv_migration"]
        assert len(mig) == 1 and mig[0].data["outcome"] == "ok"
        assert mig[0].data["from"] == first
        assert mig[0].data["tokens"] >= SHARED.shape[0]
        cold = router._ctl(1 - first).replica.engine
        assert cold.registry.get(
            "serving_prefix_cache_hits").value >= 1
        assert cold.registry.get(
            "serving_prefix_shared_tokens").value >= SHARED.shape[0]
        # the cold replica prefilled ONLY the private tail
        assert cold.registry.get("serving_prefill_tokens").value \
            <= _prompt(1).shape[0] - SHARED.shape[0] + PS
        # token-exact vs solo runs
        for fr in (ha, hb):
            solo = InferenceEngine(CFG, mesh1, params, _config())
            hs = solo.submit(fr.prompt)
            solo.run_pending()
            np.testing.assert_array_equal(
                np.concatenate([fr.prompt, fr.generated]),
                hs.result(0))
        # debugz surfaces the advertisement
        rows = router.debugz()["replicas"]
        assert all(r["prefix_digest"] is not None for r in rows)
    finally:
        router.close()


def test_migration_adopt_path_never_recompiles(params, mesh1):
    """helpers.assert_no_recompiles over the migration adopt path
    (ISSUE-14 satellite): after the first migration warms the
    chain-adopt/page-gather programs, further migrations of OTHER
    tenants compile nothing — chains, pages, and indices are all
    runtime data."""
    router = _router(params, mesh1)
    try:
        def tenant_wave(base):
            shared = (np.arange(16, dtype=np.int32) + base) % 29
            h0 = router.submit(np.concatenate(
                [shared, np.asarray([1 + base % 7, 2], np.int32)]))
            router.run_pending()
            ha = router.submit(np.concatenate(
                [shared, np.asarray([3, 4 + base % 5], np.int32)]))
            hb = router.submit(np.concatenate(
                [shared, np.asarray([5, 6], np.int32)]))
            router.run_pending()
            assert h0.done() and ha.done() and hb.done()

        tenant_wave(0)                     # warms the adopt programs
        before = router.stats["kv_migrations_ok"]
        assert before >= 1
        with assert_no_recompiles(_compiled_chain_adopt,
                                  _compiled_page_gather):
            tenant_wave(100)
        assert router.stats["kv_migrations_ok"] > before
    finally:
        router.close()


def test_stale_advertised_chain_counts_stale_and_mispredict(params,
                                                            mesh1):
    """A digest advertising a chain the source has since evicted:
    export returns None (stale), the request prefills normally on its
    target, and the mispredict counter catches the shortfall. Probes
    are slowed to one (tick 0) so the pinned stale advertisement is
    exactly what a router between probe cycles would hold."""
    router = _router(params, mesh1,
                     fleet_kw=dict(probe_every_ticks=10 ** 6))
    try:
        h0 = router.submit(_prompt(0))
        router.run_pending()
        first = _dispatch_replicas(h0)[0]
        warm_eng = router._ctl(first).replica.engine
        stale_digest = warm_eng.health()["prefix_digest"]
        assert stale_digest["entries"] > 0
        # flush the source cache behind the advertisement's back and
        # pin the stale digest on the warm replica only: the first
        # concurrent request follows the (stale) affinity there and
        # MISPREDICTS; the second spills to the cold replica, whose
        # migration pull finds the chain gone — STALE
        warm_eng._prefix_cache.flush()
        now = router._clock()
        for ctl in router._ctls:
            ctl.digest = (dict(stale_digest) if ctl.id == first
                          else None)
            ctl.digest_at = now
        ha = router.submit(_prompt(1))
        hb = router.submit(_prompt(2))
        router.run_pending()
        s = router.stats
        assert s["kv_migrations_stale"] >= 1, s
        assert s["affinity_mispredicts"] >= 1, s
        for fr in (ha, hb):
            assert fr.status == "completed"
    finally:
        router.close()


def test_cache_source_handoff_weights_skew_refused(params, mesh1):
    """A migrated chain encodes the exporter's weights: a target on a
    different weights version refuses the seed (counted seed_failed)
    and prefills — correct tokens, no poisoned cache."""
    src = InferenceEngine(CFG, mesh1, params, _config())
    h = src.submit(_prompt(0))
    src.run_pending()
    dg = src.health()["prefix_digest"]
    toks, ch = digest_lookup(dg, chain_hashes(_prompt(1), PS))
    kvh = src.export_cached_chain(ch)
    assert kvh is not None and kvh.weights_step is None
    kvh.weights_step = 41                  # simulate exporter skew
    dst = InferenceEngine(CFG, mesh1, params, _config())
    h2 = dst.submit(_prompt(1), kv=kvh)
    dst.run_pending()
    solo = InferenceEngine(CFG, mesh1, params, _config())
    hs = solo.submit(_prompt(1))
    solo.run_pending()
    np.testing.assert_array_equal(h2.result(0), hs.result(0))
    assert len(dst._prefix_cache._by_hash) > 0  # its OWN insert only
    fam = dst.registry.get("serving_kv_adoptions")
    vals = {labels[0]: child.value for labels, child in fam.collect()}
    assert vals.get("seed_failed", 0) == 1
    assert h.done()


# ---------------------------------------------------------------------------
# cross-host compile-cache priming (ISSUE-14 satellite)
# ---------------------------------------------------------------------------

def test_autoscaled_fresh_replica_inherits_compile_cache(
        tmp_path, params, mesh1):
    """A tier config carrying compile_cache_dir reaches autoscale-
    built FRESH replicas (the scale-onto-new-host priming path), and
    the warm/cold verdict surfaces per replica."""
    from deeplearning4j_tpu.serving import AutoscalePolicy, TieredRouter
    from deeplearning4j_tpu.serving.disagg import PREFILL
    from deeplearning4j_tpu.serving.fleet import _warmup_cache_warm
    cache_dir = str(tmp_path / "aot")
    ec = _config(compile_cache_dir=cache_dir)
    router = TieredRouter(cfg=CFG, mesh=mesh1, params=params,
                          prefill_replicas=1, decode_replicas=1,
                          prefill_engine_config=ec,
                          decode_engine_config=ec,
                          prefill_autoscale=AutoscalePolicy(
                              min_replicas=1, max_replicas=2))
    try:
        assert router._scale_up(PREFILL, router._clock())
        fresh = router._tier_ctls(PREFILL)[-1]
        eng = fresh.replica.engine
        assert eng.config.compile_cache_dir == cache_dir
        from deeplearning4j_tpu.serving.compile_cache import \
            CompileCache
        if CompileCache.available():
            assert eng._aot is not None
        # warm-vs-cold classification from warmup reports
        assert _warmup_cache_warm(None) is None
        assert _warmup_cache_warm({"jit": 0, "aot_cache": 5}) is True
        assert _warmup_cache_warm({"jit": 3, "aot_cache": 0}) is False
        rows = router.debugz()["replicas"]
        assert all("cache_warm" in r for r in rows)
    finally:
        router.close()
