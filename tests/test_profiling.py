"""Continuous profiling & cost attribution (ISSUE-15).

The accounting layer's contracts, each proven deterministically on
CPU:

- **Exactness.** XLA's cost analysis of an analytic MLP matches the
  closed-form FLOP count, and `profiling.cost_from_compiled` agrees
  with `util/flops.cost_analysis` (one compiler, one number). The
  engine's per-program cost table holds exactly the analysis of the
  programs it resolved; per-tenant fleet cost totals are exact — the
  sum of per-request bills (terminal trace events) equals the
  federated per-tenant counters across a 2-replica, 3-tenant run.
- **Zero-cost paths.** A prefix-cache hit bills only the recomputed
  suffix tokens; a migrated cache chain adopted at seating bills only
  the private tail — cached compute is free for the tenant exactly as
  it is free for the engine (round-19 serving_prefill_tokens_total
  semantics).
- **Cardinality.** A hostile stream of distinct tenant ids folds into
  "other" past the top-N bound — the scrape stays inside
  `federation.check_cardinality`'s budget no matter the traffic.
- **Cache-warm cost tables.** A compile-cache-warm restart (zero jit
  compiles, every program an AOT load) still reports a COMPLETE cost
  table: the analysis is persisted beside the cached executable, and
  pre-meta (round 17-19) entries degrade to a lazy recompute from the
  loaded executable — never a cache miss.
- **Attribution + capture.** Tick-attributed device seconds sum to
  the engine's busy total; rooflines classify against injected peaks;
  `/profilez` is single-flight and 503s when unsupported.
"""
import json
import time
import urllib.request
import urllib.error

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.observability import MetricsServer
from deeplearning4j_tpu.observability.export import (json_snapshot,
                                                     prometheus_text)
from deeplearning4j_tpu.observability.federation import (
    check_cardinality)
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability.profiling import (
    EngineProfiler, NULL_PROFILER, ProfileCapture, TenantMeter,
    cost_from_compiled, roofline)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import (EngineConfig, InferenceEngine,
                                        Router)
from deeplearning4j_tpu.util import flops as util_flops

CFG = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                        n_layers=2, max_len=64)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(MeshSpec(data=1, model=1))


def _prompt(t0=8, seed=0):
    return (np.arange(t0, dtype=np.int32) * (seed + 3)) % CFG.vocab_size


# ---------------------------------------------------------------------------
# exactness: closed-form MLP vs the compiler's cost model
# ---------------------------------------------------------------------------

def test_cost_analysis_matches_closed_form_mlp():
    """An analytic two-layer MLP whose FLOPs are known in closed form
    (2*m*k*n per dense matmul): XLA's cost model, read through BOTH
    `util/flops.cost_analysis` (the training path) and
    `profiling.cost_from_compiled` (the serving path), reports exactly
    that number."""
    m, k, n, p = 8, 32, 16, 4
    w1 = np.zeros((k, n), np.float32)
    w2 = np.zeros((n, p), np.float32)
    x = np.zeros((m, k), np.float32)

    fn = jax.jit(lambda x, w1, w2: (x @ w1) @ w2)
    closed_form = 2 * m * k * n + 2 * m * n * p

    via_util = util_flops.cost_analysis(fn, x, w1, w2)
    assert via_util.get("flops") == closed_form

    exe = fn.lower(x, w1, w2).compile()
    via_profiling = cost_from_compiled(exe)
    assert via_profiling["flops"] == closed_form
    assert via_profiling["bytes"] > 0


def test_engine_cost_table_matches_util_flops(params, mesh1):
    """The engine's per-program table holds exactly what
    util/flops-style lower+compile cost analysis reports for the SAME
    geometry — the serving accounting and the training MFU path can
    never disagree about one program's cost."""
    eng = InferenceEngine(CFG, mesh1, params,
                          EngineConfig(decode_chunk=2,
                                       max_new_tokens=6, num_slots=2))
    h = eng.submit(_prompt(), tenant="t0")
    eng.run_pending()
    assert h.done()
    programs = eng.profiler.program_report()
    assert "decode" in programs
    # independently lower+compile the same decode geometry and compare
    from dataclasses import astuple
    from deeplearning4j_tpu.serving.engine import _compiled_decode_chunk
    fargs = (astuple(CFG), mesh1, eng._chunk, eng._num_slots,
             float(eng.config.temperature), int(eng.config.top_k),
             float(eng.config.top_p))
    fn = _compiled_decode_chunk(*fargs)
    eng._ensure_state()
    active = np.zeros((eng._num_slots,), bool)
    rem = np.zeros((eng._num_slots,), np.int32)
    ref = util_flops.cost_analysis(
        fn, eng._params, *eng._slot_state, active, rem,
        eng._root_key())
    assert programs["decode"]["flops_per_invocation"] == \
        ref.get("flops")
    assert programs["decode"]["tokens_per_invocation"] == \
        eng._chunk * eng._num_slots


def test_device_seconds_attribution_sums_to_busy_total(params, mesh1):
    """Tick attribution conserves time: the per-program device-second
    counters sum to the engine's cumulative dispatched-work interval
    (each tick's busy interval is split, never invented)."""
    eng = InferenceEngine(CFG, mesh1, params,
                          EngineConfig(decode_chunk=2,
                                       max_new_tokens=8, num_slots=2))
    hs = [eng.submit(_prompt(6 + i, i)) for i in range(4)]
    eng.run_pending()
    assert all(h.done() for h in hs)
    programs = eng.profiler.program_report()
    attributed = sum(p["device_seconds"] for p in programs.values())
    assert attributed == pytest.approx(eng._busy_total_s, rel=1e-6)
    assert attributed > 0
    # every dispatched program gained invocations and flops totals
    assert programs["decode"]["invocations"] > 0
    assert programs["decode"]["flops_total"] > 0


# ---------------------------------------------------------------------------
# per-tenant metering: exact fleet totals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_replicas", [2, 1])
def test_fleet_tenant_costs_sum_exactly(params, mesh1, num_replicas):
    """The acceptance bar: across a 2-replica, 3-tenant run the
    federated per-tenant counters equal the sum of per-request bills
    (terminal trace events carry each request's accumulated cost),
    and the fleet total equals the sum over tenants. One replica: one
    engine's serving_request_cost_flops counters are the whole bill.
    Every request is billed exactly once."""
    router = Router(cfg=CFG, mesh=mesh1, params=params,
                    num_replicas=num_replicas,
                    engine_config=EngineConfig(
                        decode_chunk=2, max_new_tokens=4,
                        max_batch_size=2, backoff_base_s=0.0))
    tenants = ["acme", "beta", "gamma"]
    try:
        hs = [router.submit(_prompt(6 + i % 3, i),
                            tenant=tenants[i % 3])
              for i in range(9)]
        router.run_pending()
        assert all(h.done() for h in hs)
        rep = router.cost_report()
        # per-request bills, harvested from the replica engines'
        # terminal trace events
        bills: dict = {}
        n_bills, counters = 0, 0.0
        for ctl in router._ctls:
            for ev in ctl.replica.engine.recorder.recent(10_000):
                if ev.kind == "finished":
                    t = ev.data.get("tenant", "default")
                    bills[t] = (bills.get(t, 0.0)
                                + ev.data.get("cost_flops", 0.0))
                    n_bills += 1
            counters += sum(c.value for _, c in ctl.replica.engine
                            .registry.get("serving_request_cost_flops")
                            .collect())
        assert n_bills == len(hs)
        assert counters == pytest.approx(sum(bills.values()), rel=1e-12)
        assert set(rep["tenants"]) == set(tenants)
        for t in tenants:
            assert rep["tenants"][t]["flops"] == pytest.approx(
                bills[t], rel=1e-12), t
            assert rep["tenants"][t]["flops"] > 0
        assert rep["total_flops"] == pytest.approx(
            sum(v["flops"] for v in rep["tenants"].values()),
            rel=1e-12)
        assert rep["total_flops"] == pytest.approx(
            sum(bills.values()), rel=1e-12)
    finally:
        router.close()


def test_prefix_hit_bills_only_suffix_tokens(params, mesh1):
    """Zero-cost path #1: a prefix-cache hit. The second tenant's
    prompt shares the first's page-aligned prefix, so it bills ONLY
    the recomputed suffix tokens — the cached prefix is free in the
    bill exactly as it is free on the device."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(decode_chunk=2, max_new_tokens=4, num_slots=1,
                     max_batch_size=1, paged=True, page_size=4))
    shared = np.arange(16, dtype=np.int32)
    p1 = np.concatenate([shared, np.asarray([5, 7], np.int32)])
    h1 = eng.submit(p1, tenant="first")
    eng.run_pending()
    assert h1.done()
    p2 = np.concatenate([shared, np.asarray([6, 9], np.int32)])
    h2 = eng.submit(p2, tenant="second")
    eng.run_pending()
    assert h2.done()
    rep = eng.profiler.meter.report()["tenants"]
    assert rep["first"]["prefill_tokens"] == p1.shape[0]
    # the hit covers the page-aligned shared prefix (16 tokens):
    # tenant two pays for the 2-token tail only
    assert rep["second"]["prefill_tokens"] == 2
    assert rep["second"]["flops"] < rep["first"]["flops"]
    # decode tokens bill identically (max_new=4: one token from the
    # prefill sample + 3 decode-chunk tokens)
    assert rep["second"]["decode_tokens"] == \
        rep["first"]["decode_tokens"] == 3


def test_migrated_chain_bills_only_private_tail(params, mesh1):
    """Zero-cost path #2: a migrated prefix chain. Engine B adopts
    engine A's exported cache chain at seating, so the request admits
    as a prefix hit and its tenant bills only the private tail — KV
    that arrived as bytes is never billed as FLOPs."""
    ec = EngineConfig(decode_chunk=2, max_new_tokens=4, num_slots=1,
                      max_batch_size=1, paged=True, page_size=4)
    shared = np.arange(16, dtype=np.int32)
    prompt = np.concatenate([shared, np.asarray([6, 9], np.int32)])
    a = InferenceEngine(CFG, mesh1, params, ec)
    ha = a.submit(np.concatenate(
        [shared, np.asarray([5, 7], np.int32)]), tenant="warm")
    a.run_pending()
    assert ha.done()
    dg = a.health()["prefix_digest"]
    assert dg["top"], "engine A must advertise its cached chain"
    chain_hash, chain_tokens = dg["top"][0]
    ho = a.export_cached_chain(int(chain_hash))
    assert ho is not None and ho.source == "cache"

    b = InferenceEngine(CFG, mesh1, params, ec)
    hb = b.submit(prompt, kv=ho, tenant="cold")
    b.run_pending()
    assert hb.done()
    rep = b.profiler.meter.report()["tenants"]
    assert rep["cold"]["prefill_tokens"] == \
        prompt.shape[0] - int(chain_tokens)
    # and the tokens are exact vs a no-migration run
    ref = InferenceEngine(CFG, mesh1, params, ec)
    href = ref.submit(prompt)
    ref.run_pending()
    np.testing.assert_array_equal(hb.result(0), href.result(0))


# ---------------------------------------------------------------------------
# cardinality: hostile tenant streams
# ---------------------------------------------------------------------------

def test_hostile_tenant_stream_stays_inside_the_budget(params, mesh1):
    """A stream of 40 distinct tenant ids against tenant_top_n=4:
    only the first 4 get their own label, the rest fold into "other"
    — the scrape has at most 5 tenant series per family and passes
    federation.check_cardinality."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(decode_chunk=2, max_new_tokens=2, num_slots=4,
                     max_queue=128, tenant_top_n=4))
    hs = [eng.submit(_prompt(6, i), tenant=f"hostile-{i:03d}")
          for i in range(40)]
    eng.run_pending()
    assert all(h.done() for h in hs)
    fam = eng.registry.get("serving_request_cost_flops")
    labels = {v[0] for v, _ in fam.collect()}
    assert len(labels) <= 5
    assert "other" in labels
    rep = eng.profiler.meter.report()
    assert rep["distinct_tenants_seen"] == 40
    assert rep["bills_folded_to_other"] == 36
    # the "other" row carries everyone past the bound
    assert rep["tenants"]["other"]["prefill_tokens"] == 36 * 6
    check_cardinality(json_snapshot(eng.registry), budget=64)


def test_federated_hostile_tenants_pass_cardinality(params, mesh1):
    """The fleet-level version of the bound: hostile tenants through
    a 2-replica router, the FEDERATED snapshot (tenant labels merged
    across replicas) still passes check_cardinality."""
    router = Router(cfg=CFG, mesh=mesh1, params=params,
                    num_replicas=2,
                    engine_config=EngineConfig(
                        decode_chunk=2, max_new_tokens=2,
                        max_batch_size=2, tenant_top_n=4,
                        max_queue=128))
    try:
        hs = [router.submit(_prompt(6, i), tenant=f"h{i}")
              for i in range(24)]
        router.run_pending()
        assert all(h.done() for h in hs)
        snap = router.federate()
        check_cardinality(snap, budget=64)
        # per-family bound: <= (top_n + other) per replica
        n = len(snap["serving_request_cost_flops"]["samples"])
        assert n <= 2 * 5
        rep = router.cost_report()
        assert "other" in rep["tenants"]
    finally:
        router.close()


# ---------------------------------------------------------------------------
# cache-warm restarts: cost tables without compiles
# ---------------------------------------------------------------------------

def test_cache_warm_restart_reports_complete_cost_table(
        tmp_path, params, mesh1):
    """The acceptance bar: a compile-cache-warm restart — zero jit
    compiles, every program an AOT load — still has a complete
    per-program cost table (the analysis is persisted beside each
    cached executable and loaded with it)."""
    from tests.test_compile_cache import _fresh_process

    def build():
        return InferenceEngine(
            CFG, mesh1, params,
            EngineConfig(decode_chunk=2, max_new_tokens=6,
                         num_slots=2, compile_cache_dir=str(tmp_path),
                         warmup_on_init=True))

    _fresh_process()
    cold = build()
    cold_table = cold.profiler.program_report()
    assert cold.last_warmup["jit"] > 0

    _fresh_process()
    warm = build()
    assert warm.last_warmup["jit"] == 0, \
        "a warm restart must not XLA-compile anything"
    assert warm.last_warmup["aot_cache"] == \
        warm.last_warmup["programs"] > 0
    warm_table = warm.profiler.program_report()
    assert set(warm_table) == set(cold_table)
    for label in cold_table:
        assert warm_table[label]["flops_per_invocation"] == \
            cold_table[label]["flops_per_invocation"], label
        assert warm_table[label]["flops_per_invocation"] > 0, label
    # and traffic bills off the loaded table immediately
    h = warm.submit(_prompt(), tenant="t")
    warm.run_pending()
    assert h.done() and h.cost_flops > 0


def test_old_format_cache_entry_degrades_to_lazy_recompute(tmp_path):
    """A round-17-format entry (3-tuple frame, no cost sidecar) still
    loads its executable — load_entry returns meta=None and the
    caller recomputes the analysis from the LOADED executable. An old
    entry degrades; it never becomes a cache miss."""
    import pickle
    import zlib
    from deeplearning4j_tpu.serving import CompileCache
    from deeplearning4j_tpu.serving.compile_cache import _MAGIC

    cache = CompileCache(tmp_path)
    fn = jax.jit(lambda a, b: a @ b)
    x = np.zeros((4, 8), np.float32)
    y = np.zeros((8, 2), np.float32)
    exe = fn.lower(x, y).compile()
    from jax.experimental import serialize_executable as se
    # hand-write the PRE-META frame (exactly what rounds 17-19 stored)
    payload = pickle.dumps(se.serialize(exe))
    blob = (_MAGIC + zlib.crc32(payload).to_bytes(4, "little")
            + payload)
    key = "decode-oldformat"
    cache.path(key).write_bytes(blob)

    loaded, meta = cache.load_entry(key, jax.devices()[:1])
    assert loaded is not None and meta is None
    assert cache.stats()["corrupt"] == 0
    # lazy recompute from the loaded executable: full analysis
    cost = cost_from_compiled(loaded)
    assert cost["flops"] == 2 * 4 * 8 * 2
    np.testing.assert_array_equal(np.asarray(loaded(x, y)), x @ y)


def test_meta_roundtrip_beside_executable(tmp_path):
    """The new frame: store(meta=) publishes the cost dict beside the
    executable, load_entry returns both, and the version field rides
    in-payload (a future meta schema drops the sidecar, never the
    executable)."""
    from deeplearning4j_tpu.serving import CompileCache

    cache = CompileCache(tmp_path)
    fn = jax.jit(lambda a: a * 2.0)
    x = np.zeros((4,), np.float32)
    exe = fn.lower(x).compile()
    cost = cost_from_compiled(exe)
    assert cache.store("p-meta", exe, meta={"cost": cost})
    loaded, meta = cache.load_entry("p-meta", jax.devices()[:1])
    assert loaded is not None
    assert meta["cost"] == cost
    assert meta["meta_version"] >= 1


# ---------------------------------------------------------------------------
# rooflines, MFU, units
# ---------------------------------------------------------------------------

def test_roofline_classification():
    """Arithmetic intensity vs ridge point: left = memory-bound,
    right = compute-bound, unknown peaks = unknown."""
    # ridge = 1e12 / 1e9 = 1000 FLOPs/byte
    r = roofline(flops=1e6, bytes_=1e5, peak_flops=1e12,
                 peak_bytes_per_s=1e9)
    assert r["bound"] == "memory" and \
        r["intensity_flops_per_byte"] == 10.0
    r = roofline(flops=1e9, bytes_=1e5, peak_flops=1e12,
                 peak_bytes_per_s=1e9)
    assert r["bound"] == "compute"
    assert roofline(1e6, 1e5, None, None)["bound"] == "unknown"
    assert roofline(1e6, 0.0, 1e12, 1e9)["bound"] == "unknown"


def test_mfu_and_roofline_with_injected_peaks(params, mesh1):
    """With injected chip peaks (the CPU container has none) the live
    MFU gauge reads positive after traffic and every program gets a
    definite roofline verdict; the chosen ridge makes the small
    decode geometry memory-bound and the whole report coherent."""
    registry = MetricsRegistry()
    profiler = EngineProfiler(registry, peak_flops=1e15,
                              peak_bytes_per_s=1e9)
    eng = InferenceEngine(CFG, mesh1, params,
                          EngineConfig(decode_chunk=2,
                                       max_new_tokens=6, num_slots=2),
                          registry=registry, profiler=profiler)
    h = eng.submit(_prompt(), tenant="t")
    eng.run_pending()
    assert h.done()
    assert profiler.mfu() > 0
    rep = eng.profile_report()
    assert rep["ridge_flops_per_byte"] == 1e15 / 1e9
    for label, row in rep["programs"].items():
        # tiny-model serving programs sit far left of a 1e6 ridge
        assert row["bound"] == "memory", label
    gauge = registry.get("serving_mfu")
    assert gauge.value > 0
    # debugz carries the same report
    assert "profiling" in eng.debugz()


def test_chip_peak_tables():
    """The serving roofline's denominators: known TPU kinds resolve
    both peaks; unknown device kinds (this CPU) resolve None."""
    class _Dev:
        device_kind = "TPU v5 lite"

    assert util_flops.chip_peak_flops(_Dev()) == 197e12
    assert util_flops.chip_peak_bytes_per_s(_Dev()) == 819e9
    class _Cpu:
        device_kind = "cpu"

    assert util_flops.chip_peak_bytes_per_s(_Cpu()) is None


def test_null_profiler_disables_by_injection(params, mesh1):
    """profiler=NULL_PROFILER: no serving_mfu / serving_program_* /
    tenant series in the scrape, zero per-request bills — and the same
    tokens as a profiled engine serves."""
    eng = InferenceEngine(CFG, mesh1, params,
                          EngineConfig(decode_chunk=2,
                                       max_new_tokens=4),
                          profiler=NULL_PROFILER)
    h = eng.submit(_prompt(), tenant="t")
    eng.run_pending()
    assert h.done()
    text = prometheus_text(eng.registry)
    assert "serving_mfu" not in text
    assert "serving_program_flops" not in text
    assert "serving_program_device_seconds" not in text
    assert "serving_request_cost" not in text
    assert "serving_tenant_tokens" not in text
    assert h.cost_flops == 0.0
    assert "profiling" not in eng.debugz()
    on = InferenceEngine(CFG, mesh1, params,
                         EngineConfig(decode_chunk=2, max_new_tokens=4))
    h_on = on.submit(_prompt(), tenant="t")
    on.run_pending()
    assert h_on.cost_flops > 0.0
    np.testing.assert_array_equal(h.result(0), h_on.result(0))


def test_tenant_meter_unit():
    """TenantMeter in isolation: top-N assignment, fold accounting,
    ranking by FLOPs."""
    m = TenantMeter(MetricsRegistry(), top_n=2)
    m.bill("a", 100.0, 10.0, 5, "prefill")
    m.bill("b", 300.0, 30.0, 5, "decode")
    m.bill("c", 50.0, 5.0, 1, "decode")       # folds: top_n reached
    m.bill("d", 60.0, 6.0, 1, "decode")       # folds
    m.bill(None, 10.0, 1.0, 1, "decode")      # "default" folds too
    rep = m.report()
    assert list(rep["tenants"]) == ["b", "other", "a"]
    assert rep["tenants"]["other"]["flops"] == 120.0
    assert rep["bills_folded_to_other"] == 3


# ---------------------------------------------------------------------------
# on-demand capture: /profilez
# ---------------------------------------------------------------------------

def test_profilez_unsupported_and_unwired(params, mesh1):
    """No profile_dir configured -> the engine answers 503; an
    exporter without the callable wired -> 404."""
    eng = InferenceEngine(CFG, mesh1, params,
                          EngineConfig(max_new_tokens=2))
    code, body = eng.profilez(1.0)
    assert code == 503 and "unsupported" in body["error"]
    srv = MetricsServer(eng.registry, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/profilez?seconds=1",
                                   timeout=5)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_profilez_capture_single_flight(tmp_path, params, mesh1):
    """The wired endpoint: a capture starts (200), a second request
    while it runs is rejected 503 BUSY (single-flight), bad seconds
    are 400, and the bounded trace lands in the configured
    directory."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        EngineConfig(max_new_tokens=2,
                     profile_dir=str(tmp_path / "prof")))
    srv = MetricsServer(eng.registry, port=0, profilez=eng.profilez)
    try:
        with urllib.request.urlopen(
                srv.url + "/profilez?seconds=0.3", timeout=10) as r:
            assert r.getcode() == 200
            body = json.loads(r.read().decode())
            assert body["started"] and body["seconds"] == 0.3
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/profilez?seconds=0.3",
                                   timeout=10)
        assert ei.value.code == 503
        assert "in progress" in json.loads(
            ei.value.read().decode())["error"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/profilez?seconds=nope",
                                   timeout=10)
        assert ei.value.code == 400
        # run a little traffic DURING the capture so it has content
        h = eng.submit(_prompt())
        eng.run_pending()
        assert h.done()
        deadline = time.time() + 10
        while eng._capture.active and time.time() < deadline:
            time.sleep(0.05)
        assert not eng._capture.active, "capture must stop itself"
        assert any((tmp_path / "prof").rglob("*")), \
            "the capture must write into the configured directory"
        # and the engine accepts a NEW capture after the first ends
        code, _ = eng.profilez(0.05)
        assert code == 200
        deadline = time.time() + 10
        while eng._capture.active and time.time() < deadline:
            time.sleep(0.05)
    finally:
        srv.stop()


def test_profile_capture_unit():
    """ProfileCapture argument semantics without touching the real
    profiler: no directory -> 503, bad seconds -> 400, max_seconds
    clamps."""
    cap = ProfileCapture(None)
    assert cap.capture(1.0)[0] == 503
    cap = ProfileCapture("/tmp/never-used", max_seconds=2.0)
    assert cap.capture("x")[0] == 400
    assert cap.capture(-1)[0] == 400


def test_fleet_profilez_fans_to_replicas(params, mesh1, tmp_path):
    """Router.profilez fans the capture per replica: with no replica
    configured for capture the fleet answer is 503 with per-replica
    errors; cost/profile reports still work."""
    router = Router(cfg=CFG, mesh=mesh1, params=params,
                    num_replicas=2,
                    engine_config=EngineConfig(
                        decode_chunk=2, max_new_tokens=2,
                        max_batch_size=2))
    try:
        code, body = router.profilez(0.5)
        assert code == 503 and body["started"] == 0
        assert len(body["replicas"]) == 2
        pr = router.profile_report()
        assert set(pr) == {"serving/0", "serving/1"}
    finally:
        router.close()
