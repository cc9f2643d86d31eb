"""Disaggregated prefill/decode tiers: deterministic CPU suite.

Every ISSUE-11 acceptance behavior:

- the cross-tier KV handoff is TOKEN-EXACT: a request prefilled on
  tier P and decoded on tier D produces bit-identical tokens to a
  single-replica run — greedy and sampled, float and int8 KV, fresh
  and prefix-hit, one-shot and chunked prefill tiers;
- `PageAllocator`-backed export/adopt round-trips the committed rows
  (and quantized per-row scales) bit-exactly, adopting into a
  near-full pool BLOCKS-or-sheds instead of corrupting residents, and
  every adoption error path decrefs what it claimed (the
  `_free_slot`-style refcount audit) with the typed
  ``shed{reason="handoff"}``;
- a killed decode replica's requests generalize round-14 failover by
  RE-PREFILLING on the prefill tier (hitting its prefix cache), then
  handing off again — zero lost requests;
- a failed KV export degrades to re-prefill on the decode tier
  (``outcome="failed"``), never a lost request;
- the occupancy-driven `Autoscaler` scales each tier independently
  between min/max replicas through drain + supervised-restart
  machinery — an up/down cycle loses zero requests, and the prefill
  tier scales to ZERO under decode-only idle and force-scales back up
  on the next admission.
"""
import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.failure import (FleetFaultInjector,
                                                 ServingFaultInjector)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.quant.kv import handoff_bytes
from deeplearning4j_tpu.serving import (AutoscalePolicy, Autoscaler,
                                        EngineConfig, FleetConfig,
                                        HandoffError, InferenceEngine,
                                        RequestStatus, TieredRouter)
from deeplearning4j_tpu.serving.engine import _compiled_kv_adopt

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


def _ec(**kw):
    base = dict(decode_chunk=2, max_new_tokens=12, backoff_base_s=0.0,
                max_batch_size=2, paged=True)
    base.update(kw)
    return EngineConfig(**base)


def _tiered(params, mesh, *, prefill=1, decode=1, pc=None, dc=None,
            **kw):
    return TieredRouter(cfg=CFG, mesh=mesh, params=params,
                        prefill_replicas=prefill,
                        decode_replicas=decode,
                        prefill_engine_config=pc or _ec(),
                        decode_engine_config=dc or _ec(),
                        config=kw.pop("config", FleetConfig(
                            restart_backoff_base_s=0.01)), **kw)


def _reference(params, mesh, prompts, max_new=12, ec=None):
    """Uninterrupted single-engine run — the token-exactness oracle."""
    eng = InferenceEngine(CFG, mesh, params, ec or _ec())
    out = []
    for p in prompts:
        h = eng.submit(p, max_new_tokens=max_new)
        eng.run_pending()
        out.append(h.result(0))
    return out


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _drive(router, clock=None, step=0.05, limit=3000):
    """Bounded run-to-completion, advancing an injected clock if any."""
    for _ in range(limit):
        if not router.pending():
            return
        router.tick()
        if clock is not None:
            clock.advance(step)
    raise AssertionError("tiered router failed to drain within bound")


# ---------------------------------------------------------------------------
# token-exact handoff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_quantize,temperature", [
    (None, 0.0),             # float KV, greedy
    ("int8", 0.0),           # quantized KV: rows + scales travel
    (None, 0.8),             # sampled: position-keyed schedule
], ids=["float-greedy", "int8-greedy", "float-sampled"])
def test_handoff_token_exact(params, mesh1, kv_quantize, temperature):
    """Prefill on tier P + decode on tier D == one replica, bit for
    bit — the acceptance bar. Every request takes the full two-hop
    path (handoffs == completions, outcome ok)."""
    ec = _ec(kv_quantize=kv_quantize, temperature=temperature)
    prompts = [_prompt(6 + i, i) for i in range(5)]
    want = _reference(params, mesh1, prompts, ec=ec)
    r = _tiered(params, mesh1, pc=ec, dc=ec)
    try:
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
            assert h.status == RequestStatus.COMPLETED
        assert r.stats["completed"] == 5
        assert r.stats["handoffs_ok"] == 5
        assert r.stats["handoffs_failed"] == 0
    finally:
        r.close()


def test_handoff_prefix_hit_token_exact(params, mesh1):
    """A second tenant sharing the first's prompt hits the PREFILL
    tier's radix cache (prefill resumes from the hit boundary), and
    the handed-off continuation is still bit-exact."""
    shared = _prompt(32, 3)
    prompts = [shared, shared.copy()]
    want = _reference(params, mesh1, prompts)
    r = _tiered(params, mesh1)
    try:
        hs = []
        for p in prompts:       # serialize so the 2nd sees the cache
            hs.append(r.submit(p, max_new_tokens=12))
            _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
        pre_eng = r._ctls[0].replica.engine
        assert int(pre_eng._m_prefix_hits.value) >= 1
    finally:
        r.close()


def test_chunked_prefill_tier_token_exact(params, mesh1):
    """The prefill tier runs the round-15 chunked scheduler; the
    decode tier never prefills — still bit-exact vs a single chunked
    engine."""
    pc = _ec(prefill_chunk=8)
    prompts = [_prompt(20, i) for i in range(3)]
    want = _reference(params, mesh1, prompts, ec=pc)
    r = _tiered(params, mesh1, pc=pc)
    try:
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
        assert r.stats["handoffs_ok"] == 3
    finally:
        r.close()


def test_trace_carries_handoff_event(params, mesh1):
    r = _tiered(params, mesh1)
    try:
        h = r.submit(_prompt(), max_new_tokens=8)
        _drive(r)
        kinds = h.trace.kinds()
        assert "handoff" in kinds
        ev = next(e for e in h.trace.events if e.kind == "handoff")
        assert ev.data["outcome"] == "ok"
        assert ev.data["tokens"] >= 8      # the committed prefix rows
        # two dispatches bracket the handoff: prefill hop, decode hop
        assert kinds.count("dispatched") == 2
        assert kinds.index("dispatched") < kinds.index("handoff")
    finally:
        r.close()


# ---------------------------------------------------------------------------
# export / adopt mechanics (engine level)
# ---------------------------------------------------------------------------

def _held_export(params, mesh, ec, prompt, release=True):
    """Prefill `prompt` on a fresh engine with hold_kv and export."""
    eng = InferenceEngine(CFG, mesh, params, ec)
    h = eng.submit(prompt, max_new_tokens=1, hold_kv=True)
    eng.run_pending()
    assert h.done()
    kv = eng.export_slot_kv(h, release=release)
    return eng, h, kv


@pytest.mark.parametrize("kv_quantize,spec", [
    (None, False), ("int8", False), (None, True)],
    ids=["float", "int8", "spec"])
def test_export_adopt_roundtrip_bit_exact(params, mesh1, kv_quantize,
                                          spec):
    """The committed rows (values AND per-row scales) survive
    host-gather -> device-put -> decode bit-exactly: re-exporting the
    adopting engine's pool returns the identical prefix — and the
    decode continuation equals the single-engine run. The adoption is
    ONE batched all-layer program (a per-layer loop would build
    n_layers of them), on a speculative engine too."""
    ec = _ec(kv_quantize=kv_quantize,
             **(dict(spec_decode=True, spec_k=3, draft="layers:1")
                if spec else {}))
    prompt = _prompt(10, 2)
    want = _reference(params, mesh1, [prompt], ec=ec)[0]
    src, h, kv = _held_export(params, mesh1, ec, prompt)
    assert kv.pos == prompt.shape[0]
    assert kv.tok == int(h.generated[-1])
    assert (kv.k_scale is not None) == (kv_quantize == "int8")
    _compiled_kv_adopt.cache_clear()       # so this adoption builds it
    dst = InferenceEngine(CFG, mesh1, params, ec)
    prompt_d = np.concatenate([prompt, h.generated]).astype(np.int32)
    hd = dst.submit(prompt_d, max_new_tokens=11, kv=kv, hold_kv=True)
    dst.run_pending()
    np.testing.assert_array_equal(hd.result(0), want)
    # the adopted prefix is still bit-identical in dst's pool
    back = dst.export_slot_kv(hd)
    np.testing.assert_array_equal(back.k[:, :kv.pos], kv.k)
    np.testing.assert_array_equal(back.v[:, :kv.pos], kv.v)
    if kv_quantize:
        np.testing.assert_array_equal(back.k_scale[:, :kv.pos],
                                      kv.k_scale)
        np.testing.assert_array_equal(back.v_scale[:, :kv.pos],
                                      kv.v_scale)
    assert int(dst._m_adoptions.labels("ok").value) == 1
    assert sum(int(child.value) for labels, child in
               dst.registry.get("serving_compiles").collect()
               if labels[0] == "kv_adopt") == 1


def test_export_requires_hold_and_releases(params, mesh1):
    """Without hold_kv the slot reaps at completion (export raises);
    a held slot frees exactly once on export and occupancy returns to
    zero."""
    eng = InferenceEngine(CFG, mesh1, params, _ec())
    h = eng.submit(_prompt(), max_new_tokens=1)
    eng.run_pending()
    with pytest.raises(HandoffError, match="not resident"):
        eng.export_slot_kv(h)
    h2 = eng.submit(_prompt(9, 1), max_new_tokens=1, hold_kv=True)
    eng.run_pending()
    assert eng.committed_kv_pages(h2) >= 1
    assert not eng.drained()             # the hold keeps it seated
    eng.export_slot_kv(h2)               # release=True default
    assert eng.committed_kv_pages(h2) == 0
    assert eng.drained()
    assert eng.release_held(h2) is False  # idempotent


def test_handoff_bytes_match_analytic(params, mesh1):
    """Measured handoff payload == quant/kv.handoff_bytes — the
    accounting behind serving_handoff_bytes_total."""
    for kvq in (None, "int8"):
        _, _, kv = _held_export(params, mesh1, _ec(kv_quantize=kvq),
                                _prompt(12, 1))
        assert kv.nbytes == handoff_bytes(CFG, kv.pos, kv_mode=kvq,
                                          tp=1)


def test_adopt_near_full_pool_blocks_not_corrupts(params, mesh1):
    """Adoption into a pool too full to cover the chain BLOCKS at the
    queue head until a resident frees pages — the resident's tokens
    stay bit-exact (no write ever landed on its pages) and the
    adopted request then completes bit-exactly too."""
    ec = _ec(page_size=4, kv_pages=12, max_new_tokens=24,
             prefix_cache=False)
    res_prompt, ado_prompt = _prompt(8, 1), _prompt(8, 5)
    want_res = _reference(params, mesh1, [res_prompt], max_new=24,
                          ec=ec)[0]
    want_ado = _reference(params, mesh1, [ado_prompt], max_new=12,
                          ec=ec)[0]
    _, h_src, kv = _held_export(params, mesh1, ec, ado_prompt)
    dst = InferenceEngine(CFG, mesh1, params, ec)
    res = dst.submit(res_prompt, max_new_tokens=24)   # 8 pages
    dst.tick()                                        # resident seated
    prompt_d = np.concatenate([ado_prompt,
                               h_src.generated]).astype(np.int32)
    ado = dst.submit(prompt_d, max_new_tokens=11, kv=kv)  # needs 5
    dst.tick()
    assert not ado.done() and ado.status == RequestStatus.QUEUED
    assert int(dst._m_adoptions.labels("blocked").value) >= 1
    dst.run_pending()
    np.testing.assert_array_equal(res.result(0), want_res)
    np.testing.assert_array_equal(ado.result(0), want_ado)


def test_adopt_that_never_fits_is_rejected(params, mesh1):
    """A handoff no pool state could ever seat is rejected at
    submit() — typed ValueError, nothing allocated — the shed half of
    blocks-or-sheds (the block half: the near-full test above; the
    seat-time shed paths: the injector + misalignment tests below)."""
    ec = _ec(page_size=4, kv_pages=4, prefix_cache=False)
    _, h_src, kv = _held_export(params, mesh1, _ec(), _prompt(16, 2))
    dst = InferenceEngine(CFG, mesh1, params, ec)
    prompt_d = np.concatenate([_prompt(16, 2),
                               h_src.generated]).astype(np.int32)
    with pytest.raises(ValueError, match="could never be admitted"):
        dst.submit(prompt_d, max_new_tokens=1, kv=kv)
    assert dst._allocator.pages_used == 0


def test_adopt_fault_sheds_typed_and_decrefs(params, mesh1):
    """ServingFaultInjector.adopt_fail_requests: the decode-side
    adoption fails -> typed ``shed{reason="handoff"}``, HandoffError
    on the handle, reason="handoff" counter child, and EVERY page the
    adoption claimed decref'd (the refcount audit)."""
    _, h_src, kv = _held_export(params, mesh1, _ec(), _prompt(10, 4))
    inj = ServingFaultInjector(adopt_fail_requests=[1])
    dst = InferenceEngine(CFG, mesh1, params, _ec(),
                          fault_injector=inj)
    used0 = dst._allocator.pages_used if dst._paged else 0
    prompt_d = np.concatenate([_prompt(10, 4),
                               h_src.generated]).astype(np.int32)
    ado = dst.submit(prompt_d, max_new_tokens=11, kv=kv)
    dst.run_pending()
    assert inj.adoptions_failed == 1
    assert ado.status == RequestStatus.SHED
    assert isinstance(ado.error, HandoffError)
    ev = [e for e in ado.trace.events if e.kind == "shed"]
    assert ev and ev[0].data["reason"] == "handoff"
    assert dst._allocator.pages_used == used0
    assert int(dst._m_shed.labels("handoff").value) == 1
    assert int(dst._m_adoptions.labels("shed").value) == 1


def test_misaligned_handoff_sheds_typed(params, mesh1):
    """A handoff whose pending token disagrees with the committed
    prefix would decode silently wrong text — it must shed typed, not
    seat."""
    _, h_src, kv = _held_export(params, mesh1, _ec(), _prompt(10, 4))
    dst = InferenceEngine(CFG, mesh1, params, _ec())
    bad = np.concatenate([_prompt(10, 4),
                          [(int(h_src.generated[-1]) + 1)
                           % CFG.vocab_size]]).astype(np.int32)
    ado = dst.submit(bad, max_new_tokens=11, kv=kv)
    dst.run_pending()
    assert ado.status == RequestStatus.SHED
    assert isinstance(ado.error, HandoffError)
    assert dst._allocator.pages_used == 0


def test_unpaged_target_falls_back_to_prefill(params, mesh1):
    """An engine that cannot adopt (contiguous pool) drops the
    handoff with a warning and re-prefills — correct tokens, no shed."""
    ec = _ec(paged=False)
    _, h_src, kv = _held_export(params, mesh1, _ec(), _prompt(10, 1))
    want = _reference(params, mesh1, [_prompt(10, 1)], ec=_ec())[0]
    dst = InferenceEngine(CFG, mesh1, params, ec)
    prompt_d = np.concatenate([_prompt(10, 1),
                               h_src.generated]).astype(np.int32)
    ado = dst.submit(prompt_d, max_new_tokens=11, kv=kv)
    dst.run_pending()
    np.testing.assert_array_equal(ado.result(0), want)


# ---------------------------------------------------------------------------
# failover across the tier boundary
# ---------------------------------------------------------------------------

def test_kill_decode_replica_reprefills_on_prefill_tier(params, mesh1):
    """Round-14 failover generalized: a killed decode replica's
    requests reset to the PREFILL phase, re-prefill their committed
    prefix on the prefill tier, hand off again, and finish
    bit-identically to an uninterrupted run — zero lost requests."""
    prompts = [_prompt(8, i) for i in range(5)]
    want = _reference(params, mesh1, prompts)
    inj = FleetFaultInjector(kill_at={6: 1})   # replica 1 = decode
    r = _tiered(params, mesh1, decode=2, fault_injector=inj)
    try:
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
        assert inj.kills_injected == 1
        assert r.stats["failovers"] >= 1
        # the failovers re-prefilled AND re-handed-off
        assert r.stats["handoffs_ok"] > len(prompts)
        assert r.stats["shed_outage"] == 0
    finally:
        r.close()


def test_kill_prefill_replica_recovers(params, mesh1):
    """A killed prefill replica's in-flight prefills requeue (still
    phase prefill) and the supervised restart brings the tier back —
    zero lost, token-exact."""
    prompts = [_prompt(8, i) for i in range(4)]
    want = _reference(params, mesh1, prompts)
    inj = FleetFaultInjector(kill_at={1: 0})   # replica 0 = prefill
    r = _tiered(params, mesh1, decode=1, fault_injector=inj)
    try:
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
        assert inj.kills_injected == 1
        # the tier's ONLY prefill replica died with admissions still
        # queued: nothing can finish without the supervised restart
        assert r.stats["restarts"] >= 1
    finally:
        r.close()


def test_handoff_export_failure_falls_back(params, mesh1):
    """FleetFaultInjector.handoff_fail_at: the first export dies ->
    outcome "failed", the decode dispatch re-prefills the committed
    prefix, and the result is still bit-exact."""
    prompts = [_prompt(8, i) for i in range(3)]
    want = _reference(params, mesh1, prompts)
    inj = FleetFaultInjector(handoff_fail_at=[0])
    r = _tiered(params, mesh1, fault_injector=inj)
    try:
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
        assert inj.handoffs_failed == 1
        assert r.stats["handoffs_failed"] == 1
        assert r.stats["handoffs_ok"] == 2
        # the prefill tier's held slot was released despite the
        # injected failure (no leaked seats)
        assert r._ctls[0].replica.engine.drained()
    finally:
        r.close()


# ---------------------------------------------------------------------------
# autoscaling
# ---------------------------------------------------------------------------

def test_autoscaler_policy_unit():
    """The pure decision core: window hysteresis, cooldown, min/max
    bounds, scale-to-zero idle gate, and the cold-start force-up."""
    p = AutoscalePolicy(min_replicas=0, max_replicas=3, window=2,
                        cooldown_s=1.0, scale_up_occupancy=0.8,
                        scale_down_occupancy=0.2)
    a = Autoscaler(p)
    # one high observation is not enough (window=2)...
    assert a.observe(0.0, 1, 0.9, None, 2, 2) == 0
    assert a.observe(0.1, 1, 0.9, None, 2, 2) == 1
    # ...cooldown gates the next action...
    assert a.observe(0.2, 2, 0.9, None, 2, 2) == 0
    assert a.observe(0.3, 2, 0.9, None, 2, 2) == 0
    assert a.observe(1.2, 2, 0.9, None, 2, 2) == 1
    # ...max bound
    assert a.observe(3.0, 3, 1.0, None, 5, 5) == 0
    # idle: down after window, but the LAST replica only retires when
    # in-flight work is gone
    a2 = Autoscaler(p)
    assert a2.observe(0.0, 2, 0.0, None, 0, 0) == 0
    assert a2.observe(0.1, 2, 0.0, None, 0, 0) == -1
    a3 = Autoscaler(p)
    assert a3.observe(0.0, 1, 0.0, None, 0, 3) == 0
    assert a3.observe(0.1, 1, 0.0, None, 0, 3) == 0   # still serving
    assert a3.observe(1.2, 1, 0.0, None, 0, 0) == 0
    assert a3.observe(1.3, 1, 0.0, None, 0, 0) == -1  # to zero
    # cold start: pending work, zero active -> +1 immediately
    a4 = Autoscaler(p)
    assert a4.observe(0.0, 0, 0.0, None, 1, 0) == 1
    # budget utilization is an OR'd up-signal
    a5 = Autoscaler(p)
    assert a5.observe(0.0, 1, 0.1, 0.99, 1, 1) == 0
    assert a5.observe(0.1, 1, 0.1, 0.99, 1, 1) == 1
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalePolicy(min_replicas=3, max_replicas=1)


def test_autoscale_up_down_cycle_zero_lost(params, mesh1):
    """A burst scales the decode tier up (occupancy-driven), idleness
    scales it back to min through drain — zero lost requests, the
    trajectory lands in autoscale_log/metrics, and stopped replicas
    revive on the next burst."""
    clock = _Clock()
    r = _tiered(params, mesh1, decode=1,
                dc=_ec(max_new_tokens=16),
                pc=_ec(max_new_tokens=16),
                decode_autoscale=AutoscalePolicy(
                    min_replicas=1, max_replicas=3, window=2,
                    cooldown_s=0.1),
                clock=clock)
    try:
        hs = [r.submit(_prompt(8, i), max_new_tokens=16)
              for i in range(8)]
        _drive(r, clock)
        assert all(h.status == RequestStatus.COMPLETED for h in hs)
        ups = [e for e in r.autoscale_log
               if e["tier"] == "decode" and e["direction"] == "up"]
        assert ups, "the burst never scaled the decode tier up"
        for _ in range(60):               # idle: scale back down
            r.tick()
            clock.advance(0.05)
        downs = [e for e in r.autoscale_log
                 if e["tier"] == "decode" and e["direction"] == "down"]
        assert downs, "idleness never scaled the decode tier down"
        assert len(r._active_ctls("decode")) == 1
        stopped = [c for c in r._ctls if c.state() == "stopped"]
        assert stopped
        # second burst revives a stopped replica, still zero lost
        hs2 = [r.submit(_prompt(8, i + 20), max_new_tokens=16)
               for i in range(8)]
        _drive(r, clock)
        assert all(h.status == RequestStatus.COMPLETED for h in hs2)
        assert r.stats["shed_outage"] == 0
        assert int(r._m_autoscale.labels("decode", "up").value) >= 2
    finally:
        r.close()


def test_prefill_tier_scales_to_zero_and_cold_starts(params, mesh1):
    """min_replicas=0 on the prefill tier: decode-only idle retires
    the last prefill replica; the next admission force-scales it back
    up (pending work, zero active) and completes token-exactly."""
    clock = _Clock()
    want = _reference(params, mesh1, [_prompt(8, 7)])[0]
    r = _tiered(params, mesh1,
                prefill_autoscale=AutoscalePolicy(
                    min_replicas=0, max_replicas=1, window=2,
                    cooldown_s=0.1),
                clock=clock)
    try:
        h0 = r.submit(_prompt(8, 1), max_new_tokens=12)
        _drive(r, clock)
        assert h0.done()
        for _ in range(40):
            r.tick()
            clock.advance(0.05)
        assert len(r._active_ctls("prefill")) == 0
        assert [c.state() for c in r._tier_ctls("prefill")] \
            == ["stopped"]
        h = r.submit(_prompt(8, 7), max_new_tokens=12)
        _drive(r, clock)
        np.testing.assert_array_equal(h.result(0), want)
        # the cold start revived the stopped replica (it may retire
        # again once the request's prefill is done — that's the
        # policy working, not a failure)
        ups = [e for e in r.autoscale_log
               if e["tier"] == "prefill" and e["direction"] == "up"]
        assert ups, "the pending admission never force-scaled up"
    finally:
        r.close()


# ---------------------------------------------------------------------------
# introspection + satellites
# ---------------------------------------------------------------------------

def test_debugz_tier_table_and_probe_piggyback(params, mesh1):
    """The per-tier debugz table (tier, states, occupancy, in-flight,
    last handoff) and the health-probe load piggyback: every probe
    carries slot_occupancy / tick_budget_utilization, so the router
    sees load without scraping /metrics."""
    r = _tiered(params, mesh1, pc=_ec(prefill_chunk=8))
    try:
        hs = [r.submit(_prompt(8, i), max_new_tokens=12)
              for i in range(3)]
        _drive(r)
        assert all(h.done() for h in hs)
        d = r.debugz()
        tiers = {row["tier"]: row for row in d["tiers"]}
        assert set(tiers) == {"prefill", "decode"}
        assert tiers["decode"]["replicas"] == 1
        assert tiers["prefill"]["occupancy"] is not None
        assert d["handoffs"]["ok"] == 3
        assert d["handoffs"]["last"]["outcome"] == "ok"
        assert tiers["prefill"]["last_handoff"] is not None
        # probe piggyback: the chunked prefill tier reports budget
        # utilization, every replica reports occupancy
        rows = {row["replica"]: row for row in d["replicas"]}
        assert all(row["slot_occupancy"] is not None
                   for row in rows.values())
        assert rows[0]["budget_utilization"] is not None
        assert rows[0]["tier"] == "prefill"
        h = r.health()
        assert set(h["tiers"]) == {"prefill", "decode"}
        # the engine health dict itself carries the piggyback fields
        eh = r._ctls[0].replica.engine.health()
        assert eh["slot_occupancy"] == 0.0
        assert eh["tick_budget_utilization"] is not None
    finally:
        r.close()


def test_flat_router_debugz_has_single_tier(params, mesh1):
    """The base Router grows the same table with one 'serving' tier
    (satellite: Router.debugz AND TieredRouter.debugz)."""
    from deeplearning4j_tpu.serving import Router
    r = Router(cfg=CFG, mesh=mesh1, params=params, num_replicas=2,
               engine_config=_ec(paged=False))
    try:
        h = r.submit(_prompt(), max_new_tokens=8)
        r.run_pending()
        assert h.done()
        d = r.debugz()
        assert [row["tier"] for row in d["tiers"]] == ["serving"]
        assert d["tiers"][0]["replicas"] == 2
        assert d["tiers"][0]["last_handoff"] is None
    finally:
        r.close()


def test_tier_config_parity_validated(params, mesh1):
    with pytest.raises(ValueError, match="temperature"):
        TieredRouter(cfg=CFG, mesh=mesh1, params=params,
                     prefill_engine_config=_ec(temperature=0.5),
                     decode_engine_config=_ec(temperature=0.0))


def test_committed_kv_pages_reporting(params, mesh1):
    """engine.committed_kv_pages — what fleet_worker.py now stamps on
    its progress lines — tracks the slot's page chain and zeroes on
    release."""
    eng = InferenceEngine(CFG, mesh1, params,
                          _ec(page_size=4, max_new_tokens=8))
    h = eng.submit(_prompt(10, 1), max_new_tokens=8, hold_kv=True)
    assert eng.committed_kv_pages(h) == 0        # not seated yet
    eng.run_pending()
    from deeplearning4j_tpu.serving.paging import pages_for
    assert eng.committed_kv_pages(h) == pages_for(10 + 8, 4)
    eng.release_held(h)
    assert eng.committed_kv_pages(h) == 0
    unpaged = InferenceEngine(CFG, mesh1, params, _ec(paged=False))
    h2 = unpaged.submit(_prompt(), max_new_tokens=4)
    unpaged.run_pending()
    assert unpaged.committed_kv_pages(h2) == 0
