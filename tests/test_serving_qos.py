"""Tenant QoS control plane (ISSUE-16).

The tentpole guarantees, each proven deterministically on the CPU
backend:

- weighted fair share: under sustained two-tenant contention the
  deficit scheduler converges the granted-prefill-token ratio to the
  configured weights, and a backlogged tenant behind a hostile flood
  reaches its first token within a bounded number of ticks
  (no-starvation) — where the QoS-off oldest-first scheduler provably
  starves it for the flood's whole prefill;
- priority preemption: a high-priority arrival with no free slot
  evicts the lowest-priority resident through the committed-prefix
  resume path (token-exact vs the uninterrupted reference), bounded
  by preemption_budget evictions per tick, and zero high-priority
  requests are lost under preemption + a replica kill;
- admission + overload control: per-tenant concurrency and rate caps
  reject at admission with the typed `TenantCapExceeded` (injected
  clock makes the token bucket deterministic), and the SLO-aware
  controller walks the degradation ladder spec-off -> chunk-shrink ->
  shed-lowest-priority and back down after the cooldown;
- legacy preservation: QoS-off engines produce bit-identical tokens
  with unchanged compile-cache keys and no qos metric series.
"""
import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.observability.export import prometheus_text
from deeplearning4j_tpu.parallel.failure import (FleetFaultInjector,
                                                 hostile_tenant_storm,
                                                 storm_prompt)
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import (EngineConfig, FleetConfig,
                                        InferenceEngine, Router)
from deeplearning4j_tpu.serving.engine import (
    MAX_PRIORITY, QoSValidationError, _compiled_chunked_prefill,
    _compiled_decode_chunk, _compiled_prefill,
    validate_tenant_priority)
from deeplearning4j_tpu.serving.fleet import TenantCapExceeded
from helpers import assert_no_recompiles

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


def _config(**kw):
    base = dict(decode_chunk=2, max_new_tokens=4, backoff_base_s=0.0,
                prefill_chunk=4, max_batch_size=2)
    base.update(kw)
    return EngineConfig(**base)


def _solo(params, mesh, prompt, max_new):
    """Uninterrupted reference run — the token-exactness oracle."""
    eng = InferenceEngine(CFG, mesh, params,
                          _config(max_new_tokens=max_new))
    h = eng.submit(prompt, max_new_tokens=max_new)
    eng.run_pending()
    return h.result(0)


# ---------------------------------------------------------------------------
# submit() validation (satellite 1)
# ---------------------------------------------------------------------------

def test_validate_tenant_priority_coerce_or_reject():
    """The shared validator: int tenants coerce to their decimal
    string; everything else non-str — including bool — is rejected
    typed, as are exposition-breaking ids and out-of-range or
    non-int priorities."""
    assert validate_tenant_priority(None, 0) == (None, 0)
    assert validate_tenant_priority("acme", 3) == ("acme", 3)
    assert validate_tenant_priority(42, 0) == ("42", 0)
    for bad_tenant in ("", "a" * 65, 'evil"', "two\nlines",
                       "back\\slash", "bell\x07", 1.5, b"bytes",
                       True, object()):
        with pytest.raises(QoSValidationError):
            validate_tenant_priority(bad_tenant, 0)
    for bad_prio in (-1, MAX_PRIORITY + 1, 1.0, "1", None, False):
        with pytest.raises(QoSValidationError):
            validate_tenant_priority("t", bad_prio)
    # the typed error IS a ValueError: pre-ISSUE-16 callers that
    # caught ValueError on submit keep working
    assert issubclass(QoSValidationError, ValueError)


def test_engine_and_router_submit_validate(params, mesh1):
    eng = InferenceEngine(CFG, mesh1, params, _config())
    with pytest.raises(QoSValidationError):
        eng.submit(_prompt(), tenant="bad\nid")
    with pytest.raises(QoSValidationError):
        eng.submit(_prompt(), priority=MAX_PRIORITY + 1)
    h = eng.submit(_prompt(), tenant=7, priority=2)
    assert (h.tenant, h.priority) == ("7", 2)
    eng.run_pending()
    assert h.error is None

    router = Router(cfg=CFG, mesh=mesh1, params=params,
                    num_replicas=1, engine_config=_config())
    try:
        with pytest.raises(QoSValidationError):
            router.submit(_prompt(), tenant="")
        with pytest.raises(QoSValidationError):
            router.submit(_prompt(), priority=-1)
        fr = router.submit(_prompt(), tenant=9, priority=1)
        assert (fr.tenant, fr.priority) == ("9", 1)
        router.run_pending()
        assert fr.error is None
    finally:
        router.close()


def test_qos_config_validation(params, mesh1):
    """Misconfigured QoS knobs fail at CONSTRUCTION, not mid-traffic."""
    with pytest.raises(ValueError):    # fair share needs the chunked
        InferenceEngine(CFG, mesh1, params,   # prefill scheduler
                        _config(prefill_chunk=None,
                                tenant_weights={"a": 1.0}))
    with pytest.raises(ValueError):
        InferenceEngine(CFG, mesh1, params,
                        _config(tenant_weights={"a": 0.0}))
    with pytest.raises(ValueError):
        InferenceEngine(CFG, mesh1, params,
                        _config(tenant_weights={"": 1.0}))
    with pytest.raises(ValueError):
        InferenceEngine(CFG, mesh1, params,
                        _config(preemption_budget=-1))
    with pytest.raises(ValueError):
        InferenceEngine(CFG, mesh1, params,
                        _config(mode="batch", decode_chunk=0,
                                prefill_chunk=None,
                                preemption_budget=1))


# ---------------------------------------------------------------------------
# weighted fair share (tentpole 1)
# ---------------------------------------------------------------------------

def test_weighted_share_ratio_converges(params, mesh1):
    """Two tenants, weights 3:1, both saturating the pool with long
    prompts under a small tick budget: the granted-prefill-token
    ratio converges to the weights (the serving_qos_prefill_tokens
    counters ARE the measurement)."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        _config(max_batch_size=4, max_new_tokens=2,
                tick_token_budget=8,
                tenant_weights={"gold": 3.0, "bronze": 1.0}))
    for i in range(2):
        eng.submit(_prompt(48, i), tenant="gold")
        eng.submit(_prompt(48, 10 + i), tenant="bronze")
    for _ in range(8):
        eng.tick()
    gold = eng._m_qos_prefill_tokens.labels("gold").value
    bronze = eng._m_qos_prefill_tokens.labels("bronze").value
    assert gold > 0 and bronze > 0
    ratio = gold / bronze
    assert 2.0 <= ratio <= 4.0, \
        f"weighted share diverged from 3:1: {gold}/{bronze}={ratio}"
    # the deficit table only tracks live demand (both still backlogged)
    dz = eng.debugz()["qos"]
    assert set(dz["deficits"]) <= {"gold", "bronze"}
    eng.run_pending()   # everything still completes


def test_no_starvation_within_k_ticks(params, mesh1):
    """A small victim prompt co-resident with a hostile 48-token
    prefill reaches prefill-done within K ticks under fair share —
    while the QoS-off oldest-first scheduler provably serves the
    hostile prompt's ENTIRE prefill first."""
    def ticks_until_victim_decodes(weights):
        eng = InferenceEngine(
            CFG, mesh1, params,
            _config(max_new_tokens=2, tick_token_budget=4,
                    tenant_weights=weights))
        hostile = eng.submit(_prompt(48, 1), tenant="hostile")
        victim = eng.submit(_prompt(8, 2), tenant="victim")
        for t in range(1, 64):
            eng.tick()
            if victim._prefill_pos >= victim._prefill_target:
                eng.run_pending()
                assert victim.error is None and hostile.error is None
                return t
        pytest.fail("victim never finished prefill")

    fair = ticks_until_victim_decodes({"victim": 1.0, "hostile": 1.0})
    assert fair <= 8, f"victim starved {fair} ticks under fair share"
    fifo = ticks_until_victim_decodes(None)
    assert fifo >= 12, \
        f"control arm invalid: oldest-first served victim at {fifo}"


def test_idle_tenant_share_rolls_over(params, mesh1):
    """With only ONE tenant backlogged, fair share must not slow it
    down: the full budget lands on the backlogged tenant (idle keys
    are dropped, not banked) and throughput matches the QoS-off
    engine tick for tick."""
    def ticks_to_drain(weights):
        eng = InferenceEngine(
            CFG, mesh1, params,
            _config(max_new_tokens=2, tick_token_budget=8,
                    tenant_weights=weights))
        h = eng.submit(_prompt(48, 3), tenant="solo")
        for t in range(1, 64):
            eng.tick()
            if h.done():
                assert h.error is None
                return t
        pytest.fail("request never completed")

    assert ticks_to_drain({"solo": 1.0, "idle": 8.0}) \
        == ticks_to_drain(None)


# ---------------------------------------------------------------------------
# priority preemption (tentpole 2)
# ---------------------------------------------------------------------------

def test_priority_preempts_lowest_and_resumes_token_exact(params,
                                                          mesh1):
    """A priority-3 arrival with both slots held by priority-0
    decodes evicts exactly one victim (lowest class, youngest seat),
    seats immediately, and the victim resumes from its committed
    prefix to the SAME tokens as an uninterrupted run."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        _config(max_new_tokens=8, preemption_budget=1))
    low = [eng.submit(_prompt(8, i), max_new_tokens=8, tenant="batch")
           for i in range(2)]
    eng.tick()                       # both seated, prefill advancing
    hi = eng.submit(_prompt(8, 5), max_new_tokens=8,
                    tenant="urgent", priority=3)
    eng.tick()                       # preempt + seat the class-3
    assert eng._m_qos_preemptions.labels("batch").value == 1
    evicted = [r for r in low
               if any(e.kind == "preempted"
                      and e.data.get("reason") == "priority"
                      for e in r.trace.events)]
    assert len(evicted) == 1
    ev = next(e for e in evicted[0].trace.events
              if e.kind == "preempted")
    assert ev.data["by"] == hi.rid
    eng.run_pending()
    for r in low + [hi]:
        assert r.error is None
        np.testing.assert_array_equal(
            r.result(0), _solo(params, mesh1, r.prompt, 8))


def test_preemption_budget_bounds_evictions_per_tick(params, mesh1):
    """Two waiting class-5 requests against a full pool of class-0
    residents: budget=1 evicts ONE resident per tick, not both at
    once — and nothing of any class is lost."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        _config(max_new_tokens=8, preemption_budget=1))
    low = [eng.submit(_prompt(8, i), max_new_tokens=8)
           for i in range(2)]
    eng.tick()
    his = [eng.submit(_prompt(8, 7 + i), max_new_tokens=8, priority=5)
           for i in range(2)]
    eng.tick()
    assert eng._m_qos_preemptions.labels("default").value == 1
    eng.tick()
    assert eng._m_qos_preemptions.labels("default").value == 2
    eng.run_pending()
    for r in low + his:
        assert r.error is None
        np.testing.assert_array_equal(
            r.result(0), _solo(params, mesh1, r.prompt, 8))


def test_equal_priority_never_thrashes(params, mesh1):
    """A waiter only displaces a STRICTLY lower class: a storm of
    equal-priority arrivals degrades to ordinary queueing with zero
    preemptions."""
    eng = InferenceEngine(
        CFG, mesh1, params,
        _config(max_new_tokens=4, preemption_budget=4))
    hs = [eng.submit(_prompt(8, i), priority=3) for i in range(5)]
    eng.run_pending()
    assert all(h.error is None for h in hs)
    assert eng._m_qos_preemptions.labels("default").value == 0


def test_priority_overcommit_reaches_engine_preemption(params, mesh1):
    """A full fleet must not park a high class in the ROUTER queue
    where engine preemption cannot see it: priority_overcommit lets
    the dispatch over-commit one in-flight request so the engine
    evicts a class-0 resident for the seat. With overcommit 0 the
    same arrival waits its turn (zero preemptions, low done first)."""
    def run(overcommit):
        router = Router(
            cfg=CFG, mesh=mesh1, params=params, num_replicas=1,
            engine_config=_config(max_batch_size=1, max_new_tokens=8,
                                  preemption_budget=1),
            config=FleetConfig(priority_overcommit=overcommit))
        try:
            lo = router.submit(_prompt(8, 1), max_new_tokens=8,
                               priority=0)
            router.tick()            # lo dispatched + seated
            hi = router.submit(_prompt(8, 2), max_new_tokens=8,
                               priority=2)
            order = []
            for _ in range(400):
                router.tick()
                for name, h in (("lo", lo), ("hi", hi)):
                    if h.done() and name not in order:
                        order.append(name)
                if len(order) == 2:
                    break
            assert lo.error is None and hi.error is None
            eng = router._ctls[0].replica.engine
            pre = (eng._m_qos_preemptions.labels("default").value
                   if eng._m_qos_preemptions is not None else 0)
            return order, pre
        finally:
            router.close()

    order, pre = run(1)
    assert order == ["hi", "lo"] and pre == 1
    order, pre = run(0)
    assert order == ["lo", "hi"] and pre == 0


# ---------------------------------------------------------------------------
# hostile-tenant storm: fleet-level zero-lost-high-priority (+ kill)
# ---------------------------------------------------------------------------

def _run_storm(params, mesh1, arrivals, inj_kwargs, qos=True, ttft=None):
    """Replays `arrivals` through a 2-replica fleet, one router tick a
    storm tick. `qos` False: no weights, no tenants, no priorities.
    `ttft`, a dict, gets each arrival's ticks from submit to its first
    committed token (the clock the fair-share scheduler divides)."""
    inj = FleetFaultInjector(**inj_kwargs)
    router = Router(
        cfg=CFG, mesh=mesh1, params=params, num_replicas=2,
        engine_config=_config(
            max_new_tokens=8, tick_token_budget=16,
            **(dict(tenant_weights={"victim": 4.0},
                    preemption_budget=1) if qos else {})),
        fault_injector=inj,
        config=FleetConfig(restart_backoff_base_s=0.01))
    handles, born = {}, {}
    try:
        pending = sorted(arrivals, key=lambda a: a.tick)
        tick = 0
        for _ in range(3000):
            while pending and pending[0].tick <= tick:
                a = pending.pop(0)
                handles[a] = router.submit(
                    storm_prompt(a, CFG.vocab_size),
                    max_new_tokens=min(a.max_new_tokens, 8),
                    **(dict(tenant=a.tenant, priority=a.priority)
                       if qos else {}))
                born[a] = tick
            router.tick()
            tick += 1
            if ttft is not None:
                for a, h in handles.items():
                    if a not in ttft and h.generated.shape[0] > 0:
                        ttft[a] = tick - born[a]
            if not pending and all(h.done()
                                   for h in handles.values()):
                break
        assert all(h.done() for h in handles.values())
        if not qos:
            assert not any(
                "qos" in prometheus_text(c.replica.engine.registry)
                for c in router._ctls)
    finally:
        router.close()
    return handles, inj


def _small_storm(**kw):
    return hostile_tenant_storm(
        ticks=10, hostiles=2, flood_per_tick=1, victim_every=2,
        victim_prompt=8, victim_new=8, hostile_prompt=24,
        hostile_new=8, **kw)


@pytest.mark.parametrize("bound", ["zero_lost", "ttft_vs_solo"])
def test_storm_zero_lost_high_priority(params, mesh1, bound):
    """The storm's two bounds. `zero_lost`: every high-priority request
    completes whole. `ttft_vs_solo`: with QoS on, the victim's worst
    time to first token, in scheduler ticks, stays within 1.25x of what
    it gets alone on the fleet, where the same storm without QoS makes
    it wait several times as long."""
    arrivals, ik = _small_storm()
    assert ik == {}
    if bound == "ttft_vs_solo":
        victims = [a for a in arrivals if a.tenant == "victim"]
        worst = {}
        for arm, arr, qos in (("solo", victims, False),
                              ("off", arrivals, False),
                              ("on", arrivals, True)):
            ttft = {}
            _run_storm(params, mesh1, arr, ik, qos=qos, ttft=ttft)
            worst[arm] = max(ttft[a] for a in victims)
        assert worst["on"] <= 1.25 * worst["solo"], worst
        assert worst["off"] > 2 * worst["solo"], worst
        return
    handles, _ = _run_storm(params, mesh1, arrivals, ik)
    victims = [(a, h) for a, h in handles.items()
               if a.tenant == "victim"]
    assert victims
    for a, h in victims:
        assert h.error is None, f"high-priority lost: {h.error}"
        assert h.generated.shape[0] == 8


def test_storm_zero_lost_high_priority_under_kill_one(params, mesh1):
    """Kill a replica mid-storm: failover + preemption together still
    lose ZERO high-priority requests (committed-prefix resume)."""
    arrivals, ik = _small_storm(kill_tick=5, kill_replica=0)
    assert ik == {"kill_at": {5: 0}}
    handles, inj = _run_storm(params, mesh1, arrivals, ik)
    assert inj.kills_injected == 1
    for a, h in handles.items():
        if a.tenant != "victim":
            continue
        assert h.error is None, f"high-priority lost: {h.error}"
        assert h.generated.shape[0] == 8


def test_storm_generator_is_deterministic():
    a1, k1 = hostile_tenant_storm(ticks=40, kill_tick=7)
    a2, k2 = hostile_tenant_storm(ticks=40, kill_tick=7)
    assert a1 == a2 and k1 == k2
    p1 = storm_prompt(a1[3], CFG.vocab_size)
    p2 = storm_prompt(a2[3], CFG.vocab_size)
    np.testing.assert_array_equal(p1, p2)
    with pytest.raises(ValueError):
        hostile_tenant_storm(ticks=0)


# ---------------------------------------------------------------------------
# admission caps + SLO-aware overload control (tentpole 3)
# ---------------------------------------------------------------------------

def test_tenant_concurrency_cap_rejects_then_releases(params, mesh1):
    router = Router(
        cfg=CFG, mesh=mesh1, params=params, num_replicas=1,
        engine_config=_config(),
        config=FleetConfig(tenant_max_concurrency=2))
    try:
        hs = [router.submit(_prompt(8, i), tenant="capped")
              for i in range(2)]
        with pytest.raises(TenantCapExceeded):
            router.submit(_prompt(), tenant="capped")
        with pytest.raises(TenantCapExceeded):
            router.submit(_prompt(), tenant="capped")
        other = router.submit(_prompt(8, 4), tenant="other")
        router.run_pending()
        assert all(h.error is None for h in hs + [other])
        # terminal requests release their seats: same tenant admits
        again = router.submit(_prompt(8, 5), tenant="capped")
        router.run_pending()
        assert again.error is None
        assert router._m_qos_rejections.labels(
            "concurrency").value >= 2
        # TenantCapExceeded IS an OverloadError: pre-ISSUE-16 callers
        # treating rejections as overload keep working
        from deeplearning4j_tpu.serving.engine import OverloadError
        assert issubclass(TenantCapExceeded, OverloadError)
    finally:
        router.close()


def test_tenant_rate_cap_token_bucket_injected_clock(params, mesh1):
    class _Clk:
        t = 0.0

        def __call__(self):
            return self.t

    clk = _Clk()
    router = Router(
        cfg=CFG, mesh=mesh1, params=params, num_replicas=1,
        engine_config=_config(),
        config=FleetConfig(tenant_rate_per_s=1.0,
                           tenant_rate_burst=2),
        clock=clk)
    try:
        hs = [router.submit(_prompt(8, i), tenant="rl")
              for i in range(2)]           # burst of 2 admits
        with pytest.raises(TenantCapExceeded):
            router.submit(_prompt(), tenant="rl")
        assert router._m_qos_rejections.labels("rate").value == 1
        clk.t = 1.0                        # one token refilled
        hs.append(router.submit(_prompt(8, 3), tenant="rl"))
        with pytest.raises(TenantCapExceeded):
            router.submit(_prompt(), tenant="rl")
        # other tenants have their own buckets
        hs.append(router.submit(_prompt(8, 4), tenant="free"))
        router.run_pending()
        assert all(h.error is None for h in hs)
    finally:
        router.close()


def test_overload_ladder_degrades_and_restores(params, mesh1):
    """Deterministic queue-depth trigger: the controller walks
    spec-off -> chunk-shrink -> shed-lowest-priority one rung per
    check, the engine knobs actually move, rung 3 sheds the LOWEST
    class first (typed reason 'qos'), and the ladder unwinds after
    the cooldown once the queue drains."""
    router = Router(
        cfg=CFG, mesh=mesh1, params=params, num_replicas=1,
        engine_config=_config(max_batch_size=1, max_new_tokens=8),
        config=FleetConfig(overload_queue_depth=2,
                           overload_check_every_ticks=1,
                           overload_cooldown_ticks=3,
                           overload_shed_per_tick=2))
    try:
        eng = router._ctls[0].replica.engine
        base_chunk = eng._base_chunk
        keep = [router.submit(_prompt(8, i), priority=2)
                for i in range(2)]
        flood = [router.submit(_prompt(8, 10 + i))
                 for i in range(8)]
        for _ in range(3):
            router.tick()
        dz = router.debugz()["qos"]
        assert dz["level"] == 3
        assert eng._qos_spec_off is True
        assert eng._chunk == max(1, base_chunk // 2)
        shed = [h for h in flood if h.done() and h.error is not None]
        assert shed, "rung 3 shed nothing"
        assert router._m_shed_qos.value >= len(shed)
        # the class-2 requests were NOT shed (lowest-priority-first)
        assert not any(h.done() and h.error is not None
                       for h in keep)
        router.run_pending()               # drain the survivors
        for h in keep:
            assert h.error is None
        for _ in range(16):                # healthy ticks: unwind
            router.tick()
        dz = router.debugz()["qos"]
        assert dz["level"] == 0
        assert eng._qos_spec_off is False
        assert eng._chunk == base_chunk
        acts = router._m_qos_actions
        assert acts.labels("degrade_spec_off").value == 1
        assert acts.labels("degrade_chunk_shrink").value == 1
        assert acts.labels("degrade_shed_low").value == 1
        assert acts.labels("restore_none").value == 1
        # every transition is a typed qos trace event
        kinds = [(e.data.get("action"), e.data.get("step"))
                 for e in router.recorder.recent(200)
                 if e.kind == "qos"]
        assert ("degrade", "spec_off") in kinds
        assert ("restore", "none") in kinds
    finally:
        router.close()


# ---------------------------------------------------------------------------
# debugz surfaces (satellite 2)
# ---------------------------------------------------------------------------

def test_debugz_tenant_priority_columns(params, mesh1):
    eng = InferenceEngine(
        CFG, mesh1, params,
        _config(max_batch_size=1, preemption_budget=1,
                tick_token_budget=8,
                tenant_weights={"a": 2.0}))
    eng.submit(_prompt(8, 0), tenant="a", priority=1)
    eng.submit(_prompt(8, 1), tenant="b")
    eng.submit(_prompt(8, 2), tenant="b")
    eng.tick()
    d = eng.debugz()
    assert all({"tenant", "priority"} <= set(row)
               for row in d["slots"] + d["queue"])
    assert d["queue_by_tenant"] == {"b": 2}
    assert d["qos"]["preemption_budget"] == 1
    assert d["qos"]["tenant_weights"] == {"a": 2.0}
    eng.run_pending()

    router = Router(cfg=CFG, mesh=mesh1, params=params,
                    num_replicas=1,
                    engine_config=_config(max_batch_size=1),
                    config=FleetConfig(tenant_max_concurrency=8))
    try:
        for i in range(3):
            router.submit(_prompt(8, i), tenant="x", priority=i % 2)
        d = router.debugz()
        assert all({"tenant", "priority"} <= set(row)
                   for row in d["queue"])
        assert d["queue_by_tenant"].get("x", 0) >= 1
        assert d["qos"]["tenant_max_concurrency"] == 8
        assert "tenant_live" in d["qos"]
        router.run_pending()
    finally:
        router.close()


# ---------------------------------------------------------------------------
# legacy preservation: QoS off is bit-identical, same compile keys
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("traffic", ["engine", "storm"])
def test_qos_off_bit_identical_no_new_compile_keys(params, mesh1,
                                                   traffic):
    """A QoS-off engine built after the baseline reuses every compiled
    program (zero new cache entries — the cache keys did not move)
    and produces byte-identical tokens; a QoS-ON engine changes
    scheduling only, so its tokens match too. `storm`: the same of the
    hostile-tenant storm replayed through the fleet (and, QoS off, no
    qos series in either replica's scrape)."""
    if traffic == "engine":
        def replay(qos):
            eng = InferenceEngine(
                CFG, mesh1, params,
                _config(tick_token_budget=8, preemption_budget=1,
                        tenant_weights={"gold": 3.0})
                if qos else _config())
            h = eng.submit(_prompt(24, 6),
                           **(dict(tenant="gold", priority=1)
                              if qos else {}))
            eng.run_pending()
            return [h.result(0)]
        ref = [_solo(params, mesh1, _prompt(24, 6), 4)]
    else:
        arrivals, ik = _small_storm()

        def replay(qos):
            handles, _ = _run_storm(params, mesh1, arrivals, ik, qos=qos)
            return [handles[a].result(0) for a in arrivals]
        ref = replay(False)
    with assert_no_recompiles(_compiled_prefill,
                              _compiled_chunked_prefill,
                              _compiled_decode_chunk):
        got = replay(False)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref, replay(True)):
        np.testing.assert_array_equal(a, b)


def test_qos_off_engine_has_no_qos_series(params, mesh1):
    eng = InferenceEngine(CFG, mesh1, params, _config())
    h = eng.submit(_prompt(), tenant="t")
    eng.run_pending()
    assert h.error is None
    assert "qos" not in prometheus_text(eng.registry)
