"""Spans that keep a record (ISSUE-26): the ring, the serving tick's
spans and counters, and the names the kernels and serving programs carry
onto the device. CPU: counts and structure only, no time is asserted.
Every test injects a ring of its own (`spans=`): the suite shares a
process, and the default ring with it.
"""
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.observability import tracing
from deeplearning4j_tpu.observability.tracing import (NULL_SPANS, SpanRing,
                                                      annotate, mark, span)
from deeplearning4j_tpu.parallel import serving as pserving
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import EngineConfig, InferenceEngine

CFG = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                        n_layers=2, max_len=64)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh(MeshSpec(data=1, model=1))


def _prompt(t0, seed):
    return (np.arange(t0, dtype=np.int32) * (seed + 3)) % CFG.vocab_size


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_is_bounded_and_keeps_the_newest():
    ring = SpanRing(capacity=4)
    for i in range(10):
        with span("s", spans=ring, i=i):
            pass
    snap = ring.snapshot()
    assert len(ring) == len(snap.spans) == 4
    assert [s.args["i"] for s in snap.spans] == [6, 7, 8, 9]
    assert tracing.DEFAULT_CAPACITY >= 65536
    assert tracing.default_spans().capacity == tracing.DEFAULT_CAPACITY
    with pytest.raises(ValueError):
        SpanRing(capacity=0)


def test_nested_spans_record_parents_and_inherit_tick_and_rid():
    ring = SpanRing()
    with span("outer", spans=ring, tick=7, rid=3, depth=5) as q0:
        with span("inner", spans=ring) as q1:
            annotate(found=2)
        mark("note", spans=ring, reason="pages")
    assert (q0, q1) == ("outer", "outer/inner")
    by = {s.name: s for s in ring.snapshot().spans}
    outer, inner, note = by["outer"], by["inner"], by["note"]
    assert outer.parent_id is None and outer.args == {"depth": 5}
    assert inner.parent_id == note.parent_id == outer.id
    assert len({outer.id, inner.id, note.id}) == 3
    assert (inner.tick, inner.rid) == (note.tick, note.rid) == (7, 3)
    assert inner.args == {"found": 2}
    assert outer.start <= inner.start <= inner.end <= note.start
    assert note.end <= outer.end
    assert tracing.current_span() is None


def test_mark_is_zero_length_and_stands_alone():
    ring = SpanRing()
    mark("lonely", spans=ring, tick=2, why="x")
    (m,) = ring.snapshot().spans
    assert m.start == m.end and m.parent_id is None
    assert (m.name, m.tick, m.args) == ("lonely", 2, {"why": "x"})


def test_threads_nest_independently():
    ring = SpanRing()
    inside, release = threading.Event(), threading.Event()

    def other():
        with span("other", spans=ring):
            inside.set()
            release.wait(5)

    t = threading.Thread(target=other)
    with span("main", spans=ring):
        t.start()
        assert inside.wait(5)
        with span("main.child", spans=ring):
            pass
        release.set()
        t.join()
    by = {s.name: s for s in ring.snapshot().spans}
    assert by["other"].parent_id is None          # not main's child
    assert by["main.child"].parent_id == by["main"].id


def test_snapshot_anchor_is_one_moment_on_both_clocks():
    import time
    ring = SpanRing()
    p0, w0 = time.perf_counter(), time.time_ns()
    snap = ring.snapshot()
    p1, w1 = time.perf_counter(), time.time_ns()
    assert p0 <= snap.anchor[0] <= p1 and w0 <= snap.anchor[1] <= w1
    # an offset on the host's clock carries over to the trace's
    t = snap.anchor[0] - 2.5
    assert snap.to_trace_s(t) == pytest.approx(snap.anchor[1] * 1e-9 - 2.5)


def test_null_spans_record_nothing_and_span_still_yields(monkeypatch):
    opened = []

    class Probe:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(tracing, "_Annotation", Probe)
    with span("a", spans=NULL_SPANS, tick=1) as q:
        with span("b", spans=NULL_SPANS) as q2:
            annotate(x=1)
        mark("m", spans=NULL_SPANS)
    assert (q, q2) == ("a", "a/b")
    assert len(NULL_SPANS) == 0 and NULL_SPANS.snapshot().spans == ()
    assert opened == []                  # no profiler annotation either
    ring = SpanRing()
    with span("a", spans=ring):
        mark("m", spans=ring)
    assert opened == ["a"]               # a live ring annotates spans only


def test_span_without_registry_makes_no_histogram():
    from deeplearning4j_tpu.observability import MetricsRegistry
    reg, ring = MetricsRegistry(), SpanRing()
    with span("plain", spans=ring):
        pass
    with span("timed", registry=reg, spans=ring):
        pass
    hist = reg.get("trace_span_seconds")
    assert [l[0] for l, _ in hist.collect()] == ["timed"]
    assert [s.name for s in ring.snapshot().spans] == ["plain", "timed"]


# ---------------------------------------------------------------------------
# the tick's spans
# ---------------------------------------------------------------------------

def _engine(mesh, params, ring, **kw):
    base = dict(decode_chunk=2, max_new_tokens=8, num_slots=4,
                backoff_base_s=0.0, paged=True, page_size=4, kv_pages=64,
                prefill_chunk=8, pipeline=True, prefix_cache=False)
    base.update(kw)
    return InferenceEngine(CFG, mesh, params, EngineConfig(**base),
                           spans=ring)


def _children(spans):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def test_tick_spans_nest_and_commit_one_tick_behind(params, mesh1):
    ring = SpanRing()
    eng = _engine(mesh1, params, ring)
    hs = [eng.submit(_prompt(5 + 3 * i, i)) for i in range(6)]
    n_ticks = eng.run_pending()
    assert all(h.done() and h.error is None for h in hs)
    spans = ring.snapshot().spans
    ticks = [s for s in spans if s.name == "engine.tick"]
    # run_pending's last call is the idle tick that ends it
    assert len(ticks) == n_ticks + 1
    assert [t.tick for t in ticks] == list(range(1, len(ticks) + 1))
    assert ticks[0].args["queue"] == 6
    kids = _children(spans)
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.parent_id is not None:
            p = by_id[s.parent_id]
            assert p.start <= s.start <= s.end <= p.end, (s.name, p.name)
            assert s.tick == p.tick
    for t in ticks:
        names = [k.name for k in kids.get(t.id, [])]
        assert names[0] == "engine.tick.admit"
        assert set(names) <= {
            "engine.tick.admit", "engine.tick.dispatch",
            "engine.tick.commit", "engine.tick.reap",
            "engine.tick.listeners"}
    commits = [s for s in spans if s.name == "engine.tick.commit"]
    assert commits and all(
        c.args["commits_tick"] == c.tick - 1 for c in commits)
    for c in commits:
        assert [k.name for k in kids[c.id]] == ["engine.tick.commit.sync"]
    dispatch = [s for s in spans if s.name.startswith("engine.dispatch.")]
    assert {by_id[d.parent_id].name for d in dispatch} == {
        "engine.tick.dispatch"}
    admits = [s for s in spans if s.name == "engine.tick.admit"]
    assert sum(a.args["seated"] for a in admits) == 6


def test_dispatch_spans_count_the_tokens_the_handles_got(params, mesh1):
    ring = SpanRing()
    eng = _engine(mesh1, params, ring)
    prompts = [_prompt(5 + 3 * i, i) for i in range(6)]
    hs = [eng.submit(p) for p in prompts]
    eng.run_pending()
    spans = ring.snapshot().spans
    pre = [s for s in spans if s.name == "engine.dispatch.prefill"]
    dec = [s for s in spans if s.name == "engine.dispatch.decode"]
    assert {s.args["program"] for s in pre} == {"paged_chunked_prefill"}
    assert {s.args["program"] for s in dec} == {"paged_decode"}
    assert sum(s.args["prefill_tokens"] for s in pre) == sum(
        len(p) for p in prompts)
    assert sum(s.args["prefill_pairs"] for s in pre) == sum(
        len(p) * (len(p) + 1) // 2 for p in prompts)
    assert sum(s.args["reprefill_tokens"] for s in pre) == 0
    # a request's first token is prefill's; every other is a decode token
    assert sum(s.args["decode_tokens"] for s in dec) == sum(
        h.generated.shape[0] - 1 for h in hs)
    assert sum(s.args["decode_rows"] for s in dec) == sum(
        sum(len(p) + j for j in range(1, h.generated.shape[0]))
        for p, h in zip(prompts, hs))
    assert all(s.args["steps"] == 2 and 1 <= s.args["rows"] <= 4
               for s in dec)
    commits = [s for s in spans if s.name == "engine.tick.commit"]
    assert sum(c.args["tokens"] for c in commits) == sum(
        h.generated.shape[0] for h in hs)


def test_sync_failure_yields_one_recover_span_and_counter(params, mesh1):
    ring = SpanRing()
    eng = _engine(mesh1, params, ring)
    orig, fired = eng._block_on_many, []

    def flaky(xs):
        if not fired and eng._m_batches.value >= 3:
            fired.append(True)
            raise RuntimeError("injected sync-time device failure")
        return orig(xs)

    eng._block_on_many = flaky
    hs = [eng.submit(_prompt(5 + 3 * i, i)) for i in range(6)]
    eng.run_pending()
    assert fired and all(h.done() and h.error is None for h in hs)
    spans = ring.snapshot().spans
    rec = [s for s in spans if s.name == "engine.tick.recover"]
    assert len(rec) == 1
    assert rec[0].args["error"] == "RuntimeError"
    assert rec[0].args["requests"] >= 1
    assert eng.registry.get("serving_ticks_recovered").value == 1
    # the isolated requests were prefilled again from their committed
    # prefix: the spans and the counter agree on how many tokens
    again = sum(s.args["reprefill_tokens"] for s in spans
                if s.name == "engine.dispatch.prefill")
    assert again > 0
    assert eng.registry.get("serving_reprefill_tokens").value == again


def test_small_pool_marks_admission_blocked_on_pages(params, mesh1):
    ring = SpanRing()
    # 6 usable pages of 4 rows: one 16-token prompt with its answer
    # takes them all, so the second request waits for pages
    eng = _engine(mesh1, params, ring, kv_pages=7, num_slots=2,
                  max_new_tokens=4)
    hs = [eng.submit(_prompt(16, i)) for i in range(2)]
    eng.run_pending()
    assert all(h.done() and h.error is None for h in hs)
    spans = ring.snapshot().spans
    blocked = [s for s in spans if s.name == "engine.admit.blocked"]
    assert blocked and {b.args["reason"] for b in blocked} == {"pages"}
    assert all(b.rid == hs[1].rid and b.start == b.end for b in blocked)
    by_id = {s.id: s for s in spans}
    assert {by_id[b.parent_id].name for b in blocked} == {
        "engine.tick.admit"}
    counter = eng.registry.get("serving_admission_blocked")
    assert counter.labels("pages").value == len(blocked)
    assert counter.labels("slots").value == 0


def test_full_slots_mark_admission_blocked_on_slots(params, mesh1):
    ring = SpanRing()
    eng = _engine(mesh1, params, ring, num_slots=2)
    hs = [eng.submit(_prompt(6, i)) for i in range(3)]
    eng.run_pending()
    assert all(h.done() for h in hs)
    blocked = [s for s in ring.snapshot().spans
               if s.name == "engine.admit.blocked"]
    assert blocked and {b.args["reason"] for b in blocked} == {"slots"}
    assert all(b.rid == hs[2].rid for b in blocked)


def test_synchronous_tick_loops_get_the_spans_from_the_same_places(
        params, mesh1):
    for kw in (dict(pipeline=False),
               dict(pipeline=False, prefill_chunk=None, paged=False)):
        ring = SpanRing()
        eng = _engine(mesh1, params, ring, **kw)
        hs = [eng.submit(_prompt(6, i)) for i in range(3)]
        eng.run_pending()
        spans = ring.snapshot().spans
        names = {s.name for s in spans}
        assert {"engine.tick", "engine.tick.admit", "engine.tick.reap",
                "engine.tick.listeners", "engine.dispatch.prefill",
                "engine.dispatch.decode"} <= names
        assert "engine.tick.commit" not in names
        dec = [s for s in spans if s.name == "engine.dispatch.decode"]
        assert sum(s.args["decode_tokens"] for s in dec) == sum(
            h.generated.shape[0] - 1 for h in hs)


def test_null_spans_engine_runs_and_records_nothing(params, mesh1):
    before = len(tracing.default_spans())
    eng = _engine(mesh1, params, NULL_SPANS)
    h = eng.submit(_prompt(6, 0))
    eng.run_pending()
    assert h.done() and h.error is None
    assert eng.spans is NULL_SPANS and len(NULL_SPANS) == 0
    assert len(tracing.default_spans()) == before
    # and with no ring named, the engine's is the process's
    assert InferenceEngine(CFG, mesh1, params, EngineConfig(
        num_slots=2)).spans is tracing.default_spans()


# ---------------------------------------------------------------------------
# names on the device
# ---------------------------------------------------------------------------

def _pallas_names(jaxpr, under=()):
    """[(kernel name, primitives it is nested under)] of every
    pallas_call in a jaxpr, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], under))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _pallas_names(
                        inner, under + (eqn.primitive.name,))
    return out


def test_flash_kernels_keep_their_names_under_checkpoint(monkeypatch):
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    from deeplearning4j_tpu.ops.flash_attention import flash_attention
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        f = jax.checkpoint(
            lambda q, k, v: flash_attention(q, k, v, causal=True))
        return jnp.sum(f(q, k, v))

    fwd = _pallas_names(jax.make_jaxpr(loss)(q, q, q).jaxpr)
    assert [n for n, _ in fwd] == ["flash_fwd"]
    assert any("checkpoint" in p or "remat" in p for p in fwd[0][1])
    grad = _pallas_names(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr)
    names = sorted(n for n, _ in grad)
    assert set(names) == {"flash_fwd", "flash_bwd"}
    assert names.count("flash_bwd") == 1


def test_decode_kernels_are_named(monkeypatch):
    monkeypatch.setenv("DL4JTPU_FLASH", "interpret")
    from deeplearning4j_tpu.ops import flash_decode as fd
    q = jnp.ones((2, 4, 64), jnp.float32)
    cache = jnp.ones((2, 128, 256), jnp.float32)
    pos = jnp.array([5, 9], jnp.int32)
    one = jax.make_jaxpr(lambda q, k, v: fd._split_k_call(
        fd._decode_kernel, q, k, v, pos, 0, None, 4))(q, cache, cache)
    assert [n for n, _ in _pallas_names(one.jaxpr)] == ["flash_decode"]
    qw = jnp.ones((2, 3 * 4, 64), jnp.float32)
    win = jax.make_jaxpr(lambda q, k, v: fd._split_k_call(
        fd._window_kernel, q, k, v, pos, 0, None, 4, window=3))(
            qw, cache, cache)
    assert [n for n, _ in _pallas_names(win.jaxpr)] == [
        "flash_decode_window"]


NS, PS, MP, NP, C = 2, 4, 16, 12, 8


def _maker_cases():
    """(module name expected, make_* and its geometry, runtime args)."""
    vec, mask = jnp.zeros((NS,), jnp.int32), jnp.zeros((NS,), bool)
    key = jax.random.PRNGKey(0)
    bt = jnp.zeros((NS, MP), jnp.int32)
    toks = jnp.zeros((NS, C), jnp.int32)

    def slot(mesh):
        return pserving.init_slot_state(CFG, mesh, NS)

    def paged(mesh):
        return pserving.init_paged_state(CFG, mesh, NS, PS, NP)

    geo = (NS, PS, MP, NP)
    return [
        ("parallel_generate", lambda m: pserving.make_parallel_generate(
            CFG, m, 2), lambda m: (jnp.zeros((NS, 4), jnp.int32), key)),
        ("continuous_prefill", lambda m: pserving.make_continuous_prefill(
            CFG, m, C, NS), lambda m: (*slot(m), toks, vec, key)),
        ("continuous_decode", lambda m: pserving.make_continuous_decode(
            CFG, m, 2, NS), lambda m: (*slot(m), mask, vec, key)),
        ("chunked_prefill", lambda m: pserving.make_chunked_prefill(
            CFG, m, C, NS),
         lambda m: (*slot(m), toks, vec, vec, mask, key)),
        ("paged_prefill", lambda m: pserving.make_paged_prefill(
            CFG, m, C, *geo), lambda m: (*paged(m), bt, toks, vec, vec,
                                         key)),
        ("paged_chunked_prefill",
         lambda m: pserving.make_paged_chunked_prefill(CFG, m, C, *geo),
         lambda m: (*paged(m), bt, toks, vec, vec, mask, key)),
        ("paged_decode", lambda m: pserving.make_paged_decode(
            CFG, m, 2, *geo), lambda m: (*paged(m), bt, mask, vec, key)),
        ("speculative_decode", lambda m: pserving.make_speculative_decode(
            CFG, m, 2, NS), lambda m: (*slot(m), mask, vec, mask, key)),
        ("paged_speculative_decode",
         lambda m: pserving.make_paged_speculative_decode(
             CFG, m, 2, *geo),
         lambda m: (*paged(m), bt, mask, vec, mask, key)),
    ]


@pytest.mark.parametrize("case", _maker_cases(), ids=lambda c: c[0])
def test_each_serving_maker_lowers_to_a_module_of_its_own_name(
        case, params, mesh1):
    maker, make, args = case
    sp = pserving.shard_serving_params(params, CFG, mesh1)
    fn = make(mesh1)
    extra = args(mesh1)
    if "speculative" in maker:
        text = fn.lower(sp, sp, *extra).as_text()
    else:
        text = fn.lower(sp, *extra).as_text()
    name = re.search(r"module @(\S+)", text).group(1)
    assert name == f"jit_run_{maker}"
    assert re.search("jit_run", name)        # what the old readers match
