"""KV wire transport (ISSUE-17): deterministic CPU suite.

Every acceptance behavior of the kvwire subsystem:

- the frame codec round-trips a `KVHandoff` BIT-EXACTLY — float and
  int8 (values AND per-row scales), slot- and cache-source, committed
  token prefix and weights-step included;
- every malformed frame fails TYPED (`WireError.kind` in magic |
  version | crc | truncated | type | error) and the serving paths
  that consume frames degrade to re-prefill — a deterministically
  injected corrupt frame (`FleetFaultInjector.corrupt_frame_at`)
  costs one re-prefill, never a lost request, never a wrong token;
- quantize-on-adopt: a FLOAT handoff headed for an int8 decode tier
  is row-quantized at encode time with the same absmax math as
  `quant.kv.quantize_rows`, so heterogeneous tiers adopt instead of
  re-prefilling;
- proactive migration: autoscale-up pushes the fleet's hottest
  advertised chains into the new replica's radix cache before any
  traffic lands on it, and replica LRU eviction is biased away from
  fleet-advertised chains (bias, not immunity);
- the `multiproc`-marked tests put a REAL process boundary under the
  wire: a 2-prefill + 1-decode subprocess tiered fleet completes a
  long-prompt trace with ZERO happy-path re-prefills (handoff frames
  cross the worker pipes, outcome ok), token-exact vs an in-process
  engine; chain export/seed and qos_control actuate over the same
  framing.
"""
import socket
import struct
import threading
import time

import numpy as np
import jax
import pytest

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params)
from deeplearning4j_tpu.parallel.failure import FleetFaultInjector
from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
from deeplearning4j_tpu.serving import (EngineConfig, FleetConfig,
                                        InferenceEngine, KVHandoff,
                                        RequestStatus,
                                        SubprocessReplica, TieredRouter,
                                        WireError, WireServer,
                                        decode_control, decode_handoff,
                                        encode_control, encode_handoff,
                                        frame_from_text, frame_to_text,
                                        recv_frame, requantize_handoff,
                                        send_frame, wire_call)
from deeplearning4j_tpu.serving import kvwire
from deeplearning4j_tpu.serving.paging import (PageAllocator,
                                               RadixPrefixCache)

CFG = TransformerConfig(vocab_size=32, d_model=32, n_heads=4,
                        n_layers=2, max_len=64)

#: Hard wall for anything that could block on a child process.
HARD_TIMEOUT_S = 240.0


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


def _drive(router, limit=3000):
    for _ in range(limit):
        if not router.pending():
            return
        router.tick()
    raise AssertionError("tiered router failed to drain within bound")


def _mk_kv(kv_mode=None, pos=12, seed=0, source="slot",
           with_tokens=False):
    """A synthetic committed-KV handoff, float or pre-quantized."""
    rng = np.random.default_rng(seed)
    shape = (CFG.n_layers, pos, CFG.d_model)
    k = rng.normal(size=shape).astype(np.float32)
    v = rng.normal(size=shape).astype(np.float32)
    ks = vs = None
    if kv_mode == "int8":
        ks = rng.uniform(0.01, 0.1, (CFG.n_layers, pos, 1)) \
            .astype(np.float32)
        vs = rng.uniform(0.01, 0.1, (CFG.n_layers, pos, 1)) \
            .astype(np.float32)
        k = rng.integers(-127, 128, shape).astype(np.int8)
        v = rng.integers(-127, 128, shape).astype(np.int8)
    tokens = (np.arange(pos, dtype=np.int32) if with_tokens else None)
    return KVHandoff(pos=pos, tok=7, k=k, v=v, k_scale=ks, v_scale=vs,
                     kv_mode=kv_mode, n_layers=CFG.n_layers,
                     d_model=CFG.d_model, source=source, tokens=tokens,
                     weights_step=3)


# ---------------------------------------------------------------------------
# codec: bit-exact round trips
# ---------------------------------------------------------------------------

def test_float_roundtrip_bit_exact():
    kv = _mk_kv()
    out = decode_handoff(encode_handoff(kv))
    np.testing.assert_array_equal(out.k, kv.k)
    np.testing.assert_array_equal(out.v, kv.v)
    assert out.k.dtype == np.float32
    assert (out.pos, out.tok, out.kv_mode) == (kv.pos, kv.tok, None)
    assert out.k_scale is None and out.v_scale is None
    assert out.n_layers == CFG.n_layers and out.d_model == CFG.d_model
    assert out.source == "slot" and out.tokens is None
    assert out.weights_step == 3


def test_int8_cache_roundtrip_bit_exact():
    """Quantized rows AND per-row float32 scales AND the cached token
    prefix all survive the wire bit-identically."""
    kv = _mk_kv("int8", source="cache", with_tokens=True)
    out = decode_handoff(encode_handoff(kv))
    np.testing.assert_array_equal(out.k, kv.k)
    np.testing.assert_array_equal(out.v, kv.v)
    np.testing.assert_array_equal(out.k_scale, kv.k_scale)
    np.testing.assert_array_equal(out.v_scale, kv.v_scale)
    np.testing.assert_array_equal(out.tokens, kv.tokens)
    assert out.k.dtype == np.int8 and out.k_scale.dtype == np.float32
    assert out.kv_mode == "int8" and out.source == "cache"


def test_frame_header_layout():
    """The documented 16-byte header: magic, version, type, reserved,
    payload length, CRC32 — little-endian, stable on the wire."""
    frame = encode_handoff(_mk_kv())
    assert len(frame) >= kvwire.HEADER_SIZE == 16
    magic, ver, ftype, rsvd, plen, crc = struct.unpack_from(
        "<4sHBBII", frame)
    assert magic == b"KVWR" and ver == kvwire.WIRE_VERSION
    assert ftype == kvwire.FRAME_HANDOFF and rsvd == 0
    assert plen == len(frame) - kvwire.HEADER_SIZE
    import zlib
    assert crc == zlib.crc32(frame[kvwire.HEADER_SIZE:]) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# codec: every failure is typed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flip", [16, -1, 200],
                         ids=["first-payload", "last-payload", "mid"])
def test_crc_corruption_detected(flip):
    frame = bytearray(encode_handoff(_mk_kv("int8")))
    frame[flip] ^= 0xFF
    with pytest.raises(WireError) as ei:
        decode_handoff(bytes(frame))
    assert ei.value.kind == "crc"


def test_truncation_detected():
    frame = encode_handoff(_mk_kv())
    for cut in (0, 4, kvwire.HEADER_SIZE - 1, kvwire.HEADER_SIZE + 8,
                len(frame) - 1):
        with pytest.raises(WireError) as ei:
            decode_handoff(frame[:cut])
        assert ei.value.kind == "truncated", f"cut={cut}"


def test_bad_magic_detected():
    frame = bytearray(encode_handoff(_mk_kv()))
    frame[:4] = b"NOPE"
    with pytest.raises(WireError) as ei:
        decode_handoff(bytes(frame))
    assert ei.value.kind == "magic"


def test_version_skew_refused():
    """A frame from a NEWER protocol is refused typed (the receiver
    can't know what it means); re-prefill is the degradation."""
    frame = bytearray(encode_handoff(_mk_kv()))
    struct.pack_into("<H", frame, 4, kvwire.WIRE_VERSION + 1)
    with pytest.raises(WireError) as ei:
        decode_handoff(bytes(frame))
    assert ei.value.kind == "version"


def test_frame_type_mismatch_detected():
    with pytest.raises(WireError) as ei:
        decode_handoff(encode_control({"spec_off": True}))
    assert ei.value.kind == "type"
    with pytest.raises(WireError) as ei:
        decode_control(encode_handoff(_mk_kv()))
    assert ei.value.kind == "type"


def test_control_roundtrip():
    p = {"spec_off": True, "chunk_shrink": False, "decode_chunk": 3}
    assert decode_control(encode_control(p)) == p


def test_text_transport_roundtrip():
    """The base64 wrapping used on the worker pipe's JSON lines."""
    frame = encode_handoff(_mk_kv("int8"))
    text = frame_to_text(frame)
    assert isinstance(text, str) and "\n" not in text
    assert frame_from_text(text) == frame
    with pytest.raises(WireError) as ei:
        frame_from_text("!!not base64!!")
    assert ei.value.kind == "truncated"


# ---------------------------------------------------------------------------
# quantize-on-adopt math
# ---------------------------------------------------------------------------

def test_requantize_matches_engine_quantizer():
    """The wire's numpy row quantizer is bit-identical to the
    engine's own `quant.kv.quantize_rows` — an adopted requantized
    row equals what the target would have produced itself."""
    from deeplearning4j_tpu.quant.kv import quantize_rows
    kv = _mk_kv(seed=5)
    q = requantize_handoff(kv, "int8")
    assert q.kv_mode == "int8" and q.k.dtype == np.int8
    assert q.k_scale.shape == (CFG.n_layers, kv.pos, 1)
    assert q.k_scale.dtype == np.float32
    jq, jscale = quantize_rows(kv.k, "int8")
    np.testing.assert_array_equal(np.asarray(jq), q.k)
    np.testing.assert_array_equal(
        np.asarray(jscale).reshape(q.k_scale.shape), q.k_scale)
    # the original float handoff is untouched
    assert kv.kv_mode is None and kv.k.dtype == np.float32


def test_requantize_zero_rows_and_passthrough():
    import dataclasses
    kv = _mk_kv()
    z = kv.k.copy()
    z[0, 0, :] = 0.0                      # an all-zero row
    kvz = dataclasses.replace(kv, k=z)
    q = requantize_handoff(kvz, "int8")
    assert q.k_scale[0, 0, 0] == 1.0      # zero row -> scale 1.0
    assert not np.any(q.k[0, 0])
    # same-mode passthrough is the identity
    assert requantize_handoff(kv, None) is kv
    q8 = _mk_kv("int8")
    assert requantize_handoff(q8, "int8") is q8
    # a quantized source cannot be REquantized to a different mode
    # (resolve_mode degrades "fp8" to "int8" on CPU, so fake the
    # mismatch from the source side)
    alien = dataclasses.replace(q8, kv_mode="fp8")
    with pytest.raises(WireError) as ei:
        requantize_handoff(alien, "int8")
    assert ei.value.kind == "error"


# ---------------------------------------------------------------------------
# socket transport
# ---------------------------------------------------------------------------

def test_socket_send_recv_roundtrip():
    frame = encode_handoff(_mk_kv("int8", with_tokens=True))
    a, b = socket.socketpair()
    try:
        send_frame(a, frame)
        assert recv_frame(b) == frame
    finally:
        a.close()
        b.close()


def test_wire_server_roundtrip():
    """One frame in -> handler -> one frame out, over a real TCP
    connection (the remote-target transport)."""
    def handler(frame):
        kv = decode_handoff(frame)
        return encode_control({"pos": int(kv.pos),
                               "tok": int(kv.tok)})
    srv = WireServer(handler)
    try:
        resp = wire_call(srv.address, encode_handoff(_mk_kv()))
        assert decode_control(resp) == {"pos": 12, "tok": 7}
    finally:
        srv.stop()


def test_wire_server_handler_failure_is_typed_at_dialer():
    """A handler that dies closes the connection without a response:
    the DIALER sees a typed truncated read, never a hang — and the
    server survives to answer the next call."""
    calls = []

    def handler(frame):
        calls.append(frame)
        if len(calls) == 1:
            raise RuntimeError("boom")
        return encode_control({"ok": True})
    srv = WireServer(handler)
    try:
        with pytest.raises(WireError) as ei:
            wire_call(srv.address, encode_control({}))
        assert ei.value.kind == "truncated"
        resp = wire_call(srv.address, encode_control({}))
        assert decode_control(resp) == {"ok": True}
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# tiered serving: degradation + quantize-on-adopt (in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arm", ["corrupt", "fallback"])
def test_corrupt_frame_degrades_to_reprefill(params, mesh1, arm):
    """FleetFaultInjector.corrupt_frame_at runs the first handoff
    through a REAL encode -> flip-one-byte -> decode round trip: the
    frame's CRC32 rejects it, the request re-prefills on the decode
    tier, the answer is still bit-exact — and the failure is visible
    as a typed `kvwire` trace event + serving_kvwire_frames{crc}.
    `fallback`: a prefill tier that cannot export (supports_handoff
    False) sends NO frame — every request re-prefills on the decode
    tier, counted as a fallback and not as a failure, bit-exact too."""
    prompts = [_prompt(8, i) for i in range(3)]
    want = _reference(params, mesh1, prompts)
    inj = FleetFaultInjector(
        corrupt_frame_at=[0] if arm == "corrupt" else [])
    r = _tiered(params, mesh1, fault_injector=inj)
    try:
        if arm == "fallback":
            r._ctls[0].replica.supports_handoff = False
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
            assert h.status == RequestStatus.COMPLETED
        m = r._kvwire_metrics()
        if arm == "fallback":
            assert inj.frames_corrupted == 0
            assert r.stats["handoffs_ok"] == 0
            assert r.stats["handoffs_failed"] == 0
            assert r.stats["handoffs_fallback"] == 3
            assert int(m["bytes"].value) == 0
            return
        assert inj.frames_corrupted == 1
        assert r.stats["handoffs_failed"] == 1
        assert r.stats["handoffs_ok"] == 2
        assert r.stats["handoffs_fallback"] == 0
        assert int(m["bytes"].value) > 0
        evs = [e for h in hs for e in h.trace.events
               if e.kind == "kvwire"]
        assert any(e.data["outcome"] == "crc" for e in evs)
        assert int(m["frames"].labels("export", "crc").value) == 1
        # the prefill tier's held slot was released despite the
        # corrupt frame (no leaked seats)
        assert r._ctls[0].replica.engine.drained()
    finally:
        r.close()


def test_quantize_on_adopt_heterogeneous_tiers(params, mesh1):
    """A float prefill tier handing off to an int8 decode tier: the
    router requantizes the float rows at encode time (per-row absmax
    scales ride along) and the decode tier ADOPTS — handoffs all ok,
    adoptions all ok, zero re-prefills — token-exact vs a single
    int8 engine."""
    pc, dc = _ec(), _ec(kv_quantize="int8")
    prompts = [_prompt(8, i) for i in range(3)]
    want = _reference(params, mesh1, prompts, ec=dc)
    r = _tiered(params, mesh1, pc=pc, dc=dc)
    try:
        hs = [r.submit(p, max_new_tokens=12) for p in prompts]
        _drive(r)
        for h, w in zip(hs, want):
            np.testing.assert_array_equal(h.result(0), w)
        assert r.stats["handoffs_ok"] == 3
        assert r.stats["handoffs_failed"] == 0
        dec_eng = r._ctls[1].replica.engine
        assert dec_eng._kv_mode == "int8"
        assert int(dec_eng._m_adoptions.labels("ok").value) == 3
    finally:
        r.close()


def test_proactive_seed_on_scale_up(params, mesh1):
    """Autoscale-up pushes the fleet's hottest advertised chains into
    the NEW replica's radix cache before any traffic lands on it —
    counted as kv_migration{proactive} and visible as a non-empty
    prefix cache on the fresh engine."""
    r = _tiered(params, mesh1, config=FleetConfig(
        restart_backoff_base_s=0.01, proactive_chains=4))
    try:
        h = r.submit(_prompt(32, 1), max_new_tokens=4)
        _drive(r)
        assert h.done()
        # the prefill replica advertises its cached chain on the next
        # probe; tick until the router has its digest
        deadline = time.monotonic() + 30
        while (not (r._ctls[0].digest or {}).get("top")
               and time.monotonic() < deadline):
            r.tick()
            time.sleep(0.01)
        assert (r._ctls[0].digest or {}).get("top")
        n0 = len(r._ctls)
        assert r._scale_up("prefill", r._clock())
        ctl = r._ctls[-1]
        assert len(r._ctls) == n0 + 1 and ctl.tier == "prefill"
        seeded = ctl.replica.engine._prefix_cache
        assert seeded is not None and len(seeded) > 0
        evs = r.recorder.recent(kind="kv_migration")
        pro = [e for e in evs if e.data.get("proactive")]
        assert pro and any(e.data["outcome"] == "ok" for e in pro)
        assert int(r._m_migrations_ok.value) >= 1
    finally:
        r.close()


def test_eviction_biased_away_from_advertised():
    """`RadixPrefixCache.evict` takes the LRU UNADVERTISED leaf
    first, even when an advertised leaf is older — and still takes
    the advertised one when nothing else remains (bias, not
    immunity)."""
    alloc = PageAllocator(num_pages=8, page_size=2)
    cache = RadixPrefixCache(page_size=2, allocator=alloc)
    for toks in ([1, 2], [3, 4]):     # [1,2] inserted first == LRU
        p = alloc.alloc()
        cache.insert(toks, [p])
        alloc.decref(p)               # the owning slot frees: the
        #                               cache is now sole owner
    # "old" is LRU; advertise it
    (old_h,) = [h for h, n in cache._by_hash.items()
                if list(n.key) == [1, 2]]
    assert cache.set_advertised([old_h]) == 1
    assert cache.evict(1) == 1
    assert old_h in cache._by_hash        # the advertised chain held
    assert len(cache) == 1
    assert cache.evict(1) == 1            # ...but it is not immune
    assert len(cache) == 0


def test_debugz_shows_handoff_mode(params, mesh1):
    """/debugz replica rows carry handoff_mode: wire for any replica
    that can export KV, fallback otherwise (ISSUE-17 satellite)."""
    r = _tiered(params, mesh1)
    try:
        rows = r.debugz()["replicas"]
        assert all(row["handoff_mode"] == "wire" for row in rows)
        r._ctls[0].replica.supports_handoff = False
        rows = r.debugz()["replicas"]
        modes = {row["replica"]: row["handoff_mode"] for row in rows}
        assert modes[0] == "fallback" and modes[1] == "wire"
    finally:
        r.close()


# ---------------------------------------------------------------------------
# the real process boundary (multiproc: subprocess replicas)
# ---------------------------------------------------------------------------

PAGED_SUB_SPEC = {
    "cfg": dict(vocab_size=32, d_model=32, n_heads=4, n_layers=2,
                max_len=64),
    "engine": dict(decode_chunk=2, max_new_tokens=12,
                   backoff_base_s=0.0, max_batch_size=2, paged=True),
    "params_seed": 0,
    "progress_interval_s": 0.01,
}


@pytest.fixture
def fleet_watchdog():
    replicas = []
    fired = threading.Event()

    def _fire():
        fired.set()
        for rep in replicas:
            try:
                rep.kill()
            except Exception:
                pass

    timer = threading.Timer(HARD_TIMEOUT_S, _fire)
    timer.daemon = True
    timer.start()
    try:
        yield replicas.append
    finally:
        timer.cancel()
        for rep in replicas:
            try:
                rep.close()
            except Exception:
                pass
    assert not fired.is_set(), \
        f"fleet watchdog fired after {HARD_TIMEOUT_S}s"


@pytest.mark.multiproc
def test_subprocess_2p1d_wire_handoff_zero_reprefills(
        params, mesh1, fleet_watchdog):
    """Acceptance: a 2-prefill + 1-decode tiered fleet of SUBPROCESS
    replicas completes a long-prompt trace with every handoff crossing
    the worker pipes as a kvwire frame (handoffs all ok, ZERO
    fallbacks/failures) and the decode worker ADOPTING every one
    (zero happy-path re-prefills) — token-exact vs an in-process
    engine with the same params seed. Chain export/seed and
    qos_control actuate over the same framing."""
    reps = [SubprocessReplica(i, PAGED_SUB_SPEC,
                              startup_timeout_s=HARD_TIMEOUT_S)
            for i in range(3)]
    for rep in reps:
        fleet_watchdog(rep)
    assert all(rep.wire_version == kvwire.WIRE_VERSION
               for rep in reps), "workers did not handshake kvwire"
    prompts = [_prompt(16 + 2 * i, i) for i in range(4)]
    want = _reference(params, mesh1, prompts, max_new=8)
    r = TieredRouter(cfg=CFG, replicas=reps,
                     tiers=["prefill", "prefill", "decode"],
                     config=FleetConfig(max_restarts=0,
                                        hang_min_s=30.0))
    hs = [r.submit(p, max_new_tokens=8) for p in prompts]
    deadline = time.monotonic() + HARD_TIMEOUT_S
    while r.pending() and time.monotonic() < deadline:
        r.tick()
    for h, w in zip(hs, want):
        assert h.done()
        np.testing.assert_array_equal(h.result(0), w)
    assert r.stats["handoffs_ok"] == 4
    assert r.stats["handoffs_failed"] == 0
    assert r.stats["handoffs_fallback"] == 0
    # zero happy-path re-prefills: the decode WORKER adopted all 4
    fed = r.federate()
    adopted = sum(
        row["value"] for row in fed["serving_kv_adoptions"]["samples"]
        if row["labels"].get("outcome") == "ok")
    assert adopted == 4
    # the wire accounting saw both directions of every handoff
    m = r._kvwire_metrics()
    assert int(m["frames"].labels("export", "ok").value) == 4
    assert int(m["frames"].labels("adopt", "ok").value) == 4
    assert int(m["bytes"].value) > 0
    # every request's trace carries the kvwire spans
    evs = [e for e in hs[0].trace.events if e.kind == "kvwire"]
    assert {e.data["direction"] for e in evs} == {"export", "adopt"}
    assert all(e.data["outcome"] == "ok" for e in evs)

    # cached-chain migration over the SAME framing: export the chain
    # a prefill worker cached, seed it into the decode worker
    deadline = time.monotonic() + 30
    src = None
    while src is None and time.monotonic() < deadline:
        for rep in reps[:2]:
            dg = rep.prefix_digest or {}
            if dg.get("top"):
                src = rep
                break
        time.sleep(0.05)
    assert src is not None, "no prefill worker advertised a chain"
    chain_hash = src.prefix_digest["top"][0][0]
    kv = src.export_cached_chain(chain_hash)
    assert kv is not None and kv.source == "cache"
    assert src.last_wire and src.last_wire["bytes"] > 0
    assert reps[2].seed_chain(kv) is True
    # a stale hash is None, not an error
    assert src.export_cached_chain(0xDEAD) is None

    # qos actuation over the pipe: one CONTROL frame; the worker
    # halves its decode chunk against its OWN base and acks async
    nbytes = reps[2].qos_control(spec_off=True, chunk_shrink=True)
    assert nbytes >= kvwire.HEADER_SIZE
    deadline = time.monotonic() + 30
    while reps[2].last_qos is None and time.monotonic() < deadline:
        time.sleep(0.05)
    assert reps[2].last_qos == {"spec_off": True, "decode_chunk": 1,
                                "base_decode_chunk": 2}
    r.close()
