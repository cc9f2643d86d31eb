"""Fault-tolerant inference engine over `parallel/serving.py`.

`InferenceEngine` turns the compiled sharded decode programs into a
service: callers `submit()` prompts and get a `RequestHandle`.

CONTINUOUS BATCHING (the default, ``mode="continuous"``, ISSUE-4):
requests live in a fixed pool of ``num_slots`` slots whose KV cache,
position, and pending token stay RESIDENT ON DEVICE across decode
chunks (parallel/serving.init_slot_state). Each scheduling round
(`tick()`): free slots are filled from the queue and prefilled in ONE
fixed-shape pad-tolerant program (mixed prompt lengths share it — the
bucket, not the exact length, keys the compiled-program cache), then
every occupied slot advances one decode chunk through ONE fixed-shape
program whose active/remaining-budget masks are runtime data. A slot
frees the moment its request completes or is shed, and the next tick
refills it — so a 4-token request admitted behind a 512-token one
finishes thousands of tokens earlier (no head-of-line blocking), a
request's prompt is prefilled exactly ONCE (no quadratic re-prefill),
and steady-state mixed traffic triggers zero XLA recompiles.

``mode="batch"`` keeps the PR-1 batch-to-completion path: a dynamic
batcher coalesces queued prompts of IDENTICAL length, re-stacks
prompt+generated, and re-invokes `make_parallel_generate` per chunk —
it is also the single-shot
(`decode_chunk=0`) lowest-overhead mode.

Failure semantics:
- A decode-step failure (XlaRuntimeError, injected `TrainingFailure`)
  is retried with exponential backoff up to `max_retries`. Decode is
  deterministic given (params, prompt, key) and the per-chunk key
  depends only on the decoded-position offset, so a retried request
  completes with byte-identical tokens to a no-fault run.
- When a batch (or the slot pool) exhausts its retries, the engine
  isolates: each in-flight request is re-run solo, continuing from its
  decoded prefix (continuous mode: evicted from its slot — counted as
  preempted — and re-run on a SCRATCH slot pool so surviving state is
  never clobbered). Requests that fail solo too are QUARANTINED — the
  per-request hard fault — without poisoning co-resident requests.
- Consecutive step failures trip a circuit breaker: admissions are
  rejected with `OverloadError` for `breaker_cooldown_s`, then a
  half-open probe admission closes it again on success.
- Load shedding: a full queue rejects admissions outright; past the
  soft watermark (`degrade_queue_depth`) the engine degrades by
  capping `max_new_tokens` at `degraded_max_new_tokens`.
- Requests past their deadline are shed (`DeadlineExceeded`) or — with
  `on_deadline="partial"` — complete early with the tokens decoded so
  far, instead of stalling the rest of the batch.

Weights hot-reload: `reload_weights()` restores a param tree from a
`CheckpointManager` directory using the live (sharded) params as the
placement template and swaps it in atomically. Batch mode: in-flight
batches finish on the weights they started with (no drain), later
batches use the new ones. Continuous mode: a slot's KV cache encodes
the weights that wrote it, so in-flight slots are PREEMPTED instead —
evicted and requeued at the queue front with their committed tokens
preserved; they re-prefill under the new weights and continue, and
newly admitted slots use the new weights immediately (tokens decoded
but not yet committed at the swap are discarded and re-decoded).
Corrupt/partial `step_<N>` directories fall back to the previous good
step.

Quantized inference (round 10, `quantize=` / `kv_quantize=` — engine
kwargs or EngineConfig fields, "int8"/"fp8"/None): the weight tree is
quantized ON LOAD (float weights never reach the mesh) and on every
hot reload (checkpoints stay float; restore goes through a float
template, then requantizes), and the continuous slot pool switches to
int8 rows + per-row scales (quant/kv.py). The compiled-program caches
key on the modes, `quantize=None` stays bit-identical to the
pre-quantization engine, and HBM accounting (`serving_param_bytes`,
`serving_kv_bytes_per_slot`, `serving_kv_pool_bytes`) surfaces as
pull gauges + health()/stats fields. See docs/quantization.md.

Observability: every counter the engine keeps (completed / shed /
quarantined / retries / step failures / batches / reloads), the
queue-depth / breaker-state / degraded gauges, and the per-step decode
+ per-batch latency histograms live in an
`observability.MetricsRegistry` — a private one by default (per-engine
counts stay exact), or inject a shared registry /
`observability.NULL_REGISTRY` via the `registry` kwarg. `stats` and
`health()` are read-through views over the same instruments, so the
dict surface is unchanged while `GET /metrics` (observability.export)
serves the identical numbers. Pull-model gauges (`set_function`) keep
the hot decode path free of scrape-time work.

Flight recorder + SLO layer (round 11, ISSUE-6): every request
carries a `RequestTrace` of typed lifecycle events
(``submit → queued → admitted{slot,bucket} → prefill_done →
decode_chunk{tokens}* → finished`` — plus ``retry``, ``preempted``,
``quarantined``, ``shed{reason}``) on `RequestHandle.trace`, recorded
into a bounded ring (`engine.recorder`,
observability/events.FlightRecorder) — so when ONE request is slow or
shed, its trace explains why, not just the aggregate counters. An
`SLOTracker` (`engine.slo`) derives TTFT / TPOT (inter-token) / e2e /
queue-age histograms and goodput from the traces in BOTH scheduling
modes, with a windowed `slo_report()`. Introspection surfaces:
`debugz()` (slot table + queue ages + breaker + recent events),
`slo_report()`, and `timeline()` (Chrome/Perfetto trace_event JSON,
one lane per slot plus the queue lane) — wire them into
`observability.MetricsServer(debug=..., slo=..., timeline=...)` for
`/debugz`, `/slo`, `/timeline.json`. Recording defaults ON with a
live registry and mirrors it off: `registry=NULL_REGISTRY` (or
`recorder=observability.NULL_RECORDER`) makes every trace call a
no-op.

Paged KV + radix prefix sharing (round 12, ISSUE-7,
`EngineConfig(paged=True, page_size=, kv_pages=, prefix_cache=)`):
continuous-mode slot storage becomes a fixed pool of page_size-token
pages behind host-owned per-slot block tables
(parallel/serving.py paged section; data=1 meshes). A radix/trie
prefix cache (serving/paging.py) maps the longest cached token-prefix
chain into each admission's block table — refcounted, copy-on-write
before any divergent write — so co-tenant traffic sharing a system
prompt shares the KV bytes AND the prefill compute (prefill resumes
from the matched boundary; `admitted` trace events carry
`prefix_hit_tokens`). Freed slots return pages to the free list;
unreferenced cache entries evict LRU; exhausted pools BLOCK admission
instead of corrupting residents; quarantine/preemption release only
the quarantined slot's references, never a sharer's pages; hot reload
flushes the cache (cached KV encodes the old weights). Both float and
int8 KV pools page identically (quant/kv.py per-row scales travel
with their page). The contiguous path stays the default and the
regression baseline. Observability: `serving_kv_pages_{free,used}`
gauges, `serving_prefix_cache_{hits,misses,evictions}_total` +
`serving_prefix_shared_tokens_total` counters, block tables +
prefix-cache stats in `debugz()`. See docs/serving.md "Paged KV &
prefix sharing".

Speculative decoding (round 13, ISSUE-8, `EngineConfig(spec_decode=,
spec_k=, draft=, spec_adaptive=)`; continuous mode, dense configs):
each decode chunk becomes a speculative ROUND — K draft-model steps
(int8-quantized tree by default, or the target itself / an early-exit
truncation) propose tokens per slot, ONE target pass verifies all K+1
window positions, and the longest accepted prefix + the target's
correction token commit. Position-keyed sampling makes verification
deterministic, so the speculative engine is TOKEN-EXACT vs the
non-speculative one at any temperature, float/int8 KV, contiguous or
paged (speculative writes are COW-privatized; rejected rows sit past
the committed position and are never attended). Per-slot acceptance
EMAs drive an adaptive K over a closed compiled-program set, with a
plain-decode fallback + re-probe so adversarial traffic converges to
plain throughput. `decode_chunk` trace events carry
`drafted=`/`accepted=`, `draft_rejected` marks all-rejected rounds,
and `serving_spec_*` metrics cover totals/ratio/current-K. The
`draft_poison_at` injector knob proves a poisoned draft pass cannot
corrupt committed KV. See docs/serving.md "Speculative decoding".

Chunked prefill + token-budget scheduler (round 15, ISSUE-10,
`EngineConfig(prefill_chunk=, tick_token_budget=)`; continuous mode):
one-shot admission prefill runs a whole prompt as a single fused call,
so a long prompt freezes every co-resident decoding slot for its full
prefill — a TPOT-p99 stall the SLO layer measures but nothing bounds.
With ``prefill_chunk`` set, admission merely SEATS the request (slot
state PREFILLING: pos < committed-prefix length, not yet sampling) and
the prompt advances through fixed-shape CHUNKED-prefill programs
(parallel/serving.make_chunked_prefill / make_paged_chunked_prefill —
resume position, valid length, and final-chunk flag are runtime data).
Each tick spends ``tick_token_budget`` tokens: the decode chunk for
every DECODING slot is billed first (decode never stalls), the
remainder buys prefill chunks oldest-admission-first (TTFT fairness —
the _fill_slots order assert), and a decode-saturated tick still
advances the oldest admission one chunk (progress floor). Chunked
prefill is TOKEN-EXACT vs one-shot (greedy and sampled, float and
int8 KV, contiguous and paged, prefix-hit resume included) and a slot
that dies or preempts MID-PREFILL resumes from its committed prefix
exactly like a mid-decode one: isolation re-runs it solo, reload
requeues it, deadline/cancel shed it, and a fleet failover re-prefills
it on a survivor. `prefill_chunk=None` (default) keeps the one-shot
path bit-identically with unchanged compiled-program cache keys.
Observability: `serving_prefill_chunks_total`,
`serving_tick_budget_utilization` (pull gauge), `prefill_chunk` fields
on `admitted`/`prefill_done`/`decode_chunk` trace events, a
`chunked_prefill` section in `debugz()`. See docs/serving.md "Chunked
prefill & the token-budget scheduler".

Raw speed (round 17, ISSUE-12): compiled-program resolution runs
through a three-level stack — the in-memory program cache (ONE
process-wide `EngineConfig.program_cache_size` bound for every
factory below, evictions published because an evicted geometry is a
guaranteed steady-state recompile), the persistent AOT compile cache
(`EngineConfig.compile_cache_dir` → serving/compile_cache.py:
compiled-executable bytes on disk, keyed by the same geometry tuples
plus a jax/jaxlib/backend salt, atomic publish + corrupt-entry
fallback), and finally `jit(...).lower(...).compile()`. `warmup()` /
`EngineConfig(warmup_on_init=True)` resolves the whole closed program
set up front, so a restarted or autoscaled replica with a warm cache
LOADS instead of recompiling. Independently,
`EngineConfig(pipeline=True)` double-buffers the continuous tick
loop: each tick's compiled calls are DISPATCHED without blocking and
the previous tick's outputs commit at one sync point, so host
scheduling/accounting work overlaps device compute (the schedule
runs one tick ahead on deterministic token COUNTS; token VALUES are
only ever observed after their sync — committed-prefix semantics,
deadline/cancel/isolation/reload, and KV export all keep their
contracts). `pipeline=False` (default) keeps this loop bit-identical
to the synchronous PR-11 one. See docs/serving.md "Engine internals
& raw speed".

Continuous profiling & cost attribution (round 20, ISSUE-15,
observability/profiling.py): `_resolve_program` captures every
compiled program's XLA cost analysis (FLOPs + bytes accessed) into a
per-engine cost table — jit compiles, in-memory hits, AND AOT-cache
loads (the analysis is persisted beside the cached executable, so a
cache-warm restart has a complete table with zero compiles; pre-meta
entries lazily recompute it from the loaded executable). The tick
loop attributes each tick's device-busy interval across the programs
dispatched in it (`serving_program_device_seconds_total{program}`,
`serving_program_flops_total{program}`), a live `serving_mfu` gauge
tracks achieved FLOP/s against the chip's peak, and each program gets
a roofline classification (arithmetic intensity vs the chip's ridge
point → compute- or memory-bound) in `profile_report()`/`debugz()`.
`submit(tenant=)` meters per-tenant analytic cost — tokens actually
computed (prefix-cache hits and migrated chains bill only the
recompute) x the per-token program cost — into
`serving_request_cost_{flops,bytes}_total{tenant}` under a top-N +
"other" label bound; per-request bills accumulate on
`handle.cost_flops` and ride the terminal trace event.
`EngineConfig(profile_dir=)` + `engine.profilez(seconds)` back the
`/profilez?seconds=N` on-demand jax.profiler capture (single-flight,
503 when unsupported). `profiler=observability.NULL_PROFILER`
disables it all by injection. See docs/observability.md
"Profiling & cost attribution".

Every behavior is deterministically testable on the CPU backend via
`parallel.failure.ServingFaultInjector` — see
tests/test_serving_engine.py and docs/serving.md.
"""
from __future__ import annotations

import itertools
import logging
import threading
import time
import weakref
from collections import OrderedDict, deque, namedtuple
from dataclasses import dataclass, astuple
from typing import Callable, Dict, Iterable, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.observability.events import (FlightRecorder,
                                                     NULL_RECORDER,
                                                     NULL_TRACE)
from deeplearning4j_tpu.observability.metrics import (
    DECODE_LATENCY_BUCKETS, MetricsRegistry, NullRegistry)
from deeplearning4j_tpu.observability.profiling import (
    EngineProfiler, NULL_PROFILER, ProfileCapture, cost_from_compiled)
from deeplearning4j_tpu.observability.slo import NULL_SLO, SLOTracker
from deeplearning4j_tpu.observability.tracing import (annotate,
                                                      default_spans, mark,
                                                      span)
from deeplearning4j_tpu.parallel.serving import (
    init_paged_state, init_slot_state, make_chunked_prefill,
    make_continuous_decode, make_continuous_prefill,
    make_paged_chunked_prefill, make_paged_decode, make_paged_prefill,
    make_paged_speculative_decode, make_parallel_generate,
    make_speculative_decode, shard_serving_params)
from deeplearning4j_tpu.serving.paging import (PageAllocator,
                                               RadixPrefixCache,
                                               pages_for)
from deeplearning4j_tpu.util.checkpointing import CheckpointManager

log = logging.getLogger("deeplearning4j_tpu")

_perf = time.perf_counter

_BREAKER_STATE = {"closed": 0.0, "half-open": 1.0, "open": 2.0}


class OverloadError(RuntimeError):
    """Admission rejected: queue full or circuit breaker open."""


class EngineStopped(RuntimeError):
    """Admission rejected: the engine has been stopped. Raised by
    `submit()` IMMEDIATELY (ISSUE-9 satellite) — a request enqueued
    after `stop()` would sit on the bounded queue forever with nothing
    left to drain it, so the caller hangs in `result()` instead of
    learning the engine is gone."""


class EngineDraining(RuntimeError):
    """Admission rejected: the engine is draining. `drain()` closes
    admissions the moment it is called (readiness flips not-ready at
    the same instant) while resident requests finish; `resume()`
    reopens them — the rolling-weight-reload dance."""


class DeadlineExceeded(RuntimeError):
    """Request shed because its deadline passed before completion."""


class RequestCancelled(RuntimeError):
    """Request cancelled by the caller via `engine.cancel()` — e.g. a
    hedged fleet dispatch whose twin finished first (serving/fleet.py
    first-winner-cancels)."""


class RequestQuarantined(RuntimeError):
    """Request failed persistently (solo, after max retries) and was
    quarantined so it cannot poison further batches."""


class HandoffError(RuntimeError):
    """Cross-tier KV handoff failed (ISSUE-11): exporting a held
    slot's committed KV, or adopting a handed-off page chain at
    seating. A request shed on the adoption path carries this error
    and the typed ``shed{reason="handoff"}`` trace event, and every
    page the adoption claimed is decref'd first."""


class QoSValidationError(ValueError):
    """submit() rejected a malformed tenant or priority (ISSUE-16):
    tenant ids flow into metric labels and the Prometheus exposition
    (per-tenant cost counters, QoS series), so a non-string /
    oversized / control-character id is rejected HERE — typed, at
    admission — instead of corrupting the scrape; priorities outside
    [0, MAX_PRIORITY] or of non-int type are rejected the same way."""


#: Priority classes are the closed set 0..MAX_PRIORITY (ISSUE-16):
#: 0 = default/batch, higher preempts lower when the engine's
#: ``preemption_budget`` allows it and dispatches first at the router.
MAX_PRIORITY = 9
#: Tenant ids are metric-label material: bound their length so a
#: hostile id cannot bloat every labeled sample it lands in.
MAX_TENANT_LEN = 64


def validate_tenant_priority(tenant, priority):
    """The ONE tenant/priority validation (ISSUE-16), shared by
    `InferenceEngine.submit` and `Router.submit`: coerce-or-reject
    BEFORE the values reach the metric-label path. Returns the
    normalized ``(tenant, priority)`` pair; raises
    `QoSValidationError` on anything else.

    Coercions: int tenant ids (a common caller convenience) become
    their decimal string; everything non-str is otherwise rejected —
    a bytes/float/object id silently str()'d would mint unbounded
    label variants for what the caller thinks is one tenant."""
    if tenant is not None:
        if isinstance(tenant, int) and not isinstance(tenant, bool):
            tenant = str(tenant)
        if not isinstance(tenant, str):
            raise QoSValidationError(
                f"tenant must be a str (or int), got "
                f"{type(tenant).__name__}")
        if not tenant or len(tenant) > MAX_TENANT_LEN:
            raise QoSValidationError(
                f"tenant id length must be 1..{MAX_TENANT_LEN}, got "
                f"{len(tenant)}")
        if any(ch in '"\\\n' or ord(ch) < 0x20 for ch in tenant):
            raise QoSValidationError(
                "tenant id contains control/exposition-breaking "
                "characters (newline, quote, backslash)")
    if isinstance(priority, bool) or not isinstance(priority, int):
        raise QoSValidationError(
            f"priority must be an int, got "
            f"{type(priority).__name__}")
    if not 0 <= priority <= MAX_PRIORITY:
        raise QoSValidationError(
            f"priority must be in [0, {MAX_PRIORITY}], got {priority}")
    return tenant, priority


@dataclass
class KVHandoff:
    """One request's committed KV state, portable across engines
    (ISSUE-11): the host-gathered K/V rows for positions [0, pos), the
    pending token (committed but not yet fed — its row is written by
    the FIRST decode step on the adopting side), and — for quantized
    pools — the per-row scales, which travel with their rows exactly
    as they travel with their page through share/COW remaps
    (quant/kv.py). Bit-preserving by construction: values are sliced,
    never re-quantized, so a float OR int8 decode continuation on the
    adopting engine is token-exact vs an uninterrupted single-engine
    run.

    ISSUE-14 adds the CACHE-CHAIN source: ``source="cache"`` carries a
    radix-prefix-cache chain (full pages only) instead of a live
    slot's committed state — ``tokens`` holds the chain's token ids
    (adoption must know WHAT text the rows encode to seed the target's
    radix cache) and ``weights_step`` the exporter's weights version
    (rows encode the weights that wrote them; a target on different
    weights must refuse the seed and fall back to prefilling)."""
    pos: int                 # K/V rows [0, pos) are committed
    tok: int                 # pending token == last committed token
    k: "np.ndarray"          # [L, pos, D] at the pool dtype
    v: "np.ndarray"
    k_scale: Optional["np.ndarray"] = None   # [L, pos, tp] f32
    v_scale: Optional["np.ndarray"] = None
    kv_mode: Optional[str] = None
    n_layers: int = 0
    d_model: int = 0
    source: str = "slot"     # "slot" (ISSUE-11) | "cache" (ISSUE-14)
    tokens: Optional["np.ndarray"] = None    # cache source: chain ids
    weights_step: Optional[int] = None

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in
                   (self.k, self.v, self.k_scale, self.v_scale)
                   if a is not None)


class RequestStatus:
    QUEUED = "queued"
    RUNNING = "running"
    COMPLETED = "completed"
    SHED = "shed"
    QUARANTINED = "quarantined"


DEFAULT_CONTINUOUS_CHUNK = 8

# the ONE in-memory compiled-program cache bound (ISSUE-12 satellite:
# the factories below used to mix lru maxsizes of 8 and 64);
# EngineConfig.program_cache_size / set_program_cache_size resize it
DEFAULT_PROGRAM_CACHE_SIZE = 64


@dataclass
class EngineConfig:
    """Queueing / batching / fault-handling policy knobs.

    ``mode="continuous"`` (default) runs the slotted continuous-
    batching scheduler: ``max_batch_size`` sizes the slot pool (unless
    ``num_slots`` overrides it; both are rounded up to a 'data'-axis
    multiple), ``decode_chunk`` is the tokens-per-chunk scheduling
    quantum (0 falls back to DEFAULT_CONTINUOUS_CHUNK — continuous
    mode always chunks: chunk boundaries are where slots are freed and
    admitted). ``mode="batch"`` keeps the PR-1 batch-to-completion
    batcher, where ``decode_chunk=0`` decodes each batch's full token
    budget in ONE compiled call (lowest overhead — the benchmark mode)
    and ``decode_chunk=N`` re-prefills the grown prompt every N
    tokens."""
    max_queue: int = 64              # hard admission bound
    max_batch_size: int = 8          # slot-pool size / coalescing cap
    batch_timeout_s: float = 0.005   # worker coalescing window
    max_new_tokens: int = 32         # engine default AND per-request cap
    decode_chunk: int = 0            # 0 = single-shot (batch mode) /
    #                                  DEFAULT_CONTINUOUS_CHUNK (cont.)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    max_retries: int = 3             # per decode step (batch, then solo)
    backoff_base_s: float = 0.01     # exponential: base * 2^(attempt-1)
    backoff_max_s: float = 1.0
    breaker_failure_threshold: int = 5   # consecutive step failures
    breaker_cooldown_s: float = 5.0
    degrade_queue_depth: int = 48    # soft watermark -> degraded mode
    degraded_max_new_tokens: int = 8
    seed: int = 0                    # sampling key root
    mode: str = "continuous"         # "continuous" | "batch"
    num_slots: int = 0               # 0 = max_batch_size
    prefill_bucket_min: int = 16     # smallest prefill-length bucket
    # quantized inference (quant/): "int8" | "fp8" | None. ``quantize``
    # quantizes the WEIGHT tree on load (and on every hot reload);
    # ``kv_quantize`` switches the continuous slot pool to int8/fp8
    # rows + per-row scales (~4x fewer cache bytes per slot). Both go
    # through quant.core.resolve_mode, so "fp8" lands on int8 off-TPU.
    quantize: Optional[str] = None
    kv_quantize: Optional[str] = None
    # paged slot KV cache + radix prefix sharing (ISSUE-7, continuous
    # mode only, data=1 mesh). ``paged`` switches slot storage from
    # per-slot contiguous [S] rows to a fixed pool of ``page_size``-
    # token pages behind per-slot block tables; ``kv_pages`` sizes the
    # pool (0 = full provisioning: num_slots * ceil(max_len/page_size)
    # + 1 scratch — set it LOWER to realize the capacity win, the
    # free list + prefix-cache LRU eviction absorb the pressure and
    # admission blocks, never corrupts, when truly out).
    # ``prefix_cache`` adds the radix prefix cache: admissions sharing
    # a cached token prefix map the shared pages into their block
    # table and prefill resumes from the matched boundary.
    paged: bool = False
    page_size: int = 16
    kv_pages: int = 0                # 0 = full provisioning
    prefix_cache: bool = True        # only meaningful with paged=True
    # speculative decoding (ISSUE-8, continuous mode, dense configs).
    # ``spec_decode`` replaces each slot's decode chunk with a
    # speculative ROUND: K draft-model steps propose tokens, ONE
    # target pass verifies all K+1 window positions and commits the
    # longest accepted prefix + the correction token — token-EXACT vs
    # the non-speculative engine at any temperature (position-keyed
    # sampling makes verification deterministic; docs/serving.md).
    # ``spec_k`` is the max draft length; the adaptive controller
    # walks K over {spec_k, spec_k/2, ..., 1} (a closed set riding the
    # compiled-program caches) from the pool's acceptance EMA, and
    # falls back to PLAIN decode for a cooldown when even K=1 doesn't
    # pay — adversarial traffic never underperforms plain decode by
    # more than the probe overhead. ``draft`` picks the drafter:
    # "int8" (default: the int8-quantized weight tree — free when the
    # engine is already weight-quantized), "self" (the target tree —
    # 100% acceptance, the exactness/bench baseline), or "layers:N"
    # (early-exit through the first N blocks — cheapest draft FLOPs).
    spec_decode: bool = False
    spec_k: int = 4
    draft: str = "int8"
    spec_adaptive: bool = True       # False pins K at spec_k
    # chunked prefill + token-budget scheduler (ISSUE-10, continuous
    # mode). ``prefill_chunk`` splits every admission's prompt into
    # fixed-size token chunks interleaved with decode: a seated slot
    # enters the PREFILLING state and advances up to ``prefill_chunk``
    # prompt tokens per scheduled chunk, so one long prompt can no
    # longer freeze co-resident decoding slots for its whole prefill
    # (the TPOT-p99 stall). Each tick spends ``tick_token_budget``
    # tokens: the decode chunk for every DECODING slot is budgeted
    # first (decode never stalls), and the remainder buys prefill
    # chunks oldest-admission-first (TTFT fairness) — partial chunks
    # spend the budget to the token. A tick whose decode work exhausts
    # the budget still advances the oldest PREFILLING slot one chunk
    # (progress floor: admissions can never starve). 0 auto-sizes the
    # budget to num_slots * decode_chunk + prefill_chunk — every
    # resident decodes AND one prefill chunk lands per tick.
    # ``prefill_chunk=None`` (default) keeps the legacy one-shot
    # admission prefill, bit-identically, with unchanged compiled-
    # program cache keys.
    prefill_chunk: Optional[int] = None
    tick_token_budget: int = 0       # 0 = auto (see above)
    # raw-speed subsystem (ISSUE-12). ``program_cache_size`` is the
    # ONE bound on the process-wide in-memory compiled-program caches
    # (the old per-factory lru maxsizes mixed 8 and 64); evictions
    # publish to serving_program_cache_evictions_total because an
    # evicted geometry is a guaranteed steady-state recompile.
    # ``compile_cache_dir`` enables the persistent AOT compile cache
    # (serving/compile_cache.py): every continuous-mode program this
    # engine compiles is serialized (compiled-executable bytes, not
    # StableHLO) into the directory, and the next engine over the same
    # geometry — a restarted replica, an autoscaled one — LOADS it
    # instead of recompiling (serving_compiles_total{source=
    # "aot_cache"}). ``warmup_on_init`` runs `warmup()` inside
    # __init__ so the constructor returns a ready engine: the whole
    # closed program set resolved (from the AOT cache when warm).
    # ``pipeline`` switches the continuous tick loop to the
    # double-buffered schedule: compiled calls are DISPATCHED without
    # blocking and their outputs committed at the NEXT tick's single
    # sync point, so host-side scheduling/accounting overlaps device
    # compute (decode/prefill token COUNTS are deterministic, so the
    # schedule runs one tick ahead of the committed values — token
    # values are never observed before their sync). True (the default
    # since ISSUE-14: tests/test_serving_pipeline.py holds the loop
    # token-exact with every failure semantic preserved) pipelines
    # every continuous engine; spec_decode (acceptance makes commit
    # counts nondeterministic) and mode="batch" AUTO-FALL-BACK to the
    # synchronous loop with a warning — bit-identically, never a
    # constructor rejection. pipeline=False pins the synchronous
    # PR-11 loop.
    program_cache_size: int = DEFAULT_PROGRAM_CACHE_SIZE
    compile_cache_dir: Optional[str] = None
    warmup_on_init: bool = False
    pipeline: bool = True
    # flight-recorder ring depth (ISSUE-13 satellite): the engine's
    # FlightRecorder keeps the last N lifecycle events. The default
    # matches the old hardcoded ring; fleet-level trace stitching on
    # long soaks needs DEEPER rings (the router reads replica rings
    # for its fleet timeline), so the bound is finally a config knob.
    # Ignored when an explicit recorder= is injected.
    recorder_capacity: int = 4096
    # continuous profiling & cost attribution (ISSUE-15).
    # ``profile_dir`` enables the on-demand `/profilez?seconds=N`
    # jax.profiler capture into that directory (None = the endpoint
    # answers 503 unsupported). ``tenant_top_n`` bounds the tenant
    # label cardinality of the per-tenant cost counters: the first N
    # distinct tenants get their own label, later ones fold into
    # "other" — a hostile tenant-id stream cannot explode the scrape.
    # The profiler itself (per-program cost table, device-time
    # attribution, serving_mfu, rooflines) defaults ON with a live
    # registry and OFF with NULL_REGISTRY, exactly like the flight
    # recorder; inject profiler=observability.NULL_PROFILER for the
    # profiling-disabled arm.
    profile_dir: Optional[str] = None
    tenant_top_n: int = 8
    # tenant QoS control plane (ISSUE-16). ``tenant_weights`` turns on
    # weighted fair-share prefill scheduling (requires prefill_chunk —
    # the token-budget scheduler is the thing being divided): each
    # tick's prefill budget is split across BACKLOGGED tenants by
    # weight via a deficit counter, so an idle tenant's share rolls to
    # others within the tick but a backlogged tenant accumulates
    # credit and can never be starved. Tenants absent from the map get
    # ``qos_default_weight``. None (default) keeps the round-15
    # oldest-admission-first order bit-identically.
    # ``preemption_budget`` > 0 enables priority preemption: a queued
    # higher-priority request with no free slot evicts the
    # lowest-priority resident through the preempt/requeue/committed-
    # prefix path (token-exact resume, same machinery as failover),
    # at most ``preemption_budget`` evictions per tick so a priority
    # storm cannot thrash the slot pool. 0 (default) disables
    # preemption AND priority-ordered seating — scheduling stays
    # bit-identical to the QoS-off engine.
    tenant_weights: Optional[Dict[str, float]] = None
    qos_default_weight: float = 1.0
    preemption_budget: int = 0
    # ``constrain_state_cap`` bounds the per-engine constraint table:
    # the dense [cap, V] allow/transition planes shipped to the device
    # are a fixed shape (so grammars are pure runtime data — swapping
    # one never recompiles), and every resident grammar's DFA must fit
    # inside cap-1 rows (row 0 is the unconstrained all-allow state).
    # A submit() whose compiled grammar exceeds the free rows is
    # rejected with ConstraintError(reason="oversize"); the documented
    # device-memory bound is cap * vocab_size * 5 bytes (bool allow +
    # int32 trans). 512 states x 32k vocab ~ 80 MB.
    constrain_state_cap: int = 512


class RequestHandle:
    """Caller-facing future for one submitted prompt."""

    def __init__(self, rid: int, prompt: np.ndarray, max_new: int,
                 deadline_at: Optional[float], on_deadline: str):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new
        self.deadline_at = deadline_at
        self.on_deadline = on_deadline
        self.status = RequestStatus.QUEUED
        self.error: Optional[BaseException] = None
        self.deadline_exceeded = False
        # per-tenant cost metering (ISSUE-15): the tenant label this
        # request bills under, and its accumulated analytic bill —
        # sum(handle.cost_flops) over a run equals the
        # serving_request_cost_flops_total counters by construction
        self.tenant: Optional[str] = None
        # QoS priority class (ISSUE-16): 0 = default/batch; higher
        # seats first and may preempt lower when the engine's
        # preemption budget allows it
        self.priority = 0
        self.cost_flops = 0.0
        self.cost_bytes = 0.0
        self._cancelled = False
        self._hold_kv = False            # keep slot seated when done
        self._kv = None                  # KVHandoff to adopt at seat
        self._handoff_failed = False     # shed reason "handoff"
        self._generated: List[np.ndarray] = []
        self._done = threading.Event()
        self._in_flight = False          # continuous-mode accounting
        # tokens dispatched-but-uncommitted in the double-buffered
        # tick pipeline (ISSUE-12): the scheduler's one-tick-ahead
        # view; always 0 on synchronous engines
        self._pending_n = 0
        # leading positions whose K/V this engine has computed before
        # (prefill or decode): what a later prefill covers again is
        # counted as `reprefill_tokens`
        self._kv_seen = 0
        # flight recorder (ISSUE-6): the engine swaps in a live
        # RequestTrace at submit; NULL_TRACE keeps direct
        # constructions (and disabled recording) zero-cost
        self.trace = NULL_TRACE
        self._on_terminal: Optional[Callable] = None
        # grammar-constrained decoding (ISSUE-20): the compiled
        # grammar, its base row in the engine's device table, the
        # normalized spec dict (forwarded across fleet hops), how many
        # prompt-tail tokens the grammar has already consumed, and the
        # HOST-authoritative DFA state after every committed token —
        # device states are scratch that reseeds from this on every
        # (re)seat, which is what makes failover/preemption resume
        # token-exact for free
        self._grammar = None
        self._cbase = 0
        self._constrain: Optional[dict] = None
        self._consumed = 0
        self._cinit = 0        # local state after the consumed tail
        self._cstate_host = 0

    @property
    def generated(self) -> np.ndarray:
        """Tokens decoded so far (may be partial)."""
        if not self._generated:
            return np.zeros((0,), np.int32)
        return np.concatenate(self._generated)

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Full sequence [T0 + generated] (mirrors `generate`'s layout).
        Raises the terminal error for shed/quarantined requests."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} not done")
        if self.error is not None:
            raise self.error
        return np.concatenate([self.prompt, self.generated])

    # -- engine-side terminal transitions ------------------------------
    def _finish(self, status: str,
                error: Optional[BaseException] = None) -> None:
        self.status = status
        self.error = error
        # the ONE terminal transition point: record the terminal trace
        # event + SLO accounting BEFORE waking result() waiters, so a
        # caller observing done() always sees a complete trace
        cb = self._on_terminal
        if cb is not None:
            try:
                cb(self)
            except Exception:    # observability must not kill serving
                log.exception("terminal trace hook failed")
        self._done.set()


class _BatchDecodeFailed(RuntimeError):
    """Internal: a batch exhausted its retries (carries the last
    underlying error); triggers the solo-isolation path."""


@dataclass
class _PendingTick:
    """One dispatched-but-uncommitted scheduling round of the
    double-buffered tick loop (ISSUE-12): the ordered commit items
    (("prefill", entries, first_dev) / ("prefill_chunk", plan,
    first_dev, finished) / ("decode", entries, toks_dev, needs,
    data)), the device slot-state snapshot taken BEFORE the tick's
    first dispatch (the recovery point for sync-time failures), and
    the active count for the tick-epilogue metrics."""
    items: list
    in_state: Optional[tuple]
    n_active: int
    # constrained engines: (device cstate snapshot, dict of pending
    # per-slot seeds) captured BEFORE dispatch — restoring both is
    # what makes a failed pipelined tick invisible to the DFA walk
    c_in_state: Optional[tuple] = None
    # index of the round that dispatched it (`engine.tick`'s `tick`)
    tick: int = 0


# ---------------------------------------------------------------------------
# the in-memory compiled-program cache (ISSUE-12 satellite)
# ---------------------------------------------------------------------------
_PROGRAM_CACHE_SIZE = [DEFAULT_PROGRAM_CACHE_SIZE]
_CacheInfo = namedtuple("CacheInfo", ["hits", "misses", "maxsize",
                                      "currsize"])
# counters (one per live engine registry) notified on every eviction:
# a silently-evicted program is a silent steady-state recompile, so
# evictions are a first-class series, not a cache implementation detail
_EVICTION_COUNTERS: "weakref.WeakSet" = weakref.WeakSet()


def _notify_evictions(n: int) -> None:
    for c in list(_EVICTION_COUNTERS):
        try:
            c.inc(n)
        except Exception:        # observability must not kill serving
            pass


class _ProgramLRU:
    """`functools.lru_cache` twin for the compiled-program factories,
    with the three properties lru_cache cannot give us (ISSUE-12
    satellite): ONE process-wide maxsize for every factory (the old
    code mixed 8 and 64 — `EngineConfig.program_cache_size` /
    `set_program_cache_size` now govern them all), evictions published
    to `serving_program_cache_evictions_total`, and a per-entry side
    table (`entry()`) carrying the AOT-resolved executable through the
    SAME lifecycle as its jit factory result — an eviction drops both,
    so the eviction counter really does mean "this geometry will
    recompile". `cache_info()`/`cache_clear()` keep the lru_cache
    surface tests and benches already consume
    (tests/helpers.assert_no_recompiles)."""

    _instances: List["_ProgramLRU"] = []

    def __init__(self, fn):
        self.__wrapped__ = fn
        self.__name__ = getattr(fn, "__name__", repr(fn))
        self.__doc__ = fn.__doc__
        self._od: "OrderedDict" = OrderedDict()
        self._hits = 0
        self._misses = 0
        self._lock = threading.RLock()
        _ProgramLRU._instances.append(self)

    @staticmethod
    def _key(args, kw):
        return (args, tuple(sorted(kw.items())))

    def __call__(self, *args, **kw):
        k = self._key(args, kw)
        with self._lock:
            ent = self._od.get(k)
            if ent is not None:
                self._od.move_to_end(k)
                self._hits += 1
                return ent[0]
            self._misses += 1
        # build OUTSIDE the lock: factory bodies trace jax programs
        val = self.__wrapped__(*args, **kw)
        with self._lock:
            if k not in self._od:
                self._od[k] = [val, {}]
                self._evict_overflow_locked()
            else:
                self._od.move_to_end(k)
            return self._od[k][0]

    def entry(self, *args, **kw) -> dict:
        """The per-program side table (AOT executables). Created with
        the cache entry and dropped with it at eviction."""
        self(*args, **kw)
        k = self._key(args, kw)
        with self._lock:
            ent = self._od.get(k)
            return ent[1] if ent is not None else {}

    def _evict_overflow_locked(self) -> None:
        n = 0
        while len(self._od) > max(1, _PROGRAM_CACHE_SIZE[0]):
            self._od.popitem(last=False)
            n += 1
        if n:
            _notify_evictions(n)

    def cache_info(self) -> _CacheInfo:
        with self._lock:
            return _CacheInfo(self._hits, self._misses,
                              _PROGRAM_CACHE_SIZE[0], len(self._od))

    def cache_clear(self) -> None:
        with self._lock:
            self._od.clear()
            self._hits = 0
            self._misses = 0


def _program_cache(fn) -> _ProgramLRU:
    return _ProgramLRU(fn)


def set_program_cache_size(n: int) -> int:
    """Resize the process-wide compiled-program caches (all factories
    share one bound — `EngineConfig.program_cache_size` routes here).
    Shrinking evicts LRU entries immediately (counted)."""
    n = int(n)
    if n < 1:
        raise ValueError(f"program_cache_size must be >= 1, got {n}")
    _PROGRAM_CACHE_SIZE[0] = n
    for c in _ProgramLRU._instances:
        with c._lock:
            c._evict_overflow_locked()
    return n


def resolved_executables() -> Dict[str, list]:
    """{factory name: compiled executables} for every program this
    process has resolved so far — what `chip_smoke.py` reads the
    compiled HLO of (collectives on a sharded mesh)."""
    out: Dict[str, list] = {}
    for c in _ProgramLRU._instances:
        with c._lock:
            exes = [side["exec"] for _, side in c._od.values()
                    if side.get("exec") is not None]
        if exes:
            out[c.__name__] = exes
    return out


@_program_cache
def _compiled_generate(cfg_fields: tuple, mesh, max_new_tokens: int,
                       temperature: float, top_k: int, top_p: float,
                       quantized=None):
    """Process-wide compiled-pgen cache: engines over the same
    (config, mesh, sampling) share the jit cache instead of re-tracing
    per engine instance (fault-injection tests build many engines)."""
    cfg = TransformerConfig(*cfg_fields)
    return make_parallel_generate(cfg, mesh, max_new_tokens,
                                  temperature=temperature, top_k=top_k,
                                  top_p=top_p, quantized=quantized)


@_program_cache
def _compiled_prefill(cfg_fields: tuple, mesh, bucket_len: int,
                      num_slots: int, temperature: float, top_k: int,
                      top_p: float, quantized=None, kv_mode=None):
    """Compiled-program cache for the continuous-batching admission
    prefill, keyed on BUCKET geometry (bucket_len, num_slots) rather
    than exact prompt length: all traffic whose prompts round up to
    the same bucket shares one entry — the no-recompile guard test
    counts this cache's entries before/after mixed-length traffic.
    The quantization modes ride in the key: a quantized engine's
    programs are distinct geometry."""
    cfg = TransformerConfig(*cfg_fields)
    return make_continuous_prefill(cfg, mesh, bucket_len, num_slots,
                                   temperature=temperature,
                                   top_k=top_k, top_p=top_p,
                                   quantized=quantized,
                                   kv_mode=kv_mode)


@_program_cache
def _compiled_decode_chunk(cfg_fields: tuple, mesh, chunk: int,
                           num_slots: int, temperature: float,
                           top_k: int, top_p: float, quantized=None,
                           kv_mode=None):
    """Compiled-program cache for the continuous-batching decode
    chunk: ONE entry per engine geometry — occupancy, per-slot
    positions, and budgets are runtime data, not shapes."""
    cfg = TransformerConfig(*cfg_fields)
    return make_continuous_decode(cfg, mesh, chunk, num_slots,
                                  temperature=temperature,
                                  top_k=top_k, top_p=top_p,
                                  quantized=quantized,
                                  kv_mode=kv_mode)


@_program_cache
def _compiled_chunked_prefill(cfg_fields: tuple, mesh, chunk_len: int,
                              num_slots: int, temperature: float,
                              top_k: int, top_p: float, quantized=None,
                              kv_mode=None):
    """Compiled-program cache for the CHUNKED admission prefill
    (ISSUE-10): ONE entry per (prefill_chunk, num_slots) geometry —
    resume positions, partial-chunk budgets, and final-chunk flags are
    runtime data, so a whole mixed-length trace prefills through a
    single program. Registered separately from _compiled_prefill so
    prefill_chunk=None engines keep the PR-4/7/8 cache keys
    byte-unchanged."""
    cfg = TransformerConfig(*cfg_fields)
    return make_chunked_prefill(cfg, mesh, chunk_len, num_slots,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, quantized=quantized,
                                kv_mode=kv_mode)


@_program_cache
def _compiled_paged_chunked_prefill(cfg_fields: tuple, mesh,
                                    chunk_len: int, num_slots: int,
                                    page_size: int, max_pages: int,
                                    num_pages: int, temperature: float,
                                    top_k: int, top_p: float,
                                    quantized=None, kv_mode=None):
    """Paged twin of _compiled_chunked_prefill (block tables and
    chunk boundaries are runtime data)."""
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_chunked_prefill(
        cfg, mesh, chunk_len, num_slots, page_size, max_pages,
        num_pages, temperature=temperature, top_k=top_k, top_p=top_p,
        quantized=quantized, kv_mode=kv_mode)


@_program_cache
def _compiled_paged_prefill(cfg_fields: tuple, mesh, bucket_len: int,
                            num_slots: int, page_size: int,
                            max_pages: int, num_pages: int,
                            temperature: float, top_k: int,
                            top_p: float, quantized=None,
                            kv_mode=None):
    """Compiled-program cache for the PAGED admission prefill, keyed
    on the SUFFIX bucket plus the (static) page-pool geometry: block
    tables, hit boundaries, and admission patterns are runtime data,
    so steady-state traffic — hits and misses alike — stays inside a
    closed set of entries (the paged no-recompile guard counts this
    cache)."""
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_prefill(cfg, mesh, bucket_len, num_slots,
                              page_size, max_pages, num_pages,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, quantized=quantized,
                              kv_mode=kv_mode)


@_program_cache
def _compiled_paged_decode(cfg_fields: tuple, mesh, chunk: int,
                           num_slots: int, page_size: int,
                           max_pages: int, num_pages: int,
                           temperature: float, top_k: int,
                           top_p: float, quantized=None, kv_mode=None):
    """ONE paged decode program per engine geometry — occupancy,
    budgets, and the whole block table are runtime data."""
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_decode(cfg, mesh, chunk, num_slots, page_size,
                             max_pages, num_pages,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, quantized=quantized,
                             kv_mode=kv_mode)


@_program_cache
def _compiled_spec_decode(cfg_fields: tuple, mesh, spec_k: int,
                          num_slots: int, temperature: float,
                          top_k: int, top_p: float, quantized=None,
                          kv_mode=None, draft_quantized=None,
                          draft_layers: int = 0):
    """Compiled-program cache for the speculative round: one entry per
    (K, num_slots, quant modes, drafter shape). The adaptive
    controller only ever visits K in {spec_k, spec_k/2, .., 1}, so
    steady-state acceptance variance walks a CLOSED set of entries —
    never a recompile."""
    cfg = TransformerConfig(*cfg_fields)
    return make_speculative_decode(cfg, mesh, spec_k, num_slots,
                                   temperature=temperature,
                                   top_k=top_k, top_p=top_p,
                                   quantized=quantized,
                                   kv_mode=kv_mode,
                                   draft_quantized=draft_quantized,
                                   draft_layers=draft_layers)


@_program_cache
def _compiled_paged_spec_decode(cfg_fields: tuple, mesh, spec_k: int,
                                num_slots: int, page_size: int,
                                max_pages: int, num_pages: int,
                                temperature: float, top_k: int,
                                top_p: float, quantized=None,
                                kv_mode=None, draft_quantized=None,
                                draft_layers: int = 0):
    """Paged twin of _compiled_spec_decode (block tables, acceptance,
    and poison masks are all runtime data)."""
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_speculative_decode(
        cfg, mesh, spec_k, num_slots, page_size, max_pages, num_pages,
        temperature=temperature, top_k=top_k, top_p=top_p,
        quantized=quantized, kv_mode=kv_mode,
        draft_quantized=draft_quantized, draft_layers=draft_layers)


# --- constrained (grammar-masked) program factories -------------------
# Registered SEPARATELY from their unmasked twins so constrain=None
# engines keep their compile-cache keys byte-unchanged (the ISSUE-20
# bit-identity guarantee counts these caches staying empty). Mask
# tables, per-slot DFA states, and seed vectors are runtime operands —
# every grammar shares one compiled program per geometry.

@_program_cache
def _compiled_prefill_c(cfg_fields: tuple, mesh, bucket_len: int,
                        num_slots: int, temperature: float, top_k: int,
                        top_p: float, quantized=None, kv_mode=None,
                        constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_continuous_prefill(cfg, mesh, bucket_len, num_slots,
                                   temperature=temperature,
                                   top_k=top_k, top_p=top_p,
                                   quantized=quantized,
                                   kv_mode=kv_mode, constrain=True)


@_program_cache
def _compiled_decode_chunk_c(cfg_fields: tuple, mesh, chunk: int,
                             num_slots: int, temperature: float,
                             top_k: int, top_p: float, quantized=None,
                             kv_mode=None, constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_continuous_decode(cfg, mesh, chunk, num_slots,
                                  temperature=temperature,
                                  top_k=top_k, top_p=top_p,
                                  quantized=quantized,
                                  kv_mode=kv_mode, constrain=True)


@_program_cache
def _compiled_chunked_prefill_c(cfg_fields: tuple, mesh,
                                chunk_len: int, num_slots: int,
                                temperature: float, top_k: int,
                                top_p: float, quantized=None,
                                kv_mode=None,
                                constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_chunked_prefill(cfg, mesh, chunk_len, num_slots,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, quantized=quantized,
                                kv_mode=kv_mode, constrain=True)


@_program_cache
def _compiled_paged_prefill_c(cfg_fields: tuple, mesh,
                              bucket_len: int, num_slots: int,
                              page_size: int, max_pages: int,
                              num_pages: int, temperature: float,
                              top_k: int, top_p: float,
                              quantized=None, kv_mode=None,
                              constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_prefill(cfg, mesh, bucket_len, num_slots,
                              page_size, max_pages, num_pages,
                              temperature=temperature, top_k=top_k,
                              top_p=top_p, quantized=quantized,
                              kv_mode=kv_mode, constrain=True)


@_program_cache
def _compiled_paged_chunked_prefill_c(cfg_fields: tuple, mesh,
                                      chunk_len: int, num_slots: int,
                                      page_size: int, max_pages: int,
                                      num_pages: int,
                                      temperature: float, top_k: int,
                                      top_p: float, quantized=None,
                                      kv_mode=None,
                                      constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_chunked_prefill(
        cfg, mesh, chunk_len, num_slots, page_size, max_pages,
        num_pages, temperature=temperature, top_k=top_k, top_p=top_p,
        quantized=quantized, kv_mode=kv_mode, constrain=True)


@_program_cache
def _compiled_paged_decode_c(cfg_fields: tuple, mesh, chunk: int,
                             num_slots: int, page_size: int,
                             max_pages: int, num_pages: int,
                             temperature: float, top_k: int,
                             top_p: float, quantized=None,
                             kv_mode=None, constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_decode(cfg, mesh, chunk, num_slots, page_size,
                             max_pages, num_pages,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, quantized=quantized,
                             kv_mode=kv_mode, constrain=True)


@_program_cache
def _compiled_spec_decode_c(cfg_fields: tuple, mesh, spec_k: int,
                            num_slots: int, temperature: float,
                            top_k: int, top_p: float, quantized=None,
                            kv_mode=None, draft_quantized=None,
                            draft_layers: int = 0,
                            constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_speculative_decode(cfg, mesh, spec_k, num_slots,
                                   temperature=temperature,
                                   top_k=top_k, top_p=top_p,
                                   quantized=quantized,
                                   kv_mode=kv_mode,
                                   draft_quantized=draft_quantized,
                                   draft_layers=draft_layers,
                                   constrain=True)


@_program_cache
def _compiled_paged_spec_decode_c(cfg_fields: tuple, mesh,
                                  spec_k: int, num_slots: int,
                                  page_size: int, max_pages: int,
                                  num_pages: int, temperature: float,
                                  top_k: int, top_p: float,
                                  quantized=None, kv_mode=None,
                                  draft_quantized=None,
                                  draft_layers: int = 0,
                                  constrain_cap: int = 0):
    cfg = TransformerConfig(*cfg_fields)
    return make_paged_speculative_decode(
        cfg, mesh, spec_k, num_slots, page_size, max_pages, num_pages,
        temperature=temperature, top_k=top_k, top_p=top_p,
        quantized=quantized, kv_mode=kv_mode,
        draft_quantized=draft_quantized, draft_layers=draft_layers,
        constrain=True)


@_program_cache
def _compiled_page_copy(n_pool_arrays: int):
    """Copy one physical page (all layers, values + scales) — the
    copy-on-write materializer. One tiny fixed-shape program per pool
    arity (2 float / 4 quantized); page indices are runtime data."""
    import jax

    def copy(src, dst, *pool):
        return tuple(a.at[:, dst].set(a[:, src]) for a in pool)

    return jax.jit(copy)


@_program_cache
def _compiled_page_poison(n_pool_arrays: int):
    """Scribble a deterministic out-of-distribution pattern over one
    physical page's K/V values (scales untouched) — backs the
    ServingFaultInjector.corrupt_page_at knob."""
    import jax
    import jax.numpy as jnp

    def poison(pg, *pool):
        out = []
        for i, a in enumerate(pool):
            if i < 2:      # kp, vp — scale planes keep their values
                bad = jnp.asarray(97 if a.dtype == jnp.int8 else 1e3,
                                  a.dtype)
                a = a.at[:, pg].set(bad)
            out.append(a)
        return tuple(out)

    return jax.jit(poison)


@_program_cache
def _compiled_page_gather(n_pool_arrays: int, mesh=None, geom=None):
    """Gather a page chain out of the pool — ALL layers, values AND
    scales, in ONE batched program (the KV-export half of the
    cross-tier handoff, ISSUE-11). The index vector is runtime data
    padded to a power-of-two bucket (ISSUE-19), so exporting never
    recompiles and the device->host transfer scales with the chain,
    not the pool's max_pages capacity. ``mesh``/``geom`` are
    cache-key-only: they pin the AOT executable resolved through
    `_resolve_program` to one pool geometry."""
    import jax

    def gather(idx, *pool):
        return tuple(a[:, idx] for a in pool)

    return jax.jit(gather)


@_program_cache
def _compiled_slot_gather(n_pool_arrays: int, mesh=None, geom=None):
    """Contiguous twin of _compiled_page_gather: one slot's full
    [L, S, ...] planes out of the slot pool (slot index is runtime
    data). ``mesh``/``geom`` are cache-key-only (see
    _compiled_page_gather)."""
    import jax

    def gather(slot, *pool):
        return tuple(a[:, slot] for a in pool)

    return jax.jit(gather)


@_program_cache
def _compiled_kv_adopt(n_pool_arrays: int, mesh=None, geom=None):
    """Scatter a handed-off row chain INTO freshly allocated pages and
    point the slot's pos/tok at the committed prefix — the device-put
    half of the handoff, ONE batched all-layer scatter per adoption
    (one launch, not n_layers). ``idx`` is bucket-padded; invalid
    entries are routed to the scratch page 0 (never attended), so the
    scatter shape stays static within a bucket and adoption never
    recompiles. ``mesh``/``geom`` are cache-key-only (see
    _compiled_page_gather)."""
    import jax
    import jax.numpy as jnp

    def adopt(idx, valid, slot, new_pos, new_tok, *arrs):
        n = (len(arrs) - 2) // 2
        rows, pool = arrs[:n], arrs[n:2 * n]
        pos, tok = arrs[-2], arrs[-1]
        tgt = jnp.where(valid, idx, 0)
        out = tuple(a.at[:, tgt].set(r.astype(a.dtype))
                    for a, r in zip(pool, rows))
        pos = pos.at[slot].set(new_pos)
        tok = tok.at[slot].set(new_tok)
        return (*out, pos, tok)

    return jax.jit(adopt)


@_program_cache
def _compiled_chain_adopt(n_pool_arrays: int, mesh=None, geom=None):
    """Pool-only twin of _compiled_kv_adopt (ISSUE-14): scatter a
    migrated prefix-cache chain into freshly allocated pages WITHOUT
    touching any slot's pos/tok — the chain seeds the radix cache, not
    a seated request, so per-slot state must stay untouched. Page
    indices are runtime data; invalid entries route to the scratch
    page 0, so seeding never recompiles within a bucket.
    ``mesh``/``geom`` are cache-key-only (see
    _compiled_page_gather)."""
    import jax
    import jax.numpy as jnp

    def adopt(idx, valid, *arrs):
        n = len(arrs) // 2
        rows, pool = arrs[:n], arrs[n:]
        tgt = jnp.where(valid, idx, 0)
        return tuple(a.at[:, tgt].set(r.astype(a.dtype))
                     for a, r in zip(pool, rows))

    return jax.jit(adopt)


class InferenceEngine:
    """Bounded-queue, deadline-aware, fault-tolerant front end for the
    sharded generate path. See module docstring for semantics; see
    EngineConfig for the policy knobs.

    Drive it either synchronously — `submit()` then `run_pending()` on
    the caller thread (deterministic; what the tests use) — or with the
    background worker via `start()`/`stop()`."""

    def __init__(self, cfg: TransformerConfig, mesh, params,
                 config: Optional[EngineConfig] = None,
                 fault_injector=None,
                 clock: Callable[[], float] = time.monotonic,
                 registry=None,
                 quantize: Optional[str] = None,
                 kv_quantize: Optional[str] = None,
                 recorder=None, slo=None, profiler=None, spans=None):
        self.cfg = cfg
        self.mesh = mesh
        self.config = config or EngineConfig()
        if self.config.mode not in ("continuous", "batch"):
            raise ValueError(f"mode must be 'continuous' or 'batch', "
                             f"got {self.config.mode!r}")
        self._dp = mesh.shape["data"]
        self._continuous = self.config.mode == "continuous"
        ns = self.config.num_slots or self.config.max_batch_size
        self._num_slots = -(-ns // self._dp) * self._dp
        self._chunk = (self.config.decode_chunk
                       if self.config.decode_chunk > 0
                       else DEFAULT_CONTINUOUS_CHUNK)
        # chunked prefill + token-budget scheduler (ISSUE-10): None
        # keeps the legacy one-shot admission prefill bit-identically
        self._prefill_chunk = self.config.prefill_chunk
        if self._prefill_chunk is not None:
            if not self._continuous:
                raise ValueError(
                    "prefill_chunk requires mode='continuous' (batch "
                    "mode has no persistent slot state to resume a "
                    "partial prefill from)")
            self._prefill_chunk = int(self._prefill_chunk)
            if not 0 < self._prefill_chunk <= cfg.max_len:
                raise ValueError(
                    f"prefill_chunk {self._prefill_chunk} out of "
                    f"(0, {cfg.max_len}]")
        elif self.config.tick_token_budget:
            raise ValueError(
                "tick_token_budget without prefill_chunk has nothing "
                "to schedule: set prefill_chunk to enable the "
                "token-budget scheduler")
        self._tick_budget = (
            int(self.config.tick_token_budget)
            or (self._num_slots * self._chunk
                + (self._prefill_chunk or 0)))
        self._last_tick_spent = 0
        self._seat_seq = itertools.count()
        # tenant QoS control plane (ISSUE-16): weighted fair share
        # divides the token-budget scheduler's prefill budget, so it
        # requires the scheduler; preemption requires the continuous
        # slot pool (the preempt/requeue/committed-prefix path)
        self._qos_weights: Optional[Dict[str, float]] = None
        if self.config.tenant_weights is not None:
            if self._prefill_chunk is None:
                raise ValueError(
                    "tenant_weights requires prefill_chunk: fair "
                    "share divides the token-budget scheduler's "
                    "prefill budget, which only exists under chunked "
                    "prefill")
            w = {}
            for t, v in self.config.tenant_weights.items():
                if not isinstance(t, str) or not t:
                    raise ValueError(
                        f"tenant_weights keys must be non-empty str, "
                        f"got {t!r}")
                v = float(v)
                if v <= 0:
                    raise ValueError(
                        f"tenant_weights[{t!r}] must be > 0, got {v}")
                w[t] = v
            self._qos_weights = w
        if float(self.config.qos_default_weight) <= 0:
            raise ValueError(
                f"qos_default_weight must be > 0, got "
                f"{self.config.qos_default_weight}")
        # per-tenant deficit counters (tokens of owed prefill budget);
        # populated lazily for backlogged tenants, dropped when a
        # tenant goes idle (idle share rolls to others — no banking)
        self._qos_deficit: Dict[str, float] = {}
        self._preempt_budget = int(self.config.preemption_budget)
        if self._preempt_budget < 0:
            raise ValueError(
                f"preemption_budget must be >= 0, got "
                f"{self._preempt_budget}")
        if self._preempt_budget and not self._continuous:
            raise ValueError(
                "preemption_budget requires mode='continuous' (batch "
                "mode has no resident slots to preempt)")
        # overload-controller degradation state (driven by the fleet
        # Router's qos_control() calls; engine-local knobs so a solo
        # engine stays inert): spec decode off, shrunken decode chunk
        self._qos_spec_off = False
        self._base_chunk = self._chunk
        self._qos_tenants_seen: set = set()
        # double-buffered tick loop (ISSUE-12): dispatch tick N without
        # blocking, commit tick N-1's synced outputs — host scheduling
        # work overlaps device compute. _pending holds the (at most
        # one) dispatched-but-uncommitted tick; _pipe_defer is True
        # only while _dispatch_tick runs, so every OTHER compiled-call
        # site (isolation solo re-runs, batch mode, spec rounds) keeps
        # its synchronous semantics untouched.
        self._pipe = bool(self.config.pipeline)
        # typed fallback surface (ISSUE-19 satellite): the reason a
        # pipelined config dropped to the synchronous loop, surfaced
        # in debugz()'s tick_pipeline section and counted into the
        # lazily registered serving_pipeline_fallbacks_total{reason}.
        # Speculative decoding no longer falls back: the scheduler
        # dispatches one tick ahead against a worst-case K+1 window
        # per slot and reconciles actual acceptance at the commit
        # boundary (schedule-ahead spec, ISSUE-19 tentpole).
        self._pipe_fallback: Optional[str] = None
        if self._pipe and not self._continuous:
            # auto-fallback, not rejection (ISSUE-14 satellite):
            # pipeline became the default once it soaked, so configs
            # it cannot serve drop to the synchronous loop
            # bit-identically instead of refusing to construct
            log.warning(
                "pipeline requires mode='continuous' (the batch path "
                "has no persistent slot state to schedule ahead "
                "over); falling back to the synchronous loop")
            self._pipe = False
            self._pipe_fallback = "batch"
        self._pending: deque = deque()
        self._pipe_defer = False
        self._pipe_items: Optional[list] = None
        # host-sync discipline + device-idle accounting: _block_on /
        # _block_on_many are the ONLY device->host sync points on the
        # tick path (the satellite test counts them); the busy-interval
        # estimate under them feeds serving_device_idle_fraction
        self._syncs_total = 0
        self._tick_sync_count = 0
        self._last_tick_syncs = 0
        self._last_sync_s = 0.0
        self._busy_since: Optional[float] = None
        self._tick_busy_s = 0.0
        self._busy_total_s = 0.0     # cumulative dispatched-work time
        self._last_idle = 0.0
        self._tick_perf0 = _perf()
        # in-memory compiled-program cache bound (process-wide; the
        # factories are module-level, so the LAST constructed engine's
        # setting governs — document, don't pretend otherwise)
        set_program_cache_size(self.config.program_cache_size)
        # persistent AOT compile cache (serving/compile_cache.py):
        # compiled executables round-trip to disk so a restarted
        # replica loads instead of recompiling
        from deeplearning4j_tpu.serving.compile_cache import CompileCache
        self._aot: Optional[CompileCache] = None
        if self.config.compile_cache_dir is not None:
            if CompileCache.available():
                self._aot = CompileCache(self.config.compile_cache_dir)
            else:
                log.warning(
                    "compile_cache_dir set but this runtime cannot "
                    "serialize executables; engine will recompile")
        # quantized inference: resolve the requested modes against the
        # backend (fp8 -> int8 off-TPU), quantize the weight tree ON
        # LOAD — float weights never reach the mesh — and remember a
        # float restore TEMPLATE so hot reloads can read a float
        # checkpoint and requantize (quant/model.py)
        from deeplearning4j_tpu.quant.core import resolve_mode
        self._qmode = resolve_mode(
            quantize if quantize is not None else self.config.quantize)
        self._kv_mode = resolve_mode(
            kv_quantize if kv_quantize is not None
            else self.config.kv_quantize)
        self._float_template = None
        if self._qmode:
            import jax
            from deeplearning4j_tpu.quant.model import quantize_params
            self._float_template = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype),
                params)
            params = quantize_params(params, mode=self._qmode)
        # slot pool: host-side seating; device-side persistent state
        # (KV caches + scales, per-slot pos + pending token) allocated
        # lazily on the first admission — an opaque tuple whose arity
        # the compiled programs own (4 float / 6 quantized-KV)
        self._slots: List[Optional[RequestHandle]] = \
            [None] * self._num_slots
        self._slot_state = None
        self._key = None
        # grammar-constrained decoding (ISSUE-20): everything here is
        # lazy — the mask table, the per-slot DFA-state vector, and
        # the serving_constrained_* metrics exist only once the first
        # submit(constrain=...) lands, so constrain-off engines are
        # byte-identical to the pre-constraint engine (compile keys,
        # scrapes, traces)
        self._constrain_active = False
        self._ctab = None                 # ConstraintTable (lazy)
        self._cstate = None               # np.int32 [num_slots]
        self._cseed_pending: Dict[int, int] = {}
        self._cgrammar_keys: set = set()
        # paged slot KV + radix prefix sharing (ISSUE-7): page indices
        # are host-owned — the allocator/radix cache here, the block
        # table as a numpy array passed to every compiled call — so
        # sharing, COW, and recycling never change compiled geometry
        self._paged = bool(self.config.paged)
        if self._paged:
            if not self._continuous:
                raise ValueError(
                    "paged KV requires mode='continuous' (batch mode "
                    "has no persistent slot state to page)")
            if mesh.shape["data"] != 1:
                raise ValueError(
                    "paged KV requires a data=1 serving mesh: pages "
                    "are shared across slots (see parallel/serving.py)")
            self._page_size = int(self.config.page_size)
            if self._page_size < 1:
                raise ValueError("page_size must be >= 1")
            self._max_pages = pages_for(cfg.max_len, self._page_size)
            self._num_pages = (int(self.config.kv_pages)
                               or self._num_slots * self._max_pages + 1)
            self._allocator = PageAllocator(self._num_pages,
                                            self._page_size)
            self._prefix_cache = (
                RadixPrefixCache(self._page_size, self._allocator)
                if self.config.prefix_cache else None)
            self._bt = np.zeros((self._num_slots, self._max_pages),
                                np.int32)
            self._slot_pages: List[List[int]] = \
                [[] for _ in range(self._num_slots)]
        else:
            self._prefix_cache = None
        self._params = shard_serving_params(params, cfg, mesh)
        # speculative decoding (ISSUE-8): draft K tokens per slot with
        # a cheap drafter, verify them in ONE target pass, commit the
        # longest accepted prefix — token-exact vs plain decode. The
        # drafter tree is derived from the LIVE params (and re-derived
        # on every hot reload); acceptance state drives the adaptive-K
        # controller (_spec_update).
        self._spec = bool(self.config.spec_decode)
        self._draft_params = None
        if self._spec:
            if not self._continuous:
                raise ValueError(
                    "spec_decode requires mode='continuous' (batch "
                    "mode has no persistent slot state to verify "
                    "against)")
            if cfg.n_experts > 0:
                raise ValueError(
                    "spec_decode does not support MoE configs (the "
                    "verify pass's token count changes the expert-"
                    "capacity cap — see parallel/serving.py)")
            self._spec_k = int(self.config.spec_k)
            if self._spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got "
                                 f"{self._spec_k}")
            self._rebuild_draft()
            self._spec_cur_k = self._spec_k
            self._spec_plain = 0          # plain-decode cooldown ticks
            self._accept_ema = [1.0] * self._num_slots
            self._accept_pool = 1.0       # engine-wide acceptance EMA
        self._injector = fault_injector
        self._clock = clock
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._queue: deque = deque()
        self._rids = itertools.count(1)
        self._accepting = True
        self._draining = False
        self._stop_flag = False
        self._thread: Optional[threading.Thread] = None
        self._listeners: list = []
        # breaker: closed -> open (consecutive failures) -> half-open
        # (cooldown elapsed) -> closed (probe success) | open (failure)
        self._breaker = "closed"
        self._opened_at = 0.0
        self._consec_failures = 0
        # step counter indexes COMPLETED decode steps: a failed attempt
        # retries the same index (ServingFaultInjector contract)
        self._step_counter = 0
        self._weights_step: Optional[int] = None
        # observability: every counter the old ad-hoc stats dict held
        # now lives in a MetricsRegistry; `stats`/`health()` are
        # read-through views. A fresh private registry per engine keeps
        # per-engine counts exact — inject a shared registry (e.g.
        # observability.default_registry()) to publish into a process
        # scrape, or NULL_REGISTRY to disable instrumentation.
        self.registry = (registry if registry is not None
                         else MetricsRegistry())
        self._init_metrics(self.registry)
        # flight recorder + SLO layer (ISSUE-6): on by default with a
        # live registry, and mirroring NULL_REGISTRY off — pass
        # recorder=observability.NULL_RECORDER (or a NULL registry) to
        # make every trace/SLO call a no-op, or inject a shared
        # FlightRecorder/SLOTracker the way a registry is shared
        if recorder is None:
            recorder = (NULL_RECORDER
                        if isinstance(self.registry, NullRegistry)
                        else FlightRecorder(
                            capacity=self.config.recorder_capacity))
        self.recorder = recorder
        if slo is None:
            slo = (NULL_SLO if not recorder.enabled
                   else SLOTracker(registry=self.registry))
        self.slo = slo
        # continuous profiling & cost attribution (ISSUE-15): the
        # per-program cost table + device-time attribution + tenant
        # meter. Defaults ON with a live registry, mirroring the
        # recorder; profiler=NULL_PROFILER is the disabled arm.
        if profiler is None:
            profiler = (NULL_PROFILER
                        if isinstance(self.registry, NullRegistry)
                        else EngineProfiler(
                            self.registry,
                            tenant_top_n=self.config.tenant_top_n))
        self.profiler = profiler
        # the tick's spans (observability/tracing.py): the process-wide
        # ring unless one is injected; spans=NULL_SPANS makes no record
        # and opens no profiler annotation
        self.spans = default_spans() if spans is None else spans
        self._tick_no = 0
        self._tokens_committed = 0
        self._decode_bill_label: Optional[str] = None
        self._capture = ProfileCapture(self.config.profile_dir)
        # cold-start warm-up (ISSUE-12): resolve the whole closed
        # program set before the constructor returns — from the AOT
        # cache when warm, so restart-to-ready is a load, not a compile
        self._last_warmup: Optional[dict] = None
        if self.config.warmup_on_init:
            self.warmup()

    def _init_metrics(self, r) -> None:
        self._m_completed = r.counter(
            "serving_requests_completed", "Requests fully decoded")
        shed = r.counter("serving_requests_shed",
                         "Requests rejected or abandoned, by reason",
                         labelnames=("reason",))
        self._m_shed = shed          # reason="handoff" child created
        #                              lazily: legacy scrapes unchanged
        self._m_shed_overload = shed.labels("overload")
        self._m_shed_deadline = shed.labels("deadline")
        self._m_shed_cancelled = shed.labels("cancelled")
        self._m_quarantined = r.counter(
            "serving_requests_quarantined",
            "Requests that failed persistently after solo retries")
        self._m_retries = r.counter(
            "serving_decode_retries", "Decode step retry attempts")
        self._m_step_failures = r.counter(
            "serving_decode_step_failures", "Failed decode step calls")
        self._m_batches = r.counter(
            "serving_batches", "Dynamic batches processed")
        self._m_reloads = r.counter(
            "serving_weight_reloads", "Successful hot weight reloads")
        self._m_in_flight = r.gauge(
            "serving_in_flight_requests",
            "Requests currently inside the decode loop")
        # pull-model gauges: evaluated only at scrape/snapshot time, so
        # the hot path pays nothing for them
        r.gauge("serving_queue_depth",
                "Admitted requests waiting for a batch").set_function(
            lambda: float(len(self._queue)))
        r.gauge("serving_breaker_state",
                "Circuit breaker: 0=closed 1=half-open 2=open"
                ).set_function(
            lambda: _BREAKER_STATE.get(self._breaker, -1.0))
        r.gauge("serving_degraded",
                "1 while admissions are token-budget-capped"
                ).set_function(lambda: float(
                    len(self._queue) >= self.config.degrade_queue_depth
                    or self._breaker != "closed"))
        self._m_preempted = r.counter(
            "serving_requests_preempted",
            "In-flight requests evicted from their slot (isolation or "
            "weight reload) and re-run from their committed prefix")
        self._m_ticks_recovered = r.counter(
            "serving_ticks_recovered",
            "Pipelined ticks whose outputs failed at the sync and were "
            "recovered from the last committed state")
        blocked = r.counter(
            "serving_admission_blocked",
            "Scheduling rounds that left a queued request unseated, by "
            "what was short (pages: the KV page pool; slots: every "
            "slot taken)", labelnames=("reason",))
        self._m_blocked = {reason: blocked.labels(reason)
                           for reason in ("pages", "slots")}
        self._m_reprefill_tokens = r.counter(
            "serving_reprefill_tokens",
            "Prompt or generated tokens prefilled again for a request "
            "that lost its slot (preemption, isolation, reload)")
        r.gauge("serving_slot_occupancy",
                "Occupied continuous-batching slots").set_function(
            lambda: float(sum(s is not None for s in self._slots)))
        # HBM accounting (pull-model: sized at scrape time, nothing on
        # the decode path) — the operator's slot-pool sizing inputs:
        # bytes of weights at rest, bytes one slot's KV costs, and the
        # whole pool. With quantize="int8"/kv_quantize="int8" these are
        # the numbers that shrink ~4x (docs/quantization.md).
        r.gauge("serving_param_bytes",
                "At-rest bytes of the serving weight tree "
                "(values + scales when quantized)").set_function(
            lambda: float(self.param_bytes()))
        r.gauge("serving_kv_bytes_per_slot",
                "KV-cache bytes one continuous-batching slot costs "
                "(caches + scales + slot vectors)").set_function(
            lambda: float(self.kv_bytes_per_slot()))
        r.gauge("serving_kv_pool_bytes",
                "Total at-rest bytes of the slot-pool KV state"
                ).set_function(lambda: float(self.kv_pool_bytes()))
        self._m_batch_size = r.histogram(
            "serving_batch_size", "Coalesced batch sizes",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        self._m_batch_seconds = r.histogram(
            "serving_batch_latency_seconds",
            "Wall time from batch formation to completion")
        self._m_step_seconds = r.histogram(
            "serving_decode_step_seconds",
            "Wall time of one compiled decode call",
            buckets=DECODE_LATENCY_BUCKETS)
        self._m_prefill_seconds = r.histogram(
            "serving_prefill_seconds",
            "Wall time of one compiled admission-prefill call",
            buckets=DECODE_LATENCY_BUCKETS)
        # prefill-compute accounting (ISSUE-14): the prompt tokens
        # whose K/V THIS engine actually computed — prefix-cache hits
        # and adopted handoffs excluded
        self._m_prefill_tokens = r.counter(
            "serving_prefill_tokens",
            "Prompt tokens prefilled by this engine (prefix-cache "
            "hits and adopted KV handoffs excluded)")
        # raw-speed observability (ISSUE-12): every program build is
        # counted by source — "jit" = traced+XLA-compiled here, a
        # recompile when it shows up in steady state; "aot_cache" =
        # loaded from the persistent compile cache — and timed, so a
        # cold start's compile bill and a warm start's load bill are
        # both first-class series instead of mystery latency
        self._m_compiles = r.counter(
            "serving_compiles",
            "Compiled-program builds, by program and source (jit = "
            "traced + XLA-compiled in-process, aot_cache = loaded "
            "from the persistent AOT compile cache)",
            labelnames=("program", "source"))
        self._m_compile_seconds = r.histogram(
            "serving_compile_seconds",
            "Wall time to materialize one compiled program (XLA "
            "compile for source=jit, deserialize for aot_cache)",
            labelnames=("program",), buckets=DECODE_LATENCY_BUCKETS)
        self._m_prog_evictions = r.counter(
            "serving_program_cache_evictions",
            "In-memory compiled-program cache entries evicted "
            "(process-wide caches; an evicted geometry is a "
            "guaranteed steady-state recompile)")
        _EVICTION_COUNTERS.add(self._m_prog_evictions)
        r.gauge("serving_device_idle_fraction",
                "Estimated fraction of the last scheduling round the "
                "device spent idle (1 - dispatched-work interval / "
                "tick wall time): the double-buffered tick loop's "
                "target metric").set_function(
            lambda: float(self._last_idle))
        # pipelined-tick fallback surface (ISSUE-19 satellite):
        # registered only when a fallback actually happened, so
        # scrapes of engines that pipeline (or never asked to) stay
        # byte-identical
        self._m_pipe_fallbacks = None
        if self._pipe_fallback is not None:
            self._m_pipe_fallbacks = r.counter(
                "serving_pipeline_fallbacks",
                "Pipelined tick-loop configurations dropped to the "
                "synchronous loop at construction, by reason",
                labelnames=("reason",))
            self._m_pipe_fallbacks.labels(self._pipe_fallback).inc()
        # forced pipeline flushes (ISSUE-19 satellite): KV export and
        # cache-chain migration must drain the in-flight tick before
        # reading slot state — the wait is billed here by reason
        # instead of vanishing into the caller's latency
        self._last_flush: Optional[dict] = None
        self._m_flush_seconds = None
        if self._pipe:
            self._m_flush_seconds = r.histogram(
                "serving_pipeline_flush_seconds",
                "Wall time a committed-view consumer (KV export, "
                "cache-chain migration, drain) spent draining the "
                "in-flight pipelined tick, by reason",
                labelnames=("reason",),
                buckets=DECODE_LATENCY_BUCKETS)
        # paged KV + prefix sharing (ISSUE-7): registered only on
        # paged engines, so unpaged scrapes are byte-unchanged
        if self._paged:
            r.gauge("serving_kv_pages_free",
                    "Allocatable pages on the KV free list"
                    ).set_function(
                lambda: float(self._allocator.pages_free))
            r.gauge("serving_kv_pages_used",
                    "KV pages referenced by slots or the prefix cache"
                    ).set_function(
                lambda: float(self._allocator.pages_used))
            self._m_prefix_hits = r.counter(
                "serving_prefix_cache_hits",
                "Admissions whose prefix matched a cached page chain")
            self._m_prefix_misses = r.counter(
                "serving_prefix_cache_misses",
                "Admissions with no cached prefix to share")
            self._m_prefix_evictions = r.counter(
                "serving_prefix_cache_evictions",
                "Cached prefix pages reclaimed by LRU eviction")
            self._m_prefix_shared_tokens = r.counter(
                "serving_prefix_shared_tokens",
                "Prompt tokens whose prefill compute AND KV bytes "
                "were served from the radix prefix cache")
            # cross-tier KV adoption (ISSUE-11): children created
            # lazily, so non-disagg paged scrapes are unchanged
            self._m_adoptions = r.counter(
                "serving_kv_adoptions",
                "Handed-off KV chains seated into this engine's page "
                "pool, by outcome (ok / blocked / shed)",
                labelnames=("outcome",))
        # speculative decoding (ISSUE-8): registered only on spec
        # engines, so non-speculative scrapes are byte-unchanged
        if self._spec:
            self._m_spec_drafted = r.counter(
                "serving_spec_drafted_tokens",
                "Draft tokens proposed by speculative decode rounds")
            self._m_spec_accepted = r.counter(
                "serving_spec_accepted_tokens",
                "Draft tokens accepted by target-model verification")
            r.gauge("serving_spec_acceptance_ratio",
                    "Cumulative accepted/drafted draft-token ratio"
                    ).set_function(lambda: (
                        float(self._m_spec_accepted.value)
                        / max(1.0,
                              float(self._m_spec_drafted.value))))
            r.gauge("serving_spec_k",
                    "Adaptive draft length in use (0 while the "
                    "controller has fallen back to plain decode)"
                    ).set_function(lambda: float(
                        0 if self._spec_plain > 0
                        else self._spec_cur_k))
        # schedule-ahead reservation waste (ISSUE-19 satellite):
        # registered only on PIPELINED spec engines, so synchronous
        # spec scrapes (and every spec-off scrape) are byte-unchanged
        self._m_spec_waste = None
        if self._spec and self._pipe:
            self._m_spec_waste = r.counter(
                "serving_spec_schedule_waste_tokens",
                "Worst-case K+1 window slots the schedule-ahead "
                "dispatch reserved that verification then rejected "
                "(the price of pipelining a nondeterministic commit "
                "count)")
        # chunked prefill (ISSUE-10): registered only on chunked
        # engines, so legacy scrapes are byte-unchanged
        if self._prefill_chunk is not None:
            self._m_prefill_chunks = r.counter(
                "serving_prefill_chunks",
                "Prefill chunks advanced by the token-budget "
                "scheduler (one per slot per chunked-prefill call)")
            r.gauge("serving_tick_budget_utilization",
                    "Tokens scheduled in the last tick / "
                    "tick_token_budget (decode chunks + prefill "
                    "chunks; >1 when the progress floor overrode "
                    "the budget)").set_function(
                lambda: float(self._last_tick_spent)
                / float(max(1, self._tick_budget)))
        # tenant QoS (ISSUE-16): registered only when the relevant
        # knob is on, so QoS-off scrapes are byte-unchanged
        if self._qos_weights is not None:
            self._m_qos_prefill_tokens = r.counter(
                "serving_qos_prefill_tokens",
                "Prefill tokens granted by the weighted fair-share "
                "scheduler, by tenant (folds past tenant_top_n)",
                labelnames=("tenant",))
        if self._preempt_budget > 0:
            self._m_qos_preemptions = r.counter(
                "serving_qos_preemptions",
                "Residents evicted by priority preemption, by the "
                "evicted request's tenant (token-exact resume from "
                "the committed prefix)",
                labelnames=("tenant",))
        # grammar constraints (ISSUE-20): registered lazily by
        # _ensure_constrain_metrics on the first submit(constrain=...),
        # so a constrain-off engine's scrape is byte-unchanged
        self._m_c_requests = None
        self._m_c_rejections = None
        self._m_c_compiles = None
        self._m_c_terminal = None

    # ------------------------------------------------------------------
    # grammar-constrained decoding (ISSUE-20): lazy activation
    # ------------------------------------------------------------------
    def _ensure_constrain_metrics(self) -> None:
        """Register the serving_constrained_* series on first use (a
        constrain-off engine's /metrics scrape must stay
        byte-identical — see tests/test_metrics_naming.py)."""
        if self._m_c_requests is not None:
            return
        r = self.registry
        self._m_c_requests = r.counter(
            "serving_constrained_requests",
            "Requests admitted with a grammar constraint")
        self._m_c_rejections = r.counter(
            "serving_constrained_rejections",
            "Constrained submissions rejected at submit() with a "
            "typed ConstraintError, by reason (never mid-decode)",
            labelnames=("reason",))
        self._m_c_compiles = r.counter(
            "serving_constrained_grammar_compiles",
            "Distinct compiled grammars this engine has admitted "
            "(cache hits on the same grammar hash do not count)")
        self._m_c_terminal = r.counter(
            "serving_constrained_terminal_completions",
            "Constrained requests completed early because their DFA "
            "reached a terminal accepting state")
        r.gauge("serving_constrained_states",
                "DFA states resident in the constraint mask table "
                "(bound: constrain_state_cap)").set_function(
            lambda: float(self._ctab.rows_used
                          if self._ctab is not None else 0))

    def _ensure_constrain(self) -> None:
        """First constrained admission: allocate the fixed-geometry
        mask table and the per-slot DFA-state vector and flip the
        engine into constrain-aware mode. From here on every
        continuous-batching call uses the masked program variants —
        registered under SEPARATE cache names, so the unmasked
        programs (and any engine that never sees a constraint) keep
        their compile keys byte-unchanged."""
        if self._constrain_active:
            return
        from deeplearning4j_tpu.serving.constrain import ConstraintTable
        self._ctab = ConstraintTable(
            int(self.config.constrain_state_cap),
            int(self.cfg.vocab_size))
        self._ensure_constrain_metrics()
        self._cstate = np.zeros((self._num_slots,), np.int32)
        self._constrain_active = True

    def _c_state_for(self, r: RequestHandle) -> int:
        """Device-table row for a (re)seated request: replay the
        committed prefix through the host DFA — this is what makes
        failover/requeue token-exact, the state is always derivable
        from committed bytes — and offset into the request's table
        slab. Row 0 (all-allow) for unconstrained requests."""
        if r._grammar is None:
            return 0
        g = r._grammar
        st = r._cinit
        for t in np.asarray(r.generated, np.int32).tolist():
            st = g.advance(st, int(t))
        r._cstate_host = st
        return int(r._cbase) + int(st)

    def _c_advance_commit(self, r: RequestHandle,
                          toks: np.ndarray):
        """Host-authoritative DFA advance at commit time. Walks the
        committed tokens through the request's grammar; returns the
        (possibly truncated) token array plus whether the walk reached
        a terminal accepting state. Tokens past a terminal state — or
        past a (defensive, should-be-impossible) illegal token — are
        dropped: the device mask guarantees legality, so truncation
        only ever fires at grammar completion."""
        g = r._grammar
        st = r._cstate_host
        keep = 0
        terminal = False
        for t in np.asarray(toks, np.int32).tolist():
            if not g.legal(st, int(t)):
                log.error("request %d: committed token %d illegal in "
                          "DFA state %d (truncating)", r.rid, int(t),
                          st)
                break
            st = g.advance(st, int(t))
            keep += 1
            if g.is_terminal(st):
                terminal = True
                break
        r._cstate_host = st
        return toks[:keep], terminal

    def _cmask_begin(self):
        """Snapshot the constraint operands for one compiled call:
        the device mask/transition planes, the current per-slot state
        vector, and the pending reseat seeds (as dense vectors — a
        seed overrides the stale device state for slots that changed
        occupants since the last call). Returns an operand jar the
        call site threads through `_cmask_commit` on success; a
        `_guarded` retry reuses the same snapshot, so retries are
        bit-exact."""
        allow_d, trans_d = self._ctab.device(self.mesh)
        ns = self._num_slots
        cseed = np.zeros((ns,), bool)
        cseedval = np.zeros((ns,), np.int32)
        for i, v in self._cseed_pending.items():
            cseed[i] = True
            cseedval[i] = np.int32(v)

        class _Jar:
            pass
        jar = _Jar()
        jar.ops = (allow_d, trans_d, self._cstate, cseed, cseedval)
        jar.taken = tuple(self._cseed_pending.keys())
        jar.out = None
        return jar

    def _cmask_commit(self, jar) -> None:
        """Adopt the call's updated per-slot DFA-state vector and
        retire the seeds it consumed (seeds recorded AFTER the
        snapshot — e.g. by a reseat racing a pipelined dispatch —
        survive for the next call)."""
        if jar.out is not None:
            self._cstate = jar.out
        for i in jar.taken:
            self._cseed_pending.pop(i, None)

    # ------------------------------------------------------------------
    # HBM accounting (quant subsystem; backs the serving_param_bytes /
    # serving_kv_* pull gauges and the health()/stats surfaces)
    # ------------------------------------------------------------------
    def param_bytes(self) -> int:
        """At-rest bytes of the serving weight tree (quantized trees
        count int8 values + float32 scales)."""
        from deeplearning4j_tpu.quant.model import param_bytes
        return param_bytes(self._params)

    def kv_pool_bytes(self) -> int:
        """At-rest bytes of the slot-pool KV state (paged engines:
        page pool + scale planes + block tables): measured when the
        lazily-allocated pool exists, analytic otherwise (so operators
        can size pools before traffic arrives)."""
        if self._slot_state is not None:
            meas = int(sum(int(a.nbytes) for a in self._slot_state))
            if self._paged:
                meas += int(self._bt.nbytes)
            return meas
        if self._paged:
            from deeplearning4j_tpu.quant.kv import paged_pool_bytes
            return paged_pool_bytes(self.cfg, self._num_slots,
                                    self._page_size, self._num_pages,
                                    self._max_pages,
                                    kv_mode=self._kv_mode,
                                    tp=self.mesh.shape["model"])
        from deeplearning4j_tpu.quant.kv import slot_pool_bytes
        return slot_pool_bytes(self.cfg, self._num_slots,
                               kv_mode=self._kv_mode,
                               tp=self.mesh.shape["model"])

    def kv_bytes_per_slot(self) -> int:
        return self.kv_pool_bytes() // max(1, self._num_slots)

    @property
    def stats(self) -> dict:
        """Counter snapshot (registry-backed; keys unchanged from the
        pre-observability ad-hoc dict) plus the HBM accounting trio."""
        return {"param_bytes": self.param_bytes(),
                "kv_bytes_per_slot": self.kv_bytes_per_slot(),
                "kv_pool_bytes": self.kv_pool_bytes(),
                "completed": int(self._m_completed.value),
                "shed_overload": int(self._m_shed_overload.value),
                "shed_deadline": int(self._m_shed_deadline.value),
                "quarantined": int(self._m_quarantined.value),
                "retries": int(self._m_retries.value),
                "step_failures": int(self._m_step_failures.value),
                "batches": int(self._m_batches.value),
                "reloads": int(self._m_reloads.value),
                "preempted": int(self._m_preempted.value),
                "in_flight": int(self._m_in_flight.value)}

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               on_deadline: str = "shed",
               hold_kv: bool = False,
               kv: Optional[KVHandoff] = None,
               trace_ctx: Optional[dict] = None,
               tenant: Optional[str] = None,
               priority: int = 0,
               constrain=None) -> RequestHandle:
        """Admit one prompt. Raises OverloadError when the queue is full
        or the circuit breaker is open; in degraded mode the token
        budget is silently capped (reported via health()).

        ``tenant``/``priority`` (ISSUE-16) are validated HERE with a
        typed `QoSValidationError` — tenant ids are metric-label
        material and priorities drive preemption, so malformed values
        never reach the registry or the scheduler. ``priority`` is
        0..MAX_PRIORITY; on engines with ``preemption_budget`` > 0 a
        higher class seats first and may preempt a lower-class
        resident (token-exact resume from its committed prefix).

        ``trace_ctx`` (ISSUE-13) is the distributed-tracing hop
        context a fleet router stamps on each dispatch
        (``{"fleet_rid": ..., "hop": ...}``): merged into every
        lifecycle event this request records, so the engine's local
        ring stays attributable to the fleet request — the raw
        material `observability/stitch.py` reassembles.

        ISSUE-11 (cross-tier handoff): ``hold_kv`` keeps the request's
        slot SEATED after it completes — its KV pages stay referenced
        — until `export_slot_kv()` / `release_held()` frees it (the
        prefill-tier side). ``kv`` seats the request by ADOPTING a
        `KVHandoff` instead of prefilling: the handed-off rows are
        device-put into freshly allocated pages and decode resumes
        from the committed prefix (the decode-tier side; paged
        continuous engines only — an engine that cannot adopt drops
        the handoff with a warning and re-prefills, which is slower
        but token-identical)."""
        # grammar-constrained decoding (ISSUE-20): compile + validate
        # OUTSIDE the admission lock (DFA construction is pure CPU
        # work keyed by the grammar hash). Every failure mode is a
        # typed ConstraintError raised HERE — a constrained request
        # that admits never fails mid-decode for grammar reasons.
        cgrammar = cspec = None
        cconsumed = cstart = 0
        if constrain is not None:
            from deeplearning4j_tpu.serving.constrain import (
                ConstraintError, compile_grammar, normalize_constraint)
            prompt_a = np.asarray(prompt, np.int32)
            try:
                if not self._continuous:
                    raise ConstraintError(
                        "constrain= requires mode='continuous' (batch "
                        "mode has no per-slot DFA state to carry "
                        "across steps)", "mode")
                cspec, cconsumed = normalize_constraint(constrain)
                cgrammar = compile_grammar(
                    cspec, int(self.cfg.vocab_size),
                    state_cap=int(self.config.constrain_state_cap))
                if cconsumed > int(prompt_a.size):
                    raise ConstraintError(
                        f"constrain consumed={cconsumed} exceeds the "
                        f"prompt length {int(prompt_a.size)}",
                        "invalid")
                # a failover hop folds committed tokens into the
                # prompt and reports them consumed: replaying the
                # tail both validates it and recovers the DFA state
                cstart = cgrammar.replay(
                    prompt_a[prompt_a.size - cconsumed:]
                    if cconsumed else ())
                if cgrammar.is_terminal(cstart):
                    raise ConstraintError(
                        "grammar is already terminal at the start "
                        "state: it would emit zero tokens", "empty")
            except ConstraintError as e:
                self._ensure_constrain_metrics()
                self._m_c_rejections.labels(reason=e.reason).inc()
                raise
        if kv is not None:
            adoptable = (self._continuous and self._paged
                         and kv.kv_mode == self._kv_mode
                         and kv.n_layers == self.cfg.n_layers
                         and kv.d_model == self.cfg.d_model)
            if getattr(kv, "source", "slot") == "cache":
                # a migrated cache chain (ISSUE-14) seeds the radix
                # cache at seating — no cache, nothing to seed
                adoptable = adoptable and self._prefix_cache is not None
            if not adoptable:
                # availability over purity: a mismatched handoff
                # target re-prefills (correct tokens, no shared
                # compute) instead of failing the request for a
                # router-side config skew
                log.warning("KV handoff not adoptable here (paged=%s, "
                            "kv_mode=%s vs handoff %s, source=%s): "
                            "falling back to re-prefill", self._paged,
                            self._kv_mode, kv.kv_mode,
                            getattr(kv, "source", "slot"))
                kv = None
        if on_deadline not in ("shed", "partial"):
            raise ValueError(f"on_deadline must be 'shed' or 'partial', "
                             f"got {on_deadline!r}")
        # ISSUE-16 satellite: coerce-or-reject tenant/priority BEFORE
        # anything touches the metric-label path or the scheduler
        tenant, priority = validate_tenant_priority(tenant, priority)
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt must be a non-empty 1-D token array")
        now = self._clock()
        with self._lock:
            # typed, IMMEDIATE rejection (ISSUE-9 satellite): a submit
            # raced against stop()/drain() used to land on the bounded
            # queue with nothing left to drain it — the caller then
            # hangs in result() forever. Stopped and draining engines
            # refuse admission synchronously instead.
            if not self._accepting:
                raise EngineStopped(
                    "engine is stopped: submit() would never be served")
            if self._draining:
                raise EngineDraining(
                    "engine is draining: admissions are closed until "
                    "resume()")
            self._tick_breaker(now)
            if self._breaker == "open":
                self._m_shed_overload.inc()
                raise OverloadError(
                    "circuit breaker open (recent step failures); "
                    f"retry after {self.config.breaker_cooldown_s}s")
            if len(self._queue) >= self.config.max_queue:
                self._m_shed_overload.inc()
                raise OverloadError(
                    f"queue full ({self.config.max_queue})")
            cap = (self.config.degraded_max_new_tokens
                   if self._degraded_locked()
                   else self.config.max_new_tokens)
            eff = min(max_new_tokens or self.config.max_new_tokens,
                      cap, self.config.max_new_tokens)
            if eff < 1:
                raise ValueError("max_new_tokens must be >= 1")
            if prompt.shape[0] + eff > self.cfg.max_len:
                raise ValueError(
                    f"prompt {prompt.shape[0]} + {eff} new tokens "
                    f"exceeds max_len={self.cfg.max_len}")
            if self._paged:
                need = pages_for(prompt.shape[0] + eff,
                                 self._page_size)
                if need > self._allocator.usable_pages:
                    raise ValueError(
                        f"request needs {need} KV pages but the pool "
                        f"has {self._allocator.usable_pages} "
                        f"(kv_pages={self._num_pages}, page_size="
                        f"{self._page_size}) — it could never be "
                        "admitted")
            cbase = 0
            if cgrammar is not None:
                # last admission check: reserve grammar rows in the
                # fixed-shape mask table (refcounted — a resubmit of
                # the same grammar is free). An overflow is the typed
                # `oversize` reject, still at submit() time.
                self._ensure_constrain()
                from deeplearning4j_tpu.serving.constrain import \
                    ConstraintError
                try:
                    cbase = self._ctab.acquire(cgrammar)
                except ConstraintError as e:
                    self._m_c_rejections.labels(reason=e.reason).inc()
                    raise
                if cgrammar.key not in self._cgrammar_keys:
                    self._cgrammar_keys.add(cgrammar.key)
                    self._m_c_compiles.inc()
                self._m_c_requests.inc()
            handle = RequestHandle(
                next(self._rids), prompt, eff,
                now + deadline_s if deadline_s is not None else None,
                on_deadline)
            handle._hold_kv = bool(hold_kv)
            handle._kv = kv
            # per-tenant cost metering (ISSUE-15): the tenant label
            # rides the handle AND every trace event (via the submit
            # event) so the bill and the forensic trace agree on who
            # the work was for
            handle.tenant = tenant
            handle.priority = priority
            if cgrammar is not None:
                handle._grammar = cgrammar
                handle._cbase = cbase
                handle._constrain = cspec   # JSON-able, consumed-free
                handle._consumed = int(cconsumed)
                handle._cinit = int(cstart)
                handle._cstate_host = int(cstart)
            handle.trace = self.recorder.start_trace(handle.rid,
                                                     ctx=trace_ctx)
            handle._on_terminal = self._on_terminal
            handle.trace.add(
                "submit", prompt_tokens=int(prompt.shape[0]),
                max_new_tokens=int(eff),
                deadline_s=(float(deadline_s)
                            if deadline_s is not None else None),
                **({"tenant": handle.tenant}
                   if handle.tenant is not None else {}),
                **({"priority": priority} if priority else {}),
                **({"constrained": True,
                    "grammar": cgrammar.key[:12],
                    "dfa_states": cgrammar.num_states}
                   if cgrammar is not None else {}))
            self._queue.append(handle)
            handle.trace.add("queued", depth=len(self._queue))
            self._cv.notify()
        return handle

    def _on_terminal(self, r: RequestHandle) -> None:
        """RequestHandle._finish hook: terminal trace event + SLO
        accounting — runs exactly once, whatever path finished the
        request (complete / deadline shed / partial / quarantine)."""
        # the request's accumulated analytic bill (ISSUE-15) rides its
        # terminal event — the audit trail for "sum of per-request
        # bills == the per-tenant counters" (shed/quarantined requests
        # billed the compute they consumed before dying)
        bill = ({"cost_flops": float(r.cost_flops),
                 "cost_bytes": float(r.cost_bytes),
                 **({"tenant": r.tenant}
                    if r.tenant is not None else {})}
                if self.profiler.enabled else {})
        if r.status == RequestStatus.COMPLETED:
            r.trace.add("finished",
                        tokens=int(sum(a.shape[0]
                                       for a in r._generated)),
                        partial=bool(r.deadline_exceeded), **bill)
        elif r.status == RequestStatus.SHED:
            r.trace.add("shed", reason=(
                "handoff" if r._handoff_failed
                else "cancelled" if r._cancelled
                else "deadline" if r.deadline_exceeded
                else "overload"), **bill)
        elif r.status == RequestStatus.QUARANTINED:
            r.trace.add("quarantined", **bill)
        if r._grammar is not None and self._ctab is not None:
            # drop the grammar's table refcount (rows stay resident
            # for cache-friendly resubmits until space is needed)
            self._ctab.release(r._grammar.key)
        self.slo.finished(r.trace)

    # ------------------------------------------------------------------
    # driving: synchronous drain or background worker
    # ------------------------------------------------------------------
    def run_pending(self) -> int:
        """Process queued requests on the caller thread until the
        queue AND the slot pool are drained. Returns the number of
        scheduling rounds run (batch mode: batches; continuous mode:
        ticks)."""
        n = 0
        while self.tick():
            n += 1
        return n

    def tick(self) -> bool:
        """Advance the engine by one scheduling round and return
        whether any work was done. Batch mode: form one same-length
        batch and run it to completion. Continuous mode: fill free
        slots from the queue (one fused prefill), then advance every
        occupied slot one decode chunk. Public so callers can
        interleave submissions with decode progress."""
        if self._continuous:
            self._tick_no += 1
            with span("engine.tick", spans=self.spans,
                      tick=self._tick_no, queue=len(self._queue)):
                if self._pipe:
                    return self._tick_pipelined()
                return self._tick_continuous()
        batch = self._form_batch()
        if not batch:
            return False
        self._process_batch(batch)
        return True

    def start(self) -> "InferenceEngine":
        with self._lock:
            if self._thread is not None:
                return self
            self._stop_flag = False
            self._thread = threading.Thread(target=self._worker,
                                            daemon=True,
                                            name="inference-engine")
            self._thread.start()
        return self

    def stop(self, drain: bool = True) -> None:
        with self._cv:
            self._accepting = not drain and self._accepting
            self._stop_flag = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if drain:
            self.run_pending()
        self._accepting = False

    # ------------------------------------------------------------------
    # graceful drain / cancel (ISSUE-9: the fleet router's per-replica
    # hooks — but just as useful standalone)
    # ------------------------------------------------------------------
    def drain(self, wait: bool = True,
              timeout: Optional[float] = None) -> "InferenceEngine":
        """Close admissions IMMEDIATELY — `submit()` raises
        `EngineDraining` and `ready()` (hence `/readyz`) reports
        not-ready from this instant, NOT from when the last resident
        finishes — while queued and resident requests keep decoding to
        completion. With ``wait`` the call blocks until the engine is
        drained (driving the work on the caller thread when no worker
        thread is running). `resume()` reopens admissions; the rolling
        weight-reload dance is ``drain() → reload_weights() →
        resume()`` (serving/fleet.py does it fleet-wide)."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()
        if wait:
            if self._thread is None:
                self.run_pending()
            else:
                deadline = (None if timeout is None
                            else time.monotonic() + timeout)
                while not self.drained():
                    if (deadline is not None
                            and time.monotonic() > deadline):
                        raise TimeoutError(
                            f"engine did not drain within {timeout}s")
                    time.sleep(0.002)
        return self

    def drained(self) -> bool:
        """True when no request is queued, resident, or pending commit
        in the tick pipeline."""
        with self._lock:
            return (not self._queue
                    and not self._pending
                    and all(s is None for s in self._slots))

    def draining(self) -> bool:
        return self._draining

    def resume(self) -> None:
        """Reopen admissions after a `drain()` (no-op when stopped)."""
        with self._cv:
            self._draining = False
            self._cv.notify_all()

    def cancel(self, handle: RequestHandle) -> bool:
        """Best-effort cancel: a queued request is shed immediately, an
        in-flight one at its next chunk boundary (the slot frees at the
        following reap). Terminal handles are untouched (returns
        False). The shed is typed `RequestCancelled` and counted under
        ``serving_requests_shed_total{reason="cancelled"}`` — the
        fleet router's first-winner-cancels hedging relies on this."""
        with self._lock:
            if handle.done():
                return False
            handle._cancelled = True
            try:
                self._queue.remove(handle)
            except ValueError:
                return True      # in-flight: chunk boundary sheds it
        self._m_shed_cancelled.inc()
        handle._finish(RequestStatus.SHED, RequestCancelled(
            f"request {handle.rid} cancelled while queued"))
        return True

    def _worker(self) -> None:
        while True:
            with self._cv:
                while (not self._queue and not self._pool_busy()
                       and not self._stop_flag):
                    self._cv.wait(0.05)
                if self._stop_flag:
                    return
            # coalescing window: let near-simultaneous submissions
            # join — but never stall an actively decoding slot pool
            # (admissions happen at the next chunk boundary anyway),
            # and never sleep when the queue can already fill every
            # free slot (ISSUE-10 satellite: there is nothing left to
            # coalesce, so the wait was pure TTFT latency)
            if (self.config.batch_timeout_s > 0
                    and not self._pool_busy()
                    and not self._queue_fills_pool()):
                time.sleep(self.config.batch_timeout_s)
            self.tick()

    def _pool_busy(self) -> bool:
        return self._continuous and any(s is not None
                                        for s in self._slots)

    def _queue_fills_pool(self) -> bool:
        """True when waiting cannot improve the next scheduling round:
        the queue already holds at least as many requests as there are
        seats to fill (free slots in continuous mode, the coalescing
        cap in batch mode)."""
        with self._lock:
            if self._continuous:
                seats = sum(s is None for s in self._slots)
            else:
                seats = self.config.max_batch_size
            return len(self._queue) >= max(1, seats)

    def set_listeners(self, *listeners) -> None:
        """Attach train-listener-protocol observers: after every batch
        the engine calls `record_batch(batch_size)` (when present —
        PerformanceListener's hook) then `iteration_done(engine,
        batch_index, batch_latency_s)`."""
        self._listeners = list(listeners)

    # ------------------------------------------------------------------
    # batching
    # ------------------------------------------------------------------
    def _form_batch(self) -> List[RequestHandle]:
        """Pop the head request plus every queued request with the SAME
        prompt length, up to max_batch_size (no pad masking in the
        model, so mixed lengths cannot share a batch)."""
        with self._lock:
            if not self._queue:
                return []
            head = self._queue.popleft()
            t0 = head.prompt.shape[0]
            batch = [head]
            rest = deque()
            while self._queue and len(batch) < self.config.max_batch_size:
                r = self._queue.popleft()
                if r.prompt.shape[0] == t0:
                    batch.append(r)
                else:
                    rest.append(r)
            rest.extend(self._queue)
            self._queue = rest
            self._m_in_flight.inc(len(batch))
        self._m_batch_size.observe(len(batch))
        for r in batch:
            r.status = RequestStatus.RUNNING
            r.trace.add("admitted", batch_size=len(batch))
            self.slo.admitted(r.trace)
        return batch

    def _process_batch(self, batch: List[RequestHandle]) -> None:
        t_start = self._clock()
        params = self._params    # batch runs on the weights at start
        try:
            self._decode_loop(batch, params)
        finally:
            self._m_in_flight.dec(len(batch))
            self._m_batches.inc()
            idx = int(self._m_batches.value)
            latency = self._clock() - t_start
            self._m_batch_seconds.observe(latency)
            for l in self._listeners:
                if hasattr(l, "record_batch"):
                    l.record_batch(len(batch))
                try:
                    l.iteration_done(self, idx, latency)
                except Exception:     # listeners must not kill serving
                    log.exception("engine listener failed")

    def _decode_loop(self, batch: List[RequestHandle], params) -> None:
        self._shed_expired(batch)
        while True:
            active = [r for r in batch
                      if r.status == RequestStatus.RUNNING]
            if not active:
                return
            done = active[0].generated.shape[0]
            remaining = max(r.max_new_tokens - done for r in active)
            if remaining <= 0:
                for r in active:
                    self._complete(r)
                return
            n = remaining if self.config.decode_chunk <= 0 \
                else min(self.config.decode_chunk, remaining)
            prompts = np.stack(
                [np.concatenate([r.prompt, r.generated])
                 for r in active]).astype(np.int32)
            try:
                toks = self._invoke(params, prompts, n, active)
            except _BatchDecodeFailed as e:
                self._isolate(active, params, e)
                return
            for i, r in enumerate(active):
                need = min(n, r.max_new_tokens - done)
                self._commit_tokens(r, toks[i, :need], "decode_chunk")
                if r.generated.shape[0] >= r.max_new_tokens:
                    self._complete(r)
            self._shed_expired(batch)

    def _shed_expired(self, batch: Sequence[RequestHandle]) -> None:
        now = self._clock()
        for r in batch:
            if r.status not in (RequestStatus.RUNNING,
                                RequestStatus.QUEUED):
                continue
            if r._cancelled:
                # caller-cancelled (engine.cancel): shed at the chunk
                # boundary, slot freed at the next reap
                self._m_shed_cancelled.inc()
                r._finish(RequestStatus.SHED, RequestCancelled(
                    f"request {r.rid} cancelled with "
                    f"{r.generated.shape[0]} tokens decoded"))
                continue
            if (r.deadline_at is not None
                    and now > r.deadline_at):
                r.deadline_exceeded = True
                if r.on_deadline == "partial":
                    # return what we have; the rest of the batch moves on
                    self._complete(r)
                else:
                    self._m_shed_deadline.inc()
                    r._finish(RequestStatus.SHED, DeadlineExceeded(
                        f"request {r.rid} past deadline with "
                        f"{r.generated.shape[0]}/{r.max_new_tokens} "
                        "tokens decoded"))

    def _complete(self, r: RequestHandle) -> None:
        self._m_completed.inc()
        r._finish(RequestStatus.COMPLETED)

    def _commit_tokens(self, r: RequestHandle, toks: np.ndarray,
                       kind: str, **data) -> None:
        """The ONE place generated tokens land on a handle: appends
        the chunk, records the trace event (`prefill_done` /
        `decode_chunk`), and — on the request's FIRST generated token,
        in either scheduling mode — feeds TTFT to the SLO tracker
        (batch mode's first chunk is its first-token moment; without
        this, batch-mode TTFT would simply not exist)."""
        first = not r._generated
        hit_terminal = False
        if r._grammar is not None:
            # host-authoritative DFA advance (ISSUE-20): the device
            # mask made every token legal; the host walk is what
            # DECIDES — it truncates past the accepting terminal and
            # keeps r._cstate_host the single source of truth for
            # reseat/failover replay
            toks, hit_terminal = self._c_advance_commit(r, toks)
        r._generated.append(toks)
        self._tokens_committed += int(toks.shape[0])
        r._kv_seen = max(r._kv_seen, r.prompt.shape[0]
                         + sum(t.shape[0] for t in r._generated) - 1)
        ev = r.trace.add(kind, tokens=int(toks.shape[0]), **data)
        if first:
            self.slo.first_token(r.trace, ev.ts)
        if kind == "decode_chunk":
            # per-tenant decode billing (ISSUE-15): committed tokens x
            # the per-token analytic cost of the decode program that
            # produced them (prefill tokens bill at their own call
            # sites — a prefill_done's sampled token is prefill work)
            self.profiler.bill_tokens(r, self._decode_bill_label,
                                      int(toks.shape[0]), "decode")
        if hit_terminal and not r.done():
            r.trace.add("constraint", terminal=True,
                        state=int(r._cstate_host))
            self._m_c_terminal.inc()
            # grammar complete -> EOS: finish now unless the caller's
            # own `>= max_new_tokens` check is about to (then this
            # _complete would double-fire — it is not idempotent)
            if r.generated.shape[0] < r.max_new_tokens:
                self._complete(r)

    # ------------------------------------------------------------------
    # continuous batching: slot-pool scheduling
    # ------------------------------------------------------------------
    def _tick_continuous(self) -> bool:
        """One scheduling round. Legacy (prefill_chunk=None): admit
        into free slots (one fused prefill over the pool), then
        advance every occupied slot one decode chunk. Chunked
        (ISSUE-10): admissions merely SEAT (state PREFILLING), then
        the tick spends its token budget — prefill chunks for
        mid-prefill slots (oldest first, budget = tick_token_budget
        minus the decode bill) followed by ONE decode chunk for every
        DECODING slot — so no decode chunk ever waits longer than one
        budget's worth of prefill compute. Slots free the moment
        their request completes or is shed, so the next round refills
        them from the queue."""
        self._tick_perf0 = _perf()
        self._tick_sync_count = 0
        self.profiler.tick_begin()
        t_start = self._clock()
        params = self._params    # admissions + this chunk share a tree
        admitted = self._fill_slots()
        if self._prefill_chunk is not None:
            return self._tick_budgeted(admitted, params, t_start)
        if admitted:
            try:
                self._prefill_slots(admitted, params)
            except _BatchDecodeFailed as e:
                self._isolate_slots([r for _, r in admitted], e)
        # done-but-held slots (hold_kv, ISSUE-11) stay seated but must
        # never re-enter the decode round
        occupied = [(i, r) for i, r in self._occupied()
                    if not r.done()]
        if occupied:
            try:
                self._decode_chunk_slots(occupied, params)
            except _BatchDecodeFailed as e:
                self._isolate_slots([r for _, r in occupied], e)
            self._reap(shed=True)
        if not admitted and not occupied:
            return False
        self._m_batches.inc()
        n_active = len(occupied) or len(admitted)
        self._tick_epilogue(t_start, n_active)
        return True

    def _tick_epilogue(self, t_start: float, n_active: int) -> None:
        """Shared per-tick bookkeeping: batch-size/latency metrics,
        the device-idle estimate, + the train-listener protocol."""
        nowp = _perf()
        wall = nowp - self._tick_perf0
        if self._busy_since is not None:
            # a dispatch chain is still outstanding (pipelined tick):
            # fold the elapsed busy interval into THIS tick and roll
            # the marker forward into the next one
            self._tick_busy_s += nowp - self._busy_since
            self._busy_since = nowp
        if wall > 0:
            self._last_idle = min(1.0, max(
                0.0, 1.0 - self._tick_busy_s / wall))
        # device-time attribution (ISSUE-15): this tick's busy
        # interval splits across the programs dispatched in it
        self.profiler.tick_end(self._tick_busy_s)
        self._busy_total_s += self._tick_busy_s
        self._tick_busy_s = 0.0
        self._last_tick_syncs = self._tick_sync_count
        self._m_batch_size.observe(n_active)
        idx = int(self._m_batches.value)
        latency = self._clock() - t_start
        self._m_batch_seconds.observe(latency)
        with span("engine.tick.listeners", spans=self.spans,
                  listeners=len(self._listeners)):
            for l in self._listeners:
                if hasattr(l, "record_batch"):
                    l.record_batch(n_active)
                try:
                    l.iteration_done(self, idx, latency)
                except Exception:     # listeners must not kill serving
                    log.exception("engine listener failed")

    # ------------------------------------------------------------------
    # chunked prefill: the token-budget scheduler (ISSUE-10)
    # ------------------------------------------------------------------
    def _is_prefilling(self, r: RequestHandle) -> bool:
        """Slot state PREFILLING: seated with pos short of its
        committed prefix — not yet sampling. Only a chunked engine
        ever observes it (one-shot prefill completes at admission)."""
        return (self._prefill_chunk is not None
                and getattr(r, "_prefill_pos", 0)
                < getattr(r, "_prefill_target", 0))

    def _tick_budgeted(self, admitted, params, t_start) -> bool:
        """The chunked scheduling round: decode's bill (one chunk per
        DECODING slot) is reserved off the top of tick_token_budget,
        the remainder buys prefill chunks oldest-first, then every
        decoding slot — including admissions whose final prefill chunk
        just landed — advances one decode chunk. The budget bounds the
        prefill work co-scheduled with any decode chunk, which bounds
        the residents' inter-token stall at ceil(budget/prefill_chunk)
        chunk latencies instead of the longest prompt's full prefill."""
        decoding0 = [(i, r) for i, r in self._occupied()
                     if not self._is_prefilling(r)]
        pf_budget = self._tick_budget - len(decoding0) * self._chunk
        pf_spent = self._advance_prefill(params, pf_budget)
        decoding = [(i, r) for i, r in self._occupied()
                    if not self._is_prefilling(r) and not r.done()]
        if decoding:
            try:
                self._decode_chunk_slots(decoding, params,
                                         prefill_tokens=pf_spent)
            except _BatchDecodeFailed as e:
                self._isolate_slots([r for _, r in decoding], e)
        self._reap(shed=True)
        if not admitted and not decoding and pf_spent == 0:
            return False            # idle tick: keep the last busy
        #                             tick's budget utilization
        self._last_tick_spent = pf_spent + len(decoding) * self._chunk
        self._m_batches.inc()
        self._tick_epilogue(t_start,
                            len(decoding) or len(admitted) or 1)
        return True

    def _advance_prefill(self, params, budget: int) -> int:
        """Spend up to ``budget`` prompt tokens advancing PREFILLING
        slots, oldest admission first (admission order == queue order
        — the _fill_slots micro-assert — so TTFT stays fair). Each
        compiled call advances a subset of slots by up to
        prefill_chunk tokens each; partial chunks spend the budget to
        the token. When decode's bill already exhausted the budget,
        the oldest admission still advances ONE chunk (progress
        floor — prefill can never starve). Returns tokens spent.

        Weighted fair share (ISSUE-16, tenant_weights set): the tick's
        prefill budget is first CREDITED to each backlogged tenant's
        deficit counter by weight (idle tenants get nothing — their
        share rolls to the backlogged), then slots are served
        highest-deficit tenant first (oldest admission within a
        tenant) and every granted token is charged back. A tenant the
        budget shortchanges this tick carries positive deficit into
        the next, so a backlogged tenant can never be starved however
        heavy its neighbors' traffic is."""
        if self._prefill_chunk is None:
            return 0
        qos = self._qos_weights is not None
        if qos:
            self._qos_credit(budget)
        spent = 0
        floor_used = False
        while True:
            prefilling = sorted(
                ((i, r) for i, r in self._occupied()
                 if self._is_prefilling(r) and not r.done()),
                key=lambda e: e[1]._seat_seq)
            if not prefilling:
                break
            if qos:
                # stable sort: highest owed tenant first, admission
                # order (the seat_seq sort above) within a tenant
                prefilling.sort(key=lambda e: -self._qos_deficit.get(
                    e[1].tenant or "default", 0.0))
            rem = budget - spent
            floor = False
            if rem < 1:
                if spent > 0 or floor_used:
                    break
                # progress floor: one chunk for the oldest admission
                # (under fair share: the most-owed tenant's oldest)
                floor_used = floor = True
                rem = self._prefill_chunk
                prefilling = prefilling[:1]
            plan = []
            if qos and not floor:
                # true deficit round-robin: a tenant's grant this pass
                # is CAPPED by what it is owed, so a heavyweight
                # tenant drains multiple chunks (one per compiled
                # call) before a lightweight one sees the budget —
                # ordering alone would still split the plan evenly
                owed = dict(self._qos_deficit)
                for i, r in prefilling:
                    if rem < 1:
                        break
                    t = r.tenant or "default"
                    cap = owed.get(t, 0.0)
                    if cap < 1.0:
                        continue
                    n = min(self._prefill_chunk,
                            r._prefill_target - r._prefill_pos, rem,
                            int(cap))
                    plan.append((i, r, n))
                    rem -= n
                    owed[t] = cap - n
            if not plan:
                # every owed deficit is spent (or fair share is off):
                # WORK CONSERVATION — the leftover budget serves
                # slots in (deficit-, then admission-) order anyway
                for i, r in prefilling:
                    if rem < 1:
                        break
                    n = min(self._prefill_chunk,
                            r._prefill_target - r._prefill_pos, rem)
                    plan.append((i, r, n))
                    rem -= n
            try:
                self._prefill_chunk_call(plan, params)
            except _BatchDecodeFailed as e:
                self._isolate_slots([r for _, r, _ in plan], e)
                continue
            if qos:
                for i, r, n in plan:
                    t = r.tenant or "default"
                    self._qos_deficit[t] = (
                        self._qos_deficit.get(t, 0.0) - n)
                    self._m_qos_prefill_tokens.labels(
                        self._qos_label(r.tenant)).inc(int(n))
            spent += sum(n for _, _, n in plan)
        return spent

    # ------------------------------------------------------------------
    # tenant QoS helpers (ISSUE-16)
    # ------------------------------------------------------------------
    def _qos_weight(self, tenant: str) -> float:
        return self._qos_weights.get(
            tenant, float(self.config.qos_default_weight))

    def _qos_label(self, tenant: Optional[str]) -> str:
        """Bounded metric label for a tenant id: first tenant_top_n
        distinct ids get their own label, later ones fold into
        "other" (same cardinality bound as the cost meter)."""
        t = "default" if tenant is None else tenant
        seen = self._qos_tenants_seen
        if t in seen:
            return t
        if len(seen) < self.config.tenant_top_n:
            seen.add(t)
            return t
        return "other"

    def _qos_credit(self, budget: int) -> None:
        """Divide this tick's prefill budget across BACKLOGGED
        tenants by weight. A tenant with no prefilling slot loses its
        counter entirely (no banking: an idle tenant's share rolls to
        the backlogged within the tick it was idle), so deficits
        measure only live, unserved demand."""
        backlogged = {r.tenant or "default"
                      for _, r in self._occupied()
                      if self._is_prefilling(r) and not r.done()}
        for t in list(self._qos_deficit):
            if t not in backlogged:
                del self._qos_deficit[t]
        if not backlogged or budget <= 0:
            return
        total = sum(self._qos_weight(t) for t in backlogged)
        for t in backlogged:
            self._qos_deficit[t] = (
                self._qos_deficit.get(t, 0.0)
                + budget * self._qos_weight(t) / total)

    def _prefill_chunk_call(self, plan, params) -> None:
        """One guarded chunked-prefill call advancing ``plan``
        [(slot, handle, n_tokens)]: feeds each slot its next prompt
        slice, marks final chunks so the program samples the first
        generated token, and commits `prefill_done` (+ completion /
        prefix-cache insertion) for slots whose prefill just finished."""
        self._ensure_state()
        entries = [(i, r) for i, r, _ in plan]
        c = self._prefill_chunk
        toks = np.zeros((self._num_slots, c), np.int32)
        clen = np.zeros((self._num_slots,), np.int32)
        start = np.zeros((self._num_slots,), np.int32)
        lastm = np.zeros((self._num_slots,), bool)
        for i, r, n in plan:
            pre = np.concatenate([r.prompt, r.generated]
                                 ).astype(np.int32)
            toks[i, :n] = pre[r._prefill_pos:r._prefill_pos + n]
            clen[i] = n
            start[i] = r._prefill_pos
            lastm[i] = (r._prefill_pos + n >= r._prefill_target)
        state = self._slot_state
        key = self._root_key()
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        cext = () if cjar is None else cjar.ops
        fkw = (self._quant_kwargs() if cjar is None
               else {**self._quant_kwargs(), **self._ckey_kw()})
        if self._paged:
            with self._lock:
                self._ensure_writable(entries, prefill=True)
                self._maybe_corrupt_page(entries, prefill=True)
                bt = self._bt.copy()
                state = self._slot_state
            name, factory = (
                ("paged_chunked_prefill", _compiled_paged_chunked_prefill)
                if cjar is None else
                ("paged_chunked_prefill_c",
                 _compiled_paged_chunked_prefill_c))
            fn = self._resolve_program(
                name, factory,
                (astuple(self.cfg), self.mesh, c, self._num_slots,
                 self._page_size, self._max_pages, self._num_pages,
                 float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p)),
                fkw,
                (params, *state, bt, toks, clen, start, lastm, *cext,
                 key))
            extra = (bt,)
        else:
            name, factory = (
                ("chunked_prefill", _compiled_chunked_prefill)
                if cjar is None else
                ("chunked_prefill_c", _compiled_chunked_prefill_c))
            fn = self._resolve_program(
                name, factory,
                (astuple(self.cfg), self.mesh, c, self._num_slots,
                 float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p)),
                fkw,
                (params, *state, toks, clen, start, lastm, *cext,
                 key))
            extra = ()
        n_state = len(state)

        def call():
            o = fn(params, *state, *extra, toks, clen, start, lastm,
                   *cext, key)
            if cjar is not None:
                cjar.out, o = o[-1], o[:-1]
            return tuple(o[:n_state]), self._out_sync(o[n_state])

        state, first = self._guarded(
            call, [r for _, r in entries], self._m_prefill_seconds,
            self._prefill_work(name, [(r, r._prefill_pos, n)
                                      for _, r, n in plan]),
            prefill=True, chunked=True)
        if cjar is not None:
            self._cmask_commit(cjar)
        self._slot_state = state
        # per-tenant prefill billing (ISSUE-15): the chunk tokens each
        # slot actually advanced this call (partial chunks bill to the
        # token; a prefix-hit resume never re-bills the cached prefix)
        bill_label = ("paged_chunked_prefill" if self._paged
                      else "chunked_prefill")
        for i, r, n in plan:
            self.profiler.bill_tokens(r, bill_label, int(n),
                                      "prefill")
        finished = []
        for i, r, n in plan:
            with self._lock:
                if self._slots[i] is not r:   # preempted by a reload
                    continue
            r._prefill_pos += n
            self._m_prefill_chunks.inc()
            if r._prefill_pos >= r._prefill_target:
                finished.append((i, r))
        if self._pipe_defer:
            # double-buffered dispatch (ISSUE-12): chunk progress is
            # host scheduling state and advances NOW; the finished
            # slots' first tokens commit at the next tick's sync
            for i, r in finished:
                r._pending_n += 1
            if self._paged and finished:
                self._cache_prefilled(finished)
            self._pipe_items.append(
                ("prefill_chunk", list(plan), first, finished))
            return
        for i, r in finished:
            self._commit_tokens(
                r, np.asarray([first[i]], np.int32),
                "prefill_done", slot=i,
                prefill_chunk=self._prefill_chunk)
            if r.generated.shape[0] >= r.max_new_tokens:
                self._complete(r)
        if self._paged and finished:
            # the prompt's pages only hold complete KV once the FINAL
            # chunk lands — mid-prefill pages must never be shareable
            self._cache_prefilled(finished)
        self._reap()

    # ------------------------------------------------------------------
    # the double-buffered tick loop (ISSUE-12)
    # ------------------------------------------------------------------
    def _tick_pipelined(self) -> bool:
        """One double-buffered scheduling round: seat admissions,
        DISPATCH this tick's prefill/decode calls without blocking
        (jax async dispatch — the device starts immediately), then
        commit the PREVIOUS tick's outputs at the single sync point —
        so the host's admission assembly, runtime-data building, trace
        /SLO accounting, and listener work all overlap device compute
        instead of serializing after it. The schedule runs exactly one
        tick ahead of the committed values: plain-decode and
        chunked-prefill token COUNTS are deterministic (min(chunk,
        remaining) / the chunk plan), so active/rem masks, write
        ranges, and completion predictions never need the token
        VALUES — which are observed only after sync, preserving the
        committed-prefix contract every failure path (deadline,
        cancel, isolation, reload, fleet failover) is built on."""
        self._tick_perf0 = _perf()
        self._tick_sync_count = 0
        self.profiler.tick_begin()
        t_start = self._clock()
        params = self._params
        admitted = self._fill_slots()
        pending = self._dispatch_tick(admitted, params)
        prev = self._pending.popleft() if self._pending else None
        if pending is not None:
            self._pending.append(pending)
        if prev is not None:
            self._commit_tick(prev)
        self._reap(shed=True)
        if pending is None and prev is None and not admitted:
            # a tick that ADMITTED but dispatched nothing (the whole
            # admission wave was isolated away) still did work — the
            # queue behind it must get the next round
            return False
        self._m_batches.inc()
        self._tick_epilogue(t_start,
                            (pending.n_active if pending else 0) or 1)
        return True

    def _dispatch_tick(self, admitted, params) -> "Optional[_PendingTick]":
        """Dispatch one tick's compiled calls without syncing their
        outputs; returns the pending record to commit next tick (None
        when there was nothing to dispatch)."""
        self._pipe_in_state = self._slot_state
        # constraint-state snapshot (ISSUE-20 x ISSUE-12): the DFA
        # vector + unconsumed seeds BEFORE this tick's dispatches —
        # the recovery point `_recover_failed_tick` restores alongside
        # the KV snapshot, so a failed pipelined tick rolls the device
        # DFA back to the last committed-consistent view
        c_in = ((self._cstate, dict(self._cseed_pending))
                if self._constrain_active else None)
        self._pipe_items = []
        self._pipe_defer = True
        try:
            with span("engine.tick.dispatch", spans=self.spans):
                if self._prefill_chunk is not None:
                    n_active = self._dispatch_budgeted(admitted, params)
                else:
                    n_active = self._dispatch_oneshot(admitted, params)
        finally:
            self._pipe_defer = False
            items, self._pipe_items = self._pipe_items, None
        if not items:
            return None
        return _PendingTick(items=items, in_state=self._pipe_in_state,
                            n_active=n_active, c_in_state=c_in,
                            tick=self._tick_no)

    def _sched_decoding(self) -> List[tuple]:
        """Slots eligible for this tick's decode dispatch under the
        SCHEDULED view: seated, not terminal, past prefill, and with
        budget left after the tokens already in flight."""
        return [(i, r) for i, r in self._occupied()
                if not r.done() and not self._is_prefilling(r)
                and (r.generated.shape[0] + r._pending_n
                     < r.max_new_tokens)]

    def _dispatch_oneshot(self, admitted, params) -> int:
        if admitted:
            self._ensure_state()
            try:
                call = (self._call_prefill_paged if self._paged
                        else self._call_prefill)
                state, first = call(params, self._slot_state, admitted)
            except _BatchDecodeFailed as e:
                with self._lock:
                    for i, r in admitted:
                        if self._slots[i] is r:
                            self._free_slot(i)
                self._isolate_slots([r for _, r in admitted], e)
                admitted = []
            else:
                self._slot_state = state
                for i, r in admitted:
                    r._pending_n += 1
                if self._paged:
                    # page indices are host bookkeeping; the rows land
                    # before any reader because every later dispatch
                    # chains on this call's output state
                    self._cache_prefilled(admitted)
                self._pipe_items.append(
                    ("prefill", list(admitted), first))
        decoding = self._sched_decoding()
        if decoding:
            self._ensure_state()
            self._dispatch_decode(decoding, params, {})
        return len(decoding) or len(admitted)

    def _dispatch_budgeted(self, admitted, params) -> int:
        decoding0 = [(i, r) for i, r in self._occupied()
                     if not self._is_prefilling(r)]
        pf_budget = self._tick_budget - len(decoding0) * self._chunk
        pf_spent = self._advance_prefill(params, pf_budget)
        decoding = self._sched_decoding()
        if decoding:
            self._dispatch_decode(decoding, params,
                                  {"prefill_chunk": int(pf_spent)})
        self._last_tick_spent = pf_spent + len(decoding) * self._chunk
        return len(decoding) or len(admitted)

    def _dispatch_decode(self, decoding, params, data: dict) -> None:
        if (self._spec and not self._qos_spec_off
                and self._spec_tick()):
            self._dispatch_spec(decoding, params, data)
            return
        try:
            call = (self._call_chunk_paged if self._paged
                    else self._call_chunk)
            state, toks = call(params, self._slot_state, decoding)
        except _BatchDecodeFailed as e:
            self._isolate_slots([r for _, r in decoding], e)
            return
        self._slot_state = state
        needs = []
        for i, r in decoding:
            n = min(self._chunk, r.max_new_tokens
                    - r.generated.shape[0] - r._pending_n)
            needs.append(int(n))
            r._pending_n += int(n)
        self._pipe_items.append(
            ("decode", list(decoding), toks, needs, data))

    def _dispatch_spec(self, decoding, params, data: dict) -> None:
        """Schedule-ahead speculative dispatch (ISSUE-19): acceptance
        makes a round's commit COUNT nondeterministic, so the one-
        ahead schedule reserves the WORST CASE — K+1 tokens per slot
        charged to `_pending_n`, so rem/budget masks and the next
        tick's eligibility treat the whole window as spent — and the
        commit boundary reconciles actual acceptance, releasing the
        unused reservation. Token VALUES stay bit-identical to the
        synchronous spec engine because sampling is position-keyed:
        a conservative rem mask can only move a round boundary, never
        change the concatenated stream. K is whatever the LAST commit
        decided (`_spec_update` runs at commit), so this dispatch
        never depends on uncommitted values."""
        call = (self._call_spec_paged if self._paged
                else self._call_spec)
        k1 = self._spec_cur_k + 1
        try:
            state, toks, nc, drafted, accepted, poison = call(
                params, self._slot_state, decoding)
        except _BatchDecodeFailed as e:
            self._isolate_slots([r for _, r in decoding], e)
            return
        self._slot_state = state
        reserved = []
        for i, r in decoding:
            n = min(k1, r.max_new_tokens
                    - r.generated.shape[0] - r._pending_n)
            reserved.append(int(n))
            r._pending_n += int(n)
        self._pipe_items.append(
            ("spec", list(decoding), (toks, nc, drafted, accepted),
             reserved,
             dict(data, poison=poison, step=self._step_counter - 1,
                  bill=self._decode_bill_label)))

    def _commit_tick(self, prev: "_PendingTick") -> None:
        """Sync a pending tick's outputs (the ONE blocking sync) and
        commit them in dispatch order: prefill first tokens, then
        decode chunks — exactly what the synchronous tick would have
        committed, one tick later."""
        with span("engine.tick.commit", spans=self.spans,
                  commits_tick=prev.tick):
            n0 = self._tokens_committed
            self._commit_tick_items(prev)
            annotate(tokens=self._tokens_committed - n0)

    def _commit_tick_items(self, prev: "_PendingTick") -> None:
        # a speculative item's deferred outputs are a TUPLE (toks,
        # ncommit, drafted, accepted); flatten across items so the
        # whole tick still drains through ONE blocking sync
        flat, spans = [], []
        for it in prev.items:
            out = it[2] if isinstance(it[2], tuple) else (it[2],)
            spans.append(len(out))
            flat.extend(out)
        try:
            with span("engine.tick.commit.sync", spans=self.spans):
                drained = self._block_on_many(flat)
        except RuntimeError as e:
            self._recover_failed_tick(prev, e)
            return
        synced, at = [], 0
        for n in spans:
            synced.append(tuple(drained[at:at + n]) if n > 1
                          else drained[at])
            at += n
        for it, arr in zip(prev.items, synced):
            kind = it[0]
            if kind == "prefill":
                for i, r in it[1]:
                    with self._lock:
                        live = self._slots[i] is r
                    r._pending_n = max(0, r._pending_n - 1)
                    if not live or r.done():
                        continue
                    self._commit_tokens(
                        r, np.asarray([arr[i]], np.int32),
                        "prefill_done", slot=i)
                    if r.generated.shape[0] >= r.max_new_tokens:
                        self._complete(r)
            elif kind == "prefill_chunk":
                for i, r in it[3]:
                    with self._lock:
                        live = self._slots[i] is r
                    r._pending_n = max(0, r._pending_n - 1)
                    if not live or r.done():
                        continue
                    self._commit_tokens(
                        r, np.asarray([arr[i]], np.int32),
                        "prefill_done", slot=i,
                        prefill_chunk=self._prefill_chunk)
                    if r.generated.shape[0] >= r.max_new_tokens:
                        self._complete(r)
            elif kind == "spec":
                # schedule-ahead reconcile (ISSUE-19): the dispatch
                # reserved a worst-case K+1 window per slot; the
                # actual acceptance commits 1..K+1 tokens, and the
                # unused reservation is released here — priced into
                # serving_spec_schedule_waste_tokens_total. The
                # adaptive-K controller (and its plain-decode
                # fallback) also runs HERE, so the NEXT dispatch's K
                # was always decided at a commit boundary and the
                # one-ahead schedule stays deterministic.
                entries, reserved = it[1], it[3]
                toks, nc, drafted, accepted = arr
                data = dict(it[4])
                poison = data.pop("poison")
                step = data.pop("step")
                bill = data.pop("bill")
                cur_bill = self._decode_bill_label
                self._decode_bill_label = bill
                try:
                    for (i, r), n_res in zip(entries, reserved):
                        with self._lock:
                            live = self._slots[i] is r
                        r._pending_n = max(0, r._pending_n - n_res)
                        if not live or r.done() or n_res <= 0:
                            continue
                        d_i = int(drafted[i])
                        a_i = int(accepted[i])
                        self._m_spec_drafted.inc(d_i)
                        self._m_spec_accepted.inc(a_i)
                        if d_i and a_i == 0:
                            r.trace.add("draft_rejected", step=step,
                                        drafted=d_i,
                                        poisoned=bool(poison[i]))
                        need = min(int(nc[i]), r.max_new_tokens
                                   - r.generated.shape[0])
                        if self._m_spec_waste is not None:
                            self._m_spec_waste.inc(
                                max(0, n_res - need))
                        self._commit_tokens(
                            r, toks[i, :need].astype(np.int32),
                            "decode_chunk", slot=i, drafted=d_i,
                            accepted=a_i, **data)
                        if r.generated.shape[0] >= r.max_new_tokens:
                            self._complete(r)
                finally:
                    self._decode_bill_label = cur_bill
                self._spec_update(entries, drafted, accepted, poison)
            else:                    # ("decode", entries, _, needs, d)
                entries, needs, data = it[1], it[3], it[4]
                for (i, r), n in zip(entries, needs):
                    with self._lock:
                        live = self._slots[i] is r
                    r._pending_n = max(0, r._pending_n - n)
                    if not live or r.done() or n <= 0:
                        continue
                    self._commit_tokens(
                        r, arr[i, :n].astype(np.int32),
                        "decode_chunk", slot=i, **data)
                    if r.generated.shape[0] >= r.max_new_tokens:
                        self._complete(r)

    def _recover_failed_tick(self, prev: "_PendingTick", err) -> None:
        """A pipelined tick's outputs failed AT SYNC (an async device
        fault surfacing after dispatch): restore the slot state
        snapshotted before the tick's first dispatch — the last
        committed-consistent device state — drop every later dispatch
        (it consumed the failed outputs), flush the prefix cache
        (pages inserted at dispatch may hold the failed call's rows),
        and hand every implicated request to slot isolation, whose
        scratch-pool solo re-runs resume from the COMMITTED prefix:
        token-exact, the same guarantee as a synchronous step
        failure."""
        log.warning("pipelined tick failed at sync (%s); recovering "
                    "from last committed state", err)
        records = [prev] + list(self._pending)
        self._pending.clear()
        reqs, seen = [], set()
        for rec in records:
            for it in rec.items:
                ent = ([(i, r) for i, r, _ in it[1]]
                       if it[0] == "prefill_chunk" else it[1])
                for i, r in ent:
                    if id(r) not in seen:
                        seen.add(id(r))
                        reqs.append(r)
        self._m_ticks_recovered.inc()
        with span("engine.tick.recover", spans=self.spans,
                  requests=len(reqs), error=type(err).__name__):
            self._restore_committed(prev, reqs, err)

    def _restore_committed(self, prev: "_PendingTick", reqs, err) -> None:
        self._slot_state = prev.in_state
        if prev.c_in_state is not None:
            # roll the device DFA back with the KV: restore the
            # pre-tick state vector, then merge seeds — snapshot
            # first, so a seed recorded AFTER the dispatch (a reseat
            # racing the failure) still wins
            cstate, seeds = prev.c_in_state
            self._cstate = cstate
            merged = dict(seeds)
            merged.update(self._cseed_pending)
            self._cseed_pending = merged
        if self._prefix_cache is not None:
            flushed = self._prefix_cache.flush()
            if flushed:
                self._m_prefix_evictions.inc(flushed)
        self._isolate_slots(reqs, _BatchDecodeFailed(str(err)))

    def _flush_pipeline(self, reason: Optional[str] = None) -> None:
        """Commit any dispatched-but-uncommitted tick NOW — KV export
        and other committed-view consumers call this before reading
        slot state. A ``reason`` stamps the forced sync (ISSUE-19
        satellite): the blocking wait the CALLER caused is recorded
        into serving_pipeline_flush_seconds{reason} and surfaced as
        tick_pipeline.last_flush in debugz(), so cross-tier handoff
        cost under pipelining is attributable instead of invisible."""
        if not self._pending:
            return
        t0 = _perf()
        while self._pending:
            self._commit_tick(self._pending.popleft())
        if reason is not None:
            dt = _perf() - t0
            if self._m_flush_seconds is not None:
                self._m_flush_seconds.labels(reason).observe(dt)
            self._last_flush = {"reason": reason,
                                "seconds": round(dt, 6)}

    def _fill_slots(self) -> List[tuple]:
        """Admission at a chunk boundary: seat queued requests into
        free slots (deadline-expired ones are shed or completed
        partial instead of seated). Paged engines additionally map the
        longest cached token prefix into the slot's block table and
        allocate private pages for the rest — when the free list (plus
        LRU eviction) cannot cover it, admission BLOCKS (the request
        returns to the queue head) rather than corrupting resident
        pages. Returns [(slot, handle)].

        Priority preemption (ISSUE-16, preemption_budget > 0): before
        seating, queued higher-priority requests with no free seat
        evict the lowest-priority residents (bounded per tick), and
        the queue is served highest class first. preemption_budget=0
        keeps FIFO seating bit-identically."""
        admitted = []
        with span("engine.tick.admit", spans=self.spans), self._lock:
            if self._preempt_budget > 0:
                self._preempt_for_priority_locked()
            # deque cursor, not list.pop(0) (ISSUE-10 satellite): the
            # old quadratic pop also made it easy to perturb seating
            # order; the popleft cursor is order-stable by construction
            free = deque(i for i in range(self._num_slots)
                         if self._slots[i] is None)
            n_free = len(free)
            seated_order: List[RequestHandle] = []
            while free and self._queue:
                r = self._pop_request_locked()
                self._shed_expired([r])
                if r.done():
                    continue
                i = free[0]
                hit = 0
                adopted = False
                if (r._kv is not None
                        and getattr(r._kv, "source", "slot")
                        == "cache"):
                    # KV migration (ISSUE-14): the handoff seeds the
                    # radix cache, then admission proceeds as a NORMAL
                    # paged seat that hits the just-seeded chain — a
                    # failed seed (pool full, weights skew, malformed
                    # chain) costs one normal prefill, never
                    # correctness
                    self._seed_cached_chain(r._kv)
                    r._kv = None
                if r._kv is not None:
                    # cross-tier KV adoption (ISSUE-11): seat by
                    # device-putting the handed-off rows into fresh
                    # pages — no prefill call for this request
                    seated = self._seat_adopted(i, r)
                    if seated is None:
                        # pool exhausted: block at the queue head,
                        # exactly like a fresh paged admission —
                        # unless _seat_adopted already shed it
                        if not r.done():
                            self._queue.appendleft(r)
                            self._admission_blocked("pages", r)
                        break
                    if r.done():
                        continue     # shed typed "handoff" at seating
                    adopted = True
                elif self._paged:
                    seated = self._seat_paged(i, r)
                    if seated is None:
                        # pool exhausted: block (requeue at the head)
                        # — unless _seat_paged already shed a request
                        # that could never fit
                        if not r.done():
                            self._queue.appendleft(r)
                            self._admission_blocked("pages", r)
                        break
                    hit = seated
                free.popleft()
                if not adopted:
                    seated_order.append(r)
                self._slots[i] = r
                if self._constrain_active:
                    # (re)seat: overwrite whatever DFA state the
                    # slot's previous occupant left on device. The
                    # seed is replayed from the COMMITTED prefix, so
                    # requeue/failover/adoption resume token-exact;
                    # unconstrained occupants seed row 0 (all-allow)
                    self._cseed_pending[i] = self._c_state_for(r)
                if self._spec:
                    # seat with the engine's CURRENT belief, not blind
                    # optimism: under adversarial traffic a stream of
                    # fresh admissions must not drag the pool EMA back
                    # up and re-trigger expensive high-K rounds
                    self._accept_ema[i] = self._accept_pool
                r.status = RequestStatus.RUNNING
                r._in_flight = True
                # chunked prefill (ISSUE-10): the slot seats in the
                # PREFILLING state — pos starts at the prefix-cache
                # hit boundary and advances chunk by chunk toward the
                # committed prefix; re-seated (preempted) requests
                # reset here, so a resume always re-prefills from its
                # committed prefix, never from stale chunk progress
                r._seat_seq = next(self._seat_seq)
                r._pending_n = 0
                r._prefill_pos = int(hit)
                r._prefill_target = int(r.prompt.shape[0]
                                        + r.generated.shape[0])
                if not adopted:
                    self._m_prefill_tokens.inc(
                        max(0, r._prefill_target - r._prefill_pos))
                self._m_in_flight.inc()
                extra = ({"prefill_chunk": self._prefill_chunk}
                         if self._prefill_chunk is not None else {})
                if adopted:
                    # the whole committed prefix arrived via the
                    # handoff: no prefill call, no bucket — the slot
                    # goes straight to DECODING (pos/tok were set by
                    # the adopt program)
                    r._prefill_pos = r._prefill_target
                    r.trace.add("admitted", slot=i, bucket=0,
                                adopted=True, prefix_hit_tokens=int(
                                    r._prefill_target - 1), **extra)
                    self.slo.admitted(r.trace)
                    continue
                r.trace.add("admitted", slot=i, bucket=int(
                    self._bucket_len(r.prompt.shape[0]
                                     + r.generated.shape[0] - hit)),
                    prefix_hit_tokens=int(hit), **extra)
                self.slo.admitted(r.trace)
                admitted.append((i, r))
            # micro-assert (ISSUE-10 satellite): admission order IS
            # queue order — the TTFT-fairness claim the oldest-first
            # prefill scheduler builds on
            assert [r for _, r in admitted] == seated_order, \
                "admission order diverged from queue order"
            if not free and self._queue:
                self._admission_blocked("slots", self._queue[0])
            annotate(seated=n_free - len(free), prefix_tokens=sum(
                r._prefill_pos for _, r in admitted))
        return admitted

    def _admission_blocked(self, reason: str, r: RequestHandle) -> None:
        """The queue's head stays unseated this round: `pages` (the
        pool, after eviction, cannot cover it) or `slots` (none free)."""
        self._m_blocked[reason].inc()
        mark("engine.admit.blocked", spans=self.spans, reason=reason,
             rid=r.rid)

    def _pop_request_locked(self) -> RequestHandle:
        """Next request to seat. FIFO unless priority preemption is on
        (preemption_budget > 0), in which case the FIRST request of
        the HIGHEST priority class is served — FIFO within a class,
        and bit-identical to plain popleft when everything is class 0."""
        q = self._queue
        if self._preempt_budget <= 0 or len(q) <= 1:
            return q.popleft()
        best = max(range(len(q)), key=lambda j: (q[j].priority, -j))
        if best == 0:
            return q.popleft()
        r = q[best]
        del q[best]
        return r

    def _preempt_for_priority_locked(self) -> None:
        """Evict low-priority residents so queued HIGHER-priority
        requests can seat this tick, at most preemption_budget
        evictions per tick (a priority storm degrades to ordinary
        queueing instead of thrashing the slot pool). Eviction rides
        the reload/failover path — freed slot, QUEUED at the head,
        token-exact resume from the committed prefix — and picks the
        lowest-priority resident, youngest seat first (least sunk
        prefill work). A waiter only ever displaces a STRICTLY lower
        class, so equal-priority traffic can never thrash."""
        budget = self._preempt_budget
        free_n = sum(s is None for s in self._slots)
        waiting = sorted((r for r in self._queue
                          if r.priority > 0 and not r.done()),
                         key=lambda r: -r.priority)
        for w in waiting:
            if budget <= 0:
                break
            if free_n > 0:
                free_n -= 1      # a free seat serves this waiter
                continue
            residents = [(i, r) for i, r in enumerate(self._slots)
                         if r is not None and not r.done()
                         and not r._hold_kv]
            if not residents:
                break
            i, v = min(residents,
                       key=lambda e: (e[1].priority, -e[1]._seat_seq))
            if v.priority >= w.priority:
                break            # nothing strictly lower to displace
            self._free_slot(i)
            v.status = RequestStatus.QUEUED
            v._pending_n = 0     # dispatched-but-uncommitted tokens
            #                      are re-decoded after the resume
            self._leave_flight(v)
            self._m_preempted.inc()
            self._m_qos_preemptions.labels(
                self._qos_label(v.tenant)).inc()
            v.trace.add("preempted", reason="priority",
                        by=int(w.rid), slot=i)
            self._queue.appendleft(v)
            budget -= 1
            # the freed seat belongs to THIS waiter: do not count it
            # toward free_n or the next waiter would double-spend it

    # ------------------------------------------------------------------
    # paged KV: host page bookkeeping (all under self._lock)
    # ------------------------------------------------------------------
    def _alloc_page(self) -> Optional[int]:
        """One private page, LRU-evicting unreferenced prefix-cache
        entries when the free list runs dry."""
        p = self._allocator.alloc()
        if p is None and self._prefix_cache is not None:
            freed = self._prefix_cache.evict(1)
            if freed:
                self._m_prefix_evictions.inc(freed)
                p = self._allocator.alloc()
        return p

    def _seat_paged(self, i: int, r: RequestHandle) -> Optional[int]:
        """Build slot ``i``'s block table for request ``r``: map the
        longest cached prefix chain (refcount bumped per sharer),
        allocate private pages for the suffix + full decode budget,
        and copy-on-write the boundary page when a full-prefix hit
        forces re-computing the last token inside a shared page.
        Returns the prefix-hit token count, or None when the pool
        cannot cover the request (admission must block). A blocked
        request that could NEVER fit (nothing left to evict, no slot
        holding pages) is shed instead — waiting would deadlock."""
        self._ensure_state()
        prefix = np.concatenate([r.prompt, r.generated]).astype(np.int32)
        plen = int(prefix.shape[0])
        total = plen + (r.max_new_tokens - int(r.generated.shape[0]))
        need = pages_for(total, self._page_size)
        ps = self._page_size
        matched: List[int] = []
        if self._prefix_cache is not None:
            matched = self._prefix_cache.match(prefix)
        m = len(matched) * ps
        cow_src = None
        if m >= plen:                 # full-prefix hit: recompute the
            m = plen - 1              # last token — COW its page
            cow_src = matched[-1]
            matched = matched[:-1]
        # claim the shared chain first so eviction can't reap it while
        # we allocate the private tail
        for p in matched:
            self._allocator.incref(p)
        if cow_src is not None:
            self._allocator.incref(cow_src)
        fresh: List[int] = []
        for _ in range(need - len(matched)):
            p = self._alloc_page()
            if p is None:
                for q in fresh:
                    self._allocator.decref(q)
                for q in matched:
                    self._allocator.decref(q)
                if cow_src is not None:
                    self._allocator.decref(cow_src)
                if not any(pgs for pgs in self._slot_pages):
                    # nothing else holds pages and eviction is dry:
                    # blocking would deadlock — shed with a typed error
                    self._m_shed_overload.inc()
                    r._finish(RequestStatus.SHED, OverloadError(
                        f"request {r.rid} needs {need} KV pages; the "
                        f"pool cannot free enough "
                        f"({self._allocator.pages_free} free)"))
                return None
            fresh.append(p)
        pages = matched + fresh
        if cow_src is not None:
            # materialize the divergent copy BEFORE any write lands:
            # the shared page keeps serving its other readers
            self._copy_page(cow_src, pages[len(matched)])
            self._allocator.decref(cow_src)
        self._slot_pages[i] = pages
        self._bt[i, :] = 0
        self._bt[i, :len(pages)] = pages
        r._page_start = m
        if self._prefix_cache is not None:
            if m > 0:
                self._m_prefix_hits.inc()
                self._m_prefix_shared_tokens.inc(m)
            else:
                self._m_prefix_misses.inc()
        return m

    # ------------------------------------------------------------------
    # cross-tier KV handoff: export / adopt (ISSUE-11)
    # ------------------------------------------------------------------
    def _shed_handoff(self, r: RequestHandle, msg: str) -> None:
        """The typed handoff shed: ``shed{reason="handoff"}`` on the
        trace, the lazily-created reason="handoff" counter child, and
        a `HandoffError` on the handle — the satellite contract."""
        r._handoff_failed = True
        self._m_shed.labels("handoff").inc()
        if self._paged:
            self._m_adoptions.labels("shed").inc()
        r._finish(RequestStatus.SHED, HandoffError(msg))

    def _seat_adopted(self, i: int, r: RequestHandle) -> Optional[bool]:
        """Seat request ``r`` into slot ``i`` by adopting its
        `KVHandoff` (caller holds the lock): allocate a fresh private
        page chain for the whole committed-prefix + decode budget
        (all-or-nothing), scatter the handed-off rows + scales into it,
        and point the slot's pos/tok at the committed prefix — decode
        resumes token-exactly with no prefill call. Returns True on
        success, None when the pool cannot cover it (admission BLOCKS
        at the queue head, exactly like a fresh paged admission — a
        near-full pool never corrupts residents), and sheds typed
        ``handoff`` — decref'ing every page this adoption claimed —
        on validation failure, injected adoption faults, or a failed
        adopt call (the `_free_slot`-style refcount audit)."""
        kv = r._kv
        self._ensure_state()
        prefix = np.concatenate([r.prompt, r.generated]).astype(np.int32)
        plen = int(prefix.shape[0])
        # hard alignment check: the handoff must be exactly one
        # pending token short of the committed prefix, with its
        # pending token equal to the prefix's last token — anything
        # else means the rows do not describe this request's text, and
        # decoding over them would be silently wrong
        if kv.pos != plen - 1 or int(kv.tok) != int(prefix[-1]) \
                or kv.k.shape[1] != kv.pos:
            self._shed_handoff(
                r, f"request {r.rid}: KV handoff misaligned "
                   f"(pos={kv.pos} rows={kv.k.shape[1]} vs committed "
                   f"prefix {plen}, tok={kv.tok} vs {int(prefix[-1])})")
            return False
        inj = self._injector
        if (inj is not None and hasattr(inj, "check_adopt")
                and inj.check_adopt(r.rid)):
            self._shed_handoff(
                r, f"request {r.rid}: injected adoption fault")
            return False
        total = plen + (r.max_new_tokens - int(r.generated.shape[0]))
        need = pages_for(total, self._page_size)
        fresh: List[int] = []
        for _ in range(need):
            p = self._alloc_page()
            if p is None:
                self._allocator.release_chain(fresh)   # no partial claim
                if not any(pgs for pgs in self._slot_pages):
                    # nothing else holds pages and eviction is dry:
                    # blocking would deadlock — shed typed "handoff"
                    self._shed_handoff(
                        r, f"request {r.rid} needs {need} KV pages to "
                           f"adopt its handoff; the pool cannot free "
                           f"enough ({self._allocator.pages_free} "
                           "free)")
                    return False
                self._m_adoptions.labels("blocked").inc()
                return None
            fresh.append(p)
        try:
            self._adopt_rows(fresh, kv, i)
        except Exception as e:
            # the decref audit on the handoff error path: every page
            # this adoption claimed goes back before the shed
            self._allocator.release_chain(fresh)
            self._shed_handoff(
                r, f"request {r.rid}: KV adopt call failed: {e}")
            return False
        self._slot_pages[i] = fresh
        self._bt[i, :] = 0
        self._bt[i, :len(fresh)] = fresh
        r._page_start = plen - 1
        r._kv = None                 # adopted: drop the host copy
        self._m_adoptions.labels("ok").inc()
        if self._prefix_cache is not None and kv.pos > 0:
            # the adopted prompt rows are complete KV — cache the full
            # pages so co-tenant decode-tier traffic sharing the
            # prefix maps them instead of re-prefilling (the cache
            # becomes a co-owner via refcount, as after any prefill)
            self._prefix_cache.insert(prefix[:kv.pos], fresh)
        return True

    def _handoff_bucket(self, npages: int) -> int:
        """Power-of-two page-count bucket for one handoff's geometry
        (quant/kv.py `handoff_page_bucket`): transfer and scatter cost
        scale with the chain, program count stays log2-bounded."""
        from deeplearning4j_tpu.quant.kv import handoff_page_bucket
        return handoff_page_bucket(npages, self._max_pages)

    def _handoff_row_buffers(self, kv: KVHandoff,
                             npages: int) -> List[np.ndarray]:
        """Pad a handoff's rows (and scales, which travel with their
        rows) to the bucketed [L, npages * page_size, ...] geometry
        and reshape to page granularity — the runtime-data form both
        adopt programs scatter from (quant/kv.py owns the layout)."""
        from deeplearning4j_tpu.quant.kv import handoff_row_buffers
        pool, _ = self._pool_arrays()
        return handoff_row_buffers(kv, self.cfg.n_layers, npages,
                                   self._page_size, pool[0].dtype)

    def _state_geom(self, npages: int = 0) -> tuple:
        """Shape/dtype signature of the live slot state plus the
        handoff bucket — the geometry component of the adopt/export
        program cache keys, so AOT executables resolved through
        `_resolve_program` never collide across engines with
        different pools in one process."""
        return (npages,) + tuple(
            (tuple(a.shape), str(a.dtype)) for a in self._slot_state)

    def _page_index_vectors(self, pages: List[int],
                            size: int) -> tuple:
        idx = np.zeros((size,), np.int32)
        idx[:len(pages)] = pages
        valid = np.zeros((size,), bool)
        valid[:len(pages)] = True
        return idx, valid

    def _adopt_rows(self, pages: List[int], kv: KVHandoff,
                    slot: int) -> None:
        """Device-put the handed-off rows into the prefix's pages:
        rows (and scales, which travel with their rows) are padded to
        the bucketed page-granular geometry and scattered through ONE
        batched all-layer program — resolved via `_resolve_program`,
        so the launch is visible in serving_compiles_total{program=
        "kv_adopt"}, AOT-cacheable, and costed by the profiler.
        Page indices are runtime data — adoption never recompiles
        within a bucket. Pages past the committed prefix are left for
        decode to write (a row is always rewritten before it is
        attended, the same invariant plain decode relies on)."""
        pool, _ = self._pool_arrays()
        nb = self._handoff_bucket(
            pages_for(max(int(kv.pos), 1), self._page_size))
        rows = self._handoff_row_buffers(kv, nb)
        idx, valid = self._page_index_vectors(pages[:nb], nb)
        args = (idx, valid, np.int32(slot), np.int32(kv.pos),
                np.int32(kv.tok), *rows, *self._slot_state)
        fn = self._resolve_program(
            "kv_adopt", _compiled_kv_adopt,
            (len(pool), self.mesh, self._state_geom(nb)), {}, args)
        self._slot_state = tuple(fn(*args))

    def export_slot_kv(self, handle: RequestHandle,
                       release: bool = True) -> KVHandoff:
        """Host-gather request ``handle``'s committed KV out of its
        (still seated — submit with ``hold_kv=True``) slot: K/V rows
        for positions [0, pos) plus per-row scales when the pool is
        quantized, bit-exact slices of the live pool. ``release`` frees
        the held slot afterwards (always, via finally — a failed
        export must not leak the seat). Raises `HandoffError` when the
        handle is not resident or still mid-prefill."""
        try:
            # a pipelined engine's committed view trails one tick:
            # commit the pending dispatch before gathering (the wait
            # is billed to serving_pipeline_flush_seconds{reason})
            self._flush_pipeline(reason="export_slot_kv")
            with self._lock:
                slot = next((i for i, r in enumerate(self._slots)
                             if r is handle), None)
                if slot is None:
                    raise HandoffError(
                        f"request {handle.rid} is not resident — "
                        "nothing to export (was it submitted with "
                        "hold_kv=True?)")
                if self._is_prefilling(handle):
                    raise HandoffError(
                        f"request {handle.rid} is mid-prefill: its KV "
                        "rows are incomplete")
                if self._slot_state is None:
                    raise HandoffError("slot pool not allocated")
                state = self._slot_state        # immutable snapshot
                pages = (list(self._slot_pages[slot]) if self._paged
                         else None)
            import jax.numpy as jnp
            pos = int(np.asarray(state[-2])[slot])
            tok = int(np.asarray(state[-1])[slot])
            pool = state[:-2]
            if self._paged:
                nb = self._handoff_bucket(len(pages))
                idx = np.zeros((nb,), np.int32)
                idx[:len(pages)] = pages
                args = (jnp.asarray(idx), *pool)
                fn = self._resolve_program(
                    "page_gather", _compiled_page_gather,
                    (len(pool), self.mesh, self._state_geom(nb)),
                    {}, args)
                planes = fn(*args)
                # [L, nb, ps, X] -> [L, nb*ps, X] -> the committed
                # rows (the bucketed gather moves ~chain bytes, not
                # the pool's max_pages capacity)
                planes = [np.asarray(a).reshape(
                    self.cfg.n_layers, -1, a.shape[-1])[:, :pos]
                    for a in planes]
            else:
                args = (np.int32(slot), *pool)
                fn = self._resolve_program(
                    "slot_gather", _compiled_slot_gather,
                    (len(pool), self.mesh, self._state_geom()),
                    {}, args)
                planes = fn(*args)
                planes = [np.asarray(a)[:, :pos] for a in planes]
            k, v = planes[0], planes[1]
            ksc = planes[2] if self._kv_mode else None
            vsc = planes[3] if self._kv_mode else None
            return KVHandoff(pos=pos, tok=tok, k=k, v=v, k_scale=ksc,
                             v_scale=vsc, kv_mode=self._kv_mode,
                             n_layers=self.cfg.n_layers,
                             d_model=self.cfg.d_model)
        finally:
            if release:
                self.release_held(handle)

    def release_held(self, handle: RequestHandle) -> bool:
        """Free a slot held past completion by ``hold_kv=True``
        (idempotent). The pages decref; whatever the prefix cache
        co-owns stays resident for the next tenant."""
        with self._lock:
            handle._hold_kv = False
            for i, r in enumerate(self._slots):
                if r is handle and r.done():
                    self._free_slot(i)
                    self._leave_flight(r)
                    return True
        return False

    def export_cached_chain(self,
                            chain_hash: int) -> Optional[KVHandoff]:
        """Host-gather a radix-prefix-cache chain by its advertised
        chain hash (ISSUE-14): the fleet router's KV-migration source.
        Returns a ``source="cache"`` `KVHandoff` carrying the chain's
        K/V rows (+ per-row scales on quantized pools, bit-exact
        slices), its token ids, and this engine's weights version —
        or None when the chain is no longer cached (evicted since the
        advertisement) or the pool was never materialized. A None here
        costs the caller one normal prefill, never correctness."""
        if not (self._continuous and self._paged
                and self._prefix_cache is not None):
            return None
        self._flush_pipeline(reason="export_cached_chain")
        with self._lock:
            node = self._prefix_cache.node_for_hash(chain_hash)
            if node is None or self._slot_state is None:
                return None
            pages = self._prefix_cache.chain_pages(node)
            tokens = self._prefix_cache.chain_tokens(node)
            import jax.numpy as jnp
            pos = len(pages) * self._page_size
            pool = self._slot_state[:-2]
            nb = self._handoff_bucket(len(pages))
            idx = np.zeros((nb,), np.int32)
            idx[:len(pages)] = pages
            args = (jnp.asarray(idx), *pool)
            fn = self._resolve_program(
                "page_gather", _compiled_page_gather,
                (len(pool), self.mesh, self._state_geom(nb)),
                {}, args)
            planes = fn(*args)
            planes = [np.asarray(a).reshape(
                self.cfg.n_layers, -1, a.shape[-1])[:, :pos]
                for a in planes]
        return KVHandoff(
            pos=pos, tok=int(tokens[-1]),
            k=planes[0], v=planes[1],
            k_scale=planes[2] if self._kv_mode else None,
            v_scale=planes[3] if self._kv_mode else None,
            kv_mode=self._kv_mode, n_layers=self.cfg.n_layers,
            d_model=self.cfg.d_model, source="cache", tokens=tokens,
            weights_step=self._weights_step)

    def _seed_cached_chain(self, kv: KVHandoff) -> bool:
        """Adopt a migrated ``source="cache"`` handoff INTO the radix
        prefix cache (caller holds the lock): allocate fresh pages for
        the chain (all-or-nothing), scatter the rows through the
        pool-only adopt program (no slot's pos/tok is touched — the
        chain seeds the CACHE, not a seat), and insert tokens->pages
        so the very next admission sharing the prefix maps them as an
        ordinary prefix hit. Every failure path returns False with
        nothing claimed — the request just prefills normally."""
        cache = self._prefix_cache
        ps = self._page_size
        npages = kv.pos // ps
        tokens = (np.asarray(kv.tokens, np.int32)
                  if kv.tokens is not None else None)
        if (cache is None or tokens is None or npages < 1
                or kv.pos % ps != 0
                or int(tokens.shape[0]) != kv.pos
                or kv.k.shape[1] != kv.pos):
            self._m_adoptions.labels("seed_failed").inc()
            return False
        if kv.weights_step != self._weights_step:
            # cached K/V encodes the weights that wrote it: a seed
            # from a different weights version would be silently wrong
            log.warning("cache-chain seed refused: exporter weights "
                        "step %s vs local %s", kv.weights_step,
                        self._weights_step)
            self._m_adoptions.labels("seed_failed").inc()
            return False
        self._ensure_state()
        pages: List[int] = []
        for _ in range(npages):
            p = self._alloc_page()
            if p is None:
                self._allocator.release_chain(pages)  # no partial claim
                self._m_adoptions.labels("seed_failed").inc()
                return False
            pages.append(p)
        try:
            pool_n = len(self._slot_state) - 2
            nb = self._handoff_bucket(npages)
            rows = self._handoff_row_buffers(kv, nb)
            idx, valid = self._page_index_vectors(pages, nb)
            args = (idx, valid, *rows, *self._slot_state[:-2])
            fn = self._resolve_program(
                "chain_adopt", _compiled_chain_adopt,
                (pool_n, self.mesh, self._state_geom(nb)), {}, args)
            out = fn(*args)
            self._slot_state = (*out, *self._slot_state[-2:])
        except Exception as e:
            self._allocator.release_chain(pages)
            self._m_adoptions.labels("seed_failed").inc()
            log.warning("cache-chain seed scatter failed: %s", e)
            return False
        cache.insert(tokens, pages)
        # the cache co-owns what it adopted; drop our claim (chunks it
        # already had keep their older page — ours just frees)
        self._allocator.release_chain(pages)
        self._m_adoptions.labels("seeded").inc()
        return True

    def seed_cached_chain(self, kv: KVHandoff) -> bool:
        """Public cache-seed entry (ISSUE-17): adopt a
        ``source="cache"`` handoff — decoded off the wire or exported
        by a peer — into this engine's radix prefix cache. The fleet
        router's proactive-migration sink: autoscale-up pushes the
        fleet's hottest chains here before traffic lands. Returns
        False (nothing claimed, next request prefills normally) when
        this engine cannot host cached chains or the seed fails."""
        if not (self._continuous and self._paged
                and self._prefix_cache is not None):
            return False
        with self._lock:
            return self._seed_cached_chain(kv)

    def set_advertised_chains(self, hashes) -> int:
        """Install the fleet-advertised chain-hash set (ISSUE-17):
        the radix cache biases LRU eviction away from these, so a
        chain the router is actively routing by is not the first
        casualty of a local pool squeeze. Returns the set size
        installed (0 when there is no prefix cache)."""
        if self._prefix_cache is None:
            return 0
        with self._lock:
            return self._prefix_cache.set_advertised(hashes)

    def committed_kv_pages(self, handle: RequestHandle) -> int:
        """KV pages request ``handle``'s slot currently references —
        what fleet_worker.py reports in its progress lines (0 for
        non-resident requests and unpaged pools)."""
        with self._lock:
            if not self._paged:
                return 0
            for i, r in enumerate(self._slots):
                if r is handle:
                    return len(self._slot_pages[i])
        return 0

    def _pool_arrays(self):
        """The page-indexed leading arrays of the slot state (kp, vp
        [+ kscale, vscale]) — pos/tok trail them."""
        return self._slot_state[:-2], self._slot_state[-2:]

    def _copy_page(self, src: int, dst: int) -> None:
        pool, rest = self._pool_arrays()
        out = _compiled_page_copy(len(pool))(
            np.int32(src), np.int32(dst), *pool)
        self._slot_state = (*out, *rest)

    def _poison_page(self, pg: int) -> None:
        pool, rest = self._pool_arrays()
        out = _compiled_page_poison(len(pool))(np.int32(pg), *pool)
        self._slot_state = (*out, *rest)

    def _release_slot_pages(self, i: int) -> None:
        self._allocator.release_chain(self._slot_pages[i])
        self._slot_pages[i] = []
        self._bt[i, :] = 0

    def _free_slot(self, i: int) -> None:
        """The ONE place a slot is vacated: paged engines return the
        slot's pages to the refcount pool (pages the prefix cache or a
        co-resident slot still references live on — quarantining a
        sharer can never free a reader's pages)."""
        self._slots[i] = None
        if self._paged:
            self._release_slot_pages(i)

    def _write_range(self, r: RequestHandle,
                     prefill: bool) -> tuple:
        """The logical [lo, hi) positions the next compiled call will
        write for ``r``: the un-cached prefix tail for a prefill, the
        next decode chunk otherwise (a generated token's K/V row is
        written when the token is FED, so decoding writes start at
        committed-length - 1)."""
        plen = int(r.prompt.shape[0] + r.generated.shape[0]
                   + r._pending_n)
        if prefill:
            if self._prefill_chunk is not None:
                # chunked prefill writes at most one chunk from the
                # slot's resume position
                lo = int(getattr(r, "_prefill_pos", 0))
                return lo, min(lo + self._prefill_chunk,
                               int(getattr(r, "_prefill_target",
                                           plen)))
            return getattr(r, "_page_start", 0), plen
        lo = plen - 1
        span = self._chunk
        if (self._spec and self._spec_plain == 0
                and not self._qos_spec_off):
            # a speculative round writes the whole K+1-token verify
            # window (rejected rows included) — the COW guard must
            # privatize every page it can touch. Under schedule-ahead
            # dispatch (ISSUE-19) the round's start position is only
            # known to within the in-flight reservation: the device
            # may have advanced by anywhere from 1 to _pending_n
            # tokens when this round executes, so the guard widens to
            # the worst-case union of every possible window.
            span = self._spec_cur_k + 1
            if r._pending_n > 0:
                lo = max(0, plen - 1 - r._pending_n)
                span = r._pending_n + self._spec_cur_k + 1
        return lo, min(lo + span,
                       int(r.prompt.shape[0]) + r.max_new_tokens)

    def _ensure_writable(self, entries, prefill: bool) -> None:
        """Copy-on-write guard before every compiled call that writes:
        any physical page backing an entry's write range that is still
        SHARED (refcount > 1) is copied to a fresh private page first.
        Admission already privatizes the ranges it can foresee, so
        this is the invariant's backstop — no write ever lands on a
        page another slot or the prefix cache references."""
        ps = self._page_size
        for i, r in entries:
            lo, hi = self._write_range(r, prefill)
            if hi <= lo:
                continue
            for lp in range(lo // ps, (hi - 1) // ps + 1):
                if lp >= len(self._slot_pages[i]):
                    continue
                p = self._slot_pages[i][lp]
                if self._allocator.refcount(p) > 1:
                    fresh = self._alloc_page()
                    if fresh is None:
                        raise RuntimeError(
                            f"copy-on-write for slot {i} page {lp}: "
                            "page pool exhausted")
                    self._copy_page(p, fresh)
                    self._allocator.decref(p)
                    self._slot_pages[i][lp] = fresh
                    self._bt[i, lp] = fresh

    def _maybe_corrupt_page(self, entries, prefill: bool) -> None:
        """ServingFaultInjector.corrupt_page_at hook: poison the named
        request's next-write page (post-COW, so provably private) —
        the shared-page isolation proof."""
        inj = self._injector
        if inj is None or not hasattr(inj, "check_corrupt_page"):
            return
        rid = inj.check_corrupt_page(self._step_counter)
        if rid is None:
            return
        for i, r in entries:
            if r.rid == rid and self._slot_pages[i]:
                lp = self._write_range(r, prefill)[0] // self._page_size
                lp = min(lp, len(self._slot_pages[i]) - 1)
                self._poison_page(self._slot_pages[i][lp])
                inj.pages_corrupted += 1
                log.warning("injected corruption: request %d slot %d "
                            "page %d poisoned", rid, i,
                            self._slot_pages[i][lp])
                return

    def _occupied(self) -> List[tuple]:
        with self._lock:
            return [(i, r) for i, r in enumerate(self._slots)
                    if r is not None]

    def _ensure_state(self) -> None:
        if self._slot_state is None:
            if self._paged:
                self._slot_state = init_paged_state(
                    self.cfg, self.mesh, self._num_slots,
                    self._page_size, self._num_pages,
                    kv_mode=self._kv_mode)
            else:
                self._slot_state = init_slot_state(
                    self.cfg, self.mesh, self._num_slots,
                    kv_mode=self._kv_mode)

    def _quant_kwargs(self) -> dict:
        """Compiled-program cache key extension: only present when a
        quantization mode is on, so unquantized engines keep sharing
        cache entries with direct (legacy-signature) callers."""
        kw = {}
        if self._qmode:
            kw["quantized"] = self._qmode
        if self._kv_mode:
            kw["kv_mode"] = self._kv_mode
        return kw

    def _ckey_kw(self) -> dict:
        """Masked-program cache key extension: masked programs lower
        against this engine's ``[constrain_state_cap, V]`` tables, so
        the cap is geometry — an engine with a custom cap must never
        reuse an executable compiled for another cap's table shape."""
        return {"constrain_cap": int(self.config.constrain_state_cap)}

    def _root_key(self):
        if self._key is None:
            import jax
            self._key = jax.random.PRNGKey(self.config.seed)
        return self._key

    # ------------------------------------------------------------------
    # host-sync discipline + compiled-program resolution (ISSUE-12)
    # ------------------------------------------------------------------
    def _busy_mark(self) -> None:
        """Mark the device busy from now until the sync that drains
        every outstanding dispatch — the interval estimate behind
        serving_device_idle_fraction."""
        if self._busy_since is None:
            self._busy_since = _perf()

    def _sync_done(self, t0: float) -> None:
        now = _perf()
        self._last_sync_s = now - t0
        self._syncs_total += 1
        self._tick_sync_count += 1
        if self._busy_since is not None and not self._pending:
            self._tick_busy_s += now - self._busy_since
            self._busy_since = None

    def _block_on(self, x) -> np.ndarray:
        """ONE of the two device->host sync points on the tick path
        (with `_block_on_many`): every `np.asarray` a scheduling round
        performs funnels through here, so the double-buffered loop's
        "<= 1 blocking sync per tick" contract is countable, and the
        sync wait feeds the device-idle estimate."""
        t0 = _perf()
        out = np.asarray(x)
        self._sync_done(t0)
        return out

    def _block_on_many(self, xs: Sequence) -> List[np.ndarray]:
        """Sync a whole pending tick's outputs as ONE blocking event
        (the first conversion waits on the chain; the rest are ready)."""
        t0 = _perf()
        out = [np.asarray(x) for x in xs]
        self._sync_done(t0)
        return out

    def _out_sync(self, x):
        """Output-conversion seam of the compiled-call wrappers: the
        synchronous engine blocks here per call (the PR-11 contract,
        bit-identical); a pipelined dispatch defers the block to the
        NEXT tick's commit."""
        if self._pipe_defer:
            return x
        return self._block_on(x)

    def _out_sync_many(self, xs) -> list:
        """`_out_sync` for a compiled call with several host-bound
        outputs (the speculative round's toks/ncommit/drafted/
        accepted): ONE blocking sync when synchronous, the raw device
        values under a pipelined dispatch — the next tick's commit
        drains them with the rest of the tick."""
        if self._pipe_defer:
            return list(xs)
        return self._block_on_many(xs)

    def _resolve_program(self, program: str, factory, fargs: tuple,
                         fkw: dict, example_args: Optional[tuple]):
        """Resolve one compiled serving program through the cache
        stack: in-memory program cache (the geometry-keyed factories)
        -> persistent AOT compile cache -> jit trace+lower+compile.
        Continuous-mode programs have FIXED shapes per geometry, so
        they resolve to a concrete compiled executable (jax AOT
        `lower().compile()`) that is memoized on the factory entry,
        timed into serving_compile_seconds{program}, counted into
        serving_compiles_total{program,source}, and — when
        ``compile_cache_dir`` is set — serialized to disk so the next
        process loads instead of compiling. ``example_args=None``
        (batch-mode generate: shapes vary per call) keeps the lazy jit
        path. A program that does not lower or compile RAISES here: the
        un-compiled callable would fail the same way on every retry,
        and the caller (warmup, or the tick's guarded call) must see
        the real error instead of a quarantined request."""
        fn = factory(*fargs, **fkw)
        label = self._program_label(program, fargs)
        if example_args is None:
            # batch-mode generate: per-call shapes, no fixed geometry
            # to cost — invocations still count under the bare label
            self.profiler.dispatched(label)
            return fn
        ptokens = self._program_tokens(program, fargs)
        slot = factory.entry(*fargs, **fkw)
        exe = slot.get("exec")
        if exe is not None:
            self._profile_program(label, slot, exe, ptokens)
            if self._aot is not None:
                # resolved earlier in-process (possibly by an engine
                # without a cache dir): publish it so the NEXT process
                # still gets the load-not-compile cold start
                pub = ("published", str(self._aot.directory))
                if not slot.get(pub):
                    key = self._aot.entry_key(
                        program, self.mesh,
                        (fargs[0], *fargs[2:],
                         tuple(sorted(fkw.items()))))
                    if not self._aot.path(key).exists():
                        self._aot.store(key, exe,
                                        meta={"cost":
                                              slot.get("cost") or {}})
                    slot[pub] = True
            return exe
        key = None
        t0 = _perf()
        if self._aot is not None:
            # the disk key strips the mesh OBJECT (position 1 of every
            # factory signature) for its logical descriptor; the rest
            # of the geometry tuple is the in-memory cache key itself
            key = self._aot.entry_key(
                program, self.mesh,
                (fargs[0], *fargs[2:], tuple(sorted(fkw.items()))))
            exe, meta = self._aot.load_entry(
                key, list(self.mesh.devices.flat))
            if exe is not None:
                self._m_compile_seconds.labels(program).observe(
                    _perf() - t0)
                self._m_compiles.labels(program, "aot_cache").inc()
                slot["exec"] = exe
                # cost sidecar (ISSUE-15): persisted beside the cached
                # executable; pre-meta entries (rounds 17-19) degrade
                # to a lazy recompute from the LOADED executable —
                # never a cache miss
                if meta is not None and "cost" in meta:
                    slot["cost"] = dict(meta["cost"])
                self._profile_program(label, slot, exe, ptokens)
                slot[("published", str(self._aot.directory))] = True
                return exe
        exe = fn.lower(*example_args).compile()
        self._m_compile_seconds.labels(program).observe(_perf() - t0)
        self._m_compiles.labels(program, "jit").inc()
        slot["cost"] = cost_from_compiled(exe)
        if self._aot is not None and key is not None:
            self._aot.store(key, exe, meta={"cost": slot["cost"]})
            slot[("published", str(self._aot.directory))] = True
        slot["exec"] = exe
        self._profile_program(label, slot, exe, ptokens)
        return exe

    # ------------------------------------------------------------------
    # continuous profiling & cost attribution (ISSUE-15)
    # ------------------------------------------------------------------
    @staticmethod
    def _program_label(program: str, fargs: tuple) -> str:
        """Bounded-cardinality metric label for one compiled program:
        the program name, plus the bucket for admission prefills (the
        bucket ladder is log2-bounded) and K for speculative rounds —
        the geometries whose per-invocation cost genuinely differs."""
        if program in ("prefill", "paged_prefill", "prefill_c",
                       "paged_prefill_c"):
            return f"{program}_b{int(fargs[2])}"
        if program in ("spec_decode", "paged_spec_decode",
                       "spec_decode_c", "paged_spec_decode_c"):
            return f"{program}_k{int(fargs[2])}"
        return program

    def _program_tokens(self, program: str, fargs: tuple
                        ) -> Optional[int]:
        """Tokens one full invocation of ``program`` computes — the
        denominator of the per-token analytic cost. Every continuous
        program's factory signature carries (chunk-or-bucket,
        num_slots) at positions 2 and 3; a speculative round scores
        K+1 window positions per slot."""
        if program in ("decode", "paged_decode", "prefill",
                       "paged_prefill", "chunked_prefill",
                       "paged_chunked_prefill", "decode_c",
                       "paged_decode_c", "prefill_c",
                       "paged_prefill_c", "chunked_prefill_c",
                       "paged_chunked_prefill_c"):
            return int(fargs[2]) * int(fargs[3])
        if program in ("spec_decode", "paged_spec_decode",
                       "spec_decode_c", "paged_spec_decode_c"):
            return (int(fargs[2]) + 1) * int(fargs[3])
        return None

    def _profile_program(self, label: str, slot: dict, exe,
                         ptokens: Optional[int]) -> None:
        """Install ``label``'s cost into the profiler table (lazily
        recomputing the analysis from the executable when no sidecar
        survived) and record the dispatch for this tick's device-time
        attribution."""
        if self.profiler.enabled and not self.profiler.has_program(
                label):
            cost = slot.get("cost")
            if cost is None:
                cost = cost_from_compiled(exe)
                slot["cost"] = cost
            self.profiler.record_program(label, cost, ptokens)
        self.profiler.dispatched(label)

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> dict:
        """Resolve the engine's whole CLOSED compiled-program set up
        front — decode (or the adaptive-K speculative ladder), the
        admission-prefill bucket ladder (or the chunked-prefill
        program), paged twins as configured — so the first admission
        serves from warm programs. With a warm `compile_cache_dir`
        every resolution is an AOT LOAD: restart-to-ready collapses
        from the compile set's cost to the deserialize set's
        cost. Returns a report dict
        ({"seconds", "programs", "jit", "aot_cache"}), also kept on
        `engine.last_warmup` for debugz/health surfaces."""
        if not self._continuous:
            raise ValueError(
                "warmup requires mode='continuous' (batch-mode "
                "programs are shaped by per-call batch geometry)")
        t0 = _perf()

        def _totals():
            out = {"jit": 0.0, "aot_cache": 0.0}
            for labels, child in self._m_compiles.collect():
                if len(labels) == 2 and labels[1] in out:
                    out[labels[1]] += child.value
            return out

        before = _totals()
        self._ensure_state()
        params, state = self._params, self._slot_state
        key = self._root_key()
        ns = self._num_slots
        active = np.zeros((ns,), bool)
        rem = np.zeros((ns,), np.int32)
        qkw = self._quant_kwargs()
        cfgf = astuple(self.cfg)
        samp = (float(self.config.temperature),
                int(self.config.top_k), float(self.config.top_p))
        n_programs = 0
        if self._paged:
            bt = np.zeros((ns, self._max_pages), np.int32)
            pgeo = (self._page_size, self._max_pages, self._num_pages)
            self._resolve_program(
                "paged_decode", _compiled_paged_decode,
                (cfgf, self.mesh, self._chunk, ns, *pgeo, *samp), qkw,
                (params, *state, bt, active, rem, key))
            n_programs += 1
        else:
            self._resolve_program(
                "decode", _compiled_decode_chunk,
                (cfgf, self.mesh, self._chunk, ns, *samp), qkw,
                (params, *state, active, rem, key))
            n_programs += 1
        if self._spec:
            poison = np.zeros((ns,), bool)
            k = self._spec_k
            ks = []
            while k >= 1:
                ks.append(k)
                if k == 1:
                    break
                k = max(1, k // 2)
            for k in ks:
                skw = dict(qkw, draft_quantized=self._draft_qmode,
                           draft_layers=self._draft_layers)
                if self._paged:
                    self._resolve_program(
                        "paged_spec_decode", _compiled_paged_spec_decode,
                        (cfgf, self.mesh, k, ns, *pgeo, *samp), skw,
                        (params, self._draft_params, *state, bt, active,
                         rem, poison, key))
                else:
                    self._resolve_program(
                        "spec_decode", _compiled_spec_decode,
                        (cfgf, self.mesh, k, ns, *samp), skw,
                        (params, self._draft_params, *state, active,
                         rem, poison, key))
                n_programs += 1
        if self._prefill_chunk is not None:
            c = self._prefill_chunk
            toks = np.zeros((ns, c), np.int32)
            clen = np.zeros((ns,), np.int32)
            start = np.zeros((ns,), np.int32)
            lastm = np.zeros((ns,), bool)
            if self._paged:
                self._resolve_program(
                    "paged_chunked_prefill",
                    _compiled_paged_chunked_prefill,
                    (cfgf, self.mesh, c, ns, *pgeo, *samp), qkw,
                    (params, *state, bt, toks, clen, start, lastm,
                     key))
            else:
                self._resolve_program(
                    "chunked_prefill", _compiled_chunked_prefill,
                    (cfgf, self.mesh, c, ns, *samp), qkw,
                    (params, *state, toks, clen, start, lastm, key))
            n_programs += 1
        # the admission-prefill bucket ladder (one-shot engines; paged
        # engines warm the paged twin). The contiguous SCRATCH-pool
        # programs a paged/chunked engine's solo isolation would use
        # are deliberately NOT warmed: isolation is a failure path,
        # and warming them here would resolve contiguous programs
        # against this engine's differently-shaped pool state.
        if buckets is None:
            buckets = []
            b = max(1, self.config.prefill_bucket_min)
            while True:
                buckets.append(min(b, self.cfg.max_len))
                if b >= self.cfg.max_len:
                    break
                b *= 2
        for tb in dict.fromkeys(int(b) for b in buckets):
            prompts = np.zeros((ns, tb), np.int32)
            if self._paged:
                if self._prefill_chunk is None:
                    slen = np.zeros((ns,), np.int32)
                    st = np.zeros((ns,), np.int32)
                    self._resolve_program(
                        "paged_prefill", _compiled_paged_prefill,
                        (cfgf, self.mesh, tb, ns, *pgeo, *samp), qkw,
                        (params, *state, bt, prompts, slen, st, key))
                    n_programs += 1
                continue
            if self._prefill_chunk is None:
                plen = np.zeros((ns,), np.int32)
                self._resolve_program(
                    "prefill", _compiled_prefill,
                    (cfgf, self.mesh, tb, ns, *samp), qkw,
                    (params, *state, prompts, plen, key))
                n_programs += 1
        after = _totals()
        report = {"seconds": round(_perf() - t0, 4),
                  "programs": n_programs,
                  "jit": int(after["jit"] - before["jit"]),
                  "aot_cache": int(after["aot_cache"]
                                   - before["aot_cache"]),
                  "aot": (self._aot.stats()
                          if self._aot is not None else None)}
        self._last_warmup = report
        log.info("engine warmup: %d program(s) in %.3fs (%d compiled, "
                 "%d AOT-loaded)", n_programs, report["seconds"],
                 report["jit"], report["aot_cache"])
        return report

    @property
    def last_warmup(self) -> Optional[dict]:
        return self._last_warmup

    def _bucket_len(self, need: int) -> int:
        """Prefill bucket policy: the smallest power-of-two scaling of
        prefill_bucket_min that covers ``need``, capped at max_len.
        The compiled prefill program is keyed on the BUCKET, so all
        prompts rounding to one bucket share one program — the
        no-recompile guarantee under mixed-length traffic."""
        b = max(1, self.config.prefill_bucket_min)
        while b < need:
            b *= 2
        return min(b, self.cfg.max_len)

    def _call_prefill(self, params, state, entries):
        """One guarded fused admit+prefill over ``state`` for
        ``entries`` [(slot, handle)] — each entry's committed prefix
        (prompt + generated-so-far: requeued preempted requests resume
        mid-stream) is right-padded to the bucket. ``state`` is the
        opaque slot-state tuple (4 arrays float KV / 6 quantized KV).
        Returns (state', first_tokens)."""
        prefixes = {i: np.concatenate([r.prompt, r.generated]
                                      ).astype(np.int32)
                    for i, r in entries}
        tb = self._bucket_len(max(p.shape[0]
                                  for p in prefixes.values()))
        prompts = np.zeros((self._num_slots, tb), np.int32)
        plen = np.zeros((self._num_slots,), np.int32)
        for i, r in entries:
            pre = prefixes[i]
            prompts[i, :pre.shape[0]] = pre
            plen[i] = pre.shape[0]
        key = self._root_key()
        fargs = (astuple(self.cfg), self.mesh, int(tb),
                 self._num_slots, float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p))
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        if cjar is None:
            fn = self._resolve_program(
                "prefill", _compiled_prefill, fargs,
                self._quant_kwargs(),
                (params, *state, prompts, plen, key))
        else:
            fn = self._resolve_program(
                "prefill_c", _compiled_prefill_c, fargs,
                {**self._quant_kwargs(), **self._ckey_kw()},
                (params, *state, prompts, plen, *cjar.ops, key))
        n_state = len(state)

        def call():
            if cjar is None:
                o = fn(params, *state, prompts, plen, key)
            else:
                o = fn(params, *state, prompts, plen, *cjar.ops, key)
                cjar.out, o = o[-1], o[:-1]
            return tuple(o[:n_state]), self._out_sync(o[n_state])

        out = self._guarded(
            call, [r for _, r in entries], self._m_prefill_seconds,
            self._prefill_work(f"prefill_b{int(tb)}",
                               [(r, 0, int(plen[i]))
                                for i, r in entries]), prefill=True)
        if cjar is not None:
            self._cmask_commit(cjar)
        # per-tenant prefill billing (ISSUE-15): every prompt token
        # this call actually computed, at this bucket's analytic rate
        for i, r in entries:
            self.profiler.bill_tokens(r, f"prefill_b{int(tb)}",
                                      int(plen[i]), "prefill")
        return out

    def _call_chunk(self, params, state, entries):
        """One guarded decode chunk over ``state`` for the occupied
        ``entries``: per-slot budgets ride as the ``rem`` mask, so a
        slot finishing mid-chunk stops decoding on device. Returns
        (state', toks [Ns, chunk])."""
        active = np.zeros((self._num_slots,), bool)
        rem = np.zeros((self._num_slots,), np.int32)
        for i, r in entries:
            active[i] = True
            # scheduled-remaining (= committed-remaining when the tick
            # loop is synchronous: _pending_n is 0 outside a pipelined
            # dispatch) — the schedule-ahead contract of ISSUE-12
            rem[i] = (r.max_new_tokens - r.generated.shape[0]
                      - r._pending_n)
        key = self._root_key()
        fargs = (astuple(self.cfg), self.mesh, self._chunk,
                 self._num_slots, float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p))
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        if cjar is None:
            fn = self._resolve_program(
                "decode", _compiled_decode_chunk, fargs,
                self._quant_kwargs(),
                (params, *state, active, rem, key))
        else:
            fn = self._resolve_program(
                "decode_c", _compiled_decode_chunk_c, fargs,
                {**self._quant_kwargs(), **self._ckey_kw()},
                (params, *state, active, rem, *cjar.ops, key))
        self._decode_bill_label = "decode"
        n_state = len(state)

        def call():
            if cjar is None:
                o = fn(params, *state, active, rem, key)
            else:
                o = fn(params, *state, active, rem, *cjar.ops, key)
                cjar.out, o = o[-1], o[:-1]
            return tuple(o[:n_state]), self._out_sync(o[n_state])

        out = self._guarded(call, [r for _, r in entries],
                            self._m_step_seconds,
                            self._decode_work(entries, rem, self._chunk))
        if cjar is not None:
            self._cmask_commit(cjar)
        return out

    def _call_prefill_paged(self, params, state, entries):
        """Paged admission prefill: each entry's NOT-YET-CACHED suffix
        (committed prefix minus its prefix-cache hit), right-padded to
        the SUFFIX bucket — a full-prefix hit therefore prefills a
        1-token suffix instead of the whole prompt. The block table
        rides as runtime data. Returns (state', first_tokens)."""
        with self._lock:
            self._ensure_writable(entries, prefill=True)
            self._maybe_corrupt_page(entries, prefill=True)
            bt = self._bt.copy()
            state = self._slot_state
        suffix_map = {}
        for i, r in entries:
            pre = np.concatenate([r.prompt, r.generated]
                                 ).astype(np.int32)
            start = int(getattr(r, "_page_start", 0))
            suffix_map[i] = (start, pre[start:])
        tb = self._bucket_len(max(s.shape[0]
                                  for _, s in suffix_map.values()))
        suffix = np.zeros((self._num_slots, tb), np.int32)
        slen = np.zeros((self._num_slots,), np.int32)
        start = np.zeros((self._num_slots,), np.int32)
        for i, (st, tail) in suffix_map.items():
            suffix[i, :tail.shape[0]] = tail
            slen[i] = tail.shape[0]
            start[i] = st
        key = self._root_key()
        fargs = (astuple(self.cfg), self.mesh, int(tb),
                 self._num_slots, self._page_size, self._max_pages,
                 self._num_pages, float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p))
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        if cjar is None:
            fn = self._resolve_program(
                "paged_prefill", _compiled_paged_prefill, fargs,
                self._quant_kwargs(),
                (params, *state, bt, suffix, slen, start, key))
        else:
            fn = self._resolve_program(
                "paged_prefill_c", _compiled_paged_prefill_c, fargs,
                {**self._quant_kwargs(), **self._ckey_kw()},
                (params, *state, bt, suffix, slen, start, *cjar.ops,
                 key))
        n_state = len(state)

        def call():
            if cjar is None:
                o = fn(params, *state, bt, suffix, slen, start, key)
            else:
                o = fn(params, *state, bt, suffix, slen, start,
                       *cjar.ops, key)
                cjar.out, o = o[-1], o[:-1]
            return tuple(o[:n_state]), self._out_sync(o[n_state])

        out = self._guarded(
            call, [r for _, r in entries], self._m_prefill_seconds,
            self._prefill_work(f"paged_prefill_b{int(tb)}",
                               [(r, int(start[i]), int(slen[i]))
                                for i, r in entries]), prefill=True)
        if cjar is not None:
            self._cmask_commit(cjar)
        # per-tenant prefill billing (ISSUE-15): the SUFFIX lengths —
        # prefix-cache hits bill only the tokens actually recomputed
        for i, r in entries:
            self.profiler.bill_tokens(r, f"paged_prefill_b{int(tb)}",
                                      int(slen[i]), "prefill")
        return out

    def _call_chunk_paged(self, params, state, entries):
        """Paged decode chunk: contiguous contract + the block table
        as runtime data. Returns (state', toks [Ns, chunk])."""
        with self._lock:
            self._ensure_writable(entries, prefill=False)
            self._maybe_corrupt_page(entries, prefill=False)
            bt = self._bt.copy()
            state = self._slot_state
        active = np.zeros((self._num_slots,), bool)
        rem = np.zeros((self._num_slots,), np.int32)
        for i, r in entries:
            active[i] = True
            rem[i] = (r.max_new_tokens - r.generated.shape[0]
                      - r._pending_n)
        key = self._root_key()
        fargs = (astuple(self.cfg), self.mesh, self._chunk,
                 self._num_slots, self._page_size, self._max_pages,
                 self._num_pages, float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p))
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        if cjar is None:
            fn = self._resolve_program(
                "paged_decode", _compiled_paged_decode, fargs,
                self._quant_kwargs(),
                (params, *state, bt, active, rem, key))
        else:
            fn = self._resolve_program(
                "paged_decode_c", _compiled_paged_decode_c, fargs,
                {**self._quant_kwargs(), **self._ckey_kw()},
                (params, *state, bt, active, rem, *cjar.ops, key))
        self._decode_bill_label = "paged_decode"
        n_state = len(state)

        def call():
            if cjar is None:
                o = fn(params, *state, bt, active, rem, key)
            else:
                o = fn(params, *state, bt, active, rem, *cjar.ops,
                       key)
                cjar.out, o = o[-1], o[:-1]
            return tuple(o[:n_state]), self._out_sync(o[n_state])

        out = self._guarded(call, [r for _, r in entries],
                            self._m_step_seconds,
                            self._decode_work(entries, rem, self._chunk))
        if cjar is not None:
            self._cmask_commit(cjar)
        return out

    def _cache_prefilled(self, entries) -> None:
        """After a successful paged prefill: insert each admitted
        request's FULL prompt pages into the radix cache (the cache
        becomes a co-owner via refcount), so the next tenant sharing
        the prefix maps them instead of recomputing. Decode pages are
        never inserted — they are the slot's private, still-mutating
        tail."""
        if self._prefix_cache is None:
            return
        with self._lock:
            for i, r in entries:
                if self._slots[i] is not r or not self._slot_pages[i]:
                    continue
                self._prefix_cache.insert(
                    np.asarray(r.prompt, np.int32),
                    self._slot_pages[i])

    def _prefill_slots(self, admitted, params) -> None:
        """Admission prefill on the LIVE pool; appends each admitted
        request's first generated token. On persistent failure the
        admitted slots are evicted (running peers' device state is
        untouched — the failed call produced no new state) and the
        _BatchDecodeFailed propagates to slot isolation."""
        self._ensure_state()
        call = (self._call_prefill_paged if self._paged
                else self._call_prefill)
        try:
            state, first = call(params, self._slot_state, admitted)
        except _BatchDecodeFailed:
            with self._lock:
                for i, r in admitted:
                    if self._slots[i] is r:
                        self._free_slot(i)
            raise
        self._slot_state = state
        if self._paged:
            self._cache_prefilled(admitted)
        for i, r in admitted:
            with self._lock:
                if self._slots[i] is not r:   # preempted by a reload
                    continue
            self._commit_tokens(r, np.asarray([first[i]], np.int32),
                                "prefill_done", slot=i)
            if r.generated.shape[0] >= r.max_new_tokens:
                self._complete(r)
        self._reap()

    def _decode_chunk_slots(self, occupied, params,
                            prefill_tokens: Optional[int] = None) -> \
            None:
        """``prefill_tokens`` (chunked scheduler): prompt tokens the
        same tick's prefill phase advanced — stamped on each
        decode_chunk event so a trace shows exactly how much prefill
        work was co-scheduled with (and therefore delayed) the chunk."""
        data = ({} if prefill_tokens is None
                else {"prefill_chunk": int(prefill_tokens)})
        # overload-controller rung 1 (ISSUE-16): spec decode is the
        # cheapest thing to shed — drafts burn compute the SLO-bound
        # target pass must repeat, and plain decode is token-exact
        if (self._spec and not self._qos_spec_off
                and self._spec_tick()):
            self._decode_spec_slots(occupied, params, **data)
            return
        call = (self._call_chunk_paged if self._paged
                else self._call_chunk)
        state, toks = call(params, self._slot_state, occupied)
        self._slot_state = state
        for i, r in occupied:
            with self._lock:
                if self._slots[i] is not r:   # preempted by a reload:
                    continue                  # uncommitted tokens drop
            # commit exactly the call's chunk width (== self._chunk
            # unless qos_control resized it mid-call from another
            # thread — the device advanced by THIS width)
            need = min(int(toks.shape[1]),
                       r.max_new_tokens - r.generated.shape[0])
            self._commit_tokens(r, toks[i, :need].astype(np.int32),
                                "decode_chunk", slot=i, **data)
            if r.generated.shape[0] >= r.max_new_tokens:
                self._complete(r)

    # ------------------------------------------------------------------
    # speculative decoding (ISSUE-8)
    # ------------------------------------------------------------------
    def _rebuild_draft(self) -> None:
        """(Re)derive the drafter tree from the live serving params —
        at construction and after every hot reload (a drafter built
        from stale weights would tank acceptance AND, worse, silently
        look healthy)."""
        from deeplearning4j_tpu.quant.model import draft_tree
        (self._draft_params, self._draft_qmode,
         self._draft_layers) = draft_tree(self._params,
                                          self.config.draft, self.cfg,
                                          self.mesh,
                                          base_mode=self._qmode)

    def _spec_tick(self) -> bool:
        """Whether THIS tick decodes speculatively; advances the
        plain-decode cooldown the controller imposes when even K=1
        doesn't pay, probing with K=1 when it expires."""
        if self._spec_plain > 0:
            self._spec_plain -= 1
            if self._spec_plain == 0:
                self._spec_cur_k = 1
            return False
        return True

    def _decode_spec_slots(self, occupied, params, **data) -> None:
        """One speculative round over the occupied slots: commit each
        slot's accepted prefix + correction token (1..K+1 tokens), feed
        acceptance to the metrics and the adaptive-K controller, and
        stamp `decode_chunk{drafted, accepted}` (plus `draft_rejected`
        on all-rejected rounds) into the flight recorder."""
        call = (self._call_spec_paged if self._paged
                else self._call_spec)
        state, toks, nc, drafted, accepted, poison = call(
            params, self._slot_state, occupied)
        self._slot_state = state
        for i, r in occupied:
            with self._lock:
                if self._slots[i] is not r:   # preempted by a reload
                    continue
            d_i, a_i = int(drafted[i]), int(accepted[i])
            self._m_spec_drafted.inc(d_i)
            self._m_spec_accepted.inc(a_i)
            if d_i and a_i == 0:
                r.trace.add("draft_rejected",
                            step=self._step_counter - 1, drafted=d_i,
                            poisoned=bool(poison[i]))
            need = min(int(nc[i]),
                       r.max_new_tokens - r.generated.shape[0])
            self._commit_tokens(r, toks[i, :need].astype(np.int32),
                                "decode_chunk", slot=i, drafted=d_i,
                                accepted=a_i, **data)
            if r.generated.shape[0] >= r.max_new_tokens:
                self._complete(r)
        self._spec_update(occupied, drafted, accepted, poison)

    def _spec_poison(self, entries) -> np.ndarray:
        """ServingFaultInjector.draft_poison_at hook: mark the named
        request's slot so the compiled round derails its drafts on
        device (runtime data — no recompile)."""
        poison = np.zeros((self._num_slots,), bool)
        inj = self._injector
        if inj is None or not hasattr(inj, "check_draft_poison"):
            return poison
        rid = inj.check_draft_poison(self._step_counter)
        if rid is None:
            return poison
        for i, r in entries:
            if r.rid == rid:
                poison[i] = True
                inj.drafts_poisoned += 1
                log.warning("injected draft poison: request %d "
                            "(slot %d) at step %d", rid, i,
                            self._step_counter)
        return poison

    def _call_spec(self, params, state, entries):
        """One guarded speculative round over the CONTIGUOUS pool.
        Returns (state', toks [Ns, K+1], ncommit, drafted, accepted,
        poison)."""
        active = np.zeros((self._num_slots,), bool)
        rem = np.zeros((self._num_slots,), np.int32)
        for i, r in entries:
            active[i] = True
            # schedule-ahead budget mask (ISSUE-19): tokens already in
            # flight count as SPENT (zero-delta on the synchronous
            # path, where _pending_n is always 0). Conservative rem
            # can only move a round boundary — sampling is position-
            # keyed, so the token stream is unchanged.
            rem[i] = (r.max_new_tokens - r.generated.shape[0]
                      - r._pending_n)
        poison = self._spec_poison(entries)
        key = self._root_key()
        dparams = self._draft_params
        fargs = (astuple(self.cfg), self.mesh, self._spec_cur_k,
                 self._num_slots, float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p))
        fkw = dict(self._quant_kwargs(),
                   draft_quantized=self._draft_qmode,
                   draft_layers=self._draft_layers)
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        if cjar is None:
            fn = self._resolve_program(
                "spec_decode", _compiled_spec_decode, fargs, fkw,
                (params, dparams, *state, active, rem, poison, key))
        else:
            fn = self._resolve_program(
                "spec_decode_c", _compiled_spec_decode_c, fargs,
                {**fkw, **self._ckey_kw()},
                (params, dparams, *state, active, rem, poison,
                 *cjar.ops, key))
        self._decode_bill_label = f"spec_decode_k{self._spec_cur_k}"
        n_state = len(state)

        def call():
            if cjar is None:
                o = fn(params, dparams, *state, active, rem, poison,
                       key)
            else:
                o = fn(params, dparams, *state, active, rem, poison,
                       *cjar.ops, key)
                cjar.out, o = o[-1], o[:-1]
            return (tuple(o[:n_state]),
                    *self._out_sync_many(o[n_state:n_state + 4]))

        state, toks, nc, drafted, accepted = self._guarded(
            call, [r for _, r in entries], self._m_step_seconds,
            self._decode_work(entries, rem, self._spec_cur_k + 1))
        if cjar is not None:
            self._cmask_commit(cjar)
        return state, toks, nc, drafted, accepted, poison

    def _call_spec_paged(self, params, state, entries):
        """Paged speculative round: the copy-on-write guard privatizes
        the whole K+1-row write window before the call (speculative
        writes must never land on a shared page), then the block table
        rides as runtime data."""
        with self._lock:
            self._ensure_writable(entries, prefill=False)
            self._maybe_corrupt_page(entries, prefill=False)
            bt = self._bt.copy()
            state = self._slot_state
        active = np.zeros((self._num_slots,), bool)
        rem = np.zeros((self._num_slots,), np.int32)
        for i, r in entries:
            active[i] = True
            # schedule-ahead budget mask (ISSUE-19): see _call_spec
            rem[i] = (r.max_new_tokens - r.generated.shape[0]
                      - r._pending_n)
        poison = self._spec_poison(entries)
        key = self._root_key()
        dparams = self._draft_params
        fargs = (astuple(self.cfg), self.mesh, self._spec_cur_k,
                 self._num_slots, self._page_size, self._max_pages,
                 self._num_pages, float(self.config.temperature),
                 int(self.config.top_k), float(self.config.top_p))
        fkw = dict(self._quant_kwargs(),
                   draft_quantized=self._draft_qmode,
                   draft_layers=self._draft_layers)
        cjar = (self._cmask_begin() if self._constrain_active
                else None)
        if cjar is None:
            fn = self._resolve_program(
                "paged_spec_decode", _compiled_paged_spec_decode,
                fargs, fkw,
                (params, dparams, *state, bt, active, rem, poison,
                 key))
        else:
            fn = self._resolve_program(
                "paged_spec_decode_c", _compiled_paged_spec_decode_c,
                fargs, {**fkw, **self._ckey_kw()},
                (params, dparams, *state, bt, active, rem, poison,
                 *cjar.ops, key))
        self._decode_bill_label = \
            f"paged_spec_decode_k{self._spec_cur_k}"
        n_state = len(state)

        def call():
            if cjar is None:
                o = fn(params, dparams, *state, bt, active, rem,
                       poison, key)
            else:
                o = fn(params, dparams, *state, bt, active, rem,
                       poison, *cjar.ops, key)
                cjar.out, o = o[-1], o[:-1]
            return (tuple(o[:n_state]),
                    *self._out_sync_many(o[n_state:n_state + 4]))

        state, toks, nc, drafted, accepted = self._guarded(
            call, [r for _, r in entries], self._m_step_seconds,
            self._decode_work(entries, rem, self._spec_cur_k + 1))
        if cjar is not None:
            self._cmask_commit(cjar)
        return state, toks, nc, drafted, accepted, poison

    def _spec_update(self, occupied, drafted, accepted,
                     poison) -> None:
        """Adaptive-K controller: per-slot acceptance EMAs (surfaced
        in debugz) drive one global K over the closed set {spec_k,
        spec_k/2, .., 1} — halve when the pool's acceptance stops
        paying, double back when it recovers, and drop to PLAIN decode
        for a cooldown when even K=1 is a loss, so adversarial
        (low-acceptance) traffic converges to plain-decode throughput
        instead of underperforming it. A poisoned round bypasses the
        EMA and falls straight back to K=1."""
        if bool(np.asarray(poison).any()):
            self._spec_cur_k = 1
            return
        if not self.config.spec_adaptive:
            return
        sampled = [i for i, _ in occupied if drafted[i] > 0]
        if not sampled:
            return
        for i in sampled:
            ratio = float(accepted[i]) / float(drafted[i])
            # pessimistic-fast, optimistic-slow: a drop takes effect
            # IMMEDIATELY (every round at an oversized K is wasted
            # draft compute), recovery averages in over rounds
            self._accept_ema[i] = min(
                ratio, 0.5 * self._accept_ema[i] + 0.5 * ratio)
        pool = float(np.mean([self._accept_ema[i] for i in sampled]))
        self._accept_pool = pool
        k = self._spec_cur_k
        if pool < 0.2:
            # not paying at all: collapse straight to K=1, and from
            # K=1 to plain decode for a cooldown (then re-probe at 1).
            # The cooldown is long relative to a chunk: a probe tick
            # commits ~1 token where a plain chunk tick commits
            # `chunk`, so probe frequency IS the adversarial floor
            if k == 1:
                self._spec_plain = 24
            else:
                self._spec_cur_k = 1
        elif pool < 0.45 and k > 1:
            self._spec_cur_k = max(1, k // 2)
        elif pool > 0.8 and k < self._spec_k:
            self._spec_cur_k = min(self._spec_k, k * 2)

    def _reap(self, shed: bool = False) -> None:
        """Free slots whose request reached a terminal state; with
        ``shed``, first run the deadline check over occupied slots."""
        with span("engine.tick.reap", spans=self.spans):
            n_shed = n_freed = 0
            if shed:
                live = [r for _, r in self._occupied() if not r.done()]
                self._shed_expired(live)
                n_shed = sum(r.done() for r in live)
            with self._lock:
                for i, r in enumerate(self._slots):
                    if r is not None and r.done():
                        if r._hold_kv:
                            # held for KV export (ISSUE-11): the slot
                            # (and its pages) stays seated until
                            # release_held / export_slot_kv frees it
                            continue
                        self._free_slot(i)
                        self._leave_flight(r)
                        n_freed += 1
            annotate(finished=n_freed, shed=n_shed)

    def _leave_flight(self, r: RequestHandle) -> None:
        if r._in_flight:
            r._in_flight = False
            self._m_in_flight.dec()

    def _isolate_slots(self, requests: List[RequestHandle],
                       batch_err: _BatchDecodeFailed) -> None:
        """Continuous-batching isolation: the pool call exhausted its
        retries, so every implicated request is PREEMPTED (evicted
        from its slot) and re-run solo on a scratch pool, continuing
        from its committed prefix. Solo survivors complete; solo
        failures are quarantined — a poisoned slot's request cannot
        take down co-resident slots, and the pool keeps serving."""
        log.warning("slot pool of %d exhausted retries (%s); "
                    "isolating", len(requests), batch_err)
        # solo re-runs are always synchronous, even when isolation is
        # entered from inside a pipelined dispatch
        defer, self._pipe_defer = self._pipe_defer, False
        try:
            self._isolate_slots_inner(requests, batch_err)
        finally:
            self._pipe_defer = defer

    def _isolate_slots_inner(self, requests: List[RequestHandle],
                             batch_err: _BatchDecodeFailed) -> None:
        with self._lock:
            implicated = set(id(r) for r in requests)
            for i, r in enumerate(self._slots):
                if r is not None and id(r) in implicated:
                    self._free_slot(i)
        for r in requests:
            r._pending_n = 0       # dispatched-but-uncommitted tokens
            #                        died with the failed tick
            if r.status != RequestStatus.RUNNING:
                if r.done():
                    self._leave_flight(r)
                continue
            self._m_preempted.inc()
            r.trace.add("preempted", reason="isolation")
            try:
                self._run_isolated(r)
            except _BatchDecodeFailed as e:
                self._m_quarantined.inc()
                log.error("request %d quarantined after solo retries "
                          "(%s)", r.rid, e)
                r._finish(RequestStatus.QUARANTINED,
                          RequestQuarantined(
                              f"request {r.rid} failed persistently: "
                              f"{e}"))
            self._leave_flight(r)

    def _run_isolated(self, r: RequestHandle) -> None:
        """Solo re-run on a SCRATCH slot pool (the live pool's caches
        stay intact for later traffic; the scratch pool reuses the
        same compiled programs): re-prefill the committed prefix, then
        decode chunks to completion. The position-keyed sampling
        schedule makes the continuation identical to what the pooled
        run would have produced."""
        if not self._constrain_active:
            return self._run_isolated_inner(r)
        # scratch DFA vector to match the scratch KV pool: slot 0
        # carries the request's committed-prefix replay, the live
        # pool's states are untouched for when pooled traffic resumes
        saved = (self._cstate, self._cseed_pending)
        self._cstate = np.zeros((self._num_slots,), np.int32)
        self._cseed_pending = {0: self._c_state_for(r)}
        try:
            return self._run_isolated_inner(r)
        finally:
            self._cstate, self._cseed_pending = saved

    def _run_isolated_inner(self, r: RequestHandle) -> None:
        params = self._params
        state = init_slot_state(self.cfg, self.mesh, self._num_slots,
                                kv_mode=self._kv_mode)
        r.trace.add("admitted", slot=0, scratch=True, bucket=int(
            self._bucket_len(r.prompt.shape[0]
                             + r.generated.shape[0])))
        self.slo.admitted(r.trace)
        state, first = self._call_prefill(params, state, [(0, r)])
        self._commit_tokens(r, np.asarray([first[0]], np.int32),
                            "prefill_done", scratch=True)
        while True:
            self._shed_expired([r])
            if r.status != RequestStatus.RUNNING:
                return
            if r.generated.shape[0] >= r.max_new_tokens:
                self._complete(r)
                return
            state, toks = self._call_chunk(params, state, [(0, r)])
            need = min(int(toks.shape[1]),
                       r.max_new_tokens - r.generated.shape[0])
            self._commit_tokens(r, toks[0, :need].astype(np.int32),
                                "decode_chunk", scratch=True)

    def _evict_all_locked(self) -> int:
        """Weight-reload preemption (continuous mode; caller holds the
        lock): every in-flight slot's request is evicted and requeued
        at the FRONT of the queue with its committed tokens preserved
        — it re-prefills under the new weights and continues, since
        its KV cache encodes the OLD weights and mixing the two would
        be incoherent. Returns the number preempted."""
        if not self._continuous:
            return 0
        n = 0
        for i in range(self._num_slots - 1, -1, -1):
            r = self._slots[i]
            if r is None:
                continue
            if r.done():
                # a done-but-held slot (hold_kv): free it — the KV
                # encodes the old weights, so a later export would be
                # wrong anyway (the exporter falls back to re-prefill)
                self._free_slot(i)
                self._leave_flight(r)
                continue
            self._free_slot(i)
            r.status = RequestStatus.QUEUED
            r._pending_n = 0     # uncommitted pipeline tokens are
            #                      discarded and re-decoded (the
            #                      documented reload semantic)
            self._leave_flight(r)
            r.trace.add("preempted", reason="reload")
            self._queue.appendleft(r)
            n += 1
        return n

    # ------------------------------------------------------------------
    # the guarded decode step
    # ------------------------------------------------------------------
    def _prefill_work(self, program: str, rows) -> dict:
        """Args of an `engine.dispatch.prefill` span for ``rows``
        [(handle, start, n)], each advancing n tokens from position
        start: the live tokens, their causal (query, key) pairs, and
        the tokens among them whose K/V this engine had computed
        before (a request that lost its slot is prefilled again from
        its committed prefix). Counted as
        `perfbench/harness/stats.decode_and_prefill_work` counts them,
        so the program's count and a client's can be held against
        each other."""
        tokens = pairs = again = 0
        for r, start, n in rows:
            tokens += n
            pairs += n * start + n * (n + 1) // 2
            again += max(0, min(start + n, r._kv_seen) - start)
            r._kv_seen = max(r._kv_seen, start + n)
        self._m_reprefill_tokens.inc(again)
        return {"program": program, "rows": len(rows),
                "prefill_tokens": tokens, "prefill_pairs": pairs,
                "reprefill_tokens": again}

    def _decode_work(self, entries, rem, steps: int) -> dict:
        """Args of an `engine.dispatch.decode` span: the tokens this
        call is scheduled to produce (min(steps, remaining budget) a
        row; a speculative round's are its worst case) and the live
        cache rows they attend, the row just written included."""
        tokens = rows = 0
        for i, r in entries:
            n = max(0, min(steps, int(rem[i])))
            done = r.max_new_tokens - int(rem[i])
            tokens += n
            rows += (n * (r.prompt.shape[0] + done - 1)
                     + n * (n + 1) // 2)
        return {"program": self._decode_bill_label,
                "rows": len(entries), "steps": steps,
                "decode_tokens": tokens, "decode_rows": rows}

    def _guarded(self, call, reqs: List[RequestHandle], hist,
                 work: dict, prefill: bool = False,
                 chunked: bool = False):
        """One compiled-call guard shared by every decode path:
        fault-injection hook (the injector sees the request ids of ALL
        co-resident work), latency histogram, retry with exponential
        backoff (every co-resident trace gets the `retry` event),
        breaker accounting. The step counter indexes COMPLETED calls —
        prefills and chunks share it — so a failed attempt retries the
        same index (ServingFaultInjector contract). Raises
        _BatchDecodeFailed after max_retries.

        The whole guard, retries included, is one
        `engine.dispatch.prefill` / `engine.dispatch.decode` span
        whose args are ``work`` (`_prefill_work` / `_decode_work`). A
        pipelined dispatch returns before the device has finished; a
        synchronous one blocks inside the span."""
        rids = [r.rid for r in reqs]
        attempt = 0
        with span("engine.dispatch.prefill" if prefill
                  else "engine.dispatch.decode", spans=self.spans,
                  **work):
            while True:
                try:
                    if self._injector is not None:
                        hook = self._injector.on_decode_step
                        if (prefill and chunked
                                and hasattr(self._injector,
                                            "on_prefill_chunk")):
                            hook = self._injector.on_prefill_chunk
                        elif prefill and hasattr(self._injector,
                                                 "on_prefill"):
                            hook = self._injector.on_prefill
                        hook(self._step_counter, rids)
                    t_step = _perf()
                    self._busy_mark()
                    out = call()
                    hist.observe(_perf() - t_step)
                    self._record_success()
                    self._step_counter += 1
                    return out
                except RuntimeError as e:       # XlaRuntimeError, injected
                    self._record_failure(e)
                    attempt += 1
                    if attempt > self.config.max_retries:
                        raise _BatchDecodeFailed(str(e)) from e
                    self._m_retries.inc()
                    for r in reqs:
                        r.trace.add("retry", step=self._step_counter,
                                    attempt=attempt, prefill=prefill)
                    delay = min(self.config.backoff_base_s
                                * (2 ** (attempt - 1)),
                                self.config.backoff_max_s)
                    log.warning(
                        "decode step %d failed (%s); retry %d/%d in %.3fs",
                        self._step_counter, e, attempt,
                        self.config.max_retries, delay)
                    if delay > 0:
                        time.sleep(delay)

    def _invoke(self, params, prompts: np.ndarray, n: int,
                reqs: List[RequestHandle]) -> np.ndarray:
        """One compiled batch-mode decode call (batch padded to a
        'data' multiple), retried via _guarded. Returns [B_real, n]
        new tokens. Raises _BatchDecodeFailed after max_retries."""
        import jax
        import jax.numpy as jnp

        b = prompts.shape[0]
        b_pad = -(-b // self._dp) * self._dp
        if b_pad != b:
            prompts = np.concatenate(
                [prompts, np.repeat(prompts[:1], b_pad - b, axis=0)])
        # key depends only on the decoded-position offset, so a retry —
        # and a solo continuation — reproduces the same tokens
        key = jax.random.fold_in(
            jax.random.PRNGKey(self.config.seed), prompts.shape[1])
        qkw = ({"quantized": self._qmode} if self._qmode else {})
        # batch-mode generate keeps the lazy jit path (prompt length
        # shapes vary per call — example_args=None)
        fn = self._resolve_program(
            "generate", _compiled_generate,
            (astuple(self.cfg), self.mesh, int(n),
             float(self.config.temperature), int(self.config.top_k),
             float(self.config.top_p)), qkw, None)

        # batch-mode shapes vary per call, so "generate" carries no
        # analytic rate: decode tokens still COUNT per tenant, the
        # FLOP bill is continuous-mode-only (documented)
        self._decode_bill_label = "generate"

        def call():
            return self._block_on(fn(params, jnp.asarray(prompts), key))

        out = self._guarded(call, reqs, self._m_step_seconds,
                            {"program": "generate", "rows": b,
                             "steps": int(n)})
        return out[:b, prompts.shape[1]:]

    def _isolate(self, active: List[RequestHandle], params,
                 batch_err: _BatchDecodeFailed) -> None:
        """Batch-level retries exhausted: re-run each request solo so a
        single poisoned request cannot starve its co-batched peers.
        Solo survivors complete; solo failures are quarantined."""
        log.warning("batch of %d exhausted retries (%s); isolating",
                    len(active), batch_err)
        for r in active:
            if r.status != RequestStatus.RUNNING:
                continue
            try:
                self._decode_solo(r, params)
            except _BatchDecodeFailed as e:
                self._m_quarantined.inc()
                log.error("request %d quarantined after solo retries "
                          "(%s)", r.rid, e)
                r._finish(RequestStatus.QUARANTINED, RequestQuarantined(
                    f"request {r.rid} failed persistently: {e}"))

    def _decode_solo(self, r: RequestHandle, params) -> None:
        while r.status == RequestStatus.RUNNING:
            self._shed_expired([r])
            if r.status != RequestStatus.RUNNING:
                return
            done = r.generated.shape[0]
            if done >= r.max_new_tokens:
                self._complete(r)
                return
            n = r.max_new_tokens - done
            if self.config.decode_chunk > 0:
                n = min(self.config.decode_chunk, n)
            prompts = np.concatenate([r.prompt, r.generated])[None]
            toks = self._invoke(params, prompts.astype(np.int32), n,
                                [r])
            self._commit_tokens(r, toks[0], "decode_chunk", solo=True)

    # ------------------------------------------------------------------
    # circuit breaker / degradation
    # ------------------------------------------------------------------
    def _record_failure(self, err: BaseException) -> None:
        self._m_step_failures.inc()
        with self._lock:
            self._consec_failures += 1
            if (self._breaker != "open" and self._consec_failures
                    >= self.config.breaker_failure_threshold):
                self._breaker = "open"
                self._opened_at = self._clock()
                log.error("circuit breaker OPEN after %d consecutive "
                          "step failures (last: %s)",
                          self._consec_failures, err)

    def _record_success(self) -> None:
        with self._lock:
            self._consec_failures = 0
            # any completed decode step proves the path healthy — close
            # from half-open (the probe) AND from open (e.g. the failure
            # streak came from one poisoned request whose co-batched
            # peers then completed solo; automatic recovery, no cooldown
            # wait needed)
            if self._breaker != "closed":
                log.info("circuit breaker closed (was %s: decode step "
                         "succeeded)", self._breaker)
                self._breaker = "closed"

    def _tick_breaker(self, now: float) -> None:
        if (self._breaker == "open"
                and now - self._opened_at
                >= self.config.breaker_cooldown_s):
            self._breaker = "half-open"
            log.info("circuit breaker half-open (cooldown elapsed)")

    def _degraded_locked(self) -> bool:
        return (len(self._queue) >= self.config.degrade_queue_depth
                or self._breaker != "closed")

    # ------------------------------------------------------------------
    # introspection: /debugz, /slo, /timeline.json bodies (ISSUE-6)
    # ------------------------------------------------------------------
    def debugz(self, recent: int = 100) -> dict:
        """The operator's "why is it slow RIGHT NOW" snapshot: the
        live slot table (who is seated where, for how long), queue
        entries with their ages, breaker/degradation state, and the
        recorder's recent lifecycle events — wire into
        `MetricsServer(debug=engine.debugz)` for `GET /debugz`."""
        now = self.recorder.now()

        def age(r):
            t = r.trace.first_ts("submit")
            return round(now - t, 6) if t is not None else None

        with self._lock:
            slots = [{"slot": i, "rid": r.rid, "status": r.status,
                      "tenant": r.tenant, "priority": r.priority,
                      "generated": int(sum(a.shape[0]
                                           for a in r._generated)),
                      "max_new_tokens": r.max_new_tokens,
                      "age_s": age(r),
                      **({"phase": ("prefilling"
                                    if self._is_prefilling(r)
                                    else "decoding"),
                          "prefill_pos": int(r._prefill_pos),
                          "prefill_target": int(r._prefill_target)}
                         if self._prefill_chunk is not None else {})}
                     for i, r in enumerate(self._slots)
                     if r is not None]
            queue = [{"rid": r.rid, "queue_age_s": age(r),
                      "tenant": r.tenant, "priority": r.priority}
                     for r in self._queue]
            # per-tenant queue depths (ISSUE-16 satellite): a tenant
            # storm is diagnosable from this endpoint alone
            queue_by_tenant: Dict[str, int] = {}
            for r in self._queue:
                t = r.tenant or "default"
                queue_by_tenant[t] = queue_by_tenant.get(t, 0) + 1
            breaker = self._breaker
            degraded = self._degraded_locked()
            qos = None
            if (self._qos_weights is not None
                    or self._preempt_budget > 0
                    or self._qos_spec_off
                    or self._chunk != self._base_chunk):
                qos = {"tenant_weights": (dict(self._qos_weights)
                                          if self._qos_weights
                                          is not None else None),
                       "deficits": {t: round(d, 2) for t, d in
                                    self._qos_deficit.items()},
                       "preemption_budget": self._preempt_budget,
                       "spec_off": self._qos_spec_off,
                       "decode_chunk": self._chunk,
                       "base_decode_chunk": self._base_chunk}
        out = {"mode": self.config.mode,
               "num_slots": self._num_slots,
               "slots_occupied": len(slots),
               "slots": slots,
               "queue_depth": len(queue),
               "queue": queue,
               "queue_by_tenant": queue_by_tenant,
               "breaker": breaker,
               "degraded": degraded,
               "weights_step": self._weights_step,
               "recorder_events": len(self.recorder),
               "recent_events": [e.as_dict() for e in
                                 self.recorder.recent(recent)]}
        if self._paged:
            with self._lock:
                out["paged"] = {
                    "page_size": self._page_size,
                    "num_pages": self._num_pages,
                    "pages_free": self._allocator.pages_free,
                    "pages_used": self._allocator.pages_used,
                    "block_tables": {
                        i: list(map(int, pgs))
                        for i, pgs in enumerate(self._slot_pages)
                        if pgs},
                    "prefix_cache": (
                        {**self._prefix_cache.stats(),
                         "hits": int(self._m_prefix_hits.value),
                         "misses": int(self._m_prefix_misses.value),
                         "shared_tokens": int(
                             self._m_prefix_shared_tokens.value)}
                        if self._prefix_cache is not None else None)}
        if self._continuous or self._pipe_fallback is not None:
            # tick-pipeline + compile-cache state (ISSUE-12): the
            # raw-speed section of the "why is it slow" snapshot.
            # Also emitted for an engine that FELL BACK out of the
            # pipeline (batch mode) so the fallback reason is
            # inspectable where the pipeline state would have been
            # (ISSUE-19 satellite).
            out["tick_pipeline"] = {
                "pipeline": self._pipe,
                "fallback_reason": self._pipe_fallback,
                "in_flight_ticks": len(self._pending),
                "last_sync_s": round(self._last_sync_s, 6),
                "syncs_last_tick": self._last_tick_syncs,
                "syncs_total": self._syncs_total,
                "device_idle_fraction": round(self._last_idle, 4),
                "last_flush": self._last_flush}
            out["compile_cache"] = {
                "program_cache_size": _PROGRAM_CACHE_SIZE[0],
                "aot": (self._aot.stats() if self._aot is not None
                        else None),
                "last_warmup": self._last_warmup}
        if self.profiler.enabled:
            # profiling & cost attribution (ISSUE-15): live MFU,
            # per-program rooflines, and the per-tenant bill — the
            # "how fast COULD it have gone, and for whom" section
            out["profiling"] = self.profiler.report()
        if self._prefill_chunk is not None:
            out["chunked_prefill"] = {
                "prefill_chunk": self._prefill_chunk,
                "tick_token_budget": self._tick_budget,
                "last_tick_tokens": self._last_tick_spent,
                "budget_utilization": round(
                    self._last_tick_spent
                    / max(1, self._tick_budget), 3),
                "prefill_chunks_total": int(
                    self._m_prefill_chunks.value)}
        if self._spec:
            out["spec"] = {
                "spec_k": self._spec_k,
                "k": (0 if self._spec_plain > 0
                      else self._spec_cur_k),
                "plain_cooldown": self._spec_plain,
                "draft": self.config.draft,
                "draft_layers": self._draft_layers,
                "accept_ema": {i: round(self._accept_ema[i], 3)
                               for i, r in enumerate(self._slots)
                               if r is not None},
                "drafted": int(self._m_spec_drafted.value),
                "accepted": int(self._m_spec_accepted.value)}
        if qos is not None:
            out["qos"] = qos
        return out

    def qos_control(self, spec_off: Optional[bool] = None,
                    decode_chunk: Optional[int] = None) -> dict:
        """Overload-controller actuation surface (ISSUE-16): the fleet
        Router's SLO-aware controller degrades a replica in cost order
        through this ONE method. ``spec_off=True`` suspends
        speculative rounds (plain decode is token-exact, so nothing
        but throughput changes); ``decode_chunk=N`` shrinks the decode
        scheduling quantum (clamped to [1, configured chunk] — a
        smaller chunk frees slots and re-checks deadlines more often
        under pressure, at one extra compiled geometry); ``0``
        restores the configured chunk. Both are reversible and leave
        committed tokens untouched. Returns the live knob state."""
        with self._lock:
            if spec_off is not None:
                self._qos_spec_off = bool(spec_off)
            if decode_chunk is not None:
                c = int(decode_chunk)
                self._chunk = (self._base_chunk if c == 0
                               else min(max(1, c), self._base_chunk))
        return {"spec_off": self._qos_spec_off,
                "decode_chunk": self._chunk,
                "base_decode_chunk": self._base_chunk}

    def slo_report(self) -> dict:
        """Windowed SLO report (observability/slo.py): TTFT / TPOT /
        e2e / queue-age percentiles + goodput — `GET /slo`'s body."""
        return self.slo.report()

    def profile_report(self) -> dict:
        """Continuous-profiling report (ISSUE-15,
        observability/profiling.py): chip peaks, live MFU, achieved
        FLOP/s and bytes/s, the per-program cost/roofline table, and
        the per-tenant bill — the `/slo`-style accounting surface."""
        return self.profiler.report()

    def profilez(self, seconds) -> tuple:
        """`GET /profilez?seconds=N` backend (ISSUE-15): start one
        bounded single-flight jax.profiler capture into
        ``EngineConfig.profile_dir``; (503, ...) when no directory is
        configured, the runtime lacks jax.profiler, or a capture is
        already running. Returns ``(http_status, body_dict)`` — wire
        via ``MetricsServer(profilez=engine.profilez)``."""
        return self._capture.capture(seconds)

    def timeline(self, n: Optional[int] = None) -> dict:
        """Chrome/Perfetto trace_event JSON over the recorder's recent
        events: one lane per slot plus the queue lane — load
        `GET /timeline.json` in https://ui.perfetto.dev and the slot
        schedule (gaps, preemption storms, lane-pinning requests) is
        visible instead of inferred."""
        from deeplearning4j_tpu.observability.timeline import \
            timeline_json
        return timeline_json(self.recorder, num_slots=self._num_slots,
                             n=n)

    # ------------------------------------------------------------------
    # health / readiness / weights
    # ------------------------------------------------------------------
    def health(self) -> dict:
        with self._lock:
            self._tick_breaker(self._clock())
            occupied = sum(s is not None for s in self._slots)
            return {"ready": self.ready(),
                    "breaker": self._breaker,
                    "degraded": self._degraded_locked(),
                    "draining": self._draining,
                    "queue_depth": len(self._queue),
                    "num_slots": self._num_slots,
                    "slots_occupied": occupied,
                    # load piggyback (ISSUE-11 satellite): the
                    # serving_slot_occupancy / tick-budget-utilization
                    # gauge VALUES ride on every health probe — in-
                    # process and HTTP alike — so a router (and its
                    # autoscaler) sees per-replica load without
                    # scraping /metrics separately
                    "slot_occupancy": occupied / max(1,
                                                     self._num_slots),
                    "tick_budget_utilization": (
                        self._last_tick_spent
                        / float(max(1, self._tick_budget))
                        if self._prefill_chunk is not None else None),
                    "weights_step": self._weights_step,
                    "quantize": self._qmode,
                    "kv_quantize": self._kv_mode,
                    "paged": self._paged,
                    "spec_decode": self._spec,
                    "prefill_chunk": self._prefill_chunk,
                    "pipeline": self._pipe,
                    # cold-start piggyback (ISSUE-13 satellite): the
                    # warmup report + compiles-by-source ride every
                    # health probe, so a router's debugz replica rows
                    # show a cold autoscaled replica (compiles
                    # climbing, no warmup) without scraping /metrics
                    "last_warmup": self._last_warmup,
                    "compiles_by_source": self._compiles_by_source(),
                    # prefix-cache advertisement (ISSUE-14): the
                    # chain digest rides EVERY health probe —
                    # in-process and HTTP alike — so a fleet router
                    # can weight dispatch toward replicas whose cache
                    # already holds a request's prefix. Cached per
                    # cache generation: an idle replica's probes cost
                    # a dict lookup, not a trie walk.
                    **({"prefix_digest":
                        self._prefix_cache.chain_digest()}
                       if self._paged and self._prefix_cache is not None
                       else {}),
                    **dict(self.stats)}

    def _compiles_by_source(self) -> dict:
        """serving_compiles_total summed over programs, keyed by
        source (jit vs aot_cache) — the probe-piggyback form."""
        fam = self.registry.get("serving_compiles")
        out: dict = {}
        if fam is None:
            return out
        for values, child in fam.collect():
            src = values[1] if len(values) > 1 else "jit"
            out[src] = out.get(src, 0) + int(child.value)
        return out

    def ready(self) -> bool:
        with self._lock:
            self._tick_breaker(self._clock())
            # draining flips readiness the MOMENT drain begins (ISSUE-9
            # satellite): a rolling-reload load balancer must stop
            # routing here before the last resident finishes, not after
            return (self._accepting and not self._draining
                    and self._breaker != "open")

    def reload_weights(self, source, step: Optional[int] = None) -> int:
        """Hot-swap serving weights from a CheckpointManager (or a
        checkpoint directory path) WITHOUT draining: in-flight batches
        finish on their snapshot, subsequent batches use the new tree.
        The live sharded params are the restore template, so arrays
        come back placed on this engine's mesh. A corrupt/partial
        newest step falls back to the previous good one. Returns the
        step loaded."""
        if isinstance(source, CheckpointManager):
            mgr = source
        else:
            # sniff the on-disk format: a step_<N>/arrays.npz layout was
            # written by the npz fallback and is unreadable through an
            # orbax-backed manager (whose constructor scans step dirs)
            from pathlib import Path
            is_npz = any(Path(str(source)).glob("step_*/arrays.npz"))
            mgr = CheckpointManager(str(source),
                                    use_orbax=False if is_npz else None)
        steps = ([int(step)] if step is not None
                 else list(reversed(mgr.all_steps())))
        if not steps:
            raise FileNotFoundError(
                f"no checkpoint steps under {mgr.directory}")
        last_err: Optional[BaseException] = None
        for s in steps:
            try:
                # checksum-verify the manifest BEFORE deserializing onto
                # the mesh: a torn/corrupted step (zip-valid but wrong
                # bytes) must never swap in — serving stays on the
                # current weights and falls back to an older step
                if hasattr(mgr, "verify_step") and not mgr.verify_step(s):
                    raise RuntimeError(
                        f"step {s} failed checksum verification")
                # quantized engines restore against the FLOAT template
                # (checkpoints hold training-precision weights) and
                # requantize below — quantize-on-hot-reload
                template = (self._float_template if self._qmode
                            else self._params)
                tree = mgr.restore_tree(template, step=s)
            except Exception as e:           # corrupt / partial step dir
                last_err = e
                log.warning("weight reload: step %s unreadable (%s); "
                            "falling back", s, e)
                continue
            if tree is None:
                continue
            if self._qmode:
                from deeplearning4j_tpu.quant.model import \
                    quantize_params
                tree = shard_serving_params(
                    quantize_params(tree, mode=self._qmode), self.cfg,
                    self.mesh)
            with self._lock:
                self._params = tree
                self._weights_step = int(s)
                # continuous mode: in-flight slots' KV caches encode
                # the OLD weights — preempt them (requeue at the queue
                # front, committed tokens preserved) so they re-prefill
                # under the new tree; new admissions see it immediately
                preempted = self._evict_all_locked()
                # paged: the prefix cache's K/V pages ALSO encode the
                # old weights — a post-reload hit would graft stale KV
                # under new weights. Flush; every cached page returns
                # to the free list (all slots were just evicted).
                if self._prefix_cache is not None:
                    flushed = self._prefix_cache.flush()
                    if flushed:
                        self._m_prefix_evictions.inc(flushed)
                        log.info("weight reload flushed %d prefix-"
                                 "cache entries", flushed)
            if self._spec:
                # the drafter encodes the OLD weights: re-derive it
                # from the freshly loaded tree (re-quantize / re-share)
                self._rebuild_draft()
            if preempted:
                self._m_preempted.inc(preempted)
                log.info("weight reload preempted %d in-flight "
                         "slot(s); requeued for re-prefill", preempted)
            self._m_reloads.inc()
            log.info("weights hot-reloaded from step %d", int(s))
            return int(s)
        raise RuntimeError(
            f"no readable checkpoint step under {mgr.directory}"
        ) from last_err
